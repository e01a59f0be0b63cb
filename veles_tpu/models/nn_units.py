"""ForwardBase — common machinery of parameterized forward layers.

Reconstruction of znicz ``nn_units.Forward`` (source absent; surface per
manualrst_veles_algorithms.rst): parameters (weights/bias) with "smart
automatic initial filling", per-layer hyper-parameter overrides (extras
item 13: learning rate / weights decay / momentum per layer), and the
pure ``apply`` used both by the in-graph forward step and by the trainer's
fused autodiff program.
"""

import numpy

from veles_tpu import prng as prng_mod
from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.memory import Array
from veles_tpu.units import MissingDemand

#: per-layer hyper-parameters a trainer consults; None = inherit the
#: trainer's global value (surface: znicz kwargs of the same names)
HYPERPARAMS = ("learning_rate", "learning_rate_bias", "weights_decay",
               "weights_decay_bias", "l1_vs_l2", "gradient_moment",
               "gradient_moment_bias")


class ForwardBase(AcceleratedUnit):
    """A layer with trainable params (ref role: znicz nn_units.Forward).

    Subclasses define ``PARAMS`` (names of trainable Arrays), implement
    :meth:`apply(params, x)` as a pure function and
    :meth:`fill_params()` for initialization.
    """

    hide_from_registry = True
    VIEW_GROUP = "WORKER"
    PARAMS = ("weights", "bias")

    def __init__(self, workflow, weights_filling="uniform",
                 weights_stddev=None, bias_filling="uniform",
                 bias_stddev=None, include_bias=True, prng_key="default",
                 **kwargs):
        self.input = None
        self.output = Array()
        super(ForwardBase, self).__init__(workflow, **kwargs)
        self.weights_filling = weights_filling
        self.weights_stddev = weights_stddev
        self.bias_filling = bias_filling
        self.bias_stddev = bias_stddev
        self.include_bias = include_bias
        #: recompute this unit's forward during backward instead of
        #: saving its internal activations (``jax.checkpoint``) — a
        #: transformer block on long sequences would otherwise pin its
        #: [seq, seq] attention tensors across the whole backward pass;
        #: rematerializing trades those HBM bytes for extra MXU FLOPs
        self.remat = bool(kwargs.get("remat", False))
        self.prng = prng_mod.get(prng_key)
        self.weights = Array()
        self.bias = Array()
        for h in HYPERPARAMS:
            setattr(self, h, kwargs.get(h))
        self.demand("input")

    # -- contract -------------------------------------------------------------

    @property
    def reads(self):
        return ("input",) + tuple(self.PARAMS)

    WRITES = ("output",)

    def apply(self, params, x):
        """Pure forward: params is {name: jax array}."""
        raise NotImplementedError()

    def output_shape_for(self, input_shape):
        raise NotImplementedError()

    def fill_params(self):
        """Allocate + smart-fill params given self.input's shape."""
        raise NotImplementedError()

    # -- helpers ---------------------------------------------------------------

    def _fill(self, arr, filling, stddev, fan_in, fan_out):
        """Smart automatic weights/bias filling (extras item 12): scaled
        uniform (Glorot) or gaussian; explicit stddev overrides."""
        if stddev is None:
            stddev = numpy.sqrt(6.0 / (fan_in + fan_out))
        if filling == "uniform":
            self.prng.fill(arr, -stddev, stddev)
        elif filling in ("gaussian", "normal"):
            self.prng.fill_normal(arr, 0.0, stddev)
        elif filling == "constant":
            arr[...] = stddev
        else:
            raise ValueError("unknown filling %r" % filling)

    def param_arrays(self):
        return {name: getattr(self, name) for name in self.PARAMS
                if bool(getattr(self, name))}

    #: parameters whose EVERY use in this unit's traced code is
    #: ``params[name].astype(dtypes.compute_dtype())``
    MATMUL_PARAMS = ()

    def compute_dtype_params(self):
        """The parameters a FROZEN copy may hold in the compute dtype
        already (serving/weights.py): of ``MATMUL_PARAMS``, those
        stored as floats of another dtype.  ``.astype`` of an array
        that has the dtype is no operation, so the traced code is the
        same and its operands are bit-identical: the rounding is done
        once instead of on every call.  An int8 checkpoint weight
        keeps its int8, and under float32 compute there is nothing to
        name."""
        from veles_tpu import dtypes
        cd = numpy.dtype(dtypes.compute_dtype())
        arrays = self.param_arrays()
        return tuple(
            name for name in self.MATMUL_PARAMS
            if name in arrays and arrays[name].dtype != cd
            and numpy.issubdtype(arrays[name].dtype, numpy.floating))

    def _export_activation(self):
        """Activation name for export_config — callables can't ride a
        JSON manifest."""
        if callable(self.activation):
            raise ValueError(
                "%s: callable activations cannot be exported — register "
                "a named activation instead" % self)
        return self.activation

    def hyperparams(self):
        """Per-layer overrides, Nones meaning 'inherit'."""
        return {h: getattr(self, h) for h in HYPERPARAMS}

    # -- lifecycle -------------------------------------------------------------

    def initialize(self, device=None, **kwargs):
        if not isinstance(self.input, Array) or not bool(self.input):
            raise MissingDemand(self, {"input"})
        # fill only when NO param is populated (i.e. not restored from a
        # snapshot) — checked across PARAMS, not just "weights", so units
        # with custom param sets (e.g. attention's wq/wk/wv/wo) keep
        # their restored values too
        if not any(bool(getattr(self, p)) for p in self.PARAMS):
            self.fill_params()
        out_shape = self.output_shape_for(self.input.shape)
        self.output.reset(numpy.zeros(out_shape, numpy.float32))
        super(ForwardBase, self).initialize(device=device, **kwargs)

    def step(self, input, **params):
        return {"output": self.apply(params, input)}

    def export_config(self):
        cfg = {"weights_filling": self.weights_filling,
               "include_bias": self.include_bias}
        return cfg
