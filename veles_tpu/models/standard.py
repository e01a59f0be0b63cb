"""Standard workflow builders (reconstruction of the znicz
StandardWorkflow surface, manualrst_veles_algorithms.rst: models are
described by a ``layers`` list of type+kwargs dicts).

Two entry points:

- :func:`build_mlp_classifier` — imperative wiring for simple MLPs
  (bench / driver entry points);
- :class:`StandardWorkflow` — the config-driven graph the samples use:
  ``layers=[{"type": "conv_relu", "n_kernels": 32, ...}, ...]`` builds
  the full train graph (repeater → loader → trainer → decision →
  snapshotter, loop + end gates) in one unit.

Layer spec keys: ``type`` (see :data:`LAYER_TYPES`); ``"->"`` merges
extra forward kwargs; ``"<-"`` merges per-layer trainer hyper-parameter
overrides (extras item 13) — both znicz conventions.
"""

from veles_tpu.accelerated_units import AcceleratedWorkflow
from veles_tpu.models.attention import MultiHeadAttention
from veles_tpu.models.embedding import Embedding
from veles_tpu.models.lfm2 import Lfm2Block, NormedTokenLogits
from veles_tpu.models.moe import MoE
from veles_tpu.models.ouro import OuroStack, PlainTokenLogits
from veles_tpu.models.solar import SolarBlock
from veles_tpu.models.transformer import MeanPoolSeq, TransformerBlock, TokenProjection
from veles_tpu.models.all2all import (
    All2All, All2AllRELU, All2AllSigmoid, All2AllSoftmax,
    All2AllStrictRELU, All2AllTanh)
from veles_tpu.models.conv import (
    Conv, ConvRELU, ConvStrictRELU, ConvTanh, Deconv)
from veles_tpu.models.dropout import DropoutForward
from veles_tpu.models.evaluator import EvaluatorMSE, EvaluatorSoftmax
from veles_tpu.models.gd import GradientDescent
from veles_tpu.models.lrn import LRNormalizerForward
from veles_tpu.models.pooling import AvgPooling, Depooling, MaxPooling
from veles_tpu.models.recurrent import LSTM, LastTimestep, SimpleRNN

#: znicz layer-type names → forward unit classes
LAYER_TYPES = {
    "all2all": All2All,
    "all2all_tanh": All2AllTanh,
    "all2all_relu": All2AllRELU,
    "all2all_str": All2AllStrictRELU,
    "all2all_sigmoid": All2AllSigmoid,
    "softmax": All2AllSoftmax,
    "conv": Conv,
    "conv_tanh": ConvTanh,
    "conv_relu": ConvRELU,
    "conv_str": ConvStrictRELU,
    "deconv": Deconv,
    "max_pooling": MaxPooling,
    "avg_pooling": AvgPooling,
    "depooling": Depooling,
    "dropout": DropoutForward,
    "norm": LRNormalizerForward,
    "attention": MultiHeadAttention,
    "moe": MoE,
    "embedding": Embedding,
    "transformer_block": TransformerBlock,
    "mean_pool_seq": MeanPoolSeq,
    "rnn": SimpleRNN,
    "lstm": LSTM,
    "last_timestep": LastTimestep,
    "token_logits": TokenProjection,
    "lfm2_block": Lfm2Block,
    "solar_block": SolarBlock,
    "rms_token_logits": NormedTokenLogits,
    "ouro_stack": OuroStack,
    "plain_token_logits": PlainTokenLogits,
}


def make_forwards(workflow, input_array, layers):
    """Instantiate the forward chain from a znicz-style ``layers`` spec;
    returns the unit list (uninitialized — the workflow's dependency-
    ordered initialize fills parameters)."""
    units = []
    prev = input_array
    for i, spec in enumerate(dict(s) for s in layers):
        ltype = spec.pop("type")
        kwargs = dict(spec.pop("->", {}))
        kwargs.update(spec.pop("<-", {}))
        kwargs.update(spec)
        cls = LAYER_TYPES[ltype]
        u = cls(workflow, name="%s%d" % (ltype, i), **kwargs)
        u.input = prev
        prev = u.output
        units.append(u)
    return units


def build_mlp_classifier(device, loader, hidden=(100,), classes=10,
                         mesh=None, workflow=None, name="mlp",
                         hidden_cls=All2AllTanh, **gd_kwargs):
    """loader (already constructed, not yet initialized) →
    tanh hidden layers → softmax head → evaluator → fused trainer.

    Returns (workflow, layers, evaluator, trainer)."""
    wf = workflow or AcceleratedWorkflow(None, name=name)
    loader.initialize(device=device)
    layers = []
    prev_out = loader.minibatch_data
    for li, width in enumerate(hidden):
        u = hidden_cls(wf, output_sample_shape=(width,),
                       name="fc%d" % li)
        u.input = prev_out
        u.initialize(device=device)
        layers.append(u)
        prev_out = u.output
    head = All2AllSoftmax(wf, output_sample_shape=(classes,), name="head")
    head.input = prev_out
    head.initialize(device=device)
    layers.append(head)
    ev = EvaluatorSoftmax(wf, name="evaluator")
    ev.output = head.output
    ev.labels = loader.minibatch_labels
    ev.loader = loader
    ev.initialize(device=device)
    gd_kwargs.setdefault("solver", "sgd")
    gd_kwargs.setdefault("learning_rate", 0.05)
    gd = GradientDescent(wf, forwards=layers, evaluator=ev,
                         loader=loader, mesh=mesh, name="gd", **gd_kwargs)
    gd.initialize(device=device)
    return wf, layers, ev, gd


class StandardWorkflow(AcceleratedWorkflow):
    """The config-driven training graph (znicz StandardWorkflow role).

    Parameters mirror the znicz config surface:

    - ``loader_factory(workflow, **loader_config)`` builds the loader
      (or pass a ready ``loader`` instance);
    - ``layers`` — the forward-chain spec (see :func:`make_forwards`);
    - ``loss`` — "softmax" | "mse" | "next_token" selects the
      evaluator (next_token: per-token LM cross-entropy against the
      input shifted by one — EvaluatorNextToken);
    - ``decision_config`` / ``snapshotter_config`` / trainer kwargs.
    """

    def __init__(self, workflow, loader_factory=None, loader=None,
                 loader_config=None, layers=(), loss="softmax",
                 decision_config=None, snapshotter_config=None,
                 mesh=None, name="StandardWorkflow", plotters=True,
                 **trainer_kwargs):
        from veles_tpu.models.decision import DecisionGD
        from veles_tpu.plumbing import Repeater
        from veles_tpu.snapshotter import Snapshotter

        if mesh is None:
            # every config-driven sample honours the generic mesh knob:
            # -c "root.common.mesh = {'dp': -1}" shards ANY standard
            # workflow without sample-specific plumbing
            from veles_tpu.config import root
            raw = root.common.get_dict("mesh")
            if raw:
                from veles_tpu.parallel import build_mesh
                mesh = build_mesh(raw)

        super(StandardWorkflow, self).__init__(workflow, name=name)
        self.repeater = Repeater(self)
        self.repeater.link_from(self.start_point)

        if loader is None:
            loader = loader_factory(self, **(loader_config or {}))
        self.loader = loader
        self.loader.link_from(self.repeater)

        self.forwards = make_forwards(
            self, self.loader.minibatch_data, layers)

        if loss == "mse":
            self.evaluator = EvaluatorMSE(self)
            self.evaluator.target = self.loader.minibatch_targets
        elif loss == "next_token":
            from veles_tpu.models.evaluator import EvaluatorNextToken
            self.evaluator = EvaluatorNextToken(self)
            self.evaluator.tokens = self.loader.minibatch_data
        else:
            self.evaluator = EvaluatorSoftmax(self)
            self.evaluator.labels = self.loader.minibatch_labels
            if isinstance(self.forwards[-1], All2AllSoftmax):
                # exact in-graph loss from the head's real logits
                self.evaluator.logits = self.forwards[-1].logits_out
        self.evaluator.output = self.forwards[-1].output
        self.evaluator.loader = self.loader

        self.gd = GradientDescent(
            self, forwards=self.forwards, evaluator=self.evaluator,
            loader=self.loader, mesh=mesh, **trainer_kwargs)
        self.gd.link_from(self.loader)

        self.decision = DecisionGD(self, **(decision_config or {}))
        self.decision.loader = self.loader
        self.decision.trainer = self.gd
        self.decision.link_from(self.gd)

        snapshotter_config = dict(snapshotter_config or {})
        if snapshotter_config.pop("enabled", True):
            self.snapshotter = Snapshotter(self, **snapshotter_config)
            self.snapshotter.decision = self.decision
            self.snapshotter.link_from(self.decision)
        else:
            self.snapshotter = None

        # live plots (ref: znicz StandardWorkflow wired its plotter set
        # the same way); payloads publish only when a graphics server or
        # web-status notifier is attached
        self.plotters = []
        if plotters:
            from veles_tpu.plotting_units import AccumulatingPlotter
            err_plot = AccumulatingPlotter(
                self, obj=self.decision, attr="validation_error_pct",
                label="validation error", ylabel="%",
                name="error_curve")
            err_plot.gate_skip = ~self.loader.epoch_ended
            loss_plot = AccumulatingPlotter(
                self, obj=self.gd, attr="loss", label="train loss",
                ylabel="loss", name="loss_curve")
            for plot in (err_plot, loss_plot):
                plot.link_from(self.decision)
                self.plotters.append(plot)

        self.repeater.link_from(self.decision)
        self.loader.gate_block = self.decision.complete
        self.end_point.link_from(self.decision)
        self.end_point.gate_block = ~self.decision.complete
