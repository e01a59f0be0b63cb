"""Convolutional layers (reconstruction of znicz conv, surface per
manualrst_veles_algorithms.rst: grouping, padding, stride — "sliding" in
Veles terms — and Deconvolution).

Data layout is NHWC with HWIO kernels — the layouts XLA:TPU tiles onto
the MXU without transposes.  The convolution itself is
``lax.conv_general_dilated`` (one XLA op; the reference lowered conv to
im2col + its hand-tiled GEMM).
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu import dtypes
from veles_tpu.models.activations import get_activation
from veles_tpu.models.nn_units import ForwardBase


def _pair(v):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v[:2])
    return (int(v), int(v))


def validate_space_to_depth(h, w, ky, kx, n):
    """Raise unless a stride-n VALID ky×kx conv over [h, w] produces
    the same output from the blocked form — i.e. (h-ky) and (w-kx)
    are stride multiples AND the blocked VALID output count matches
    the logical one.  Loaders/samples that pre-block data call this
    with the model's stem geometry (misalignment would silently add
    border outputs computed from block padding)."""
    for dim, k in ((h, ky), (w, kx)):
        if (dim - k) % n:
            raise ValueError(
                "space_to_depth=%d misaligned: (%d - %d) %% %d != 0"
                % (n, dim, k, n))
        logical = (dim - k) // n + 1
        blocked = -(-dim // n) - (-(-k // n)) + 1
        if logical != blocked:
            raise ValueError(
                "space_to_depth=%d: blocked VALID output %d != "
                "logical %d over extent %d (kernel %d)"
                % (n, blocked, logical, dim, k))


def space_to_depth(x, n):
    """[B, H, W, C] → [B, ceil(H/n), ceil(W/n), n²·C] (zero-padded to
    block multiples; block channel layout (dh, dw, c)).  Loaders call
    this to pre-block data for a ``Conv(space_to_depth=n)`` stem —
    and should call :func:`validate_space_to_depth` with the stem
    geometry first."""
    b, h, w, c = x.shape
    hp = -h % n
    wp = -w % n
    if hp or wp:
        x = jnp.pad(x, ((0, 0), (0, hp), (0, wp), (0, 0)))
    hb, wb = (h + hp) // n, (w + wp) // n
    x = x.reshape(b, hb, n, wb, n, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, hb, wb, n * n * c)


class Conv(ForwardBase):
    """y = activation(conv(x, W) + b), x: [N, H, W, C]
    (znicz conv.Conv; kwargs kx/ky/n_kernels/sliding/padding match the
    reference surface, grouping via ``n_groups``)."""

    ACTIVATION = "linear"

    def __init__(self, workflow, n_kernels=None, kx=3, ky=3,
                 sliding=(1, 1), padding="same", n_groups=1,
                 activation=None, space_to_depth=0,
                 space_to_depth_hw=None, **kwargs):
        super(Conv, self).__init__(workflow, **kwargs)
        if n_kernels is None:
            raise ValueError("n_kernels is required")
        self.n_kernels = int(n_kernels)
        self.kx, self.ky = int(kx), int(ky)
        #: user-facing (sliding_x, sliding_y) — the znicz convention
        #: (kx = horizontal); internally NHWC wants (stride_H, stride_W)
        self.sliding = _pair(sliding)
        self.padding = padding  # "same" | "valid" | ((t,b),(l,r)) | int
        self.n_groups = int(n_groups)
        self.activation = activation or self.ACTIVATION
        #: stride-matched space-to-depth stem (TPU emitter fix for
        #: tiny-C strided stems like AlexNet's 11×11/4 over RGB: the
        #: blocked form measured 5.42 vs 7.88 ms fwd+dk on v5e in
        #: round 5).  Weights stay in the LOGICAL
        #: [ky, kx, C, O] convention — the blocked kernel is built
        #: in-graph, so export/snapshot/autodiff are unchanged.  The
        #: loader must feed pre-blocked data (``space_to_depth()``).
        #: NOT supported by the C++ runner's Conv (runtime/units.cc
        #: computes the plain strided form) — export with
        #: space_to_depth=0 for package_export targets.
        self.space_to_depth = int(space_to_depth or 0)
        #: (hb, wb) of the blocked input when the loader stores it
        #: FLAT [batch, hb·wb·n²·C] — 4D-blocked dataset layouts
        #: gather pathologically, so the fast
        #: path is flat storage + this in-graph reshape
        self.space_to_depth_hw = tuple(space_to_depth_hw) \
            if space_to_depth_hw else None
        if self.space_to_depth:
            if self.n_groups != 1:
                raise ValueError("space_to_depth requires n_groups=1")
            if self.sliding != (self.space_to_depth,) * 2:
                raise ValueError(
                    "space_to_depth=%d requires sliding=(%d, %d)"
                    % ((self.space_to_depth,) * 3))
            if not (isinstance(self.padding, str)
                    and self.padding.lower() == "valid"):
                raise ValueError("space_to_depth requires VALID padding")

    @property
    def _hw_strides(self):
        sx, sy = self.sliding
        return (sy, sx)

    def _lax_padding(self):
        if isinstance(self.padding, str):
            return self.padding.upper()
        if isinstance(self.padding, int):
            p = self.padding
            return ((p, p), (p, p))
        return tuple(tuple(int(x) for x in p) for p in self.padding)

    def _blocked_in_channels(self, input_shape):
        """Per-block input channels (n²·C_logical) from either the 4D
        blocked layout or the flat [batch, hb·wb·n²·C] one."""
        if len(input_shape) == 2 and self.space_to_depth:
            if not self.space_to_depth_hw:
                raise ValueError(
                    "flat space_to_depth input needs space_to_depth_hw")
            hb, wb = self.space_to_depth_hw
            return input_shape[-1] // (hb * wb)
        return input_shape[-1]

    def output_shape_for(self, input_shape):
        kshape = self._kernel_shape(
            self._blocked_in_channels(input_shape))
        out = jax.eval_shape(
            lambda x, k: self._conv(x, k),
            jax.ShapeDtypeStruct(input_shape, jnp.float32),
            jax.ShapeDtypeStruct(kshape, jnp.float32))
        return out.shape

    def _kernel_shape(self, in_channels):
        if self.space_to_depth:
            in_channels //= self.space_to_depth ** 2
        return (self.ky, self.kx, in_channels // self.n_groups,
                self.n_kernels)

    def _blocked_kernel(self, kernel):
        """Logical [ky, kx, C, O] → blocked [kby, kbx, n²·C, O]
        matching ``space_to_depth``'s (dh, dw, c) channel layout.
        Built in-graph: tiny (≤ tens of KB), and autodiff maps the
        blocked-kernel cotangent back onto the logical weights."""
        n = self.space_to_depth
        ky, kx, c, o = kernel.shape
        kby, kbx = -(-ky // n), -(-kx // n)
        kp = jnp.pad(kernel, ((0, kby * n - ky), (0, kbx * n - kx),
                              (0, 0), (0, 0)))
        kp = kp.reshape(kby, n, kbx, n, c, o)
        return kp.transpose(0, 2, 1, 3, 4, 5).reshape(
            kby, kbx, n * n * c, o)

    def _unflatten_s2d(self, x):
        if x.ndim == 2 and self.space_to_depth:
            c = self._blocked_in_channels(x.shape)
            hb, wb = self.space_to_depth_hw
            x = x.reshape(x.shape[0], hb, wb, c)
        return x

    def _conv(self, x, kernel):
        if self.space_to_depth:
            x = self._unflatten_s2d(x)
            # blocked stem: stride-n VALID conv over [B, H, W, C]
            # becomes a stride-1 VALID conv over the pre-blocked
            # [B, ceil(H/n), ceil(W/n), n²·C] input.  The caller must
            # pre-block with ``space_to_depth()`` and guarantee
            # (H - ky) % n == 0 so the blocked output equals the
            # logical one (AlexNet's 227/11/4 stem does).
            cd = dtypes.compute_dtype()
            return jax.lax.conv_general_dilated(
                x.astype(cd), self._blocked_kernel(kernel).astype(cd),
                window_strides=(1, 1), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=dtypes.matmul_precision())
        # BOTH operands cast to the compute dtype and the output kept in
        # it: the conv trunk's activations are the HBM-bandwidth hot
        # spot (bf16 halves the traffic), and the conv VJP needs
        # matching operand/cotangent dtypes — a bf16-in/f32-out mix is
        # rejected by lax.conv.  The MXU accumulates in f32 internally
        # regardless; the loss is computed in f32 at the evaluator.
        # (The space_to_depth branch above is the r5 stem rewrite:
        # 2.2 ms faster in isolation but net-negative in the full
        # step because of the blocked dataset's gather layout; it
        # therefore ships opt-in.)
        cd = dtypes.compute_dtype()
        return jax.lax.conv_general_dilated(
            x.astype(cd), kernel.astype(cd),
            window_strides=self._hw_strides,
            padding=self._lax_padding(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.n_groups,
            precision=dtypes.matmul_precision())

    def fill_params(self):
        in_ch = self._blocked_in_channels(self.input.shape)
        kshape = self._kernel_shape(in_ch)
        fan_in = self.kx * self.ky * kshape[2]
        fan_out = self.n_kernels
        self.weights.reset(numpy.zeros(kshape, numpy.float32))
        self._fill(self.weights.mem, self.weights_filling,
                   self.weights_stddev, fan_in, fan_out)
        if self.include_bias:
            self.bias.reset(numpy.zeros((self.n_kernels,), numpy.float32))
            self._fill(self.bias.mem, self.bias_filling,
                       self.bias_stddev or 0.0, fan_in, fan_out)

    def apply(self, params, x):
        y = self._conv(x, params["weights"])
        if self.include_bias:
            y = y + params["bias"].astype(y.dtype)
        return get_activation(self.activation)(y)

    def export_config(self):
        cfg = {"n_kernels": self.n_kernels, "kx": self.kx, "ky": self.ky,
               "sliding": list(self.sliding), "padding": self.padding,
               "n_groups": self.n_groups, "activation": self._export_activation(),
               "include_bias": self.include_bias}
        if self.space_to_depth:
            cfg["space_to_depth"] = self.space_to_depth
            if self.space_to_depth_hw:
                cfg["space_to_depth_hw"] = list(self.space_to_depth_hw)
        return cfg


class ConvTanh(Conv):
    ACTIVATION = "tanh"


class ConvRELU(Conv):
    ACTIVATION = "relu"


class ConvStrictRELU(Conv):
    ACTIVATION = "strict_relu"


class Deconv(ForwardBase):
    """Transposed convolution (znicz deconv; extras item 1) — used by the
    convolutional autoencoders."""

    ACTIVATION = "linear"

    def __init__(self, workflow, n_kernels=None, kx=3, ky=3,
                 sliding=(1, 1), padding="same", activation=None, **kwargs):
        super(Deconv, self).__init__(workflow, **kwargs)
        if n_kernels is None:
            raise ValueError("n_kernels is required")
        self.n_kernels = int(n_kernels)
        self.kx, self.ky = int(kx), int(ky)
        self.sliding = _pair(sliding)  # (sx, sy), znicz convention
        self.padding = padding
        self.activation = activation or self.ACTIVATION

    def _kernel_shape(self, in_channels):
        return (self.ky, self.kx, self.n_kernels, in_channels)

    def _deconv(self, x, kernel):
        cd = dtypes.compute_dtype()  # see Conv._conv dtype note
        pad = self.padding.upper() if isinstance(self.padding, str) \
            else self.padding
        sx, sy = self.sliding
        return jax.lax.conv_transpose(
            x.astype(cd), kernel.astype(cd),
            strides=(sy, sx), padding=pad,
            dimension_numbers=("NHWC", "HWOI", "NHWC"),
            precision=dtypes.matmul_precision())

    def output_shape_for(self, input_shape):
        out = jax.eval_shape(
            lambda x, k: self._deconv(x, k),
            jax.ShapeDtypeStruct(input_shape, jnp.float32),
            jax.ShapeDtypeStruct(self._kernel_shape(input_shape[-1]),
                                 jnp.float32))
        return out.shape

    def fill_params(self):
        in_ch = self.input.shape[-1]
        kshape = self._kernel_shape(in_ch)
        fan_in = self.kx * self.ky * in_ch
        fan_out = self.n_kernels
        self.weights.reset(numpy.zeros(kshape, numpy.float32))
        self._fill(self.weights.mem, self.weights_filling,
                   self.weights_stddev, fan_in, fan_out)
        if self.include_bias:
            self.bias.reset(numpy.zeros((self.n_kernels,), numpy.float32))
            self._fill(self.bias.mem, self.bias_filling,
                       self.bias_stddev or 0.0, fan_in, fan_out)

    def apply(self, params, x):
        y = self._deconv(x, params["weights"])
        if self.include_bias:
            y = y + params["bias"]
        return get_activation(self.activation)(y.astype(jnp.float32))

    def export_config(self):
        return {"n_kernels": self.n_kernels, "kx": self.kx, "ky": self.ky,
                "sliding": list(self.sliding), "padding": self.padding,
                "activation": self._export_activation(),
                "include_bias": self.include_bias}
