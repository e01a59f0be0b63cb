"""Cross-channel LRN as a banded matmul — the AlexNet hot op.

    y = x / (k + alpha * sum_{j in window(c)} x_j^2) ** beta

Formulation log (every number measured on the full AlexNet train step,
TPU v5e, batch 1024, jax.profiler XLA-op timeline — isolated
micro-benchmarks of this op actively mislead, the fusion context
dominates):

- banded C×C matmul, plain autodiff (THIS file): 15.4k samples/sec
- shifted adds / ``reduce_window`` on the VPU:   12.1-12.7k (the
  cross-lane rotations schedule as extra HBM round trips)
- Pallas kernels (pad-shift / roll / in-kernel band): 5.9k best —
  lane rotations in Mosaic ran far below HBM speed at C=96
- custom-VJP band (recompute denominator):       13.5k — the whole
  minibatch step is ONE XLA program, so autodiff's "saved" forward
  product is CSE-shared for free and recompute just adds a matmul
- band + ``optimization_barrier`` isolation:     13.8-14.7k — XLA's
  own fusion choices beat hand-drawn fusion boundaries

The remaining known waste: the backward transposed band dot picks
XLA's batch-in-sublanes convolution emitter (~3x the forward's
batch-in-lanes schedule).  None of the tricks above flips it without
losing more elsewhere; revisit when XLA's emitter heuristics change.

ROUND 5 ADDENDUM — ``lrn_pallas`` below: a pallas kernel pair
(forward + recompute-backward under ``jax.custom_vjp``) that does the
band product ON THE MXU inside the kernel (never a cross-lane rotate,
the r2 attempts' mistake), with narrow channel counts packed to lane
multiples (``_pack_group``).  Measured on TPU v5e at the AlexNet
shapes it beats the band formulation IN ISOLATION (9.2 vs 13.3 ms at
[1024·55·55, 96] fwd+bwd, 5.7 vs 8.8 at [1024·27·27, 256]) — but
LOSES in the full train step, because the graph-level [B,55,55,96] →
[R,C] flatten is a tiled-layout change XLA must materialize (W=55 is
not a sublane multiple), costing ~1.8 ms per crossing, four crossings
per layer-pass; the r5 full-step A/B measured 15.2k (band) vs 9.9k
(pallas) samples/s.  A fused LRN+maxpool kernel prototype (per-sample
blocks, in-VMEM W-padding, H-pool via free leading-dim reshapes,
W-pool via a 2·C lane fold) reached parity-to-slightly-better on the
forward (5.7 vs 6.7 ms) but its backward is VPU-pointwise-bound at
the same ~10 ms the XLA backward already costs: Mosaic DMA streams
cap at ~330 GB/s aggregate on this chip (measured; XLA fusions reach
~660), and the EUP is f32-only, so the kernel cannot beat the fused
XLA loops on a streaming-plus-transcendental op (round 5, on the
chip).  The band formulation therefore REMAINS the
production TPU path; ``lrn_pallas`` ships tested as the in-repo
native-kernel counterpart (SURVEY §2.2) and the decision record.
"""

import functools

import jax
import jax.numpy as jnp
import numpy
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@functools.lru_cache(maxsize=None)
def _band(c, n):
    """band[src, dst] = 1 iff channel ``src`` is inside ``dst``'s
    window [dst-half, dst+n-1-half] (reduce_window semantics with
    (half, n-1-half) padding).  Cached as NUMPY — a cached jax array
    created under a trace would leak the tracer across jit scopes."""
    half = n // 2
    src = numpy.arange(c)[:, None]
    dst = numpy.arange(c)[None, :]
    b = ((dst - src) <= half) & ((src - dst) <= (n - 1 - half))
    return b.astype(numpy.float32)


def _band_dot(t, c, n):
    """[..., C] @ band with f32 accumulation."""
    band = jnp.asarray(_band(c, n), t.dtype)
    return jax.lax.dot_general(
        t, band, (((t.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _power(s, beta):
    if beta == 0.75:
        # s^-0.75 = rsqrt(s)·sqrt(rsqrt(s)): cheap VPU ops (lax.pow
        # lowers to exp/log)
        r = jax.lax.rsqrt(s)
        return r * jnp.sqrt(r)
    return jax.lax.pow(s, -beta)


def lrn(x, alpha=1e-4, beta=0.75, n=5, k=2.0):
    """LRN over the last (channel) axis of ``x``.

    Plain autodiff band matmul: the whole minibatch step is one XLA
    program, so the forward band product is CSE-shared with the
    backward, and XLA's own fusion choices measured faster than every
    alternative tried (custom-VJP recompute, optimization_barrier
    isolation, reduce_window, shifted adds, three Pallas kernels —
    each benchmarked on the full AlexNet step, see the module
    docstring)."""
    c = x.shape[-1]
    sq = x * x
    # the downcast of the window sum to x.dtype is DELIBERATE: with
    # bf16 activations it keeps the saved denominator chain bf16,
    # which measured 4% faster end-to-end than carrying f32 (the
    # denominator is k-dominated, so bf16 rounding of the sum is
    # harmless — convergence suites pass either way)
    ssum = _band_dot(sq, c, n).astype(x.dtype)
    s = k + alpha * ssum.astype(jnp.float32)
    return (x.astype(jnp.float32) * _power(s, beta)).astype(x.dtype)


# -- fused pallas kernels ---------------------------------------------------
#
# One grid dimension over row blocks of the [R, C] flattening
# (R = batch x spatial).  The channel window is an in-VMEM [C, C]
# band matmul on the MXU — never a cross-lane rotate.  The backward
# recomputes the denominator from x (two more tiny band dots) instead
# of saving it, so the residual is just x and each pass is exactly
# one HBM read + one write.
#
# ROW PACKING: narrow channel counts stream badly (a width-96 block
# measured 4.84 ms for a pure copy of 0.59 GB vs 3.57 at width 1536 —
# Mosaic DMA pays for partial lanes).  ``_pack_group`` folds g
# consecutive rows into one width-g·C row (a FREE reshape — row-major
# bytes are unchanged) and the band becomes a [g·C, g·C] block
# diagonal, so every row of the packed block is g independent LRN
# windows and the lane dim is a 128-multiple.

#: rows per block: 1024 x 256ch x bf16 = 512 KB/block — three
#: double-buffered streams (x, dy, dx) fit VMEM with headroom
_BLOCK_ROWS = 1024


_LANES = 128


def _pack_group(c):
    """Smallest g with g*c a lane multiple (capped — the [g*c, g*c]
    band and the f32 intermediates must stay VMEM-friendly)."""
    g = 1
    while (g * c) % _LANES and g * c < 1024:
        g += 1
    return g if (g * c) % _LANES == 0 else 1


@functools.lru_cache(maxsize=None)
def _band_packed(c, n, g):
    """Block-diagonal [g*c, g*c] band: g independent channel windows."""
    b = _band(c, n)
    out = numpy.zeros((g * c, g * c), numpy.float32)
    for i in range(g):
        out[i * c:(i + 1) * c, i * c:(i + 1) * c] = b
    return out


def _lrn_fwd_kernel(x_ref, band_ref, y_ref, *, alpha, beta, k):
    x = x_ref[...]
    ssum = jax.lax.dot(x * x, band_ref[...],
                       preferred_element_type=jnp.float32)
    s = k + alpha * ssum
    y_ref[...] = (x.astype(jnp.float32)
                  * _power(s, beta)).astype(y_ref.dtype)


def _lrn_bwd_kernel(x_ref, dy_ref, band_ref, dx_ref, *, alpha, beta, k):
    xb = x_ref[...]
    band = band_ref[...]
    ssum = jax.lax.dot(xb * xb, band,
                       preferred_element_type=jnp.float32)
    s = k + alpha * ssum
    p = _power(s, beta)                      # s^-beta, f32
    x = xb.astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    t = dy * x * (p / s)                     # dy·x·s^(-beta-1)
    # u_i = sum_c band[i, c] t_c  ==  t @ band^T
    u = jax.lax.dot_general(
        t.astype(xb.dtype), band, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dx = dy * p - (2.0 * alpha * beta) * x * u
    dx_ref[...] = dx.astype(dx_ref.dtype)


from veles_tpu.ops.common import use_interpret as _pallas_interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _lrn_rows(x, c, alpha, beta, n, k, interpret):
    """x: [R, W] with W = g*c (g packed windows per row)."""
    y, _ = _lrn_rows_fwd(x, c, alpha, beta, n, k, interpret)
    return y


def _band_arg(c, n, g, dtype):
    # 0/1 entries are exact in bf16, so the band feeds the MXU in the
    # activation dtype at full rate
    return jnp.asarray(_band_packed(c, n, g), dtype)


def _lrn_rows_fwd(x, c, alpha, beta, n, k, interpret):
    r, w = x.shape
    y = pl.pallas_call(
        functools.partial(_lrn_fwd_kernel, alpha=alpha, beta=beta, k=k),
        grid=(pl.cdiv(r, _BLOCK_ROWS),),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, w), lambda i: (i, 0)),
            pl.BlockSpec((w, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, w), x.dtype),
        interpret=interpret,
    )(x, _band_arg(c, n, w // c, x.dtype))
    return y, (x,)


def _lrn_rows_bwd(c, alpha, beta, n, k, interpret, res, dy):
    (x,) = res
    r, w = x.shape
    dx = pl.pallas_call(
        functools.partial(_lrn_bwd_kernel, alpha=alpha, beta=beta, k=k),
        grid=(pl.cdiv(r, _BLOCK_ROWS),),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, w), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, w), lambda i: (i, 0)),
            pl.BlockSpec((w, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, w), x.dtype),
        interpret=interpret,
    )(x, dy.astype(x.dtype), _band_arg(c, n, w // c, x.dtype))
    return (dx,)


_lrn_rows.defvjp(_lrn_rows_fwd, _lrn_rows_bwd)


def lrn_pallas(x, alpha=1e-4, beta=0.75, n=5, k=2.0, backend=None):
    """LRN over the last axis via the fused pallas kernel pair
    (differentiable — backward is its own fused kernel).

    ``backend`` is the platform of the TARGET device (callers inside a
    unit pass ``unit.device.jax_device.platform``); off-TPU the same
    kernels run under ``interpret=True`` so CPU tests exercise the
    real code path."""
    c = x.shape[-1]
    rows = x.reshape(-1, c)
    g = _pack_group(c)
    if g > 1 and rows.shape[0] % g == 0:
        rows = rows.reshape(-1, g * c)
    y = _lrn_rows(rows, int(c), float(alpha), float(beta), int(n),
                  float(k), _pallas_interpret(backend))
    return y.reshape(x.shape)
