"""Dequant-fused paged-attention pallas kernel — the block-gather
attention loop of ``ops/paged_attention.py`` as ONE kernel, for fp32
AND int8 pools.

The jnp reference path materializes the gathered table blocks as a
``[B, T·bs, d]`` tensor in HBM (dequantized to the compute dtype when
the pool is int8) before the masked softmax — for a bandwidth-bound
decode step that round-trip IS the cost.  Here each grid step DMAs
one physical block straight into VMEM (the block table rides scalar
prefetch, so the index map itself does the gather), dequantizes it
in-register against its per-row scales, and folds it into a running
online-softmax accumulation — the FlashAttention-2 decomposition of
``ops/pallas_attention.py`` restricted to one query run per row.  The
dequantized gather never exists in HBM, which is what makes int8
pools pay int8 bandwidth instead of "int8 storage, f32 traffic".

One kernel serves both step families: plain decode is the K1 = 1
special case of the width-K1 speculative verify (exactly the
relationship of the jnp pair).  The caller scatters the run's new
K/V (quantizing when int8) BEFORE invoking — the kernel then reads
the post-scatter pool, so its numerics match the two-pass jnp path
block-for-block (parity is allclose: the online softmax reorders the
reduction).

Runs under ``interpret=True`` off-TPU (``ops.common.use_interpret``,
the flash/lrn convention) — tier-1 proves parity on CPU
(tests/test_kv_quant.py) and that the Mosaic lowering compiles at the
serving shapes (tests/test_tpu_compile.py); chip_smoke.py holds it
against the jnp paths on a real chip.

Layouts: q/qpos per batch row, pools block-major
([num_blocks, block_size, d] with the per-row scales
[num_blocks, block_size] beside them); heads are folded as d = h·hd
and unfolded per-head inside the kernel (2-D dots only).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import use_interpret as _use_interpret

#: finite stand-in for -inf (ops/pallas_attention.py convention)
_NEG_INF = -1e30
#: lane width — running row-stats scratch replicates across it
_LANES = 128


def _attend_kernel(tables_ref, qp_ref, q_ref, k_ref, v_ref, *rest,
                   heads, head_dim, block_size, k1, quant, scale):
    """One (b, t) grid step: fold physical block ``tables[b, t]``
    into row b's online-softmax state.  ``tables_ref`` and ``qp_ref``
    ride scalar prefetch (SMEM); ``rest`` is
    ``[sk_ref, sv_ref,] o_ref, acc_ref, m_ref, l_ref``."""
    if quant:
        sk_ref, sv_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        sk_ref = sv_ref = None
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    h, hd, bs = heads, head_dim, block_size

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[0].astype(jnp.float32)              # [bs, d]
    v = v_ref[0].astype(jnp.float32)
    if quant:                                     # dequant in VMEM
        k = k * sk_ref[0, 0][:, None]
        v = v * sv_ref[0, 0][:, None]
    # the run's positions are SMEM scalars: spread them down the rows
    bi = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (k1, bs), 0)
    qp = jnp.zeros((k1, bs), jnp.int32)
    for j in range(k1):
        qp = jnp.where(rows == j, qp_ref[bi, j], qp)
    cols = t * bs + jax.lax.broadcasted_iota(
        jnp.int32, (k1, bs), 1)
    keep = cols <= qp                             # causal + trash tail
    for head in range(h):
        lo = head * hd
        qh = q_ref[0][:, lo:lo + hd].astype(jnp.float32)  # [k1, hd]
        s = jax.lax.dot_general(
            qh, k[:, lo:lo + hd], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [k1, bs]
        s = jnp.where(keep, s, _NEG_INF)
        r = head * k1
        m_prev = m_ref[r:r + k1, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_ref[r:r + k1, 0] * alpha + p.sum(axis=1)
        m_ref[r:r + k1] = jnp.broadcast_to(m_cur[:, None],
                                           (k1, _LANES))
        l_ref[r:r + k1] = jnp.broadcast_to(l_cur[:, None],
                                           (k1, _LANES))
        acc_ref[:, lo:lo + hd] = \
            acc_ref[:, lo:lo + hd] * alpha[:, None] + jax.lax.dot(
                p, v[:, lo:lo + hd],
                preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _finish():
        for head in range(h):
            lo = head * hd
            l = jnp.maximum(l_ref[head * k1:(head + 1) * k1, 0],
                            1e-30)
            o_ref[0, :, lo:lo + hd] = \
                (acc_ref[:, lo:lo + hd] / l[:, None]).astype(
                    o_ref.dtype)


def pallas_paged_attend(q, pool_k, pool_v, tables, qpos, heads,
                        scale_k=None, scale_v=None, interpret=None,
                        backend=None):
    """Block-gather attention over a (possibly int8) paged KV pool.

    ``q`` [B, K1, d] — row n's queries at sequence positions
    ``qpos`` [B, K1]; ``pool_k``/``pool_v`` [num_blocks, bs, d]
    POST-scatter (the caller wrote the run's K/V first);
    ``scale_k``/``scale_v`` [num_blocks, bs] f32 per-row dequant
    scales (None = fp32 pool); ``tables`` [B, T] physical block ids.
    Returns the attention context [B, K1, d] (f32) — same masked
    softmax as the jnp reference, accumulated online so the gathered
    blocks never materialize."""
    b, k1, d = q.shape
    bs = pool_k.shape[1]
    nt = tables.shape[1]
    hd = d // heads
    quant = scale_k is not None
    if interpret is None:
        interpret = _use_interpret(backend)
    kernel = functools.partial(
        _attend_kernel, heads=heads, head_dim=hd, block_size=bs,
        k1=k1, quant=quant, scale=1.0 / (hd ** 0.5))

    def row_map(bi, t, tbl, qp):
        return (bi, 0, 0)

    def blk_map(bi, t, tbl, qp):
        return (tbl[bi, t], 0, 0)

    in_specs = [
        pl.BlockSpec((1, k1, d), row_map),
        pl.BlockSpec((1, bs, d), blk_map),
        pl.BlockSpec((1, bs, d), blk_map),
    ]
    ops = [q, pool_k, pool_v]
    if quant:
        # Mosaic tiles the last two block dims: a (1, bs) slice of
        # [nb, bs] is refused, a (1, 1, bs) slice of [nb, 1, bs] has
        # both equal to the array's own
        in_specs += [pl.BlockSpec((1, 1, bs), blk_map),
                     pl.BlockSpec((1, 1, bs), blk_map)]
        ops += [scale_k[:, None, :], scale_v[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, k1, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((k1, d), jnp.float32),
            pltpu.VMEM((heads * k1, _LANES), jnp.float32),
            pltpu.VMEM((heads * k1, _LANES), jnp.float32),
        ])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, k1, d), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(qpos, jnp.int32),
      *ops)
