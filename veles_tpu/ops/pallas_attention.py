"""Native pallas flash-attention kernels — the framework's own
implementation of the attention hot op (the discipline SURVEY.md §2.2
demands: the reference hand-wrote its hottest kernels in OpenCL/CUDA,
e.g. ocl/forward.cl; on TPU the equivalent is a pallas program that
keeps the score blocks in VMEM instead of round-tripping the
[seq, seq] matrix through HBM).

Three kernels wired by a `jax.custom_vjp` — the standard
FlashAttention-2 decomposition:

- forward: online-softmax accumulation over K/V blocks, saving only
  the output and the per-row logsumexp;
- backward dq: recompute p block-by-block from (q, k, logsumexp),
  accumulate dq across K blocks;
- backward dk/dv: same recompute with the grid transposed (Q blocks
  innermost), accumulating dk/dv.

The sibling module `ops/flash.py` wraps the kernel that ships WITH
jax; keeping both is deliberate — the jax kernel is the battle-tested
default, this one is the in-repo implementation (selected with
``attn_impl="pallas"``), runs under ``interpret=True`` on CPU for
tests, and is the place to fuse framework-specific epilogues the
stock kernel can't express.

Layouts: kernels see [bh, seq, head_dim] (batch × heads flattened
into the leading grid dim); the public entry takes the framework's
[batch, seq, heads, head_dim].
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: 1024-token K blocks HALVE the per-block online-softmax bookkeeping
#: rounds (the m/l/acc rescale runs on lane-replicated [bq, 128]
#: scratch, so its cost rivals the matmuls at small batch×heads) —
#: measured faster than 512 at every length, and past the jax-shipped
#: kernel at 32k (32.3 vs 38.3 ms, round 5)
DEFAULT_BLOCK = 1024
#: larger Q blocks amortize the K/V streaming (21% on the jax kernel
#: at head_dim 128, round 4)
DEFAULT_BLOCK_Q = 1024
#: finite stand-in for -inf: exp(x - max) underflows to 0 for masked
#: entries without generating nan through (-inf) - (-inf)
_NEG_INF = -1e30
#: lane width — running row-stats scratch replicates across it
_LANES = 128


from veles_tpu.ops.common import use_interpret as _use_interpret


def _mask(s, q_base, k_base, block_q, block_k, causal, kv_len):
    """Causal and/or K-length masking of a score block.  ``kv_len``
    is the REAL key length — block-padded tail columns (the
    pad-and-mask entry for odd sequence lengths) mask away here."""
    rows = q_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = k_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = cols < kv_len
    if causal:
        keep &= cols <= rows
    return jnp.where(keep, s, _NEG_INF)


def _masked_scores(s, q_base, k_base, block_q, block_k, causal,
                   kv_len):
    """Apply causal/tail masking to a score block, but make the
    masking straight-line (see the note below)."""
    tail = kv_len % block_k != 0      # static: padded K tail exists
    if not causal and not tail:
        return s
    # NOTE a lax.cond that skips the mask on sub-diagonal blocks was
    # measured SLOWER at every length (12.0 vs 11.4 ms at seq 2048,
    # 55.7 vs 42.3 at 32k) — Mosaic's branch disrupts the pipeline
    # more than the unconditional mask costs; keep it straight-line
    return _mask(s, q_base, k_base, block_q, block_k, causal, kv_len)


def _clamp_maps(block_q, block_k, causal):
    """Index maps for the K/V streams of a (bh, q, k) grid.  For the
    causal case the K index CLAMPS to the diagonal block: grid steps
    past the diagonal re-request the same block, and pallas skips the
    DMA for a repeated index — causally dead K/V blocks are never
    fetched (this closed the r4 gap vs the jax kernel at long
    context)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def kv_map(b, i, j):
        j_max = ((i + 1) * block_q - 1) // block_k
        return (b, jnp.minimum(j, j_max), 0)

    return kv_map


def _clamp_maps_dkv(block_q, block_k, causal):
    """Index maps for the Q/dO/O/lse streams of a (bh, k, q) grid:
    the Q index clamps UP to the first block at-or-past the diagonal,
    so leading dead steps re-request that block (one DMA, no more)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def q_map(b, i, j):
        j_min = (i * block_k) // block_q
        return (b, jnp.maximum(j, j_min), 0)

    return q_map


# -- forward ----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal,
                block_q, block_k, kv_len):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_base = pl.program_id(1) * block_q
    k_base = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        # operands stay in the input dtype (bf16 feeds the MXU at
        # full rate); accumulation is f32 via preferred_element_type
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        s = _masked_scores(s, q_base, k_base, block_q, block_k,
                           causal, kv_len)
        m_prev = m_ref[:, 0]                      # [bq]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])           # [bq, bk]
        l_cur = l_ref[:, 0] * alpha + p.sum(axis=1)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)
        v = v_ref[0]                              # [bk, dv]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)

    if causal:
        # K blocks strictly above the diagonal band contribute nothing
        @pl.when(k_base <= q_base + block_q - 1)
        def _():
            _block()
    else:
        _block()

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # lane-replicated (the Mosaic-friendly layout for per-row
        # scalars — block last-dims must tile (8, 128))
        lse = m_ref[:, 0] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _run_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
             kv_len):
    """q/k/v: [bh, seq, d] (block-padded) → (o [bh, sq, dv],
    lse [bh, sq, 128] f32 lane-replicated); ``kv_len`` = real key
    length for tail masking."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    kv_map = _clamp_maps(block_q, block_k, causal)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_len=kv_len),
        name="veles_attn_fwd",
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# -- backward ---------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                   dq_ref, acc_ref, *, scale, causal, block_q,
                   block_k, kv_len):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_base = pl.program_id(1) * block_q
    k_base = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # D = rowsum(dO ⊙ O) recomputed per block (cheaper than a
        # lane-replicated HBM side array)
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32), axis=-1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _masked_scores(s, q_base, k_base, block_q, block_k,
                           causal, kv_len)
        p = jnp.exp(s - lse_ref[0][:, 0][:, None])    # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += jax.lax.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(k_base <= q_base + block_q - 1)
        def _():
            _block()
    else:
        _block()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale,
                    causal, block_q, block_k, kv_len):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    q_base = qi * block_q
    k_base = pl.program_id(1) * block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32), axis=-1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _masked_scores(s, q_base, k_base, block_q, block_k,
                           causal, kv_len)
        p = jnp.exp(s - lse_ref[0][:, 0][:, None])
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, dv]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]

    if causal:
        @pl.when(k_base <= q_base + block_q - 1)
        def _():
            _block()
    else:
        _block()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


# -- custom_vjp wiring ------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _mha(q, k, v, scale, causal, block_q, block_k, interpret, kv_len):
    o, _ = _mha_fwd(q, k, v, scale, causal, block_q, block_k,
                    interpret, kv_len)
    return o


def _mha_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
             kv_len):
    o, lse = _run_fwd(q, k, v, scale, causal, block_q, block_k,
                      interpret, kv_len)
    return o, (q, k, v, o, lse)


def _mha_bwd(scale, causal, block_q, block_k, interpret, kv_len, res,
             do):
    q, k, v, o, lse = res
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]

    kv_map = _clamp_maps(block_q, block_k, causal)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_len=kv_len),
        name="veles_attn_bwd_dq",
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, o, lse)

    q_map = _clamp_maps_dkv(block_q, block_k, causal)
    dk, dv_out = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_len=kv_len),
        name="veles_attn_bwd_dkv",
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, dv), q_map),
            pl.BlockSpec((1, block_q, dv), q_map),
            pl.BlockSpec((1, block_q, _LANES), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, o, lse)
    return dq, dk, dv_out


_mha.defvjp(_mha_fwd, _mha_bwd)


def pallas_attention(q, k, v, causal=False, scale=None,
                     block_q=None, block_k=DEFAULT_BLOCK,
                     backend=None):
    """Exact attention via the native pallas kernels.  q/k/v:
    [batch, seq, heads, head_dim] (framework layout).  ANY sequence
    length runs the fast path (odd lengths pad-and-mask to block
    multiples in-kernel); head_dim should be a lane multiple for
    real-hardware performance.  Causally dead K/V blocks are never
    FETCHED (clamped index maps — pallas skips the DMA on a repeated
    block index), so long-context cost scales with the triangle, not
    the square.  ``backend`` is the platform of the TARGET device
    (see ops.common.use_interpret) — callers that know their device
    must pass it (ADVICE.md r4 #1)."""
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if block_q is None:
        block_q = DEFAULT_BLOCK_Q
    bq = min(block_q, max(sq, 16))
    bk = min(block_k, max(sk, 16))
    # pad-and-mask (VERDICT r4 #7): odd sequence lengths keep the
    # fast path — Q/K/V zero-pad up to block multiples, the kernels
    # mask tail K columns via kv_len, and the output slices back.
    # Zero-padded Q rows produce garbage outputs that are sliced
    # away, and their backward contributions vanish because the
    # padded cotangent rows are zero.
    sq_p = -(-sq // bq) * bq
    sk_p = -(-sk // bk) * bk

    def flat(t, seq_to):
        t = jnp.swapaxes(t, 1, 2).reshape(b * h, t.shape[1],
                                          t.shape[3])
        if t.shape[1] != seq_to:
            t = jnp.pad(t, ((0, 0), (0, seq_to - t.shape[1]), (0, 0)))
        return t

    o = _mha(flat(q, sq_p), flat(k, sk_p), flat(v, sk_p),
             float(scale), bool(causal), bq, bk,
             _use_interpret(backend), sk)
    o = o[:, :sq]
    return jnp.swapaxes(o.reshape(b, h, sq, dv), 1, 2)
