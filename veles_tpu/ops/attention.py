"""Attention ops — single-chip flash-style attention and RING attention
for sequence/context parallelism (SURVEY.md §5 "Long-context": the
reference had no sequence dimension at all; the rebuild makes the ``sp``
mesh axis first-class so long contexts shard like any other dim).

Ring attention: Q stays put, K/V blocks rotate around the ``sp`` axis
via ``ppermute`` (ICI neighbour exchange), with an online-softmax
accumulator (running max + normalizer) so the result is EXACTLY
softmax(QK^T/sqrt(d))V over the full sequence while each chip only ever
holds 1/sp of K/V — the standard blockwise/ring formulation."""

import functools

import jax
import jax.numpy as jnp


def _pvary(x, axis_name):
    """Mark a fresh (axis-invariant) value as varying over axis_name."""
    return jax.lax.pcast(x, axis_name, to="varying")


def attention(q, k, v, causal=False, scale=None):
    """Reference attention on one chip.  q/k/v: [..., seq, heads, dim]
    (seq-major layout keeps the sp sharding a leading-dim spec)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    # [..., heads, seq_q, seq_k]
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
    if causal:
        seq_q, seq_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((seq_q, seq_k), bool),
                        seq_k - seq_q)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def _block_contrib(q, k, v, scale, mask=None):
    """One K/V block's unnormalized contribution: (max, sumexp,
    weighted-V) per query."""
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                       # [..., h, q]
    # guard fully-masked rows (exp(-inf - -inf) = nan)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    s = jnp.sum(p, axis=-1)                            # [..., h, q]
    o = jnp.einsum("...hqk,...khd->...qhd", p, v)
    return m_safe, s, o


def _online_merge(acc, new):
    """Merge two partial softmax accumulators (the flash-attention
    update rule)."""
    m_a, s_a, o_a = acc
    m_b, s_b, o_b = new
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    s = s_a * ca + s_b * cb
    # coefficients are [..., h, q]; outputs are [..., q, h, d]
    o = o_a * jnp.moveaxis(ca, -2, -1)[..., None] \
        + o_b * jnp.moveaxis(cb, -2, -1)[..., None]
    return m, s, o


def blockwise_attention(q, k, v, block_size=512, causal=False,
                        scale=None):
    """Exact attention WITHOUT materializing the [seq_q, seq_k] score
    matrix: a ``lax.scan`` over K/V blocks with the same online-softmax
    accumulator the ring uses — the single-chip half of the long-context
    story (the ring shards across chips; this streams within one).

    q/k/v: [..., seq, heads, dim].  Peak memory is O(seq_q ·
    block_size) per head instead of O(seq_q · seq_k).  K/V sequence
    lengths that don't divide ``block_size`` are zero-padded and
    masked.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    seq_q = q.shape[-3]
    seq_k = k.shape[-3]
    bs = min(block_size, seq_k)
    pad = (-seq_k) % bs
    if pad:
        widths = [(0, 0)] * k.ndim
        widths[-3] = (0, pad)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    blocks = (seq_k + pad) // bs
    # [..., seq, h, d] -> [blocks, ..., bs, h, d] (scan axis leads)
    kb = jnp.moveaxis(
        k.reshape(k.shape[:-3] + (blocks, bs) + k.shape[-2:]), -4, 0)
    vb = jnp.moveaxis(
        v.reshape(v.shape[:-3] + (blocks, bs) + v.shape[-2:]), -4, 0)
    q_pos = jnp.arange(seq_q)

    def body(acc, blk):
        k_blk, v_blk, idx = blk
        if causal:
            k_pos = idx * bs + jnp.arange(bs)
            mask = (k_pos < seq_k)[None, None, :] & (
                k_pos[None, None, :] <=
                q_pos[None, :, None] + (seq_k - seq_q))
        elif pad:
            k_pos = idx * bs + jnp.arange(bs)
            mask = jnp.broadcast_to((k_pos < seq_k)[None, None, :],
                                    (1, seq_q, bs))
        else:
            mask = None  # unmasked hot path: no where/select traffic
        contrib = _block_contrib(q, k_blk, v_blk, scale, mask)
        # the running sum accumulates up to seq_k exp terms — carry it
        # in f32 even when activations are bf16 (the compounding merge
        # error would otherwise grow with sequence length)
        contrib = tuple(t.astype(jnp.float32) for t in contrib)
        return _online_merge(acc, contrib), None

    heads = q.shape[-2]
    batchish = q.shape[:-3]
    # the output inherits v's value dim (may differ from q/k's key dim)
    acc0 = (jnp.full(batchish + (heads, seq_q), -jnp.inf, jnp.float32),
            jnp.zeros(batchish + (heads, seq_q), jnp.float32),
            jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32))
    acc, _ = jax.lax.scan(body, acc0,
                          (kb, vb, jnp.arange(blocks)))
    m, s, o = acc
    denom = jnp.moveaxis(jnp.maximum(s, 1e-30), -2, -1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Attention with K/V sharded over the ``axis_name`` mesh axis.

    Call under ``shard_map`` with q/k/v sharded on their sequence dim
    over ``axis_name`` (layout [seq_shard, heads, dim] per device).
    K/V rotate through every device; the online-softmax accumulator
    makes the result exact.  ``causal`` masks by GLOBAL sequence
    position (each shard owns a contiguous sequence slice)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    seq_q = q.shape[-3]
    seq_k = k.shape[-3]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def mask_for(kv_idx):
        if not causal:
            return None
        q_pos = my_idx * seq_q + jnp.arange(seq_q)       # global rows
        k_pos = kv_idx * seq_k + jnp.arange(seq_k)
        return (k_pos[None, :] <= q_pos[:, None])[None]  # [1, q, k]

    def body(carry, _):
        acc, kv, kv_idx = carry
        k_blk, v_blk = kv
        contrib = _block_contrib(q, k_blk, v_blk, scale,
                                 mask_for(kv_idx))
        # f32 accumulator: see blockwise_attention
        contrib = tuple(t.astype(jnp.float32) for t in contrib)
        acc = _online_merge(acc, contrib)
        kv = jax.lax.ppermute(kv, axis_name, perm)
        kv_idx = jax.lax.ppermute(kv_idx, axis_name, perm)
        return (acc, kv, kv_idx), None

    # derive the accumulators FROM q so they inherit q's varying-axes
    # under shard_map (a dp x sp mesh makes the carry vary over BOTH
    # axes; a fresh jnp.zeros would be axis-invariant and trip the
    # scan carry vma check)
    hs0 = jnp.swapaxes(q, -3, -2)[..., 0].astype(jnp.float32) * 0
    m0 = hs0 - jnp.inf                     # [..., heads, seq_q]
    s0 = hs0
    # the output inherits v's value dim (may differ from q/k's key dim)
    o0 = q[..., :1].astype(jnp.float32) * jnp.zeros(
        (v.shape[-1],), jnp.float32)
    (acc, _, _), _ = jax.lax.scan(
        body, ((m0, s0, o0), (k, v), my_idx), None, length=n)
    m, s, o = acc
    denom = jnp.moveaxis(jnp.maximum(s, 1e-30), -2, -1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_attention_sharded(mesh, q, k, v, axis="sp", causal=False):
    """Convenience wrapper: shard q/k/v's sequence dim over ``axis`` and
    run :func:`ring_attention` under shard_map.  q/k/v: [seq, heads,
    dim] global arrays."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(axis, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
