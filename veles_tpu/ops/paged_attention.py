"""Block-table (paged) decode attention — the serving-side attention
core over a PagedAttention-style KV layout (Kwon et al., SOSP 2023).

K/V live in per-layer POOLS of fixed-size blocks
(``[num_blocks, block_size, d]``); a request owns a *block table* — the
ordered list of physical block ids holding its sequence — instead of a
dense ``[window, d]`` row.  The decode step then

- **scatters** the new token's K/V into ``table[pos // bs]`` at row
  ``pos % bs`` (each live block belongs to exactly ONE slot, so the
  scatter never races another request), and
- **gathers** only the table's blocks — ``[B, T·bs, d]`` where ``T``
  is the caller's *block bucket* (power-of-two over the deepest active
  slot), not the full window — before the usual masked softmax.

Table entries past a slot's live blocks point at physical block 0 (the
reserved TRASH block — never allocated to a request), so the gather
reads garbage that the causal mask (`key ≤ pos`) zeroes exactly:
``softmax`` turns the ``-inf`` scores into probability 0.0, and
``0.0 · v`` contributes nothing for any finite v (pools start zeroed
and only ever receive finite projections).  Padding rows of an
occupancy bucket follow the same convention: an all-zero table writes
into and reads from the trash block.

The math is row-for-row the kv-cached scalar step
(``TransformerBlock.apply_step``) restricted to the gathered
key range — same projection dtypes, 1/sqrt(hd) scale and ONE score
convention, :func:`grouped_attend`'s: operands and probabilities in
the compute dtype, float32 scores, softmax and sums — so token
streams are ``generate()``'s (tests/test_serving.py in float32,
tests/test_serving_weights.py in bfloat16).  A decode step's ONE
query a row goes through :func:`single_query_attend` where every
query head has its own K/V head (the transformer block, the looped
stack), through :func:`grouped_attend` where fewer do (LFM2).  The
width-K cousin :func:`paged_verify_attention` scores a run of K1
consecutive tokens per row in one pass — the speculative-decoding
verify step (tests/test_spec.py proves spec-on/spec-off token parity,
in float32 and in bfloat16).  These jnp formulations lower
to a gather + GEMMs on every backend; the fused pallas kernel
(``ops/pallas_paged.py`` — gathered blocks stay in VMEM, dequant
fused for int8 pools) slots in behind the same signatures on
accelerator targets, the way ``ops/flash.py`` fronts the training
attention.  The ``*_q8`` variants below serve INT8 pools (per-row
scales beside the blocks; see serving/kv_slots.PagedKVCache), and
``paged_verify_attention_fused`` is the single-pass verify that
keeps the run's K/V out of the pool round-trip.

Tensor-parallel serving (serving/tp.py) runs these same functions
SPMD with the pools sharded HEAD-WISE over the ``tp`` mesh axis
(``[num_blocks, block_size, d/tp]`` per chip): the scatter, block
gather, per-head attention and the int8 per-row amax all partition
over the feature axis without code changes here — GSPMD keeps each
head's Q·K/probs·V chip-local (tp divides heads, so the
``[..., h, hd]`` reshape lands on whole heads), and only the output
projection downstream reduces across chips.  The one-query decode
form has no head axis for the partitioner to follow, so a tp step
runs it per shard (``ServingTP.decode_attention``).  The int8 scales
stay replicated: their amax over the sharded axis reduces exactly, so
the quantized pool bytes are bit-identical to an unsharded pool's.
"""

import jax
import jax.numpy as jnp

#: symmetric int8 quantization range — the KV pools store
#: round(x / scale) with scale = rowmax(|x|) / 127, one f32 scale per
#: (block, row) living beside the pools, so every token row
#: round-trips within amax/254 per element and the trash block's
#: all-zero rows dequantize to exactly 0.0 (the masked-garbage-is-
#: finite invariant the fp32 path already relies on)
INT8_QMAX = 127.0


def quantize_kv_rows(x):
    """Per-row symmetric int8 quantization of K/V rows ``x``
    [..., d]: returns ``(q, scale)`` with ``q`` int8 [..., d] and
    ``scale`` f32 [...] such that ``q * scale ~= x`` (absmax scaling;
    an all-zero row gets scale 0 and dequantizes to exact zeros)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / INT8_QMAX
    q = jnp.where(scale[..., None] > 0.0,
                  xf / jnp.maximum(scale[..., None], 1e-30), 0.0)
    q = jnp.clip(jnp.round(q), -INT8_QMAX, INT8_QMAX)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv_rows`: ``q`` int8 [..., d],
    ``scale`` [...] → [..., d] in ``dtype``."""
    return (q.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(dtype)


def paged_verify_attention(q, k_new, v_new, pool_k, pool_v, tables,
                           pos, lens, heads):
    """Score a WIDTH-K token run per row against a paged KV pool —
    the speculative-decoding verify kernel (one model pass scores a
    request's pending token plus its k drafted tokens).

    ``q``/``k_new``/``v_new`` [B, K1, d] — projections of the run,
    row n's position j sitting at sequence index ``pos[n] + j``;
    ``lens`` [B] ints (traced) — how many of the K1 positions are
    REAL for each row (1 = plain decode, k_eff + 1 for a row with
    k_eff drafts).  K/V of positions past ``lens[n]`` scatter into
    the reserved trash block (id 0) instead of the table, so bucket
    padding never corrupts a live block; their output rows are
    garbage the caller must not read.

    Position-for-position the arithmetic of
    :func:`paged_decode_attention` (one query a row, ``lens`` = 1,
    spelt as two plain products there): scatter first, then gather
    the table's blocks, causal mask ``key ≤ pos[n] + j`` per query,
    :func:`grouped_attend`'s conventions (operands and probabilities
    in the compute dtype, float32 scores, softmax and sums).  Because
    the scatter lands before the gather, a query at position p sees
    the drafts at positions ≤ p written THIS pass — exactly the cache
    state a sequential per-token decode of those tokens would have
    produced.

    Returns ``(pool_k', pool_v', context)`` with context [B, K1, d]
    float32."""
    b, k1, d = q.shape
    h = heads
    hd = d // h
    bs = pool_k.shape[1]
    qpos = pos[:, None] + jnp.arange(k1)[None, :]          # [B, K1]
    valid = jnp.arange(k1)[None, :] < lens[:, None]        # [B, K1]
    blk = jnp.take_along_axis(tables, qpos // bs, axis=1)
    blk = jnp.where(valid, blk, 0)                         # pad -> trash
    off = jnp.where(valid, qpos % bs, 0)
    pk = pool_k.at[blk, off].set(k_new.astype(pool_k.dtype))
    pv = pool_v.at[blk, off].set(v_new.astype(pool_v.dtype))
    rows = tables.shape[1] * bs
    return pk, pv, grouped_attend(
        q.reshape(b, k1, h, hd), pk[tables].reshape(b, rows, d),
        pv[tables].reshape(b, rows, d), qpos, h)


def grouped_attend(q, keys, values, qpos, kv_heads):
    """Causal attention of ``q`` [b, s, heads, hd] at positions ``qpos``
    [b, s] over ``keys``/``values`` [b, L, kv_heads * hd], key row i at
    position i: KV head j serves query heads j*g ... j*g + g - 1
    (``g = heads // kv_heads``).  Scores and softmax in float32, the
    two products on compute-dtype operands.  Rows past a query's
    position are masked, so what they hold (zeros of a staging row,
    the trash block's garbage) never counts.  -> [b, s, heads * hd]
    float32."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    b, s, heads, hd = q.shape
    length = keys.shape[1]
    qg = q.astype(cd).reshape(b, s, kv_heads, heads // kv_heads, hd)
    kh = keys.astype(cd).reshape(b, length, kv_heads, hd)
    vh = values.astype(cd).reshape(b, length, kv_heads, hd)
    scores = jnp.einsum("bqjgd,bkjd->bjgqk", qg, kh,
                        preferred_element_type=jnp.float32) \
        * (1.0 / jnp.sqrt(jnp.float32(hd)))
    mask = jnp.arange(length)[None, None, :] <= qpos[:, :, None]
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cd)
    return jnp.einsum("bjgqk,bkjd->bqjgd", probs, vh,
                      preferred_element_type=jnp.float32).reshape(
                          b, s, heads * hd)


def staged_chunk_attend(q, k_new, v_new, cache, offset, chunk_lens,
                        key_width, kv_heads, by_head=False):
    """A prefill chunk's grouped attention over its staging rows: ``q``
    [b, C, heads, hd] and the chunk's new ``k_new``/``v_new``
    [b, C, kv_heads * hd] at positions [offset, offset + C) continuing
    ``cache`` = {"k", "v"} [b, W, kv_heads * hd].  The new rows are
    written at ``offset`` (those at or past a row's ``chunk_lens``
    zeroed, as a one-shot prefill's ragged rows) and the queries attend
    causally over the first ``key_width`` (default W) rows.
    ``by_head``: through :func:`positioned_attend` one K/V head at a
    time (the same arithmetic, a head's float32 scores alive at once).
    -> (context [b, C, heads * hd] float32, {"k", "v"})."""
    b, c = q.shape[:2]
    positions = offset + jnp.arange(c)[None, :] \
        + jnp.zeros((b, 1), jnp.int32)
    if chunk_lens is not None:
        keep = (jnp.arange(c)[None, :] < chunk_lens[:, None])[..., None]
        k_new = jnp.where(keep, k_new, 0)
        v_new = jnp.where(keep, v_new, 0)
    at = (jnp.int32(0), offset, jnp.int32(0))
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), at)
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), at)
    kw = int(key_width or ck.shape[1])
    if by_head:
        return positioned_attend(
            q, ck[:, :kw], cv[:, :kw], positions,
            jnp.broadcast_to(jnp.arange(kw), (b, kw)), kv_heads,
            by_head=True), {"k": ck, "v": cv}
    return grouped_attend(q, ck[:, :kw], cv[:, :kw], positions,
                          kv_heads), {"k": ck, "v": cv}


def single_query_attend(q, keys, values, pos):
    """Causal attention of ONE query a row over full heads: ``q``
    [b, heads, hd] at position ``pos`` [b] over ``keys``/``values``
    [b, L, heads * hd], key row i at position i.  The gathered rows
    stay ``[L, heads * hd]`` matrices, as the pools hold them: the
    scores are ``keys @ Q`` with Q [heads * hd, heads] holding head
    h's query in its own rows of column h and zeros elsewhere, and the
    context is row h's own columns of ``probs[heads, L] @ values``.
    Both are plain products over rows read once (a batched einsum
    with the heads as a batch axis, at one query a head, makes the
    compiler relay the gathered rows head by head in HBM first, and
    widen them where the sums are float32); the zeros add
    nothing, so the arithmetic is :func:`grouped_attend`'s: operands
    and probabilities in the compute dtype, float32 sums and softmax.
    Every full-head chain's decode step takes it, whole or per tp
    shard (:func:`paged_decode_attention`).
    -> [b, 1, heads * hd] float32."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    b, heads, hd = q.shape
    d = heads * hd
    length = keys.shape[1]
    own = jnp.arange(d)[:, None] // hd == jnp.arange(heads)[None, :]
    by_head = jnp.where(own[None], q.reshape(b, d, 1), 0).astype(cd)
    scores = jnp.einsum("bld,bdh->blh", keys.astype(cd), by_head,
                        precision=dtypes.matmul_precision(),
                        preferred_element_type=jnp.float32) \
        * (1.0 / jnp.sqrt(jnp.float32(hd)))
    mask = jnp.arange(length)[None, :] <= pos[:, None]
    scores = jnp.where(mask[..., None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=1).astype(cd)
    full = jnp.einsum("blh,bld->bhd", probs, values.astype(cd),
                      precision=dtypes.matmul_precision(),
                      preferred_element_type=jnp.float32)
    return jnp.where(own.T[None], full, 0).sum(axis=1).reshape(b, 1, d)


def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, tables,
                           pos, heads, kv_heads=None):
    """One decode position per row against a paged KV pool.

    ``q``/``k_new``/``v_new`` [B, 1, d] — the new token's projections
    (row n at ITS OWN sequence index ``pos[n]``); ``pool_k``/``pool_v``
    [num_blocks, block_size, d]; ``tables`` [B, T] physical block ids
    in sequence order (T·block_size must cover ``max(pos) + 1``);
    ``pos`` [B] ints, traced.

    Returns ``(pool_k', pool_v', context)`` — the pools with the new
    K/V scattered in, and the attention context [B, 1, d] float32
    (operands and probabilities in the compute dtype, float32 scores,
    softmax and sums).

    ``kv_heads`` (default None: every query head has its own): the
    pools hold ``kv_heads`` heads a row (``[blocks, bs, kv_heads·hd]``)
    under ``heads`` query heads.  Full heads (None or ``== heads``)
    attend through :func:`single_query_attend`, fewer through
    :func:`grouped_attend`."""
    b, _, d = q.shape
    h = heads
    hd = d // h
    bs = pool_k.shape[1]
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    pk = pool_k.at[blk, off].set(k_new[:, 0].astype(pool_k.dtype))
    pv = pool_v.at[blk, off].set(v_new[:, 0].astype(pool_v.dtype))
    rows = tables.shape[1] * bs
    # the scope names the gather and the two products in each op's
    # metadata (XLA names the fusions themselves, so a trace's event
    # NAMES need not carry it: PERF.md, Open questions); the gather
    # takes ONLY the table's blocks — [B, T, bs, d] -> [B, T·bs, d]:
    # the window never materializes
    with jax.named_scope("veles_paged_decode_attention"):
        if kv_heads in (None, heads):
            return pk, pv, single_query_attend(
                q.reshape(b, h, hd), pk[tables].reshape(b, rows, -1),
                pv[tables].reshape(b, rows, -1), pos)
        return pk, pv, grouped_attend(
            q.reshape(b, 1, h, hd), pk[tables].reshape(b, rows, -1),
            pv[tables].reshape(b, rows, -1), pos[:, None], kv_heads)


# -- sliding-window rows in a per-slot ring -----------------------------------
#
# A window layer's query at position p sees the keys at positions
# (p - window, p].  Its cache is a RING a slot, ``[slots, window, d]``:
# the key of position p lives at row p mod window, so a slot never needs
# more rows, whatever the length of its request.  Which position a row
# holds follows from the newest position written alone
# (:func:`ring_positions`); a row that no position of the current
# occupant reached yet reads as a negative position and is masked, so a
# ring is never zeroed between occupants.

#: the score of a key that is not visible: finite, so that a padding
#: query that sees no key at all takes a finite mean instead of 0/0;
#: beside any visible score its exponential is exactly 0
_HIDDEN = -1e30


def ring_positions(last, width, window):
    """[b, width]: the position ring row r in [0, width) holds when
    ``last`` [b] is the newest position written (-1: none yet):
    ``last - ((last - r) mod window)``, negative for a row not written."""
    r = jnp.arange(width)
    return last[:, None] - jnp.mod(last[:, None] - r[None, :], window)


def positioned_attend(q, keys, values, qpos, kpos, kv_heads, window=None,
                      by_head=False):
    """:func:`grouped_attend`'s arithmetic with every key's position
    given: ``q`` [b, s, heads, hd] at ``qpos`` [b, s] over ``keys`` /
    ``values`` [b, L, kv_heads * hd] at ``kpos`` [b, L] (negative: no
    key there).  A key is visible when ``0 <= kpos <= qpos`` and, with
    ``window``, ``qpos - kpos < window``.  ``by_head``: one K/V head at a
    time (``lax.map``), so a wide chunk's float32 scores are
    [b, g, s, L] at a time and not ``kv_heads`` times that.
    -> [b, s, heads * hd] float32."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    b, s, heads, hd = q.shape
    length = keys.shape[1]
    g = heads // kv_heads
    seen = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[..., None])
    if window is not None:
        seen = seen & (qpos[..., None] - kpos[:, None, :] < window)
    qg = q.astype(cd).reshape(b, s, kv_heads, g, hd)
    kh = keys.astype(cd).reshape(b, length, kv_heads, hd)
    vh = values.astype(cd).reshape(b, length, kv_heads, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def head(qj, kj, vj):
        # qj [b, s, g, hd], kj/vj [b, L, hd] -> [b, s, g, hd]
        scores = jnp.einsum("bqgd,bkd->bgqk", qj, kj,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(seen[:, None], scores, _HIDDEN)
        probs = jax.nn.softmax(scores, axis=-1).astype(cd)
        return jnp.einsum("bgqk,bkd->bqgd", probs, vj,
                          preferred_element_type=jnp.float32)
    if by_head:
        out = jax.lax.map(lambda a: head(*a), (
            jnp.moveaxis(qg, 2, 0), jnp.moveaxis(kh, 2, 0),
            jnp.moveaxis(vh, 2, 0)))
        return jnp.moveaxis(out, 0, 2).reshape(b, s, heads * hd)
    scores = jnp.einsum("bqjgd,bkjd->bjgqk", qg, kh,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(seen[:, None, None], scores, _HIDDEN)
    probs = jax.nn.softmax(scores, axis=-1).astype(cd)
    return jnp.einsum("bjgqk,bkjd->bqjgd", probs, vh,
                      preferred_element_type=jnp.float32).reshape(
                          b, s, heads * hd)


def ring_chunk_attend(q, k_new, v_new, ring, offset, chunk_lens,
                      kv_heads):
    """A prefill chunk's sliding-window attention over a ring: ``q``
    [b, C, heads, hd] and the chunk's ``k_new``/``v_new``
    [b, C, kv_heads * hd] at positions [offset, offset + C) continuing
    ``ring`` = {"k", "v"} [b, window, kv_heads * hd], which holds the
    positions before ``offset``.  The queries attend over the ring's
    rows and the chunk's own keys (those at or past a row's
    ``chunk_lens`` left out), each over its last ``window`` positions
    alone; then the chunk's keys up to ``chunk_lens`` take their rows.
    -> (context [b, C, heads * hd] float32, {"k", "v"})."""
    from veles_tpu import dtypes
    b, c = q.shape[:2]
    window = ring["k"].shape[1]
    lens = jnp.full((b,), c, jnp.int32) if chunk_lens is None \
        else chunk_lens
    qpos = offset + jnp.arange(c)[None, :] + jnp.zeros((b, 1), jnp.int32)
    own = jnp.where(jnp.arange(c)[None, :] < lens[:, None], qpos, -1)
    held = ring_positions(jnp.full((b,), offset - 1), window, window)
    cd = dtypes.compute_dtype()
    keys = jnp.concatenate([ring["k"].astype(cd), k_new.astype(cd)], 1)
    values = jnp.concatenate([ring["v"].astype(cd), v_new.astype(cd)], 1)
    ctx = positioned_attend(q, keys, values, qpos,
                            jnp.concatenate([held, own], axis=1),
                            kv_heads, window=window, by_head=True)
    # each ring row takes the newest of the chunk's kept positions that
    # falls on it, or keeps what it holds
    src = ring_positions(offset + lens - 1, window, window) - offset
    fresh = (src >= 0)[..., None]
    idx = jnp.clip(src, 0, c - 1)[..., None]

    def put(old, new):
        return jnp.where(fresh, jnp.take_along_axis(
            new.astype(old.dtype), idx, axis=1), old)
    return ctx, {"k": put(ring["k"], k_new), "v": put(ring["v"], v_new)}


def ring_decode_attention(q, k_new, v_new, ring_k, ring_v, rows, pos,
                          kv_heads, width):
    """One decode position a row over a window layer's rings: ``q``
    [B, heads, hd], ``k_new``/``v_new`` [B, kv_heads * hd] of row n at
    ``pos[n]``, its ring ``rows[n]`` of ``ring_k``/``ring_v``
    [slots, window, kv_heads * hd] (a padding row's: the trash ring).
    The new key takes row ``pos mod window``; then the first ``width``
    rows (static: the step's depth bucket in positions, at most
    ``window``; a position the bucket covers lies in them) are read,
    each at the position it holds.
    -> (ring_k', ring_v', context [B, 1, heads * hd] float32)."""
    b, heads, hd = q.shape
    at = pos % ring_k.shape[1]
    rk = ring_k.at[rows, at].set(k_new.astype(ring_k.dtype))
    rv = ring_v.at[rows, at].set(v_new.astype(ring_v.dtype))
    held = ring_positions(pos, width, ring_k.shape[1])
    return rk, rv, positioned_attend(
        q[:, None], rk[rows, :width], rv[rows, :width], pos[:, None],
        held, kv_heads)


# -- latent (MLA) rows -------------------------------------------------------
#
# A latent layer caches ONE row a token, ``[c_kv | k_pe]`` (C + R wide:
# the compressed key-value latent and the rotary key shared by every
# head), in pools ``[num_blocks, block_size, C + R]`` behind the same
# block tables.  A prefill chunk expands the rows it attends into every
# head's key and value through ``W_ukv``; a decode step reads only the
# latent rows, its queries absorbed (``q_nope W_uk^T``), and multiplies
# the latent context by ``W_uv`` afterwards.  The two compute the same
# scores: q_nope . (c_kv W_uk) = (q_nope W_uk^T) . c_kv.

def latent_chunk_attend(q_nope, q_pe, latent_new, cache, offset,
                        chunk_lens, key_width, w_ukv, scale):
    """A prefill chunk's latent attention over its staging rows, in
    EXPANDED form: ``q_nope`` [b, C, H, N] and ``q_pe`` [b, C, H, R] at
    positions [offset, offset + C); the chunk's new latent rows
    ``latent_new`` [b, C, L + R] (L the latent width) continuing
    ``cache`` = {"latent": [b, W, L + R]}.  The new rows are written at
    ``offset`` (those at or past a row's ``chunk_lens`` zeroed) and the
    first ``key_width`` rows, as the cache holds them, are expanded
    through ``w_ukv`` [L, H * (N + V)] into every head's key and value;
    causal scores ``(q_nope.k_nope + q_pe.k_pe) * scale``, softmax and
    sums in float32 over compute-dtype operands.
    -> (context [b, C, H, V] float32, {"latent"})."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    b, c, heads, nope = q_nope.shape
    lat = w_ukv.shape[0]
    if chunk_lens is not None:
        keep = (jnp.arange(c)[None, :] < chunk_lens[:, None])[..., None]
        latent_new = jnp.where(keep, latent_new, 0)
    rows = jax.lax.dynamic_update_slice(
        cache["latent"], latent_new.astype(cache["latent"].dtype),
        (jnp.int32(0), offset, jnp.int32(0)))
    kw = int(key_width or rows.shape[1])
    seen = rows[:, :kw].astype(cd)
    expanded = jnp.einsum(
        "bkl,ln->bkn", seen[..., :lat], w_ukv.astype(cd),
        precision=dtypes.matmul_precision(),
        preferred_element_type=jnp.float32).reshape(b, kw, heads, -1)
    k_nope = expanded[..., :nope].astype(cd)
    values = expanded[..., nope:].astype(cd)
    scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope.astype(cd), k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe.astype(cd),
                           seen[..., lat:],
                           preferred_element_type=jnp.float32)) * scale
    qpos = offset + jnp.arange(c)
    mask = jnp.arange(kw)[None, :] <= qpos[:, None]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cd)
    return jnp.einsum("bhqk,bkhv->bqhv", probs, values,
                      preferred_element_type=jnp.float32), \
        {"latent": rows}


def latent_decode_attend(q, pool, tables, lens, scale, value_width):
    """The jnp form of ``ops/pallas_latent.pallas_latent_attend`` (the
    CPU's, and the parity tests'): the table's blocks gathered
    ``[B, T * bs, W]``, scores of ``q`` [B, H, W] over them masked past
    each row's ``lens``, softmax and sums in float32 over compute-dtype
    operands.  -> latent context [B, H, value_width] float32."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    b = q.shape[0]
    rows = pool[tables].reshape(b, -1, pool.shape[-1]).astype(cd)
    scores = jnp.einsum("bhw,blw->bhl", q.astype(cd), rows,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] < lens[:, None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf),
                           axis=-1).astype(cd)
    return jnp.einsum("bhl,blv->bhv", probs, rows[..., :value_width],
                      preferred_element_type=jnp.float32)


def paged_latent_attention(q_nope, q_pe, latent_new, pool, tables, pos,
                           w_ukv, scale):
    """One decode position a row against a paged pool of latent rows,
    in ABSORBED form: ``q_nope`` [B, H, N], ``q_pe`` [B, H, R] and the
    new token's latent row ``latent_new`` [B, L + R] (row n at
    ``pos[n]``); ``pool`` [num_blocks, bs, L + R]; ``tables`` [B, T];
    ``w_ukv`` [L, H * (N + V)].  The new row is scattered in first; the
    queries are absorbed, ``[q_nope W_uk^T | q_pe]`` [B, H, L + R], and
    attend over rows [0, pos] of their table (the Pallas call
    ``veles_mla_decode`` on an accelerator, :func:`latent_decode_attend`
    elsewhere); the latent context times ``W_uv`` is the head's.
    -> (pool', context [B, H, V] float32)."""
    from veles_tpu import dtypes
    from veles_tpu.ops.common import use_interpret
    cd = dtypes.compute_dtype()
    b, heads, nope = q_nope.shape
    lat = w_ukv.shape[0]
    bs = pool.shape[1]
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    pool = pool.at[blk, pos % bs].set(latent_new.astype(pool.dtype))
    w = w_ukv.astype(cd).reshape(lat, heads, -1)
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope.astype(cd), w[..., :nope],
                       precision=dtypes.matmul_precision(),
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_pe], axis=-1).astype(pool.dtype)
    if use_interpret():
        ctx = latent_decode_attend(q, pool, tables, pos + 1, scale, lat)
    else:
        from veles_tpu.ops.pallas_latent import pallas_latent_attend
        ctx = pallas_latent_attend(q, pool, tables, pos + 1, scale, lat)
    return pool, jnp.einsum("bhl,lhv->bhv", ctx.astype(cd), w[..., nope:],
                            precision=dtypes.matmul_precision(),
                            preferred_element_type=jnp.float32)


# -- int8 quantized pools ---------------------------------------------------
#
# Same math as the fp32 paths above with TWO twists: the new token's
# K/V rows quantize ON the scatter (per-row absmax scale stored at the
# same [block, row] coordinates, so scales follow blocks through every
# donate/evict/gather move by construction), and the gather
# dequantizes into the compute dtype before the usual masked softmax
# (fp32 accumulation unchanged).  On an accelerator target the gather
# + dequant + attend runs as the fused pallas kernel
# (ops/pallas_paged.py) instead of materializing the [B, T·bs, d]
# dequantized gather.

def _q8_ctx(q, pk, pv, sk, sv, tables, qpos, heads, backend):
    """Shared gather→dequant→attend tail of the q8 decode/verify
    paths: queries [B, K1, d] at positions ``qpos`` [B, K1], causal
    mask ``key <= qpos`` per query."""
    from veles_tpu import dtypes
    from veles_tpu.ops.common import use_interpret
    if not use_interpret(backend):
        from veles_tpu.ops.pallas_paged import pallas_paged_attend
        return pallas_paged_attend(q, pk, pv, tables, qpos, heads,
                                   scale_k=sk, scale_v=sv,
                                   backend=backend)
    cd = dtypes.compute_dtype()
    b, k1, d = q.shape
    h = heads
    hd = d // h
    bs = pk.shape[1]
    kg = dequantize_kv(pk[tables], sk[tables], cd)
    vg = dequantize_kv(pv[tables], sv[tables], cd)
    length = kg.shape[1] * bs
    qh = q.reshape(b, k1, h, hd)
    kh = kg.reshape(b, length, h, hd)
    vh = vg.reshape(b, length, h, hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) \
        * (1.0 / jnp.sqrt(hd))
    mask = (jnp.arange(length)[None, None, :]
            <= qpos[:, :, None])[:, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vh).reshape(b, k1, d)


def paged_decode_attention_q8(q, k_new, v_new, pool_k, pool_v,
                              scale_k, scale_v, tables, pos, heads,
                              backend=None):
    """:func:`paged_decode_attention` over INT8 pools: the new
    token's K/V quantize on the scatter (scale written beside them at
    ``scale[blk, off]``), the gather dequantizes block rows with
    their scales, attention accumulates in f32.  ``scale_k`` /
    ``scale_v`` [num_blocks, block_size] f32 ride beside the pools.

    Returns ``(pool_k', pool_v', scale_k', scale_v', context)``."""
    bs = pool_k.shape[1]
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None],
                              axis=1)[:, 0]
    off = pos % bs
    qk, sk_new = quantize_kv_rows(k_new[:, 0])
    qv, sv_new = quantize_kv_rows(v_new[:, 0])
    pk = pool_k.at[blk, off].set(qk)
    pv = pool_v.at[blk, off].set(qv)
    sk = scale_k.at[blk, off].set(sk_new)
    sv = scale_v.at[blk, off].set(sv_new)
    ctx = _q8_ctx(q, pk, pv, sk, sv, tables, pos[:, None], heads,
                  backend)
    return pk, pv, sk, sv, ctx


def paged_verify_attention_q8(q, k_new, v_new, pool_k, pool_v,
                              scale_k, scale_v, tables, pos, lens,
                              heads, backend=None):
    """:func:`paged_verify_attention` over INT8 pools — the fused
    speculative-verify path: ONE quantizing scatter of the width-K1
    run (padding past ``lens`` lands in the trash block, scale
    included), then ONE gather→dequant→attend pass (the pallas kernel
    on accelerator targets).  In-pass keys read back QUANTIZED —
    verify sees exactly the cache state later decode steps will read,
    which is what the quality gate measures.

    Returns ``(pool_k', pool_v', scale_k', scale_v', context)``."""
    b, k1, d = q.shape
    bs = pool_k.shape[1]
    qpos = pos[:, None] + jnp.arange(k1)[None, :]          # [B, K1]
    valid = jnp.arange(k1)[None, :] < lens[:, None]        # [B, K1]
    blk = jnp.take_along_axis(tables, qpos // bs, axis=1)
    blk = jnp.where(valid, blk, 0)                         # pad -> trash
    off = jnp.where(valid, qpos % bs, 0)
    qk, sk_new = quantize_kv_rows(k_new)
    qv, sv_new = quantize_kv_rows(v_new)
    pk = pool_k.at[blk, off].set(qk)
    pv = pool_v.at[blk, off].set(qv)
    sk = scale_k.at[blk, off].set(sk_new)
    sv = scale_v.at[blk, off].set(sv_new)
    ctx = _q8_ctx(q, pk, pv, sk, sv, tables, qpos, heads, backend)
    return pk, pv, sk, sv, ctx


def paged_verify_attention_fused(q, k_new, v_new, pool_k, pool_v,
                                 tables, pos, lens, heads,
                                 backend=None):
    """Single-pass fp32 verify.  The two-pass path scatters the
    run's K/V into the POOL and then gathers it back out before
    attending — the attention waits on a write to the
    multi-megabyte pool just to read back the handful of rows it
    wrote.  Here the gather reads the PRE-scatter pool and the run's
    rows are scattered into the small GATHERED buffer instead
    ([B, T·bs, d] — the write is O(batch·k), not O(pool)), which takes
    the pool update off the attention's critical path.

    Every verify step takes the pools donated (serving/engine.py).
    Reading the pre-scatter pool while the same donated buffer is
    scattered into makes XLA keep the old values by COPYING the pool
    (compiled for the CPU: two whole-pool copies a pool, where the
    two-pass path has none), so of the jnp branches the two-pass one
    is the in-place one; the accelerator branch below reads the
    POST-scatter pool and has no such read.

    The gathered buffer ends up elementwise IDENTICAL to the
    two-pass gather at every causally-visible position, and the
    attention subgraph has the same shapes and ops — valid output
    rows are bit-identical to :func:`paged_verify_attention`
    (rows past ``lens`` are garbage under both, as documented).

    On an accelerator target the gather+attend half runs as the
    fused pallas kernel instead (ops/pallas_paged.py), which also
    never materializes the gather.

    Returns ``(pool_k', pool_v', context)`` like the two-pass path."""
    from veles_tpu import dtypes
    from veles_tpu.ops.common import use_interpret
    cd = dtypes.compute_dtype()
    b, k1, d = q.shape
    h = heads
    hd = d // h
    bs = pool_k.shape[1]
    qpos = pos[:, None] + jnp.arange(k1)[None, :]          # [B, K1]
    valid = jnp.arange(k1)[None, :] < lens[:, None]        # [B, K1]
    blk = jnp.take_along_axis(tables, qpos // bs, axis=1)
    blk = jnp.where(valid, blk, 0)                         # pad -> trash
    off = jnp.where(valid, qpos % bs, 0)
    pk = pool_k.at[blk, off].set(k_new.astype(pool_k.dtype))
    pv = pool_v.at[blk, off].set(v_new.astype(pool_v.dtype))
    if not use_interpret(backend):
        # accelerator target: the fused pallas kernel attends over
        # the POST-scatter pool (same numerics as the two-pass jnp
        # path, without materializing the gather)
        from veles_tpu.ops.pallas_paged import pallas_paged_attend
        return pk, pv, pallas_paged_attend(q, pk, pv, tables, qpos,
                                           heads, backend=backend)
    kg = pool_k[tables].astype(cd)                # pre-scatter pools
    vg = pool_v[tables].astype(cd)
    length = kg.shape[1] * bs
    kg = kg.reshape(b, length, d)
    vg = vg.reshape(b, length, d)
    # the run's rows land in the GATHERED buffer — the same values
    # the two-pass gather reads back at these positions (per-row
    # qpos entries are distinct; positions past a row's len only
    # ever feed masked scores)
    rows = jnp.arange(b)[:, None]
    kg = kg.at[rows, qpos].set(k_new.astype(cd))
    vg = vg.at[rows, qpos].set(v_new.astype(cd))
    return pk, pv, grouped_attend(q.reshape(b, k1, h, hd), kg, vg,
                                  qpos, h)
