"""The shared compiled decode steps over serving slots.

ONE executable serves every mix of in-flight requests: per-slot
positions (slots at different decode depths), per-slot sampler
settings (temperature / top-k ride as traced vectors), and
per-REQUEST PRNG streams (token ``t`` of a request with seed ``s`` is
drawn with ``fold_in(key(s), t)`` — reproducible per seed no matter
which slot the request landed in or what traffic it shared the batch
with).

ONE step family, over the block-paged cache (``apply_step_paged``
over a PagedKVCache): :func:`paged_decode_step`, and beside it the
speculative :func:`verify_step_paged`.  The scheduler PACKS only the
active slots into a power-of-two *occupancy bucket* ``B`` and bounds
the attended range by a power-of-two *block bucket* ``T`` over the
deepest active slot, so a half-empty batch of shallow requests pays
neither full-batch nor full-window compute.  Executables are cached
per (chain, B, T) — O(log slots · log window) variants.  Sampling is
row-wise (per-request keys), so token streams are independent of
packing order.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.models.generate import (
    _StepClosure, _arch_sig, _device_params)
from veles_tpu.telemetry import trace_named, track_jit


def sample_slots(logits, temps, topks, keys):
    """Per-slot next-token sampler: rows with ``temps[n] == 0`` take
    the greedy argmax; sampling rows draw categorical(logits / temp)
    restricted to each row's top-k (0 = full vocab; ties with the
    k-th value stay in, matching ``generate``'s masking).

    Only the work the rows ask for runs: the divide and the draw sit
    behind a ``lax.cond`` on "any row samples", the vocabulary sort
    and the k-th-value mask behind a second on "any sampling row has
    a top-k", so an all-greedy batch (padding rows carry temperature
    0) runs the argmax alone.  One program either way, and the
    tokens of every row mix are those of the unconditional form."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = temps > 0

    def top_k(z):
        v = z.shape[-1]
        zs = jnp.sort(z, axis=-1)
        kth = jnp.take_along_axis(
            zs, jnp.clip(v - topks, 0, v - 1)[:, None], axis=-1)
        return jnp.where((topks[:, None] > 0) & (z < kth), -jnp.inf, z)

    def draw():
        z = logits / jnp.maximum(temps, 1e-6)[:, None]
        z = jax.lax.cond(jnp.any(sampled & (topks > 0)), top_k,
                         lambda z: z, z)
        drawn = jax.vmap(jax.random.categorical)(keys, z)
        return jnp.where(sampled, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(sampled), draw, lambda: greedy)


def _fold_keys(seeds, counts):
    """Per-request stream keys: fold each request's draw counter into
    its seed-derived base key."""
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.key(s), c))(
            seeds, counts)


def sample_first(logits, temps, topks, seeds, counts):
    """Post-prefill token sampler over the last-position logits:
    draw ``counts[n]`` of each request's stream — 0 for a fresh
    admission, ``len(generated)`` for a preempted request resuming
    after a re-prefill of prompt + prefix (the SAME key fold the
    decode step would have used, so the resumed stream is
    bit-identical to the uninterrupted one)."""
    keys = _fold_keys(seeds, counts)
    return sample_slots(logits, temps, topks, keys)


_sample_first_jit = track_jit("serving.sample_first", jax.jit(
    trace_named("serving.sample_first", sample_first)))


# Every step below takes the cache's device state (its LAST argument)
# DONATED and returns it anew: the caller swaps the cache's attribute
# for what came back at once and nothing else holds the old leaves
# (the invariant is said in serving/kv_slots.py), so the scatter of a
# step's rows lands in place instead of in a copy of every pool;
# ``cache.note_swap`` counts a call whose input came back alive.

def clear_step_cache():
    """Drop the compiled step caches (entries pin the chain's
    units — same lifetime note as
    ``generate.clear_decode_caches``)."""
    _paged_step_cached.cache_clear()
    _paged_step_tp_cached.cache_clear()
    _verify_step_cached.cache_clear()


def hidden_supported(forwards):
    """True when the chain ends in a position-wise vocab head over a
    [batch, seq, d] hidden stream — the shape the optional
    hidden-state output lane (``want_hidden``) taps for the
    model-based draft head (serving/draft.py): the lane returns the
    input of the FINAL unit, i.e. the target's last hidden state."""
    if len(forwards) < 2:
        return False
    last = forwards[-1]
    return getattr(last, "DECODE_POINTWISE", False) \
        and not hasattr(last, "init_cache")


#: keys of the counts that ride the pools a paged step returns (ints
#: like the chain indices: a pytree's keys must sort), by the name a
#: unit leaves its own under: the routed layers', a looped stack's
STEP_COUNTS = {"moe": -1, "stack": -2}


def _make_paged_step(forwards, want_hidden=False, attend=None,
                     block_size=None):
    cacheable = frozenset(i for i, u in enumerate(forwards)
                          if hasattr(u, "init_cache"))
    # units that keep per-slot state or count live rows are told which
    # slot each packed row is (-1: a padding row)
    by_slot = frozenset(i for i in cacheable
                        if hasattr(forwards[i], "cache_kind"))
    # a sliding-window layer's ring is told the positions the step's
    # block bucket covers: it reads no more of its rows
    ringed = frozenset(i for i in by_slot
                       if forwards[i].cache_kind == "window")
    # a tp step hands the units that declare a tp layout its
    # per-shard attention (ServingTP.decode_attention)
    told = frozenset(i for i in cacheable if attend is not None
                     and hasattr(forwards[i], "tp_shardable"))
    last = len(forwards) - 1

    def step(params, toks, pos, tables, temps, topks, seeds, counts,
             slots, pools):
        h = toks[:, None]
        hid = None
        out = dict(pools)
        counted = {name: [] for name in STEP_COUNTS}
        for i, u in enumerate(forwards):
            if want_hidden and i == last:
                # the final unit's INPUT is the target's last hidden
                # state — what the draft head conditions on
                hid = h.astype(jnp.float32)
            if i in cacheable:
                h, out[i] = u.apply_step_paged(
                    params[i], h, pos, tables, pools[i],
                    **({"slots": slots,
                        "depth": tables.shape[1] * (block_size or 0)}
                       if i in ringed else
                       {"slots": slots} if i in by_slot else
                       {"attend": attend} if i in told else {}))
                for name, rows in counted.items():
                    if name in out[i]:
                        rows.append(out[i].pop(name))
            elif hasattr(u, "apply_step_slots"):
                h = u.apply_step_slots(params[i], h, pos)
            else:
                h = u.apply(params[i], h)
        logits = h[:, 0].astype(jnp.float32)
        keys = _fold_keys(seeds, counts)
        nxt = sample_slots(logits, temps, topks, keys)
        for name, rows in counted.items():
            if rows:   # ONE small array a step and kind: [layers, n]
                out[STEP_COUNTS[name]] = jnp.stack(rows)
        if want_hidden:
            return nxt, hid[:, 0], out
        return nxt, out
    return step


@functools.lru_cache(maxsize=64)
def _paged_step_cached(cache_key, closure):
    return track_jit("serving.paged_step", jax.jit(
        trace_named("serving.paged_step", closure.fn),
        donate_argnums=(9,)))


def overlap_supported(forwards):
    """True when every cacheable block in the chain speaks the
    per-shard decode body (``apply_step_paged_local``) the
    collective-overlap path is built from — the gate
    ``root.common.serving.tp_overlap`` checks before swapping the
    GSPMD step for the explicit shard_map one."""
    has = False
    for u in forwards:
        if hasattr(u, "init_cache"):
            has = True
            if not hasattr(u, "apply_step_paged_local"):
                return False
    return has


def _make_paged_step_tp(forwards, ctx, pools, want_hidden=False):
    """The EXPLICIT-collective tp decode step: the same math as
    :func:`_make_paged_step` under a tp mesh, but written per-shard
    through ``shard_map`` so each block's row-parallel reductions are
    explicit collective-permute / all-gather ops
    (serving/tp.tp_allreduce) instead of GSPMD-inserted all-reduces.
    Explicit collectives let the compiler START the cross-chip hop
    while the K/V pool writeback (data-independent of the reduction)
    proceeds — the overlap the serialized auto-partitioned step never
    gets.  tp=2 reduces by a single ppermute+add (bit-identical to
    psum: two-operand float addition is order-free), wider meshes
    all-gather and sum in fixed shard order (deterministic, same
    value on every shard)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    size = ctx.size
    cacheable = frozenset(i for i, u in enumerate(forwards)
                          if hasattr(u, "init_cache"))
    last = len(forwards) - 1
    pspecs = {}
    for i, u in enumerate(forwards):
        spec_fn = getattr(u, "tp_param_spec", None)
        layer = {}
        for name in u.param_arrays():
            spec = spec_fn(name, size) if spec_fn is not None \
                else None
            layer[name] = spec if spec is not None else P()
        pspecs[i] = layer
    lspecs = {}
    for i, layer in pools.items():
        lspecs[i] = {
            name: P(None, None, "tp")
            if not name.endswith("_scale") and a.ndim == 3
            and a.shape[-1] % size == 0 else P()
            for name, a in layer.items()}

    def body(params, toks, pos, tables, temps, topks, seeds, counts,
             slots, pools_):
        h = toks[:, None]
        hid = None
        out = dict(pools_)
        for i, u in enumerate(forwards):
            if want_hidden and i == last:
                hid = h.astype(jnp.float32)
            if i in cacheable:
                h, out[i] = u.apply_step_paged_local(
                    params[i], h, pos, tables, pools_[i], size)
            elif hasattr(u, "apply_step_slots"):
                h = u.apply_step_slots(params[i], h, pos)
            else:
                h = u.apply(params[i], h)
        logits = h[:, 0].astype(jnp.float32)
        keys = _fold_keys(seeds, counts)
        nxt = sample_slots(logits, temps, topks, keys)
        if want_hidden:
            return nxt, hid[:, 0], out
        return nxt, out

    rep = P()
    in_specs = (pspecs, rep, rep, rep, rep, rep, rep, rep, rep,
                lspecs)
    out_specs = (rep, rep, lspecs) if want_hidden else (rep, lspecs)
    return shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=32)
def _paged_step_tp_cached(cache_key, closure):
    return track_jit("serving.paged_step_tp", jax.jit(
        trace_named("serving.paged_step_tp", closure.fn),
        donate_argnums=(9,)))


def paged_decode_step(forwards, cache, toks, pos, tables, temps,
                      topks, seeds, counts, want_hidden=False,
                      params=None, slots=None, resolved=None):
    """Run ONE decode step over a PACKED batch of active slots
    against ``cache`` (:class:`serving.kv_slots.PagedKVCache`,
    updated in place).

    All arrays are packed to the caller's occupancy bucket ``B``
    (padding rows: token 0, position 0, an all-zero table — they
    write into and read from the reserved trash block): ``toks``,
    ``pos``/``temps``/``topks``/``seeds``/``counts`` [B],
    ``tables`` [B, T] physical block ids (T·block_size must cover
    ``max(pos) + 1``).  Returns the [B] next tokens, a DEVICE array
    that nothing has waited for; the caller maps packed rows back to
    its slots.  The step takes its tokens in the shape it hands them
    back, so ``toks`` may be the array the previous call returned,
    passed on before anyone has read it (the scheduler's launch-ahead:
    no transfer, no further dispatch); host tokens may also come as
    [B, 1].  ``want_hidden`` additionally
    returns the [B, d] f32 last hidden state (the final unit's
    input) — the model-based draft head's conditioning
    (serving/draft.py); the flag keys the executable cache, so
    hidden-on and hidden-off never share a trace.

    ``params`` — the chain's device parameters; a server passes its
    frozen :class:`serving.weights.ServingWeights` pytree, an offline
    caller none (the units' own float32 buffers).

    ``slots`` [B] — the slot of each packed row, -1 for a padding row
    (the default for every row): what a unit with per-slot state
    indexes its state pool by.  What the chain's units counted in this
    step is left in ``cache.step_counts``, one small device array a
    kind (``STEP_COUNTS``; a chain that counts nothing leaves it
    empty): ``"moe"`` the routed layers' int32 [layers, 4 or 5],
    ``"stack"`` a looped stack's float32 [stacks, 2 + passes] = (passes
    run, live rows, the live rows' exit mass of each pass).

    ``resolved`` — called once, with no arguments, when the compiled
    step is in hand and only its call is left (a caller that times the
    two halves of a launch apart; it keys no executable).

    A cache built with a tensor-parallel context (``cache.tp_`` —
    serving/tp.py) runs the step SPMD over the tp mesh: ``params`` ride
    pre-sharded Megatron-style (the server's ``ServingWeights`` placed
    them; required then), the pools head-wise, and the
    executable cache keys on the mesh size so tp on/off never share
    a trace.  With ``root.common.serving.tp_overlap`` set (and every
    cacheable block speaking the shard_map step — see
    ``overlap_supported``) the step compiles through the EXPLICIT
    collective path instead of GSPMD auto-insertion: per-shard block
    bodies combine their row-parallel partial sums with
    collective-permute / all-gather reductions the compiler can
    issue asynchronously, overlapping the cross-chip hop with the
    K/V pool writeback."""
    from veles_tpu import dtypes
    from veles_tpu.config import root
    ctx = cache.tp_
    if params is None:
        params = _device_params(forwards)
    tables = jnp.asarray(tables, jnp.int32)
    b, t = tables.shape
    if not isinstance(toks, jax.Array):
        toks = numpy.asarray(toks, numpy.int32).reshape(b)
        # placed as a step leaves its tokens: the executable cache
        # then holds one entry a bucket whichever way the tokens came,
        # and the first launch from device tokens in a bucket is no
        # compile.  A step over uncommitted parameters leaves them
        # uncommitted, as the plain upload below does
        if cache.token_sharding is not None:
            toks = jax.device_put(toks, cache.token_sharding)
    # fp32 pools only: the int8 pool's per-row amax must reduce over
    # the FULL feature axis (GSPMD does that collectively); a
    # per-shard body would compute shard-local scales
    overlap = bool(ctx is not None
                   and root.common.serving.get("tp_overlap", False)
                   and cache.kv_dtype == "fp32"
                   and overlap_supported(forwards))
    cache_key = (_arch_sig(forwards), b, t, cache.block_size,
                 cache.capacity_blocks, cache.kv_dtype,
                 ctx.size if ctx is not None else 1,
                 bool(want_hidden), overlap,
                 str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    if overlap:
        fn = _paged_step_tp_cached(
            cache_key, _StepClosure(_make_paged_step_tp(
                forwards, ctx, cache.pools,
                want_hidden=want_hidden)))
    else:
        fn = _paged_step_cached(
            cache_key, _StepClosure(_make_paged_step(
                forwards, want_hidden=want_hidden,
                attend=ctx.decode_attention if ctx is not None
                else None, block_size=cache.block_size)))
    if resolved is not None:
        resolved()
    old = cache.first_leaf()
    got = fn(
        params, jnp.asarray(toks, jnp.int32),
        jnp.asarray(pos, jnp.int32), tables,
        jnp.asarray(temps, jnp.float32),
        jnp.asarray(topks, jnp.int32),
        jnp.asarray(seeds, jnp.uint32),
        jnp.asarray(counts, jnp.int32),
        # host rows, handed over by the call itself: a chain that reads
        # none (jit prunes the argument) pays no transfer of its own
        numpy.full((b,), -1, numpy.int32) if slots is None
        else numpy.asarray(slots, numpy.int32), cache.pools)
    pools = got[-1]
    cache.step_counts = {name: pools.pop(key) for name, key
                         in STEP_COUNTS.items() if key in pools}
    cache.pools = pools
    cache.note_swap(old)
    cache.token_sharding = got[0].sharding if got[0].committed else None
    return (got[0], got[1]) if want_hidden else got[0]


def _make_verify_step(forwards, want_hidden=False):
    cacheable = frozenset(i for i, u in enumerate(forwards)
                          if hasattr(u, "init_cache"))
    last = len(forwards) - 1

    def step(params, toks, pos, lens, tables, temps, topks, seeds,
             counts, pools):
        h = toks
        hid = None
        out = dict(pools)
        for i, u in enumerate(forwards):
            if want_hidden and i == last:
                hid = h.astype(jnp.float32)
            if i in cacheable:
                h, out[i] = u.apply_verify_paged(
                    params[i], h, pos, lens, tables, pools[i])
            elif hasattr(u, "apply_verify_slots"):
                h = u.apply_verify_slots(params[i], h, pos)
            else:
                h = u.apply(params[i], h)
        b, k1, v = h.shape
        logits = h.astype(jnp.float32).reshape(b * k1, v)
        # position j of row n draws stream token counts[n] + j — the
        # EXACT key a sequential decode of the accepted prefix would
        # fold, which is what makes acceptance distribution-exact
        keys = jax.vmap(
            lambda s, c: jax.vmap(
                lambda j: jax.random.fold_in(jax.random.key(s),
                                             c + j))(jnp.arange(k1)))(
            seeds, counts)
        nxt = sample_slots(logits, jnp.repeat(temps, k1),
                           jnp.repeat(topks, k1),
                           keys.reshape(b * k1))
        if want_hidden:
            return nxt.reshape(b, k1), hid, out
        return nxt.reshape(b, k1), out
    return step


@functools.lru_cache(maxsize=64)
def _verify_step_cached(cache_key, closure):
    # the two-pass verify scatters, then gathers the post-scatter
    # pool, as the decode step does; the fused one gathers the
    # PRE-scatter pool, which the compiler orders before the in-place
    # scatter (ops/paged_attention.paged_verify_attention_fused)
    return track_jit("serving.verify_step", jax.jit(
        trace_named("serving.verify_step", closure.fn),
        donate_argnums=(9,)))


def verify_step_paged(forwards, cache, toks, pos, lens, tables,
                      temps, topks, seeds, counts,
                      want_hidden=False, params=None, resolved=None):
    """Score a PACKED batch of speculative token runs in ONE model
    pass against ``cache`` (:class:`serving.kv_slots.PagedKVCache`,
    updated in place) — the batched verify step of speculative
    decoding.

    ``toks`` [B, K1] — row n's pending token followed by its drafted
    tokens (padded past ``lens[n]``); ``pos`` [B] — the sequence
    index of each row's pending token; ``lens`` [B] — real positions
    per row (1 = no drafts, i.e. a plain decode step riding the
    verify batch); ``tables``/``temps``/``topks``/``seeds``/
    ``params`` as in :func:`paged_decode_step`; ``counts`` [B] — the
    draw counter of the FIRST sampled token (position j draws
    ``counts + j``).

    Returns [B, K1] next tokens: entry (n, j) is the token a
    sequential decode would emit after row n's context extended by
    its first j drafted tokens — the host accepts the longest prefix
    where draft j matches sample j-1 (plus the first non-matching
    sample, the "free" correction token), which reproduces the
    spec-off stream bit-for-bit for greedy AND per-seed sampling.
    ``want_hidden`` additionally returns the [B, K1, d] f32 hidden
    states (the final unit's input at every scored position) — after
    accepting L tokens the scheduler carries row position L-1's
    hidden into the next iteration's model-based draft.
    ``resolved`` as in :func:`paged_decode_step`."""
    from veles_tpu import dtypes
    from veles_tpu.config import root
    ctx = cache.tp_
    if params is None:
        params = _device_params(forwards)
    tables = jnp.asarray(tables, jnp.int32)
    toks = jnp.asarray(toks, jnp.int32)
    b, t = tables.shape
    k1 = toks.shape[1]
    # kv_dtype and the fused-verify knob both change the traced
    # verify body (TransformerBlock.apply_verify_paged reads them at
    # trace time) — they must key the executable or a toggle would
    # silently reuse the stale trace; the tp mesh size keys it too
    # (sharded params/pools compile a different SPMD program)
    kv_dtype = cache.kv_dtype
    fused = bool(root.common.serving.get("fused_verify", False))
    cache_key = (_arch_sig(forwards), b, k1, t, cache.block_size,
                 cache.capacity_blocks, kv_dtype, fused,
                 ctx.size if ctx is not None else 1,
                 bool(want_hidden),
                 str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    fn = _verify_step_cached(
        cache_key,
        _StepClosure(_make_verify_step(forwards,
                                       want_hidden=want_hidden)))
    if resolved is not None:
        resolved()
    old = cache.first_leaf()
    got = fn(
        params, toks, jnp.asarray(pos, jnp.int32),
        jnp.asarray(lens, jnp.int32), tables,
        jnp.asarray(temps, jnp.float32),
        jnp.asarray(topks, jnp.int32),
        jnp.asarray(seeds, jnp.uint32),
        jnp.asarray(counts, jnp.int32), cache.pools)
    cache.pools = got[-1]
    cache.note_swap(old)
    return got[:-1] if want_hidden else got[0]


def verify_supported(forwards):
    """True when every cacheable block speaks the paged verify step
    (``apply_verify_paged``) and every other sequence-positioned unit
    can place a width-k run (``apply_verify_slots`` or position-
    wise) — the gate speculative decoding checks before enabling."""
    has = False
    for u in forwards:
        if hasattr(u, "init_cache"):
            has = True
            if not hasattr(u, "apply_verify_paged"):
                return False
        elif hasattr(u, "apply_step_slots") \
                and not hasattr(u, "apply_verify_slots"):
            return False
    return has


def first_tokens(last_logits, temps, topks, seeds, counts=None):
    """Sample each admitted request's next token from its prefill
    logits ([k, vocab] f32) — draw ``counts`` of its stream (default
    0, the fresh-admission case; a preempt-resume passes its
    generated-prefix length)."""
    if counts is None:
        counts = [0] * len(seeds)
    return _sample_first_jit(
        jnp.asarray(last_logits, jnp.float32),
        jnp.asarray(temps, jnp.float32),
        jnp.asarray(topks, jnp.int32),
        jnp.asarray(seeds, jnp.uint32),
        jnp.asarray(counts, jnp.int32))
