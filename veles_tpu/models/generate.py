"""Autoregressive generation from a trained next-token LM.

No reference analogue (the reference had no sequence models at all —
SURVEY.md §5); this completes the LM loop the r5 stack opened:
train (``samples/lm.py``) → snapshot → :func:`generate`.

The whole decode is ONE jitted program: a ``lax.scan`` over decode
steps on a fixed-length token buffer.  Causal attention makes the
fixed buffer exact — positions past the cursor are *future* positions
to every already-generated token, so they cannot influence the logits
the sampler reads (the buffer's tail holds zeros, not padding that
would need masking).  Each step runs the full forward over the buffer
(O(L²) per step without a KV cache — exactness first; a cached decode
is a layout change inside TransformerBlock, not an API change).
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.telemetry import track_jit


def _chain_logits(forwards, params, tokens):
    h = tokens
    for i, u in enumerate(forwards):
        h = u.apply(params[i], h)
    return h


def _chain_step(forwards, params, tok, pos, caches):
    """One-token forward with per-block KV caches: tok [batch, 1] ids
    at sequence index ``pos`` → ([batch, 1, vocab] logits, caches')."""
    h = tok
    out = dict(caches)
    for i, u in enumerate(forwards):
        if hasattr(u, "init_cache"):
            h, out[i] = u.apply_step(params[i], h, pos, caches[i])
        elif hasattr(u, "apply_step"):
            h = u.apply_step(params[i], h, pos)
        else:
            h = u.apply(params[i], h)
    return h, out


def _device_params(forwards):
    # device-resident params (Array.devmem uploads lazily ONCE and
    # stays coherent): repeated decode calls must not re-ship the
    # weights host→device — that upload dwarfs the decode itself
    return {i: {name: arr.devmem
                for name, arr in u.param_arrays().items()}
            for i, u in enumerate(forwards)}


def _check_positions(forwards, total):
    for u in forwards:
        pos_table = getattr(u, "positions", None)
        if getattr(pos_table, "shape", None) is not None \
                and len(pos_table.shape) == 2 \
                and total > pos_table.shape[0]:
            raise ValueError(
                "prompt_len + steps = %d exceeds the model's learned "
                "positional table (%d — the training sequence length)"
                % (total, pos_table.shape[0]))


def _arch_sig(forwards):
    # the architecture signature the compiled-decode caches key on
    # (identical signatures define the identical computation, so
    # sharing the executable across chains is correct — and object ids
    # would be unsound: id reuse after gc replayed a stale chain's
    # executable; caught by the test suite)
    return tuple(
        (type(u).__name__,
         repr(sorted(u.export_config().items(), key=str)),
         tuple(sorted((n, tuple(a.shape))
                      for n, a in u.param_arrays().items())))
        for u in forwards)


def _make_pre_step(forwards, b):
    """Prompt-prefill step builder: consume one prompt token at
    ``pos``, populate the KV caches, sample nothing."""
    def pre_step(params, carry, _):
        buf, pos, caches = carry
        tok = jax.lax.dynamic_slice(buf, (0, pos), (b, 1))
        _, caches = _chain_step(forwards, params, tok, pos, caches)
        return (buf, pos + 1, caches), None
    return pre_step


def _make_prefill(forwards):
    """BATCHED prompt-prefill builder (serving PR): ONE forward pass
    over the whole prompt fills every cacheable block's K/V rows —
    TTFT drops from O(prompt_len) compiled scan steps to O(1).  The
    chain runs only up to the LAST cacheable block (later units fill
    no caches and their prompt outputs are discarded).  Returns None
    when any cacheable unit predates ``apply_prefill`` — the caller
    falls back to the per-token scan."""
    cacheable = [i for i, u in enumerate(forwards)
                 if hasattr(u, "init_cache")]
    if not cacheable or any(
            not hasattr(forwards[i], "apply_prefill")
            for i in cacheable):
        return None
    last = cacheable[-1]

    def prefill(params, toks, caches):
        h = toks
        out = dict(caches)
        for i, u in enumerate(forwards[:last + 1]):
            if hasattr(u, "init_cache"):
                h, out[i] = u.apply_prefill(params[i], h, caches[i])
            else:
                h = u.apply(params[i], h)
        return out
    return prefill


def kv_cache_eligible(forwards):
    """True when :func:`generate` can decode this chain with
    ``kv_cache=True``: every cacheable block is causal and has the
    single-token step, and every other unit either has one or is
    position-wise (the same predicate the kv path validates with)."""
    for u in forwards:
        if hasattr(u, "init_cache"):
            if not u.causal or not hasattr(u, "apply_step"):
                return False
        elif not hasattr(u, "apply_step") \
                and not getattr(u, "DECODE_POINTWISE", False):
            return False
    return True


def generate(forwards, prompt, steps, temperature=0.0, top_k=0,
             key=None, kv_cache=False, prompt_lens=None,
             stop_token=None):
    """Decode ``steps`` tokens after ``prompt`` [batch, prompt_len]
    (int32) through a forward chain ending in per-token logits
    (Embedding → TransformerBlock × N → TokenProjection).

    - ``temperature`` 0 → greedy argmax; otherwise logits/temperature
      categorical sampling (``key`` required);
    - ``top_k`` > 0 restricts sampling to the k most likely tokens;
    - ``kv_cache`` True → single-token decode steps against per-block
      K/V caches (O(total) per token instead of O(total²) — the
      layout change the module docstring promises).  Exact for causal
      chains; greedy parity with the uncached scan is tested
      token-for-token in f32.  The sampling key schedule matches the
      uncached path (one split per decode step), so a given
      ``key``/settings pair draws the same tokens either way;
    - ``prompt_lens`` (optional, [batch] ints) — VARIABLE-LENGTH
      batched prompts: row ``n``'s prompt occupies its first
      ``prompt_lens[n]`` positions (front-aligned; pad the rest of the
      [batch, prompt_len] array arbitrarily — generation overwrites
      the padding in place as it reaches it) and its generated region
      starts right after.  Every row decodes to the shared buffer end
      ``prompt_len + steps``, so row ``n`` gets
      ``prompt_len + steps - prompt_lens[n]`` ≥ ``steps`` new tokens;
      slice ``out[n, :prompt_lens[n] + k]`` for exactly ``k``.
      Greedy per-row results equal a single-row decode of the same
      prompt (tested).  The lens ride the compiled decode as a traced
      argument — one executable serves ANY length mix at the same
      (batch, prompt_len, steps).  Key schedule: one split per buffer
      position (all rows advance in lockstep), so sampled streams
      differ from the uniform-length path's;
    - ``stop_token`` (optional int) — a row that GENERATES this token
      freezes: every later position repeats it (the shapes stay
      static; trim at the first occurrence).  Prompt occurrences do
      not stop a row — only generated ones count.

    Returns [batch, prompt_len + steps] tokens."""
    params = _device_params(forwards)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    total = p_len + int(steps)
    lens = None
    if prompt_lens is not None:
        lens_np = numpy.asarray(prompt_lens, numpy.int32)
        if lens_np.shape != (b,):
            raise ValueError("prompt_lens must be [batch] ints")
        if lens_np.min() < 1 or lens_np.max() > p_len:
            raise ValueError(
                "prompt_lens must be in [1, %d] (the prompt width)"
                % p_len)
        lens = jnp.asarray(lens_np)
    if temperature and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if key is None:
        key = jax.random.key(0)
    _check_positions(forwards, total)
    vocab = getattr(forwards[-1], "vocab", None)
    if top_k and vocab is not None and int(top_k) > int(vocab):
        raise ValueError("top_k %d > vocab %d" % (top_k, vocab))
    if top_k and not temperature:
        raise ValueError(
            "top_k only applies to sampling — set temperature > 0 "
            "(greedy ignores it)")

    buf0 = jnp.zeros((b, total), jnp.int32)
    buf0 = jax.lax.dynamic_update_slice(buf0, prompt, (0, 0))

    def sample(logits, k):
        if temperature:
            z = logits / float(temperature)
            if top_k:
                kth = jnp.sort(z, axis=-1)[:, -int(top_k)][:, None]
                z = jnp.where(z < kth, -jnp.inf, z)
            return jax.random.categorical(k, z).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # stop PRESENCE is static (no freeze ops compiled when absent);
    # the stop VALUE rides the carry as a traced scalar, so every
    # stop id shares one executable — same design as prompt_lens
    use_stop = stop_token is not None
    stop0 = jnp.int32(int(stop_token) if use_stop else -1)

    def freeze(nxt, consumed, consumed_pos, gen_start, stop_val):
        # a row whose last GENERATED token was the stop token repeats
        # it forever (consumed_pos >= gen_start ⇔ the consumed token
        # was generated, so prompt occurrences never freeze a row)
        if not use_stop:
            return nxt
        frozen = (consumed == stop_val) & (consumed_pos >= gen_start)
        return jnp.where(frozen, stop_val, nxt)

    def step(params, carry, _):
        buf, pos, k, stop_val = carry
        logits = _chain_logits(forwards, params, buf)
        # logits at the cursor's predecessor predict the cursor token
        row = jax.lax.dynamic_slice(
            logits, (0, pos - 1, 0), (b, 1, logits.shape[-1]))[:, 0]
        k, sub = jax.random.split(k)
        nxt = sample(row, sub)
        consumed = jax.lax.dynamic_slice(
            buf, (0, pos - 1), (b, 1))[:, 0]
        nxt = freeze(nxt, consumed, pos - 1, p_len, stop_val)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, pos))
        return (buf, pos + 1, k, stop_val), None

    pre_step = _make_pre_step(forwards, b)

    def dec_step(params, carry, _):
        buf, pos, k, caches, stop_val = carry
        tok = jax.lax.dynamic_slice(buf, (0, pos), (b, 1))
        logits, caches = _chain_step(forwards, params, tok, pos, caches)
        k, sub = jax.random.split(k)
        nxt = sample(logits[:, 0], sub)
        nxt = freeze(nxt, tok[:, 0], pos, p_len, stop_val)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None],
                                           (0, pos + 1))
        return (buf, pos + 1, k, caches, stop_val), None

    def var_step(params, carry, _):
        # variable-length lockstep (kv): consume position pos, write
        # pos+1 only for rows whose prompt has ended — prompt tokens
        # pass through untouched, padding is overwritten in place
        buf, pos, k, caches, row_lens, stop_val = carry
        tok = jax.lax.dynamic_slice(buf, (0, pos), (b, 1))
        logits, caches = _chain_step(forwards, params, tok, pos, caches)
        k, sub = jax.random.split(k)
        nxt = sample(logits[:, 0], sub)
        nxt = freeze(nxt, tok[:, 0], pos, row_lens, stop_val)
        cur = jax.lax.dynamic_slice(buf, (0, pos + 1), (b, 1))[:, 0]
        write = jnp.where(pos + 1 >= row_lens, nxt, cur)
        buf = jax.lax.dynamic_update_slice(buf, write[:, None],
                                           (0, pos + 1))
        return (buf, pos + 1, k, caches, row_lens, stop_val), None

    def var_step_full(params, carry, _):
        # variable-length lockstep, full-buffer rescan variant
        buf, pos, k, row_lens, stop_val = carry
        logits = _chain_logits(forwards, params, buf)
        row = jax.lax.dynamic_slice(
            logits, (0, pos, 0), (b, 1, logits.shape[-1]))[:, 0]
        k, sub = jax.random.split(k)
        nxt = sample(row, sub)
        consumed = jax.lax.dynamic_slice(buf, (0, pos), (b, 1))[:, 0]
        nxt = freeze(nxt, consumed, pos, row_lens, stop_val)
        cur = jax.lax.dynamic_slice(buf, (0, pos + 1), (b, 1))[:, 0]
        write = jnp.where(pos + 1 >= row_lens, nxt, cur)
        buf = jax.lax.dynamic_update_slice(buf, write[:, None],
                                           (0, pos + 1))
        return (buf, pos + 1, k, row_lens, stop_val), None

    # params travel as jit ARGUMENTS (constants baked into the trace
    # would bloat the executable) and the compiled decode is cached on
    # the chain's ARCHITECTURE SIGNATURE (_arch_sig) + every static
    # piece of the decode config (batch, lengths, sampler settings —
    # they are baked into the step closure)
    from veles_tpu import dtypes
    sig = _arch_sig(forwards)
    # the compute/precision policy is read from GLOBAL config inside
    # the trace (the casts are baked into the executable) — it must
    # key the cache or a dtype toggle would replay the other policy's
    # program on shape-identical calls
    cache_key = (sig, b, int(steps), p_len,
                 float(temperature or 0.0), int(top_k or 0),
                 bool(kv_cache), lens is not None, use_stop,
                 str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    if kv_cache:
        for u in forwards:
            if hasattr(u, "init_cache"):
                if not u.causal:
                    raise ValueError(
                        "kv_cache decoding needs causal blocks — a "
                        "non-causal block's past outputs change when "
                        "future tokens arrive, so single-token steps "
                        "cannot reproduce them")
            elif not hasattr(u, "apply_step") \
                    and not getattr(u, "DECODE_POINTWISE", False):
                # a sequence-mixing unit without a single-token step
                # (MultiHeadAttention, RNN/LSTM, pooling heads) would
                # silently attend/recur over ONE position — refuse
                # rather than decode garbage
                raise ValueError(
                    "kv_cache decoding: %s has no apply_step and is "
                    "not position-wise — use kv_cache=False for this "
                    "chain" % type(u).__name__)
        caches0 = {i: u.init_cache(b, total, dtypes.compute_dtype())
                   for i, u in enumerate(forwards)
                   if hasattr(u, "init_cache")}
        if lens is not None:
            decode = _decode_cached_kv_varlen(
                cache_key, _StepClosure(var_step))
            return decode(params, buf0, key, caches0, lens,
                          stop0)
        decode = _decode_cached_kv(
            cache_key, _StepClosure((_make_prefill(forwards),
                                     pre_step, dec_step)))
        return decode(params, buf0, key, caches0, stop0)
    if lens is not None:
        # positions before every row's prompt end need no forward at
        # all on the rescan path — start at the host-known min length
        # (part of the key: the scan length is baked into the trace)
        vmin = int(lens_np.min())
        decode = _decode_cached_varlen(
            cache_key + (vmin,), _StepClosure(var_step_full))
        return decode(params, buf0, key, lens, stop0)
    decode = _decode_cached(cache_key, _StepClosure(step))
    return decode(params, buf0, key, stop0)


def generate_beam(forwards, prompt, steps, beam):
    """Beam-search decode: keep the ``beam`` highest-cumulative-log-
    probability continuations at every step (deterministic; the
    sampling knobs live in :func:`generate`).  Rides the kv-cache
    machinery — caches carry ``batch·beam`` rows and are re-gathered
    to each step's surviving parents.

    Returns ``(tokens, scores)``: tokens [batch, beam, prompt_len +
    steps] best-first, scores [batch, beam] — the cumulative log-prob
    of each generated region under the model, exactly re-scorable by
    a teacher-forced forward (tested).  ``beam=1`` equals greedy
    :func:`generate`."""
    from veles_tpu import dtypes
    if not kv_cache_eligible(forwards):
        raise ValueError(
            "beam search decodes on the kv-cache path — this chain "
            "is not cacheable (see kv_cache_eligible)")
    beam = int(beam)
    if beam < 1:
        raise ValueError("beam must be >= 1")
    params = _device_params(forwards)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    total = p_len + int(steps)
    _check_positions(forwards, total)
    vocab = getattr(forwards[-1], "vocab", None)
    if vocab is not None and beam > int(vocab):
        raise ValueError("beam %d > vocab %d" % (beam, vocab))

    buf0 = jnp.zeros((b, total), jnp.int32)
    buf0 = jax.lax.dynamic_update_slice(buf0, prompt, (0, 0))
    caches0 = {i: u.init_cache(b, total, dtypes.compute_dtype())
               for i, u in enumerate(forwards)
               if hasattr(u, "init_cache")}

    pre_step = _make_pre_step(forwards, b)

    def beam_step(params, carry, _):
        bufs, scores, pos, caches = carry        # bufs [b, beam, total]
        tok = jax.lax.dynamic_slice(
            bufs, (0, 0, pos), (b, beam, 1)).reshape(b * beam, 1)
        logits, caches = _chain_step(forwards, params, tok, pos, caches)
        logp = jax.nn.log_softmax(
            logits[:, 0].astype(jnp.float32)).reshape(b, beam, -1)
        # the first expansion starts from `beam` IDENTICAL rows — mask
        # all but row 0 or the top-k would pick the same token k times
        first = pos == jnp.int32(p_len - 1)
        dup_pen = jnp.where(
            first & (jnp.arange(beam)[None, :, None] > 0),
            -jnp.inf, 0.0)
        cand = scores[:, :, None] + logp + dup_pen
        nv = cand.shape[-1]
        scores, flat = jax.lax.top_k(cand.reshape(b, beam * nv), beam)
        parent = flat // nv                       # [b, beam]
        token = (flat % nv).astype(jnp.int32)
        bufs = jnp.take_along_axis(bufs, parent[:, :, None], axis=1)
        bufs = jax.lax.dynamic_update_slice(
            bufs, token[:, :, None], (0, 0, pos + 1))

        def regather(leaf):                       # [b·beam, ...]
            shaped = leaf.reshape((b, beam) + leaf.shape[1:])
            idx = parent.reshape(
                (b, beam) + (1,) * (len(leaf.shape) - 1))
            return jnp.take_along_axis(shaped, idx,
                                       axis=1).reshape(leaf.shape)

        caches = jax.tree_util.tree_map(regather, caches)
        return (bufs, scores, pos + 1, caches), None

    cache_key = (_arch_sig(forwards), b, int(steps), p_len, beam,
                 "beam", str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    decode = _decode_cached_beam(
        cache_key, _StepClosure((_make_prefill(forwards), pre_step,
                                 beam_step, beam)))
    return decode(params, buf0, caches0)


class _StepClosure:
    """Always-equal wrapper: the cache keys on ``cache_key`` (the
    architecture signature + batch/lengths/sampler settings) —
    everything the step closure actually varies over — while the
    closure itself rides along uncompared."""

    def __init__(self, fn):
        self.fn = fn

    def __hash__(self):
        return 0

    def __eq__(self, other):
        return isinstance(other, _StepClosure)


def clear_decode_caches():
    """Drop EVERY compiled-decode cache (all five LRUs below), freeing
    the parameter Arrays their step closures pin.  A serving process
    that cycles many large models through decode should call this when
    it retires one — entries otherwise hold the retired chain's units
    (host + device memory) alive until LRU eviction at 16 entries."""
    for cache in (_decode_cached, _decode_cached_kv,
                  _decode_cached_varlen, _decode_cached_kv_varlen,
                  _decode_cached_beam):
        cache.cache_clear()


# NOTE on lifetime: a cached entry's step closure holds the chain's
# units (and therefore their parameter Arrays, host + device) alive
# until LRU eviction — retire models with clear_decode_caches().
@functools.lru_cache(maxsize=16)
def _decode_cached(cache_key, step_closure):
    steps, p_len = cache_key[2], cache_key[3]

    @jax.jit
    def decode(params, buf, key, stop):
        (buf, _, _, _), _ = jax.lax.scan(
            functools.partial(step_closure.fn, params),
            (buf, jnp.int32(p_len), key, stop), None, length=steps)
        return buf

    return track_jit("generate.decode", decode)


@functools.lru_cache(maxsize=16)
def _decode_cached_kv(cache_key, step_closure):
    steps, p_len = cache_key[2], cache_key[3]
    prefill, pre_step, dec_step = step_closure.fn

    @jax.jit
    def decode(params, buf, key, caches, stop):
        if p_len > 1:  # prefill caches over the prompt's predecessors
            if prefill is not None:
                # ONE batched pass over the prompt (TTFT O(1) steps)
                caches = prefill(params, buf[:, :p_len - 1], caches)
            else:
                (buf, _, caches), _ = jax.lax.scan(
                    functools.partial(pre_step, params),
                    (buf, jnp.int32(0), caches), None,
                    length=p_len - 1)
        (buf, _, _, caches, _), _ = jax.lax.scan(
            functools.partial(dec_step, params),
            (buf, jnp.int32(p_len - 1), key, caches, stop), None,
            length=steps)
        return buf

    return track_jit("generate.decode_kv", decode)


@functools.lru_cache(maxsize=16)
def _decode_cached_varlen(cache_key, step_closure):
    total = cache_key[2] + cache_key[3]  # steps + p_len
    vmin = cache_key[-1]                 # min prompt length

    @jax.jit
    def decode(params, buf, key, lens, stop):
        (buf, _, _, _, _), _ = jax.lax.scan(
            functools.partial(step_closure.fn, params),
            (buf, jnp.int32(vmin - 1), key, lens, stop), None,
            length=total - vmin)
        return buf

    return track_jit("generate.decode_varlen", decode)


@functools.lru_cache(maxsize=16)
def _decode_cached_beam(cache_key, step_closure):
    steps, p_len = cache_key[2], cache_key[3]
    prefill, pre_step, beam_step, beam = step_closure.fn

    @jax.jit
    def decode(params, buf, caches):
        if p_len > 1:  # prefill at batch b, then tile beam-ways
            if prefill is not None:
                caches = prefill(params, buf[:, :p_len - 1], caches)
            else:
                (buf, _, caches), _ = jax.lax.scan(
                    functools.partial(pre_step, params),
                    (buf, jnp.int32(0), caches), None,
                    length=p_len - 1)
        b, total = buf.shape
        bufs = jnp.repeat(buf[:, None, :], beam, axis=1)
        caches = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, beam, axis=0), caches)
        scores = jnp.zeros((b, beam), jnp.float32)
        (bufs, scores, _, _), _ = jax.lax.scan(
            functools.partial(beam_step, params),
            (bufs, scores, jnp.int32(p_len - 1), caches), None,
            length=steps)
        return bufs, scores

    return track_jit("generate.decode_beam", decode)


@functools.lru_cache(maxsize=16)
def _decode_cached_kv_varlen(cache_key, step_closure):
    total = cache_key[2] + cache_key[3]  # steps + p_len

    @jax.jit
    def decode(params, buf, key, caches, lens, stop):
        (buf, _, _, _, _, _), _ = jax.lax.scan(
            functools.partial(step_closure.fn, params),
            (buf, jnp.int32(0), key, caches, lens, stop), None,
            length=total - 1)
        return buf

    return track_jit("generate.decode_kv_varlen", decode)
