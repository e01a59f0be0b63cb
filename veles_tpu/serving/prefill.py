"""Batched prompt prefill — one jitted pass over the whole prompt.

The pre-serving decode stack consumed prompts one token at a time
(``_make_pre_step`` scanning ``apply_step`` — O(prompt_len) compiled
steps before the first generated token).  :func:`prefill` runs the
chain ONCE over all prompt positions, writes every cacheable block's
K/V rows in that single pass, and returns the logits at each row's
last prompt position — everything a request needs to emit its first
token and start single-token decoding.

Ragged batches prefill together: ``prompt_lens`` rides the compiled
pass as a traced argument (one executable serves any length mix at the
same shapes), rows at or past a row's length are zeroed in the cache
(exactly the rows a per-row sequential prefill would have left at the
``init_cache`` zeros), and the last-position logits gather follows the
per-row lengths.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.models.generate import (
    _StepClosure, _arch_sig, _check_positions, _device_params)
from veles_tpu.serving.kv_slots import slot_state_units
from veles_tpu.telemetry import trace_named, track_jit


def serving_refusal(forwards):
    """Why the chain cannot serve through the slot scheduler, in
    words, or None when it can: every cacheable unit is causal and
    speaks the serving step shapes (``apply_prefill`` +
    ``apply_step_paged``) AND every other sequence-dependent unit has
    a per-slot step or is position-wise."""
    has_cache = False
    for u in forwards:
        if hasattr(u, "init_cache"):
            has_cache = True
            if not u.causal:
                return "cacheable unit %s is not causal" % u.name
            missing = [m for m in ("apply_prefill", "apply_step_paged")
                       if not hasattr(u, m)]
            if missing:
                return "cacheable unit %s has no %s" % (
                    u.name, " and no ".join(missing))
        elif getattr(u, "DECODE_POINTWISE", False):
            continue
        elif not hasattr(u, "apply_step") \
                or not hasattr(u, "apply_step_slots"):
            return ("unit %s is neither position-wise nor has a "
                    "per-slot step (apply_step + apply_step_slots)"
                    % u.name)
    return None if has_cache else "the chain has no cacheable unit"


def serving_supported(forwards):
    """True when :func:`serving_refusal` finds nothing to refuse."""
    return serving_refusal(forwards) is None


def serving_window(forwards):
    """The widest decode window the chain supports, from the smallest
    learned positional table in the chain — None when no unit bounds
    the sequence length (the scheduler then requires an explicit
    window)."""
    best = None
    for u in forwards:
        pos_table = getattr(u, "positions", None)
        if getattr(pos_table, "shape", None) is not None \
                and len(pos_table.shape) == 2:
            n = int(pos_table.shape[0])
            best = n if best is None else min(best, n)
    return best


def chunked_supported(forwards):
    """True when the chain can prefill in chunks: every cacheable
    block continues from an offset (``apply_prefill_chunk``) and every
    other sequence-positioned unit speaks chunk offsets
    (``apply_chunk``) or is position-wise."""
    has = False
    for u in forwards:
        if hasattr(u, "init_cache"):
            has = True
            if not hasattr(u, "apply_prefill_chunk"):
                return False
        elif getattr(u, "positions", None) is not None \
                and not hasattr(u, "apply_chunk"):
            return False
    return has


def _make_chunk_fn(forwards, key_width):
    cacheable = frozenset(i for i, u in enumerate(forwards)
                          if hasattr(u, "init_cache"))

    def run(params, chunk, offset, chunk_lens, caches):
        h = chunk
        out = dict(caches)
        for i, u in enumerate(forwards):
            if i in cacheable:
                h, out[i] = u.apply_prefill_chunk(
                    params[i], h, caches[i], offset,
                    chunk_lens=chunk_lens, key_width=key_width)
            elif hasattr(u, "apply_chunk"):
                h = u.apply_chunk(params[i], h, offset)
            else:
                h = u.apply(params[i], h)
        last = jnp.take_along_axis(
            h, (chunk_lens - 1)[:, None, None], axis=1)[:, 0]
        return out, last.astype(jnp.float32)
    return run


@functools.lru_cache(maxsize=64)
def _chunk_cached(cache_key, closure):
    return track_jit("serving.prefill_chunk", jax.jit(
        trace_named("serving.prefill_chunk", closure.fn)))


def clear_chunk_cache():
    """Drop the compiled chunk-prefill cache (same lifetime note as
    :func:`clear_prefill_cache`)."""
    _chunk_cached.cache_clear()


def prefill_chunk(forwards, chunk, offset, chunk_lens, caches,
                  key_width=None, tp=None, params=None):
    """Prefill ONE chunk — ``chunk`` [batch, C] int32 tokens at
    sequence positions [offset, offset+C) — into existing staging
    ``caches`` (``{chain index: {"k", "v"} [batch, W, d]}``; W a
    multiple of C, rows still zero past every previously-written
    position).

    ``offset`` (a host int, multiple of C) rides the executable as a
    traced scalar; ``chunk_lens`` [batch] ints mark how much of the
    chunk each row's prompt actually covers (pad the rest — its K/V
    rows are zeroed like one-shot prefill's ragged rows).
    ``key_width`` (static, default W) bounds the attended key range;
    callers bucket it to a power of two ≥ offset + C.

    Returns ``(caches', last_logits)`` where ``last_logits``
    [batch, vocab] (f32) sit at each row's position
    ``offset + chunk_lens[n] - 1`` — the first-token logits once the
    final chunk lands.  Running the chunks in order reproduces the
    one-shot :func:`prefill` cache rows and logits (tested).

    ``params`` — the chain's device parameters: a server passes its
    frozen :class:`serving.weights.ServingWeights` pytree, an offline
    caller none (the units' own float32 buffers).

    ``tp`` (a :class:`serving.tp.ServingTP`, default None) runs the
    chunk SPMD over the tensor-parallel mesh with the Megatron-sharded
    ``params`` the server placed there — the staging caches ride
    uncommitted and land wherever
    GSPMD places them; the later block insert re-places them against
    the head-sharded pools."""
    from veles_tpu import dtypes
    if not chunked_supported(forwards):
        raise ValueError("chain cannot prefill in chunks (see "
                         "chunked_supported)")
    if params is None:
        params = _device_params(forwards)
    chunk = jnp.asarray(chunk, jnp.int32)
    b, c = chunk.shape
    state = slot_state_units(forwards)   # a fixed state has no width
    widths = {tuple(a.shape[-2] for a in layer.values())
              for i, layer in caches.items() if i not in state}
    w = next(iter(widths))[0]
    if any(x != w for tup in widths for x in tup):
        raise ValueError("staging caches disagree on width")
    if w % c or offset % c or offset + c > w:
        raise ValueError(
            "chunk [%d, %d) must tile the staging width %d"
            % (offset, offset + c, w))
    kw = int(key_width or w)
    if kw > w or kw < min(offset + c, w):
        raise ValueError("key_width %d outside [%d, %d]"
                         % (kw, offset + c, w))
    lens_np = numpy.asarray(chunk_lens, numpy.int32)
    if lens_np.shape != (b,):
        raise ValueError("chunk_lens must be [batch] ints")
    if lens_np.min() < 1 or lens_np.max() > c:
        raise ValueError("chunk_lens must be in [1, %d]" % c)
    cache_key = (_arch_sig(forwards), b, c, w, kw,
                 tp.size if tp is not None else 1,
                 str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    fn = _chunk_cached(cache_key,
                       _StepClosure(_make_chunk_fn(forwards, kw)))
    return fn(params, chunk, jnp.int32(offset),
              jnp.asarray(lens_np), caches)


def _make_prefill_fn(forwards, window):
    cacheable = frozenset(i for i, u in enumerate(forwards)
                          if hasattr(u, "init_cache"))

    def run(params, prompt, lens):
        from veles_tpu import dtypes
        b = prompt.shape[0]
        caches = {i: forwards[i].init_cache(b, window,
                                            dtypes.compute_dtype())
                  for i in cacheable}
        h = prompt
        for i, u in enumerate(forwards):
            if i in cacheable:
                h, caches[i] = u.apply_prefill(params[i], h,
                                               caches[i], lens=lens)
            else:
                h = u.apply(params[i], h)
        # h: [b, P, vocab]; each row's next token is predicted by the
        # logits at ITS last prompt position
        last = jnp.take_along_axis(
            h, (lens - 1)[:, None, None], axis=1)[:, 0]
        return caches, last.astype(jnp.float32)
    return run


@functools.lru_cache(maxsize=32)
def _prefill_cached(cache_key, closure):
    return track_jit("serving.prefill", jax.jit(
        trace_named("serving.prefill", closure.fn)))


def clear_prefill_cache():
    """Drop the compiled-prefill cache (same lifetime note as
    ``generate.clear_decode_caches``: entries pin the chain's units)."""
    _prefill_cached.cache_clear()


def prefill(forwards, prompt, prompt_lens=None, window=None,
            tp=None, params=None):
    """Prefill ``prompt`` [batch, P] (int32, front-aligned rows) in
    ONE compiled pass.

    Returns ``(caches, last_logits)``: ``caches`` maps the chain index
    of every cacheable block to its ``{"k", "v"}`` buffers —
    [batch, window, d] with rows [0, lens[n]) holding the prompt's K/V
    and every later row zero; ``last_logits`` [batch, vocab] (f32) are
    the logits at each row's position ``lens[n] - 1``.

    ``prompt_lens`` (optional [batch] ints) marks ragged rows (pad the
    array arbitrarily past each length); it rides the executable as a
    traced argument.  ``window`` (default P) sizes the returned cache
    buffers — a request decoding into a slot cache prefills straight
    at the slot width.  ``tp`` (serving/tp.py context) runs the pass
    SPMD over the tensor-parallel mesh; ``params`` as in
    :func:`prefill_chunk`."""
    from veles_tpu import dtypes
    for u in forwards:
        if hasattr(u, "init_cache") \
                and not hasattr(u, "apply_prefill"):
            raise ValueError(
                "batched prefill: %s has no apply_prefill"
                % type(u).__name__)
    if params is None:
        params = _device_params(forwards)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    window = int(window or p)
    if window < p:
        raise ValueError("window %d < prompt width %d" % (window, p))
    _check_positions(forwards, p)
    if prompt_lens is None:
        lens = jnp.full((b,), p, jnp.int32)
    else:
        lens_np = numpy.asarray(prompt_lens, numpy.int32)
        if lens_np.shape != (b,):
            raise ValueError("prompt_lens must be [batch] ints")
        if lens_np.min() < 1 or lens_np.max() > p:
            raise ValueError(
                "prompt_lens must be in [1, %d] (the prompt width)"
                % p)
        lens = jnp.asarray(lens_np)
    cache_key = (_arch_sig(forwards), b, p, window,
                 tp.size if tp is not None else 1,
                 str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    fn = _prefill_cached(cache_key,
                         _StepClosure(_make_prefill_fn(forwards,
                                                       window)))
    return fn(params, prompt, lens)
