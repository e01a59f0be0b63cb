"""OpenAI-compatible facade + the servable non-LM entry points.

Ecosystem clients (SDKs, gateways, load-test harnesses) speak the
OpenAI REST dialect; this module maps it onto the Veles serving
engine so the fleet is a drop-in backend:

- ``POST /v1/completions`` — prompt in, completion out, with
  ``stream: true`` SSE chunks and ``usage`` accounting.  The engine
  is tokenizer-free (clients send token ids), so the ``text`` field
  of every choice carries SPACE-SEPARATED DECIMAL TOKEN IDS and the
  non-standard ``tokens`` field carries them as ints — deterministic
  and machine-parseable, which is what a drop-in harness actually
  needs;
- ``GET /v1/models`` — the one served model
  (``root.common.api.model_id``);
- ``POST /v1/embeddings`` — batched pooled hidden states:
  :func:`embed_pool` runs the chain through its LAST HIDDEN layer
  (the logits head is skipped) in one jitted pass per
  (batch, width) bucket — the same one-shot prefill computation a
  decode admission pays, minus the cache insert — then mean-pools
  each row's real positions and L2-normalizes (the OpenAI unit-norm
  convention);
- ``POST /v1/classify`` — classifier scoring over the full chain:
  the last-position logits (exactly :func:`serving.prefill.prefill`'s
  TTFT edge) as per-class log-probabilities with top-k labels, which
  makes the Veles classifier surface servable rather than
  train-only.

The jax work here never runs on HTTP handler threads — the
scheduler's decode loop executes embed/score jobs between decode
boundaries (``InferenceScheduler.submit_embed`` /
``submit_score``), preserving the one-jax-thread invariant.
Parsing helpers raise ``ValueError`` with client-facing messages
(HTTP 400 material); the REST layer owns status codes and headers.
"""

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.models.generate import (
    _StepClosure, _arch_sig, _check_positions, _device_params)
from veles_tpu.telemetry import track_jit


def _conf(name, default):
    from veles_tpu.config import root
    return root.common.api.get(name, default)


def model_id():
    """The model name this process serves under ``/v1/*``
    (``root.common.api.model_id``)."""
    return str(_conf("model_id", "veles-lm"))


def _bucket(n, floor=1):
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


# -- pooled embeddings (the serving.embed_pool jitted entry) ------------------

def embed_supported(forwards):
    """True when the chain can answer ``/v1/embeddings``: a prefill-
    capable chain with a distinct head unit to strip (the pooled
    states come from the layer UNDER the logits projection)."""
    from veles_tpu.serving.prefill import serving_supported
    return len(forwards) >= 2 and serving_supported(forwards)


def _make_embed_fn(forwards, window):
    cacheable = frozenset(i for i, u in enumerate(forwards)
                          if hasattr(u, "init_cache"))
    head = len(forwards) - 1   # the logits projection is skipped

    def run(params, prompt, lens):
        from veles_tpu import dtypes
        b, p = prompt.shape
        caches = {i: forwards[i].init_cache(b, window,
                                            dtypes.compute_dtype())
                  for i in cacheable}
        h = prompt
        for i, u in enumerate(forwards):
            if i == head:
                break
            if i in cacheable:
                h, caches[i] = u.apply_prefill(params[i], h,
                                               caches[i], lens=lens)
            else:
                h = u.apply(params[i], h)
        # h: [b, P, d] hidden states; mean-pool each row's REAL
        # positions (padding rows must not dilute the vector), then
        # L2-normalize — cosine similarity becomes a dot product
        mask = (jnp.arange(h.shape[1])[None, :]
                < lens[:, None]).astype(jnp.float32)
        pooled = (h.astype(jnp.float32) * mask[:, :, None]).sum(1) \
            / jnp.maximum(lens, 1).astype(jnp.float32)[:, None]
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-12)
    return run


@functools.lru_cache(maxsize=32)
def _embed_cached(cache_key, closure):
    return track_jit("serving.embed_pool", jax.jit(closure.fn))


def clear_embed_cache():
    """Drop the compiled embed-pool cache (entries pin the chain's
    units — same lifetime note as ``generate.clear_decode_caches``)."""
    _embed_cached.cache_clear()


def embed_pool(forwards, prompt, prompt_lens, params=None):
    """Pooled embeddings for ``prompt`` [b, P] int32 (front-aligned
    rows, ``prompt_lens`` [b] real lengths): ONE jitted pass through
    the chain's hidden layers (head skipped), masked mean-pool,
    L2-normalized [b, d] f32.  Callers bucket b and P — each (b, P)
    pair is one compiled executable.  ``params``: the server's frozen
    ``ServingWeights`` pytree (default: the units' own buffers)."""
    if not embed_supported(forwards):
        raise ValueError("chain cannot serve embeddings (needs a "
                         "prefill-capable chain with a head unit)")
    if params is None:
        params = _device_params(forwards)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    _check_positions(forwards, p)
    lens_np = numpy.asarray(prompt_lens, numpy.int32)
    if lens_np.shape != (b,) or lens_np.min() < 1 or lens_np.max() > p:
        raise ValueError("prompt_lens must be [batch] ints in "
                         "[1, %d]" % p)
    from veles_tpu import dtypes
    cache_key = (_arch_sig(forwards), b, p,
                 str(dtypes.compute_dtype()),
                 str(dtypes.matmul_precision()))
    fn = _embed_cached(cache_key,
                       _StepClosure(_make_embed_fn(forwards, p)))
    return fn(params, prompt, jnp.asarray(lens_np))


def _pad_rows(rows, width_cap):
    """Front-aligned [b_bucket, p_bucket] padding of ragged token
    rows: both axes power-of-two bucketed (compiled-executable
    economy), width capped at the serving window."""
    lens = [len(r) for r in rows]
    width = min(_bucket(max(lens), 8), int(width_cap))
    b = _bucket(len(rows), 1)
    padded = numpy.zeros((b, width), numpy.int32)
    for i, r in enumerate(rows):
        padded[i, :len(r)] = r
    lens_arr = numpy.ones((b,), numpy.int32)
    lens_arr[:len(rows)] = lens
    return padded, lens_arr


def pooled_embeddings(forwards, rows, window, params=None):
    """Batched ``/v1/embeddings`` execution: bucket + pad the rows,
    one :func:`embed_pool` pass, unpadded [n, d] float lists back."""
    padded, lens = _pad_rows(rows, window)
    out = numpy.asarray(embed_pool(forwards, padded, lens,
                                   params=params))
    return [out[i].tolist() for i in range(len(rows))]


def score_rows(forwards, rows, window, tp=None, params=None):
    """Batched ``/v1/classify`` execution: the last-position logits
    of each row through the FULL chain (the prefill TTFT edge),
    log-softmaxed to per-class log-probabilities [n, classes]."""
    from veles_tpu.serving.prefill import prefill
    padded, lens = _pad_rows(rows, window)
    _, last = prefill(forwards, padded,
                      prompt_lens=lens, window=padded.shape[1],
                      tp=tp, params=params)
    logits = numpy.asarray(last, numpy.float64)[:len(rows)]
    z = logits - logits.max(axis=-1, keepdims=True)
    logp = z - numpy.log(numpy.exp(z).sum(axis=-1, keepdims=True))
    return logp


# -- request parsing ----------------------------------------------------------

def parse_token_rows(raw, what="prompt"):
    """An OpenAI prompt/input: one token row or a batch of rows →
    list of non-empty int lists.  Raises ``ValueError`` (400
    material) on anything else — silently coercing junk would decode
    a phantom prompt."""
    if not isinstance(raw, list) or not raw:
        raise ValueError(
            "%s must be a non-empty token list or a batch of token "
            "lists (this engine is tokenizer-free: send token ids)"
            % what)
    rows = list(raw) if isinstance(raw[0], list) else [raw]
    out = []
    for r in rows:
        if not isinstance(r, list) or not r:
            raise ValueError("%s rows must be non-empty flat token "
                             "lists" % what)
        try:
            out.append([int(t) for t in r])
        except (TypeError, ValueError):
            raise ValueError("%s rows must be flat lists of int "
                             "token ids" % what)
    return out, not isinstance(raw[0], list)


def parse_completions(body):
    """``/v1/completions`` body → submit kwargs dict.  Client errors
    raise ``ValueError``; unsupported OpenAI parameters are REJECTED
    (a silently ignored ``n=4`` bills the client for answers it never
    gets)."""
    def _neutral_only(name, neutral):
        # SDKs send these at their neutral defaults — accept that,
        # reject anything that would change the output
        v = body.get(name)
        if v is not None and float(v) != float(neutral):
            raise ValueError("unsupported parameter %r (only the "
                             "neutral value %r)" % (name, neutral))
    _neutral_only("n", 1)
    _neutral_only("best_of", 1)
    _neutral_only("top_p", 1)
    _neutral_only("presence_penalty", 0)
    _neutral_only("frequency_penalty", 0)
    for unsupported in ("logprobs", "logit_bias", "suffix"):
        if body.get(unsupported):
            raise ValueError("unsupported parameter %r"
                             % unsupported)
    rows, squeeze = parse_token_rows(body.get("prompt"))
    try:
        steps = int(body.get("max_tokens", 16))
    except (TypeError, ValueError):
        raise ValueError("max_tokens must be an int")
    if steps < 1:
        raise ValueError("max_tokens must be >= 1")
    try:
        temperature = float(body.get("temperature") or 0.0)
        top_k = int(body.get("top_k") or 0)
    except (TypeError, ValueError):
        raise ValueError("temperature must be a number and top_k an "
                         "int")
    stop = body.get("stop")
    if stop is not None:
        try:
            stop = int(stop)
        except (TypeError, ValueError):
            raise ValueError("stop must be an int token id (this "
                             "engine is tokenizer-free)")
    seed = body.get("seed")
    if seed is not None:
        try:
            seed = int(seed)
        except (TypeError, ValueError):
            raise ValueError("seed must be an int")
    return {
        "rows": rows, "squeeze": squeeze, "steps": steps,
        "temperature": temperature, "top_k": top_k, "stop": stop,
        "seed": seed, "stream": bool(body.get("stream")),
        "echo": bool(body.get("echo")),
        "priority": body.get("priority"),
        "model": str(body.get("model") or model_id()),
    }


# -- response shaping ---------------------------------------------------------

def completion_id():
    return "cmpl-%s" % os.urandom(12).hex()


def text_of(tokens):
    """The ``text`` rendering of a token list: space-separated
    decimal ids (tokenizer-free engine — see module docstring)."""
    return " ".join(str(int(t)) for t in tokens)


def finish_reason(generated, steps, stop):
    return "stop" if (stop is not None and generated
                      and generated[-1] == stop) else "length"


def completion_choice(index, prompt, generated, params):
    toks = (list(prompt) + list(generated)) if params["echo"] \
        else list(generated)
    return {"index": index, "text": text_of(toks), "tokens": toks,
            "finish_reason": finish_reason(generated,
                                           params["steps"],
                                           params["stop"]),
            "logprobs": None}


def usage_of(rows, generated_counts):
    p = sum(len(r) for r in rows)
    c = sum(generated_counts)
    return {"prompt_tokens": p, "completion_tokens": c,
            "total_tokens": p + c}


def completion_reply(cid, created, model, choices, usage):
    return {"id": cid, "object": "text_completion",
            "created": created, "model": model, "choices": choices,
            "usage": usage}


def completion_chunk(cid, created, model, index, tokens,
                     finish=None, usage=None, trace_id=None):
    """One SSE chunk of a streaming completion: the newly accepted
    tokens (spec bursts arrive together), finish_reason/usage — and
    the request ``trace_id`` for server-side correlation — only on
    the terminal chunk (the OpenAI shape, plus the non-standard
    trace field this tokenizer-free engine adds)."""
    out = {"id": cid, "object": "text_completion", "created": created,
           "model": model,
           "choices": [{"index": index, "text": text_of(tokens),
                        "tokens": [int(t) for t in tokens],
                        "finish_reason": finish, "logprobs": None}]}
    if usage is not None:
        out["usage"] = usage
    if trace_id is not None:
        out["trace_id"] = trace_id
    return out


def models_reply():
    return {"object": "list",
            "data": [{"id": model_id(), "object": "model",
                      "created": int(time.time()),
                      "owned_by": "veles_tpu"}]}


def embeddings_reply(model, vectors, rows):
    return {"object": "list", "model": model,
            "data": [{"object": "embedding", "index": i,
                      "embedding": v}
                     for i, v in enumerate(vectors)],
            "usage": {"prompt_tokens": sum(len(r) for r in rows),
                      "total_tokens": sum(len(r) for r in rows)}}


def classify_reply(model, logp, rows, top):
    """Per-row class scores: full log-probability vector plus the
    top-k (label = class index — the Veles classifier heads are
    index-labeled)."""
    data = []
    for i in range(len(rows)):
        order = numpy.argsort(-logp[i])[:max(1, int(top))]
        data.append({
            "index": i,
            "label": int(order[0]),
            "top": [{"label": int(c),
                     "logprob": round(float(logp[i][c]), 6)}
                    for c in order],
            "logprobs": [round(float(x), 6) for x in logp[i]],
        })
    return {"object": "list", "model": model, "data": data,
            "usage": {"prompt_tokens": sum(len(r) for r in rows),
                      "total_tokens": sum(len(r) for r in rows)}}
