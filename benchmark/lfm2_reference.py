"""The plain reference of the LFM2 decoder (``lfm2_moe``) as this repo
runs it: float32 ``jax.numpy`` at matmul precision ``highest``, one
sequence at a time, no cache, no batching, no kernels, the experts in a
plain loop.  It imports nothing of the program and makes no weights:
the caller hands each layer's leaves (named as the program's units name
them) and runs layer after layer, so one layer's weights are alive at a
time.  ``benchmark/lfm2_reference.py`` and
``veles_tpu/models/lfm2_reference.py`` are copies of one file
(``benchmark/tests/test_lfm2.py`` holds the two equal).

    h = x + Op(RMS(x; operator_norm));  y = h + FFN(RMS(h; ffn_norm))

``cfg``: heads, kv_heads, conv_kernel, top_k, norm_topk_prob,
routed_scaling_factor, rope_theta, norm_eps.  A layer's ``kind`` is
``(operator, ffn)``: ("conv" | "attention", "dense" | "routed").

Departures from the published model, all the caller's: the depth, the
weights (seeded, rounded to bfloat16 by whoever makes them), the untied
head.  None is made here.

``mode="int8"`` is the CONTROL: both operands of every weight product
(and of the two attention products) rounded to int8, the nearest
precision below the bfloat16 the configuration states.  ``fault`` plants
ONE fault for the calibration of the comparison (``FAULTS``); those that
belong to the serving path take ``prompt_len``, the position at which
decode steps take over from the prefill.
"""

import functools
import math

FAULTS = (
    "conv_zero_at_chunk",     # conv state zeroed at each chunk's start
    "conv_not_carried",       # ... not carried from prefill to decode
    "choice_without_bias",    # experts chosen by s, not s + expert_bias
    "weights_with_bias",      # gate weights taken from s + expert_bias
    "no_renorm",              # gate weights not renormalised
    "top_k_minus_1",          # one expert fewer a token
    "rope_off_by_one_decode",  # decode positions rotated one too far
    "kv_tiled")               # KV head i % kv_heads, not i // group
#: positions a prefill chunk (``cfg["prefill_chunk"]``, where given)
CHUNK = 64


def _fake_int8(x, axis):
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


def matmul(x, w, mode):
    """x [s, k] @ w [k, n], float32 at precision highest."""
    import jax
    import jax.numpy as jnp
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode != "f32":
        raise ValueError("unknown mode %r" % (mode,))
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, weight, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def conv_operator(p, u, cfg, mode, fault, prompt_len):
    import jax.numpy as jnp
    s, d = u.shape
    k = cfg["conv_kernel"]
    gate_b, gate_c, x = jnp.split(matmul(u, p["conv_in"], mode), 3,
                                  axis=-1)
    z = gate_b * x
    t = jnp.arange(s)
    conv = jnp.zeros_like(z)
    for j in range(k):
        back = k - 1 - j                      # z[t - back]
        src = t - back
        seen = src >= 0
        if fault == "conv_zero_at_chunk":
            chunk = cfg.get("prefill_chunk", CHUNK)
            seen = seen & (src // chunk == t // chunk)
        if fault == "conv_not_carried":
            seen = seen & ~((t >= prompt_len) & (src < prompt_len))
        rows = jnp.where(seen[:, None], z[jnp.maximum(src, 0)], 0.0)
        conv = conv + p["conv_taps"][j] * rows
    return matmul(gate_c * conv, p["conv_out"], mode)


def rotary(x, positions, theta):
    """x [s, heads, hd]; rotate-half over the whole head."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = positions.astype(jnp.float32)[:, None] * inv
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(angle) \
        + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angle)


def attention_operator(p, u, cfg, mode, fault, prompt_len):
    import jax
    import jax.numpy as jnp
    s, d = u.shape
    heads, kv_heads = cfg["heads"], cfg["kv_heads"]
    hd = d // heads
    positions = jnp.arange(s)
    if fault == "rope_off_by_one_decode":
        positions = positions + (positions >= prompt_len)
    q = matmul(u, p["wq"], mode).reshape(s, heads, hd)
    k = matmul(u, p["wk"], mode).reshape(s, kv_heads, hd)
    v = matmul(u, p["wv"], mode).reshape(s, kv_heads, hd)
    q = rotary(rms(q, p["q_norm"], cfg["norm_eps"]), positions,
               cfg["rope_theta"])
    k = rotary(rms(k, p["k_norm"], cfg["norm_eps"]), positions,
               cfg["rope_theta"])
    serves = jnp.arange(heads) % kv_heads if fault == "kv_tiled" \
        else jnp.arange(heads) // (heads // kv_heads)
    k, v = k[:, serves], v[:, serves]
    if mode == "int8":
        q, k = _fake_int8(q, -1), _fake_int8(k, -1)
    scores = jnp.einsum("qhe,khe->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    if mode == "int8":
        probs, v = _fake_int8(probs, -1), _fake_int8(v, 0)
    ctx = jnp.einsum("hqk,khe->qhe", probs, v,
                     precision=jax.lax.Precision.HIGHEST).reshape(s, d)
    return matmul(ctx, p["wo"], mode)


def gated_ffn(u, w1, w3, w2, mode):
    import jax
    return matmul(jax.nn.silu(matmul(u, w1, mode)) * matmul(u, w3, mode),
                  w2, mode)


def route(p, u, cfg, fault=None):
    """-> (gates [s, experts]: each token's weight on each expert, 0
    where not chosen; near_ties [s] bool: the margin between the last
    expert chosen and the first left out is under the bfloat16 step of
    the score there).  Scores in float32 whatever the mode."""
    import jax
    import jax.numpy as jnp
    top_k = cfg["top_k"] - (fault == "top_k_minus_1")
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    biased = s + p["expert_bias"]
    ranked, chosen = jax.lax.top_k(
        s if fault == "choice_without_bias" else biased, top_k + 1)
    near = (ranked[:, top_k - 1] - ranked[:, top_k]) \
        < jnp.abs(ranked[:, top_k - 1]) * 2.0 ** -8
    chosen = chosen[:, :top_k]
    weight = jnp.take_along_axis(
        biased if fault == "weights_with_bias" else s, chosen, axis=-1)
    if cfg["norm_topk_prob"] and fault != "no_renorm":
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(weight)
    return gates, near


def routed_ffn(p, u, cfg, mode, fault):
    import jax
    import jax.numpy as jnp
    gates, near = route(p, u, cfg, fault)

    def one_expert(e, acc):
        out = gated_ffn(u, p["expert_w1"][e], p["expert_w3"][e],
                        p["expert_w2"][e], mode)
        return acc + gates[:, e][:, None] * out
    return jax.lax.fori_loop(0, gates.shape[1], one_expert,
                             jnp.zeros_like(u)), near


def layer_apply(p, x, kind, cfg, mode="f32", fault=None, prompt_len=0):
    """x [s, d] -> (y [s, d], near ties [s] bool: the tokens whose
    routing in this layer is one; none for a dense FFN)."""
    import jax.numpy as jnp
    operator, ffn = kind
    op = conv_operator if operator == "conv" else attention_operator
    h = x + op(p, rms(x, p["operator_norm"], cfg["norm_eps"]), cfg,
               mode, fault, prompt_len)
    u = rms(h, p["ffn_norm"], cfg["norm_eps"])
    if ffn == "dense":
        return h + gated_ffn(u, p["ffn_w1"], p["ffn_w3"], p["ffn_w2"],
                             mode), jnp.zeros((x.shape[0],), bool)
    out, near = routed_ffn(p, u, cfg, mode, fault)
    return h + out, near


def embed(p, tokens):
    import jax.numpy as jnp
    return p["weights"][tokens].astype(jnp.float32)


def head_logits(p, x, cfg, mode="f32"):
    import jax.numpy as jnp
    return matmul(rms(x, p["embedding_norm"].astype(jnp.float32),
                      cfg["norm_eps"]),
                  p["weights"].astype(jnp.float32), mode)


@functools.lru_cache(maxsize=None)
def layer_program(kind, cfg_items, mode="f32", fault=None):
    """One jitted layer of a kind: (leaves, x, prompt_len) -> (y, near
    ties); ``cfg_items`` is ``tuple(sorted(cfg.items()))``."""
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)

    def run(p, x, prompt_len):
        # leaves may come narrower (bfloat16 values): widened here
        p = {name: leaf.astype(jnp.float32) for name, leaf in p.items()}
        return layer_apply(p, x, kind, cfg, mode, fault, prompt_len)
    return jax.jit(run)


def forward_logits(chain, kinds, tokens, cfg, mode="f32", fault=None,
                   prompt_len=0):
    """The whole forward pass of ONE sequence held whole (tests; the
    benchmark runs layer by layer itself): ``chain`` = [embedding
    leaves, a layer's leaves ..., head leaves].  -> (logits [s, vocab],
    share of (token, routed layer) pairs that are near ties)."""
    import jax.numpy as jnp
    items = tuple(sorted(cfg.items()))
    x = embed(chain[0], jnp.asarray(tokens, jnp.int32))
    near, routed = 0, 0
    for p, kind in zip(chain[1:-1], kinds):
        x, n = layer_program(tuple(kind), items, mode, fault)(
            p, x, jnp.int32(prompt_len))
        near += int(n.sum())
        routed += kind[1] == "routed"
    return head_logits(chain[-1], x, cfg, mode), \
        near / max(1, routed * len(tokens))
