"""The plain reference of the Solar-Open2 decoder (``solar_open2``) as
this repo runs it: float32 ``jax.numpy`` at matmul precision
``highest``, one sequence at a time, no cache, no conv state (the whole
sequence is convolved), no batching, no kernels; the recurrence is a
plain loop over positions of ONE head's equations (``jax.vmap`` repeats
it for each head), the experts a plain loop.  It imports nothing of the
program and makes no weights: the caller hands each layer's leaves
(named as the program's units name them) and runs layer after layer.
``benchmark/solar_reference.py`` and
``veles_tpu/models/solar_reference.py`` are copies of one file
(``benchmark/tests/test_solar.py`` holds the two equal).

    h = x + Op(RMS(x; input_norm));  y = h + FFN(RMS(h; post_norm))

A layer's ``kind`` is its operator, "kda" or "gqa" (u the normed input):

KDA (H heads of width K):  q~ = u Wq, k~ = u Wk, v~ = u Wv, each through
its depthwise causal conv of ``conv_kernel`` taps and SiLU
(``q_t = silu(sum_j taps[j] q~[t - 3 + j])``); per head q <- q / |q|_2
* K^-1/2, k <- k / |k|_2; decay alpha = exp(-exp(A_log[h]) *
softplus((u F_down) F_up + dt_bias)) per key channel; beta = 2
sigmoid(u Wb) per head; per head, S = 0 at the start:

    S <- diag(alpha_t) S;  S <- S + beta_t k_t (x) (v_t - S^T k_t);
    o_t = S^T q_t

o <- RMS(o; o_norm) * sigmoid((u G_down) G_up + b_g);  Op = concat(o) Wo.

GQA: ``heads`` query heads over ``kv_heads`` K/V heads of ``head_dim``,
no rotary, no positions, causal softmax at scale K^-1/2, then
ctx * sigmoid(u Wg) before Wo.

FFN: s = sigmoid(u router) over ALL the experts; the ``top_k`` largest
of s + expert_bias chosen, weighted by s there, renormalised, times
``routed_scaling_factor``; plus the shared expert.  ``held_first`` and
``held_count`` name the experts whose weights the caller hands
(``expert_w*`` hold those alone): a chosen expert outside them adds
nothing, here as in the program.  The vocabulary slice is the table's
and the head's own shape.

``cfg``: heads, kv_heads, head_dim, conv_kernel, top_k, held_first,
held_count, norm_topk_prob, routed_scaling_factor, norm_eps, rope_theta
(read by one planted fault alone).

Departures from the published model, all the caller's: the depth, the
experts held, the vocabulary rows, the weights (seeded, rounded to
bfloat16 by whoever makes them).  None is made here.

``mode="int8"`` is the CONTROL: both operands of every weight product
(and of the two attention products) rounded to int8, the nearest
precision below the bfloat16 the configuration states; the recurrence
stays float32, as the configuration states its state.  ``fault`` plants
ONE fault for the calibration of the comparison (``FAULTS``); those that
belong to the serving path take ``prompt_len``, the position at which
decode steps take over from the prefill.
"""

import functools
import math

FAULTS = (
    "state_not_carried",      # S zeroed at every boundary between two
                              # calls: a chunk's start, each decode step
    "conv_not_carried",       # conv rows not carried over such a boundary
    "no_decay",               # alpha = 1
    "beta_not_doubled",       # beta = sigmoid(.), in (0, 1)
    "no_l2_norm",             # q and k not normalised
    "output_gate_off",        # the KDA output gate left out
    "gqa_gate_off",           # the GQA gate left out
    "rope_on_gqa",            # rotary positions where the model has none
    "top_k_minus_1",          # one expert fewer a token
    "unheld_expert_added",    # an absent expert's part added all the same
    "shared_expert_twice",    # the shared expert counted twice
    "weights_with_bias")      # gate weights taken from s + expert_bias
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


def delta_rule_head(q, k, v, alpha, beta, reset):
    """ONE head over the sequence: q, k, alpha [s, K]; v [s, V]; beta
    [s]; ``reset`` [s] bool: positions before which the state is zeroed
    (a planted fault; none in the sound model) -> o [s, V]."""
    import jax
    import jax.numpy as jnp
    high = jax.lax.Precision.HIGHEST

    def position(t, carry):
        S, out = carry
        S = jnp.where(reset[t], 0.0, S)
        S = alpha[t][:, None] * S
        pred = jnp.matmul(S.T, k[t], precision=high)
        S = S + beta[t] * jnp.outer(k[t], v[t] - pred)
        return S, out.at[t].set(jnp.matmul(S.T, q[t], precision=high))
    return jax.lax.fori_loop(
        0, q.shape[0], position,
        (jnp.zeros((k.shape[1], v.shape[1]), jnp.float32),
         jnp.zeros_like(v)))[1]


def kda_operator(p, u, cfg, mode, fault, prompt_len):
    import jax
    import jax.numpy as jnp
    s, _ = u.shape
    heads, hd, taps_n = cfg["heads"], cfg["head_dim"], cfg["conv_kernel"]
    raw = jnp.concatenate([matmul(u, p[w], mode)
                           for w in ("wq", "wk", "wv")], axis=-1)
    t = jnp.arange(s)
    # the call a position is computed in (read by two planted faults):
    # a prefill chunk, or the decode step of its own
    call = jnp.where(t < prompt_len,
                     t // cfg.get("prefill_chunk", CHUNK), t)
    mixed = jnp.zeros_like(raw)
    for j in range(taps_n):
        src = t - (taps_n - 1 - j)
        seen = src >= 0
        if fault == "conv_not_carried":
            seen = seen & (call[jnp.maximum(src, 0)] == call)
        mixed = mixed + p["conv_taps"][j] * jnp.where(
            seen[:, None], raw[jnp.maximum(src, 0)], 0.0)
    q, k, v = (x.reshape(s, heads, hd)
               for x in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
    if fault != "no_l2_norm":
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q = q / math.sqrt(hd)
    a = matmul(matmul(u, p["decay_down"], mode), p["decay_up"], mode)
    alpha = jnp.exp(-jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        a + p["dt_bias"]).reshape(s, heads, hd))
    if fault == "no_decay":
        alpha = jnp.ones_like(alpha)
    beta = jax.nn.sigmoid(matmul(u, p["wb"], mode)) \
        * (1.0 if fault == "beta_not_doubled" else 2.0)
    reset = jnp.zeros((s,), bool)
    if fault == "state_not_carried":
        reset = call != jnp.concatenate([call[:1], call[:-1]])
    o = jax.vmap(delta_rule_head, in_axes=(1, 1, 1, 1, 1, None),
                 out_axes=1)(q, k, v, alpha, beta, reset)
    o = rms(o, p["o_norm"], cfg["norm_eps"]).reshape(s, heads * hd)
    if fault != "output_gate_off":
        o = o * jax.nn.sigmoid(
            matmul(matmul(u, p["gate_down"], mode), p["gate_up"], mode)
            + p["gate_bias"])
    return matmul(o, p["wo"], mode)


def rotary(x, positions, theta):
    """x [s, heads, hd]; rotate-half over the whole head (a planted
    fault's: the model has no positions)."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = positions.astype(jnp.float32)[:, None] * inv
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(angle) \
        + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angle)


def gqa_operator(p, u, cfg, mode, fault, prompt_len):
    import jax
    import jax.numpy as jnp
    s, _ = u.shape
    heads, kv_heads, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = matmul(u, p["wq"], mode).reshape(s, heads, hd)
    k = matmul(u, p["wk"], mode).reshape(s, kv_heads, hd)
    v = matmul(u, p["wv"], mode).reshape(s, kv_heads, hd)
    if fault == "rope_on_gqa":
        q = rotary(q, jnp.arange(s), cfg["rope_theta"])
        k = rotary(k, jnp.arange(s), cfg["rope_theta"])
    serves = jnp.arange(heads) // (heads // kv_heads)
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
                     precision=jax.lax.Precision.HIGHEST).reshape(
                         s, heads * hd)
    if fault != "gqa_gate_off":
        ctx = ctx * jax.nn.sigmoid(matmul(u, p["wg"], mode))
    return matmul(ctx, p["wo"], mode)


def gated_ffn(u, w1, w3, w2, mode):
    import jax
    return matmul(jax.nn.silu(matmul(u, w1, mode)) * matmul(u, w3, mode),
                  w2, mode)


def route(p, u, cfg, fault=None):
    """-> (gates [s, experts]: each token's weight on each of ALL the
    experts, 0 where not chosen; near_ties [s] bool: the margin between
    the last expert chosen and the first left out is under the bfloat16
    step of the score there).  Scores in float32 whatever the mode."""
    import jax
    import jax.numpy as jnp
    top_k = cfg["top_k"] - (fault == "top_k_minus_1")
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    biased = s + p["expert_bias"]
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    near = (ranked[:, top_k - 1] - ranked[:, top_k]) \
        < jnp.abs(ranked[:, top_k - 1]) * 2.0 ** -8
    chosen = chosen[:, :top_k]
    weight = jnp.take_along_axis(
        biased if fault == "weights_with_bias" else s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(weight)
    return gates, near


def routed_ffn(p, u, cfg, mode, fault):
    """The held experts' part of the routed sum, and the near ties."""
    import jax
    import jax.numpy as jnp
    gates, near = route(p, u, cfg, fault)
    first, count = cfg["held_first"], cfg["held_count"]

    def one_expert(e, acc):
        # sound: e runs over the held experts.  The planted fault runs
        # it over ALL of them, an absent one standing in with the
        # weights of a held one
        here = e % count
        out = gated_ffn(u, p["expert_w1"][here], p["expert_w3"][here],
                        p["expert_w2"][here], mode)
        gate = gates[:, e] if fault == "unheld_expert_added" \
            else gates[:, first + e]
        return acc + gate[:, None] * out
    return jax.lax.fori_loop(
        0, gates.shape[1] if fault == "unheld_expert_added" else count,
        one_expert, jnp.zeros_like(u)), near


def layer_apply(p, x, kind, cfg, mode="f32", fault=None, prompt_len=0):
    """x [s, d] -> (y [s, d], near ties [s] bool: the tokens whose
    routing in this layer is one)."""
    op = kda_operator if kind == "kda" else gqa_operator
    h = x + op(p, rms(x, p["input_norm"], cfg["norm_eps"]), cfg, mode,
               fault, prompt_len)
    u = rms(h, p["post_norm"], cfg["norm_eps"])
    out, near = routed_ffn(p, u, cfg, mode, fault)
    shared = gated_ffn(u, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                       mode)
    return h + out + shared * (1 + (fault == "shared_expert_twice")), near


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
    share of (token, layer) pairs whose routing is a near tie)."""
    import jax.numpy as jnp
    items = tuple(sorted(cfg.items()))
    x = embed(chain[0], jnp.asarray(tokens, jnp.int32))
    near = 0
    for p, kind in zip(chain[1:-1], kinds):
        x, n = layer_program(kind, items, mode, fault)(
            p, x, jnp.int32(prompt_len))
        near += int(n.sum())
    return head_logits(chain[-1], x, cfg, mode), \
        near / max(1, len(kinds) * len(tokens))
