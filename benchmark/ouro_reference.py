"""The plain reference of the looped decoder (``model_type`` ``ouro``;
the LoopLM family, arXiv:2510.25741) as this repo runs it: float32
``jax.numpy`` at matmul precision ``highest``, one sequence at a time,
no cache, no batching, no kernels, the passes and the layers in plain
Python loops.  It imports nothing of the program and makes no weights:
the caller hands the leaves, named as the program's units name them
(``chain`` = [{"weights": table}, the stack's leaves with a leading
layer axis and ``final_norm``, ``gate_w``, ``gate_b``, {"weights":
head}]), which may come narrower (bfloat16 values) and are widened one
layer at a time as they are read.  ``benchmark/ouro_reference.py`` and
``veles_tpu/models/ouro_reference.py`` are copies of one file
(``benchmark/tests/test_ouro.py`` holds the two equal).

    h = E[tokens]
    for r in 0 .. R-1:                 # R = cfg["passes"], the SAME weights
        for l in 0 .. L-1:
            a = Attn_l(RMS(h; attn_in_norm_l))     # rotary, causal softmax,
                                                   # keys and values of pass r
            h = h + RMS(a; attn_out_norm_l)        # the OUTPUT is normed
            m = W2_l(silu(W1_l u) * W3_l u),  u = RMS(h; ffn_in_norm_l)
            h = h + RMS(m; ffn_out_norm_l)
        h = RMS(h; final_norm)                     # after EVERY pass
        g_r = sigmoid(gate_w . h + gate_b)
    logits = W_head h
    p_r = g_r * prod_{j<r}(1 - g_j)  (r < R-1),  p_{R-1} = prod_{j<R-1}(1 - g_j)

``cfg``: heads, passes, rope_theta, norm_eps.

Departures from the published model, all the caller's: the weights
(seeded, rounded to bfloat16 by whoever makes them); no bias and no
QK-norm; every token runs all passes and takes the last pass's logits
(``early_exit_threshold`` 1).  None is made here.

``mode="int8"`` is the CONTROL: both operands of every weight product
(and of the two attention products) rounded to int8, the nearest
precision below the bfloat16 the configuration states.  ``fault`` plants
ONE fault for the calibration of the comparison (``FAULTS``); the one
that belongs to the serving path takes ``prompt_len``, the position at
which decode steps take over from the prefill.
"""

import functools
import math

FAULTS = (
    "one_pass_fewer",            # R - 1 passes
    "kv_shared_across_passes",   # every pass attends the first's K and V
    "no_norm_between_passes",    # the next pass takes the un-normed stream
    "no_output_norms",           # plain pre-norm residual: h + a, h + m
    "rope_off_by_one_decode",    # decode positions rotated one too far
    "weights_with_bias")         # every projection adds BIAS * cos(j)
#: the planted bias of ``weights_with_bias``: b_j = BIAS * cos(j)
BIAS = 0.1
MATRICES = ("wq", "wk", "wv", "wo", "ffn_w1", "ffn_w3", "ffn_w2")


def _fake_int8(x, axis):
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


def matmul(x, w, mode, fault=None):
    """x [s, k] @ w [k, n], float32 at precision highest."""
    import jax
    import jax.numpy as jnp
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode != "f32":
        raise ValueError("unknown mode %r" % (mode,))
    y = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if fault == "weights_with_bias":
        y = y + BIAS * jnp.cos(jnp.arange(w.shape[1], dtype=jnp.float32))
    return y


def rms(x, weight, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


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


def attention(p, u, cfg, mode, fault, prompt_len, shared):
    """-> (a [s, d], (k, v) of this application).  ``shared``: the
    (k, v) to attend instead of its own (``kv_shared_across_passes``)."""
    import jax
    import jax.numpy as jnp
    s, d = u.shape
    heads = cfg["heads"]
    hd = d // heads
    positions = jnp.arange(s)
    if fault == "rope_off_by_one_decode":
        positions = positions + (positions >= prompt_len)
    q = rotary(matmul(u, p["wq"], mode, fault).reshape(s, heads, hd),
               positions, cfg["rope_theta"])
    k = rotary(matmul(u, p["wk"], mode, fault).reshape(s, heads, hd),
               positions, cfg["rope_theta"])
    v = matmul(u, p["wv"], mode, fault).reshape(s, heads, hd)
    own = (k, v)
    if shared is not None:
        k, v = shared
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
    return matmul(ctx, p["wo"], mode, fault), own


def layer_apply(p, h, cfg, mode="f32", fault=None, prompt_len=0,
                shared=None):
    """One application of one layer: h [s, d] -> (h', its (k, v))."""
    import jax
    eps = cfg["norm_eps"]
    plain = fault == "no_output_norms"
    a, own = attention(p, rms(h, p["attn_in_norm"], eps), cfg, mode,
                       fault, prompt_len, shared)
    h = h + (a if plain else rms(a, p["attn_out_norm"], eps))
    u = rms(h, p["ffn_in_norm"], eps)
    m = matmul(jax.nn.silu(matmul(u, p["ffn_w1"], mode, fault))
               * matmul(u, p["ffn_w3"], mode, fault), p["ffn_w2"], mode,
               fault)
    return h + (m if plain else rms(m, p["ffn_out_norm"], eps)), own


@functools.lru_cache(maxsize=None)
def layer_program(cfg_items, mode="f32", fault=None):
    """One jitted layer application: (the stack's leaves, layer index,
    h, prompt_len[, the (k, v) to attend]) -> (h', its (k, v));
    ``cfg_items`` is ``tuple(sorted(cfg.items()))``.  The layer's
    leaves are sliced and widened here, one layer's float32 at a time."""
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)

    def run(stack, index, h, prompt_len, shared=None):
        p = {name: jax.lax.dynamic_index_in_dim(
            leaf, index, keepdims=False).astype(jnp.float32)
            for name, leaf in stack.items()
            if name in MATRICES or name.endswith("_norm")
            and name != "final_norm"}
        return layer_apply(p, h, cfg, mode, fault, prompt_len, shared)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def pass_end_program(eps):
    """(stack's leaves, h) -> (RMS(h; final_norm), the gate [s])."""
    import jax
    import jax.numpy as jnp

    def run(stack, h):
        h = rms(h, stack["final_norm"].astype(jnp.float32), eps)
        gate = jax.nn.sigmoid(
            jnp.matmul(h, stack["gate_w"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
            + stack["gate_b"].astype(jnp.float32)[0])
        return h, gate
    return jax.jit(run)


def exit_distribution(gates):
    """gates [R, s] -> p [R, s], each position's R shares summing to 1."""
    import jax.numpy as jnp
    out, left = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        out.append(g * left)
        left = left * (1.0 - g)
    return jnp.stack(out + [left])


def stack_forward(stack, x, cfg, mode="f32", fault=None, prompt_len=0):
    """x [s, d] through the looped stack -> (the last pass's normed
    stream [s, d], the exit distribution p [R', s]; R' the passes run)."""
    import jax.numpy as jnp
    items = tuple(sorted(cfg.items()))
    layers = stack["wq"].shape[0]
    passes = cfg["passes"] - (fault == "one_pass_fewer")
    share = fault == "kv_shared_across_passes"
    first, gates = [], []
    h = x
    run = layer_program(items, mode, fault)
    for r in range(passes):
        for index in range(layers):
            h, own = run(stack, jnp.int32(index), h,
                         jnp.int32(prompt_len),
                         first[index] if share and r else None)
            if share and not r:
                first.append(own)
        normed, gate = pass_end_program(cfg["norm_eps"])(stack, h)
        gates.append(gate)
        last = r == passes - 1
        h = h if fault == "no_norm_between_passes" and not last \
            else normed
    return h, exit_distribution(jnp.stack(gates))


def embed(p, tokens):
    import jax.numpy as jnp
    return p["weights"][tokens].astype(jnp.float32)


def head_logits(p, x, mode="f32"):
    import jax.numpy as jnp
    return matmul(x, p["weights"].astype(jnp.float32), mode)


def forward_logits(chain, tokens, cfg, mode="f32", fault=None,
                   prompt_len=0):
    """The whole forward pass of ONE sequence: ``chain`` = [table
    leaves, the stack's leaves, head leaves].  -> (logits [s, vocab],
    the exit distribution p [passes run, s])."""
    import jax.numpy as jnp
    x = embed(chain[0], jnp.asarray(tokens, jnp.int32))
    h, p = stack_forward(chain[1], x, cfg, mode, fault, prompt_len)
    return head_logits(chain[2], h, mode), p
