"""The plain reference of the Command A+ decoder (``cohere2_moe``) as this
repo runs it: float32 ``jax.numpy`` at matmul precision ``highest``, one
sequence at a time, no cache, no ring, no batching, no kernels; the
attention takes a block of queries at a time over every key its mask can
admit (every key on the full layer, the window's band on a sliding
layer), the mask applied explicitly on absolute positions; the held
experts a plain loop.  It imports nothing of the program and makes
no weights: the caller hands each layer's leaves (named as the program's
units name them) and runs layer after layer.
``benchmark/cmdaplus_reference.py`` and
``veles_tpu/models/cohere_moe_reference.py`` are copies of one file
(``benchmark/tests/test_cmdaplus.py`` holds the two equal).

    LN(x; g) = (x - mean(x)) / sqrt(var(x) + eps) * g     (no bias)
    u = LN(x; input_norm);  y = x + Attn(u) + F(u)        (parallel block)

A layer's ``kind`` is "sliding" or "full".  Attention: H query heads
over G K/V heads of width D, no bias; on a sliding layer q and k turned
by rotary over interleaved pairs (2i, 2i+1) at theta ``rope_theta`` and
key j seen from query i iff j <= i and i - j < ``window``; on the full
layer no positions and every j <= i seen; scale D^-1/2.

F = (R + S) / 2: ``s = sigmoid(u router)`` over ALL the experts, the
``top_k`` largest chosen (no selection bias), weighted by ``s`` and
renormalised; R the held experts' part (``held_first``, ``held_count``
name the experts whose weights the caller hands; a chosen expert outside
them adds nothing, here as in the program); S the mean of the
``n_shared`` shared experts.  ``E(u) = (silu(u w1) * u w3) w2``.  The
reading of ``shared_expert_combination_strategy: "average"`` as that
half and half is this repo's (the configuration lists it under
``assumed``).

logits = logit_scale * LN(x; final_norm) table^T, the table the
embedding's own (tied); the vocabulary slice is the table's shape.

``cfg``: heads, kv_heads, head_dim, window, rope_theta, top_k,
held_first, held_count, n_shared, norm_eps, logit_scale, and where
given ``query_block`` (queries a block of the score matrix, default
``QUERY_BLOCK``), ``expert_rows`` (the share of the rows a held expert
is computed on when no more chose it, default 1) and ``prefill_chunk``
(read by one planted fault).  ``rows`` (where given) asks a layer for
its output at that many positions from the prompt's last on, all that
the head reads of the last layer: their queries over every key, and
nothing computed at the positions before.

Departures from the published model, all the caller's: the depth, the
experts held, the vocabulary rows, the weights (seeded, rounded to
bfloat16 by whoever makes them), no vision tower.  None is made here.

``mode="int8"`` is the CONTROL: both operands of every weight product
and of the two attention products rounded to int8, the nearest
precision below the bfloat16 the configuration states.  A product
multiplies the integers themselves (exact in bfloat16, summed in
float32) and scales the sum by the two operands' scales after: the
rounded operands' float32 product, at a sixth of its passes.  ``fault``
plants ONE fault for the calibration of the comparison (``FAULTS``);
the one that belongs to the serving path takes ``prompt_len``, the
position at which decode steps take over from the prefill chunks.
"""

import functools
import math

FAULTS = (
    "no_window",              # the sliding layers attend to every key
    "window_half",            # a window of half the positions
    "window_off_by_one",      # the query and ``window`` keys before it
    "rope_on_full_layer",     # rotary on the full layer too
    "rope_not_interleaved",   # rotate-half pairs (i, i + D/2)
    "sequential_block",       # the FFN reads the stream after attention
    "layernorm_as_rms",       # RMSNorm where the model has LayerNorm
    "shared_not_averaged",    # the shared experts summed, not averaged
    "top_k_minus_1",          # one expert fewer a token
    "unheld_expert_added",    # an absent expert's part added all the same
    "ring_not_carried")       # a sliding layer's keys not carried from
                              # one call (a prefill chunk, a decode step)
                              # to the next: each call sees its own alone
#: positions a prefill chunk (``cfg["prefill_chunk"]``, where given)
CHUNK = 256
#: queries a block of the score matrix (``cfg["query_block"]``)
QUERY_BLOCK = 128


def _int8(x, axis):
    """x rounded to int8 along ``axis``: (the integers, in bfloat16,
    where they are exact; the scale that turns them back)."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale).astype(jnp.bfloat16), scale


def _product(spec, a, b):
    """einsum of two operands at precision highest, summed in float32."""
    import jax
    import jax.numpy as jnp
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def matmul(x, w, mode):
    """x [s, k] @ w [k, n], float32 at precision highest."""
    import jax.numpy as jnp
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "int8":
        (xi, xs), (wi, ws) = _int8(x, -1), _int8(w, 0)
        return _product("sk,kn->sn", xi, wi) * xs * ws
    if mode != "f32":
        raise ValueError("unknown mode %r" % (mode,))
    return _product("sk,kn->sn", x, w)


def norm(x, weight, eps, fault=None):
    import jax.numpy as jnp
    weight = weight.astype(jnp.float32)
    if fault == "layernorm_as_rms":
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + eps) * weight
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) \
        * weight


def rotary(x, positions, theta, interleaved=True):
    """x [s, heads, D] at ``positions`` [s]: pair (2i, 2i+1) turned by
    position * theta^(-2i/D); rotate-half pairs (i, i + D/2) where not
    ``interleaved`` (a planted fault)."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if interleaved:
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(p, u, kind, cfg, mode, fault, prompt_len, rows=None):
    """The attention of every position, or with ``rows`` of the
    ``rows`` positions from ``prompt_len - 1`` on: their queries over
    the keys of the whole sequence."""
    import jax
    import jax.numpy as jnp
    s = u.shape[0]
    heads, kv_heads, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    first = 0 if rows is None else prompt_len - 1
    n = s if rows is None else rows
    uq = u if rows is None else jax.lax.dynamic_slice_in_dim(u, first, n)
    q = matmul(uq, p["wq"], mode).reshape(n, heads, hd)
    k = matmul(u, p["wk"], mode).reshape(s, kv_heads, hd)
    v = matmul(u, p["wv"], mode).reshape(s, kv_heads, hd)
    t = jnp.arange(s)
    if kind == "sliding" or fault == "rope_on_full_layer":
        inter = fault != "rope_not_interleaved"
        q = rotary(q, first + jnp.arange(n), cfg["rope_theta"], inter)
        k = rotary(k, t, cfg["rope_theta"], inter)
    window = None
    if kind == "sliding" and fault != "no_window":
        window = {"window_half": cfg["window"] // 2,
                  "window_off_by_one": cfg["window"] + 1}.get(
                      fault, cfg["window"])
    # the call a position is computed in (read by one planted fault): a
    # prefill chunk, or the decode step of its own
    call = jnp.where(t < prompt_len, t // cfg.get("prefill_chunk", CHUNK),
                     prompt_len + t)
    # query head h reads K/V head h // (heads / kv_heads)
    g = heads // kv_heads
    block = min(cfg.get("query_block", QUERY_BLOCK), n)
    blocks = -(-n // block)
    pad = blocks * block - n
    if mode == "int8":
        # scales: a query's and a key's per head, a value column's
        (q, qs), (k, ks), (v, vs) = _int8(q, -1), _int8(k, -1), _int8(v, 0)
        qs = jnp.pad(qs[..., 0], ((0, pad), (0, 0))).reshape(
            blocks, block, kv_heads, g)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, block, kv_heads, g, hd)
    # the keys a block's mask can admit: every key on the full layer; on
    # a sliding layer a band of fixed width from the window before the
    # block's first query through its last query
    band = s if window is None else min(s, window + block)

    def one_block(args):
        b0, qi, qsi = args
        i = first + b0 + jnp.arange(block)
        lo = jnp.clip(first + b0 + block - band, 0, s - band)
        j = lo + jnp.arange(band)
        kj, vj = (jax.lax.dynamic_slice_in_dim(y, lo, band) for y in (k, v))
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen = seen & (i[:, None] - j[None, :] < window)
        if fault == "ring_not_carried" and kind == "sliding":
            seen = seen & (call[j][None, :]
                           == call[jnp.minimum(i, s - 1)][:, None])
        scores = _product("qjge,kje->jgqk", qi, kj)
        if mode == "int8":
            ksj = jax.lax.dynamic_slice_in_dim(ks[..., 0], lo, band)
            scores = scores * qsi.transpose(1, 2, 0)[..., None] \
                * ksj.T[:, None, None, :]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores / math.sqrt(hd), -jnp.inf),
            axis=-1)
        if mode == "int8":
            pi, ps = _int8(probs, -1)
            return _product("jgqk,kje->qjge", pi, vj) \
                * ps[..., 0].transpose(2, 0, 1)[..., None] \
                * vs[0][None, :, None, :]
        return _product("jgqk,kje->qjge", probs, vj)
    scales = qs if mode == "int8" else jnp.zeros((blocks,))
    ctx = jax.lax.map(one_block, (jnp.arange(blocks) * block, qb, scales))
    ctx = ctx.reshape(blocks * block, heads * hd)[:n]
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
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"].astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
    ranked, chosen = jax.lax.top_k(s, top_k + 1)
    near = (ranked[:, top_k - 1] - ranked[:, top_k]) \
        < jnp.abs(ranked[:, top_k - 1]) * 2.0 ** -8
    chosen = chosen[:, :top_k]
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    gates = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(weight)
    return gates, near


def routed_ffn(p, u, cfg, mode, fault):
    """The held experts' part of the routed sum, and the near ties.  An
    expert is computed on the rows that chose it, gathered, up to
    ``expert_rows`` (a share of the rows, default all of them), and on
    every row, weighted by its gate (0 where not chosen), where more
    chose it: the same sum either way."""
    import jax
    import jax.numpy as jnp
    gates, near = route(p, u, cfg, fault)
    first, count = cfg["held_first"], cfg["held_count"]
    cap = max(1, int(u.shape[0] * cfg.get("expert_rows", 1.0)))

    def one_expert(e, acc):
        # sound: e runs over the held experts.  The planted fault runs
        # it over ALL of them, an absent one standing in with the
        # weights of a held one
        here = e % count
        w = [p[name][here] for name in ("expert_w1", "expert_w3",
                                        "expert_w2")]
        gate = gates[:, e] if fault == "unheld_expert_added" \
            else gates[:, first + e]
        chose = gate > 0

        def chosen_rows(acc):
            rows = jnp.nonzero(chose, size=cap, fill_value=0)[0]
            g = jnp.where(jnp.arange(cap) < chose.sum(), gate[rows], 0.0)
            return acc.at[rows].add(g[:, None]
                                    * gated_ffn(u[rows], *w, mode))

        def every_row(acc):
            return acc + gate[:, None] * gated_ffn(u, *w, mode)
        return jax.lax.cond(chose.sum() <= cap, chosen_rows, every_row,
                            acc)
    return jax.lax.fori_loop(
        0, gates.shape[1] if fault == "unheld_expert_added" else count,
        one_expert, jnp.zeros_like(u)), near


def shared_ffn(p, u, cfg, mode, fault):
    import jax
    import jax.numpy as jnp

    def one(i, acc):
        return acc + gated_ffn(u, p["shared_w1"][i], p["shared_w3"][i],
                               p["shared_w2"][i], mode)
    total = jax.lax.fori_loop(0, cfg["n_shared"], one, jnp.zeros_like(u))
    return total if fault == "shared_not_averaged" \
        else total / cfg["n_shared"]


def ffn(p, u, cfg, mode, fault):
    routed, near = routed_ffn(p, u, cfg, mode, fault)
    return 0.5 * (routed + shared_ffn(p, u, cfg, mode, fault)), near


def layer_apply(p, x, kind, cfg, mode="f32", fault=None, prompt_len=0,
                rows=None):
    """x [s, d] -> (y [s, d], near ties [s] bool: the tokens whose
    routing in this layer is one); with ``rows``, y and the ties of the
    ``rows`` positions from ``prompt_len - 1`` on alone."""
    import jax
    eps = cfg["norm_eps"]
    u = norm(x, p["input_norm"], eps, fault)
    a = attention(p, u, kind, cfg, mode, fault, prompt_len, rows)
    if rows is not None:
        x, u = (jax.lax.dynamic_slice_in_dim(y, prompt_len - 1, rows)
                for y in (x, u))
    if fault == "sequential_block":
        h = x + a
        f, near = ffn(p, norm(h, p["input_norm"], eps), cfg, mode, fault)
        return h + f, near
    f, near = ffn(p, u, cfg, mode, fault)
    return x + a + f, near


def embed(p, tokens):
    import jax.numpy as jnp
    return p["weights"][tokens].astype(jnp.float32)


def head_logits(table, final_norm, x, cfg, mode="f32", fault=None):
    """The tied head: ``table`` [vocab, d] (the embedding's),
    ``final_norm`` [d]."""
    import jax.numpy as jnp
    return cfg["logit_scale"] * matmul(
        norm(x, final_norm, cfg["norm_eps"], fault),
        table.astype(jnp.float32).T, mode)


@functools.lru_cache(maxsize=None)
def layer_program(kind, cfg_items, mode="f32", fault=None, rows=None):
    """One jitted layer of a kind: (leaves, x, prompt_len) -> (y, near
    ties); ``cfg_items`` is ``tuple(sorted(cfg.items()))``; ``rows`` as
    :func:`layer_apply` takes it.  Leaves may come narrower (bfloat16
    values): each is widened where it is read."""
    import jax
    cfg = dict(cfg_items)

    def run(p, x, prompt_len):
        return layer_apply(p, x, kind, cfg, mode, fault, prompt_len, rows)
    return jax.jit(run)


def forward_logits(chain, kinds, tokens, cfg, mode="f32", fault=None,
                   prompt_len=0):
    """The whole forward pass of ONE sequence held whole (tests; the
    benchmark runs layer by layer itself): ``chain`` = [embedding
    leaves, a layer's leaves ..., head leaves ({"final_norm"})].
    -> (logits [s, vocab], share of (token, layer) pairs whose routing
    is a near tie)."""
    import jax.numpy as jnp
    items = tuple(sorted(cfg.items()))
    x = embed(chain[0], jnp.asarray(tokens, jnp.int32))
    near = 0
    for p, kind in zip(chain[1:-1], kinds):
        x, n = layer_program(kind, items, mode, fault)(
            p, x, jnp.int32(prompt_len))
        near += int(n.sum())
    return head_logits(chain[0]["weights"], chain[-1]["final_norm"], x,
                       cfg, mode, fault), \
        near / max(1, len(kinds) * len(tokens))
