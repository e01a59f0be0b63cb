"""The plain reference: embedding with learned positions -> L pre-LN
blocks (MHA without biases, ReLU FFN) -> token logits, the next-token
loss and Adam, in straightforward float32 ``jax.numpy`` at matmul
precision ``highest``.  No kernels, no cache, no batching; imports
nothing of the program and makes its own weights (``weights.py``).

This is the architecture the configuration file states AS RUN (its
``assumed`` list names the departures from the published model).

``mode`` selects the arithmetic of every weight matmul:
  "f32"   the reference proper;
  "int8"  the CONTROL: both operands of every product rounded to int8
          (symmetric, scaled along their free dimension), in the forward
          pass and, for the weight matmuls, in the backward pass too --
          the nearest precision below the bfloat16 the configurations
          state.
It runs layer by layer and row by row so that it fits beside nothing
else on one chip: only one layer's gradient is ever alive.
"""

import functools
import math

import numpy

from benchmark import weights

B1, B2, EPS = 0.9, 0.999, 1e-8


def _jnp():
    import jax.numpy as jnp
    return jnp


def _fake_int8(x, axis):
    jnp = _jnp()
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


def _quant_ste(x, axis):
    """Round to int8 in the forward pass, identity in the backward."""
    import jax
    return x + jax.lax.stop_gradient(_fake_int8(x, axis) - x)


def _mm(a, b):
    import jax
    return _jnp().matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _int8_matmul():
    """x [m, k] @ w [k, n] with every product of the forward AND the
    backward pass between int8-rounded operands, each scaled along its
    own free dimension as an int8 GEMM scales them."""
    import jax

    @jax.custom_vjp
    def mm8(x, w):
        return _mm(_fake_int8(x, -1), _fake_int8(w, 0))

    def fwd(x, w):
        return mm8(x, w), (x, w)

    def bwd(saved, g):
        x, w = saved
        gx = _mm(_fake_int8(g, -1), _fake_int8(w, 1).T)
        gw = _mm(_fake_int8(x, 0).T, _fake_int8(g, 0))
        return gx, gw
    mm8.defvjp(fwd, bwd)
    return mm8


def matmul(x, w, mode):
    """x [s, k] @ w [k, n] in float32 at precision highest."""
    if mode == "int8":
        return _int8_matmul()(x, w)
    if mode != "f32":
        raise ValueError("unknown mode %r" % (mode,))
    return _mm(x, w)


def layer_norm(x, scale, bias, eps=1e-5):
    jnp = _jnp()
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def block_apply(p, x, heads, mode="f32"):
    """x [s, d] -> [s, d]: x + MHA(LN(x)), then + FFN(LN(.)); causal."""
    import jax
    jnp = _jnp()
    s, d = x.shape
    hd = d // heads
    a = layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    q, k, v = (matmul(a, p[n], mode).reshape(s, heads, hd)
               for n in ("wq", "wk", "wv"))
    if mode == "int8":
        q, k = _quant_ste(q, -1), _quant_ste(k, -1)
    scores = jnp.einsum("qhe,khe->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if mode == "int8":
        probs, v = _quant_ste(probs, -1), _quant_ste(v, 0)
    o = jnp.einsum("hqk,khe->qhe", probs, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, d)
    x = x + matmul(o, p["wo"], mode)
    a = layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    hid = jnp.maximum(matmul(a, p["ffn_w1"], mode) + p["ffn_b1"], 0.0)
    return x + matmul(hid, p["ffn_w2"], mode) + p["ffn_b2"]


def embed_apply(p, tokens):
    return p["weights"][tokens] + p["positions"][:tokens.shape[0]]


def head_logits(p, x, mode="f32"):
    return matmul(x, p["weights"], mode) + p["bias"]


def next_token_loss(p, x, tokens, mode="f32"):
    """Mean cross-entropy of position t against token t+1, over s-1."""
    import jax
    jnp = _jnp()
    logp = jax.nn.log_softmax(head_logits(p, x[:-1], mode), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, tokens[1:, None], axis=-1))


def lr_multiplier(step, schedule):
    """The configuration's schedule: linear warm-up then half-cosine to
    ``floor`` over ``total_steps`` (step counts from 0)."""
    warmup, total = schedule["warmup"], schedule["total_steps"]
    floor = schedule["floor"]
    if warmup and step < warmup:
        return step / warmup
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return floor + (1.0 - floor) * 0.5 * (1.0 + math.cos(math.pi * frac))


@functools.lru_cache(maxsize=None)
def _programs(heads, mode):
    """The reference's few jitted pieces (same shapes in every layer)."""
    import jax
    jnp = _jnp()

    def block_bwd(p, x, gy):
        _, vjp = jax.vjp(lambda p, x: block_apply(p, x, heads, mode), p, x)
        return vjp(gy)

    def head_bwd(p, x, tokens):
        return jax.value_and_grad(
            lambda p, x: next_token_loss(p, x, tokens, mode),
            argnums=(0, 1))(p, x)

    def embed_bwd(p, tokens, gx):
        return {"weights": jnp.zeros_like(p["weights"]).at[tokens].add(gx),
                "positions": jnp.zeros_like(p["positions"])
                .at[:gx.shape[0]].add(gx)}

    def adam(p, g, m, v, t, lr):
        m = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, m, g)
        v = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, v, g)
        p = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - B1 ** t))
            / (jnp.sqrt(v / (1 - B2 ** t)) + EPS), p, m, v)
        return p, m, v

    def norms(tree):
        return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(a * a)), tree)

    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    return {
        "block": jax.jit(lambda p, x: block_apply(p, x, heads, mode)),
        "block_bwd": jax.jit(block_bwd), "head_bwd": jax.jit(head_bwd),
        "embed": jax.jit(embed_apply), "embed_bwd": jax.jit(embed_bwd),
        "adam": jax.jit(adam, donate_argnums=(0, 2, 3)),
        "norms": jax.jit(norms), "add": jax.jit(add, donate_argnums=(0,)),
        "logits": jax.jit(lambda p, x: head_logits(p, x, mode)),
    }


def train_steps(shapes, seed, batches, hyper, mode="f32", fault=None):
    """Follow the first ``len(batches)`` optimizer steps from the seed's
    weights.  ``batches`` are [rows, seq] int token arrays, one a step.

    Returns the loss of every step, the norm of every leaf's gradient at
    the first step (as Adam receives it), and the norm of every leaf's
    change after the last step, the two as ``[{leaf: float}]`` by layer.

    ``fault`` plants one fault for the calibration of the comparison:
    "half_batch" takes the mean over the first half of the rows only.
    """
    import jax
    jnp = _jnp()
    fns = _programs(shapes["heads"], mode)
    layout = weights.chain_layout(shapes)
    n = len(layout)

    def fresh(i):
        return weights.fresh_layer(seed, i, layout)
    params = [fresh(i) for i in range(n)]
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m = [zeros(p) for p in params]
    v = [zeros(p) for p in params]
    losses, grad_norms = [], None
    for step, batch in enumerate(batches):
        rows = [jnp.asarray(r, jnp.int32) for r in numpy.asarray(batch)]
        if fault == "half_batch":
            rows = rows[:max(1, len(rows) // 2)]
        inv = 1.0 / len(rows)
        lr = hyper["learning_rate"] * lr_multiplier(
            step, hyper["lr_schedule_params"])
        t = float(step + 1)
        acts = []
        for tokens in rows:
            xs = [fns["embed"](params[0], tokens)]
            for i in range(1, n - 1):
                xs.append(fns["block"](params[i], xs[-1]))
            acts.append(xs)
        norms_now = [None] * n

        def update(i, grad):
            grad = jax.tree.map(lambda g: g * inv, grad)
            if step == 0:
                norms_now[i] = fns["norms"](grad)
            params[i], m[i], v[i] = fns["adam"](
                params[i], grad, m[i], v[i], t, lr)

        loss, ghead, gxs = 0.0, None, []
        for tokens, xs in zip(rows, acts):
            l, (gp, gx) = fns["head_bwd"](params[-1], xs[-1], tokens)
            loss += float(l) * inv
            ghead = gp if ghead is None else fns["add"](ghead, gp)
            gxs.append(gx)
        update(n - 1, ghead)
        for i in range(n - 2, 0, -1):
            gacc = None
            for r, xs in enumerate(acts):
                gp, gxs[r] = fns["block_bwd"](params[i], xs[i - 1], gxs[r])
                gacc = gp if gacc is None else fns["add"](gacc, gp)
            update(i, gacc)
        gacc = None
        for tokens, gx in zip(rows, gxs):
            gp = fns["embed_bwd"](params[0], tokens, gx)
            gacc = gp if gacc is None else fns["add"](gacc, gp)
        update(0, gacc)
        losses.append(loss)
        if step == 0:
            grad_norms = [_floats(t_) for t_ in norms_now]
    sub = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
    change_norms = [_floats(fns["norms"](sub(params[i], fresh(i))))
                    for i in range(n)]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


@functools.lru_cache(maxsize=None)
def _gap_program():
    import jax
    jnp = _jnp()

    def gaps(full, low, start, served):
        at = jnp.minimum(start + jnp.arange(served.shape[0]),
                         full.shape[0] - 1)
        rows = full[at]
        best = jnp.max(rows, axis=-1)

        def below_best(tokens):
            return best - jnp.take_along_axis(
                rows, tokens[:, None], axis=-1)[:, 0]
        return below_best(served), below_best(jnp.argmax(low[at], axis=-1))
    return jax.jit(gaps)


def served_gaps(full, low, start, served):
    """At the ``len(served)`` positions from ``start`` on, the gap by which
    a token's logit in ``full`` (the reference's [positions, vocab]) lies
    below the best there: for the ``served`` tokens, and for the tokens
    ``low`` (the same positions in a lower precision) puts first.  One
    program for every request: pad ``served`` to one length and cut the
    answers to the tokens there are."""
    return _gap_program()(full, low, start, served)


def _floats(tree):
    return {k: float(x) for k, x in tree.items()}


def batch_logits(shapes, seed, sequences, pad_to, mode="f32"):
    """Full forward passes over token sequences, layer by layer: each
    layer's weights are made once, used for every sequence and dropped.
    Yields the float32 logits [pad_to, vocab] of one sequence after the
    other.  Each sequence is padded at its end to ``pad_to`` tokens so
    that all run the same programs; under the causal mask the padding
    changes no earlier position."""
    jnp = _jnp()
    fns = _programs(shapes["heads"], mode)
    layout = weights.chain_layout(shapes)
    table = weights.fresh_layer(seed, 0, layout)
    xs = []
    for tokens in sequences:
        padded = numpy.zeros((pad_to,), numpy.int32)
        padded[:len(tokens)] = tokens
        xs.append(fns["embed"](table, jnp.asarray(padded)))
    del table
    for i in range(1, len(layout) - 1):
        layer = weights.fresh_layer(seed, i, layout)
        xs = [fns["block"](layer, x) for x in xs]
    head = weights.fresh_layer(seed, len(layout) - 1, layout)
    for x in xs:
        yield fns["logits"](head, x)
