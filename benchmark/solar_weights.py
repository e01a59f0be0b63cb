"""The benchmark's own weights for a ``solar_open2`` chain, made from
``--seed`` one leaf at a time (yardstick; imports nothing of the
program).

A chain is ``embedding -> layers (shapes["kinds"]) -> norm + head`` at the
configuration file's ``shapes``: the table and the head hold the
``vocab`` rows of this chip's slice, a layer the ``held`` experts of the
``experts`` it routes over.  Leaf names are those of the program's
parameter arrays (``veles_tpu/models/solar.py``).  Every leaf is drawn in
float32 and ROUNDED TO BFLOAT16, as the published checkpoint is: the
program holds the matrices and the table as those bfloat16 arrays and
the small float32 leaves (``FLOAT32``) as the same values widened; the
reference widens all of them.  So no float32 copy of the model ever
exists: the largest transient is one leaf's float32 (0.84 GB).

Two leaves are drawn so that the comparison can see what they do
(``make_leaf``).  The router's columns have unequal norms (an expert's
logit has a standard deviation of its own, log-normal about
``ROUTER_LOGIT_STD``), and ``expert_bias`` is what balances such a
router's load, as a trained selection bias does: each expert's bias
lifts its score at the choice's quantile to the same threshold,
``BIAS_MARGIN``, so every expert is chosen equally often and the routing
is even, and yet the bias differs from expert to expert.  The threshold
is common to all, so it changes no choice; it is set low, so that gate
weights wrongly taken from ``s + expert_bias`` (a chosen expert's excess
over the threshold, plus the margin) differ by tens of per cent from
the sound ones, which are taken from ``s``.  At a bias of 0, or of any
common value, that fault IS the sound model and cannot be seen; a bias
drawn at random makes a few experts take most tokens.
"""

import functools
import math

from statistics import NormalDist

from benchmark import weights

#: leaves the program holds (and computes with) in float32
FLOAT32 = ("input_norm", "post_norm", "embedding_norm", "o_norm",
           "conv_taps", "A_log", "dt_bias", "gate_bias", "router",
           "expert_bias")
#: the router's logit spread over the experts and the biased score of
#: an expert at the choice's quantile (the module's docstring says why)
ROUTER_LOGIT_STD, ROUTER_LOG_SPREAD, BIAS_MARGIN = 0.6, 0.5, 0.02
LEAVES = sorted(FLOAT32 + (
    "weights", "wq", "wk", "wv", "wg", "wo", "decay_down", "decay_up",
    "wb", "gate_down", "gate_up", "expert_w1", "expert_w3", "expert_w2",
    "shared_w1", "shared_w3", "shared_w2"))


def layer_layout(shapes, kind):
    d, h, r = shapes["dim"], shapes["expert_ffn"], shapes["low_rank"]
    heads, hd = shapes["heads"], shapes["head_dim"]
    wide, held = heads * hd, shapes["held"][1]
    out = {"input_norm": (d,), "post_norm": (d,),
           "router": (d, shapes["experts"]),
           "expert_bias": (shapes["experts"],),
           "expert_w1": (held, d, h), "expert_w3": (held, d, h),
           "expert_w2": (held, h, d), "shared_w1": (d, h),
           "shared_w3": (d, h), "shared_w2": (h, d),
           "wq": (d, wide), "wo": (wide, d)}
    if kind == "kda":
        out.update(wk=(d, wide), wv=(d, wide),
                   conv_taps=(shapes["conv_kernel"], 3 * wide),
                   decay_down=(d, r), decay_up=(r, wide), A_log=(heads,),
                   dt_bias=(wide,), wb=(d, heads), gate_down=(d, r),
                   gate_up=(r, wide), gate_bias=(wide,), o_norm=(hd,))
    else:
        kvd = shapes["kv_heads"] * hd
        out.update(wk=(d, kvd), wv=(d, kvd), wg=(d, wide))
    return out


def chain_layout(shapes):
    """[{leaf: shape}]: the table, every layer, the norm and head."""
    d, v = shapes["dim"], shapes["vocab"]
    return ([{"weights": (v, d)}]
            + [layer_layout(shapes, kind) for kind in shapes["kinds"]]
            + [{"embedding_norm": (d,), "weights": (d, v)}])


def make_leaf(key, layer, name, shape, chosen_share):
    """One leaf in bfloat16 (``key`` and ``layer`` may be traced).
    ``chosen_share``: experts a token over the experts routed over."""
    import jax
    import jax.numpy as jnp
    of_layer = jax.random.fold_in(key, layer)
    key = jax.random.fold_in(of_layer, LEAVES.index(name))
    if name in ("router", "expert_bias"):
        # the standard deviation of each expert's logit: ONE draw a
        # layer (an index that no leaf has), read by both leaves
        spread = ROUTER_LOGIT_STD * jnp.exp(
            ROUTER_LOG_SPREAD * jax.random.normal(
                jax.random.fold_in(of_layer, len(LEAVES)), shape[-1:],
                jnp.float32))
    if name.endswith("_norm"):
        leaf = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "router":
        leaf = jax.random.normal(key, shape, jnp.float32) \
            * (spread / math.sqrt(shape[0]))
    elif name == "expert_bias":
        leaf = BIAS_MARGIN - jax.nn.sigmoid(
            spread * NormalDist().inv_cdf(1.0 - chosen_share))
    elif name == "gate_bias":
        leaf = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "conv_taps":
        leaf = 0.5 * jax.random.normal(key, shape, jnp.float32)
    elif name == "A_log":
        leaf = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0))
    elif name == "dt_bias":         # the inverse softplus of dt
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        leaf = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "weights" and shape[0] > shape[1]:      # the table
        leaf = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                           # a matrix, or one a leading expert
        a = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        leaf = jax.random.uniform(key, shape, jnp.float32, -a, a)
    return leaf.astype(jnp.bfloat16)


def chosen_share(shapes):
    return shapes["experts_per_token"] / shapes["experts"]


@functools.lru_cache(maxsize=None)
def _leaf_maker(name, shape, widen, share):
    import jax
    import jax.numpy as jnp

    def make(key, layer):
        leaf = make_leaf(key, layer, name, shape, share)
        return leaf.astype(jnp.float32) if widen else leaf
    return jax.jit(make)


def program_leaf(seed, layer, name, shape, share):
    """The leaf as the program holds it: bfloat16, or the bfloat16
    values in float32 for the ``FLOAT32`` names (``share``:
    :func:`chosen_share`)."""
    return _leaf_maker(name, tuple(shape), name in FLOAT32, share)(
        weights.base_key(seed), layer)


def reference_layer(seed, layer, layout, share):
    """{leaf: bfloat16 array} of one layer, for the reference (which
    widens each as it reads it)."""
    return {name: _leaf_maker(name, tuple(shape), False, share)(
        weights.base_key(seed), layer)
        for name, shape in sorted(layout[layer].items())}


def count_params(shapes):
    import numpy
    return sum(int(numpy.prod(s)) for layer in chain_layout(shapes)
               for s in layer.values())
