"""The benchmark's own weights for a ``cohere2_moe`` chain, made from
``--seed`` one leaf at a time (yardstick; imports nothing of the
program).

A chain is ``embedding -> layers (shapes["kinds"]) -> final norm`` at the
configuration file's ``shapes``, the head tied to the table: the table
holds the ``vocab`` rows of this chip's slice, a layer the ``held``
experts of the ``experts`` it routes over and its ``shared`` experts.
Leaf names are those of the program's parameter arrays
(``veles_tpu/models/cohere_moe.py``).  Every leaf is drawn in float32 and
ROUNDED TO BFLOAT16, as the published checkpoint is: the program holds
the matrices and the table as those bfloat16 arrays and the small
float32 leaves (``FLOAT32``) as the same values widened; the reference
widens all of them.  So no float32 copy of the model ever exists: the
largest transient is one leaf's float32 (1.07 GB, a layer's held
``expert_w1``).

The model has no selection bias, so nothing here balances the router:
its 128 columns are drawn with EQUAL norms (``ROUTER_COLUMN_NORM``) in
random directions, and each expert is equally likely to be chosen in
expectation over seeds.  The query projection ``wq`` is drawn at
``WQ_GAIN`` times its Glorot bound, so that a head's attention is peaked
as a trained model's is (at the Glorot bound a score spreads by about
0.8 over thousands of keys and every head averages them, so neither the
window nor the rotary would move a layer's output past the bfloat16
rounding; ``gigachat_weights.WQ_UP_GAIN`` is the same choice for MLA).
"""

import functools
import math

from benchmark import weights

#: leaves the program holds (and computes with) in float32
FLOAT32 = ("input_norm", "final_norm", "router")
#: the query projection's gain over its Glorot bound (module docstring)
WQ_GAIN = 8.0
#: the norm of each router column: an expert's logit spreads by about
#: this over the normed hidden states (unit scale a coordinate)
ROUTER_COLUMN_NORM = 1.0
LEAVES = sorted(FLOAT32 + (
    "weights", "wq", "wk", "wv", "wo", "expert_w1", "expert_w3",
    "expert_w2", "shared_w1", "shared_w3", "shared_w2"))


def layer_layout(shapes):
    d, h, sh = shapes["dim"], shapes["expert_ffn"], shapes["shared_ffn"]
    wide = shapes["heads"] * shapes["head_dim"]
    kvd = shapes["kv_heads"] * shapes["head_dim"]
    held, n = shapes["held"][1], shapes["shared"]
    return {"input_norm": (d,), "wq": (d, wide), "wk": (d, kvd),
            "wv": (d, kvd), "wo": (wide, d),
            "router": (d, shapes["experts"]),
            "expert_w1": (held, d, h), "expert_w3": (held, d, h),
            "expert_w2": (held, h, d), "shared_w1": (n, d, sh),
            "shared_w3": (n, d, sh), "shared_w2": (n, sh, d)}


def chain_layout(shapes):
    """[{leaf: shape}]: the table, every layer, the final norm."""
    return ([{"weights": (shapes["vocab"], shapes["dim"])}]
            + [layer_layout(shapes) for _ in shapes["kinds"]]
            + [{"final_norm": (shapes["dim"],)}])


def make_leaf(key, layer, name, shape):
    """One leaf in bfloat16 (``key`` and ``layer`` may be traced)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.fold_in(key, layer),
                             LEAVES.index(name))
    if name.endswith("_norm"):
        leaf = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "router":
        leaf = jax.random.normal(key, shape, jnp.float32)
        leaf = leaf * (ROUTER_COLUMN_NORM / jnp.sqrt(
            (leaf * leaf).sum(axis=0, keepdims=True)))
    elif name == "weights":                             # the table
        leaf = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                           # a matrix, or one a leading expert
        a = math.sqrt(6.0 / (shape[-2] + shape[-1])) \
            * (WQ_GAIN if name == "wq" else 1.0)
        leaf = jax.random.uniform(key, shape, jnp.float32, -a, a)
    return leaf.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _leaf_maker(name, shape, widen):
    import jax
    import jax.numpy as jnp

    def make(key, layer):
        leaf = make_leaf(key, layer, name, shape)
        return leaf.astype(jnp.float32) if widen else leaf
    return jax.jit(make)


def program_leaf(seed, layer, name, shape):
    """The leaf as the program holds it: bfloat16, or the bfloat16
    values in float32 for the ``FLOAT32`` names."""
    return _leaf_maker(name, tuple(shape), name in FLOAT32)(
        weights.base_key(seed), layer)


def reference_layer(seed, layer, layout):
    """{leaf: bfloat16 array} of one layer, for the reference (which
    widens each as it reads it)."""
    return {name: _leaf_maker(name, tuple(shape), False)(
        weights.base_key(seed), layer)
        for name, shape in sorted(layout[layer].items())}


def count_params(shapes):
    import numpy
    return sum(int(numpy.prod(s)) for layer in chain_layout(shapes)
               for s in layer.values())
