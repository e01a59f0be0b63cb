"""The benchmark's own weights for an ``lfm2_moe`` chain, made from
``--seed`` one leaf at a time (yardstick; imports nothing of the
program).

A chain is ``embedding -> layers (shapes["kinds"]) -> norm + head`` at the
configuration file's ``shapes``.  Leaf names are those of the program's
parameter arrays (``veles_tpu/models/lfm2.py``).  Every leaf is drawn in
float32 and ROUNDED TO BFLOAT16, as the published checkpoint is: the
program holds the matrices and the table as those bfloat16 arrays and
the small float32 leaves (``FLOAT32``) as the same values widened; the
reference widens all of them.  So no float32 copy of the model ever
exists: the largest transient is one leaf's float32 (0.8 GB).
"""

import functools
import math

from benchmark import weights

#: leaves the program holds (and computes with) in float32
FLOAT32 = ("operator_norm", "ffn_norm", "embedding_norm", "q_norm",
           "k_norm", "conv_taps", "router", "expert_bias")
LEAVES = sorted(FLOAT32 + (
    "weights", "conv_in", "conv_out", "wq", "wk", "wv", "wo", "ffn_w1",
    "ffn_w3", "ffn_w2", "expert_w1", "expert_w3", "expert_w2"))


def layer_layout(shapes, kind):
    d, e = shapes["dim"], shapes["experts"]
    hd = d // shapes["heads"]
    kvd = shapes["kv_heads"] * hd
    operator, ffn = kind
    out = {"operator_norm": (d,), "ffn_norm": (d,)}
    if operator == "conv":
        out.update(conv_in=(d, 3 * d), conv_out=(d, d),
                   conv_taps=(shapes["conv_kernel"], d))
    else:
        out.update(wq=(d, d), wk=(d, kvd), wv=(d, kvd), wo=(d, d),
                   q_norm=(hd,), k_norm=(hd,))
    if ffn == "dense":
        h = shapes["ffn"]
        out.update(ffn_w1=(d, h), ffn_w3=(d, h), ffn_w2=(h, d))
    else:
        h = shapes["expert_ffn"]
        out.update(router=(d, e), expert_bias=(e,),
                   expert_w1=(e, d, h), expert_w3=(e, d, h),
                   expert_w2=(e, h, d))
    return out


def chain_layout(shapes):
    """[{leaf: shape}]: the table, every layer, the norm and head."""
    d, v = shapes["dim"], shapes["vocab"]
    return ([{"weights": (v, d)}]
            + [layer_layout(shapes, tuple(k)) for k in shapes["kinds"]]
            + [{"embedding_norm": (d,), "weights": (d, v)}])


def make_leaf(key, layer, name, shape):
    """One leaf in bfloat16 (``key`` and ``layer`` may be traced)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.fold_in(key, layer),
                             LEAVES.index(name))
    if name.endswith("_norm"):
        leaf = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "expert_bias":
        leaf = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "conv_taps":
        leaf = 0.5 * jax.random.normal(key, shape, jnp.float32)
    elif name == "weights" and shape[0] > shape[1]:      # the table
        leaf = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                           # a matrix, or one a leading expert
        a = math.sqrt(6.0 / (shape[-2] + shape[-1]))
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


def count_params(shapes, tied=False):
    import numpy
    total = sum(int(numpy.prod(s)) for layer in chain_layout(shapes)
                for s in layer.values())
    return total - shapes["vocab"] * shapes["dim"] * bool(tied)
