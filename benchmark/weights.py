"""The benchmark's own weights and token rows, made from ``--seed``.

Part of the yardstick: the driver hands these arrays to the program and
the plain reference makes the same ones again, leaf by leaf, so the
reference never takes anything the program has made.  Imports nothing of
the program.

A chain is ``embedding -> L x block -> head`` in the shapes of the
configuration file (``dim``, ``heads``, ``ffn``, ``vocab``, ``positions``,
``layers``).  Leaf names are those of the program's parameter arrays.
"""

import functools
import math

import numpy

BLOCK_LEAVES = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                "ln2_scale", "ln2_bias", "ffn_w1", "ffn_b1", "ffn_w2",
                "ffn_b2")


def chain_layout(shapes):
    """[{leaf: shape}] for every layer of the chain, input first."""
    d, h, v = shapes["dim"], shapes["ffn"], shapes["vocab"]
    block = {"ln1_scale": (d,), "ln1_bias": (d,), "wq": (d, d),
             "wk": (d, d), "wv": (d, d), "wo": (d, d), "ln2_scale": (d,),
             "ln2_bias": (d,), "ffn_w1": (d, h), "ffn_b1": (h,),
             "ffn_w2": (h, d), "ffn_b2": (d,)}
    return ([{"weights": (v, d), "positions": (shapes["positions"], d)}]
            + [dict(block) for _ in range(shapes["layers"])]
            + [{"weights": (d, v), "bias": (v,)}])


def count_params(shapes):
    return sum(int(numpy.prod(s)) for layer in chain_layout(shapes)
               for s in layer.values())


def base_key(seed):
    """Any whole seed, past 2**31 too, folded into one threefry key (the
    implementation is named so that no flag of the program can change
    what a seed means)."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x3fffffff, impl="threefry2x32")
    return jax.random.fold_in(key, (seed >> 30) & 0x3fffffff)


def make_leaf(key, layer, name, shape):
    """One float32 leaf; ``key`` and ``layer`` may be traced.  Matrices
    are Glorot-uniform (the program's own default filling); tables,
    biases and LayerNorm offsets are small normals and LayerNorm scales
    sit near 1, so that no affine path is an identity the comparison
    could not see."""
    import jax
    import jax.numpy as jnp
    leaves = sorted(("weights", "positions", "bias") + BLOCK_LEAVES)
    key = jax.random.fold_in(jax.random.fold_in(key, layer),
                             leaves.index(name))
    table = name == "positions" or (name == "weights"
                                    and shape[0] > shape[1])
    if len(shape) == 2 and not table:
        a = math.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, shape, jnp.float32, -a, a)
    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + 0.1 * normal
    return 0.02 * normal


def make_layer(key, layer, leaves):
    return {name: make_leaf(key, layer, name, shape)
            for name, shape in sorted(leaves.items())}


@functools.lru_cache(maxsize=None)
def _layer_maker(items):
    import jax
    return jax.jit(lambda key, layer: make_layer(key, layer, dict(items)))


def fresh_layer(seed, layer, layout):
    """Layer ``layer`` alone (one small program per kind of layer, the
    same for every seed and every block)."""
    return _layer_maker(tuple(sorted(layout[layer].items())))(
        base_key(seed), layer)


@functools.lru_cache(maxsize=None)
def _chain_maker(items):
    import jax
    layout = chain_layout(dict(items))
    return jax.jit(lambda key: [make_layer(key, i, leaves)
                                for i, leaves in enumerate(layout)])


def make_chain(seed, shapes):
    """Every leaf of the chain in ONE jitted call on the default device,
    the seed a traced argument so that every seed runs one program."""
    keys = ("dim", "ffn", "vocab", "positions", "layers")
    return _chain_maker(tuple((k, shapes[k]) for k in keys))(
        base_key(seed))


def token_rows(seed, rows, seq, vocab):
    """[rows, seq] int32 token ids, uniform over the vocabulary."""
    return numpy.random.default_rng(int(seed)).integers(
        0, vocab, (rows, seq)).astype(numpy.int32)
