"""The benchmark's own weights for a looped ``ouro`` chain, made from
``--seed`` one leaf at a time (yardstick; imports nothing of the
program).

A chain is ``embedding -> the looped stack -> head`` at the
configuration file's ``shapes``.  Leaf names are those of the program's
parameter arrays (``veles_tpu/models/ouro.py``); the stack's leaves
carry a leading layer axis.  Every leaf is drawn in float32 and ROUNDED
TO BFLOAT16, as the published checkpoint is: the program holds the
matrices and the table as those bfloat16 arrays and the small float32
leaves (``FLOAT32``) as the same values widened; the reference widens
one layer's at a time.  So no float32 copy of the model ever exists: the
largest transient is one stacked leaf's float32 (2.2 GB).
"""

import functools
import math

from benchmark import weights

NORMS = ("attn_in_norm", "attn_out_norm", "ffn_in_norm", "ffn_out_norm")
#: leaves the program holds (and computes with) in float32
FLOAT32 = NORMS + ("final_norm", "gate_w", "gate_b")
LEAVES = sorted(FLOAT32 + ("weights", "wq", "wk", "wv", "wo", "ffn_w1",
                           "ffn_w3", "ffn_w2"))
GATE_BIAS = -1.0
#: the sandwich norms' OUTPUT vectors sit near this, not near 1: a
#: sub-layer's normed output is then a quarter of the stream it is
#: added to, as a trained stack's is small against its stream.  Near 1
#: the seeded network of 192 layer applications amplifies any rounding
#: until bfloat16 and int8 arithmetic both decorrelate from float32 and
#: the comparison cannot tell them apart (PERF.md, PR 33)
OUT_NORM = 0.25


def stack_layout(shapes):
    n, d, h = shapes["layers"], shapes["dim"], shapes["ffn"]
    out = {name: (n, d) for name in NORMS}
    out.update(wq=(n, d, d), wk=(n, d, d), wv=(n, d, d), wo=(n, d, d),
               ffn_w1=(n, d, h), ffn_w3=(n, d, h), ffn_w2=(n, h, d),
               final_norm=(d,), gate_w=(d,), gate_b=(1,))
    return out


def chain_layout(shapes):
    """[{leaf: shape}]: the table, the stack, the head."""
    d, v = shapes["dim"], shapes["vocab"]
    return [{"weights": (v, d)}, stack_layout(shapes),
            {"weights": (d, v)}]


def make_leaf(key, unit, name, shape):
    """One leaf in bfloat16 (``key`` may be traced)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.fold_in(key, unit),
                             LEAVES.index(name))
    if name.endswith("_norm"):
        leaf = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
        if name.endswith("_out_norm"):
            leaf = OUT_NORM * leaf
    elif name == "gate_b":
        leaf = jnp.full(shape, GATE_BIAS, jnp.float32)
    elif name == "gate_w" or name == "weights" and shape[0] > shape[1]:
        leaf = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                       # a matrix, or one a leading layer
        a = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        leaf = jax.random.uniform(key, shape, jnp.float32, -a, a)
    return leaf.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _leaf_maker(unit, name, shape, widen):
    import jax
    import jax.numpy as jnp

    def make(key):
        leaf = make_leaf(key, unit, name, shape)
        return leaf.astype(jnp.float32) if widen else leaf
    return jax.jit(make)


def program_leaf(seed, unit, name, shape):
    """The leaf as the program holds it: bfloat16, or the bfloat16
    values in float32 for the ``FLOAT32`` names."""
    return _leaf_maker(unit, name, tuple(shape), name in FLOAT32)(
        weights.base_key(seed))


def reference_chain(seed, shapes):
    """[{leaf: bfloat16 array}] of the whole chain, for the reference
    (which widens one layer's leaves at a time): 5.3 GB."""
    return [{name: _leaf_maker(i, name, tuple(shape), False)(
        weights.base_key(seed)) for name, shape in sorted(unit.items())}
        for i, unit in enumerate(chain_layout(shapes))]


def count_params(shapes):
    return sum(math.prod(s) for unit in chain_layout(shapes)
               for s in unit.values())
