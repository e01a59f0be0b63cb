"""The paged decode attention of a full-head chain, one query a row:
its arithmetic against a plain per-head softmax attention written here,
and what its tensor-parallel step asks of the mesh.  Float32 and tiny
widths (4 heads of 8): the kernel-sized programs are compiled for the
described chip in tests/test_tpu_compile.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.config import root

pytestmark = pytest.mark.serving

HEADS, HD, BS, BLOCKS = 4, 8, 4, 9
DIM = HEADS * HD


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _per_head_attention(q, keys, values, pos, heads):
    """Plain masked softmax attention, a head at a time: ``q`` [d] at
    position ``pos`` over rows ``keys``/``values`` [L, d]."""
    hd = q.shape[0] // heads
    out = numpy.zeros_like(q, dtype=numpy.float64)
    for h in range(heads):
        cols = slice(h * hd, (h + 1) * hd)
        scores = keys[:pos + 1, cols].astype(numpy.float64) \
            @ q[cols].astype(numpy.float64) / numpy.sqrt(hd)
        probs = numpy.exp(scores - scores.max())
        probs /= probs.sum()
        out[cols] = probs @ values[:pos + 1, cols].astype(numpy.float64)
    return out


#: name -> (position of each row, block table of each row); block 0 is
#: the trash block, so an all-zero table is a padding row
CASES = {
    "rows_at_different_positions": ([0, 5, 11], [[1, 0, 0], [2, 3, 0],
                                                 [4, 5, 6]]),
    "padding_row_with_an_all_zero_table": ([6, 0, 0], [[7, 8], [0, 0],
                                                       [0, 0]]),
    "table_deeper_than_any_row_needs": ([2, 5], [[3, 0, 0, 0, 0, 0, 0, 0],
                                                 [1, 2, 0, 0, 0, 0, 0, 0]]),
    "one_row_filling_its_last_block": ([7], [[5, 2]]),
}


def _inputs(pos, tables, seed):
    rng = numpy.random.default_rng(seed)
    b = len(pos)
    pool_k = rng.standard_normal((BLOCKS, BS, DIM)).astype(numpy.float32)
    pool_v = rng.standard_normal((BLOCKS, BS, DIM)).astype(numpy.float32)
    q, k_new, v_new = (rng.standard_normal((b, 1, DIM)).astype(
        numpy.float32) for _ in range(3))
    return (q, k_new, v_new, pool_k, pool_v,
            numpy.asarray(tables, numpy.int32),
            numpy.asarray(pos, numpy.int32))


def _expected(q, k_new, v_new, pool_k, pool_v, tables, pos, heads):
    """The pools after the scatter of every row in turn (a live block
    belongs to one row; padding rows all write the trash block's row
    0, the last wins) and each row's context over its own table."""
    pk, pv = pool_k.copy(), pool_v.copy()
    for n, p in enumerate(pos):
        pk[tables[n, p // BS], p % BS] = k_new[n, 0]
        pv[tables[n, p // BS], p % BS] = v_new[n, 0]
    ctx = numpy.stack([_per_head_attention(
        q[n, 0], pk[tables[n]].reshape(-1, pk.shape[-1]),
        pv[tables[n]].reshape(-1, pv.shape[-1]), int(p), heads)
        for n, p in enumerate(pos)])
    return pk, pv, ctx[:, None]


@pytest.mark.parametrize("kv_heads", [None, HEADS])
@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_attention_is_plain_per_head_attention(case, kv_heads, f32):
    """``kv_heads=None`` (the transformer block's call) and
    ``kv_heads == heads`` (the looped stack's) are one form; both are
    the per-head softmax attention the batched einsum used to spell."""
    from veles_tpu.ops.paged_attention import paged_decode_attention
    pos, tables = CASES[case]
    args = _inputs(pos, tables, seed=len(case))
    pk, pv, ctx = jax.jit(
        paged_decode_attention, static_argnums=(7, 8))(
            *args, HEADS, kv_heads)
    want_k, want_v, want = _expected(*args, HEADS)
    assert ctx.dtype == jnp.float32 and ctx.shape == want.shape
    live = [n for n, row in enumerate(tables) if any(row)]
    numpy.testing.assert_allclose(numpy.asarray(ctx)[live], want[live],
                                  atol=1e-6)
    # a padding row reads the trash block only: finite, never compared
    assert numpy.isfinite(numpy.asarray(ctx)).all()
    numpy.testing.assert_array_equal(numpy.asarray(pk)[1:], want_k[1:])
    numpy.testing.assert_array_equal(numpy.asarray(pv)[1:], want_v[1:])


@pytest.mark.parametrize("shard", [0, 1])
def test_decode_attention_on_half_the_heads_is_that_half(shard, f32):
    """The per-shard call of the collective-overlap tp step:
    ``heads // 2`` heads over that half of the feature axis give that
    half of the whole call's context and pool rows."""
    from veles_tpu.ops.paged_attention import paged_decode_attention
    pos, tables = CASES["rows_at_different_positions"]
    args = _inputs(pos, tables, seed=3)
    half = slice(shard * DIM // 2, (shard + 1) * DIM // 2)
    local = tuple(a[..., half] for a in args[:5]) + args[5:]
    pk, pv, ctx = jax.jit(paged_decode_attention, static_argnums=(7,))(
        *local, HEADS // 2)
    want_k, want_v, want = _expected(*args, HEADS)
    numpy.testing.assert_allclose(numpy.asarray(ctx), want[..., half],
                                  atol=1e-6)
    numpy.testing.assert_array_equal(numpy.asarray(pk)[1:],
                                     want_k[1:, :, half])
    numpy.testing.assert_array_equal(numpy.asarray(pv)[1:],
                                     want_v[1:, :, half])


def test_transformer_block_step_hands_the_tail_its_own_dtype(f32):
    """The attention's context is float32 whatever the compute dtype;
    the block's paged step returns its input's dtype."""
    from tests.test_tp import _tiny_fw
    fw = _tiny_fw("paged-attn-dtype", dim=DIM, heads=HEADS, blocks=1)
    block = fw[1]
    params = {n: jnp.asarray(a.mem)
              for n, a in block.param_arrays().items()}
    pos, tables = CASES["rows_at_different_positions"]
    pool = block.init_block_pool(BLOCKS, BS, jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        root.common.precision.compute_dtype = jnp.dtype(dtype).name
        x = jnp.ones((len(pos), 1, DIM), dtype)
        y, out = block.apply_step_paged(
            params, x, jnp.asarray(pos), jnp.asarray(tables), pool)
        assert y.dtype == dtype and y.shape == x.shape
        assert out["k"].dtype == pool["k"].dtype


# -- tensor parallel: what the GSPMD step asks of a mesh of two ---------------

#: collectives in the parent's compiled tp=2 decode step of the chain
#: below (the batched per-head einsum, counted before it was deleted):
#: one all-reduce for each of the two row-parallel products of each of
#: the two blocks
PARENT_TP2_COLLECTIVES = 4


@pytest.mark.tp
def test_tp2_step_adds_no_collective():
    """The two products contract over a feature axis the mesh shards
    head-wise.  Each head's query column is zero outside its own rows,
    which the partitioner cannot know: left to it, every block sums its
    scores across the chips (one more all-reduce a block).  Handed
    ``ServingTP.decode_attention``, as ``engine.paged_decode_step``
    hands it, a block attends per shard and the step keeps the
    parent's two reductions a block."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tests.test_tp import _tiny_fw
    from veles_tpu.serving import engine
    from veles_tpu.serving.tp import ServingTP
    fw = _tiny_fw("paged-attn-tp2", vocab=32, dim=DIM, heads=HEADS,
                  blocks=2)
    ctx = ServingTP(2)

    def arr(dtype, shape, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(ctx.mesh, spec))
    params = {i: {n: arr(jnp.float32, a.mem.shape,
                         getattr(u, "tp_param_spec", lambda *_: None)(
                             n, 2) or P())
                  for n, a in u.param_arrays().items()}
              for i, u in enumerate(fw)}
    b, t = 4, 4
    pool = arr(jnp.float32, (BLOCKS, BS, DIM), P(None, None, "tp"))
    pools = {i: {"k": pool, "v": pool} for i, u in enumerate(fw)
             if hasattr(u, "init_cache")}
    text = jax.jit(engine._make_paged_step(
        fw, attend=ctx.decode_attention)).lower(
            params, arr(jnp.int32, (b,)), arr(jnp.int32, (b,)),
            arr(jnp.int32, (b, t)), arr(jnp.float32, (b,)),
            arr(jnp.int32, (b,)), arr(jnp.uint32, (b,)),
            arr(jnp.int32, (b,)), arr(jnp.int32, (b,)),
            pools).compile().as_text()
    assert re.findall(
        r"= \S+ (all-reduce|all-gather|collective-permute|"
        r"all-to-all|reduce-scatter)(?:-start)?\(", text) \
        == ["all-reduce"] * PARENT_TP2_COLLECTIVES
