"""Native pallas flash-attention kernels (ops/pallas_attention.py) —
exactness against the dense reference, fwd and all three gradients,
causal and not (interpret mode on the CPU mesh; chip_smoke.py's
``kernels`` phase holds the same comparison on a real TPU)."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops.attention import attention
from veles_tpu.ops.pallas_attention import pallas_attention


def _qkv(b=2, s=64, h=2, d=16, dv=None, seed=0):
    rng = numpy.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, dv or d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    out = pallas_attention(q, k, v, causal=causal, block_q=32,
                           block_k=32)
    ref = attention(q, k, v, causal=causal)
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv()

    def loss(core):
        def f(a, b, c):
            return jnp.sum(jnp.sin(core(a, b, c)))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g1 = loss(lambda a, b, c: pallas_attention(
        a, b, c, causal=causal, block_q=32, block_k=32))
    g2 = loss(lambda a, b, c: attention(a, b, c, causal=causal))
    for name, a, b in zip("qkv", g1, g2):
        numpy.testing.assert_allclose(
            numpy.asarray(a), numpy.asarray(b), atol=1e-4,
            err_msg="d%s diverged (causal=%s)" % (name, causal))


def test_dv_neq_dqk():
    q, k, v = _qkv(d=16, dv=8)
    out = pallas_attention(q, k, v, causal=True, block_q=32,
                           block_k=32)
    assert out.shape == v.shape[:1] + (q.shape[1],) + v.shape[2:]
    ref = attention(q, k, v, causal=True)
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), atol=2e-5)


def test_non_divisible_seq_pads_and_masks():
    # r5: odd lengths no longer raise — they pad to block multiples
    # and mask (the old ValueError contract is gone)
    from veles_tpu.ops.attention import attention as dense_attention
    q, k, v = _qkv(s=60)
    out = pallas_attention(q, k, v, block_q=32, block_k=32)
    ref = dense_attention(q, k, v)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_mha_apply_pallas_impl():
    from veles_tpu.models.attention import mha_apply
    rng = numpy.random.default_rng(1)
    d, heads = 8, 2
    x = jnp.asarray(rng.normal(size=(2, 32, d)), jnp.float32)
    params = {n: jnp.asarray(rng.normal(size=(d, d)) * 0.2,
                             jnp.float32)
              for n in ("wq", "wk", "wv", "wo")}
    out = mha_apply(params, x, heads, True, attn_impl="pallas")
    ref = mha_apply(params, x, heads, True, attn_impl="dense")
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), atol=5e-2)


def test_mha_apply_kernel_core_runs_per_shard_under_a_mesh():
    """Under the trainer's mesh the kernel cores run inside shard_map
    (a Mosaic call cannot be partitioned by GSPMD): same numbers as
    the unsharded call, batch over dp and heads over tp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veles_tpu.models.attention import mha_apply
    from veles_tpu.parallel import build_mesh
    rng = numpy.random.default_rng(2)
    d, heads = 32, 2
    x = jnp.asarray(rng.normal(size=(4, 32, d)), jnp.float32)
    params = {n: jnp.asarray(rng.normal(size=(d, d)) * 0.2,
                             jnp.float32)
              for n in ("wq", "wk", "wv", "wo")}
    ref = mha_apply(params, x, heads, True, attn_impl="pallas")
    mesh = build_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    fn = jax.jit(lambda p, x: mha_apply(
        p, x, heads, True, attn_impl="pallas", sp_mesh=mesh))
    out = fn(params, jax.device_put(
        x, NamedSharding(mesh, P("dp"))))
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), atol=1e-5)


class TestOddLengthsAndDmaSkip:
    """r5: pad-and-mask entry (odd sequence lengths keep the native
    kernels) and the clamped causal index maps."""

    def _qkv(self, seq, heads=2, dim=64, batch=2, seed=0, seq_k=None):
        rng = numpy.random.default_rng(seed)
        shape_q = (batch, seq, heads, dim)
        shape_k = (batch, seq_k or seq, heads, dim)
        q = jnp.asarray(rng.standard_normal(shape_q), jnp.float32)
        k = jnp.asarray(rng.standard_normal(shape_k), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape_k), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("seq", [1000, 1536, 100, 17])
    @pytest.mark.parametrize("causal", [False, True])
    def test_odd_seq_matches_dense(self, seq, causal):
        from veles_tpu.ops.attention import attention as dense_attention
        q, k, v = self._qkv(seq)
        out = pallas_attention(q, k, v, causal=causal)
        ref = dense_attention(q, k, v, causal=causal)
        assert out.shape == ref.shape
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_odd_seq_gradients(self):
        from veles_tpu.ops.attention import attention as dense_attention
        q, k, v = self._qkv(100)

        def f(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v, causal=True) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        gp = f(pallas_attention)
        gr = f(dense_attention)
        for a, b in zip(gp, gr):
            assert float(jnp.max(jnp.abs(a - b))) < 5e-5

    def test_cross_lengths(self):
        from veles_tpu.ops.attention import attention as dense_attention
        q, k, v = self._qkv(96, seq_k=200)
        out = pallas_attention(q, k, v, causal=False)
        ref = dense_attention(q, k, v, causal=False)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
