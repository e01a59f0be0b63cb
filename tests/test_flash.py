"""Flash-attention wrapper (ops/flash.py): applicability gate and the
streaming fallback used on CPU meshes — the pallas kernel itself runs
only on real TPU (exercised by bench.py's transformer benchmark)."""

import jax.numpy as jnp
import numpy

from veles_tpu.ops.attention import attention
from veles_tpu.ops.flash import flash_attention, flash_available


def test_availability_gate():
    assert not flash_available((2, 512, 4, 128), backend="cpu")
    assert not flash_available((2, 500, 4, 128), backend="tpu")  # seq
    assert not flash_available((2, 512, 4, 64), backend="tpu")   # lane
    assert flash_available((2, 512, 4, 128), backend="tpu")
    assert flash_available((2, 1024, 8, 256), backend="tpu")


def test_cpu_fallback_matches_dense():
    rng = numpy.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 64, 2, 8)),
                           jnp.float32) for _ in range(3))
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal)
        ref = attention(q, k, v, causal=causal)
        numpy.testing.assert_allclose(numpy.asarray(out),
                                      numpy.asarray(ref), atol=1e-5)


def test_mha_apply_attn_impl_selection():
    """attn_impl plumbs through mha_apply; every impl agrees."""
    from veles_tpu.models.attention import mha_apply
    rng = numpy.random.default_rng(1)
    d, heads = 8, 2
    x = jnp.asarray(rng.normal(size=(2, 16, d)), jnp.float32)
    params = {n: jnp.asarray(rng.normal(size=(d, d)) * 0.2, jnp.float32)
              for n in ("wq", "wk", "wv", "wo")}
    outs = [mha_apply(params, x, heads, True, attn_impl=impl)
            for impl in ("dense", "blockwise", "flash", None)]
    for o in outs[1:]:
        numpy.testing.assert_allclose(numpy.asarray(o),
                                      numpy.asarray(outs[0]), atol=5e-2)
