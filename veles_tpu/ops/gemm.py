"""GEMM — MXU matmul under the precision policy, plus a Pallas tiled
kernel with a fused-epilogue hook.

Rebuild of ocl/matrix_multiplication*.cl (351 LoC of hand-tiled
shared-memory GEMM in 3 precision levels) and the ``STORE_OUTPUT``
epilogue-injection hook (ref: ocl/gemm.store_output.cl).  On TPU:

- :func:`matmul` is the framework-wide matrix multiply: casts operands to
  the policy compute dtype (bf16 feeds the MXU at full rate), accumulates
  in the policy accumulation dtype, applies the policy
  ``jax.lax.Precision``.  The reference's Kahan/multipartial
  PRECISION_LEVEL ladder maps onto that precision enum + f32 accumulation
  (documented delta: SURVEY.md §7 "Numerics parity knobs").
- :func:`pallas_matmul` is the hand-tiled path for cases XLA cannot fuse:
  an arbitrary ``epilogue`` traced into the same kernel right before the
  store — the STORE_OUTPUT capability, TPU-style.
"""

import functools

import jax
import jax.numpy as jnp

from veles_tpu import dtypes


def matmul(a, b, out_dtype=None):
    """Policy matmul: ``a @ b`` on the MXU.

    Operands cast to ``root.common.precision.compute_dtype``,
    accumulation in ``accum_dtype``, output cast to ``out_dtype`` (default
    accum dtype — callers keeping bf16 activations pass it explicitly).
    """
    cd = dtypes.compute_dtype()
    ad = dtypes.accum_dtype()
    out = jax.lax.dot_general(
        a.astype(cd), b.astype(cd),
        dimension_numbers=(((a.ndim - 1,), (0,)), ((), ())),
        precision=dtypes.matmul_precision(),
        preferred_element_type=ad)
    return out.astype(out_dtype) if out_dtype is not None else out


def _mm_kernel(a_ref, b_ref, *rest, k_steps, epilogue, precision,
               has_scale):
    """Tiled GEMM kernel body: accumulate over the K grid axis in VMEM
    scratch, run the epilogue (including the optional fused per-column
    scale — the int8 weight-only dequant) on the final step, store."""
    import jax.experimental.pallas as pl
    if has_scale:
        scale_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
        scale_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if b.dtype != a.dtype:   # int8 weight tiles feed the MXU in the
        b = b.astype(a.dtype)  # activation dtype; dequant is deferred
    acc_ref[...] += jax.lax.dot_general(
        a, b,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        acc = acc_ref[...]
        if scale_ref is not None:
            acc = acc * scale_ref[...]        # [1, bn] broadcasts
        if epilogue is not None:
            acc = epilogue(acc)
        out_ref[...] = acc.astype(out_ref.dtype)


def _pallas_matmul_body(a, b, col_scale=None, block_m=256,
                        block_n=256, block_k=512, epilogue=None,
                        out_dtype=jnp.float32, interpret=False,
                        precision=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        "shapes must tile evenly; pad first (%s @ %s)" % (a.shape, b.shape)
    if precision is None:
        # f32 operands default to exact f32 passes; bf16 operands are
        # already the policy's fast path
        precision = (jax.lax.Precision.HIGHEST
                     if a.dtype == jnp.float32 else
                     jax.lax.Precision.DEFAULT)
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)
    kernel = functools.partial(_mm_kernel, k_steps=k_steps,
                               epilogue=epilogue, precision=precision,
                               has_scale=col_scale is not None)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    operands = [a, b]
    if col_scale is not None:
        in_specs.append(
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        operands.append(col_scale.reshape(1, n))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def pallas_matmul(a, b, block_m=256, block_n=256, block_k=512,
                  epilogue=None, out_dtype=jnp.float32, interpret=None,
                  precision=None, col_scale=None, backend=None):
    """Hand-tiled MXU GEMM with a fused epilogue.

    ``epilogue(acc) -> acc`` is traced into the kernel between the last
    accumulation and the store — the TPU-native STORE_OUTPUT hook
    (ref: ocl/gemm.store_output.cl usage in matrix_multiplication.cl).
    ``col_scale`` ([n] f32, optional) is a fused per-output-column
    multiply applied before ``epilogue`` — the int8 weight-only
    dequantization.  Shapes must tile evenly; callers pad (the
    framework zero-pads batches anyway for jit shape stability).

    ``interpret`` defaults to ``ops.common.use_interpret(backend)`` —
    the flash/lrn convention: off-TPU targets run the kernel under the
    pallas interpreter instead of tracing Mosaic (previously the
    default here was a hard ``False``, which left every CPU caller to
    pass ``interpret=True`` by hand or crash — the epilogue path went
    untested on tier-1)."""
    from veles_tpu.ops.common import use_interpret
    if interpret is None:
        interpret = use_interpret(backend)
    return _pallas_matmul_jit()(a, b, col_scale=col_scale,
                                block_m=block_m, block_n=block_n,
                                block_k=block_k, epilogue=epilogue,
                                out_dtype=out_dtype,
                                interpret=bool(interpret),
                                precision=precision)


from veles_tpu.telemetry import track_jit  # noqa: E402 (cycle-free: telemetry only needs logger)


@functools.lru_cache(maxsize=1)
def _pallas_matmul_jit():
    # built lazily (no module-level executable ref — the track_jit
    # lifetime note): one process-wide jitted entry, registered under
    # the stable name bench and the compile dashboards key on
    return track_jit("ops.pallas_matmul", jax.jit(
        _pallas_matmul_body,
        static_argnames=("block_m", "block_n", "block_k", "epilogue",
                         "out_dtype", "interpret", "precision")))


# -- int8 weight-only matmul ------------------------------------------------

def int8_weight_quantize(w):
    """Per-output-channel symmetric int8 weight quantization:
    ``w`` [k, n] → ``(wq int8 [k, n], scale f32 [n])`` with
    ``wq * scale ~= w`` (absmax per column; an all-zero column gets
    scale 0 and dequantizes to exact zeros)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = amax / 127.0
    q = jnp.where(scale[None, :] > 0.0,
                  wf / jnp.maximum(scale[None, :], 1e-30), 0.0)
    return jnp.clip(jnp.round(q), -127.0, 127.0).astype(jnp.int8), \
        scale.astype(jnp.float32)


def int8_matmul(a, wq, scale, out_dtype=jnp.float32, block_m=256,
                block_n=256, block_k=512, interpret=None,
                backend=None):
    """Weight-only int8 GEMM: ``a`` [m, k] (f32/bf16) times int8
    weights ``wq`` [k, n] with the per-column dequant ``scale`` [n]
    FUSED into the store epilogue — the accumulator sees raw int8
    products (full-rate MXU feed), the scale is applied once per
    output tile instead of dequantizing the whole weight matrix into
    HBM first.  Shapes that don't tile the block sizes fall back to
    an XLA dot with the same deferred-dequant math (serving buckets
    are powers of two, so the decode MLP/proj always takes the
    kernel)."""
    m, k = a.shape
    k2, n = wq.shape
    assert k == k2, (a.shape, wq.shape)
    if m % min(block_m, m) or n % min(block_n, n) \
            or k % min(block_k, k):
        acc = jax.lax.dot_general(
            a.astype(jnp.float32), wq.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (acc * scale[None, :]).astype(out_dtype)
    return pallas_matmul(a, wq, block_m=block_m, block_n=block_n,
                         block_k=block_k, out_dtype=out_dtype,
                         interpret=interpret, col_scale=scale,
                         backend=backend)
