"""Flash attention — the pallas TPU kernel path for the attention hot
op (SURVEY.md §5 "Long-context"; the reference's hottest ops were
hand-written CUDA/OpenCL kernels, e.g. ocl/forward.cl — on TPU the
equivalent discipline is a pallas kernel that keeps the score blocks
in VMEM instead of round-tripping the [seq, seq] matrix through HBM).

The kernel itself is ``jax.experimental.pallas.ops.tpu.flash_attention``
(a pallas_call program with custom fwd/dq/dkv kernels, shipped with
JAX the way cuDNN ships with CUDA); this module owns the framework's
integration: the [batch, seq, heads, head_dim] layout adaptation, the
block-size tuning that measured 2.6x over the kernel's defaults on
TPU v5e (1024-token Q blocks over 512-token K blocks, dropping to
uniform 512 when seq doesn't divide 1024; round 4), the
applicability check, and the numerically-equivalent streaming fallback
(ops.attention.blockwise_attention) for CPU meshes and odd shapes so
tests and virtual-device dryruns run the same model code."""

import functools

import jax.numpy as jnp

#: the kernel wants block-aligned tiles; Q blocks of 1024 over K
#: blocks of 512 measured fastest at head_dim 128 on TPU v5e
#: (21% over uniform 512 at seq 2048 / 16 heads, round 4) —
#: the applicability gate stays at the K granularity
_BLOCK_Q = 1024
_BLOCK = 512


def flash_available(q_shape, backend=None):
    """True when the pallas TPU kernel applies: TPU backend, seq a
    multiple of the block, head_dim a lane multiple.

    ``backend`` should be the platform of the device the computation
    actually targets (callers inside a unit pass
    ``unit.device.jax_device.platform``) — the process default backend
    is only a last resort, since a CPU-compiled program on a TPU host
    must NOT trace the TPU kernel."""
    from veles_tpu.ops.common import ACCEL_PLATFORMS, resolve_backend
    if resolve_backend(backend) not in ACCEL_PLATFORMS:
        return False
    seq, hd = q_shape[-3], q_shape[-1]
    return seq % _BLOCK == 0 and hd % 128 == 0


@functools.lru_cache(maxsize=None)
def _block_sizes(seq):
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    # the kernel's backward pass REQUIRES seq divisible by the q
    # block — a 512-but-not-1024 multiple (1536, 2560, …) drops to
    # the uniform 512 config the applicability gate guarantees
    bq = _BLOCK_Q if seq % _BLOCK_Q == 0 else _BLOCK
    bq = min(bq, seq)
    bk = min(_BLOCK, seq)
    return fa.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)


def flash_attention(q, k, v, causal=False, scale=None, backend=None):
    """Exact attention via the pallas TPU kernel.  q/k/v:
    [batch, seq, heads, head_dim] (the framework layout — seq-major so
    sp sharding stays a leading-dim spec); falls back to the streaming
    blockwise op when the kernel doesn't apply.  ``backend`` is the
    TARGET device platform (see :func:`flash_available`) — callers
    that know their device must pass it, or a CPU-compiled program on
    a TPU host would trace the TPU kernel."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if not flash_available(q.shape, backend=backend):
        from veles_tpu.ops.attention import blockwise_attention
        return blockwise_attention(q, k, v, block_size=_BLOCK,
                                   causal=causal, scale=scale)
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    qt, kt, vt = (jnp.swapaxes(t, -3, -2) for t in (q, k, v))
    o = fa.flash_attention(qt, kt, vt, causal=causal, sm_scale=scale,
                           block_sizes=_block_sizes(q.shape[-3]))
    return jnp.swapaxes(o, -3, -2)
