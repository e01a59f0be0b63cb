"""Tensor-parallel serving context — shard the jitted decode steps
over a ``tp`` mesh axis so a model (weights AND paged K/V pools)
bigger than one chip's HBM still serves.

Megatron-LM-style layer sharding (Shoeybi et al., 2019) mapped onto
the serving engine: each unit that wants to shard DECLARES its own
layout through ``tp_param_spec(name, tp)`` (see
``models/transformer.py`` — wq/wk/wv and the FFN up-projection are
column-parallel, wo and the FFN down-projection row-parallel, so the
only cross-chip traffic per layer is the two output reductions XLA
inserts), and the paged K/V block pools shard **head-wise** — each
chip stores ``[num_blocks, block_size, d/tp]`` of every pool, the
per-row int8 dequant scales riding along replicated (their amax
reduces over the sharded feature axis, which is exact, so quantized
values are bit-identical to the unsharded pools).  Everything
host-side — block tables, admission, the radix trie, spec drafting,
the scheduler loop — stays replicated logic; ONLY the jitted steps
shard, which is why the integration is a context object threaded
through the compiled-step factories (the executable caches key on
``tp`` so toggling never reuses a stale trace).

The context rides :class:`~veles_tpu.serving.kv_slots.PagedKVCache`
(``cache.tp_``) into ``serving/engine.py`` and is passed explicitly
to ``serving/prefill.py`` — the full set of jitted serving entry
points (``apply_prefill_chunk``, ``apply_step_paged``,
``verify_step_paged`` and the ``serving.kv_*`` block movers) then
runs SPMD over the mesh with no per-step host logic changes.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.parallel.mesh import build_mesh

#: how a paged layer's K/V pool ``[num_blocks, block_size, d]`` (and
#: a decode step's q/k/v rows ``[B, 1, d]``) lie on the mesh: head-wise
HEADWISE = P(None, None, "tp")


def tp_allreduce(x, axis, size):
    """Deterministic EXPLICIT all-reduce for the collective-overlap
    decode step (``engine._make_paged_step_tp`` — per-shard bodies
    under shard_map): sums ``x`` over the ``axis`` mesh axis of
    ``size`` shards.

    tp=2 reduces with ONE collective-permute plus a local add —
    bit-identical to ``psum`` (two-operand float addition is
    order-free) and expressed as a point-to-point the compiler can
    issue asynchronously, overlapping the hop with independent
    compute (the K/V pool writeback in the decode step).  Wider
    meshes all-gather and sum in FIXED shard order, so every shard
    folds the partials identically and the result is replicated
    exactly — the property the bit-parity tests lean on."""
    if size == 2:
        return x + jax.lax.ppermute(x, axis, [(0, 1), (1, 0)])
    return jnp.sum(jax.lax.all_gather(x, axis, axis=0), axis=0)


def tp_supported(forwards, size):
    """True when every cacheable block in the chain declares a
    tensor-parallel layout that divides over ``size`` shards
    (``tp_shardable`` — heads, model dim and FFN hidden all
    divisible; MoE and int8-weight decode blocks opt out).  The
    scheduler falls back to unsharded serving otherwise."""
    if size < 2:
        return False
    has = False
    for u in forwards:
        if hasattr(u, "init_cache"):
            has = True
            fn = getattr(u, "tp_shardable", None)
            if fn is None or not fn(size):
                return False
    return has


class ServingTP:
    """One serving replica's tensor-parallel mesh + placement cache.

    ``size`` chips off the front of ``devices`` (default
    ``jax.devices()``) form a ``{"tp": size}`` mesh
    (``parallel/mesh.py`` axis conventions).  The server's
    :class:`~veles_tpu.serving.weights.ServingWeights` places the
    chain's frozen weights on it ONCE, each leaf by its unit's
    declared ``tp_param_spec`` (replicated where it declares none);
    ``shard_pools`` places a paged layer's K/V pools head-wise and
    its scale arrays replicated."""

    def __init__(self, size, devices=None):
        self.size = int(size)
        if self.size < 2:
            raise ValueError("tp needs size >= 2 (got %d)" % size)
        devs = list(devices if devices is not None
                    else jax.devices())
        if len(devs) < self.size:
            raise ValueError(
                "tp=%d needs %d devices, found %d"
                % (self.size, self.size, len(devs)))
        self.mesh = build_mesh({"tp": self.size}, devs[:self.size])

    def sharding(self, spec):
        return NamedSharding(self.mesh, spec)

    def shard_pools(self, pools):
        """Place one cache's per-layer pool dicts on the mesh: K/V
        buffers ``[num_blocks, block_size, d]`` shard head-wise over
        the feature axis (each chip holds ``d/tp`` of every block);
        ``*_scale`` arrays (and any axis that doesn't divide)
        replicate — scales are indexed [block, row] like the pools,
        and a replicated copy is what keeps every later block move
        (insert/gather/export) shard-layout-free."""
        out = {}
        for i, layer in pools.items():
            got = {}
            for name, a in layer.items():
                if name.endswith("_scale") or a.ndim != 3 \
                        or a.shape[-1] % self.size:
                    got[name] = jax.device_put(a, self.sharding(P()))
                else:
                    got[name] = jax.device_put(
                        a, self.sharding(HEADWISE))
            out[i] = got
        return out

    def decode_attention(self, q, k_new, v_new, pool_k, pool_v,
                         tables, pos, heads):
        """``ops.paged_attention.paged_decode_attention`` inside the
        GSPMD step, each shard on its own heads over its own columns
        of the head-wise pools.  Its one-query products contract over
        the feature axis the mesh shards; a query column is zero
        outside its own head's rows, which the partitioner cannot
        know, so left to it every block sums its scores across the
        chips (one more all-reduce a block: PERF.md, PR 34)."""
        from veles_tpu.ops.paged_attention import paged_decode_attention
        return jax.shard_map(
            functools.partial(paged_decode_attention,
                              heads=heads // self.size),
            mesh=self.mesh, in_specs=(HEADWISE,) * 5 + (P(), P()),
            out_specs=(HEADWISE,) * 3, check_vma=False)(
                q, k_new, v_new, pool_k, pool_v, tables, pos)


def per_chip_bytes(tree):
    """The WORST per-device resident bytes of the jax arrays in a
    (possibly nested) dict tree — the honest "does this model fit one
    chip" measure: sharded arrays count ``nbytes / tp`` per chip,
    replicated arrays count in full on every chip.  This is the
    number ``bench.py tp`` holds fixed while growing d_model."""
    acc = {}

    def visit(x):
        if isinstance(x, dict):
            for v in x.values():
                visit(v)
        elif hasattr(x, "addressable_shards"):
            for sh in x.addressable_shards:
                acc[sh.device.id] = acc.get(sh.device.id, 0) \
                    + sh.data.nbytes
        elif hasattr(x, "nbytes"):   # plain single-device array
            acc[0] = acc.get(0, 0) + x.nbytes

    visit(tree)
    return max(acc.values()) if acc else 0
