"""Pipeline parallelism — GPipe-style stage execution over the ``pp``
mesh axis (SURVEY.md §2.3: the reference had no cross-device model
partitioning; here a stage is a mesh-axis shard and activations hop
stage→stage over ICI via ``ppermute``).

The partitioner stacks per-stage parameters along a leading stage dim
(sharded over ``pp``); the scheduler is the classic bubble loop: with S
stages and M microbatches, steps t = 0..S+M-2, stage s processes
microbatch t-s, activations ppermute forward each step."""

import functools

import jax
import jax.numpy as jnp


def _pvary(x, axis_name):
    """Mark a fresh (axis-invariant) value as varying over axis_name."""
    return jax.lax.pcast(x, axis_name, to="varying")


def split_stages(n_layers, n_stages):
    """Contiguous layer→stage assignment: [n_stages] lists of layer
    indices, balanced within ±1 (the first n_layers %% n_stages stages
    take one extra layer)."""
    if n_stages > n_layers:
        raise ValueError("more stages (%d) than layers (%d)"
                         % (n_stages, n_layers))
    base, extra = divmod(n_layers, n_stages)
    out, start = [], 0
    for s in range(n_stages):
        size = base + (1 if s < extra else 0)
        out.append(list(range(start, start + size)))
        start += size
    return out


def stack_stage_params(per_stage_params):
    """[stage][...pytree...] → one pytree with a leading stage dim
    (shard it over ``pp``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def gpipe_apply(stage_fn, stacked_params, microbatches, axis_name):
    """Run the pipeline under ``shard_map``.

    - ``stage_fn(params, h) -> h`` — one stage's forward (all stages
      must map activations of identical shape/dtype, the classic GPipe
      constraint);
    - ``stacked_params`` — per-device slice of the stage-stacked params
      (leading dim 1 under shard_map);
    - ``microbatches`` — [M, mb, ...] the SAME on every device
      (replicated input).

    Returns [M, mb, ...] final-stage outputs (valid on the last stage;
    callers broadcast/psum as needed — the wrapper below does)."""
    n = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    params = jax.tree.map(lambda p: p[0], stacked_params)
    steps = m + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    # carries derive FROM the input so they inherit its varying axes
    # (batch may be dp-sharded on a pp×dp mesh), then get marked
    # varying over the stage axis the loop rotates them around
    h0 = _pvary(microbatches[0] * 0, axis_name)
    outputs0 = _pvary(microbatches * 0, axis_name)
    microbatches = _pvary(microbatches, axis_name)

    def body(carry, t):
        recv, outputs = carry
        # stage 0 injects microbatch t; later stages consume the hop
        mb_idx = jnp.clip(t, 0, m - 1)
        h_in = jnp.where(stage == 0, microbatches[mb_idx], recv)
        h_out = stage_fn(params, h_in)
        # the last stage banks its result for microbatch t-(n-1)
        out_idx = jnp.clip(t - (n - 1), 0, m - 1)
        valid = (stage == n - 1) & (t >= n - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(valid, h_out, outputs[out_idx]), out_idx, 0)
        recv = jax.lax.ppermute(h_out, axis_name, perm)
        return (recv, outputs), None

    (recv, outputs), _ = jax.lax.scan(
        body, (h0, outputs0), jnp.arange(steps))
    # broadcast the last stage's outputs to every device so the result
    # is replicated (one psum over pp; zeros elsewhere)
    outputs = jnp.where(stage == n - 1, outputs, 0)
    return jax.lax.psum(outputs, axis_name)


def gpipe_train(mesh, stage_fn, stacked_params, x, n_micro,
                axis="pp", batch_axes=None):
    """Trace-friendly GPipe: runs INSIDE a jitted (and differentiable)
    program — no device_put, shardings applied as constraints.  The
    trainer (models/gd.py) calls this from its fused step, so the
    pipeline's backward (the transposed ppermute schedule) and the
    solver update live in the same XLA program.

    - ``stacked_params``: pytree with leading stage dim (traced
      values); constrained to P(axis) here;
    - ``x``: [batch, ...] activations entering stage 0;
    - ``batch_axes``: data-parallel mesh axes the batch dim is sharded
      over (pp×dp composition — each dp slice runs its own bubble
      schedule).

    Returns [batch, ...] outputs of the last stage, replicated over
    ``axis``."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    if x.shape[0] % n_micro:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (x.shape[0], n_micro))
    micro = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
    stage_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    stacked = jax.lax.with_sharding_constraint(
        stacked_params,
        jax.tree.map(lambda s: NamedSharding(mesh, s), stage_spec))
    mb_spec = P(None, tuple(batch_axes)) if batch_axes else P()
    micro = jax.lax.with_sharding_constraint(
        micro, NamedSharding(mesh, mb_spec))
    fn = shard_map(
        functools.partial(gpipe_apply, stage_fn, axis_name=axis),
        mesh=mesh, in_specs=(stage_spec, mb_spec), out_specs=mb_spec)
    out = fn(stacked, micro)
    return out.reshape((x.shape[0],) + out.shape[2:])


def pipeline_forward(mesh, stage_fn, per_stage_params, x, n_micro,
                     axis="pp", batch_axes=None):
    """Convenience wrapper: stack params, microbatch x [batch, ...],
    run the GPipe loop, return [batch, ...] outputs (replicated over
    ``pp``).

    ``batch_axes`` composes the pipeline with data parallelism: each
    microbatch's sample dim shards over those mesh axes (e.g.
    ``("dp",)`` on a pp×dp mesh — every dp slice runs its own bubble
    schedule on its batch shard, stages still hop over ``pp``)."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(per_stage_params) != mesh.shape[axis]:
        raise ValueError(
            "%d stages != %s axis size %d — each mesh position holds "
            "exactly one stage (group layers with split_stages first)"
            % (len(per_stage_params), axis, mesh.shape[axis]))
    if x.shape[0] % n_micro:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (x.shape[0], n_micro))
    micro = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
    stacked = stack_stage_params(per_stage_params)
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis)))
    stage_spec = jax.tree.map(lambda _: P(axis), stacked)
    mb_spec = P(None, tuple(batch_axes)) if batch_axes else P()

    fn = shard_map(
        functools.partial(gpipe_apply, stage_fn, axis_name=axis),
        mesh=mesh, in_specs=(stage_spec, mb_spec), out_specs=mb_spec)
    out = fn(stacked, micro)
    return out.reshape((x.shape[0],) + out.shape[2:])
