"""One decode position of the gated delta rule over a POOL of per-slot
matrix states, as ONE Pallas kernel: each live row's state is read from
the pool by its slot, updated in VMEM and written back in place.

A delta-rule layer (Solar-Open2's KDA, GigaChat's Gated DeltaNet) keeps
a float32 state ``S`` [H, K, V] a slot in a pool ``[slots + 1, H, K,
V]`` (the last slot is the trash a padding row would use).  One decode
position of row n in slot ``slots[n]`` is ``models/solar.delta_step``:

    S <- alpha (.) S               (alpha per key channel, [H, K])
    S <- S + (beta k) (x) (v - S^T k)
    o = S^T q

In jnp that is a gather of the live rows' states, three passes over
them and a scatter back: six touches of 4 MB a row and layer at the
cells' widths, where reading and writing each once is enough.  Here the
grid is (row, block of ``HEADS_PER_BLOCK`` heads): the slots ride
scalar prefetch, each grid step's state block is fetched from the pool
at the row's slot, updated in float32 in VMEM, and written back to the
same place (the pool is aliased to the output, and donated by the
serving step), so each live state moves once each way.  The small
operands (q, k, alpha, beta k [b, H, K]; v [b, H, V]) and ``o`` ride
blocks of the row.  A head's state is ``S`` [K, V] with V along the
lanes, so what scales its rows (alpha, k, beta k, q) is wanted down a
column: ``GROUP`` heads' four vectors are stacked and turned once, and
each head reads its columns from the turned block.  The arithmetic is
``delta_step``'s, in its order.

A padding row (slot -1) moves no state: its state blocks' index stays
on the previous grid step's (or, before the first live row, on the next
live row's first block), so the pipeline fetches and writes nothing new
for it, and the step computes nothing; a batch of padding rows alone
stays on the trash slot's first block and hands it back as it was.
Its ``o`` is zero.

The decay arrives as ``alpha`` [b, H, K] whether it is per key channel
(Solar) or one scalar a head broadcast over K (GigaChat): the kernel
reads the shapes it is given.  The serving step calls it on the chip
alone (:func:`fused`); off the chip the step keeps the jnp form, and the
kernel runs under ``interpret=True`` only in its parity tests.  The call
is named ``veles_delta_state`` so that a trace finds it
(``benchmark/metrics/delta_state_roofline.json``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.common import use_interpret as _use_interpret
from veles_tpu.telemetry import track_jit

#: the kernel's name in the compiled program and in a trace
NAME = "veles_delta_state"
#: heads a grid step updates at most: 2 MB of float32 state at K = V =
#: 128, double-buffered each way
HEADS_PER_BLOCK = 32
#: heads whose columns are turned together, under one loop iteration
GROUP = 16


def fused():
    """True where the serving step updates the state through the
    kernel: on the chip."""
    return not _use_interpret()


def _divisor(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _kernel(slots_ref, rows_ref, hold_ref, q_ref, k_ref, a_ref, bk_ref,
            v_ref, s_ref, s_out, o_ref, group):
    """One (row, head block) grid step."""
    b = pl.program_id(0)

    @pl.when(slots_ref[b] >= 0)
    def _update():
        def heads(g, carry):
            at = pl.multiple_of(g * group, group)
            part = pl.ds(at, group)
            cols = jnp.concatenate(
                [a_ref[0, part], k_ref[0, part], bk_ref[0, part],
                 q_ref[0, part]], axis=0).T       # [K, 4 group]
            v = v_ref[0, part]
            o = []
            for h in range(group):
                alpha, k, bk, q = (cols[:, j * group + h:j * group + h + 1]
                                   for j in range(4))
                S = alpha * s_ref[0, at + h]
                pred = (S * k).sum(axis=0, keepdims=True)    # S^T k
                S = S + bk * (v[h:h + 1] - pred)
                s_out[0, at + h] = S
                o.append((S * q).sum(axis=0, keepdims=True))  # S^T q
            o_ref[0, part] = jnp.concatenate(o, axis=0)
            return carry
        jax.lax.fori_loop(0, s_ref.shape[1] // group, heads, 0)

    @pl.when(slots_ref[b] < 0)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(hold_ref[b] < 0)
    def _keep():
        # no live row at all: the block in hand is the trash slot's,
        # handed back as it came
        s_out[...] = s_ref[...]


def delta_state_update(slots, pool, q, k, v, alpha, beta, interpret=False):
    """One decode position of the delta rule for the rows of a batch,
    on their slots' states in ``pool`` [slots + 1, H, K, V] float32 (the
    result is the same buffer where the caller donates it).  ``slots``
    [b] int32, -1 for a padding row; ``q``, ``k``, ``alpha`` [b, H, K];
    ``v`` [b, H, V]; ``beta`` [b, H].  -> (pool', o [b, H, V] float32),
    each live row's state advanced by ``models/solar.delta_step``."""
    n = slots.shape[0]
    heads, width, value = pool.shape[1:]
    hb = _divisor(heads, HEADS_PER_BLOCK)
    blocks = heads // hb
    slots = jnp.asarray(slots, jnp.int32)
    live = slots >= 0
    # the last live row at or before each row (-1: none yet), and the
    # first live row of all: where a padding row's state index stays
    before = jax.lax.cummax(jnp.where(live, jnp.arange(n), -1))
    rows = jnp.where(
        before >= 0, slots[jnp.maximum(before, 0)],
        jnp.where(live.any(), slots[jnp.argmax(live)], pool.shape[0] - 1))
    hold = jnp.where(before >= 0, blocks - 1,
                     jnp.where(live.any(), 0, -1)).astype(jnp.int32)

    def state_at(r, j, slots, rows, hold):
        return (rows[r], jnp.where(slots[r] >= 0, j,
                                   jnp.maximum(hold[r], 0)), 0, 0)

    def row_at(r, j, slots, rows, hold):
        return (r, j, 0)
    f32 = jnp.float32
    small = pl.BlockSpec((1, hb, width), row_at)
    state = pl.BlockSpec((1, hb, width, value), state_at)
    out = pl.BlockSpec((1, hb, value), row_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, blocks),
        in_specs=[small, small, small, small, out, state],
        out_specs=[state, out])
    bk = beta.astype(f32)[..., None] * k.astype(f32)
    return pl.pallas_call(
        functools.partial(_kernel, group=_divisor(hb, GROUP)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, f32),
                   jax.ShapeDtypeStruct((n, heads, value), f32)],
        # operand 8 (after the three prefetched) is the pool
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=NAME,
    )(slots, rows, hold, q.astype(f32), k.astype(f32), alpha.astype(f32),
      bk, v.astype(f32), pool)


@functools.lru_cache(maxsize=1)
def step_call():
    """The call as a decode step makes it, under a jit of its own: the
    state layers of a step program, and the step programs of one
    occupancy bucket, share one trace and one lowering of it, where
    lowering the call anew for each layer costs ≈ 0.6 s of host time a
    step program of six layers (PERF.md §6).  Built once, on first use,
    and tracked, as every jit of the package is; nested in the step
    programs it compiles nothing of its own."""
    return track_jit("serving.delta_state", jax.jit(delta_state_update))
