"""The delta-rule state update as one Pallas call over the slot pool
(``veles_tpu/ops/pallas_delta.py``) against ``models/solar.delta_step``,
in interpret mode at tiny sizes: each live row's state advanced in its
own slot, in any slot order, padding rows moving nothing, the rest of
the pool left bit for bit; the decode steps of both delta-rule layers
through the call against their jnp form; the benchmark's readers of the
call's metrics."""

import functools

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.models.solar import delta_step
from veles_tpu.ops import pallas_delta

SLOTS, HEADS, KEY, VALUE = 7, 4, 16, 8
#: the same sums in another order: a few float32 ulps of values of one
LIMIT = dict(rtol=1e-5, atol=2e-6)
#: packed rows' slots, -1 a padding row (the pool's trash is slot 7)
LAYOUTS = {
    "in_order": [0, 1, 2, 3],
    "any_order": [5, 1, 6, 0],
    "padding_between": [3, -1, 0, -1],
    "padding_first": [-1, -1, 5, 2],
    "partly_occupied": [6, 2, -1, -1],
    "all_padding": [-1, -1, -1, -1],
}
#: (HEADS_PER_BLOCK, GROUP): the four heads in one grid step and one
#: loop iteration, or two steps a row of two one-head iterations each
TILINGS = {"one_block": (32, 16), "two_blocks": (2, 1)}


@pytest.fixture(autouse=True)
def _own_generator(monkeypatch):
    """The units made here draw their weights from a generator of their
    own: the process's shared one, from which the seeded chains of other
    test files draw, is left where it was."""
    from veles_tpu.prng import random_generator
    monkeypatch.setitem(random_generator._generators, "default",
                        random_generator.RandomGenerator())


def _operands(seed, rows, per_head_decay):
    rng = numpy.random.default_rng(seed)
    pool = rng.standard_normal((SLOTS + 1, HEADS, KEY, VALUE))
    q, k = (rng.standard_normal((rows, HEADS, KEY)) for _ in range(2))
    k /= numpy.linalg.norm(k, axis=-1, keepdims=True)
    if per_head_decay:        # GigaChat: one scalar a head over K
        alpha = numpy.broadcast_to(
            rng.uniform(0.5, 1.0, (rows, HEADS, 1)), (rows, HEADS, KEY))
    else:                     # Solar: one a key channel
        alpha = rng.uniform(0.5, 1.0, (rows, HEADS, KEY))
    v = rng.standard_normal((rows, HEADS, VALUE))
    beta = rng.uniform(0.0, 2.0, (rows, HEADS))
    return [jnp.asarray(x, jnp.float32)
            for x in (pool, q, k, v, alpha, beta)]


@pytest.mark.parametrize("tiling", sorted(TILINGS))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("decay", ["per_channel", "per_head"])
def test_kernel_advances_each_live_slot_as_delta_step_does(
        decay, layout, tiling, monkeypatch):
    heads_per_block, group = TILINGS[tiling]
    monkeypatch.setattr(pallas_delta, "HEADS_PER_BLOCK", heads_per_block)
    monkeypatch.setattr(pallas_delta, "GROUP", group)
    slots = LAYOUTS[layout]
    pool, q, k, v, alpha, beta = _operands(
        len(slots), len(slots), decay == "per_head")
    before = numpy.asarray(pool)
    got, o = pallas_delta.delta_state_update(
        jnp.asarray(slots, jnp.int32), pool, q, k, v, alpha, beta,
        interpret=True)
    got, o = numpy.asarray(got), numpy.asarray(o)
    assert got.dtype == o.dtype == numpy.float32
    live = [(n, s) for n, s in enumerate(slots) if s >= 0]
    if live:
        rows, at = (numpy.asarray(x) for x in zip(*live))
        want_S, want_o = delta_step(
            jnp.asarray(before[at]), q[rows], k[rows], v[rows],
            alpha[rows], beta[rows])
        numpy.testing.assert_allclose(got[at], want_S, **LIMIT)
        numpy.testing.assert_allclose(o[rows], want_o, **LIMIT)
    # a padding row's output is zero, and no slot that no row names
    # (the trash included) moves by a bit
    numpy.testing.assert_array_equal(
        o[[n for n, s in enumerate(slots) if s < 0]], 0.0)
    untouched = [s for s in range(SLOTS + 1) if s not in slots]
    numpy.testing.assert_array_equal(got[untouched], before[untouched])


def test_the_pool_comes_back_in_place():
    """Donated, the pool is the call's output buffer: the input is
    consumed and the result lies where it lay."""
    slots = jnp.asarray(LAYOUTS["partly_occupied"], jnp.int32)
    pool, q, k, v, alpha, beta = _operands(0, 4, False)
    where = pool.unsafe_buffer_pointer()
    step = jax.jit(functools.partial(pallas_delta.delta_state_update,
                                     interpret=True), donate_argnums=(1,))
    text = step.lower(slots, pool, q, k, v, alpha, beta).compile().as_text()
    assert "input_output_alias={ {0}: (1, {}, may-alias) }" in text
    got, _ = step(slots, pool, q, k, v, alpha, beta)
    assert pool.is_deleted()
    assert got.unsafe_buffer_pointer() == where


def _unit(kind):
    """One delta-rule layer at a tiny size, filled by itself."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    common = dict(dim=32, heads=HEADS, n_experts=8, top_k=2)
    if kind == "kda":
        spec = dict(type="solar_block", operator="kda", hidden=24,
                    kv_heads=2, head_dim=KEY, low_rank=8, **common)
    else:
        spec = dict(type="gigachat_block", operator="gdn", ffn="routed",
                    hidden=24, shared_hidden=24, q_lora_rank=16,
                    kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
                    v_head_dim=8, linear_key_heads=2,
                    linear_value_heads=HEADS, linear_head_dim=KEY,
                    **common)
    fw = make_forwards(AcceleratedWorkflow(None, name="delta-" + kind),
                       Array(numpy.zeros((2, 16), numpy.int32)),
                       [dict(type="embedding", vocab=40, dim=32,
                             learned_positions=False), spec,
                        dict(type="rms_token_logits", vocab=40)])
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    unit = fw[1]
    return unit, {n: jnp.asarray(a.mem)
                  for n, a in unit.param_arrays().items()}


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_decode_step_through_the_call_is_the_jnp_step(kind, monkeypatch):
    """A delta-rule layer's decode step with the Pallas call in its
    state's place (as on the chip, here interpreted) against the same
    step in jnp: the same output, conv rows and live states; the trash
    slot, which the jnp step's padding row writes, is left as it was."""
    unit, params = _unit(kind)
    assert unit.delta_state
    slots = jnp.asarray([4, -1, 1, 6], jnp.int32)
    rng = numpy.random.default_rng(1)
    pool = {name: jnp.asarray(rng.standard_normal(
        (SLOTS + 1,) + shape), jnp.float32)
        for name, shape in (
            ("conv", unit.init_cache(1, 1, jnp.float32)["conv"].shape[1:]),
            ("S", (HEADS, KEY, KEY)))}
    x = jnp.asarray(rng.standard_normal((4, 1, 32)), jnp.float32)
    pos = jnp.zeros((4,), jnp.int32)
    want_y, want = unit.apply_step_paged(params, x, pos, None, pool,
                                         slots=slots)
    assert not unit.state_in_place
    monkeypatch.setattr(pallas_delta, "fused", lambda: True)
    monkeypatch.setattr(pallas_delta, "step_call", lambda: jax.jit(
        functools.partial(pallas_delta.delta_state_update,
                          interpret=True)))
    got_y, got = unit.apply_step_paged(params, x, pos, None, pool,
                                       slots=slots)
    assert unit.state_in_place
    live = [0, 2, 3]
    numpy.testing.assert_allclose(numpy.asarray(got_y)[live],
                                  numpy.asarray(want_y)[live], **LIMIT)
    numpy.testing.assert_array_equal(got["conv"], want["conv"])
    numpy.testing.assert_allclose(got["S"][:SLOTS], want["S"][:SLOTS],
                                  **LIMIT)
    numpy.testing.assert_array_equal(got["S"][SLOTS], pool["S"][SLOTS])
    numpy.testing.assert_array_equal(got["moe"], want["moe"])


# -- the benchmark's readers of the call's metrics ----------------------------

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _record(seconds, calls, steps, live, layers, fused):
    """A window of ``steps`` decode steps of ``live`` rows over
    ``layers`` delta-rule layers, ``fused`` of whose states the call
    advanced."""
    return {"peak": PEAK,
            "trace": {"op_seconds": {"%veles_delta_state.3 = (f32[17,": seconds,
                                     "%convert.1 = f32[16] convert(": 1.0},
                      "op_counts": {"%veles_delta_state.3 = (f32[17,": calls,
                                    "%convert.1 = f32[16] convert(": 9}},
            "counters": {"veles_serving_steps_total": steps,
                         "veles_serving_slot_busy_steps_total": steps * live,
                         "veles_serving_delta_state_rows_total":
                             steps * live * layers,
                         "veles_serving_delta_state_fused_total": fused}}


@pytest.mark.parametrize("layers", [6, 4], ids=["solar", "gigachat"])
def test_delta_state_roofline_reads_the_bytes_of_the_live_rows(layers):
    from benchmark import run
    from benchmark.readers import roofline_delta
    spec = run.load_json(run.HERE, "metrics", "delta_state_roofline.json")
    # both cells' state: 64 heads of 128 x 128 float32, read and written
    # once, and q, k, alpha, beta k, v read and o written
    row = 4 * (2 * 64 * 128 * 128 + 6 * 64 * 128)
    assert spec["params"]["row_bytes"] == row
    # 1,000 steps of 16 live rows; 600 calls traced, at the least time
    # a call could take: the share is 100
    least = 16 * row / 819e9
    every = 1000 * 16 * layers
    got = roofline_delta.read(
        _record(600 * least, 600, 1000, 16, layers, every), spec["params"])
    assert got == pytest.approx(100.0)
    assert roofline_delta.read(
        _record(600 * least * 1.25, 600, 1000, 16, layers, every),
        spec["params"]) == pytest.approx(80.0)
    # half the states through the call: half the rows a call
    assert roofline_delta.read(
        _record(300 * least, 600, 1000, 16, layers, every // 2),
        spec["params"]) == pytest.approx(100.0)
    # the parent: no call in the trace, or no such counter
    assert roofline_delta.read(_record(0.0, 0, 1000, 16, layers, 0),
                               spec["params"]) is None
    bare = _record(1.0, 600, 1000, 16, layers, 0)
    del bare["counters"]["veles_serving_delta_state_fused_total"]
    assert roofline_delta.read(bare, spec["params"]) is None
    del bare["counters"]["veles_serving_delta_state_rows_total"]
    assert roofline_delta.read(bare, spec["params"]) is None


def test_delta_state_fused_pct_is_the_share_of_the_states_advanced():
    from benchmark import run
    from benchmark.readers import ratio
    spec = run.load_json(run.HERE, "metrics", "delta_state_fused_pct.json")
    counters = {"veles_serving_delta_state_rows_total": 960.0,
                "veles_serving_delta_state_fused_total": 960.0}
    assert ratio.read({"counters": counters}, spec["params"]) == 100.0
    assert ratio.read({"counters": {}}, spec["params"]) is None


def _counters():
    from veles_tpu.telemetry import metrics
    out = {}
    for line in metrics.render_prometheus().splitlines():
        if line.startswith("veles_serving_delta_state_"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def test_served_tokens_and_counters_through_the_call(monkeypatch):
    """Three requests on two slots through the scheduler, the state
    layers' decode steps through the (interpreted) call as on the chip:
    the tokens are the jnp steps' own, and every state the steps
    advanced is counted as advanced by the call."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.serving import engine
    from veles_tpu.serving.scheduler import InferenceScheduler
    block = dict(type="solar_block", dim=32, operator="kda", hidden=24,
                 heads=HEADS, kv_heads=2, head_dim=KEY, low_rank=8,
                 n_experts=8, top_k=2)
    fw = make_forwards(AcceleratedWorkflow(None, name="delta-served"),
                       Array(numpy.zeros((2, 64), numpy.int32)),
                       [dict(type="embedding", vocab=40, dim=32,
                             learned_positions=False), block,
                        dict(block, operator="gqa"), block,
                        dict(type="rms_token_logits", vocab=40)])
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    rng = numpy.random.default_rng(4)
    prompts = [rng.integers(0, 40, n).tolist() for n in (11, 5, 9)]

    def served():
        before = _counters()
        sched = InferenceScheduler(
            fw, max_slots=2, window=64, block_size=4, prefill_chunk=8,
            spec=False, prefix_cache=False, warm_buckets=False).start()
        try:
            out = [list(f.result(300)) for f in
                   [sched.submit(p, 12) for p in prompts]]
        finally:
            sched.close()
        moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()}
        return out, moved
    want, moved = served()
    assert moved["veles_serving_delta_state_rows_total"] > 0
    assert moved["veles_serving_delta_state_fused_total"] == 0
    traced = []

    def interpreted(*args):
        traced.append(args[0].shape)     # once a trace: the rows
        return pallas_delta.delta_state_update(*args, interpret=True)
    monkeypatch.setattr(pallas_delta, "fused", lambda: True)
    on_chip = jax.jit(interpreted)
    monkeypatch.setattr(pallas_delta, "step_call", lambda: on_chip)
    # the steps compiled above are the jnp form: trace them anew
    engine.clear_step_cache()
    try:
        got, moved = served()
    finally:
        engine.clear_step_cache()
    assert traced and got == want
    assert moved["veles_serving_delta_state_fused_total"] \
        == moved["veles_serving_delta_state_rows_total"] > 0
