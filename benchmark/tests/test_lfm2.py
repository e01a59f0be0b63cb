"""Tests of what the ``lfm2-24b-a2b-9l`` configuration adds to the
benchmark, on the CPU at tiny sizes: its counts against hand counts, the
two new readers' arithmetic, its traffic file's ladder, and whole runs of
the ``serve_closed_lfm2`` driver -- sound, and with the timed path broken
underneath, where ``correct`` has to come out false."""

import os

import numpy
import pytest

from benchmark import compare, lfm2_flops, lfm2_weights
from benchmark.tests.test_benchmark import HERE, ROOT, _context, load

CELL = "lfm2moe_serve_closed8_decode"
SHAPES = load(HERE, "configs", "lfm2-24b-a2b-9l.json")["shapes"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"shapes": dict(
    SHAPES, dim=64, heads=4, kv_heads=2, ffn=128, expert_ffn=32,
    experts=8, experts_per_token=2, vocab=300, positions=128, layers=5,
    kinds=[["conv", "dense"], ["attention", "routed"], ["conv", "routed"],
           ["conv", "routed"], ["attention", "routed"]]),
    "serve": {"max_slots": 4, "max_queue": 32, "block_size": 16,
              "window": 128, "spec": False, "prefix_cache": False}}
#: the limit of the tiny stand-in, set as the cell's is: the bf16 program
#: at this size reads 0.12 to 0.30 of the int8 control's gap, the mildest
#: planted fault (weights_with_bias) 2.8 to 3.4 times the program on the
#: same sample and 0.68 on this file's seed (CPU, 3 seeds)
TINY_LIMITS = {"served_gap_vs_int8": 0.45, "stream_vs_final_mismatches": 0.0}


def test_counts_of_lfm2_24b_a2b_9l():
    # expert 3 x 2048 x 1536; routed layer 64 of them + router 131,136;
    # conv operator 16,783,360; attention operator 10,485,888; dense FFN
    # 72,351,744; table 134,217,728; two norm vectors a layer, one last
    assert lfm2_flops.expert_params(SHAPES) == 9437184
    assert lfm2_weights.count_params(SHAPES, tied=True) == 5177950976
    assert lfm2_weights.count_params(SHAPES) == 5177950976 + 134217728
    assert lfm2_flops.active_params_per_token(SHAPES) == 648063744 \
        == (7 * 16783360 + 2 * 10485888 + 8 * 131136
            + 8 * 4 * 9437184 + 72351744 + 134217728)
    assert lfm2_flops.forward_flops_per_token(SHAPES, 300) \
        == 2 * 648063744 + 4 * 300 * 2048 * 2
    assert lfm2_flops.expert_bytes(SHAPES) == 18874368
    config = load(HERE, "configs", "lfm2-24b-a2b-9l.json")
    assert config["memory"]["parameters_published_tying"] == 5177950976
    assert config["memory"]["active_parameters_a_token"] == 648063744
    assert [config["layer_types"][i] == "conv"
            for i in config["layers_run"]] \
        == [op == "conv" for op, _ in SHAPES["kinds"]]
    assert [i < config["published"]["num_dense_layers"]
            for i in config["layers_run"]] \
        == [ffn == "dense" for _, ffn in SHAPES["kinds"]]
    for key, value in config["published"].items():   # widths as published
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_readers_of_the_new_metrics():
    from benchmark.readers import mfu_serve_lfm2, weight_stream_lfm2
    spec = load(HERE, "metrics", "decode_weight_stream_pct.lfm2moe.json")
    record = {"shapes": SHAPES, "peak": PEAK,
              "processed_tokens_per_s": 1000.0, "mean_context": 300.0}
    assert mfu_serve_lfm2.read(record, {}) == pytest.approx(
        100 * 1000 * (2 * 648063744 + 4 * 300 * 2048 * 2) / 197e12)
    assert mfu_serve_lfm2.read({"shapes": SHAPES}, {}) is None
    # a parent without the counters: nothing to read, nothing raised
    assert weight_stream_lfm2.read(
        dict(record, counters={"veles_serving_steps_total": 5.0,
                               "veles_serving_loop_step_seconds_total":
                               1.0}), spec["params"]) is None
    outside = lfm2_flops.step_bytes_outside_experts(SHAPES)
    counters = {"veles_serving_steps_total": 100.0,
                "veles_serving_moe_experts_touched_total": 100 * 8 * 25.0,
                "veles_serving_loop_step_seconds_total": 1.2}
    got = weight_stream_lfm2.read(dict(record, counters=counters),
                                  spec["params"])
    assert got == pytest.approx(
        100 * (100 * outside + 20000 * 18874368) / 1.2 / 819e9)
    # at most 100 by construction: every expert of every layer touched in
    # steps that took no longer than the chip needs to stream the bytes
    most = outside + 8 * 64 * 18874368
    full = {"veles_serving_steps_total": 1.0,
            "veles_serving_moe_experts_touched_total": 8 * 64.0,
            "veles_serving_loop_step_seconds_total": most / 819e9}
    assert weight_stream_lfm2.read(dict(record, counters=full),
                                   spec["params"]) == pytest.approx(100.0)
    assert outside == 2 * (648063744 - 8 * 4 * 9437184) \
        + 2 * (8 * 131136 + 7 * 6144 + 2 * 128) + 4 * (2 * 9 + 1) * 2048


def test_lfm2_ladder_covers_every_bucket_the_mix_can_reach():
    from benchmark import traffic
    spec = load(HERE, "traffic", "serve_closed8_decode.json")
    ladder = spec["warmup"]["ladder"]
    bucket = lambda n: 1 << max(0, (n - 1).bit_length())
    pool = traffic.size_pool(spec["requests"])
    assert max(p + s for p, s in pool) <= 768 and len(pool) == 128
    # a request passes every depth from its prompt to its end
    reach = {(bucket(n), bucket(-(-depth // 16)))
             for p, s in pool for depth in range(p + 1, p + s + 1)
             for n in range(1, spec["clients"] + 1)}
    warmed = set()
    for rung in ladder["rungs"]:
        prompt, steps, clients = (rung[k] for k in ("prompt", "steps",
                                                    "clients"))
        assert prompt + steps <= 1024
        # a rung counts for ITS occupancy alone, from the step at which
        # its last request has joined: prompts over one chunk of 64 join
        # a chunk a pass, one request after the other
        joined = prompt + 1 + (0 if prompt <= 64
                               else -(-prompt // 64) * (clients - 1))
        assert joined < prompt + steps, rung
        warmed |= {(bucket(clients), bucket(-(-depth // 16)))
                   for depth in range(joined, prompt + steps)}
    assert reach <= warmed, sorted(reach - warmed)
    sweep = traffic.sweep_list(spec["requests"], 65536, 3, 2)
    assert {-(-len(p) // 16) for p, _ in sweep} \
        == {-(-p // 16) for p, _ in pool}


def test_the_benchmarks_reference_is_the_repos():
    with open(os.path.join(HERE, "lfm2_reference.py")) as f, \
            open(os.path.join(ROOT, "veles_tpu", "models",
                              "lfm2_reference.py")) as g:
        assert f.read() == g.read()


def _run(break_it=None, seed=2 ** 31 + 7, vocab=300, control=False):
    from benchmark import run as brun
    from benchmark.drivers import serve_closed_lfm2 as driver
    mix = {"kind": "serve_closed_lfm2", "clients": 4,
           "requests": {"pool": 64, "pool_seed": 1, "passes": 4,
                        "prompt": {"median": 60, "sigma": 0.5, "min": 8,
                                   "max": 96},
                        "output": {"median": 16, "sigma": 0.5, "min": 6,
                                   "max": 32}, "kv_block": 16},
           "warmup": {"sweep_steps": 2,
                      "ladder": {"rungs": [
                          {"prompt": 8, "steps": 6, "clients": 2},
                          {"prompt": 64, "steps": 10, "clients": 4}]},
                      "requests": 8},
           "check_requests": 48, "limits": TINY_LIMITS}
    config = dict(TINY, shapes=dict(TINY["shapes"], vocab=vocab))
    ctx = _context(config, mix, seed)
    lines = []
    log = ctx.log
    ctx.log = lambda phase, **facts: (lines.append(dict(facts, phase=phase)),
                                      log(phase, **facts))
    undo = break_it() if break_it else None
    try:
        state = driver.setup(ctx)
        try:
            record = driver.window(state, 1.5,
                                   brun.Tracer("unused", False))
        finally:
            driver.release(state)
    finally:
        if undo:
            undo()
    compared = driver.check(ctx, record, control=control)
    return compare.verdict(compared), {c["name"]: c["value"]
                                       for c in compared}, record, lines


def test_lfm2_serve_run_is_correct_and_every_planted_fault_is_not():
    from benchmark import lfm2_reference
    ok, values, record, lines = _run(control=True)
    assert ok, values
    assert record["failed"] == 0 and record["attempted"] >= 4
    served = record["facts"]["served_by"]
    assert served["spec"] is False and served["prefix_cache"] is False
    counters = record["counters"]
    steps = counters["veles_serving_steps_total"]
    routed = sum(1 for _, ffn in TINY["shapes"]["kinds"]
                 if ffn == "routed")
    # a step's counts are added as it is observed and the pass's own
    # counters when the pass ends: a reading of /metrics between the
    # two, at either end of the window, is one step ahead
    layer_steps = counters["veles_serving_moe_layer_steps_total"]
    assert steps > 0 and layer_steps % routed == 0
    assert abs(layer_steps - routed * steps) <= routed
    # live rows x experts a token x routed layers, padding rows excluded
    slots = TINY["serve"]["max_slots"]
    assert abs(counters["veles_serving_moe_pairs_total"] - 2 * routed
               * counters["veles_serving_slot_busy_steps_total"]) \
        <= 2 * routed * slots
    assert counters["veles_serving_moe_layer_steps_total"] \
        <= counters["veles_serving_moe_experts_touched_total"] \
        <= counters["veles_serving_moe_pairs_total"]
    assert counters["veles_serving_moe_hottest_rows_total"] \
        <= counters["veles_serving_moe_pairs_total"]
    gaps = [l for l in lines if l["phase"] == "gaps"][0]
    assert 0.0 <= gaps["near_tie_share"] < 0.2
    judged = {l["what"]: l for l in lines if l["phase"] == "control"}
    assert judged["program"]["correct"] is True
    wrong = ("int8 in the program's place",) + lfm2_reference.FAULTS
    assert {w: judged[w]["correct"] for w in wrong} \
        == dict.fromkeys(wrong, False)


def test_lfm2_fault_conv_state_not_inserted_into_the_slot():
    """Broken underneath: the prefilled conv state never reaches the
    slot (the state pool keeps what it had)."""
    def alter():
        from veles_tpu.serving import kv_slots
        sound = kv_slots._insert_state
        kv_slots._insert_state = lambda pool, src, slot: pool

        def undo():
            kv_slots._insert_state = sound
        return undo
    ok, values, _, _ = _run(alter)
    assert not ok, values


def test_lfm2_fault_a_token_altered_where_it_is_produced():
    def alter():
        from veles_tpu.serving import engine
        sound = engine.sample_slots
        engine.sample_slots = lambda logits, *a: (
            sound(logits, *a) + 1) % logits.shape[-1]

        def undo():
            engine.sample_slots = sound
        return undo
    # a vocabulary of its own: the program caches its compiled steps by
    # shape, and the broken ones must not serve the other tests
    ok, values, _, _ = _run(alter, vocab=310)
    assert not ok, values
    assert values["served_gap_vs_int8"] > 10 * TINY_LIMITS[
        "served_gap_vs_int8"]
