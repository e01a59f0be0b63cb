"""Tests of what the ``ouro-2.6b`` configuration adds to the benchmark,
on the CPU at tiny sizes: its counts against hand counts, the new
readers' arithmetic, its traffic file's ladder, and whole runs of the
``serve_closed_ouro`` driver -- sound, and with the timed path broken
underneath, where ``correct`` has to come out false."""

import os

import pytest

from benchmark import compare, ouro_flops, ouro_weights
from benchmark.tests.test_benchmark import HERE, MANIFEST, ROOT, \
    _context, load

CELL = "ouro26_serve_closed8_reason"
CONFIG = load(HERE, "configs", "ouro-2.6b.json")
SHAPES = CONFIG["shapes"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"shapes": dict(SHAPES, dim=64, heads=4, ffn=160, vocab=300,
                       positions=128, layers=3),
        "serve": {"max_slots": 4, "max_queue": 32, "block_size": 16,
                  "window": 128, "spec": False, "prefix_cache": False}}
#: the limit of the tiny stand-in, set as the cell's is: the bf16 program
#: at this size reads 0 to 0.09 of the int8 control's gap and the mildest
#: planted fault over 40 (CPU, 3 seeds; at the cell's depth of 48 layers
#: and dim 128: 0.059 and 2.9)
TINY_LIMITS = {"served_gap_vs_int8": 0.5, "stream_vs_final_mismatches": 0.0}


def test_counts_of_ouro_26b():
    # layer: q, k, v, o 4 x 2048^2, FFN 3 x 2048 x 5632, four norm
    # vectors; 48 of them; table and head; final norm; gate 2048 + 1
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert ouro_flops.layer_matmul_params(SHAPES) == layer == 51380224
    assert ouro_weights.count_params(SHAPES) == 2667974657 \
        == 48 * (layer + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049
    assert ouro_flops.layer_applications(SHAPES) == 192
    assert ouro_flops.forward_flops_per_token(SHAPES, 200) \
        == 2 * 192 * layer + 4 * 200 * 2048 * 192 + 2 * 2048 * 49152
    assert ouro_flops.stack_bytes(SHAPES) \
        == 48 * (2 * layer + 4 * 4 * 2048) + 4 * (2048 + 2049)
    assert ouro_flops.step_weight_bytes(SHAPES) \
        == 4 * ouro_flops.stack_bytes(SHAPES) + 2 * 2048 * 49152
    assert CONFIG["memory"]["parameters"] == 2667974657
    assert CONFIG["memory"]["kv_bytes_a_cached_token"] == 1572864 \
        == 192 * 2 * 2048 * 2
    # nothing cut: every key of the row as published, all 48 layers, 4
    # passes, the whole vocabulary
    assert not CONFIG["reduced"]
    entry = [c for c in MANIFEST["configs"] if c["name"] == "ouro-2.6b"]
    assert entry[0]["reduced"] == []
    for key, value in CONFIG["published"].items():
        assert CONFIG[key] == value, key
    pub = CONFIG["published"]
    assert (SHAPES["dim"], SHAPES["heads"], SHAPES["ffn"],
            SHAPES["vocab"], SHAPES["layers"], SHAPES["passes"],
            SHAPES["rope_theta"], SHAPES["norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["intermediate_size"], pub["vocab_size"],
        pub["num_hidden_layers"], pub["total_ut_steps"],
        pub["rope_theta"], pub["rms_norm_eps"])
    assert pub["head_dim"] * pub["num_attention_heads"] == SHAPES["dim"]
    assert pub["num_key_value_heads"] == pub["num_attention_heads"]


def test_readers_of_the_ouro_metrics():
    from benchmark.readers import mfu_serve_ouro, ratio, weight_stream_ouro
    record = {"shapes": SHAPES, "peak": PEAK, "window_s": 50.0,
              "processed_tokens_per_s": 200.0, "mean_context": 150.0}
    assert mfu_serve_ouro.read(record, {}) == pytest.approx(
        100 * 200 * ouro_flops.forward_flops_per_token(SHAPES, 150.0)
        / 197e12)
    assert mfu_serve_ouro.read({"shapes": SHAPES}, {}) is None
    spec = load(HERE, "metrics", "loop_weight_stream_pct.ouro.json")
    counters = {"veles_serving_steps_total": 1200.0}
    got = weight_stream_ouro.read(dict(record, counters=counters),
                                  spec["params"])
    assert got == pytest.approx(
        100 * 1200 * ouro_flops.step_weight_bytes(SHAPES) / 50 / 819e9)
    # at most 100: steps that fill the window at the chip's bandwidth
    fill = 50.0 * 819e9 / ouro_flops.step_weight_bytes(SHAPES)
    assert weight_stream_ouro.read(
        dict(record, counters={"veles_serving_steps_total": fill}),
        spec["params"]) == pytest.approx(100.0)
    # no steps, or the shapes of another chain: nothing to read
    assert weight_stream_ouro.read(dict(record, counters={}),
                                   spec["params"]) is None
    other = {k: v for k, v in SHAPES.items() if k != "passes"}
    assert weight_stream_ouro.read(
        dict(record, shapes=other, counters=counters),
        spec["params"]) is None
    spec = load(HERE, "metrics", "loop_exit_mass_before_last_pct.json")
    assert spec["reader"] == "ratio"
    assert ratio.read({"counters": {
        "veles_serving_stack_exit_mass_before_last_total": 330.0,
        "veles_serving_stack_rows_total": 500.0}}, spec["params"]) \
        == pytest.approx(66.0)
    # a parent without the counters: nothing to read, nothing raised
    assert ratio.read({"counters": {"veles_serving_steps_total": 5.0}},
                      spec["params"]) is None


def test_ouro_ladder_covers_every_bucket_the_mix_can_reach():
    from benchmark import traffic
    spec = load(HERE, "traffic", "serve_closed8_reason.json")
    ladder = spec["warmup"]["ladder"]
    bucket = lambda n: 1 << max(0, (n - 1).bit_length())
    pool = traffic.size_pool(spec["requests"])
    assert max(p + s for p, s in pool) <= 512 and len(pool) == 128
    assert CONFIG["serve"]["window"] == SHAPES["positions"] == 512
    # a request passes every depth from its prompt to its end
    reach = {(bucket(n), bucket(-(-depth // 16)))
             for p, s in pool for depth in range(p + 1, p + s + 1)
             for n in range(1, spec["clients"] + 1)}
    assert {t for _, t in reach} == {4, 8, 16, 32}
    warmed = set()
    for rung in ladder["rungs"]:
        prompt, steps, clients = (rung[k] for k in ("prompt", "steps",
                                                    "clients"))
        assert prompt + steps <= 512
        # a rung counts for ITS occupancy alone, from the step at which
        # its last request has joined: prompts over one chunk of 64 join
        # a chunk a pass, one request after the other
        joined = prompt + 1 + (0 if prompt <= 64
                               else -(-prompt // 64) * (clients - 1))
        assert joined < prompt + steps, rung
        warmed |= {(bucket(clients), bucket(-(-depth // 16)))
                   for depth in range(joined, prompt + steps)}
    assert reach <= warmed, sorted(reach - warmed)
    assert len(warmed) == 16
    sweep = traffic.sweep_list(spec["requests"], 49152, 3, 2)
    assert {-(-len(p) // 16) for p, _ in sweep} \
        == {-(-p // 16) for p, _ in pool}
    cell = [w for w in MANIFEST["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ouro-2.6b", "serve_closed8_reason", 1)


def test_the_benchmarks_ouro_reference_is_the_repos():
    with open(os.path.join(HERE, "ouro_reference.py")) as f, \
            open(os.path.join(ROOT, "veles_tpu", "models",
                              "ouro_reference.py")) as g:
        assert f.read() == g.read()


def _run(break_it=None, seed=2 ** 31 + 9, vocab=300, control=False,
         trace_seconds=None):
    from benchmark import run as brun
    from benchmark.drivers import serve_closed_ouro as driver
    mix = {"kind": "serve_closed_ouro", "clients": 4,
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
           "check_requests": 24, "limits": TINY_LIMITS}
    if trace_seconds:
        mix["trace_seconds"] = trace_seconds
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


def test_ouro_serve_run_is_correct_and_every_planted_fault_is_not():
    from benchmark import ouro_reference
    ok, values, record, lines = _run(control=True, trace_seconds=0.5)
    assert ok, values
    assert record["failed"] == 0 and record["attempted"] >= 4
    served = record["facts"]["served_by"]
    assert served["spec"] is False and served["prefix_cache"] is False
    counters = record["counters"]
    steps = counters["veles_serving_steps_total"]
    passes = TINY["shapes"]["passes"]
    # a step's counts are added as it is observed and the pass's own
    # counters when the pass ends: a reading of /metrics between the
    # two, at either end of the window, is one step ahead
    ran = counters["veles_serving_stack_passes_total"]
    assert steps > 0 and ran % passes == 0
    assert abs(ran - passes * steps) <= passes
    rows = counters["veles_serving_stack_rows_total"]
    assert abs(rows - counters["veles_serving_slot_busy_steps_total"]) \
        <= TINY["serve"]["max_slots"]
    assert counters["veles_serving_stack_exit_mass_total"] \
        == pytest.approx(rows, rel=1e-4)
    assert 0 < counters[
        "veles_serving_stack_exit_mass_before_last_total"] < rows
    assert counters.get("veles_serving_pool_copies_total", 0.0) == 0.0
    judged = {l["what"]: l for l in lines if l["phase"] == "control"}
    assert judged["program"]["correct"] is True
    wrong = ("int8 in the program's place",) + ouro_reference.FAULTS
    assert {w: judged[w]["correct"] for w in wrong} \
        == dict.fromkeys(wrong, False)


def test_ouro_fault_a_pass_keeps_no_rows_of_its_own():
    """Broken underneath: the insert leaves every cache layer past the
    first pass's as it was, so the later passes of a decode step attend
    rows the prefill never wrote."""
    def alter():
        from veles_tpu.serving import kv_slots
        sound = kv_slots._insert_stack_blocks
        layers = TINY["shapes"]["layers"]

        def first_pass_only(pool_k, pool_v, src_k, src_v, ids, start):
            keep = lambda src: src.at[layers:].set(0)
            return sound(pool_k, pool_v, keep(src_k), keep(src_v), ids,
                         start)
        kv_slots._insert_stack_blocks = first_pass_only

        def undo():
            kv_slots._insert_stack_blocks = sound
        return undo
    ok, values, _, _ = _run(alter)
    assert not ok, values


def test_ouro_fault_a_token_altered_where_it_is_produced():
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
