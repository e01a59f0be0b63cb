"""Tests of what the ``solar-open2-250b-ep8`` configuration adds to the
benchmark, on the CPU at tiny sizes: its counts against hand counts, the
new readers' arithmetic, its traffic file's ladder, and whole runs of the
``serve_closed_solar`` driver -- sound, and with the timed path broken
underneath, where ``correct`` has to come out false.

The manifest's lint holds ``reduced`` to depth, and this configuration
is also cut to one chip's share of the experts and of the vocabulary:
its file keeps the published ``n_routed_experts`` and ``vocab_size`` at
the top level (the router IS 320 wide), lists the depth alone under
``reduced`` and states the share under ``share`` and in ``shapes``, which
is what a run is built from."""

import os

import pytest

from benchmark import compare, solar_flops, solar_weights
from benchmark.tests import test_benchmark as lint
from benchmark.tests.test_benchmark import HERE, MANIFEST, ROOT, \
    _context, load

CELL = "solar250_serve_closed16_decode"
CONFIG = load(HERE, "configs", "solar-open2-250b-ep8.json")
SHAPES = CONFIG["shapes"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"shapes": dict(
    SHAPES, dim=64, heads=4, kv_heads=2, head_dim=16, low_rank=16,
    expert_ffn=32, experts=16, experts_per_token=4, held=[4, 6],
    vocab=300, positions=128, layers=5,
    kinds=["gqa", "kda", "kda", "kda", "gqa"]),
    "serve": {"max_slots": 4, "max_queue": 32, "block_size": 16,
              "window": 128, "spec": False, "prefix_cache": False}}
#: the limit of the tiny stand-in: the bf16 program at this size reads
#: 0.26 to 0.33 of the int8 control's gap over a whole sample (up to
#: 0.83 over 8 requests of a few hundred tokens, so the faults are
#: judged over 24 here), the mildest planted faults 5.5 (top_k_minus_1)
#: and 5.8 (weights_with_bias); the control in the program's place reads 1 by
#: construction and has to come out wrong (CPU, 2 seeds)
TINY_LIMITS = {"served_gap_vs_int8": 0.8, "stream_vs_final_mismatches": 0.0}


def test_counts_of_solar_open2_250b_ep8():
    # GQA operator 109,051,904; KDA operator 137,740,480; every layer: a
    # shared expert 15,728,640, the router 1,311,040, two norms 8,192, 40
    # held experts of 15,728,640; table and head 2 x 24,576 x 4,096
    assert solar_flops.expert_params(SHAPES) == 15728640
    gqa = solar_weights.layer_layout(SHAPES, "gqa")
    kda = solar_weights.layer_layout(SHAPES, "kda")
    count = lambda layer: sum(
        int(__import__("numpy").prod(s)) for s in layer.values())
    assert count(gqa) == 755245376 \
        == 109051904 + 15728640 + 1311040 + 8192 + 40 * 15728640
    assert count(kda) == 783933952 \
        == 137740480 + 15728640 + 1311040 + 8192 + 40 * 15728640
    assert solar_weights.count_params(SHAPES) == 6415425152 \
        == 2 * 755245376 + 6 * 783933952 + 2 * 100663296 + 4096
    assert CONFIG["memory"]["parameters_as_run"] == 6415425152
    outside = solar_flops.matmul_params_outside_held_experts(SHAPES)
    assert outside == 2 * 109051904 + 6 * (137740480 - 98304 - 64
                                           - 2 * 8192 - 128) \
        + 8 * (15728640 + 4096 * 320) + 100663296
    assert solar_flops.forward_flops_per_token(SHAPES, 300) \
        == 2 * (outside + 8 * 15728640) + 6 * 64 * 6 * 128 * 128 \
        + 2 * 4 * 300 * 8192
    assert solar_flops.expert_bytes(SHAPES) == 31457280
    assert solar_flops.state_row_bytes(SHAPES) == 4341760 \
        == 4 * 64 * 128 * 128 + 2 * 3 * 24576
    # the issue's reckoning of a step at 16 live rows and even routing:
    # 2.56 GB fixed, 3.35 GB of held experts, 0.81 GB of state
    moved = solar_flops.stream_bytes(SHAPES, 1, 8 * 13.3, 6 * 16)
    assert moved["weights"] == pytest.approx(2.59e9, rel=0.01)
    assert moved["experts"] == pytest.approx(3.35e9, rel=0.01)
    assert moved["state"] == pytest.approx(0.83e9, rel=0.01)


def test_the_configuration_keeps_every_published_width():
    published = CONFIG["published"]
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers"]
    for key, cut in CONFIG["reduced"].items():
        assert published[key] == cut["published"] != cut["run"] \
            == CONFIG[key]
    assert (SHAPES["dim"], SHAPES["heads"], SHAPES["kv_heads"],
            SHAPES["head_dim"], SHAPES["expert_ffn"], SHAPES["experts"],
            SHAPES["experts_per_token"], SHAPES["conv_kernel"]) \
        == (published["hidden_size"], published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"],
            published["moe_intermediate_size"],
            published["n_routed_experts"],
            published["num_experts_per_tok"],
            published["linear_attn_config"]["short_conv_kernel_size"])
    # the floors of a configuration: a whole period and four layers, 8
    # experts held, an eighth of the vocabulary
    share = CONFIG["share"]
    assert SHAPES["held"] == [0, share["n_routed_experts"]["run"]]
    assert SHAPES["held"][1] >= 8
    assert SHAPES["vocab"] == share["vocab_size"]["run"] \
        >= published["vocab_size"] // 8
    assert [i in published["gqa_layers"] for i in CONFIG["layers_run"]] \
        == [kind == "gqa" for kind in SHAPES["kinds"]]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] * SHAPES[
        "held"][1] == published["n_routed_experts"]
    assert CONFIG["serve"]["max_slots"] == load(
        HERE, "traffic", "serve_closed16_decode.json")["clients"] == 16


def test_the_share_is_stated_where_the_lint_admits_it():
    """The lint admits only depth under ``reduced``, so the chip's share
    is no key of it: the top-level keys keep the published counts (what
    the catalog's check compares), ``share`` gives published and held
    side by side, and the manifest's entry says so in its ``why``."""
    lint.test_manifest_lint()
    entry = [c for c in MANIFEST["configs"]
             if c["name"] == "solar-open2-250b-ep8"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert "0-39" in entry["why"] and "one chip of 8" in entry["why"]
    published = CONFIG["published"]
    for key, run in (("n_routed_experts", 40), ("vocab_size", 24576)):
        cut = CONFIG["share"][key]
        assert CONFIG[key] == published[key] == cut["published"] \
            == cut["key_at_top_level"] == 8 * cut["run"] == 8 * run
    cells = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1 \
        and cells[0]["config"] == entry["name"]


def test_the_cell_reports_what_the_issue_names():
    reports = {m["name"] for group in ("end_to_end", "per_layer")
               for m in MANIFEST[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"setup_s", "serve_tokens_per_s", "itl_p95_ms",
            "serve_mfu_pct.solar", "decode_stream_pct.solar",
            "kda_state_share_of_stream_pct", "moe_held_pairs_pct",
            "moe_experts_touched_per_layer_step",
            "device_idle_pct.serve", "compiles_in_window.serve",
            "decode_step_ms", "slot_occupancy_pct"} <= reports
    assert "ttft_p95_ms" not in reports
    assert not {m for m in reports if "lfm2" in m or "ouro" in m}


def test_readers_of_the_new_metrics():
    from benchmark.readers import mfu_serve_solar, ratio, stream_solar
    record = {"shapes": SHAPES, "peak": PEAK, "window_s": 50.0,
              "processed_tokens_per_s": 1000.0, "mean_context": 300.0}
    assert mfu_serve_solar.read(record, {}) == pytest.approx(
        100 * 1000 * solar_flops.forward_flops_per_token(SHAPES, 300)
        / 197e12)
    # another chain's record, or no rate: nothing to read, nothing raised
    assert mfu_serve_solar.read({"shapes": {"dim": 8}}, {}) is None
    assert mfu_serve_solar.read(dict(record, shapes={"dim": 8}), {}) is None
    peak = load(HERE, "metrics", "decode_stream_pct.solar.json")["params"]
    part = load(HERE, "metrics",
                "kda_state_share_of_stream_pct.json")["params"]
    # a parent without the counters: nothing to read
    assert stream_solar.read(
        dict(record, counters={"veles_serving_steps_total": 5.0}),
        peak) is None
    counters = {"veles_serving_steps_total": 1000.0,
                "veles_serving_moe_experts_touched_total": 1000 * 8 * 13.0,
                "veles_serving_state_rows_total": 1000 * 6 * 16.0}
    outside = solar_flops.step_bytes_outside_held_experts(SHAPES)
    total = 1000 * outside + 104000 * 31457280 + 96000 * 2 * 4341760
    assert stream_solar.read(dict(record, counters=counters), peak) \
        == pytest.approx(100 * total / 50 / 819e9)
    assert stream_solar.read(dict(record, counters=counters), part) \
        == pytest.approx(100 * 96000 * 2 * 4341760 / total)
    # at most 100 by construction: every held expert of every layer
    # touched and every slot live, in a window no longer than the chip
    # needs to stream the bytes
    most = outside + 8 * 40 * 31457280 + 6 * 16 * 2 * 4341760
    full = {"veles_serving_steps_total": 1.0,
            "veles_serving_moe_experts_touched_total": 320.0,
            "veles_serving_state_rows_total": 96.0}
    assert stream_solar.read(dict(record, counters=full,
                                  window_s=most / 819e9), peak) \
        == pytest.approx(100.0)
    held = load(HERE, "metrics", "moe_held_pairs_pct.json")["params"]
    assert ratio.read({"counters": {
        "veles_serving_moe_held_pairs_total": 16.0,
        "veles_serving_moe_pairs_total": 128.0}}, held) == 12.5
    assert ratio.read({"counters": {
        "veles_serving_moe_pairs_total": 128.0}}, held) is None


def test_solar_ladder_covers_every_bucket_the_mix_can_reach():
    from benchmark import traffic
    spec = load(HERE, "traffic", "serve_closed16_decode.json")
    ladder = spec["warmup"]["ladder"]
    bucket = lambda n: 1 << max(0, (n - 1).bit_length())
    pool = traffic.size_pool(spec["requests"])
    assert max(p + s for p, s in pool) <= 768 and len(pool) == 128
    # a request passes every depth from its prompt to its end
    reach = {(bucket(n), bucket(-(-depth // 16)))
             for p, s in pool for depth in range(p + 1, p + s + 1)
             for n in range(1, spec["clients"] + 1)}
    assert {n for n, _ in reach} == {1, 2, 4, 8, 16}
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
    sweep = traffic.sweep_list(spec["requests"], SHAPES["vocab"], 3, 2)
    assert {-(-len(p) // 16) for p, _ in sweep} \
        == {-(-p // 16) for p, _ in pool}
    assert max(max(p) for p, _ in sweep) < SHAPES["vocab"]


def test_the_benchmarks_solar_reference_is_the_repos():
    with open(os.path.join(HERE, "solar_reference.py")) as f, \
            open(os.path.join(ROOT, "veles_tpu", "models",
                              "solar_reference.py")) as g:
        assert f.read() == g.read()


def _run(break_it=None, seed=2 ** 31 + 7, vocab=300, control=False):
    from benchmark import run as brun
    from benchmark.drivers import serve_closed_solar as driver
    mix = {"kind": "serve_closed_solar", "clients": 4,
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


def test_solar_serve_run_is_correct_and_every_planted_fault_is_not(
        monkeypatch):
    from benchmark import solar_reference
    from benchmark.drivers import serve_closed_solar
    # the faults are judged over 24 requests here, not the cell's 8: at
    # this size 8 hold a few hundred tokens, which requests they are
    # depends on the window's timing, and the sound program read 0.83
    # on some (2 runs of 6) against the 0.26-0.33 of a whole sample
    monkeypatch.setattr(serve_closed_solar, "FAULT_REQUESTS", 24)
    ok, values, record, lines = _run(control=True)
    if not record["counters"]["veles_serving_steps_total"]:
        # the window's first step compiled through all of its 1.5 s (the
        # tiny ladder warms two buckets of a dozen; seen once, on a
        # loaded machine with a cold cache): once more, warm now
        ok, values, record, lines = _run(control=True)
    assert ok, values
    assert record["failed"] == 0 and record["attempted"] >= 4
    served = record["facts"]["served_by"]
    assert served["spec"] is False and served["prefix_cache"] is False
    counters = record["counters"]
    steps = counters["veles_serving_steps_total"]
    layers = len(TINY["shapes"]["kinds"])
    kda = TINY["shapes"]["kinds"].count("kda")
    # a step's counts are added as it is observed and the pass's own
    # counters when the pass ends: a reading of /metrics between the
    # two, at either end of the window, is one step ahead
    assert steps > 0
    assert abs(counters["veles_serving_moe_layer_steps_total"]
               - layers * steps) <= layers
    slots = TINY["serve"]["max_slots"]
    busy = counters["veles_serving_slot_busy_steps_total"]
    assert abs(counters["veles_serving_state_rows_total"]
               - kda * busy) <= kda * slots
    assert abs(counters["veles_serving_moe_pairs_total"]
               - 4 * layers * busy) <= 4 * layers * slots
    held = counters["veles_serving_moe_held_pairs_total"]
    assert 0 < counters["veles_serving_moe_experts_touched_total"] \
        <= held < counters["veles_serving_moe_pairs_total"]
    assert counters["veles_serving_moe_hottest_rows_total"] <= held
    # 6 of 16 experts held: 37.5 % of the pairs at even routing
    assert 0.2 < held / counters["veles_serving_moe_pairs_total"] < 0.55
    # every run's ``window`` line says what its seed's router did
    routing = record["facts"]["routing"]
    assert routing["steps"] == steps
    assert routing["held_pairs_pct"] == pytest.approx(
        100 * held / counters["veles_serving_moe_pairs_total"], abs=1e-3)
    assert 0 < routing["held_experts_touched_per_layer_step"] <= 6
    assert 0 < routing["live_rows_per_step"] <= slots
    assert serve_closed_solar.routing_facts(
        {"veles_serving_steps_total": 3.0}) == {"steps": 3.0}
    gaps = [l for l in lines if l["phase"] == "gaps"][0]
    assert 0.0 <= gaps["near_tie_share"] < 0.2
    judged = {l["what"]: l for l in lines if l["phase"] == "control"}
    assert judged["program"]["correct"] is True
    wrong = ("int8 in the program's place",) + solar_reference.FAULTS
    assert {w: judged[w]["correct"] for w in wrong} \
        == dict.fromkeys(wrong, False)


def test_solar_fault_matrix_state_not_inserted_into_the_slot():
    """Broken underneath: the prefilled state never reaches the slot (the
    state pool keeps what it had)."""
    def alter():
        from veles_tpu.serving import kv_slots
        sound = kv_slots._insert_state
        kv_slots._insert_state = lambda pool, src, slot: pool

        def undo():
            kv_slots._insert_state = sound
        return undo
    ok, values, _, _ = _run(alter)
    assert not ok, values


def test_solar_fault_a_token_altered_where_it_is_produced():
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
