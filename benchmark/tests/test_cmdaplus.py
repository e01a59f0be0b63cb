"""Tests of what the ``command-a-plus-ep8`` configuration adds to the
benchmark, on the CPU at tiny sizes: its counts against the hand counts,
the new readers' arithmetic, its traffic file's warm-up, and whole runs of
the ``serve_closed_cmdaplus`` driver -- sound, and with the timed path
broken underneath, where ``correct`` has to come out false.

As ``gigachat3.5-432b-ep16`` is, the configuration is cut to one chip's
share of the experts and of the vocabulary: its file keeps the published
``num_experts`` and ``vocab_size`` at the top level (the router IS 128
wide), lists the depth alone under ``reduced`` and states the share under
``share`` and in ``shapes``, which is what a run is built from."""

import json
import os

import numpy
import pytest

from benchmark import cmdaplus_flops, cmdaplus_weights, compare
from benchmark.tests import test_benchmark as lint
from benchmark.tests.test_benchmark import HERE, MANIFEST, ROOT, \
    _context, load

CELL = "cmdaplus_serve_closed16_rag"
CONFIG = load(HERE, "configs", "command-a-plus-ep8.json")
SHAPES = CONFIG["shapes"]
TRAFFIC = load(HERE, "traffic", "serve_closed16_rag.json")
#: a JSON-lines catalog of published model configurations, where one is
#: at hand: ``MODEL_CATALOG=path/to/architectures.jsonl``
CATALOG = os.environ.get("MODEL_CATALOG", "")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"shapes": dict(
    SHAPES, dim=64, heads=8, kv_heads=2, head_dim=8, expert_ffn=16,
    shared_ffn=16, experts=16, experts_per_token=4, held=[4, 6], shared=2,
    vocab=300, positions=128, sliding_window=16, rope_theta=1e3),
    "serve": {"max_slots": 4, "max_queue": 32, "block_size": 16,
              "window": 128, "spec": False, "prefix_cache": False}}
#: the limit of the tiny stand-in (set as the cell's is, from readings
#: of the bf16 program and of the planted faults at this size on the CPU:
#: PERF.md section 4)
TINY_LIMITS = {"served_gap_vs_int8": 0.5, "stream_vs_final_mismatches": 0.0}


def test_counts_of_command_a_plus_ep8():
    """The published arithmetic: attention, shared experts, router and
    norm, a routed expert, a layer, the chain, a step's bytes."""
    layout = cmdaplus_weights.layer_layout(SHAPES)
    count = lambda names: sum(int(numpy.prod(layout[n])) for n in names)
    assert count(("wq", "wk", "wv", "wo")) == 142606336
    assert count(("shared_w1", "shared_w3", "shared_w2")) == 201326592
    assert count(("router", "input_norm")) == 528384
    assert 142606336 + 201326592 + 528384 == 344461312
    assert cmdaplus_flops.expert_params(SHAPES) == 50331648
    assert count(layout) == 1149767680 == 344461312 + 16 * 50331648
    assert cmdaplus_weights.count_params(SHAPES) == 4733292544 \
        == 4 * 1149767680 + 134217728 + 4096
    assert CONFIG["memory"]["parameters_as_run"] == 4733292544
    # the published model: 218B in all, 25B active
    full = 32 * (344461312 + 128 * 50331648) + 262144 * 4096
    active = 32 * (344461312 + 8 * 50331648) + 262144 * 4096
    assert round(full / 1e9) == 218 and round(active / 1e9) == 25
    assert cmdaplus_flops.expert_bytes(SHAPES) == 100663296
    assert cmdaplus_flops.kv_row_bytes(SHAPES) == 4096
    # a step at 16 live rows of about 8,600 positions and 10.3 held
    # experts touched a layer: 3.03 GB of weights outside the held
    # experts (the tied table's 268 MB in it), 4.15 of experts, 1.37 of
    # rows
    moved = cmdaplus_flops.stream_bytes(SHAPES, 1, 4 * 10.3,
                                        3 * 16 * 4096, 16 * 8600)
    assert moved["weights"] == 3028369408 == 4 * (
        2 * 344461312 + 2 * 528384) + 2 * 134217728 + 4 * 4096
    assert moved["experts"] == pytest.approx(4.15e9, rel=0.01)
    assert moved["window"] + moved["full"] == pytest.approx(1.37e9,
                                                            rel=0.01)
    outside = cmdaplus_flops.matmul_params_outside_held_experts(SHAPES)
    assert outside == 4 * (344461312 - 4096) + 134217728
    assert cmdaplus_flops.forward_flops_per_token(SHAPES, 8192) == \
        2 * (outside + 4 * 8 * 16 / 128 * 50331648) \
        + 4 * 128 * 128 * (8192 + 3 * 4096)
    # a prompt token of the cell's median prompt, at half its length:
    # 3.43 GFLOP of products and 1.07 of attention
    assert cmdaplus_flops.forward_flops_per_token(SHAPES, 0) \
        == pytest.approx(3.43e9, rel=0.01)
    assert cmdaplus_flops.attention_flops(SHAPES, 4096) \
        == pytest.approx(1.07e9, rel=0.01)


def test_configuration_keeps_every_published_width():
    """Every number of the catalog's entry at its key, the depth alone
    changed; the floors of a configuration; the widths the cell runs."""
    published = CONFIG["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "command-a-plus-05-2026"]
        assert row and row[0]["config"] == published
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers"]
    assert (SHAPES["dim"], SHAPES["heads"], SHAPES["kv_heads"],
            SHAPES["head_dim"], SHAPES["expert_ffn"], SHAPES["shared_ffn"],
            SHAPES["experts"], SHAPES["experts_per_token"],
            SHAPES["shared"], SHAPES["sliding_window"],
            SHAPES["rope_theta"], SHAPES["norm_eps"],
            SHAPES["logit_scale"], SHAPES["norm_topk_prob"]) \
        == (published["hidden_size"], published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"],
            published["intermediate_size"],
            published["intermediate_size"], published["num_experts"],
            published["num_experts_per_tok"],
            published["num_shared_experts"], published["sliding_window"],
            published["rope_theta"], published["layer_norm_eps"],
            published["logit_scale"], published["norm_topk_prob"])
    assert SHAPES["held"] == [0, CONFIG["share"]["num_experts"]["run"]]
    assert SHAPES["held"][1] >= 8
    assert SHAPES["vocab"] == CONFIG["share"]["vocab_size"]["run"] \
        == published["vocab_size"] // 8
    kinds = [published["layer_types"][i] for i in CONFIG["layers_run"]]
    assert [k.split("_")[0] for k in kinds] == SHAPES["kinds"] \
        == ["sliding", "sliding", "sliding", "full"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] \
        * SHAPES["held"][1] == published["num_experts"]
    assert CONFIG["serve"]["max_slots"] == TRAFFIC["clients"] == 16
    assert CONFIG["serve"]["window"] == SHAPES["positions"] >= \
        TRAFFIC["requests"]["prompt"]["max"] \
        + TRAFFIC["requests"]["output"]["max"]
    assert not CONFIG["serve"]["spec"] \
        and not CONFIG["serve"]["prefix_cache"]
    for word in ("exchange", "logits", "vision"):
        assert any(word in item for item in CONFIG["left_out"])
    for key in ("average", "intermediate_size", "router"):
        assert any(key in item for item in CONFIG["assumed"])


def test_the_share_is_stated_and_the_cell_reports_what_the_issue_names():
    lint.test_manifest_lint()
    entry = [c for c in MANIFEST["configs"]
             if c["name"] == "command-a-plus-ep8"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert "0-15" in entry["why"] and "one chip of 8" in entry["why"]
    for key, run in (("num_experts", 16), ("vocab_size", 32768)):
        cut = CONFIG["share"][key]
        assert CONFIG[key] == CONFIG["published"][key] \
            == cut["published"] == cut["key_at_top_level"]
        assert cut["run"] == run
    cells = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1 \
        and cells[0]["config"] == entry["name"] \
        and cells[0]["traffic"] == "serve_closed16_rag"
    reports = {m["name"] for group in ("end_to_end", "per_layer")
               for m in MANIFEST[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"setup_s", "serve_tokens_per_s", "itl_p95_ms",
            "serve_mfu_pct.cmdaplus", "decode_stream_pct.cmdaplus",
            "window_kv_share_of_stream_pct", "full_kv_share_of_stream_pct",
            "moe_held_pairs_pct", "moe_experts_touched_per_layer_step",
            "device_idle_pct.serve", "compiles_in_window.serve",
            "decode_step_ms", "slot_occupancy_pct"} <= reports
    assert "ttft_p95_ms" not in reports
    assert not {m for m in reports if "lfm2" in m or "ouro" in m
                or "solar" in m or "gigachat" in m or "delta" in m
                or m.startswith(("kda", "gdn", "mla"))}


def test_readers_of_the_cmdaplus_metrics():
    from benchmark.readers import mfu_serve_cmdaplus, stream_cmdaplus
    record = {"shapes": SHAPES, "peak": PEAK, "window_s": 50.0,
              "processed_tokens_per_s": 30000.0, "mean_context": 6000.0}
    assert mfu_serve_cmdaplus.read(record, {}) == pytest.approx(
        100 * 30000 * cmdaplus_flops.forward_flops_per_token(SHAPES, 6000)
        / 197e12)
    assert mfu_serve_cmdaplus.read(dict(record, shapes={"dim": 8}),
                                   {}) is None
    names = ("decode_stream_pct.cmdaplus", "window_kv_share_of_stream_pct",
             "full_kv_share_of_stream_pct")
    params = {name: load(HERE, "metrics", name + ".json")["params"]
              for name in names}
    # a parent without the counters: nothing to read, nothing raised
    for name in names:
        assert stream_cmdaplus.read(dict(record, counters={
            "veles_serving_steps_total": 5.0,
            "veles_serving_moe_experts_touched_total": 9.0}),
            params[name]) is None
    counters = {"veles_serving_steps_total": 1000.0,
                "veles_serving_moe_experts_touched_total": 41200.0,
                "veles_serving_window_kv_rows_total": 1000 * 48 * 4096.0,
                "veles_serving_full_kv_rows_total": 1000 * 16 * 8600.0}
    moved = cmdaplus_flops.stream_bytes(SHAPES, 1000, 41200,
                                        1000 * 48 * 4096, 1000 * 16 * 8600)
    total = sum(moved.values())
    got = dict(record, counters=counters)
    assert stream_cmdaplus.read(got, params[names[0]]) \
        == pytest.approx(100 * total / 50 / 819e9)
    assert stream_cmdaplus.read(got, params[names[1]]) \
        == pytest.approx(100 * moved["window"] / total)
    assert stream_cmdaplus.read(got, params[names[2]]) \
        == pytest.approx(100 * moved["full"] / total)
    assert stream_cmdaplus.read(dict(got, shapes={"dim": 8}),
                                params[names[0]]) is None


def test_the_warm_up_meets_every_program_the_mix_runs():
    """The sweep meets every (staging width, last chunk width) of the
    pool's prompts with the shortest prompt of each, the ladder every
    (occupancy, depth) bucket of the decode steps; the prompts are as
    the cell states."""
    from benchmark import traffic
    from benchmark.drivers import serve_closed_cmdaplus as driver
    requests = TRAFFIC["requests"]
    pool = traffic.size_pool(requests)
    assert len(pool) == 128 and max(p + s for p, s in pool) == 15360
    assert 0.85 < sum(p > 4096 for p, _ in pool) / len(pool) < 0.97
    bucket = lambda n: 1 << max(0, (n - 1).bit_length())
    sweep = driver.sweep_list(requests, SHAPES["vocab"], 3, 2)
    assert {driver.chunk_programs(len(p)) for p, _ in sweep} \
        == {driver.chunk_programs(p) for p, _ in pool}
    assert max(max(p) for p, _ in sweep) < SHAPES["vocab"]
    for p, _ in sweep:
        assert driver.chunk_programs(len(p) - 1) != \
            driver.chunk_programs(len(p))
    # the chunk rule restated against the scheduler's own
    from veles_tpu.serving.scheduler import _bucket, chunk_width
    for p, _ in pool:
        width, off = _bucket(p, 256, 1 << 30), 0
        while off < p:
            c = chunk_width(p - off, off, 64, 256)
            last = c
            off += c
        assert driver.chunk_programs(p) == (width, last)
    reach = {(bucket(n), bucket(-(-depth // 16)))
             for p, s in pool for depth in range(p + 1, p + s + 1)
             for n in range(1, TRAFFIC["clients"] + 1)}
    assert {t for _, t in reach} == {256, 512, 1024}
    # a rung: the anchor alone, then the riders (one chunk of prefill
    # each) join its steps one a pass and, each one step longer than the
    # one before, leave them one at a time before it ends; the anchor is
    # the deepest row throughout, and its prompt is one of the sweep's
    ladder = TRAFFIC["warmup"]["ladder"]
    riders = ladder["riders"]
    assert riders["count"] == TRAFFIC["clients"] - 1
    assert driver.chunk_programs(riders["prompt"]) == (256, 256)
    assert riders["steps"] > riders["count"]
    warmed = set()
    for anchor in ladder["anchors"]:
        prompt, steps = anchor["prompt"], anchor["steps"]
        assert prompt in {len(p) for p, _ in sweep}
        depths = {bucket(-(-d // 16)) for d in (prompt + 1, prompt + steps)}
        assert len(depths) == 1          # a rung stays in ONE bucket
        longest = riders["steps"] + riders["count"] - 1
        assert bucket(-(-(riders["prompt"] + longest) // 16)) < min(depths)
        assert steps > riders["count"] + longest + 16
        warmed |= {(bucket(n), min(depths))
                   for n in range(1, riders["count"] + 2)}
    rungs = driver.ladder_list(ladder, SHAPES["vocab"], 3)
    assert [(len(a[0]), a[1]) for a, _ in rungs] == [
        (a["prompt"], a["steps"]) for a in ladder["anchors"]]
    assert all(len(r) == riders["count"] and len(r[0][0]) == riders["prompt"]
               and [n for _, n in r] == list(range(riders["steps"],
                                                   longest + 1))
               for _, r in rungs)
    assert reach <= warmed, sorted(reach - warmed)


def test_a_fault_is_planted_only_in_the_layers_it_changes():
    """The driver runs a planted fault's pass with the sound program in
    the layers of the kind the fault does not change
    (``fault_of_layer``), and such a layer computes with the fault
    planted what it computes without it, bit for bit."""
    import jax
    import jax.numpy as jnp
    from benchmark import cmdaplus_reference
    from benchmark.drivers.serve_closed_cmdaplus import (
        FULL_FAULTS, SLIDING_FAULTS, fault_of_layer, reference_cfg)
    assert set(SLIDING_FAULTS + FULL_FAULTS) < set(
        cmdaplus_reference.FAULTS)
    shapes = TINY["shapes"]
    layout = cmdaplus_weights.chain_layout(shapes)
    items = tuple(sorted(reference_cfg(shapes).items()))
    x = jax.random.normal(jax.random.PRNGKey(3), (40, shapes["dim"]))
    for kind, faults in (("sliding", FULL_FAULTS),
                         ("full", SLIDING_FAULTS)):
        layer = cmdaplus_weights.reference_layer(
            2 ** 31 + 5, 1 + 3 * (kind == "full"), layout)
        sound = cmdaplus_reference.layer_program(kind, items)(
            layer, x, jnp.int32(20))[0]
        for fault in faults:
            assert fault_of_layer(kind, fault) is None
            planted = cmdaplus_reference.layer_program(
                kind, items, "f32", fault)(layer, x, jnp.int32(20))[0]
            assert (planted == sound).all(), (kind, fault)
    assert fault_of_layer("full", "top_k_minus_1") == "top_k_minus_1"


def test_the_benchmarks_cmdaplus_reference_is_the_repos():
    with open(os.path.join(HERE, "cmdaplus_reference.py")) as f, \
            open(os.path.join(ROOT, "veles_tpu", "models",
                              "cohere_moe_reference.py")) as g:
        assert f.read() == g.read()


def test_weights_same_seed_same_leaves_and_an_even_router():
    """A leaf is the seed's alone; the router's columns have one norm."""
    shapes = TINY["shapes"]
    layout = cmdaplus_weights.chain_layout(shapes)
    seed = 2 ** 31 + 7
    a = cmdaplus_weights.reference_layer(seed, 2, layout)
    b = cmdaplus_weights.reference_layer(seed, 2, layout)
    assert all((a[n] == b[n]).all() for n in a)
    other = cmdaplus_weights.reference_layer(seed + 1, 2, layout)
    assert not (other["wq"] == a["wq"]).all()
    router = numpy.asarray(a["router"], numpy.float32)
    norms = numpy.sqrt((router * router).sum(axis=0))
    assert numpy.allclose(norms, cmdaplus_weights.ROUTER_COLUMN_NORM,
                          rtol=0.01)
    prog = cmdaplus_weights.program_leaf(seed, 2, "router",
                                         layout[2]["router"])
    assert str(prog.dtype) == "float32"
    assert (numpy.asarray(prog) == router).all()


@pytest.fixture
def fresh_executables():
    """Drop every compiled program this process holds before a whole
    driver run: each CPU executable keeps memory mappings of its own,
    and tier-1 runs every benchmark test in ONE process, where the
    drivers before this one had left ~60,000 of them, near the kernel's
    limit of 65,530 a process (past it a compile crashed)."""
    import gc
    import sys

    import jax
    import veles_tpu.serving.prefill  # noqa: F401  (the module, by name)
    from veles_tpu.models import generate
    from veles_tpu.serving import engine
    prefill = sys.modules["veles_tpu.serving.prefill"]
    for clear in (jax.clear_caches, engine.clear_step_cache,
                  generate.clear_decode_caches, prefill.clear_prefill_cache,
                  prefill.clear_chunk_cache, gc.collect):
        clear()
    yield


def _run(break_it=None, seed=2 ** 31 + 11, control=False):
    from benchmark import run as brun
    from benchmark.drivers import serve_closed_cmdaplus as driver
    mix = {"kind": "serve_closed_cmdaplus", "clients": 4,
           "requests": {"pool": 64, "pool_seed": 1, "passes": 4,
                        "prompt": {"median": 40, "sigma": 0.5, "min": 20,
                                   "max": 90},
                        "output": {"median": 20, "sigma": 0.5, "min": 8,
                                   "max": 32}, "kv_block": 16},
           "warmup": {"sweep_steps": 2,
                      "ladder": {"anchors": [{"prompt": 17, "steps": 15},
                                             {"prompt": 40, "steps": 24},
                                             {"prompt": 70, "steps": 40}],
                                 "riders": {"prompt": 10, "steps": 6,
                                            "count": 3}}},
           "check_requests": 24, "limits": TINY_LIMITS}
    ctx = _context(TINY, mix, seed)
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


def test_cmdaplus_serve_run_is_correct_and_every_planted_fault_is_not(
        monkeypatch, fresh_executables):
    from benchmark import cmdaplus_reference
    from benchmark.drivers import serve_closed_cmdaplus
    monkeypatch.setattr(serve_closed_cmdaplus, "FAULT_REQUESTS", 24)
    ok, values, record, lines = _run(control=True)
    if not record["counters"]["veles_serving_steps_total"]:
        ok, values, record, lines = _run(control=True)
    assert ok, values
    assert record["failed"] == 0 and record["attempted"] >= 4
    served = record["facts"]["served_by"]
    assert served["spec"] is False and served["prefix_cache"] is False
    counters = record["counters"]
    steps = counters["veles_serving_steps_total"]
    busy = counters["veles_serving_slot_busy_steps_total"]
    assert steps > 0
    # every live row reads min(length, 16) ring rows on each of three
    # sliding layers, its whole length on the full layer
    window = counters["veles_serving_window_kv_rows_total"]
    full = counters["veles_serving_full_kv_rows_total"]
    assert window == pytest.approx(3 * 16 * busy, rel=0.05)
    assert busy * 20 < full < busy * 128
    assert record["facts"]["routing"]["full_kv_rows_per_step"] \
        == pytest.approx(full / steps, abs=0.01)
    held = counters["veles_serving_moe_held_pairs_total"]
    # 6 of 16 experts held: 37.5 % of the pairs at even routing
    assert 0.15 < held / counters["veles_serving_moe_pairs_total"] < 0.6
    judged = {l["what"]: l for l in lines if l["phase"] == "control"}
    assert judged["program"]["correct"] is True
    # one key more in the window need not show: it is reported alone
    wrong = ("int8 in the program's place",) + tuple(
        f for f in cmdaplus_reference.FAULTS if f != "window_off_by_one")
    assert {w: judged[w]["correct"] for w in wrong} \
        == dict.fromkeys(wrong, False), {
            w: judged[w]["served_gap_vs_int8"] for w in wrong}
    assert "window_off_by_one" in judged


def test_cmdaplus_fault_rings_not_inserted_into_the_slot(fresh_executables):
    """Broken underneath: a prefilled ring never reaches the slot's (the
    pool keeps what it had): decode steps read stale or empty rings."""
    def alter():
        from veles_tpu.serving import kv_slots
        sound = kv_slots._insert_state

        def only_states(pool, src, slot):
            if next(iter(pool.values())).ndim == 3 and "k" in pool:
                return pool
            return sound(pool, src, slot)
        kv_slots._insert_state = only_states

        def undo():
            kv_slots._insert_state = sound
        return undo
    ok, values, _, _ = _run(alter, seed=2 ** 31 + 13)
    assert not ok, values


def test_the_driver_refuses_a_program_without_the_units(monkeypatch):
    """A parent that has no such unit fails at once, before anything is
    built or started."""
    from benchmark.drivers import serve_closed_cmdaplus as driver
    from veles_tpu.models import standard
    monkeypatch.setattr(standard, "LAYER_TYPES", {
        k: v for k, v in standard.LAYER_TYPES.items()
        if k != "tied_token_logits"})
    ctx = _context(TINY, {"kind": "serve_closed_cmdaplus"}, 5)
    with pytest.raises(SystemExit, match="tied_token_logits"):
        driver.build(ctx)
