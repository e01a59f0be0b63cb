"""Tests of the benchmark's own pieces, on the CPU at tiny sizes: the
yardstick's arithmetic, the lint of the manifest, the plain reference
against jax.grad, and whole runs of both drivers (everything of a run but
the look for a chip) -- sound, and with the timed path broken underneath,
where ``correct`` has to come out false.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy    # noqa: E402
import pytest   # noqa: E402

from benchmark import compare, flops, stats, trace_reduce, traffic, \
    weights   # noqa: E402

HERE = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(ROOT, "BENCHMARK.json")


# -- operations and bytes against hand counts ---------------------------------

def test_train_flops_of_cerebras_gpt_13b_8l():
    shapes = load(HERE, "configs", "cerebras-gpt-1.3b-8l.json")["shapes"]
    got = flops.train_flops_per_token(shapes, 2048)
    # 6 x (4 x 2048^2 + 2 x 2048 x 8192) x 8; 12 x 2048 x 2048 x 8;
    # 6 x 2048 x 50257
    assert got["blocks"] == 6 * 50331648 * 8 == 2415919104
    assert got["attention"] == 12 * 2048 * 2048 * 8 == 402653184
    assert got["head"] == 6 * 2048 * 50257 == 617558016
    assert got["total"] == pytest.approx(3.436e9, rel=1e-3)
    assert weights.count_params(shapes) == 612897873


def test_forward_flops_of_opt_67b_8l():
    shapes = load(HERE, "configs", "opt-6.7b-8l.json")["shapes"]
    got = flops.forward_flops_per_token(shapes, 512)
    assert got["blocks"] == 2 * (4 * 4096 ** 2 + 2 * 4096 * 16384) * 8
    assert got["head"] == 2 * 4096 * 50272
    assert got["attention"] == 4 * 512 * 4096 * 8
    assert weights.count_params(shapes) == 2031174752


def test_attention_kernel_cost_and_roofline():
    shapes = {"dim": 2048}
    cost = flops.attention_kernel_cost(shapes, 2048)
    assert cost["forward"]["flops"] == 2 * 2048 ** 3
    assert cost["backward"]["flops"] == 4 * 2048 ** 3
    assert cost["forward"]["bytes"] == 4 * 2048 * 2048 * 2
    peak = flops.peak_for("TPU v5 lite", load(HERE, "peaks.json"))
    least, side = flops.roofline_seconds(
        cost["forward"]["flops"], cost["forward"]["bytes"], peak)
    assert side == "compute"
    assert least == pytest.approx(2 * 2048 ** 3 / 197e12)
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_peaks_table_and_unknown_kind():
    table = load(HERE, "peaks.json")
    row = flops.peak_for("TPU v5 lite", table)
    assert row == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        flops.peak_for("TPU v9 imaginary", table)


# -- percentile and rate ------------------------------------------------------

def test_percentile_on_known_samples():
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_rate_shows_a_stall():
    steady = stats.rate(100 * 8192, 100 * 0.25)
    stalled = stats.rate(100 * 8192, 99 * 0.25 + 5.25)   # one 5 s stall
    assert steady == 32768
    assert stalled == pytest.approx(steady * 25 / 30)
    with pytest.raises(ValueError):
        stats.rate(1, 0)


# -- trace reduction ----------------------------------------------------------

def test_union_of_overlapping_intervals():
    assert trace_reduce.union_intervals(
        [(5, 7), (0, 2), (1, 3), (6, 6.5), (3, 4)]) == [(0, 4), (5, 7)]


def test_reduce_events_busy_self_time_and_gaps():
    line = [("loop", 0, 100), ("a", 10, 20), ("b", 40, 50),
            ("a", 200, 50), ("c", 1250, 750)]
    spans = [("bench.gd.run", 90, 120), ("bench.other", 2000, 10)]
    got = trace_reduce.reduce_events([line], spans, window_ns=4000)
    assert got["busy_s"] == pytest.approx((100 + 50 + 750) / 1e9)
    assert got["window_s"] == pytest.approx(4e-6)
    assert got["op_seconds"]["loop"] == pytest.approx(30 / 1e9)
    assert got["op_seconds"]["a"] == pytest.approx(70 / 1e9)
    assert got["op_counts"]["a"] == 2
    assert got["gap_seconds"]["bench.gd.run"] == pytest.approx(100 / 1e9)
    assert got["gap_seconds"]["inside_program"] == pytest.approx(1e-6)
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(1 - 900 / 4000)
    top = trace_reduce.breakdown(got)
    assert top["device_ops"][0][0] == "c"
    assert top["idle_gaps"][0][0] == "inside_program"


def test_span_of_the_device_events():
    assert trace_reduce.span_seconds(
        [[("a", 100, 50), ("b", 400, 100)], [("c", 50, 10)]]) \
        == pytest.approx(450 / 1e9)
    assert trace_reduce.span_seconds([]) == 0.0


def test_reduce_events_averages_over_chips():
    got = trace_reduce.reduce_events(
        [[("x", 0, 100)], [("x", 0, 300)]], [], window_ns=1000)
    assert got["busy_s"] == pytest.approx(200 / 1e9)
    assert got["chips"] == 2


def test_read_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))) \
            .block_until_ready()
    jax.profiler.stop_trace()
    lines, spans = trace_reduce.read_xplane(
        trace_reduce.find_xplane(str(tmp_path)))
    assert lines == []                     # no TPU plane on the CPU
    assert [s[0] for s in spans] == ["bench.step"]


# -- traffic ------------------------------------------------------------------

def test_traffic_same_seed_same_requests_other_seed_other_tokens():
    spec = load(HERE, "traffic", "serve_closed8.json")["requests"]
    spec = dict(spec, passes=1)
    one = traffic.request_list(spec, 2 ** 31 + 9, 50272)
    again = traffic.request_list(spec, 2 ** 31 + 9, 50272)
    other = traffic.request_list(spec, 7, 50272)
    assert one == again
    assert [(len(p), s) for p, s in one] == [(len(p), s) for p, s in other]
    sizes = lambda reqs: sorted((len(p), s) for p, s in reqs)
    assert sizes(one) == sizes(other) == sorted(traffic.size_pool(spec))
    assert one[0][0] != other[0][0]
    lens = [len(p) for p, _ in one]
    assert min(lens) >= 64 and max(lens) <= 1024
    assert 200 < numpy.median(lens) < 320
    assert all(16 <= s <= 192 for _, s in one)
    assert len({tuple(p[:8]) for p, _ in one}) == len(one)   # no sharing
    twice = traffic.request_list(dict(spec, passes=2), 7, 50272)
    assert len(twice) == 2 * len(one)
    assert sizes(twice[:len(one)]) == sizes(twice[len(one):]) == sizes(one)


def test_ladder_covers_every_bucket_the_mix_can_reach():
    spec = load(HERE, "traffic", "serve_closed8.json")
    ladder = spec["warmup"]["ladder"]
    batches = traffic.ladder_list(ladder, 50272, 3)
    assert [len(b) for b in batches] == [r["clients"]
                                         for r in ladder["rungs"]]
    bucket = lambda n: 1 << max(0, (n - 1).bit_length())
    pool = traffic.size_pool(spec["requests"])
    reach = {(bucket(n), bucket(-(-(p + s) // 16)))
             for p, s in pool for n in range(1, spec["clients"] + 1)}
    warmed = set()
    for rung in ladder["rungs"]:
        depths = {bucket(-(-(rung["prompt"] + n) // 16))
                  for n in (1, rung["steps"])}
        assert len(depths) == 1, rung      # a rung stays in ONE bucket
        warmed |= {(bucket(n), min(depths))
                   for n in range(1, rung["clients"] + 1)}
    assert reach <= warmed
    assert all(len(p) == rung["prompt"] and steps == rung["steps"]
               for batch, rung in zip(batches, ladder["rungs"])
               for p, steps in batch)
    sweep = traffic.sweep_list(spec["requests"], 50272, 3, 2)
    assert {-(-len(p) // 16) for p, _ in sweep} \
        == {-(-p // 16) for p, _ in pool}
    assert len(sweep) == len({-(-p // 16) for p, _ in pool})


def test_weights_same_seed_same_leaves_leaf_by_leaf():
    shapes = {"dim": 32, "heads": 2, "ffn": 64, "vocab": 100,
              "positions": 16, "layers": 2}
    chain = weights.make_chain(2 ** 31 + 5, shapes)
    layout = weights.chain_layout(shapes)
    for i in range(len(layout)):
        alone = weights.fresh_layer(2 ** 31 + 5, i, layout)
        for name in alone:
            assert numpy.array_equal(alone[name], chain[i][name])
    other = weights.make_chain(5, shapes)
    assert not numpy.array_equal(other[1]["wq"], chain[1]["wq"])
    assert abs(float(chain[1]["ln1_scale"].mean()) - 1.0) < 0.1
    rows = weights.token_rows(2 ** 31 + 5, 8, 16, 100)
    assert rows.shape == (8, 16) and rows.max() < 100
    assert numpy.array_equal(rows, weights.token_rows(2 ** 31 + 5, 8, 16,
                                                      100))


# -- the comparison's arithmetic ----------------------------------------------

def test_worst_norm_gap_and_dead_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6, "d": 3.0, "e": 2.5}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-6, "d": 0.0, "e": 2.5}
    assert compare.dead_leaves(ref) == {"c"}
    gap, leaf = compare.worst_norm_gap(prog, ref)
    assert leaf == "d" and gap == pytest.approx(1.0)   # a leaf unmoved
    prog["d"] = 3.0
    gap, leaf = compare.worst_norm_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1 / 2.0)  # median 2.0
    assert compare.verdict([{"value": 0.1, "limit": 0.2}])
    assert not compare.verdict([{"value": 0.3, "limit": 0.2}])
    assert not compare.verdict([{"value": float("nan"), "limit": 0.2}])
    assert not compare.verdict([])


# -- lint of the manifest and of every file under benchmark/ -------------------

def test_manifest_lint():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = list(cells) + list(configs) + list(e2e) \
        + [m["name"] for m in MANIFEST["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(e2e) | {m["name"] for m in MANIFEST["per_layer"]}) \
        == len(e2e) + len(MANIFEST["per_layer"])
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("benchmark/") and len(c["why"]) <= 200
        body = load(ROOT, c["file"])
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        for key in ("published", "assumed", "deployment", "shapes",
                    "memory"):
            assert key in body, (c["name"], key)
        for key, cut in body["reduced"].items():
            assert body[key] == cut["run"] != cut["published"]
            assert "layer" in key           # depth only, never a width
    pairs = set()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = load(HERE, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            HERE, "drivers", mix["kind"] + ".py"))
        assert mix["limits"]
    assert {w["config"] for w in cells.values()} == set(configs)
    reports = {name: {m["name"] for m in MANIFEST["end_to_end"]
                      if name in m.get("workloads", [name])}
               for name in cells}
    for name, got in reports.items():
        assert "setup_s" in got and len(got) >= 2
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= set(cells)
    layers = set()
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", list(cells)):
            assert m["moves"] in reports[cell], (m["name"], cell)
        spec = load(HERE, "metrics", m["name"] + ".json")
        assert os.path.isfile(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.add(m["layer"])
    for name in cells:
        assert any(name in m.get("workloads", [name])
                   for m in MANIFEST["per_layer"])
    fours = sum(1 for w in cells.values() if w["chips"] == 4)
    assert fours <= max(1, len(cells) // 4)
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_every_file_under_the_benchmark_is_well_named():
    for base, dirs, files in os.walk(HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert FILE.match(rel), rel
    for metric in os.listdir(os.path.join(HERE, "metrics")):
        assert metric[:-5] in {m["name"] for m in MANIFEST["per_layer"]}


def test_run_py_names_no_cell_configuration_or_metric():
    with open(os.path.join(HERE, "run.py")) as f:
        text = f.read()
    names = [w["name"] for w in MANIFEST["workloads"]] \
        + [c["name"] for c in MANIFEST["configs"]] \
        + [w["traffic"] for w in MANIFEST["workloads"]] \
        + [m["name"] for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]
           if m["name"] != "setup_s"]
    assert [n for n in names if n in text] == []


# -- the plain reference ------------------------------------------------------

TINY = {"dim": 128, "heads": 1, "ffn": 256, "vocab": 300, "positions": 32,
        "layers": 2}
HYPER = {"solver": "adam", "learning_rate": 2e-4, "lr_schedule": "cosine",
         "lr_schedule_params": {"total_steps": 3800, "floor": 0.1,
                                "warmup": 150},
         "batch_sequences": 4, "remat": False}


def test_reference_agrees_with_jax_grad_of_the_whole_model():
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    rows = weights.token_rows(5, 8, 32, 300)
    out = reference.train_steps(TINY, 2 ** 31 + 77, [rows[:4], rows[4:]],
                                HYPER)
    chain = weights.make_chain(2 ** 31 + 77, TINY)

    def loss(chain, batch):
        def one(tokens):
            x = reference.embed_apply(chain[0], tokens)
            for p in chain[1:-1]:
                x = reference.block_apply(p, x, TINY["heads"])
            return reference.next_token_loss(chain[-1], x, tokens)
        return jnp.mean(jax.vmap(one)(jnp.asarray(batch)))
    value, grads = jax.value_and_grad(loss)(chain, rows[:4])
    assert out["losses"][0] == pytest.approx(float(value), rel=1e-5)
    for i, layer in enumerate(grads):
        for name, g in layer.items():
            assert out["grad_norms"][i][name] == pytest.approx(
                float(jnp.linalg.norm(g)), rel=1e-4), (i, name)
    # lr(0) = 0 under the warm-up: only the second step moves a leaf
    assert 0 < out["change_norms"][1]["wq"] <= 2e-4 / 150 * 128 * 1.01
    assert reference.lr_multiplier(0, HYPER["lr_schedule_params"]) == 0
    assert reference.lr_multiplier(150, HYPER["lr_schedule_params"]) == 1


# -- whole runs of the drivers, sound and broken ------------------------------

def _context(config, mix, seed=2 ** 31 + 5):
    import jax
    from benchmark import run as brun
    from veles_tpu.backends import Device
    return brun.Context({"name": "tiny"}, config, mix, seed,
                        jax.devices()[0], Device(backend="numpy"))


#: limits for the TINY stand-ins of the two cells, set as the cells' own
#: are: the bf16 program at these sizes reads (CPU, 3 seeds) loss_gap
#: <= 1.6e-4, grad_norm_gap <= 0.0075, change_norm_gap <= 0.0022 and a
#: served gap of 0.02 to 0.1 of the int8 control's; the int8 control reads
#: grad_norm_gap >= 0.05, change_norm_gap >= 0.017, and 1 of itself
TINY_LIMITS = {
    "train_seq2048": {"loss_gap": 1e-3, "grad_norm_gap": 0.03,
                      "change_norm_gap": 0.01},
    "serve_closed8": {"served_gap_vs_int8": 0.5,
                      "stream_vs_final_mismatches": 0.0}}


def _cell_limits(traffic_name):
    cell = load(HERE, "traffic", traffic_name + ".json")["limits"]
    assert sorted(cell) == sorted(TINY_LIMITS[traffic_name])
    return TINY_LIMITS[traffic_name]


def _train_run(break_it=None, seed=2 ** 31 + 5):
    from benchmark import run as brun
    from benchmark.drivers import train
    mix = {"kind": "train", "rows": 16, "sequence": 32,
           "check_steps": 3,
           "limits": _cell_limits("train_seq2048")}
    ctx = _context({"shapes": TINY, "train": HYPER}, mix, seed)
    wf = train.build(ctx)
    if break_it:
        break_it(wf)
    state = train.observe(ctx, wf)
    record = train.window(state, 0.3, brun.Tracer("unused", False))
    train.release(state)
    compared = train.check(ctx, record)
    return compare.verdict(compared), {c["name"]: c["value"]
                                       for c in compared}, record


def test_train_run_is_correct_and_counts_all_the_work():
    ok, values, record = _train_run()
    assert ok, values
    steps = record["attempted"]
    assert record["end_to_end"]["train_tokens_per_s"] == pytest.approx(
        steps * 4 * 32 / record["window_s"])
    assert record["failed"] == 0 and len(record["step_ms"]) == steps


def test_train_fault_state_returned_unchanged():
    def frozen(wf):
        import jax.numpy as jnp
        gd, step = wf.gd, wf.gd.run

        def stuck():
            kept = [(arr, jnp.copy(arr.devmem)) for unit in wf.forwards
                    for arr in unit.param_arrays().values()]
            step()
            for arr, old in kept:
                arr.devmem = old
        gd.run = stuck
    ok, values, _ = _train_run(frozen)
    assert not ok
    assert values["change_norm_gap"] == pytest.approx(1.0, abs=1e-3)


def test_train_fault_half_of_the_batch_left_out():
    def half(wf):
        ev = wf.gd.evaluator
        whole = ev.loss
        ev.loss = lambda y, tokens, size: whole(y, tokens, size // 2)
    ok, values, _ = _train_run(half)
    assert not ok, values


def test_train_control_in_int8_is_not_correct():
    from benchmark import reference
    from benchmark.drivers import train
    rows = weights.token_rows(9, 16, 32, 300)
    batches = [rows[0:4], rows[4:8], rows[8:12]]
    ref = reference.train_steps(TINY, 9, batches, HYPER)
    low = reference.train_steps(TINY, 9, batches, HYPER, mode="int8")
    compared = train.judge(low, ref, _cell_limits("train_seq2048"))
    assert not compare.verdict(compared), compared
    same = train.judge(ref, ref, _cell_limits("train_seq2048"))
    assert compare.verdict(same)


SERVE_TINY = {"shapes": {"dim": 64, "heads": 2, "ffn": 256, "vocab": 300,
                         "positions": 128, "layers": 2},
              "serve": {"max_slots": 4, "max_queue": 32, "block_size": 16,
                        "spec": False}}


def _serve_run(break_it=None, seed=2 ** 31 + 5, vocab=300):
    from benchmark import run as brun
    from benchmark.drivers import serve_closed
    mix = {"kind": "serve_closed", "clients": 4,
           "requests": {"pool": 64, "pool_seed": 1, "passes": 4,
                        "prompt": {"median": 24, "sigma": 0.8, "min": 8,
                                   "max": 64},
                        "output": {"median": 8, "sigma": 0.6, "min": 4,
                                   "max": 24}, "kv_block": 16},
           "warmup": {"sweep_steps": 2,
                      "ladder": {"rungs": [
                          {"prompt": 8, "steps": 6, "clients": 2},
                          {"prompt": 64, "steps": 10, "clients": 4}]},
                      "requests": 8},
           "check_requests": 64, "limits": _cell_limits("serve_closed8")}
    config = dict(SERVE_TINY, shapes=dict(SERVE_TINY["shapes"],
                                          vocab=vocab))
    ctx = _context(config, mix, seed)
    undo = break_it() if break_it else None
    try:
        state = serve_closed.setup(ctx)
        try:
            record = serve_closed.window(state, 1.5,
                                         brun.Tracer("unused", False))
        finally:
            serve_closed.release(state)
    finally:
        if undo:
            undo()
    compared = serve_closed.check(ctx, record)
    return compare.verdict(compared), {c["name"]: c["value"]
                                       for c in compared}, record


def test_serve_run_is_correct_and_its_tails_are_of_all_requests():
    ok, values, record = _serve_run()
    assert ok, values
    assert record["failed"] == 0 and record["attempted"] >= 4
    t0 = record["t0"]
    assert t0 <= min(q["sent"] for q in record["records"])
    done = [r for r in record["records"]
            if r["done"] - t0 <= record["window_s"]]
    streamed = sum(1 for r in record["records"] for t in r["arrivals"]
                   if t - t0 <= record["window_s"])
    assert sum(len(r["tokens"]) for r in done) <= streamed
    assert record["end_to_end"]["serve_tokens_per_s"] == pytest.approx(
        streamed / record["window_s"])
    assert record["facts"]["gaps"] == sum(
        len(r["tokens"]) - 1 for r in record["records"])
    # as the cell: speculation off, counters read at the window's close
    assert record["facts"]["served_by"]["spec"] is False
    assert load(HERE, "configs", "opt-6.7b-8l.json")["serve"]["spec"] \
        is False
    steps = record["counters"]["veles_serving_slot_steps_total"]
    assert 0 < record["counters"]["veles_serving_slot_busy_steps_total"] \
        <= steps


def test_forward_passes_counts_what_the_window_saw():
    from benchmark.drivers import serve_closed
    records = [
        # prompt of 10, first token at 1.0, three more by the close
        {"prompt_len": 10, "arrivals": [1.0, 2.0, 3.0, 4.0, 9.0]},
        # still being prefilled at the close: nothing
        {"prompt_len": 100, "arrivals": [6.0, 7.0]},
        {"prompt_len": 4, "arrivals": [5.0]}]
    passes, contexts = serve_closed.forward_passes(records, close=5.0)
    assert passes == (10 + 3) + 4
    assert contexts == (50 + 3 * 10 + 6) + 8


def test_serve_fault_a_token_altered_where_it_is_produced():
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
    ok, values, _ = _serve_run(alter, vocab=310)
    assert not ok, values
    assert values["served_gap_vs_int8"] > 10 * _cell_limits(
        "serve_closed8")["served_gap_vs_int8"]


def test_serve_control_in_int8_is_not_correct():
    """The control in the program's place: requests decoded greedily by
    the int8 reference, judged as if they had been served."""
    import jax.numpy as jnp
    from benchmark import reference
    from benchmark.drivers import serve_closed
    shapes, seed = SERVE_TINY["shapes"], 77
    rng = numpy.random.default_rng(seed)
    records = [{"prompt": rng.integers(0, 300, 12).tolist(), "tokens": []}
               for _ in range(16)]
    for _ in range(12):
        texts = [r["prompt"] + r["tokens"] for r in records]
        for r, text, logits in zip(records, texts, reference.batch_logits(
                shapes, seed, texts, 128, "int8")):
            r["tokens"].append(int(jnp.argmax(logits[len(text) - 1])))
    numbers = serve_closed.gap_numbers(shapes, seed, records, 128, 24)
    assert numbers["int8"][1] > 0        # the precisions differ somewhere
    assert numbers["program"][1] / numbers["int8"][1] \
        > _cell_limits("serve_closed8")["served_gap_vs_int8"]


def test_served_gaps_against_plain_indexing():
    from benchmark import reference
    rng = numpy.random.default_rng(3)
    full = rng.normal(size=(12, 9)).astype(numpy.float32)
    low = full + rng.normal(size=(12, 9)).astype(numpy.float32)
    served = numpy.array([4, 0, 7, 0, 0], numpy.int32)   # 3 tokens, padded
    ours, theirs = reference.served_gaps(full, low, 8, served)
    for i, token in enumerate(served[:3]):
        row = full[8 + i]
        assert ours[i] == pytest.approx(row.max() - row[token])
        assert theirs[i] == pytest.approx(
            row.max() - row[low[8 + i].argmax()])
    assert numpy.asarray(ours).shape == (5,)      # padding past the end
