"""Telemetry subsystem (``veles_tpu/telemetry/``): registry thread
safety, Prometheus exposition, span pairing, Chrome-trace export,
compile tracking, EventSink resilience, and the instrumentation
overhead gate."""

import json
import logging
import os
import threading
import time
import urllib.request

import numpy
import pytest

from veles_tpu.config import root
from veles_tpu.logger import EventSink, events, timed
from veles_tpu.telemetry import (
    Histogram, MetricsRegistry, metrics, nearest_rank, span, track_jit)
from veles_tpu.telemetry.trace_export import export, spans_to_chrome
from veles_tpu.units import Unit
from veles_tpu.workflow import Workflow


# -- registry -----------------------------------------------------------------

def test_registry_thread_safety():
    """N concurrent writers over shared counter/gauge/histogram series
    lose no updates."""
    reg = MetricsRegistry()
    c = reg.counter("t_total")
    h = reg.histogram("t_seconds")
    fam = reg.counter("t_labeled_total", labelnames=("who",))
    n_threads, n_iter = 8, 500

    def work(i):
        child = fam.labels("w%d" % (i % 4))
        for k in range(n_iter):
            c.inc()
            h.observe(k * 1e-3)
            child.inc(2)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    total = sum(child.value for child in fam.children().values())
    assert total == 2 * n_threads * n_iter


def test_registry_get_or_create_and_type_clash():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total")


def test_nearest_rank_percentiles():
    """q=0.5 over a 2-element window returns the LOWER value; q=0.99
    never IndexErrors on tiny windows."""
    assert nearest_rank([1.0, 2.0], 0.5) == 1.0
    assert nearest_rank([1.0, 2.0], 0.99) == 2.0
    assert nearest_rank([7.0], 0.99) == 7.0
    assert nearest_rank([], 0.5) is None
    h = Histogram("h")
    h.observe(1.0)
    h.observe(2.0)
    assert h.percentile(0.5) == 1.0
    assert h.percentile(0.99) == 2.0


def test_serving_pct_helper():
    """The serving module's _pct is the shared nearest-rank."""
    from veles_tpu.serving.metrics import _pct
    assert _pct([10.0, 20.0], 0.5) == 10.0
    assert _pct([10.0, 20.0], 0.99) == 20.0
    assert _pct([], 0.99) is None


def test_prometheus_exposition_golden():
    """Exact text exposition for a small registry (format v0.0.4)."""
    reg = MetricsRegistry()
    c = reg.counter("veles_requests_total", "requests served",
                    labelnames=("code",))
    c.labels("200").inc(3)
    c.labels("500").inc()
    g = reg.gauge("veles_queue_depth", "waiting requests")
    g.set(7)
    h = reg.histogram("veles_latency_seconds", "request latency",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    expected = "\n".join([
        "# HELP veles_latency_seconds request latency",
        "# TYPE veles_latency_seconds histogram",
        'veles_latency_seconds_bucket{le="0.1"} 1',
        'veles_latency_seconds_bucket{le="1"} 2',
        'veles_latency_seconds_bucket{le="+Inf"} 3',
        "veles_latency_seconds_sum 5.55",
        "veles_latency_seconds_count 3",
        "# HELP veles_queue_depth waiting requests",
        "# TYPE veles_queue_depth gauge",
        "veles_queue_depth 7",
        "# HELP veles_requests_total requests served",
        "# TYPE veles_requests_total counter",
        'veles_requests_total{code="200"} 3',
        'veles_requests_total{code="500"} 1',
    ]) + "\n"
    assert reg.render_prometheus() == expected


def test_labeled_histogram_exposition_merges_labels():
    reg = MetricsRegistry()
    fam = reg.histogram("veles_unit_seconds", labelnames=("unit",),
                        buckets=(1.0,))
    fam.labels("loader").observe(0.5)
    text = reg.render_prometheus()
    assert 'veles_unit_seconds_bucket{unit="loader",le="1"} 1' in text
    assert 'veles_unit_seconds_count{unit="loader"} 1' in text


# -- spans + trace export -----------------------------------------------------

def _run_workflow(n_runs=2):
    class Work(Unit):
        def run(self):
            time.sleep(0.001)

    wf = Workflow(None, name="telemetry-wf")
    a = Work(wf, name="tele-a")
    b = Work(wf, name="tele-b")
    c = Work(wf, name="tele-c")
    a.link_from(wf.start_point)
    b.link_from(a)
    c.link_from(a, b)   # multi-input: exercises gate-wait
    wf.end_point.link_from(c)
    wf.initialize()
    for _ in range(n_runs):
        wf.run()
    return wf


def test_unit_span_pairing_and_histograms(tmp_path):
    """Every per-unit begin has a matching end (same span id) whose
    end event carries the duration; the shared histograms see every
    run."""
    log = tmp_path / "run.jsonl"
    events.open(str(log))
    try:
        wf = _run_workflow(n_runs=3)
    finally:
        events.close()
    recorded = [json.loads(line) for line in
                log.read_text().splitlines()]
    begins = {}
    pairs = 0
    for ev in recorded:
        if not str(ev["name"]).startswith("unit:"):
            continue
        if ev["kind"] == "begin":
            assert ev["span"] not in begins
            begins[ev["span"]] = ev
        elif ev["kind"] == "end":
            assert ev["span"] in begins, "end without begin"
            b = begins.pop(ev["span"])
            assert b["name"] == ev["name"]
            assert ev["duration"] >= 0
            assert "gate_wait" in ev
            pairs += 1
    assert not begins, "begin without end: %r" % begins
    # 3 runs x (3 Work units + Start/End plumbing) = 15 pairs
    assert pairs == 3 * 5
    # histograms: every unit's run count matches its timers
    fam = metrics.get("veles_unit_run_seconds")
    for u in wf:
        child = fam.children().get((u.name,))
        assert child is not None and child.count >= u.timers["runs"]
    # the multi-input unit accumulated gate-wait observations
    waits = metrics.get("veles_unit_gate_wait_seconds").children()
    assert waits[("tele-c",)].count >= 3


def test_chrome_trace_export_roundtrip(tmp_path):
    """A recorded workflow run's JSONL exports to structurally valid
    Chrome trace_event JSON: balanced B/E per pid/tid, X events carry
    dur, and it loads back as JSON."""
    log = tmp_path / "run.jsonl"
    events.open(str(log))
    try:
        _run_workflow(n_runs=2)
        with span("custom block", detail="x"):
            pass
        events.record("one-shot", "single", duration=0.25)
    finally:
        events.close()
    out = tmp_path / "trace.json"
    n = export(str(log), str(out))
    trace = json.loads(out.read_text())
    assert set(trace) >= {"traceEvents", "displayTimeUnit"}
    tev = trace["traceEvents"]
    assert len(tev) == n and n > 0
    stacks = {}
    for ev in tev:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        key = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ev["ph"] == "E":
            assert stacks.get(key), "E without B on track %r" % (key,)
            assert stacks[key].pop() == ev["name"], "unbalanced nesting"
        elif ev["ph"] == "X":
            assert ev["dur"] >= 0
    assert all(not s for s in stacks.values()), "unclosed B events"
    assert any(e["ph"] == "X" and e["name"] == "one-shot" for e in tev)
    # timeline starts at the first event (X events are backdated by
    # their duration, so they may sit before the origin)
    assert min(e["ts"] for e in tev if e["ph"] != "X") == 0.0


def test_trace_export_skips_malformed_lines(tmp_path):
    log = tmp_path / "torn.jsonl"
    good = {"name": "a", "kind": "single", "time": 1.0, "pid": 1,
            "tid": 1, "duration": 0.5}
    log.write_text(json.dumps(good) + "\n{torn tail")
    out = tmp_path / "trace.json"
    assert export(str(log), str(out)) == 1


def test_trace_export_cli(tmp_path, capsys):
    from veles_tpu.telemetry import trace_export
    log = tmp_path / "run.jsonl"
    log.write_text(json.dumps(
        {"name": "a", "kind": "begin", "time": 1.0, "pid": 1,
         "tid": 1}) + "\n")
    rc = trace_export.main([str(log), str(tmp_path / "t.json")])
    assert rc == 0
    assert trace_export.main([]) == 2


# -- compile tracking ---------------------------------------------------------

def test_track_jit_counts_compiles():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    # pin the persistent compilation cache OFF for this test: an
    # earlier test of the same worker (any scheduler start) may have
    # placed the on-disk cache, and a cache populated by a previous
    # run would label these compiles "hit" instead of "cold".  The
    # cache pins itself at the first compile, so the switch alone
    # does nothing to one that is already in use: reset it too.
    name = "test.track_jit_counts_compiles"
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    calls = metrics.counter(
        "veles_jit_calls_total", labelnames=("fn",)).labels(name)
    compiles = metrics.counter(
        "veles_jit_compiles_total",
        labelnames=("fn", "cache")).labels(name, "cold")
    hist = metrics.histogram(
        "veles_jit_compile_seconds", labelnames=("fn",)).labels(name)
    base_calls, base_cold, base_count = \
        calls.value, compiles.value, hist.count
    try:
        f = track_jit(name, jax.jit(lambda x: x * 2))
        assert int(f(numpy.int32(2))) == 4
        assert int(f(numpy.int32(3))) == 6        # cache hit
        assert float(f(numpy.float32(2.0))) == 4.0  # new dtype
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
    assert compiles.value == base_cold + 2  # cache off -> all cold
    assert calls.value == base_calls + 3
    assert hist.count == base_count + 2
    # the proxy stays transparent
    assert f._cache_size() >= 2


def test_persistent_compilation_cache_hits_labeled(tmp_path):
    """root.common.trace.compilation_cache_dir wiring: executables
    persist to disk on first compile, and a re-compile of the same
    program is served by the on-disk cache — labeled cache="hit" in
    veles_jit_compiles_total, distinct from the "cold" first one."""
    import jax
    from veles_tpu.__main__ import _enable_compilation_cache
    _enable_compilation_cache(str(tmp_path))
    try:
        f = track_jit("test.pcache", jax.jit(lambda x: x * 3 + 1))
        assert float(f(numpy.float32(2.0))) == 7.0
        assert list(tmp_path.iterdir()), "no cache files written"
        fam = metrics.counter("veles_jit_compiles_total",
                              labelnames=("fn", "cache"))
        assert fam.labels("test.pcache", "cold").value == 1
        # a fresh compile of the SAME program loads from disk
        jax.clear_caches()
        assert float(f(numpy.float32(2.0))) == 7.0
        assert fam.labels("test.pcache", "hit").value == 1
        assert fam.labels("test.pcache", "cold").value == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        try:
            from jax.experimental.compilation_cache import (
                compilation_cache)
            compilation_cache.reset_cache()
        except Exception:
            pass
        jax.clear_caches()


def test_compile_summary_shape():
    from veles_tpu.telemetry import compile_summary
    import jax
    f = track_jit("test.summary", jax.jit(lambda x: x + 1))
    f(1)
    summ = compile_summary()
    assert summ["total"]["compiles"] >= 1
    entry = summ["test.summary"]
    assert entry["compiles"] >= 1
    assert entry["compile_seconds_total"] > 0


# -- EventSink resilience (satellite fixes) -----------------------------------

def test_eventsink_open_failure_keeps_previous_sink(tmp_path):
    sink = EventSink(maxlen=16)
    first = tmp_path / "a.jsonl"
    sink.open(str(first))
    with pytest.raises(IsADirectoryError):
        sink.open(str(tmp_path))  # a directory: open() raises
    # the previous sink survived the failed open and still records
    sink.record("after-failed-open", "single")
    sink.close()
    assert "after-failed-open" in first.read_text()


def test_eventsink_record_survives_closed_file(tmp_path, caplog):
    sink = EventSink(maxlen=16)
    path = tmp_path / "b.jsonl"
    sink.open(str(path))
    sink._file.close()  # simulate the fd dying under the sink
    with caplog.at_level(logging.WARNING):
        for _ in range(3):  # must not raise, warn only once
            sink.record("hot-path", "single")
    warnings = [r for r in caplog.records
                if "file recording disabled" in r.getMessage()]
    assert len(warnings) == 1
    assert sink._file is None
    assert len(sink.ring) == 3  # the ring keeps recording


def test_timed_decorator_free_function_and_method():
    @timed
    def free_fn(x, y=1):
        return x + y

    class Thing:
        @timed
        def method(self, x):
            return x * 2

    before = len(events.ring)
    assert free_fn(2, y=3) == 5
    assert Thing().method(4) == 8
    tail = list(events.ring)[before:]
    names = [ev["name"] for ev in tail]
    assert any("free_fn" in n for n in names)
    assert any("Thing.method" in n for n in names)
    assert all("duration" in ev for ev in tail)


# -- export surfaces ----------------------------------------------------------

def test_web_status_metrics_endpoint():
    pytest.importorskip("tornado")
    from veles_tpu.web_status import WebStatusServer
    metrics.counter("veles_test_web_total").inc(5)
    server = WebStatusServer(port=0)
    # pick a free port: tornado binds at listen(); use an ephemeral one
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    server.port = port
    server.start(background=True)
    try:
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port, timeout=10)
        assert body.headers["Content-Type"].startswith("text/plain")
        text = body.read().decode()
        assert "veles_test_web_total 5" in text
        assert "# TYPE veles_test_web_total counter" in text
    finally:
        server.stop()


def test_rest_metrics_endpoint(tmp_path):
    """GET /metrics on the REST server returns Prometheus text
    covering serving, per-unit and compile series."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    api = None
    try:
        dev = Device(backend="numpy")
        wf = AcceleratedWorkflow(None, name="telemetry-rest")
        fw = make_forwards(
            wf, Array(numpy.zeros((1, 24), numpy.int32)), [
                {"type": "embedding", "vocab": 11, "dim": 8},
                {"type": "transformer_block", "heads": 2,
                 "causal": True},
                {"type": "token_logits", "vocab": 11}])
        for u in fw:
            u.initialize(device=dev)
        loader = RestfulLoader(wf, sample_shape=(24,),
                               minibatch_size=1, max_wait=10.0)
        loader.initialize(device=dev)
        api = RESTfulAPI(wf, loader=loader, forwards=fw,
                         name="telemetry-rest-api")
        api.output = fw[-1].output
        api.initialize()
        # drive one request through the scheduler so serving series
        # and the compiled prefill/step series are populated
        req = urllib.request.Request(
            "http://127.0.0.1:%d/generate" % api.port,
            data=json.dumps({"prompt": [3, 1, 4], "steps": 3}).encode(),
            headers={"Content-Type": "application/json"})
        json.load(urllib.request.urlopen(req, timeout=120))
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api.port, timeout=30)
        assert body.headers["Content-Type"].startswith("text/plain")
        text = body.read().decode()
        assert "veles_serving_requests_submitted_total" in text
        assert "veles_serving_ttft_ms_bucket" in text
        assert "veles_jit_compiles_total" in text
        assert 'fn="serving.prefill"' in text
        # valid exposition: every non-comment line is "name{...} value"
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            name_part, value = line.rsplit(" ", 1)
            assert name_part and not name_part[0].isdigit()
            float(value)  # parses as a number
    finally:
        if api is not None:
            api.stop()
            loader.close()
        root.common.precision.compute_dtype = saved


def test_cli_events_log_flag_opens_sink(tmp_path):
    """--events-log wires the JSONL sink before the run starts, so a
    workflow executed in the same process lands its spans in the file
    (--dump-config exits right after the flags are applied, keeping
    this test off the heavy training path)."""
    from veles_tpu.__main__ import Main
    log = tmp_path / "run.jsonl"
    try:
        assert Main(["--events-log", str(log),
                     "--dump-config"]).run() == 0
        _run_workflow(n_runs=1)
    finally:
        events.close()
    recorded = [json.loads(line) for line in
                log.read_text().splitlines()]
    names = {ev["name"] for ev in recorded}
    assert any(n.startswith("unit:") for n in names)
    assert "workflow run" in names
    out = tmp_path / "trace.json"
    assert export(str(log), str(out)) == len(recorded)


# -- overhead gate ------------------------------------------------------------

@pytest.mark.telemetry_overhead
def test_instrumentation_overhead_under_5_percent():
    """The per-unit instrumentation (2 span records + histogram
    observes per firing) must stay under 5% of a small workflow run
    with real (if modest) per-unit work."""

    class Busy(Unit):
        def initialize(self, **kwargs):
            super(Busy, self).initialize(**kwargs)
            self.mat = numpy.full((320, 320), 0.5)

        def run(self):
            # a few ms of real numpy work per firing — the scale at
            # which the per-firing instrumentation (~10 us) must be
            # invisible
            b = self.mat @ self.mat
            self.sink = float((b @ self.mat)[0, 0])

    def build():
        wf = Workflow(None, name="overhead-wf")
        prev = wf.start_point
        for i in range(6):
            u = Busy(wf, name="busy-%d" % i)
            u.link_from(prev)
            prev = u
        wf.end_point.link_from(prev)
        wf.initialize()
        return wf

    def timed(enabled, runs=4):
        root.common.telemetry.enabled = enabled
        t0 = time.perf_counter()
        for _ in range(runs):
            wf.run()
        return time.perf_counter() - t0

    wf = build()
    wf.run()  # settle
    saved = root.common.telemetry.get("enabled", True)

    def measure(reps=5):
        # on and off turn about, the best of each: under the six-worker
        # run the machine's load moves within a measurement, and two
        # blocks one after the other met different loads (16.6 % read
        # where the file alone reads under 2 %)
        pairs = [(timed(True), timed(False)) for _ in range(reps)]
        t_on, t_off = (min(t) for t in zip(*pairs))
        return (t_on - t_off) / t_off, t_on, t_off

    try:
        overhead, t_on, t_off = measure()
        if overhead >= 0.05:  # one retry rides out CI load spikes
            overhead, t_on, t_off = min(
                (overhead, t_on, t_off), measure())
    finally:
        root.common.telemetry.enabled = saved
    assert overhead < 0.05, \
        "instrumentation overhead %.1f%% >= 5%% (on %.4fs off %.4fs)" \
        % (overhead * 100, t_on, t_off)
