"""End-to-end request tracing + SLO accounting (PR 11): trace-id
stability across a router retry onto a second replica and across
stream first-byte pinning, the scheduler's phase timeline (queue →
admit → prefill → step → retire, with preempt→resume parented by one
trace id), ``/debug/requests`` consistency with ``check_kv()``, the
``trace_export --request`` multi-log merge with clock-skew
detection, SLO good/bad + burn-rate accounting, the flight-recorder
in-flight table, and tracing's cost to the loop as event counts."""

import json
import time
import urllib.error
import urllib.request
import zlib

import numpy
import pytest

from veles_tpu import faults
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.logger import events
from veles_tpu.memory import Array

pytestmark = pytest.mark.reqtrace


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


@pytest.fixture(autouse=True)
def disarm():
    faults.clear()
    yield
    faults.clear()


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name=name)
    fw = make_forwards(
        wf, Array(numpy.zeros((2, window), numpy.int32)), [
            {"type": "embedding", "vocab": vocab, "dim": dim},
            {"type": "transformer_block", "heads": heads,
             "causal": True},
            {"type": "token_logits", "vocab": vocab}])
    dev = Device(backend="numpy")
    for u in fw:
        u.initialize(device=dev)
    return fw


def _trace_events(trace):
    """Every ring event carrying ``trace`` — directly or inside a
    batched ``req.step`` span's traces map."""
    return [ev for ev in list(events.ring)
            if ev.get("trace") == trace
            or trace in (ev.get("traces") or {})]


# -- trace-id hygiene ---------------------------------------------------------

def test_trace_id_minting_and_sanitization():
    from veles_tpu.telemetry import reqtrace
    a, b = reqtrace.new_trace_id(), reqtrace.new_trace_id()
    assert a != b and len(a) == 16
    # a hostile header must not survive into replies or the JSONL
    # sink: CRLF, spaces and exotic bytes are stripped, length capped
    assert reqtrace.clean_trace_id("ok-1.2:3_X") == "ok-1.2:3_X"
    assert reqtrace.clean_trace_id("evil\r\nInjected: 1") \
        == "evilInjected:1"
    assert reqtrace.clean_trace_id("x" * 500) == "x" * 64
    assert reqtrace.clean_trace_id("\r\n ") is None
    assert reqtrace.ensure_trace_id(None)  # mints
    assert reqtrace.ensure_trace_id("keep") == "keep"


# -- the scheduler phase timeline ---------------------------------------------

def test_phase_timeline_across_preempt_resume(f32):
    """One trace id parents the WHOLE lifecycle including a forced
    preempt→resume: queue(cold) → admit → prefill → steps → preempt
    → queue(resume) → admit → retire, every span carrying the same
    id — and the stream first-byte contract holds (nothing re-emitted
    on resume, so tokens keep flowing on the same subscription)."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("reqtrace-preempt")
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             warm_buckets=False).start()
    try:
        faults.inject("serving.scheduler.step", "delay", arg=0.01)
        ts = sch.submit([3, 1, 4, 3, 1, 4], 10, stream=True,
                        trace="pr-1")
        assert ts.trace == "pr-1"
        it = iter(ts)
        first = next(it)
        sch.request_preempt()
        rest = [t for t in it]
        out = ts.result(240)
        assert [first] + rest == out[6:]  # resume re-emits nothing
    finally:
        faults.clear()
        sch.close()
    evs = _trace_events("pr-1")
    names = [ev["name"] for ev in evs]
    assert names.count("req.retire") == 1
    queues = [ev for ev in evs if ev["name"] == "req.queue"]
    assert [q["resume"] for q in queues] == [False, True]
    admits = [ev for ev in evs if ev["name"] == "req.admit"]
    assert len(admits) == 2 and admits[0]["blocks_claimed"] > 0
    assert any(ev["name"] == "serving.preempt" for ev in evs)
    assert any(ev["name"] == "req.first_token" for ev in evs)
    assert any(ev["name"] == "req.step" for ev in evs)
    retire = [ev for ev in evs if ev["name"] == "req.retire"][0]
    assert retire["outcome"] == "ok" and retire["preempts"] == 1
    # the preempt falls between the two queue spans in record order
    i_pre = names.index("serving.preempt")
    i_q2 = names.index("req.queue", names.index("req.queue") + 1)
    assert i_pre < i_q2


def test_debug_requests_consistent_with_check_kv(f32):
    """The live in-flight table must agree with the paged cache: the
    private (non-shared) blocks summed over admitted requests equal
    ``used_blocks`` minus the prefix cache's residents, and
    ``check_kv()`` passes with the table non-empty."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("reqtrace-debug")
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             warm_buckets=False).start()
    try:
        faults.inject("serving.scheduler.step", "delay", arg=0.02)
        futs = [sch.submit([7, 2, 5, 1], 12, trace="dbg-%d" % i)
                for i in range(3)]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            rows = sch.debug_requests()
            decoding = [r for r in rows if r["phase"] == "decode"]
            if len(decoding) >= 2:
                break
            time.sleep(0.01)
        assert len(decoding) >= 2
        for r in rows:
            assert r["trace"].startswith("dbg-")
            assert r["cls"] == "normal" and r["age_s"] >= 0
            assert r["blocks_budget"] > 0
        private = sum(r["blocks"] - r["blocks_shared"]
                      for r in rows)
        resident = sch.prefix_.resident if sch.prefix_ is not None \
            else 0
        assert private == sch.cache_.used_blocks - resident
        sch.check_kv()
        # the flight-recorder bundle embeds the same table
        from veles_tpu.telemetry.flight_recorder import recorder
        table = recorder.bundle("test").get("requests", [])
        assert any(str(r.get("trace", "")).startswith("dbg-")
                   for r in table)
        faults.clear()
        for f in futs:
            f.result(240)
    finally:
        faults.clear()
        sch.close()
    sch.check_kv()


# -- router propagation -------------------------------------------------------

def _make_replica(name, seed=1234):
    from veles_tpu import prng
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    from veles_tpu.serving.fleet import LocalReplica
    prng.get("default").seed(seed)
    fw = _tiny_fw(name, window=24, vocab=11, dim=8)
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    wf = AcceleratedWorkflow(None, name=name + "-wf")
    loader = RestfulLoader(wf, sample_shape=(24,), minibatch_size=1,
                           max_wait=10.0)
    loader.initialize(device=Device(backend="numpy"))
    api = RESTfulAPI(wf, loader=loader, forwards=fw,
                     name=name + "-api", max_slots=2,
                     serving_warm_buckets=False)
    api.output = fw[-1].output
    api.initialize()
    return LocalReplica(api, loader)


def _session_for(replica_ids, target_id):
    for i in range(10000):
        s = "sess%d" % i
        owner = max(replica_ids,
                    key=lambda rid: zlib.crc32(
                        ("%s|%s" % (s, rid)).encode()))
        if owner == target_id:
            return s
    raise AssertionError("no session hashed to %s" % target_id)


def test_trace_stability_across_router_retry_and_streams(f32):
    """Acceptance: ONE trace id survives a router retry onto a second
    replica (each attempt its own child span naming its replica),
    rides the reply header + structured error bodies, and stays on a
    pinned SSE stream whose terminal frame echoes it."""
    from veles_tpu.serving.router import Router
    r0 = _make_replica("rt-r0")
    r1 = _make_replica("rt-r1")
    router = Router(health_interval=0.2, retries=3,
                    retry_delay=0.01, breaker_failures=1).start()
    try:
        for r in (r0, r1):
            router.add_replica(r.host, r.port,
                               replica_id=r.replica_id)
        sess = _session_for([r0.replica_id, r1.replica_id],
                            r0.replica_id)
        # pin attempt 1 to r0, drop it at the router; the 1-failure
        # breaker opens r0 so attempt 2 MUST cross to r1
        faults.inject("router.forward", "drop", times=5,
                      key=r0.replica_id)
        req = urllib.request.Request(
            router.url + "/generate",
            data=json.dumps({"prompt": [1, 2, 3], "steps": 4,
                             "seed": 7}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Veles-Trace": "retry-abc",
                     "X-Veles-Session": sess})
        resp = urllib.request.urlopen(req, timeout=120)
        assert resp.headers.get("X-Veles-Trace") == "retry-abc"
        assert resp.headers.get("X-Veles-Router-Attempts") == "2"
        assert resp.headers.get("X-Veles-Replica") == r1.replica_id
        faults.clear()
        att = [ev for ev in list(events.ring)
               if ev.get("name") == "router.attempt"
               and ev.get("trace") == "retry-abc"]
        assert {ev.get("replica") for ev in att} \
            == {r0.replica_id, r1.replica_id}
        assert sorted({ev.get("attempt") for ev in att}) == [1, 2]
        # the WINNING replica's scheduler recorded the phase timeline
        # under the same id
        names = {ev["name"] for ev in _trace_events("retry-abc")}
        assert {"router.request", "req.queue", "req.admit",
                "req.retire"} <= names
        # streaming: first byte pins, terminal frame carries the id
        req = urllib.request.Request(
            router.url + "/generate",
            data=json.dumps({"prompt": [1, 2, 3], "steps": 3,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Veles-Trace": "sse-abc"})
        resp = urllib.request.urlopen(req, timeout=120)
        assert resp.headers.get("X-Veles-Trace") == "sse-abc"
        pinned = resp.headers.get("X-Veles-Replica")
        assert pinned in (r0.replica_id, r1.replica_id)
        frames = [f for f in resp.read().decode().split("\n\n")
                  if f.startswith("data: ")]
        assert frames[-1] == "data: [DONE]"
        term = json.loads(frames[-2][6:])
        assert term["trace_id"] == "sse-abc" and term["done"]
        # structured errors carry the id too (client-side
        # correlation of FAILURES, not just successes)
        try:
            urllib.request.urlopen(urllib.request.Request(
                "http://%s:%d/generate" % (r0.host, r0.port),
                data=json.dumps({"prompt": [1, 2, 3],
                                 "steps": -1}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Veles-Trace": "err-abc"}), timeout=30)
            raise AssertionError("steps=-1 must 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            body = json.loads(e.read().decode())
            assert body["error"]["trace_id"] == "err-abc"
            assert e.headers.get("X-Veles-Trace") == "err-abc"
        # live tables answer on both tiers
        dbg = json.load(urllib.request.urlopen(
            router.url + "/debug/requests", timeout=10))
        assert dbg["role"] == "router" \
            and isinstance(dbg["requests"], list)
        dbg = json.load(urllib.request.urlopen(
            "http://%s:%d/debug/requests" % (r0.host, r0.port),
            timeout=10))
        assert dbg["replica"] == r0.replica_id \
            and isinstance(dbg["requests"], list)
    finally:
        faults.clear()
        router.stop()
        r0.stop()
        r1.stop()


# -- SLO accounting -----------------------------------------------------------

def test_slo_good_bad_and_burn_rate():
    """Latency under the class objective counts good; over it counts
    bad and burns the error budget: bad fraction / (1 - target).
    All-bad over a window burns at 1/0.01 = 100x."""
    from veles_tpu.serving.metrics import SLOTracker
    saved = root.common.slo.ttft_ms.get("normal", None)
    root.common.slo.ttft_ms.normal = 100.0
    try:
        slo = SLOTracker("test-slo")
        for _ in range(4):
            slo.record("normal", "ttft", 50.0)    # under: good
        snap = slo.snapshot()["classes"]["normal"]["ttft"]
        assert snap["good"] == 4 and snap["bad"] == 0
        assert all(v == 0.0 for v in snap["burn_rate"].values())
        for _ in range(4):
            slo.record("normal", "ttft", 500.0)   # over: bad
        snap = slo.snapshot()["classes"]["normal"]["ttft"]
        assert snap["good"] == 4 and snap["bad"] == 4
        # 50% bad over the window / 1% budget = 50x burn
        assert snap["burn_rate"]["60s"] == pytest.approx(50.0)
        # no objective configured -> no accounting
        slo.record("normal", "e2e", 10.0**9)
        slo2 = SLOTracker("test-slo")
        assert "e2e" in slo2.objectives  # e2e objectives still exist
    finally:
        if saved is None:
            del root.common.slo.ttft_ms.normal
        else:
            root.common.slo.ttft_ms.normal = saved


def test_slo_disabled_is_inert():
    from veles_tpu.serving.metrics import SLOTracker
    saved = root.common.slo.get("enabled", True)
    root.common.slo.enabled = False
    try:
        slo = SLOTracker("test-slo-off")
        slo.record("normal", "ttft", 10.0**9)
        snap = slo.snapshot()
        assert snap["enabled"] is False and snap["classes"] == {}
    finally:
        root.common.slo.enabled = saved


# -- trace_export --request ---------------------------------------------------

def _write_jsonl(path, evs):
    with open(path, "w") as f:
        for ev in evs:
            f.write(json.dumps(ev) + "\n")


def test_trace_export_request_merges_and_adjusts_skew(tmp_path):
    """Merging a router log with a replica log whose clock runs in a
    different domain (monotonic-vs-wallclock mix: replica stamps far
    BEFORE the router span that parents them) must warn, count the
    shift in otherData.skew_adjusted, and emit a NESTED timeline —
    not silently misordered spans."""
    from veles_tpu.telemetry.trace_export import export_request
    t = 1000.0
    router_log = tmp_path / "router.jsonl"
    replica_log = tmp_path / "replica.jsonl"
    _write_jsonl(str(router_log), [
        {"name": "router.request", "kind": "begin", "time": t,
         "pid": 10, "tid": 0, "span": "10-1", "trace": "sk-1",
         "path": "/generate"},
        {"name": "router.attempt", "kind": "begin", "time": t + 0.01,
         "pid": 10, "tid": 0, "span": "10-2", "trace": "sk-1",
         "attempt": 1, "replica": "pid77:9000"},
        {"name": "router.attempt", "kind": "end", "time": t + 0.5,
         "pid": 10, "tid": 0, "span": "10-2", "trace": "sk-1",
         "attempt": 1, "replica": "pid77:9000"},
        {"name": "router.request", "kind": "end", "time": t + 0.51,
         "pid": 10, "tid": 0, "span": "10-1", "trace": "sk-1",
         "attempts": 1},
        {"name": "unrelated", "kind": "single", "time": t,
         "pid": 10, "tid": 0, "trace": "other"},
    ])
    # replica events stamped from a ~boot-relative clock (5.x s):
    # hours "before" the router — the classic monotonic mix
    _write_jsonl(str(replica_log), [
        {"name": "req.queue", "kind": "single", "time": 5.0,
         "pid": 77, "tid": 1, "trace": "sk-1", "duration": 0.002},
        {"name": "req.step", "kind": "single", "time": 5.1,
         "pid": 77, "tid": 1, "traces": {"sk-1": 1, "zz": 1},
         "duration": 0.01},
        {"name": "req.retire", "kind": "single", "time": 5.2,
         "pid": 77, "tid": 1, "trace": "sk-1", "outcome": "ok"},
    ])
    out = tmp_path / "trace.json"
    n = export_request([str(router_log), str(replica_log)], "sk-1",
                       str(out))
    trace = json.loads(out.read_text())
    assert n == len(trace["traceEvents"])
    assert trace["otherData"]["skew_adjusted"] == 1
    evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    names = [e["name"] for e in evs]
    assert "unrelated" not in names          # other traces filtered
    by_name = {e["name"]: e for e in evs}
    # the replica spans were shifted INSIDE the attempt window
    att, q = by_name["router.attempt"], by_name["req.queue"]
    assert att["ph"] == "X" and att["args"]["replica"] == "pid77:9000"
    assert q["ts"] >= att["ts"]
    step = by_name["req.step"]
    assert step["args"]["tokens"] == 1       # projected traces map
    assert "traces" not in step["args"]      # other ids don't leak
    # same-domain logs (no router leg) stay untouched
    n2 = export_request([str(replica_log)], "sk-1",
                        str(tmp_path / "t2.json"))
    t2 = json.loads((tmp_path / "t2.json").read_text())
    assert t2["otherData"]["skew_adjusted"] == 0 and n2 > 0


def test_trace_export_legacy_two_arg_mode_unchanged(tmp_path):
    from veles_tpu.telemetry.trace_export import main
    log = tmp_path / "run.jsonl"
    _write_jsonl(str(log), [
        {"name": "x", "kind": "begin", "time": 1.0, "span": "1-1"},
        {"name": "x", "kind": "end", "time": 2.0, "span": "1-1"},
    ])
    out = tmp_path / "out.json"
    assert main([str(log), str(out)]) == 0
    assert len(json.loads(out.read_text())["traceEvents"]) == 2


# -- what tracing costs the loop, as counts ----------------------------------

@pytest.mark.tracing_overhead
def test_tracing_overhead_is_one_event_a_decode_boundary(f32,
                                                         monkeypatch):
    """Tracing is default-ON, so its cost rides every decode boundary.
    What a CPU run can show of that cost is a COUNT: one ``req.step``
    event a boundary however many slots ride it, a fixed handful of
    phase events a request, and none at all with tracing off.  What
    the events cost the loop thread in time is read on the chip
    (``sched_observe_ms_per_step``, PERF.md)."""
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.telemetry import metrics
    fw = _tiny_fw("reqtrace-overhead")
    prompt = [3, 1, 4, 3, 1, 4]
    saved = root.common.reqtrace.get("enabled", True)
    seen = []
    real = events.record

    def recording(name, kind, **attrs):
        if name.startswith("req."):
            seen.append(name)
        return real(name, kind, **attrs)
    monkeypatch.setattr(events, "record", recording)

    def build(enabled):
        root.common.reqtrace.enabled = enabled
        return InferenceScheduler(fw, max_slots=2, window=64,
                                  block_size=4,
                                  prefill_chunk=4,
                                  warm_buckets=False).start()

    def soak(sch, together, lead, requests=4, steps=24):
        """(req.step events, other req.* events, decode boundaries)
        of ``requests`` requests, ``together`` at a time; each prompt
        starts with a token of its own (from ``lead``), so none is
        warm in the prefix cache."""
        boundaries = metrics.get("veles_serving_steps_total")
        del seen[:]
        before = boundaries.value
        for first in range(0, requests, together):
            futs = [sch.submit([lead + i] + prompt[1:], steps, seed=i)
                    for i in range(first, first + together)]
            for f in futs:
                f.result(240)
        while sch._working:          # the last pass's own events
            time.sleep(0.01)
        step_events = seen.count("req.step")
        return (step_events, len(seen) - step_events,
                boundaries.value - before)

    try:
        on = build(True)
        off = build(False)
        assert on._tron and not off._tron
        try:
            alone = soak(on, together=1, lead=0)
            paired = soak(on, together=2, lead=4)
            silent = soak(off, together=2, lead=8)
        finally:
            on.close()
            off.close()
    finally:
        root.common.reqtrace.enabled = saved
    # one event a boundary, at occupancy 1 and at occupancy 2
    assert alone[0] == alone[2] > 0 and paired[0] == paired[2] > 0
    assert paired[2] < alone[2]      # two slots did share boundaries
    # queue, admit, one prefill chunk (six positions go in one chunk
    # of 8), first token, retire: the same five a request whoever it
    # shares the batch with
    assert alone[1] == paired[1] == 4 * 5
    assert silent[:2] == (0, 0) and silent[2] > 0
