"""Fleet observability plane (PR 14): federation merge math +
``GET /metrics/fleet``, the alert engine's state machine / sinks /
shipped rules, dashboard rendering under hostile input, the
query-string routing regression, goodput gauges, and the
alert-engine overhead gate."""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from veles_tpu import faults
from veles_tpu.config import root
from veles_tpu.logger import events
from veles_tpu.telemetry.alerts import AlertEngine, AlertRule
from veles_tpu.telemetry.registry import (
    MetricsRegistry, render_families_text)

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def disarm():
    faults.clear()
    yield
    faults.clear()


def _serve(handler_cls):
    server = ThreadingHTTPServer(("127.0.0.1", 0), handler_cls)
    threading.Thread(target=server.serve_forever,
                     daemon=True).start()
    return server, server.server_address[1]


def _get(url, timeout=10):
    resp = urllib.request.urlopen(url, timeout=timeout)
    return resp.status, resp.read().decode()


# -- federation merge math ----------------------------------------------------

_SCRAPE_A = """\
# HELP veles_serving_tokens_generated_total tokens
# TYPE veles_serving_tokens_generated_total counter
veles_serving_tokens_generated_total 100
# TYPE veles_serving_ttft_ms histogram
veles_serving_ttft_ms_bucket{le="10"} 2
veles_serving_ttft_ms_bucket{le="+Inf"} 3
veles_serving_ttft_ms_sum 45.5
veles_serving_ttft_ms_count 3
# TYPE veles_serving_kv_blocks_free gauge
veles_serving_kv_blocks_free 7
# TYPE veles_serving_class_requests_total counter
veles_serving_class_requests_total{cls="high"} 4
"""

_SCRAPE_B = """\
# TYPE veles_serving_tokens_generated_total counter
veles_serving_tokens_generated_total 11
# TYPE veles_serving_ttft_ms histogram
veles_serving_ttft_ms_bucket{le="10"} 1
veles_serving_ttft_ms_bucket{le="+Inf"} 1
veles_serving_ttft_ms_sum 2.5
veles_serving_ttft_ms_count 1
# TYPE veles_serving_kv_blocks_free gauge
veles_serving_kv_blocks_free 3
# TYPE veles_serving_class_requests_total counter
veles_serving_class_requests_total{cls="high"} 1
veles_serving_class_requests_total{cls="low"} 9
"""


def test_federation_merge_equals_hand_summed_scrapes():
    """Counters and histogram bucket/sum/count merge by summation
    per label set; gauges stay per replica under a replica label."""
    from veles_tpu.telemetry import federation
    fams = federation.merge_scrapes([
        ("a", federation.parse_prometheus(_SCRAPE_A)),
        ("b", federation.parse_prometheus(_SCRAPE_B))])
    text = render_families_text(fams)
    assert "veles_serving_tokens_generated_total 111" in text
    assert 'veles_serving_ttft_ms_bucket{le="10"} 3' in text
    assert 'veles_serving_ttft_ms_bucket{le="+Inf"} 4' in text
    assert "veles_serving_ttft_ms_sum 48" in text
    assert "veles_serving_ttft_ms_count 4" in text
    assert 'veles_serving_class_requests_total{cls="high"} 5' in text
    assert 'veles_serving_class_requests_total{cls="low"} 9' in text
    # gauges are per-process facts: re-labeled, never summed
    assert 'veles_serving_kv_blocks_free{replica="a"} 7' in text
    assert 'veles_serving_kv_blocks_free{replica="b"} 3' in text
    # round trip: the merged text re-parses to the same families
    again = federation.parse_prometheus(text)
    assert render_families_text(again) == text


def test_registry_collect_families_matches_text_render():
    """The structured collect and the text exposition are two views
    of ONE renderer — in-process consumers (dashboard, alerts,
    federation) must see exactly what a scraper would."""
    reg = MetricsRegistry()
    reg.counter("veles_t_total", "help").inc(2)
    reg.gauge("veles_t_g", "help", labelnames=("cls",)) \
        .labels(cls="a").set(1.5)
    reg.histogram("veles_t_ms", "h", buckets=(1.0,)).observe(0.5)
    assert render_families_text(reg.collect_families()) \
        == reg.render_prometheus()
    by_name = {f["name"]: f for f in reg.collect_families()}
    assert by_name["veles_t_total"]["samples"] == [("", {}, 2.0)]
    assert by_name["veles_t_g"]["samples"] == [("", {"cls": "a"},
                                                1.5)]


# -- a canned fake fleet ------------------------------------------------------

def _fake_replica(tokens, free):
    """A replica stub: healthy /healthz, canned /serving/metrics and
    /metrics — federation/dashboard tests never pay for a chain."""

    class Fake(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def _reply(self, code, blob, ctype="application/json"):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(blob)))
            self.end_headers()
            self.wfile.write(blob)

        def do_GET(self):
            path = self.path.split("?")[0]
            if path == "/healthz":
                self._reply(200, json.dumps(
                    {"status": "ok", "role": "both", "tp": 2,
                     "draining": False}).encode())
            elif path == "/serving/metrics":
                self._reply(200, json.dumps(
                    {"queue_depth": 1, "kv_blocks_used": 3,
                     "kv_blocks_free": free,
                     "goodput_tokens_per_sec": 42.5,
                     "bucket_padding_efficiency": 0.75,
                     "prefix_cache_hit_rate": 0.5,
                     "spec_accept_rate": 0.6}).encode())
            elif path == "/metrics":
                self._reply(200, (
                    "# TYPE veles_serving_tokens_generated_total "
                    "counter\n"
                    "veles_serving_tokens_generated_total %d\n"
                    "# TYPE veles_serving_kv_blocks_free gauge\n"
                    "veles_serving_kv_blocks_free %d\n"
                    % (tokens, free)).encode(), "text/plain")
            else:
                self._reply(404, b"{}")

    return Fake


def test_fleet_scrape_and_dashboard_over_fake_replicas():
    """Acceptance: one ``GET /metrics/fleet`` returns merged families
    whose counter totals equal the sum of the individual replica
    scrapes; the dashboard renders the fleet with hostile replica ids
    HTML-escaped; query strings never 404 (the PR 3 regression,
    router-side)."""
    from veles_tpu.serving import Router
    s1, p1 = _serve(_fake_replica(100, 7))
    s2, p2 = _serve(_fake_replica(11, 3))
    hostile = 'rep<script>alert(1)</script>'
    router = Router(health_interval=0.1).start()
    try:
        router.add_replica("127.0.0.1", p1, replica_id=hostile)
        router.add_replica("127.0.0.1", p2, replica_id="rep2")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st, fleet = _get(router.url + "/metrics/fleet")
            if "veles_serving_tokens_generated_total 111" in fleet:
                break
            time.sleep(0.1)
        # the merged counter equals the hand-summed replica scrapes
        assert "veles_serving_tokens_generated_total 111" in fleet
        assert "veles_fleet_replicas 2" in fleet
        assert "veles_fleet_scrape_errors 0" in fleet
        assert 'veles_serving_kv_blocks_free{replica="rep2"} 3' \
            in fleet
        # dashboard: fleet table + goodput columns, attacker escaped
        st, page = _get(router.url + "/dashboard")
        assert st == 200
        assert "<script>" not in page
        assert "rep&lt;script&gt;" in page
        assert "42.5" in page and "0.75" in page  # goodput columns
        # regression: query strings are stripped before matching
        for path in ("/metrics?x=1", "/metrics/fleet?x=1",
                     "/alerts?probe=1", "/dashboard?r=2",
                     "/healthz?probe=1", "/router/state?x=y"):
            st, _ = _get(router.url + path)
            assert st == 200, path
    finally:
        router.stop()
        s1.shutdown()
        s2.shutdown()


# -- the alert state machine --------------------------------------------------

def test_alert_state_machine_holddown_and_no_flap():
    """pending -> firing after for_seconds of CONTINUOUS truth;
    firing -> resolved on the first false tick; a condition true for
    less than the hold-down never fires (no flapping)."""
    reg = MetricsRegistry()
    g = reg.gauge("veles_t_pressure", "x")
    engine = AlertEngine(
        name="t", registry=reg, interval=999,
        rules=[AlertRule("hot", expr="veles_t_pressure > 5",
                         for_seconds=1.0, severity="page")])
    t0 = 100.0
    g.set(9)
    assert engine.tick(now=t0) == []               # pending
    assert engine.snapshot()["pending"][0]["rule"] == "hot"
    fired = engine.tick(now=t0 + 1.1)
    assert [f[0] for f in fired] == ["fire"]
    assert engine.firing()[0]["severity"] == "page"
    # the firing gauge exports
    from veles_tpu.telemetry import metrics
    fam = metrics.get("veles_alerts_firing")
    assert fam.labels(rule="hot", severity="page").value == 1
    g.set(1)
    assert [f[0] for f in engine.tick(now=t0 + 2)] == ["resolve"]
    assert engine.firing() == []
    assert engine.snapshot()["recent_resolved"][0]["rule"] == "hot"
    assert fam.labels(rule="hot", severity="page").value == 0
    # flap guard: true shorter than the hold-down, then false
    g.set(9)
    assert engine.tick(now=t0 + 3) == []
    g.set(1)
    assert engine.tick(now=t0 + 3.5) == []
    assert engine.tick(now=t0 + 9) == []
    # the JSONL sink carried both transitions
    ring = [ev for ev in list(events.ring)
            if ev.get("rule") == "hot"]
    assert any(ev["name"] == "alert.fire" for ev in ring)
    assert any(ev["name"] == "alert.resolve" for ev in ring)


def test_slo_burn_rule_requires_both_windows():
    """The SRE multi-window pair: a fast-window spike alone (or a
    slow-window residue alone) must NOT page — both windows have to
    burn simultaneously."""
    reg = MetricsRegistry()
    burn = reg.gauge("veles_slo_burn_rate", "x",
                     labelnames=("scope", "cls", "slo", "window"))
    rule = AlertRule("page", kind="slo_burn", severity="page",
                     params={"fast": "60s", "slow": "300s",
                             "threshold": 14.4})
    engine = AlertEngine(name="slo", registry=reg, interval=999,
                         rules=[rule])

    def burn_set(fast, slow):
        burn.labels(scope="serving", cls="high", slo="ttft",
                    window="60s").set(fast)
        burn.labels(scope="serving", cls="high", slo="ttft",
                    window="300s").set(slow)

    burn_set(20.0, 1.0)          # fast spike only
    assert engine.tick(now=1.0) == []
    burn_set(1.0, 20.0)          # slow residue only
    assert engine.tick(now=2.0) == []
    burn_set(20.0, 20.0)         # both: page
    fired = engine.tick(now=3.0)
    assert [f[0] for f in fired] == ["fire"]
    labels = engine.firing()[0]["labels"]
    assert labels["cls"] == "high" and labels["window"] == "60s+300s"
    burn_set(0.0, 0.0)
    assert [f[0] for f in engine.tick(now=4.0)] == ["resolve"]


def test_webhook_sink_and_fault_point():
    """fire/resolve POST JSON to the webhook; an armed
    ``alerts.webhook`` fault point drops the POST and counts a
    failure WITHOUT breaking the engine or the other sinks."""
    posts = []

    class Sink(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def do_POST(self):
            body = self.rfile.read(
                int(self.headers.get("Content-Length", 0)))
            posts.append(json.loads(body))
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")

    server, port = _serve(Sink)
    reg = MetricsRegistry()
    g = reg.gauge("veles_t_g", "x")
    engine = AlertEngine(
        name="wh", registry=reg, interval=999,
        webhook_url="http://127.0.0.1:%d/hook" % port,
        rules=[AlertRule("r", expr="veles_t_g > 0")])
    try:
        g.set(1)
        engine.tick(now=1.0)
        assert engine.webhook_ok == 1
        assert posts and posts[0]["event"] == "fire" \
            and posts[0]["rule"] == "r"
        # armed drop: the resolve's POST is injected away
        faults.inject("alerts.webhook", "drop")
        g.set(0)
        out = engine.tick(now=2.0)
        assert [f[0] for f in out] == ["resolve"]   # engine survived
        assert engine.webhook_failures == 1
        assert len(posts) == 1
    finally:
        server.shutdown()


def test_config_rules_and_bad_expr_rejected():
    """User rules load from root.common.alerts.rules dicts; a
    malformed expr fails LOUDLY at construction, not silently at
    tick time."""
    saved_rules = root.common.alerts.get("rules", ())
    saved_defaults = root.common.alerts.get("defaults", True)
    try:
        root.common.alerts.rules = (
            {"name": "mine", "expr": "veles_t_g >= 2", "for": 0.5,
             "severity": "info"},)
        root.common.alerts.defaults = False
        engine = AlertEngine(name="cfg", registry=MetricsRegistry(),
                             interval=999)
        assert [r.name for r in engine.rules] == ["mine"]
        assert engine.rules[0].for_seconds == 0.5
    finally:
        root.common.alerts.rules = saved_rules
        root.common.alerts.defaults = saved_defaults
    with pytest.raises(ValueError):
        AlertRule("bad", expr="not a rule at all")
    with pytest.raises(ValueError):
        AlertRule("bad", expr="veles_x > 1", severity="sev51")


def test_flight_recorder_bundle_embeds_firing_alerts():
    """A hang/crash bundle must say what was ALREADY wrong: firing
    alerts from every live engine ride the bundle."""
    from veles_tpu.telemetry.flight_recorder import FlightRecorder
    reg = MetricsRegistry()
    reg.gauge("veles_t_g", "x").set(5)
    engine = AlertEngine(
        name="fr", registry=reg, interval=999,
        rules=[AlertRule("stuck", expr="veles_t_g > 1")])
    engine.tick(now=1.0)
    assert engine.firing()
    bundle = FlightRecorder().bundle("test")
    mine = [a for a in bundle.get("alerts", ())
            if a.get("engine") == "fr"]
    assert mine and mine[0]["rule"] == "stuck"


# -- end-to-end degradation ---------------------------------------------------

def test_replica_kill_drives_alert_end_to_end():
    """Acceptance: killing a replica drives the shipped
    ``replica_unreachable`` rule pending -> firing -> resolved,
    visible in GET /alerts, the JSONL event ring and the dashboard;
    reviving the replica resolves it."""
    from veles_tpu.serving import Router
    saved = root.common.alerts.get("interval", 1.0)
    root.common.alerts.interval = 0.05
    server, port = _serve(_fake_replica(5, 5))
    router = Router(health_interval=0.05, health_timeout=0.5).start()
    try:
        router.add_replica("127.0.0.1", port, replica_id="victim")
        time.sleep(0.3)     # healthy polls: replica_up = 1
        server.shutdown()   # the kill
        server.server_close()   # release the port for the revival
        deadline = time.monotonic() + 15
        firing = []
        while time.monotonic() < deadline and not firing:
            firing = [a for a in json.loads(
                _get(router.url + "/alerts")[1])["firing"]
                if a["rule"] == "replica_unreachable"]
            time.sleep(0.05)
        assert firing, "replica_unreachable never fired"
        assert firing[0]["labels"]["replica"] == "victim"
        assert any(
            ev.get("name") == "alert.fire"
            and ev.get("rule") == "replica_unreachable"
            for ev in list(events.ring))
        _, page = _get(router.url + "/dashboard")
        assert "replica_unreachable" in page
        # revive on the same port: the poll recovers, the alert
        # resolves
        server2, _ = ThreadingHTTPServer(
            ("127.0.0.1", port), _fake_replica(5, 5)), port
        threading.Thread(target=server2.serve_forever,
                         daemon=True).start()
        try:
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                snap = json.loads(_get(router.url + "/alerts")[1])
                if not [a for a in snap["firing"]
                        if a["rule"] == "replica_unreachable"]:
                    break
                time.sleep(0.05)
            resolved = [a for a in snap["recent_resolved"]
                        if a["rule"] == "replica_unreachable"]
            assert resolved, "alert never resolved after revival"
            assert any(
                ev.get("name") == "alert.resolve"
                and ev.get("rule") == "replica_unreachable"
                for ev in list(events.ring))
        finally:
            server2.shutdown()
    finally:
        root.common.alerts.interval = saved
        router.stop()


# -- dashboard hostile-input rendering ---------------------------------------

def test_dashboard_renderer_escapes_everything():
    """Every interpolated string is attacker input: replica ids off
    the wire, alert labels, trace ids from clients — none may reach
    the page as markup."""
    from veles_tpu.telemetry.dashboard import render_dashboard_html
    evil = '<script>alert(1)</script>'
    page = render_dashboard_html(
        "t" + evil,
        replicas=[{"id": evil, "role": evil, "status": evil,
                   "breaker": evil, "outstanding": 1}],
        slo={"classes": {evil: {"e2e": {
            "good": 1, "bad": 0,
            "burn_rate": {"60s": 0.5}}}}},
        alerts={"firing": [{"rule": evil, "severity": "page",
                            "labels": {evil: evil}, "value": 1}]},
        inflight=[{"trace": evil, "path": evil, "phase": "proxy"}],
        note=evil)
    assert "<script>" not in page
    assert page.count("&lt;script&gt;") >= 7


def test_web_status_links_alerts_and_dashboard():
    """The training-side status server exposes the same plane: index
    links /dashboard and /alerts, /alerts serves engine snapshots,
    /dashboard renders, /metrics rides the collect()-backed
    renderer."""
    pytest.importorskip("tornado")
    import socket
    from veles_tpu.telemetry import metrics
    from veles_tpu.web_status import WebStatusServer
    reg = MetricsRegistry()
    reg.gauge("veles_t_ws", "x").set(2)
    engine = AlertEngine(name="ws-test", registry=reg, interval=999,
                         rules=[AlertRule("wsr",
                                          expr="veles_t_ws > 1")])
    engine.tick(now=1.0)
    metrics.counter("veles_test_obs_total").inc(3)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    server = WebStatusServer(port=port)
    server.start(background=True)
    try:
        base = "http://127.0.0.1:%d" % port
        _, index = _get(base + "/")
        assert 'href="/dashboard"' in index \
            and 'href="/alerts"' in index
        _, alerts = _get(base + "/alerts")
        snap = json.loads(alerts)
        mine = [e for e in snap["engines"]
                if e["engine"] == "ws-test"]
        assert mine and mine[0]["firing"][0]["rule"] == "wsr"
        assert any(a["rule"] == "wsr" for a in snap["firing"])
        st, page = _get(base + "/dashboard")
        assert st == 200 and "wsr" in page
        _, text = _get(base + "/metrics")
        assert "veles_test_obs_total 3" in text
    finally:
        server.stop()


# -- goodput + overhead gate --------------------------------------------------

@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2):
    import numpy
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
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


@pytest.mark.alerting_overhead
def test_alerting_engine_ticks_beside_the_loop_and_goodput_gauges(f32):
    """The engine is default-ON, so it ticks beside every serving
    process: a BUSY engine (20 Hz, the full default rule set) ticks
    and evaluates every rule while the scheduler serves, none of them
    failing, and the soak's answers do not change.  What that costs
    the serving loop in time is read on the chip (PERF.md: the engine
    runs on its own thread, so it shows in ``decode_step_ms`` or not at
    all).  The same soak proves the goodput accounting: tokens/sec and
    padding efficiency export to /serving/metrics and the registry."""
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.telemetry import metrics
    fw = _tiny_fw("alerts-overhead")
    prompt = [3, 1, 4, 3, 1, 4]
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             warm_buckets=False,
                             replica_id="obs-soak").start()

    def soak(requests=4, steps=24):
        futs = [sch.submit(prompt, steps, seed=i)
                for i in range(requests)]
        return [f.result(240) for f in futs]

    try:
        quiet = soak()   # compile + settle
        snap = sch.metrics()
        # -- goodput accounting is live after real traffic
        assert snap["goodput_tokens_per_sec"] is not None \
            and snap["goodput_tokens_per_sec"] > 0
        assert 0.0 < snap["bucket_padding_efficiency"] <= 1.0
        fam = metrics.get("veles_serving_goodput_tokens_per_sec")
        assert fam.labels(replica="obs-soak").value > 0
        fam = metrics.get("veles_serving_bucket_padding_efficiency")
        assert 0.0 < fam.labels(replica="obs-soak").value <= 1.0

        # -- a busy engine beside the loop
        engine = AlertEngine(name="overhead", interval=0.05)
        evaluated = {"ok": 0, "failed": 0}

        def counting(evaluate):
            def run(*args, **kwargs):
                try:
                    rows = evaluate(*args, **kwargs)
                except Exception:
                    evaluated["failed"] += 1
                    raise
                evaluated["ok"] += 1
                return rows
            return run
        assert len(engine.rules) >= 5
        for rule in engine.rules:
            rule.evaluate = counting(rule.evaluate)
        engine.start()
        try:
            soaks = 0
            while engine.ticks < 3 and soaks < 500:
                assert soak() == quiet
                soaks += 1
        finally:
            engine.stop()
        assert engine.ticks >= 3
        assert evaluated["failed"] == 0
        assert evaluated["ok"] >= (engine.ticks - 1) * len(engine.rules)
        assert fam.labels(replica="obs-soak").value > 0
    finally:
        sch.close()
