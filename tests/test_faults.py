"""Fault-tolerant request lifecycle + coordinator failover, driven by
the ``veles_tpu.faults`` injection registry: deadlines free 100% of
KV blocks, preempt→resume token parity, graceful drain, watchdog
recovery from an injected hang, dead-worker job reassignment with
exact epoch accounting, and reconnect backoff."""

import asyncio
import json
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

from veles_tpu import faults
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.faults


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


@pytest.fixture(autouse=True)
def disarm():
    """Every test starts and ends with an empty fault registry."""
    faults.clear()
    yield
    faults.clear()


def _tiny_fw(name, window=16, vocab=12, dim=16, heads=2, blocks=1):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name=name)
    spec = [{"type": "embedding", "vocab": vocab, "dim": dim}]
    spec += [{"type": "transformer_block", "heads": heads,
              "causal": True} for _ in range(blocks)]
    spec += [{"type": "token_logits", "vocab": vocab}]
    fw = make_forwards(
        wf, Array(numpy.zeros((2, window), numpy.int32)), spec)
    dev = Device(backend="numpy")
    for u in fw:
        u.initialize(device=dev)
    return fw


def _clean(sch):
    """The acceptance sweep over a drained scheduler: no block
    leaked, double-owned, or stuck.  Blocks the radix prefix cache
    holds (ON by default since PR 10) are RESIDENT, not leaked — the
    sweep verifies every block is exactly one of free/resident."""
    cache = sch.cache_
    resident = sch.prefix_.resident if sch.prefix_ is not None else 0
    sch.check_kv()
    assert cache.used_blocks == resident
    assert cache.free_blocks == cache.capacity_blocks - resident
    assert cache.free_slots == cache.max_slots


# -- the registry itself ------------------------------------------------------

def test_registry_semantics():
    """Spec grammar, after/times/key modifiers, drop return, the
    exception action, and the injected-faults counter."""
    from veles_tpu.telemetry import metrics
    assert faults.fire("nothing.armed") is False
    # after=1 skips the first hit; times=1 disarms after one firing
    faults.inject("p.drop", "drop", after=1, times=1)
    assert faults.fire("p.drop") is False       # skipped (after)
    assert faults.fire("p.drop") is True        # fires
    assert faults.fire("p.drop") is False       # exhausted (times)
    # key scoping: only the matching caller trips
    faults.inject("p.key", "drop", key="w?")
    assert faults.fire("p.key", key="w1") is True
    assert faults.fire("p.key", key="other") is False
    assert faults.fire("p.key") is False
    # exception + delay actions
    faults.inject("p.boom", "exception")
    with pytest.raises(faults.InjectedFault):
        faults.fire("p.boom")
    faults.inject("p.slow", "delay", arg=0.05)
    t0 = time.monotonic()
    faults.fire("p.slow")
    assert time.monotonic() - t0 >= 0.05
    # spec-string grammar (the VELES_FAULTS / config surface)
    faults.clear()
    armed = faults.load("a.b=hang:1.5@3x2;c.d=drop~w*; e.f=delay")
    assert [s.action for s in armed] == ["hang", "drop", "delay"]
    assert armed[0].arg == 1.5 and armed[0].after == 3 \
        and armed[0].times == 2
    assert armed[1].key == "w*" and armed[2].arg is None
    with pytest.raises(ValueError):
        faults.load("no-equals-sign")
    with pytest.raises(ValueError):
        faults.load("p=warp")  # unknown action
    # wildcard points + the prometheus counter
    faults.clear()
    faults.inject("serving.*", "drop")
    before = metrics.counter(
        "veles_faults_injected_total",
        labelnames=("point", "action")).labels(
            point="serving.scheduler.step", action="drop").value
    assert faults.fire("serving.scheduler.step") is True
    after = metrics.counter(
        "veles_faults_injected_total",
        labelnames=("point", "action")).labels(
            point="serving.scheduler.step", action="drop").value
    assert after == before + 1


def test_http_error_action_and_point_globs():
    """The ``http_error`` action raises :class:`InjectedHTTPError`
    carrying its status code (spec arg; default 500), and the fnmatch
    scoping contract holds: point globs arm subsystems, key globs
    pick victims within one point."""
    faults.inject("p.http", "http_error", arg=503)
    with pytest.raises(faults.InjectedHTTPError) as e:
        faults.fire("p.http")
    assert e.value.status == 503
    assert isinstance(e.value, faults.InjectedFault)
    # spec-string grammar + the default status
    faults.clear()
    armed = faults.load("rest.x=http_error:418x1;rest.y=http_error")
    assert [s.action for s in armed] == ["http_error", "http_error"]
    with pytest.raises(faults.InjectedHTTPError) as e:
        faults.fire("rest.x")
    assert e.value.status == 418
    assert faults.fire("rest.x") is False      # times=1 exhausted
    with pytest.raises(faults.InjectedHTTPError) as e:
        faults.fire("rest.y")
    assert e.value.status == 500               # default
    # point-glob: router.* arms forward AND health, nothing else;
    # key-glob: only replicas r1/r2 trip it
    faults.clear()
    faults.inject("router.*", "drop", key="r[12]")
    assert faults.fire("router.forward", key="r1") is True
    assert faults.fire("router.forward", key="r3") is False
    assert faults.fire("router.replica.health", key="r2") is True
    assert faults.fire("serving.scheduler.step", key="r1") is False
    # a keyless fire never matches a keyed spec (no silent widening)
    assert faults.fire("router.forward") is False


# -- request lifecycle: deadlines, cancel, close ------------------------------

def test_deadline_expiry_frees_all_blocks(f32):
    """Acceptance (1): a request expiring MID-DECODE fails with a 408
    carrying its partial token count, and every one of its KV blocks
    returns to the pool; a queued request expires with tokens=0."""
    from veles_tpu.serving import (
        DeadlineExceededError, InferenceScheduler)
    fw = _tiny_fw("fault-deadline", window=256)
    sch = InferenceScheduler(fw, max_slots=1, window=256,
                             block_size=4, prefill_chunk=0).start()
    try:
        # slow every decode step so the 0.3s deadline lands mid-decode
        faults.inject("serving.scheduler.step", "delay", arg=0.02)
        busy = sch.submit([1, 2, 3], 200, timeout=0.3)
        queued = sch.submit([4], 4, timeout=0.2)  # never gets the slot
        with pytest.raises(DeadlineExceededError) as e1:
            busy.result(60)
        assert e1.value.tokens_generated > 0
        with pytest.raises(DeadlineExceededError) as e2:
            queued.result(60)
        assert e2.value.tokens_generated == 0
        faults.clear()
        # the slot is usable again and nothing leaked
        assert len(sch.submit([5, 6], 3).result(60)) == 5
        snap = sch.metrics()
        assert snap["requests_expired"] == 2
        _clean(sch)
    finally:
        sch.close()


def test_cancel_frees_blocks(f32):
    """A disconnected client's request — queued or mid-decode — is
    cancelled at the next boundary and its blocks return."""
    from veles_tpu.serving import (
        InferenceScheduler, RequestCancelledError)
    fw = _tiny_fw("fault-cancel", window=256)
    sch = InferenceScheduler(fw, max_slots=1, window=256,
                             block_size=4, prefill_chunk=0).start()
    try:
        # pace the decode so the request is still mid-flight when the
        # cancels land, however warm the compile caches are
        faults.inject("serving.scheduler.step", "delay", arg=0.01)
        active = sch.submit([1, 2, 3], 200)
        time.sleep(0.2)  # let it admit and decode a few tokens
        queued = sch.submit([4, 5], 8)
        assert sch.cancel(queued) is True
        assert sch.cancel(active) is True
        with pytest.raises(RequestCancelledError):
            queued.result(60)
        with pytest.raises(RequestCancelledError):
            active.result(60)
        assert sch.cancel(active) is False  # already finished
        faults.clear()
        # pool fully restored, scheduler still serves
        assert len(sch.submit([7], 2).result(60)) == 3
        assert sch.metrics()["requests_cancelled"] == 2
        _clean(sch)
    finally:
        sch.close()


def test_close_with_inflight_frees_blocks(f32):
    """The close() KV-block leak: closing with requests decoding (and
    queued) must return every block; check() passes afterward."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    fw = _tiny_fw("fault-close", window=256)
    sch = InferenceScheduler(fw, max_slots=2, window=256,
                             block_size=4, prefill_chunk=0).start()
    # pace the decode so both requests are still mid-flight at
    # close(), however warm the caches are (spec decoding — ON by
    # default since PR 10 — can finish 200 steps in well under the
    # sleep below on an untrained cyclic stream)
    faults.inject("serving.scheduler.step", "delay", arg=0.01)
    a = sch.submit([1, 2, 3], 200)
    b = sch.submit([4, 5], 200)
    time.sleep(0.2)  # both admitted, blocks claimed
    assert sch.cache_.used_blocks > 0
    sch.close()
    for fut in (a, b):
        with pytest.raises(SchedulerError):
            fut.result(10)
    _clean(sch)


# -- preemption + resume ------------------------------------------------------

def test_preempt_resume_token_parity(f32):
    """Acceptance (2): a preempted-and-resumed request emits a token
    stream bit-identical to its uninterrupted run — greedy AND seeded
    sampling — because resume re-prefills prompt+prefix and keeps the
    per-request PRNG draw counter."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("fault-preempt", window=64, blocks=2)
    prompts = [([3, 1, 4, 1, 5], dict()),
               ([7, 2], dict(temperature=0.9, top_k=5, seed=123))]

    def run(preempt):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 prefill_chunk=4).start()
        try:
            futs = [sch.submit(p, 24, **kw) for p, kw in prompts]
            if preempt:
                # wait until both streams have DECODED a few tokens
                # (busy steps tick per decode step), then evict
                deadline = time.monotonic() + 60
                while sch.metrics()["slot_busy_steps"] < 6:
                    assert time.monotonic() < deadline
                    time.sleep(0.01)
                sch.request_preempt()
                time.sleep(0.05)
                sch.request_preempt()
            outs = [f.result(120) for f in futs]
            snap = sch.metrics()
            _clean(sch)
            return outs, snap
        finally:
            sch.close()

    base, _ = run(preempt=False)
    preempted, snap = run(preempt=True)
    assert snap["preempts"] >= 1, "no preemption actually happened"
    assert snap["preempt_resumes"] >= 1
    assert preempted == base
    assert all(len(o) == len(p) + 24
               for o, (p, _) in zip(base, prompts))


# -- drain --------------------------------------------------------------------

def test_drain_completes_inflight_rejects_new(f32):
    """Acceptance (3): drain() finishes every in-flight request with
    zero failures while new submits 503 (DrainingError); the drained
    event fires once empty."""
    from veles_tpu.serving import DrainingError, InferenceScheduler
    fw = _tiny_fw("fault-drain", window=64)
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             prefill_chunk=0).start()
    try:
        futs = [sch.submit([i + 1, i + 2], 20) for i in range(4)]
        time.sleep(0.05)
        assert sch.drain() is False  # not yet drained, but closed
        with pytest.raises(DrainingError) as e:
            sch.submit([9], 2)
        assert e.value.http_status == 503
        assert e.value.retry_after >= 1
        outs = [f.result(120) for f in futs]       # ZERO failures
        assert all(len(o) == 22 for o in outs)
        assert sch.drain(timeout=60) is True
        assert sch.drained
        _clean(sch)
    finally:
        sch.close()


# -- load shedding ------------------------------------------------------------

def test_block_pressure_shed(f32):
    """Deterministic 503 once the queue's committed KV budget passes
    shed_block_factor x pool — before the client would 408 anyway."""
    from veles_tpu.serving import InferenceScheduler, QueueFullError
    fw = _tiny_fw("fault-shed", window=64)
    sch = InferenceScheduler(fw, max_slots=1, window=64,
                             block_size=4, kv_blocks=8, max_queue=32,
                             prefill_chunk=0,
                             shed_block_factor=1.0).start()
    try:
        busy = sch.submit([1, 2], 30)       # 8 blocks, holds the slot
        time.sleep(0.1)
        q = sch.submit([3], 27)             # 7 blocks committed queued
        with pytest.raises(QueueFullError, match="overloaded"):
            sch.submit([4], 27)             # 7 + 7 > 1.0 * 8 -> shed
        assert len(busy.result(120)) == 32
        assert len(q.result(120)) == 28
        assert sch.metrics()["requests_shed"] == 1
        _clean(sch)
    finally:
        sch.close()


# -- watchdog -----------------------------------------------------------------

def test_watchdog_recovers_from_injected_hang(f32):
    """Acceptance: a hung decode step trips the watchdog — pending
    clients fail FAST instead of hanging — and once the hang clears,
    the loop reaps the zombies, frees 100% of their blocks, and
    serves new traffic."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    fw = _tiny_fw("fault-watchdog", window=256)
    # compile the prefill/sample executables on a throwaway scheduler
    # FIRST (the caches are arch+shape keyed, process-wide): a cold
    # compile inside the watchdog scheduler's first iteration would
    # itself exceed the 0.3s threshold and trip a false stall
    warm_sch = InferenceScheduler(fw, max_slots=2, window=256,
                                  block_size=4,
                                  prefill_chunk=0).start()
    assert len(warm_sch.submit([9, 8], 2).result(60)) == 4
    warm_sch.close()
    sch = InferenceScheduler(fw, max_slots=2, window=256,
                             block_size=4, prefill_chunk=0,
                             watchdog=0.3).start()
    try:
        warm = sch.submit([9, 8], 2).result(60)
        assert len(warm) == 4
        faults.inject("serving.scheduler.step", "hang", arg=1.5,
                      times=1)
        fut = sch.submit([1, 2, 3], 200)
        queued = sch.submit([4], 150)
        t0 = time.monotonic()
        with pytest.raises(SchedulerError, match="stalled"):
            fut.result(60)
        with pytest.raises(SchedulerError, match="stalled"):
            queued.result(60)
        # clients were failed DURING the hang, not after it resolved
        assert time.monotonic() - t0 < 10.0
        snap = sch.metrics()
        assert snap["watchdog_trips"] >= 1
        # after the hang clears the loop reaps + serves again
        deadline = time.monotonic() + 60
        while sch.in_flight:
            assert time.monotonic() < deadline, "zombies not reaped"
            time.sleep(0.05)
        assert len(sch.submit([5, 6], 3).result(60)) == 5
        _clean(sch)
    finally:
        sch.close()


# -- mixed soak ---------------------------------------------------------------

def test_mixed_fault_soak_no_block_leak(f32):
    """Acceptance (1), soak form: a traffic mix where requests
    complete, expire, cancel, preempt and shed — under injected step
    delays — ends with PagedKVCache.check() clean and the full pool
    free."""
    from veles_tpu.serving import (
        InferenceScheduler, QueueFullError, SchedulerError)
    fw = _tiny_fw("fault-soak", window=64)
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, kv_blocks=16, max_queue=4,
                             prefill_chunk=4, watchdog=30.0).start()
    try:
        faults.inject("serving.scheduler.step", "delay", arg=0.002)
        futs = []
        for i in range(12):
            try:
                futs.append(sch.submit(
                    [(i % 11) + 1] * ((i % 5) + 1), 10 + (i % 7),
                    temperature=0.8 if i % 3 else 0.0, seed=i,
                    timeout=0.001 if i % 4 == 3 else 30.0))
            except (QueueFullError,):
                pass
            if i == 6:
                sch.request_preempt()
            if i == 8 and futs:
                sch.cancel(futs[-1])
            time.sleep(0.01)
        done = failed = 0
        for f in futs:
            try:
                f.result(120)
                done += 1
            except SchedulerError:
                failed += 1
        assert done + failed == len(futs)
        assert done >= 1
        deadline = time.monotonic() + 60
        while sch.in_flight:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        _clean(sch)
    finally:
        sch.close()


# -- REST integration ---------------------------------------------------------

def _serve_api(name, **kwargs):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    dev = Device(backend="numpy")
    wf = AcceleratedWorkflow(None, name=name)
    fw = make_forwards(
        wf, Array(numpy.zeros((1, 24), numpy.int32)), [
            {"type": "embedding", "vocab": 11, "dim": 8},
            {"type": "transformer_block", "heads": 2, "causal": True},
            {"type": "token_logits", "vocab": 11}])
    for u in fw:
        u.initialize(device=dev)
    loader = RestfulLoader(wf, sample_shape=(24,), minibatch_size=1,
                           max_wait=10.0)
    loader.initialize(device=dev)
    api = RESTfulAPI(wf, loader=loader, forwards=fw,
                     name=name + "-api", **kwargs)
    api.output = fw[-1].output
    api.initialize()

    def post(path, payload, timeout=120):
        req = urllib.request.Request(
            "http://127.0.0.1:%d%s" % (api.port, path),
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return json.load(urllib.request.urlopen(req, timeout=timeout))

    return api, loader, post


def test_rest_drain_and_structured_errors(f32):
    """Acceptance (3) over HTTP: POST /drain completes in-flight
    requests with zero errors, new submits get a structured 503 with
    Retry-After, /healthz flips to 503 "draining"; deadline expiry
    maps to 408 with a tokens_generated count; injected REST faults
    come back as structured 500s."""
    api, loader, post = _serve_api("fault-rest", max_slots=2,
                                   request_timeout=20.0)
    try:
        assert api.scheduler_ is not None
        url = "http://127.0.0.1:%d" % api.port
        # structured 400 body
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/generate", {"prompt": [3, 1]})
        body = json.loads(e.value.read().decode())
        assert e.value.code == 400
        assert body["error"]["code"] == 400
        assert "steps" in body["error"]["message"]
        # in-flight traffic, then drain
        replies = [None] * 3
        errors = []

        def client(i):
            try:
                replies[i] = post("/generate",
                                  {"prompt": [i + 1, 2], "steps": 16})
            except Exception as exc:  # noqa: BLE001 — asserted below
                errors.append((i, repr(exc)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        drain = post("/drain", {})
        assert drain["draining"] is True
        # new submit: 503 + Retry-After + structured body
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/generate", {"prompt": [5], "steps": 4})
        assert e.value.code == 503
        assert int(e.value.headers["Retry-After"]) >= 1
        body = json.loads(e.value.read().decode())
        assert body["error"]["code"] == 503
        assert body["error"].get("draining") is True
        # every in-flight client finished clean
        for t in threads:
            t.join(120)
            assert not t.is_alive()
        assert not errors, errors
        assert all(r is not None and len(r["tokens"]) == 18
                   for r in replies)
        # the loop parks and latches the drained event a beat after
        # the last future resolves — wait for it, then probe HTTP
        assert api.scheduler_.drain(timeout=60) is True
        # healthz reports the drain (503 so routers stop sending)
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/healthz", timeout=30)
        assert e.value.code == 503
        health = json.loads(e.value.read().decode())
        assert health["status"] == "draining"
        assert health["drained"] is True
        snap = json.load(urllib.request.urlopen(
            url + "/serving/metrics", timeout=30))
        assert snap["draining"] is True
        # drained: blocks are free or prefix-cache residents (ON by
        # default since PR 10), none slot-private
        assert snap["kv_blocks_used"] == snap.get(
            "prefix_cache_blocks_resident", 0)
    finally:
        api.stop()
        loader.close()


def test_rest_deadline_408_carries_tokens(f32):
    """Deadline expiry surfaces as HTTP 408 with the partial-decode
    count in the structured body (the client knows what it got)."""
    api, loader, post = _serve_api("fault-rest-408", max_slots=1,
                                   request_timeout=0.4)
    try:
        assert api.scheduler_ is not None
        # the first token lands at prefill; each later step then eats
        # 50 ms, so the 0.4s deadline expires mid-decode (the model's
        # window is 24, so 2 + 20 stays inside it)
        faults.inject("serving.scheduler.step", "delay", arg=0.05)
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/generate", {"prompt": [3, 1], "steps": 20})
        assert e.value.code == 408
        body = json.loads(e.value.read().decode())
        assert body["error"]["code"] == 408
        assert body["error"]["tokens_generated"] > 0
        faults.clear()
        _clean(api.scheduler_)
    finally:
        api.stop()
        loader.close()


def test_rest_injected_fault_is_structured_500(f32):
    """An injected handler exception answers a structured 500 — and
    the next request is unharmed."""
    api, loader, post = _serve_api("fault-rest-500")
    try:
        faults.inject("restful.generate", "exception", times=1)
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/generate", {"prompt": [3, 1], "steps": 2})
        assert e.value.code == 500
        body = json.loads(e.value.read().decode())
        assert "injected fault" in body["error"]["message"]
        assert len(post("/generate",
                        {"prompt": [3, 1], "steps": 2})["tokens"]) == 4
    finally:
        api.stop()
        loader.close()


def test_rest_injected_http_error_is_structured_reply(f32):
    """The ``http_error`` action at a REST point answers a structured
    JSON error with the INJECTED status (a replica that deliberately
    replies 503 — router/fleet drills), Retry-After included for 503,
    and the handler survives for the next request."""
    api, loader, post = _serve_api("fault-rest-http503")
    try:
        faults.inject("restful.generate", "http_error", arg=503,
                      times=1)
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/generate", {"prompt": [3, 1], "steps": 2})
        assert e.value.code == 503
        assert int(e.value.headers["Retry-After"]) >= 1
        body = json.loads(e.value.read().decode())
        assert body["error"]["code"] == 503
        assert "injected HTTP 503" in body["error"]["message"]
        assert len(post("/generate",
                        {"prompt": [3, 1], "steps": 2})["tokens"]) == 4
    finally:
        api.stop()
        loader.close()


def test_rest_admin_token_gates_remote_drain(f32):
    """Loopback keeps its admin access; the Bearer check is what a
    REMOTE router would pass — exercised here by asserting the token
    comparison path (wrong token → 403 even from loopback would be
    too strict, so the check is peer-first: loopback always passes,
    non-loopback needs the exact token)."""
    from veles_tpu.restful_api import RESTfulAPI
    saved = root.common.api.get("admin_token", None)
    root.common.api.admin_token = "sekret"
    api, loader, post = _serve_api("fault-rest-admin")
    try:
        # loopback passes with no token at all (unchanged contract)
        drain = post("/drain", {})
        assert drain["draining"] is True
        # the token comparison itself: simulate the handler check for
        # a non-loopback peer (the HTTP server binds loopback in
        # tier-1, so the Bearer path is unit-checked through the
        # handler's own predicate)
        handler = type("peer", (), {})()
        checks = []
        for peer, auth, want in [
                ("10.0.0.9", "Bearer sekret", True),
                ("10.0.0.9", "Bearer wrong", False),
                ("10.0.0.9", "", False),
                ("127.0.0.1", "", True)]:
            handler.client_address = (peer, 1234)
            handler.headers = {"Authorization": auth}
            # borrow the bound predicate off the live handler class
            cls = api._server_.RequestHandlerClass
            checks.append(cls._admin_ok(handler) == want)
        assert all(checks), checks
    finally:
        root.common.api.admin_token = saved
        api.stop()
        loader.close()


# -- coordinator failover -----------------------------------------------------

class FakeMasterWorkflow:
    """Exact-accounting master (models tests/test_coordinator.py)."""

    def __init__(self, n_jobs=6):
        self.n_jobs = n_jobs
        self.served = 0
        self.applied = []
        self.dropped = []
        self.in_flight = {}

    def checksum(self):
        return "abc123"

    def generate_data_for_slave(self, slave_id):
        self.served += 1
        self.in_flight.setdefault(slave_id, []).append(self.served)
        return {"job_no": self.served}

    def apply_data_from_slave(self, data, slave_id):
        self.applied.append((slave_id, data))
        jobs = self.in_flight.get(slave_id)
        if jobs:
            jobs.pop()

    def drop_slave(self, slave_id):
        self.dropped.append(slave_id)
        self.served -= len(self.in_flight.pop(slave_id, []))

    def has_more_jobs(self):
        return self.served < self.n_jobs

    def all_jobs_done(self):
        return len(self.applied) >= self.n_jobs


class FakeWorkerWorkflow:
    def __init__(self, checksum="abc123"):
        self._checksum = checksum
        self.jobs = []

    def checksum(self):
        return self._checksum

    def do_job(self, data, update, callback):
        self.jobs.append(data)
        callback({"result": data["job_no"] * 10})


def run_loop(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def test_dead_worker_heartbeat_failover_exact_epoch():
    """Acceptance (4): a worker that goes SILENT mid-job (job hangs,
    heartbeats stop — the half-dead case a closed socket never
    reports) is declared dead by the heartbeat tier, its job frame is
    reassigned to the live worker, and the epoch completes with exact
    sample accounting."""
    from veles_tpu.parallel.coordinator import (
        Coordinator, WorkerClient)
    from veles_tpu.telemetry import metrics
    reassigned = metrics.counter("veles_coordinator_reassigned_total")
    before = reassigned.value
    # wA: the first job hangs 1.5s in the executor; its heartbeats
    # pass twice (so the coordinator KNOWS it pings) then drop —
    # silence while holding a job frame
    faults.inject("coordinator.worker.job", "hang", arg=1.5,
                  times=1, key="wA")
    faults.inject("coordinator.worker.heartbeat", "drop", after=2,
                  key="wA")

    async def main():
        master = FakeMasterWorkflow(n_jobs=4)
        coord = Coordinator(master, port=0, job_timeout=30.0,
                            watchdog_interval=0.05,
                            heartbeat_timeout=0.4)
        await coord.start()
        addr = "127.0.0.1:%d" % coord.port
        dead = WorkerClient(FakeWorkerWorkflow(), addr,
                            worker_id="wA", heartbeat_interval=0.05,
                            reconnect_delay=0.05, max_reconnects=5)
        live = WorkerClient(FakeWorkerWorkflow(), addr,
                            worker_id="wB", heartbeat_interval=0.05)
        dead_task = asyncio.ensure_future(dead.run())
        await asyncio.wait_for(live.run(), 30)
        # the live worker finished the run; settle the dead one
        try:
            await asyncio.wait_for(dead_task, 10)
        except (ConnectionError, asyncio.TimeoutError, TimeoutError):
            dead_task.cancel()
        await coord.stop()
        return master, coord

    master, coord = run_loop(main())
    # exact accounting: every job applied exactly once — the hung
    # worker's frame was refiled (drop_slave) and re-served
    assert len(master.applied) == 4
    assert master.all_jobs_done()
    assert "wA" in master.dropped
    assert not any(master.in_flight.values())
    # the completing worker was the live one for the reassigned job
    assert any(wid == "wB" for wid, _ in master.applied)
    assert reassigned.value >= before + 1


def test_worker_reconnect_backoff():
    """Reconnects back off exponentially (with jitter) under a capped
    budget, counted in veles_coordinator_reconnects_total."""
    from veles_tpu.parallel.coordinator import WorkerClient
    from veles_tpu.telemetry import metrics
    counter = metrics.counter("veles_coordinator_reconnects_total")
    before = counter.value
    client = WorkerClient(FakeWorkerWorkflow(), "127.0.0.1:1",
                          reconnect_delay=0.05, max_reconnects=3)
    # deterministic schedule: delays are base*2^(n-1) scaled by
    # jitter in [0.5, 1.0] — total at least (0.05+0.1+0.2)/2
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="after 3 reconnect"):
        run_loop(asyncio.wait_for(client.run(), 30))
    assert time.monotonic() - t0 >= 0.17
    assert counter.value == before + 3
    assert client._backoff(1) <= 0.05
    assert client._backoff(10) <= client.reconnect_cap
