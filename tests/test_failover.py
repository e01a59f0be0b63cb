"""No request left behind (marker ``failover``): transparent
mid-stream failover (the router resumes a dead replica's SSE stream
on a peer through the ``resume_tokens`` lane, spliced bit-identical),
hardened disaggregated handoffs (per-hop retries, export TTL GC, the
one-shot 409 race) and fleet role rebalancing — driven by the chaos
phase-matrix soak that kills a replica at every request phase and
asserts zero client-visible failures with ``check_kv()`` clean on
every survivor."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from veles_tpu import faults
from veles_tpu.config import root

from tests.test_router import _make_replica, _post

pytestmark = pytest.mark.failover


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


def _read_sse(resp, on_frame=None):
    """Collect one SSE response's frames ([DONE] excluded) as parsed
    JSON payloads; ``on_frame(payload, index)`` runs after each frame
    (the mid-stream chaos hook).  Returns (token_frames, terminal,
    error_frames)."""
    frames = []
    data = None
    i = 0
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.rstrip(b"\r\n")
        if line.startswith(b"data: "):
            data = line[6:]
            continue
        if line or data is None:
            continue
        # blank line: one frame complete
        payload, data = data, None
        if payload == b"[DONE]":
            break
        obj = json.loads(payload.decode())
        frames.append(obj)
        if on_frame is not None:
            on_frame(obj, i)
        i += 1
    tokens = [f["token"] for f in frames if "token" in f]
    terminal = next((f for f in frames if "done" in f), None)
    errors = [f for f in frames if "error" in f]
    return tokens, terminal, errors


def _stream(url, payload, on_frame=None, timeout=120, headers=None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps(dict(payload, stream=True)).encode(),
        headers=hdrs)
    resp = urllib.request.urlopen(req, timeout=timeout)
    try:
        return _read_sse(resp, on_frame=on_frame)
    finally:
        resp.close()


# -- scheduler resume lane (the bit-parity core) ------------------------------

@pytest.mark.parametrize("spec", [False, True])
def test_resume_tokens_parity_greedy_and_seeded(
        f32, spec_trained_chain, spec):
    """``submit(resume_tokens=...)`` continues a stream bit-identical
    to the uninterrupted run — greedy AND seeded, spec on/off — the
    sink sees only the newly drawn tokens, and the slot/blocks come
    back clean."""
    from veles_tpu.serving import InferenceScheduler
    fw, pattern = spec_trained_chain
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             spec=spec, warm_buckets=False).start()
    try:
        prompt = (pattern * 2)[:10]
        for kwargs in ({"seed": 0},
                       {"temperature": 0.8, "top_k": 4, "seed": 7}):
            want = sch.submit(prompt, 9, **kwargs).result(240)
            gen = want[len(prompt):]
            for cut in (0, 3, len(gen) - 1):
                ts = sch.submit(prompt, 9, stream=True,
                                resume_tokens=gen[:cut], **kwargs)
                got = ts.result(240)
                assert got == want, (kwargs, cut)
                # the stream delivered ONLY the continuation
                list(ts)
                assert ts.tokens == gen[cut:], (kwargs, cut)
        with pytest.raises(ValueError):
            sch.submit(prompt, 3, resume_tokens=[1, 2, 3], seed=0)
        sch.check_kv()
    finally:
        sch.close()


def test_resume_tokens_int8_quant_noise_contract(
        f32, spec_trained_chain):
    """int8 pools: a resumed stream COMPLETES with the right budget
    and clean pools; bit-parity is documented as NOT guaranteed
    (re-prefill computes from f32 staging where the original decode
    read dequantized keys — the PR 12 preempt→resume contract)."""
    from veles_tpu.serving import InferenceScheduler
    fw, pattern = spec_trained_chain
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             kv_dtype="int8",
                             warm_buckets=False).start()
    try:
        prompt = (pattern * 2)[:10]
        want = sch.submit(prompt, 8, seed=0).result(240)
        gen = want[len(prompt):]
        got = sch.submit(prompt, 8, seed=0,
                         resume_tokens=gen[:3]).result(240)
        assert len(got) == len(prompt) + 8
        assert got[:len(prompt) + 3] == want[:len(prompt) + 3]
        sch.check_kv()
    finally:
        sch.close()


# -- export TTL GC + the one-shot 409 race ------------------------------------

def test_export_ttl_gc_and_double_fetch_409(
        f32, spec_trained_chain, monkeypatch):
    """Unfetched export records are TTL-swept by the scheduler loop
    (idle replicas included) with the expired/pending metrics
    moving; a fetched handle answers ``"fetched"``/HTTP 409 to the
    double-fetch race instead of a misleading 404."""
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.serving import scheduler as sched_mod
    fw, pattern = spec_trained_chain
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             role="prefill",
                             warm_buckets=False).start()
    try:
        prompt = (pattern * 2)[:8]
        # one-shot + race: first fetch claims, second is "fetched"
        h = sch.submit_prefill(prompt).result(240)["handle"]
        assert sch.kv_export_status(h) == "pending"
        assert sch.kv_export(h) is not None
        assert sch.kv_export(h) is None
        assert sch.kv_export_status(h) == "fetched"
        assert sch.kv_export_status("nope") == "unknown"
        assert sch.metrics()["kv_exports_fetched"] == 1
        # TTL sweep: park a record, shrink the TTL, and let the IDLE
        # loop's 1 s housekeeping tick GC it (no traffic needed)
        h2 = sch.submit_prefill(prompt).result(240)["handle"]
        assert sch.metrics()["kv_exports_pending"] == 1
        monkeypatch.setattr(sched_mod, "EXPORT_TTL", 0.05)
        deadline = time.monotonic() + 10
        while sch.metrics()["kv_exports_expired"] < 1:
            assert time.monotonic() < deadline, "TTL sweeper idle"
            time.sleep(0.1)
        assert sch.metrics()["kv_exports_pending"] == 0
        assert sch.kv_export(h2) is None
        assert sch.kv_export_status(h2) == "unknown"  # swept, gone
        sch.check_kv()
    finally:
        sch.close()


def test_double_fetch_409_over_rest(f32):
    """The wire shape of the race: the second GET of a one-shot
    export handle is a structured 409."""
    rep = _make_replica("gc-pre", serving_warm_buckets=False,
                        serving_block_size=4,
                        serving_prefill_chunk=4,
                        serving_role="prefill")
    url = "http://127.0.0.1:%d" % rep.port
    try:
        req = urllib.request.Request(
            url + "/serving/prefill",
            data=json.dumps({"prompt": [3, 1, 4, 1]}).encode(),
            headers={"Content-Type": "application/json"})
        handle = json.load(urllib.request.urlopen(
            req, timeout=60))["handle"]
        path = "/serving/kv_export/%s" % handle
        assert urllib.request.urlopen(url + path,
                                      timeout=60).status == 200
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + path, timeout=60)
        assert e.value.code == 409
        body = json.loads(e.value.read().decode())
        assert "already fetched" in body["error"]["message"]
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/serving/kv_export/junk",
                                   timeout=60)
        assert e.value.code == 404
    finally:
        rep.stop()


# -- mid-stream failover (router e2e) -----------------------------------------

def test_stream_failover_resumes_bit_identical(f32):
    """The pinned replica 'dies' under a token frame (the armed
    ``router.stream.replica_death`` window): the router resumes on
    the peer, the client sees zero error frames, and both greedy and
    seeded streams complete IDENTICAL to an uninterrupted run —
    terminal frame included."""
    from veles_tpu.serving import Router
    reps = [_make_replica("fo-r%d" % i, serving_warm_buckets=False)
            for i in range(2)]
    router = Router(health_interval=0.1, health_timeout=5.0,
                    request_timeout=90.0, retries=4,
                    retry_delay=0.02, retry_cap=0.2).start()
    try:
        for i, rep in enumerate(reps):
            router.add_replica(rep.host, rep.port,
                               replica_id="fo%d" % i)
        for body in ({"prompt": [3, 1, 4], "steps": 8},
                     {"prompt": [3, 1, 4], "steps": 8,
                      "temperature": 0.8, "top_k": 4, "seed": 17}):
            _, want = _post(router.url, body)   # uninterrupted ref
            before = dict(router.stats.snapshot()["stream_failovers"])
            faults.inject("router.stream.replica_death", "drop",
                          after=2, times=1)
            toks, terminal, errors = _stream(router.url, body)
            assert not errors, errors
            assert terminal is not None \
                and terminal["tokens"] == want["tokens"], body
            assert toks == want["tokens"][len(body["prompt"]):]
            after = router.stats.snapshot()["stream_failovers"]
            assert after.get("resumed", 0) \
                == before.get("resumed", 0) + 1
            faults.clear("router.stream.replica_death")
        # an unseeded sampled stream is NOT replayable: the armed
        # death truncates it (legacy contract), zero error frames
        faults.inject("router.stream.replica_death", "drop",
                      after=1, times=1)
        toks, terminal, errors = _stream(
            router.url, {"prompt": [3, 1, 4], "steps": 6,
                         "temperature": 0.9})
        assert terminal is None or len(toks) == 6
        for rep in reps:
            rep.api.scheduler_.check_kv()
    finally:
        router.stop()
        for rep in reps:
            rep.stop()


def test_stream_failover_real_kill_and_respawn(f32):
    """A REAL replica death mid-stream: the process stops under an
    open SSE connection, the router splices the continuation from
    the peer (zero error frames, greedy tokens identical to the
    reference), and the fleet respawns the victim."""
    from veles_tpu.serving import Fleet, Router
    router = Router(health_interval=0.1, health_timeout=5.0,
                    request_timeout=90.0, retries=4,
                    retry_delay=0.02, retry_cap=0.2).start()
    counter = [0]

    def spawn(index):
        counter[0] += 1
        return _make_replica("kill-r%d-g%d" % (index, counter[0]),
                             serving_warm_buckets=False)

    fleet = Fleet(spawn, 2, router=router,
                  monitor_interval=0.1).start()
    try:
        body = {"prompt": [3, 1, 4, 1], "steps": 10}
        _, want = _post(router.url, body)
        # slow every decode step so the kill lands mid-stream
        faults.inject("serving.scheduler.step", "delay", arg=0.05)
        req = urllib.request.Request(
            router.url + "/generate",
            data=json.dumps(dict(body, stream=True)).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=90)
        # kill the replica the stream is actually PINNED to
        pinned = resp.headers["X-Veles-Replica"]
        victim_idx = next(i for i in (0, 1)
                          if fleet.replica_id(i) == pinned)
        killed = []

        def on_frame(obj, i):
            if i == 2 and not killed:
                fleet.handles()[victim_idx].stop()
                killed.append(True)

        try:
            toks, terminal, errors = _read_sse(resp,
                                               on_frame=on_frame)
        finally:
            resp.close()
        assert killed, "the kill hook never ran"
        assert not errors, errors
        assert terminal is not None
        assert terminal["tokens"] == want["tokens"]
        assert toks == want["tokens"][4:]
        snap = router.stats.snapshot()
        assert snap["stream_failovers"].get("resumed", 0) >= 1
        # the victim respawns; survivors' pools stay clean
        deadline = time.monotonic() + 30
        while not (fleet.handles()[victim_idx]
                   and fleet.handles()[victim_idx].alive()):
            assert time.monotonic() < deadline, "no respawn"
            time.sleep(0.05)
        faults.clear()
        for handle in fleet.handles().values():
            handle.api.scheduler_.check_kv()
    finally:
        faults.clear()
        fleet.stop()
        router.stop()


# -- the chaos phase matrix (acceptance) --------------------------------------

def test_chaos_phase_matrix_zero_client_failures(f32):
    """Kill (or sever) a replica at EVERY request phase — queued,
    mid-prefill, export-pending (between export and fetch),
    mid-import, mid-stream — under a disagg-capable fleet: zero
    client-visible failures, greedy replies identical to the
    reference, ``check_kv()`` clean on every survivor."""
    from veles_tpu.serving import Router
    mk = dict(serving_warm_buckets=False, serving_block_size=4,
              serving_prefill_chunk=4)
    both = _make_replica("pm-both", **mk)
    pre = _make_replica("pm-pre", serving_role="prefill", **mk)
    dec = _make_replica("pm-dec", serving_role="decode", **mk)
    router = Router(health_interval=0.1, health_timeout=5.0,
                    request_timeout=90.0, retries=4,
                    retry_delay=0.02, retry_cap=0.2).start()
    try:
        router.add_replica("127.0.0.1", both.port,
                           replica_id="both")
        router.add_replica("127.0.0.1", pre.port, replica_id="pre")
        router.add_replica("127.0.0.1", dec.port, replica_id="dec")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            state = {r["id"]: r
                     for r in router.replica_state()["replicas"]}
            if state.get("pre", {}).get("role") == "prefill" \
                    and state.get("dec", {}).get("healthy") \
                    and state.get("both", {}).get("healthy"):
                break
            time.sleep(0.05)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        body = {"prompt": prompt, "steps": 8, "seed": 0}
        _, want = _post(router.url, body)

        # queued / admitting: the first attempt's handler 500s
        # before any scheduler work — the router replays it whole
        faults.inject("restful.generate", "http_error", arg=500,
                      times=1)
        _, got = _post(router.url, body)
        assert got["tokens"] == want["tokens"], "queued"

        # mid-prefill: the prefill pass dies on whichever replica
        # takes the request — retried elsewhere, nothing delivered
        faults.inject("serving.scheduler.prefill", "exception",
                      times=1)
        _, got = _post(router.url, body)
        assert got["tokens"] == want["tokens"], "mid-prefill"

        # export-pending: the specialist 'dies' between parking the
        # export and the router's fetch (the armed window) — with no
        # second specialist the request falls back colocated
        faults.inject("disagg.export.fetch", "drop", times=1)
        _, got = _post(router.url, body)
        assert got["tokens"] == want["tokens"], "export-pending"

        # mid-import: the decode replica dies scattering the blocks
        # — the router retries the SAME payload on the 'both' peer
        faults.inject("serving.scheduler.kv_import", "exception",
                      times=1)
        _, got = _post(router.url, body)
        assert got["tokens"] == want["tokens"], "mid-import"

        # mid-stream: the pinned replica dies under a token frame —
        # the stream resumes and splices bit-identically
        faults.inject("router.stream.replica_death", "drop",
                      after=1, times=1)
        toks, terminal, errors = _stream(router.url, body)
        assert not errors and terminal is not None, "mid-stream"
        assert terminal["tokens"] == want["tokens"], "mid-stream"

        # zero client-visible failures throughout; survivors clean
        for handle in (both, pre, dec):
            handle.api.scheduler_.check_kv()
    finally:
        router.stop()
        for handle in (both, pre, dec):
            handle.stop()


# -- role rebalancing ---------------------------------------------------------

def test_role_rebalance_restores_decode_pool(f32):
    """Kill the ONLY decode specialist of a prefill/prefill/decode
    fleet while its respawn is pinned failing: the monitor re-roles
    a surplus prefill replica into the decode pool
    (``veles_fleet_rebalances_total``), and a pending disagg-shaped
    request completes once coverage is back (clients ride the shed
    503s' Retry-After in between — backpressure, not an outage)."""
    from veles_tpu.serving import Fleet, Router
    from veles_tpu.telemetry import metrics
    rebalances = metrics.counter("veles_fleet_rebalances_total",
                                 labelnames=("role",))
    router = Router(health_interval=0.1, health_timeout=5.0,
                    request_timeout=90.0, retries=4,
                    retry_delay=0.02, retry_cap=0.2).start()
    counter = [0]

    def spawn(index, role):
        counter[0] += 1
        return _make_replica(
            "rb-r%d-g%d" % (index, counter[0]),
            serving_warm_buckets=False, serving_block_size=4,
            serving_prefill_chunk=4, serving_role=role)

    fleet = Fleet(spawn, 3, router=router, monitor_interval=0.1,
                  spawn_retries=1, spawn_delay=0.01,
                  roles=("prefill", "prefill", "decode")).start()
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            roles = {r["id"]: r["role"] for r in
                     router.replica_state()["replicas"]
                     if r["healthy"]}
            if sorted(roles.values()) == ["decode", "prefill",
                                          "prefill"]:
                break
            time.sleep(0.05)
        # startup must NOT have rebalanced anything: first spawns
        # always take their configured role
        assert sorted(roles.values()) == ["decode", "prefill",
                                          "prefill"], roles
        prompt = [3, 1, 4, 1]
        body = {"prompt": prompt, "steps": 6, "seed": 0}
        _, want = _post(router.url, body)
        before = rebalances.labels(role="decode").value

        # kill the only decode specialist AND pin its respawns dead
        # (its machine is gone) — only an active re-role can restore
        # decode coverage
        faults.inject("fleet.replica.spawn", "exception", key="2")
        t_kill = time.monotonic()
        fleet.handles()[2].stop()

        # a pending client retries through the shed window until the
        # fleet re-roles (Retry-After semantics)
        result = {}

        def client():
            give_up = time.monotonic() + 60
            while time.monotonic() < give_up:
                try:
                    _, out = _post(router.url, body, timeout=90)
                    result["tokens"] = out["tokens"]
                    result["t"] = time.monotonic()
                    return
                except urllib.error.HTTPError as e:
                    if e.code not in (502, 503):
                        result["error"] = e.code
                        return
                    time.sleep(0.1)
                except Exception:
                    time.sleep(0.1)

        t = threading.Thread(target=client)
        t.start()
        t.join(90)
        assert not t.is_alive() and "error" not in result, result
        assert result.get("tokens") == want["tokens"]
        mttr = result["t"] - t_kill
        assert rebalances.labels(role="decode").value > before
        # index 1 (the highest surplus prefill) now serves decode
        assert fleet.role_of(1) == "decode"
        assert fleet.role_of(0) == "prefill"
        assert mttr < 60, "rebalance took %.1fs" % mttr
        for idx, handle in fleet.handles().items():
            if handle is not None and handle.alive():
                handle.api.scheduler_.check_kv()
    finally:
        faults.clear()
        fleet.stop()
        router.stop()
