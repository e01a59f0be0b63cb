"""The streaming & QoS delivery layer (``serving/streams.py`` +
``serving/openai_api.py``): SSE-vs-batch bit parity (greedy + seeded,
spec on/off, across a forced preempt→resume), mid-stream disconnects
freeing slot + KV blocks, the mixed-priority soak (bounded high-class
TTFT while low-class requests are preempted/resumed/shed), class-aware
shedding, and the OpenAI facade round-trip over a plain HTTP client —
direct and through the router fleet."""

import json
import socket
import struct
import time
import urllib.error
import urllib.request

import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.streaming


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2, blocks=1):
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


# -- stream-vs-batch parity ---------------------------------------------------

def test_stream_vs_batch_bit_parity(f32):
    """Acceptance: the concatenated stream equals the batch reply
    bit for bit — greedy and seeded, spec decoding off AND on, and
    across a preemption forced mid-stream (resume re-emits
    nothing)."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("stream-parity")
    submits = [([3, 1, 4, 3, 1, 4], 12, dict(seed=0)),
               ([7, 2] * 4, 10, dict(temperature=0.9, top_k=5,
                                     seed=41))]
    for spec in (False, True):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 prefill_chunk=4, spec=spec,
                                 warm_buckets=False).start()
        try:
            batch = [sch.submit(p, n, **kw).result(240)
                     for p, n, kw in submits]
            streams = [sch.submit(p, n, stream=True, **kw)
                       for p, n, kw in submits]
            # force a preemption while the streams decode: wait for
            # each stream's FIRST token (both admitted, mid-decode),
            # then evict — the resumed stream must continue where it
            # left off, not restart or re-emit
            its = [iter(ts) for ts in streams]
            first = [next(it) for it in its]
            sch.request_preempt()
            for ts, it, f0, ref in zip(streams, its, first, batch):
                toks = [f0] + [t for t in it]
                assert ts.prompt + toks == ref, (spec, toks, ref)
                assert ts.result(10) == ref
            snap = sch.metrics()
            assert snap["preempts"] >= 1, "preempt never landed"
            sch.check_kv()
        finally:
            sch.close()


def test_stream_cancel_frees_blocks(f32):
    """Cancelling a TokenStream mid-iteration releases the slot and
    KV blocks at the next boundary; the block sweep stays clean."""
    from veles_tpu.serving import (
        InferenceScheduler, RequestCancelledError)
    fw = _tiny_fw("stream-cancel")
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             warm_buckets=False).start()
    try:
        ts = sch.submit([1, 2, 3], 40, stream=True)
        it = iter(ts)
        next(it)
        next(it)
        ts.cancel()
        with pytest.raises(RequestCancelledError):
            for _ in it:
                pass
        deadline = time.monotonic() + 30
        while sch.in_flight:
            assert time.monotonic() < deadline, "cancel leaked"
            time.sleep(0.01)
        sch.check_kv()
        assert sch.metrics()["requests_cancelled"] == 1
        # the scheduler still serves after the cancel
        assert len(sch.submit([5], 2).result(60)) == 3
    finally:
        sch.close()


# -- priority classes ---------------------------------------------------------

@pytest.mark.flaky(reason="TTFT-separation assertion is wall-clock "
                   "on a 1-core CI host: ambient load occasionally "
                   "delays the high-class probe past the low class's "
                   "p95 (passes 3/3 isolated and in most full-suite "
                   "runs); single retry per "
                   "conftest.pytest_runtest_protocol")
def test_mixed_priority_soak(f32):
    """Acceptance: under sustained low-class load that saturates the
    slots, high-class probes preempt their way in — high-class TTFT
    p95 stays bounded and far under the low class's — while every
    preempted low request resumes and completes BIT-IDENTICALLY, with
    zero KV block leaks.  Runs with the flipped-on spec + prefix-
    cache defaults (the soak that gates the default flip)."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("qos-soak")
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             warm_buckets=False).start()
    try:
        assert sch.spec and sch.prefix_cache, \
            "the soak must exercise the flipped-on defaults"
        low_prompts = [[3, 1, 4], [5, 2], [7, 2, 9], [2, 2, 4]]
        # solo references (also warms the prefill/step shapes so the
        # timed probes below measure scheduling, not compiles)
        refs = [sch.submit(p, 24, seed=0).result(240)
                for p in low_prompts]
        sch.submit([9, 1], 3, priority="high").result(240)
        lows = [sch.submit(p, 24, seed=0, priority="low")
                for p in low_prompts]
        time.sleep(0.05)  # let the first lows claim the slots
        high_ttft = []
        for _ in range(5):
            t0 = time.monotonic()
            sch.submit([9, 1], 3, priority="high").result(120)
            high_ttft.append(time.monotonic() - t0)
            time.sleep(0.01)
        outs = [f.result(240) for f in lows]
        assert outs == refs, "a preempted low request diverged"
        snap = sch.metrics()
        assert snap["preempts"] >= 1, "no preemption under pressure"
        assert snap["classes"]["low"]["preempts"] >= 1
        assert snap["classes"]["high"]["preempts"] == 0, \
            "a high-class request was victimized"
        high_ttft.sort()
        p95 = high_ttft[max(0, int(len(high_ttft) * 0.95) - 1)]
        assert p95 < 5.0, "high-class TTFT p95 %.2fs unbounded" % p95
        low_p95 = snap["classes"]["low"]["ttft_ms_p95"]
        assert snap["classes"]["high"]["ttft_ms_p95"] < low_p95, \
            "priority classes did not separate TTFT"
        sch.check_kv()
    finally:
        sch.close()


def test_class_aware_shedding(f32):
    """Block-pressure shedding trips for the LOW class while the
    high class still admits (class-scaled thresholds), the shed 503
    carries a class-aware Retry-After (low backs off longest), and a
    full queue seats a high arrival by evicting a queued low."""
    from veles_tpu.serving import InferenceScheduler, QueueFullError
    fw = _tiny_fw("qos-shed", window=256)
    sch = InferenceScheduler(fw, max_slots=1, window=256,
                             block_size=4, kv_blocks=16,
                             prefill_chunk=0, shed_block_factor=1.0,
                             max_queue=8, warm_buckets=False,
                             spec=False, prefix_cache=False).start()
    try:
        busy = sch.submit([1, 2], 40)          # holds the one slot
        time.sleep(0.05)
        # 16-block pool, factor 1.0: low sheds at 8 queued blocks,
        # normal at 16, high at 24
        q1 = sch.submit([1], 30)               # 8 blocks queued
        with pytest.raises(QueueFullError) as e_low:
            sch.submit([2], 30, priority="low")
        assert e_low.value.retry_after == 4    # low backs off longest
        q2 = sch.submit([2], 29, priority="high")  # high still admits
        snap = sch.metrics()
        assert snap["classes"]["low"]["sheds"] == 1
        assert snap["classes"].get("high", {}).get("sheds", 0) == 0
        for f in (busy, q1, q2):
            f.result(240)
        # depth-cap seat eviction: fill the queue with lows, then a
        # high arrival takes the youngest low's seat (503 on the low)
        sch2 = InferenceScheduler(fw, max_slots=1, window=256,
                                  block_size=4,
                                  prefill_chunk=0, max_queue=2,
                                  warm_buckets=False, spec=False,
                                  prefix_cache=False).start()
        try:
            b2 = sch2.submit([1, 2], 60)
            time.sleep(0.05)
            lo_a = sch2.submit([1], 4, priority="low")
            lo_b = sch2.submit([2], 4, priority="low")
            hi = sch2.submit([3], 4, priority="high")
            with pytest.raises(QueueFullError):
                lo_b.result(60)   # the YOUNGEST low lost its seat
            assert len(hi.result(240)) == 5
            assert len(lo_a.result(240)) == 5
            b2.result(240)
        finally:
            sch2.close()
    finally:
        sch.close()


# -- REST: SSE + the OpenAI facade --------------------------------------------

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
                     name=name + "-api", max_slots=2,
                     serving_warm_buckets=False, **kwargs)
    api.output = fw[-1].output
    api.initialize()

    def post(path, payload, timeout=120):
        req = urllib.request.Request(
            "http://127.0.0.1:%d%s" % (api.port, path),
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=timeout)

    return api, loader, post


def _read_sse(resp):
    """Drain one SSE response → list of JSON payloads (ends at
    ``data: [DONE]`` or EOF)."""
    events = []
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if line == b"data: [DONE]":
            break
        if line.startswith(b"data: "):
            events.append(json.loads(line[6:]))
    return events


def test_rest_sse_stream_matches_batch(f32):
    """POST /generate {"stream": true} delivers SSE frames whose
    concatenation is bit-identical to the batch reply, with usage
    accounting on the terminal frame."""
    api, loader, post = _serve_api("sse-parity")
    try:
        ref = json.load(post("/generate",
                             {"prompt": [3, 1, 4], "steps": 6,
                              "seed": 5, "temperature": 0.8,
                              "top_k": 4}))["tokens"]
        resp = post("/generate", {"prompt": [3, 1, 4], "steps": 6,
                                  "seed": 5, "temperature": 0.8,
                                  "top_k": 4, "stream": True})
        assert resp.headers["Content-Type"] == "text/event-stream"
        events = _read_sse(resp)
        toks = [e["token"] for e in events if "token" in e]
        final = [e for e in events if e.get("done")][0]
        assert [3, 1, 4] + toks == ref
        assert final["tokens"] == ref
        assert final["usage"]["completion_tokens"] == 6
        # streaming a batch of prompts is a client error
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/generate", {"prompt": [[3], [1]], "steps": 2,
                               "stream": True})
        assert e.value.code == 400
    finally:
        api.stop()
        loader.close()


def test_rest_sse_disconnect_frees_slot_and_blocks(f32):
    """A client that vanishes mid-stream (TCP RST) cancels its
    request: the slot and KV blocks free at the next boundary and
    the sweep stays clean — decode never runs for a dead socket."""
    api, loader, post = _serve_api("sse-drop")
    try:
        json.load(post("/generate", {"prompt": [3, 1], "steps": 2}))
        s = socket.create_connection(("127.0.0.1", api.port),
                                     timeout=30)
        body = json.dumps({"prompt": [3, 1, 4], "steps": 18,
                           "stream": True}).encode()
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        assert s.recv(64), "no SSE bytes arrived"
        # RST (not FIN): the server's next write fails immediately
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
        sch = api.scheduler_
        deadline = time.monotonic() + 30
        while sch.in_flight:
            assert time.monotonic() < deadline, \
                "disconnected stream not reaped"
            time.sleep(0.02)
        sch.check_kv()
        assert sch.metrics()["requests_cancelled"] >= 1
    finally:
        api.stop()
        loader.close()


def test_openai_facade_roundtrip(f32):
    """/v1/models, /v1/completions (batch + SSE + usage +
    finish_reason), /v1/embeddings (batched, unit-norm,
    deterministic) and /v1/classify round-trip over a plain HTTP
    client, with structured 400s on junk."""
    api, loader, post = _serve_api("openai-rt")
    try:
        base = "http://127.0.0.1:%d" % api.port
        models = json.load(urllib.request.urlopen(base + "/v1/models",
                                                  timeout=30))
        assert models["data"][0]["id"] == "veles-lm"
        ref = json.load(post("/generate", {"prompt": [3, 1, 4],
                                           "steps": 6}))["tokens"]
        c = json.load(post("/v1/completions",
                           {"prompt": [3, 1, 4], "max_tokens": 6}))
        assert c["object"] == "text_completion"
        assert c["choices"][0]["tokens"] == ref[3:]
        assert c["choices"][0]["finish_reason"] == "length"
        assert c["usage"] == {"prompt_tokens": 3,
                              "completion_tokens": 6,
                              "total_tokens": 9}
        # neutral SDK defaults pass; non-neutral knobs reject
        json.load(post("/v1/completions",
                       {"prompt": [3, 1], "max_tokens": 2,
                        "top_p": 1, "n": 1,
                        "frequency_penalty": 0}))
        # batch of prompts → one indexed choice per row
        cb = json.load(post("/v1/completions",
                            {"prompt": [[3, 1, 4], [5, 2]],
                             "max_tokens": 4, "echo": True}))
        assert [ch["index"] for ch in cb["choices"]] == [0, 1]
        assert cb["choices"][0]["tokens"][:3] == [3, 1, 4]  # echo
        # streaming chunks concatenate to the batch reply
        resp = post("/v1/completions",
                    {"prompt": [3, 1, 4], "max_tokens": 6,
                     "stream": True})
        chunks = _read_sse(resp)
        toks = [t for ch in chunks
                for t in ch["choices"][0]["tokens"]]
        assert toks == ref[3:]
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
        assert chunks[-1]["usage"]["completion_tokens"] == 6
        # embeddings: unit norm, batch-index aligned, deterministic
        e = json.load(post("/v1/embeddings",
                           {"input": [[3, 1, 4], [5, 2]]}))
        v0 = numpy.asarray(e["data"][0]["embedding"])
        assert abs(numpy.linalg.norm(v0) - 1.0) < 1e-5
        assert e["usage"]["prompt_tokens"] == 5
        e2 = json.load(post("/v1/embeddings", {"input": [3, 1, 4]}))
        numpy.testing.assert_allclose(
            e2["data"][0]["embedding"], v0, atol=1e-6)
        # classify: a log-probability distribution over the classes
        cl = json.load(post("/v1/classify",
                            {"input": [[3, 1, 4]], "top": 3}))
        assert len(cl["data"][0]["top"]) == 3
        assert abs(sum(numpy.exp(cl["data"][0]["logprobs"]))
                   - 1.0) < 1e-4

        def expect_400(path, payload, needle):
            with pytest.raises(urllib.error.HTTPError) as err:
                post(path, payload)
            assert err.value.code == 400, payload
            body = err.value.read().decode(errors="replace")
            assert needle in body, (needle, body)

        expect_400("/v1/completions", {"max_tokens": 2}, "prompt")
        expect_400("/v1/completions",
                   {"prompt": "text", "max_tokens": 2}, "token")
        expect_400("/v1/completions",
                   {"prompt": [3, 1], "max_tokens": 2, "n": 3}, "n")
        expect_400("/v1/completions",
                   {"prompt": [3, 1], "max_tokens": 2,
                    "priority": "urgent"}, "priority")
        expect_400("/v1/embeddings", {"input": []}, "input")
        expect_400("/v1/embeddings", {"input": [99, 1]}, "token ids")
    finally:
        api.stop()
        loader.close()


# -- through the router fleet -------------------------------------------------

def test_stream_and_facade_through_router(f32):
    """Acceptance: SSE streams and the /v1 endpoints served through
    the router fleet — the stream pins one replica (header exposed),
    concatenation still matches the batch reply, a mid-stream client
    disconnect cancels on the replica (no leaked blocks), and
    /v1/embeddings round-trips with affinity/structured errors
    intact."""
    from veles_tpu import prng
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    from veles_tpu.serving import Router
    from veles_tpu.serving.fleet import LocalReplica

    def make_replica(name):
        prng.get("default").seed(1234)  # identical weights fleetwide
        dev = Device(backend="numpy")
        wf = AcceleratedWorkflow(None, name=name)
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
                         name=name + "-api", max_slots=2,
                         serving_warm_buckets=False)
        api.output = fw[-1].output
        api.initialize()
        return LocalReplica(api, loader)

    reps = [make_replica("sse-fleet-r%d" % i) for i in range(2)]
    router = Router(health_interval=0.2, request_timeout=60.0).start()
    try:
        for i, rep in enumerate(reps):
            router.add_replica(rep.host, rep.port,
                               replica_id="sf%d" % i)
        url = router.url

        def post(path, payload):
            req = urllib.request.Request(
                url + path, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=60)

        ref = json.load(post("/generate", {"prompt": [3, 1, 4],
                                           "steps": 6}))["tokens"]
        resp = post("/generate", {"prompt": [3, 1, 4], "steps": 6,
                                  "stream": True})
        assert resp.headers["Content-Type"] == "text/event-stream"
        assert resp.headers["X-Veles-Replica"], "stream not pinned"
        events = _read_sse(resp)
        toks = [e["token"] for e in events if "token" in e]
        assert [3, 1, 4] + toks == ref
        # the facade forwards with the same machinery
        c = json.load(post("/v1/completions",
                           {"prompt": [3, 1, 4], "max_tokens": 6}))
        assert c["choices"][0]["tokens"] == ref[3:]
        e = json.load(post("/v1/embeddings", {"input": [[3, 1, 4]]}))
        assert len(e["data"][0]["embedding"]) == 8
        m = json.load(urllib.request.urlopen(url + "/v1/models",
                                             timeout=30))
        assert m["data"][0]["id"] == "veles-lm"
        # structured errors stay intact through the router
        with pytest.raises(urllib.error.HTTPError) as err:
            post("/v1/completions", {"prompt": [3, 1],
                                     "max_tokens": 2, "n": 5})
        assert err.value.code == 400
        assert "error" in json.loads(err.value.read().decode())
        # mid-stream disconnect through the router cancels upstream
        s = socket.create_connection(("127.0.0.1", router.port),
                                     timeout=30)
        body = json.dumps({"prompt": [3, 1, 4], "steps": 18,
                           "stream": True}).encode()
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        assert s.recv(64), "no forwarded SSE bytes"
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
        deadline = time.monotonic() + 30
        while any(r.api.scheduler_.in_flight for r in reps):
            assert time.monotonic() < deadline, \
                "router did not propagate the disconnect"
            time.sleep(0.02)
        for r in reps:
            r.api.scheduler_.check_kv()
        state = router.replica_state()
        assert state["router"]["streams_pinned"] >= 2
    finally:
        router.stop()
        for rep in reps:
            rep.stop()
