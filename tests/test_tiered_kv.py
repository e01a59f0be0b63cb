"""Fleet-global tiered KV (PR 19): the binary KV wire (zero-copy
framing, bit-identical round trips, the b64-JSON size win), the
host-RAM overflow tier (demote on trie eviction, token-verified
promote, byte budget + Watcher accounting), the byte-budgeted export
cap, host-promoted stream parity vs cold prefill (fp32 greedy+seeded,
spec on/off; int8 token-identical), cross-replica prefix shipping
through the router (topology routing + peer fetch, parity + fault
fallback), and ``check_kv()`` clean under churn with the promote
fault armed."""

import json
import time
import urllib.request

import numpy
import pytest

from veles_tpu import faults
from veles_tpu.config import root
from veles_tpu.memory import Watcher

pytestmark = pytest.mark.tiered_kv


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


@pytest.fixture(autouse=True)
def _no_faults():
    faults.clear()
    yield
    faults.clear()


# -- binary KV wire -----------------------------------------------------------

def _fake_record(dtype="float32", layers=2, blocks=3, bs=4, d=8,
                 logits=True, seed=0):
    rng = numpy.random.default_rng(seed)
    rec = {"handle": "h-test", "prompt": list(range(blocks * bs)),
           "length": blocks * bs, "kv_dtype":
           "int8" if dtype == "int8" else "fp32",
           "block_size": bs, "layers": {}}
    for i in range(layers):
        if dtype == "int8":
            row = {"k": rng.integers(-127, 128, (blocks, bs, d))
                   .astype(numpy.int8),
                   "v": rng.integers(-127, 128, (blocks, bs, d))
                   .astype(numpy.int8),
                   "k_scale": rng.random((blocks, bs))
                   .astype(numpy.float32),
                   "v_scale": rng.random((blocks, bs))
                   .astype(numpy.float32)}
        else:
            row = {"k": rng.standard_normal((blocks, bs, d))
                   .astype(numpy.float32),
                   "v": rng.standard_normal((blocks, bs, d))
                   .astype(numpy.float32)}
        rec["layers"][i] = row
    if logits:
        rec["logits"] = rng.standard_normal(11).astype(numpy.float32)
    return rec


def test_binary_wire_roundtrip_bit_identical():
    """encode→decode is bit-identical for fp32 and int8 records
    (scales included), with and without logits, and the ``extra``
    header dict rides the frame."""
    from veles_tpu.serving import disagg
    for dtype in ("float32", "int8"):
        for logits in (True, False):
            rec = _fake_record(dtype=dtype, logits=logits)
            blob = disagg.encode_export_binary(
                rec, extra={"steps": 6, "seed": 17})
            out, extra = disagg.decode_export_binary(blob)
            assert extra == {"steps": 6, "seed": 17}
            assert out["prompt"] == rec["prompt"]
            assert out["block_size"] == rec["block_size"]
            if logits:
                assert out["logits"].tobytes() \
                    == rec["logits"].tobytes()
            else:
                assert "logits" not in out
            for i, row in rec["layers"].items():
                for nm, a in row.items():
                    b = out["layers"][i][nm]
                    assert b.dtype == a.dtype and b.shape == a.shape
                    assert b.tobytes() == a.tobytes(), (i, nm)


def test_binary_wire_bfloat16_roundtrip():
    """The default compute dtype has NO Python buffer protocol
    (ml_dtypes bfloat16, kind 'E') — the frame must still carry it
    bit-identically, and by-name dtype lookup must resolve it."""
    import ml_dtypes
    from veles_tpu.serving import disagg
    rec = _fake_record()
    for row in rec["layers"].values():
        for nm in ("k", "v"):
            row[nm] = row[nm].astype(ml_dtypes.bfloat16)
    out, _ = disagg.decode_export_binary(
        disagg.encode_export_binary(rec))
    for i, row in rec["layers"].items():
        for nm, a in row.items():
            assert out["layers"][i][nm].dtype == a.dtype
            assert out["layers"][i][nm].tobytes() == a.tobytes()
    # the legacy b64-JSON path resolves the name too
    back = disagg.decode_export(
        json.loads(json.dumps(disagg.encode_export(rec))))
    assert back["layers"][0]["k"].tobytes() \
        == rec["layers"][0]["k"].tobytes()


def test_binary_wire_rejects_malformed():
    from veles_tpu.serving import disagg
    blob = disagg.encode_export_binary(_fake_record())
    for bad in (b"", b"XXXX" + blob[4:], blob[:20], blob[:-3]):
        with pytest.raises(ValueError):
            disagg.decode_export_binary(bad)


def test_binary_wire_beats_b64_json():
    """The size half of the wire acceptance: raw framing carries the
    same record in far fewer bytes than the b64-JSON envelope (the
    throughput half is bench.py tieredkv's kv_wire_mbps gap)."""
    from veles_tpu.serving import disagg
    rec = _fake_record(blocks=8, d=16)
    binary = disagg.encode_export_binary(rec)
    legacy = json.dumps(disagg.encode_export(rec)).encode()
    assert len(binary) < 0.8 * len(legacy), \
        (len(binary), len(legacy))


# -- host tier unit -----------------------------------------------------------

def test_host_tier_put_match_pop_budget():
    """Demoted contents come back byte-identical (int8 scales too),
    token verification degrades a digest collision to a miss, the
    byte budget LRU-evicts, and Watcher accounting returns to zero
    on clear()."""
    from veles_tpu.serving.kv_host import HostKVTier, WATCH_KEY
    base = Watcher.used.get(WATCH_KEY, 0)
    rng = numpy.random.default_rng(3)

    def one_block(seed):
        r = numpy.random.default_rng(seed)
        return {0: {"k": r.integers(-127, 128, (1, 4, 8))
                    .astype(numpy.int8),
                    "k_scale": r.random((1, 4))
                    .astype(numpy.float32)}}

    tier = HostKVTier(10 << 20, 4)
    path = tuple(rng.integers(0, 11, (8,)).tolist())
    layers = one_block(1)
    assert tier.put(path, layers)
    assert not tier.put(path[:3], layers)   # unaligned
    assert Watcher.used.get(WATCH_KEY, 0) > base

    got = tier.match(list(path) + [9, 9], 1)  # depth-1 extension
    assert len(got) == 1
    e = got[0]
    assert e.layers[0]["k"].mem.tobytes() \
        == layers[0]["k"].tobytes()
    assert e.layers[0]["k_scale"].mem.tobytes() \
        == layers[0]["k_scale"].tobytes()
    # same depth, different tokens: the digest key cannot lie
    wrong = list(path[:4]) + [(t + 1) % 11 for t in path[4:]]
    assert tier.match(wrong, 1) == []
    tier.pop(got)
    assert tier.blocks == 0 and tier.promotions == 1
    assert Watcher.used.get(WATCH_KEY, 0) == base

    # byte budget: a third block LRU-evicts the coldest
    nbytes = sum(a.nbytes for a in layers[0].values())
    tier = HostKVTier(2 * nbytes, 4)
    paths = [tuple(rng.integers(0, 11, (4,)).tolist())
             for _ in range(3)]
    for i, p in enumerate(paths):
        assert tier.put(p, one_block(10 + i))
        tier.match(list(p), 0)  # touch: oldest insert stays coldest
    assert tier.blocks == 2 and tier.evictions == 1
    assert tier.match(list(paths[0]), 0) == []  # the evictee
    tier.clear()
    assert Watcher.used.get(WATCH_KEY, 0) == base


# -- export byte cap ----------------------------------------------------------

def test_export_byte_cap_counts_expiries(f32, spec_trained_chain):
    """With the export byte budget below two records, parking the
    second evicts the first (oldest pays) and counts it on the
    expiry series; the survivor stays fetchable."""
    from veles_tpu.serving import InferenceScheduler
    fw, _ = spec_trained_chain
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=8,
                             prefix_cache=False, spec=False,
                             warm_buckets=False,
                             kv_export_bytes=1).start()
    try:
        h1 = sch.submit_prefill([1, 2, 3, 4, 5]).result(240)["handle"]
        assert sch.kv_export_status(h1) == "pending"
        h2 = sch.submit_prefill([5, 4, 3, 2, 1]).result(240)["handle"]
        assert sch.kv_export_status(h1) == "unknown"  # capped out
        assert sch.kv_export_status(h2) == "pending"
        snap = sch.metrics()
        assert snap["kv_exports_expired"] >= 1
        assert sch.kv_export(h2) is not None
        sch.check_kv()
    finally:
        sch.close()


# -- host-promoted parity -----------------------------------------------------

def _churn_to_host(sch, rng, rounds=6, min_blocks=6):
    """Push distinct long prompts through until trie eviction has
    demoted at least ``min_blocks`` into the host tier — deep enough
    that the cold chains' SHALLOW blocks (the promotable ones: a
    resubmit can only share up to its last prompt token) are among
    the evictees, not just their leaves."""
    for i in range(rounds):
        p = rng.integers(0, 12, (44,)).tolist()
        sch.submit(p, 4, seed=100 + i).result(240)
        if sch.metrics().get("kv_host_blocks", 0) >= min_blocks:
            return
    raise AssertionError("churn never demoted %d blocks: %s"
                         % (min_blocks,
                            sch.metrics().get("kv_host_blocks")))


@pytest.mark.parametrize("spec", [False, True])
def test_host_promoted_parity(f32, spec_trained_chain, spec):
    """A prompt whose prefix was evicted to the HOST tier replays
    bit-identically to its cold run once promoted back — greedy and
    seed-pinned, spec on and off — and the promotion shows on the
    counters."""
    from veles_tpu.serving import InferenceScheduler
    fw, _ = spec_trained_chain
    rng = numpy.random.default_rng(19)
    pa = rng.integers(0, 12, (16,)).tolist()
    pb = rng.integers(0, 12, (16,)).tolist()
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, kv_blocks=28,
                             prefill_chunk=8, prefix_cache=True,
                             spec=spec, spec_k=2, warm_buckets=False,
                             kv_host_bytes=32 << 20).start()
    try:
        cold_a = sch.submit(pa, 10).result(240)              # greedy
        cold_b = sch.submit(pb, 10, temperature=0.8, top_k=4,
                            seed=11).result(240)             # seeded
        _churn_to_host(sch, rng)
        demoted = sch.metrics()["kv_host_demotions"]
        assert demoted > 0
        warm_a = sch.submit(pa, 10).result(240)
        warm_b = sch.submit(pb, 10, temperature=0.8, top_k=4,
                            seed=11).result(240)
        assert warm_a == cold_a
        assert warm_b == cold_b
        assert sch.metrics()["kv_host_promotions"] >= 1, \
            "warm resubmit never promoted from the host tier"
        sch.check_kv()
    finally:
        sch.close()
    assert Watcher.used.get("host:kv-tier", 0) == 0


def test_host_promoted_parity_int8(f32, spec_trained_chain):
    """int8 pools demote and promote their quantized rows + scales
    byte-for-byte, so the warm stream is token-identical to cold."""
    from veles_tpu.serving import InferenceScheduler
    fw, _ = spec_trained_chain
    rng = numpy.random.default_rng(23)
    pa = rng.integers(0, 12, (16,)).tolist()
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, kv_blocks=28,
                             kv_dtype="int8", prefill_chunk=8,
                             prefix_cache=True, spec=False,
                             warm_buckets=False,
                             kv_host_bytes=32 << 20).start()
    try:
        cold = sch.submit(pa, 10, seed=7).result(240)
        _churn_to_host(sch, rng)
        warm = sch.submit(pa, 10, seed=7).result(240)
        assert warm == cold
        assert sch.metrics()["kv_host_promotions"] >= 1
        sch.check_kv()
    finally:
        sch.close()


def test_check_kv_clean_under_churn_with_promote_faults(
        f32, spec_trained_chain):
    """Mixed traffic over the host tier with the promote fault point
    raising and step delays armed: every request retires or fails
    without leaking a block, a host entry or a refcount."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    fw, _ = spec_trained_chain
    rng = numpy.random.default_rng(29)
    warm_p = rng.integers(0, 12, (16,)).tolist()
    sch = InferenceScheduler(fw, max_slots=3, window=64,
                             block_size=4, kv_blocks=28,
                             prefill_chunk=8, prefix_cache=True,
                             spec=True, spec_k=2, warm_buckets=False,
                             kv_host_bytes=32 << 20,
                             request_timeout=60.0).start()
    try:
        sch.submit(warm_p, 6, seed=0).result(240)
        _churn_to_host(sch, rng)
        # every other promotion attempt dies mid-flight; the
        # admission must degrade to cold, never leak
        faults.inject("scheduler.kv.promote", "exception", times=8)
        faults.load("serving.scheduler.step=delay:0.002x20")
        futs = []
        for i in range(10):
            p = warm_p if i % 2 else \
                rng.integers(0, 12, (rng.integers(4, 20),)).tolist()
            futs.append(sch.submit(p, 6, seed=i))
            if i == 5:
                sch.request_preempt()
            if i == 7:
                sch.cancel(futs[3])
        done = failed = 0
        for f in futs:
            try:
                f.result(240)
                done += 1
            except SchedulerError:
                failed += 1
        assert done + failed == 10
        assert done >= 6
        faults.clear()
        sch.check_kv()
        assert sch.metrics()["active_slots"] == 0
    finally:
        sch.close()
    sch.check_kv()


# -- cross-replica prefix shipping --------------------------------------------

def _make_replica(name, seed=1234, **api_kwargs):
    """One in-process engine replica (the test_router pattern —
    identical weights per seed, so greedy output is replica-
    independent), with the prefix cache at block_size=4 so short
    prompts are routable warmth."""
    from veles_tpu import prng
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    from veles_tpu.serving.fleet import LocalReplica
    from veles_tpu.backends import Device
    prng.get("default").seed(seed)
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
                     serving_block_size=4, serving_prefill_chunk=4,
                     serving_prefix_cache=True, serving_spec=False,
                     serving_warm_buckets=False, **api_kwargs)
    api.output = fw[-1].output
    api.initialize()
    return LocalReplica(api, loader)


def _post(url, payload, timeout=120, headers=None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers=hdrs)
    resp = urllib.request.urlopen(req, timeout=timeout)
    return dict(resp.headers), json.load(resp)


def test_peer_prefix_fetch_parity_and_fault_fallback(f32):
    """The fleet acceptance: prompts served warm on replica tk0 are
    re-served after tk0 drains — the router ships tk0's resident
    prefix to tk1 over the binary wire (peer-fetch counter moves,
    tk1's radix cache hits) and tk1's greedy reply is identical to
    the original.  With ``router.prefix.fetch`` armed the ship is
    dropped, the fail counter moves, and the request still answers
    200 with the same tokens (cold admission on tk1)."""
    from veles_tpu.serving import Router
    reps = [_make_replica("tier-r%d" % i, replica_id="tk%d" % i)
            for i in range(2)]
    router = Router(health_interval=0.1, request_timeout=60.0,
                    prefix_fetch_min=2).start()
    try:
        ids = ["tk0", "tk1"]
        for i, rep in enumerate(reps):
            router.add_replica(rep.host, rep.port,
                               replica_id=ids[i])
        # aim BOTH warmup prompts at tk0 through the public session
        # contract (caches are cold, so affinity decides the pick)
        aim = {"X-Veles-Session": _session_for(ids, "tk0")}
        p1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7]
        p2 = [7, 7, 2, 9, 1, 3, 3, 5, 6, 2, 8, 4]
        h1, ref1 = _post(router.url, {"prompt": p1, "steps": 6},
                         headers=aim)
        h2, ref2 = _post(router.url, {"prompt": p2, "steps": 6},
                         headers=aim)
        assert h1["X-Veles-Replica"] == "tk0" \
            and h2["X-Veles-Replica"] == "tk0"
        # wait for tk0's digest advertisement (both paths: 5 + 4
        # full blocks at block_size=4) to reach the router's view
        deadline = time.monotonic() + 10
        while True:
            state = {r["id"]: r for r in
                     router.replica_state()["replicas"]}
            if state["tk0"]["prefix_digests"] >= 8:
                break
            assert time.monotonic() < deadline, \
                "digests never advertised: %s" % state["tk0"]
            time.sleep(0.05)
        router.drain_replica("tk0")

        # fault leg first (tk1 still cold for p2): the one holder's
        # fetch is dropped, the request proceeds cold on tk1 and the
        # greedy reply still matches (identical weights fleet-wide)
        faults.inject("router.prefix.fetch", "drop", times=1)
        hf, out2 = _post(router.url, {"prompt": p2, "steps": 6})
        assert hf["X-Veles-Replica"] == "tk1"
        assert out2 == ref2
        rstate = router.replica_state()["router"]
        assert rstate["prefix_peer_fetch_fails"] >= 1, rstate
        fetches_before = rstate["prefix_peer_fetches"]
        faults.clear()

        # success leg: p1 is warm only on DRAINED tk0 — the router
        # rescues its prefix onto tk1 before forwarding
        hw, warm1 = _post(router.url, {"prompt": p1, "steps": 6})
        assert hw["X-Veles-Replica"] == "tk1"
        assert warm1 == ref1
        rstate = router.replica_state()["router"]
        assert rstate["prefix_peer_fetches"] >= fetches_before + 1, \
            rstate
        sch = reps[1].api.scheduler_
        assert sch.metrics()["prefix_cache_hits"] >= 1, \
            "the shipped prefix never hit on tk1"
        sch.check_kv()
    finally:
        router.stop()
        for rep in reps:
            rep.stop()


def _session_for(replica_ids, target_id):
    """A session key whose rendezvous hash (the router's affinity
    formula) lands on ``target_id``."""
    import zlib
    for i in range(10000):
        s = "sess%d" % i
        owner = max(replica_ids,
                    key=lambda rid: zlib.crc32(
                        ("%s|%s" % (s, rid)).encode()))
        if owner == target_id:
            return s
    raise AssertionError("no session hashed to %s" % target_id)
