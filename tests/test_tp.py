"""Tensor-parallel serving + disaggregated prefill/decode
(``serving/tp.py``, ``serving/disagg.py``, the role-aware scheduler
and router): TP=2 greedy/seeded streams bit-identical to TP=1 on
identical weights — through chunked prefill, spec verify, int8 pools
and preempt→resume — per-chip pool bytes dropping by the mesh
factor, a model too wide for a one-chip budget serving at tp=2 with
the per-chip budget held fixed, and the prefill→decode KV handoff
producing streams identical to the colocated path (fp32 bit-exact;
int8 blocks import unrequantized) with a clean ``check_kv()`` on
both roles.  Runs on the 8-virtual-CPU-device mesh every tier-1
test already gets (conftest XLA_FLAGS)."""

import json
import time

import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu import faults
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.tp


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2, blocks=2,
             seed=None):
    from veles_tpu import prng
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    if seed is not None:
        prng.get("default").seed(seed)
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


def _run(fw, submits, check=False, **kw):
    from veles_tpu.serving import InferenceScheduler
    kw.setdefault("max_slots", 3)
    kw.setdefault("window", 64)
    sch = InferenceScheduler(fw, warm_buckets=False, **kw).start()
    try:
        futs = [sch.submit(p, steps, **skw)
                for p, steps, skw in submits]
        outs = [f.result(240) for f in futs]
        if check:
            sch.check_kv()
        return outs, sch.metrics()
    finally:
        sch.close()


# -- layout declarations + the support gate -----------------------------------

def test_tp_specs_and_gate(f32, spec_trained_chain):
    """Units declare their own Megatron layout: wq/wk/wv and the FFN
    up-projection column-parallel, wo and the down-projection
    row-parallel, LN/bias replicated; divisibility gates the whole
    chain, and an unshardable tp silently falls back to unsharded
    serving (the documented degrade)."""
    from jax.sharding import PartitionSpec as P
    from veles_tpu.serving import InferenceScheduler, tp_supported
    fw, _ = spec_trained_chain
    block = fw[1]
    assert block.tp_shardable(2)
    assert not block.tp_shardable(3)     # d=16, heads=2 don't divide
    assert block.tp_param_spec("wq", 2) == P(None, "tp")
    assert block.tp_param_spec("ffn_w1", 2) == P(None, "tp")
    assert block.tp_param_spec("wo", 2) == P("tp", None)
    assert block.tp_param_spec("ffn_w2", 2) == P("tp", None)
    assert block.tp_param_spec("ffn_b1", 2) == P("tp")
    assert block.tp_param_spec("ln1_scale", 2) is None
    assert tp_supported(fw, 2) and not tp_supported(fw, 3)
    sch = InferenceScheduler(fw, max_slots=2, window=64, tp=3,
                             warm_buckets=False)
    assert sch.tp == 0 and sch.tp_ is None   # fallback, not a crash
    # more shards than devices is NOT a degrade: the caller sized the
    # model for 1/tp of it per chip
    with pytest.raises(ValueError, match="tp=16 needs 16 devices"):
        InferenceScheduler(fw, max_slots=2, window=64, tp=16,
                           warm_buckets=False)
    # config keys are declared with the documented defaults
    assert root.common.serving.tp == 0
    assert root.common.serving.role == "both"


def test_tp2_stream_parity(f32, spec_trained_chain):
    """Acceptance: tp=2 decode streams are BIT-IDENTICAL to tp=1 on
    the same weights — greedy and seeded sampling, through chunked
    prefill and the spec verify step — and the per-chip K/V bytes
    (and the kv_bytes_per_token gauge) drop by the mesh factor."""
    fw, pattern = spec_trained_chain
    prompts = [(pattern * 2)[:12], [5, 2] * 5, [7] * 5]
    submits = [(p, 10, dict(seed=0)) for p in prompts]
    submits += [(p, 8, dict(temperature=0.9, top_k=5, seed=41 + i))
                for i, p in enumerate(prompts)]
    kw = dict(block_size=4, prefill_chunk=4, spec=True,
              spec_k=3)
    base, snap1 = _run(fw, submits, check=True, tp=0, **kw)
    tp2, snap2 = _run(fw, submits, check=True, tp=2, **kw)
    assert tp2 == base
    assert snap2["tp"] == 2 and snap1["tp"] == 0
    # head-wise sharding halves what one chip pays per cached token
    assert snap2["kv_bytes_per_token"] \
        == snap1["kv_bytes_per_token"] // 2


def test_tp2_overlap_parity_with_model_drafter(f32,
                                               spec_trained_chain,
                                               spec_trained_head):
    """The PR 20 pair under one roof: tp=2 with the OVERLAP step
    (``serving.tp_overlap`` — the shard_map body whose row-parallel
    combines are expressed per shard as collective-permute + add)
    AND the model drafter stays bit-identical to the tp=1 spec-off
    baseline, greedy and seeded, through chunked prefill.  The
    2-operand f32 add of the tp=2 combine is the GSPMD psum's exact
    arithmetic, so overlap is purely a scheduling change."""
    from veles_tpu.config import root as cfg
    fw, pattern = spec_trained_chain
    head, _ = spec_trained_head
    prompts = [(pattern * 2)[:12], [5, 2] * 5]
    submits = [(p, 10, dict(seed=0)) for p in prompts]
    submits += [(p, 8, dict(temperature=0.9, top_k=5, seed=41 + i))
                for i, p in enumerate(prompts)]
    base, _ = _run(fw, submits, check=True, tp=0,
                   block_size=4, prefill_chunk=4, spec=False)
    cfg.common.serving.tp_overlap = True
    try:
        tp2, snap = _run(fw, submits, check=True, tp=2,
                         block_size=4, prefill_chunk=4, spec=True,
                         spec_k=4, drafter="model", draft_head=head)
    finally:
        cfg.common.serving.tp_overlap = False
    assert tp2 == base
    assert snap["tp"] == 2 and snap["drafter"] == "model"
    assert snap["spec_accept_rate_by_drafter"].get("model") \
        is not None


def test_tp2_int8_parity(f32, spec_trained_chain):
    """int8 pools under tp=2: the per-row amax reduces over the
    sharded feature axis exactly, so quantized pool bytes — and the
    emitted streams — match the unsharded int8 run bit-for-bit; the
    scale-invariant sweep stays clean."""
    fw, pattern = spec_trained_chain
    submits = [((pattern * 2)[:10], 10, dict(seed=0)),
               ([5, 2] * 4, 8, dict(temperature=0.8, top_k=4,
                                    seed=9))]
    kw = dict(block_size=4, prefill_chunk=4,
              kv_dtype="int8", spec=False, max_slots=2)
    base, snap1 = _run(fw, submits, check=True, tp=0, **kw)
    tp2, snap2 = _run(fw, submits, check=True, tp=2, **kw)
    assert tp2 == base
    assert snap2["kv_dtype"] == "int8"
    assert snap2["kv_bytes_per_token"] \
        < snap1["kv_bytes_per_token"]


def test_tp2_preempt_resume_parity(f32, spec_trained_chain):
    """Preempt → resume under tp=2 stays bit-identical to the
    uninterrupted tp=2 run (the PR 7 contract survives sharding: the
    draw counter and the re-prefilled K/V are mesh-invariant)."""
    from veles_tpu.serving import InferenceScheduler
    fw, pattern = spec_trained_chain
    jobs = [((pattern * 2)[:7], dict(seed=0)),
            ([7, 2] * 4, dict(temperature=0.9, top_k=5, seed=123))]

    def run(preempt):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 prefill_chunk=4, tp=2,
                                 warm_buckets=False).start()
        try:
            futs = [sch.submit(p, 16, **kw) for p, kw in jobs]
            if preempt:
                # the steps slowed, so that the preempt lands mid-stream
                # however few passes the prefill and the drafts leave
                faults.inject("serving.scheduler.step", "delay",
                              arg=0.02)
                deadline = time.monotonic() + 60
                while sch.metrics()["slot_busy_steps"] < 4:
                    assert time.monotonic() < deadline
                    time.sleep(0.01)
                sch.request_preempt()
            outs = [f.result(240) for f in futs]
            snap = sch.metrics()
            sch.check_kv()
            return outs, snap
        finally:
            faults.clear()
            sch.close()

    base, _ = run(preempt=False)
    preempted, snap = run(preempt=True)
    assert snap["preempts"] >= 1, "no preemption actually happened"
    assert preempted == base


def test_tp_serves_wider_model_at_fixed_chip_budget(f32):
    """Acceptance: a chain whose weights + full kv_blocks pool
    exceed a per-chip budget at tp=1 fits and SERVES at tp=2 with
    the SAME per-chip budget — the bigger-than-one-chip claim,
    measured on the real device arrays (sharded arrays count
    nbytes/tp per chip, replicated ones in full)."""
    from veles_tpu.serving import (InferenceScheduler, ServingTP,
                                   per_chip_bytes)
    fw = _tiny_fw("tp-wide", window=32, vocab=16, dim=64, heads=4,
                  blocks=2, seed=77)
    kw = dict(max_slots=2, window=32, block_size=8,
              kv_blocks=8, prefill_chunk=0, spec=False,
              prefix_cache=False)

    def chip_bytes(tp):
        sch = InferenceScheduler(fw, tp=tp, warm_buckets=False,
                                 **kw).start()
        try:
            assert sch.tp == tp
            total = per_chip_bytes({"params": sch.weights_.params,
                                    "pools": sch.cache_.pools})
            out = sch.submit([3, 1, 4, 1], 6, seed=0).result(240)
            sch.check_kv()
            return total, out
        finally:
            sch.close()

    one_chip, out1 = chip_bytes(0)
    two_chip, out2 = chip_bytes(2)
    assert out2 == out1                   # parity rides along
    # hold the per-chip budget fixed BETWEEN the two footprints: the
    # model does not fit one chip, yet serves on two
    budget = (one_chip + two_chip) // 2
    assert one_chip > budget, "model must overflow the 1-chip budget"
    assert two_chip <= budget, "tp=2 must fit the same budget"
    assert isinstance(ServingTP(2).mesh.shape["tp"], int)


# -- disaggregated prefill/decode ---------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_disagg_handoff_parity(f32, spec_trained_chain, kv_dtype):
    """Acceptance: the prefill→decode handoff (export → JSON wire →
    import) produces streams IDENTICAL to the colocated path — fp32
    bit-exact, int8 byte-identical resident blocks (raw import, no
    requant) — with check_kv() clean on BOTH roles afterward, role
    gating enforced, scales traveling with the exported blocks, and
    the export handle one-shot."""
    from veles_tpu.serving import (InferenceScheduler,
                                   RoleMismatchError, decode_export,
                                   encode_export)
    fw, pattern = spec_trained_chain
    kw = dict(max_slots=2, window=64, block_size=4,
              prefill_chunk=4, kv_dtype=kv_dtype,
              warm_buckets=False)
    colo = InferenceScheduler(fw, **kw).start()
    pre = InferenceScheduler(fw, role="prefill", **kw).start()
    dec = InferenceScheduler(fw, role="decode", **kw).start()
    try:
        prompt = (pattern * 2)[:10]
        want = colo.submit(prompt, 9, seed=0).result(240)
        want_s = colo.submit(prompt, 9, temperature=0.8, top_k=4,
                             seed=7).result(240)
        with pytest.raises(RoleMismatchError):
            pre.submit(prompt, 4)
        with pytest.raises(RoleMismatchError):
            dec.submit_prefill(prompt)
        h = pre.submit_prefill(prompt).result(240)
        assert h["blocks"] == -(-len(prompt) // 4)
        rec = pre.kv_export(h["handle"])
        assert rec is not None
        assert pre.kv_export(h["handle"]) is None   # one-shot
        if kv_dtype == "int8":
            # scales travel WITH the exported blocks
            layer = next(iter(rec["layers"].values()))
            assert {"k", "v", "k_scale", "v_scale"} <= set(layer)
            assert layer["k"].dtype == numpy.int8
        wire = decode_export(
            json.loads(json.dumps(encode_export(rec))))
        got = dec.submit_imported(wire, 9, seed=0).result(240)
        h2 = pre.submit_prefill(prompt).result(240)   # warm repeat
        rec2 = pre.kv_export(h2["handle"])
        got_s = dec.submit_imported(rec2, 9, temperature=0.8,
                                    top_k=4, seed=7).result(240)
        assert got == want and got_s == want_s
        # a mismatched pool layout is a loud client error
        bad = dict(rec2, kv_dtype="fp8")
        with pytest.raises(ValueError):
            dec.submit_imported(bad, 4)
        pre.check_kv()
        dec.check_kv()
        colo.check_kv()
        assert pre.metrics()["role"] == "prefill"
        assert dec.metrics()["role"] == "decode"
    finally:
        colo.close()
        pre.close()
        dec.close()


def test_disagg_router_dispatch(f32):
    """The full vertical: a role-aware router in front of a prefill
    specialist and a decode specialist serves POST /generate through
    the disaggregated handoff — the reply is identical to a
    colocated replica's, the handoff is attributed in the response
    headers and the router metric, and the prefill specialist
    refuses direct decode traffic with 409."""
    import urllib.error
    import urllib.request
    from veles_tpu.serving import Router
    from tests.test_router import _make_replica, _post

    colo = _make_replica("tp-colo", serving_warm_buckets=False,
                         serving_block_size=4,
                         serving_prefill_chunk=4)
    pre = _make_replica("tp-pre", serving_warm_buckets=False,
                        serving_block_size=4,
                        serving_prefill_chunk=4,
                        serving_role="prefill")
    dec = _make_replica("tp-dec", serving_warm_buckets=False,
                        serving_block_size=4,
                        serving_prefill_chunk=4,
                        serving_role="decode")
    router = Router(health_interval=0.1, health_timeout=5.0).start()
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        _, want = _post("http://127.0.0.1:%d" % colo.port,
                        {"prompt": prompt, "steps": 8, "seed": 0})
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post("http://127.0.0.1:%d" % pre.port,
                  {"prompt": prompt, "steps": 4})
        assert ei.value.code == 409
        router.add_replica("127.0.0.1", pre.port, replica_id="pre")
        router.add_replica("127.0.0.1", dec.port, replica_id="dec")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            state = {r["id"]: r
                     for r in router.replica_state()["replicas"]}
            if state.get("pre", {}).get("role") == "prefill" \
                    and state.get("dec", {}).get("healthy"):
                break
            time.sleep(0.05)
        hdrs, got = _post(router.url, {"prompt": prompt, "steps": 8,
                                       "seed": 0})
        assert got["tokens"] == want["tokens"]
        assert hdrs.get("X-Veles-Router-Disagg") == "pre>dec"
        assert router.stats.disagg_handoffs >= 1
        for handle in (pre, dec):
            handle.api.scheduler_.check_kv()
    finally:
        router.stop()
        for handle in (colo, pre, dec):
            handle.stop()
