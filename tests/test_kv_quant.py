"""Quantized KV cache (int8 block pools + per-row scales beside the
block tables), the dequant-fused paged-attention paths (jnp + pallas
interpret), the fused speculative-verify step, and the int8
weight-only gemm epilogue: quant/dequant round-trip bounds,
int8-vs-fp32 token agreement through chunked prefill + spec +
preempt→resume + warm radix resubmit, scales-follow-blocks
invariants on donate/gather/reclaim, fused-verify bit-parity vs the
PR 9 two-pass path, the CE quality gate, and ``check_kv()`` clean
under churn."""

import time

import numpy
import pytest

import jax.numpy as jnp

from veles_tpu import faults
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.kv_quant


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


@pytest.fixture
def fused_verify():
    saved = root.common.serving.get("fused_verify", False)
    root.common.serving.fused_verify = True
    yield
    root.common.serving.fused_verify = saved


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2, blocks=2,
             **block_kw):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name=name)
    spec = [{"type": "embedding", "vocab": vocab, "dim": dim}]
    spec += [dict({"type": "transformer_block", "heads": heads,
                   "causal": True}, **block_kw)
             for _ in range(blocks)]
    spec += [{"type": "token_logits", "vocab": vocab}]
    fw = make_forwards(
        wf, Array(numpy.zeros((2, window), numpy.int32)), spec)
    dev = Device(backend="numpy")
    for u in fw:
        u.initialize(device=dev)
    return fw


# -- ops: quantization + attention parity -------------------------------------

def test_quant_roundtrip_tolerance():
    """Per-row absmax int8 keeps every element within amax/254 of
    the original (half a quantization step), and all-zero rows
    round-trip EXACTLY (scale 0 — the trash-block invariant)."""
    from veles_tpu.ops.paged_attention import (
        dequantize_kv, quantize_kv_rows)
    rng = numpy.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 7, 16)) * 3.0, jnp.float32)
    x = x.at[2, 3].set(0.0)                       # a zero row
    q, scale = quantize_kv_rows(x)
    assert q.dtype == jnp.int8
    back = numpy.asarray(dequantize_kv(q, scale))
    amax = numpy.abs(numpy.asarray(x)).max(axis=-1)
    bound = amax / 254.0 + 1e-7
    assert (numpy.abs(back - numpy.asarray(x))
            <= bound[..., None]).all()
    assert float(scale[2, 3]) == 0.0
    assert (back[2, 3] == 0.0).all()


def _rig(rng, b=3, k1=4, d=16, h=2, bs=8, t=4):
    num = 1 + b * t
    q = jnp.asarray(rng.normal(size=(b, k1, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, k1, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, k1, d)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(num, bs, d)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(num, bs, d)), jnp.float32)
    pk = pk.at[0].set(0.0)
    pv = pv.at[0].set(0.0)
    tables = jnp.asarray(
        rng.permutation(numpy.arange(1, num))[:b * t].reshape(b, t),
        jnp.int32)
    pos = jnp.asarray(rng.integers(k1, t * bs - k1, (b,)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, k1 + 1, (b,)), jnp.int32)
    return q, kn, vn, pk, pv, tables, pos, lens


def test_fused_verify_bit_parity_vs_two_pass(f32):
    """The fused single-pass verify produces the SAME pools and
    BIT-IDENTICAL context rows (for real positions) as the PR 9
    scatter-then-gather two-pass path — the in-buffer scatter holds
    exactly the values the two-pass gather reads back."""
    from veles_tpu.ops import paged_attention as pa
    rng = numpy.random.default_rng(1)
    q, kn, vn, pk, pv, tables, pos, lens = _rig(rng)
    h = 2
    p2k, p2v, c2 = pa.paged_verify_attention(
        q, kn, vn, pk, pv, tables, pos, lens, h)
    pfk, pfv, cf = pa.paged_verify_attention_fused(
        q, kn, vn, pk, pv, tables, pos, lens, h)
    assert jnp.array_equal(p2k, pfk) and jnp.array_equal(p2v, pfv)
    valid = numpy.arange(q.shape[1])[None, :] \
        < numpy.asarray(lens)[:, None]
    assert (numpy.asarray(c2)[valid]
            == numpy.asarray(cf)[valid]).all()


def test_q8_paths_track_fp32(f32):
    """int8 decode/verify contexts stay within quantization noise of
    the fp32 paths on the same inputs (the op-level face of the CE
    quality gate)."""
    from veles_tpu.ops import paged_attention as pa
    rng = numpy.random.default_rng(2)
    q, kn, vn, pk, pv, tables, pos, lens = _rig(rng)
    h = 2
    qpk, sck = pa.quantize_kv_rows(pk)
    qpv, scv = pa.quantize_kv_rows(pv)
    _, _, ref = pa.paged_verify_attention(
        q, kn, vn, pk, pv, tables, pos, lens, h)
    _, _, _, _, ctx = pa.paged_verify_attention_q8(
        q, kn, vn, qpk, qpv, sck, scv, tables, pos, lens, h)
    valid = numpy.arange(q.shape[1])[None, :] \
        < numpy.asarray(lens)[:, None]
    err = numpy.abs(numpy.asarray(ctx) - numpy.asarray(ref))[valid]
    assert err.max() < 0.05
    q1, kn1, vn1 = q[:, :1], kn[:, :1], vn[:, :1]
    _, _, dref = pa.paged_decode_attention(
        q1, kn1, vn1, pk, pv, tables, pos, h)
    _, _, _, _, dctx = pa.paged_decode_attention_q8(
        q1, kn1, vn1, qpk, qpv, sck, scv, tables, pos, h)
    assert numpy.abs(numpy.asarray(dctx)
                     - numpy.asarray(dref)).max() < 0.05


def test_pallas_paged_attend_parity(f32):
    """The dequant-fused pallas kernel (interpret mode on CPU)
    matches the jnp gather→dequant→attend references — fp32 AND int8
    pools, decode (K1=1) and verify widths."""
    from veles_tpu.ops import paged_attention as pa
    from veles_tpu.ops.pallas_paged import pallas_paged_attend
    rng = numpy.random.default_rng(3)
    q, kn, vn, pk, pv, tables, pos, lens = _rig(rng)
    h, k1 = 2, q.shape[1]
    qpos = numpy.asarray(pos)[:, None] + numpy.arange(k1)[None, :]
    # fp32: post-scatter pools, same mask as the two-pass reference
    p2k, p2v, ref = pa.paged_verify_attention(
        q, kn, vn, pk, pv, tables, pos, lens, h)
    out = pallas_paged_attend(q, p2k, p2v, tables, qpos, h,
                              interpret=True)
    assert numpy.abs(numpy.asarray(out)
                     - numpy.asarray(ref)).max() < 1e-5
    # int8: the q8 jnp path vs the kernel on its scattered pools
    qpk, sck = pa.quantize_kv_rows(pk)
    qpv, scv = pa.quantize_kv_rows(pv)
    k8, v8, s8k, s8v, ref8 = pa.paged_verify_attention_q8(
        q, kn, vn, qpk, qpv, sck, scv, tables, pos, lens, h)
    out8 = pallas_paged_attend(q, k8, v8, tables, qpos, h,
                               scale_k=s8k, scale_v=s8v,
                               interpret=True)
    assert numpy.abs(numpy.asarray(out8)
                     - numpy.asarray(ref8)).max() < 1e-5
    # decode width
    dk, dv, s1k, s1v, dref = pa.paged_decode_attention_q8(
        q[:, :1], kn[:, :1], vn[:, :1], qpk, qpv, sck, scv, tables,
        pos, h)
    dout = pallas_paged_attend(q[:, :1], dk, dv, tables,
                               numpy.asarray(pos)[:, None], h,
                               scale_k=s1k, scale_v=s1v,
                               interpret=True)
    assert numpy.abs(numpy.asarray(dout)
                     - numpy.asarray(dref)).max() < 1e-5


# -- ops: int8 weight-only gemm -----------------------------------------------

def test_int8_weight_matmul_epilogue(f32):
    """Per-column int8 weight quantization + the fused dequant
    epilogue match the deferred-dequant math; pallas_matmul routes
    interpret through ops.common.use_interpret so the kernel runs on
    CPU WITHOUT an explicit interpret=True (the silently-untested
    hole this PR closes)."""
    from veles_tpu.ops.gemm import (int8_matmul, int8_weight_quantize,
                                    pallas_matmul)
    rng = numpy.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    # interpret auto-resolution: NO interpret kwarg on a CPU target
    out = numpy.asarray(pallas_matmul(a, w))
    assert numpy.abs(out - numpy.asarray(a) @ numpy.asarray(w)).max() \
        < 1e-4
    wq, scale = int8_weight_quantize(w)
    assert wq.dtype == jnp.int8
    deq = numpy.asarray(wq, numpy.float32) \
        * numpy.asarray(scale)[None, :]
    got = numpy.asarray(int8_matmul(a, wq, scale))
    want = numpy.asarray(a) @ deq
    assert numpy.abs(got - want).max() < 1e-4
    # non-tiling shapes take the XLA fallback with the same math
    a2 = jnp.asarray(rng.normal(size=(3, 50)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(50, 300)), jnp.float32)
    wq2, s2 = int8_weight_quantize(w2)
    got2 = numpy.asarray(int8_matmul(a2, wq2, s2))
    want2 = numpy.asarray(a2) @ (
        numpy.asarray(wq2, numpy.float32)
        * numpy.asarray(s2)[None, :])
    assert numpy.abs(got2 - want2).max() < 1e-4


# -- kv_slots: scales follow blocks -------------------------------------------

def test_scales_follow_blocks_donate_gather_reclaim(f32):
    """Insert known K/V through the quantizing block scatter, donate
    the blocks out of the slot, gather them back through the
    dequantizing staging path: the round trip stays within the
    per-row quantization bound — the scales travelled with the
    blocks through release → load_staging.  reclaim() then returns
    them to the free list with a clean sweep."""
    from veles_tpu import dtypes
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw = _tiny_fw("kvq-scales")
    cache = PagedKVCache(fw, max_slots=2, window=32, block_size=4,
                         kv_dtype="int8")
    assert cache.bytes_per_token() < PagedKVCache(
        fw, max_slots=2, window=32, block_size=4).bytes_per_token()
    rng = numpy.random.default_rng(5)
    cacheable = [i for i, u in enumerate(fw)
                 if hasattr(u, "init_cache")]
    staging = {i: {"k": jnp.asarray(
                       rng.normal(size=(1, 16, 16)), jnp.float32),
                   "v": jnp.asarray(
                       rng.normal(size=(1, 16, 16)), jnp.float32)}
               for i in cacheable}
    slot = cache.alloc(16)
    cache.insert(slot, staging, 16)
    _, donated = cache.release(slot, donate=4)
    assert len(donated) == 4
    zero = {i: {n: jnp.zeros((1, 16, 16), dtypes.compute_dtype())
                for n in ("k", "v")} for i in cacheable}
    back = cache.load_staging(zero, donated)
    for i in cacheable:
        for n in ("k", "v"):
            x = numpy.asarray(staging[i][n])
            amax = numpy.abs(x).max(axis=-1)
            bound = amax / 254.0 + 1e-6
            got = numpy.asarray(back[i][n])
            assert (numpy.abs(got - x) <= bound[..., None]).all(), \
                "layer %d %s lost its scales in the round trip" \
                % (i, n)
    cache.reclaim(donated)
    cache.check()


# -- scheduler: int8 end to end -----------------------------------------------

def test_int8_stream_agreement_and_determinism(f32):
    """int8 and fp32 schedulers decode the same greedy + seeded
    traffic through chunked prefill + spec with HIGH token agreement
    (quant noise may legitimately flip a near-tie, so this is a rate,
    not equality), and the int8 stream itself is deterministic
    (resubmitting reproduces it exactly)."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("kvq-agree")
    jobs = [([3, 1, 4, 3, 1, 4, 3, 1], dict(seed=0)),
            ([7, 2, 7, 2, 7, 2], dict(temperature=0.9, top_k=5,
                                      seed=42))]

    def run(kv_dtype):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 kv_dtype=kv_dtype, prefill_chunk=4,
                                 spec=True, spec_k=2,
                                 warm_buckets=False).start()
        try:
            futs = [sch.submit(p, 20, **kw) for p, kw in jobs]
            outs = [f.result(240) for f in futs]
            sch.check_kv()
            snap = sch.metrics()
            return outs, snap
        finally:
            sch.close()

    fp, _ = run("fp32")
    q8a, snap = run("int8")
    q8b, _ = run("int8")
    assert snap["kv_dtype"] == "int8"
    assert q8a == q8b, "int8 decode is not deterministic"
    matched = total = 0
    for a, b in zip(fp, q8a):
        matched += sum(x == y for x, y in zip(a, b))
        total += len(a)
    assert matched / total >= 0.8, \
        "int8 streams diverged far beyond quantization noise " \
        "(%d/%d)" % (matched, total)


def test_int8_preempt_resume_agreement(f32):
    """Preempt → resume under int8 continues within quantization
    noise of the uninterrupted int8 run — NOT bit-identical, by
    design: the re-prefill computes deeper layers' K/V from f32
    staging attention while the original decode read dequantized
    keys, so re-quantized rows can differ in the last bit (the
    bit-exact resume contract remains an fp32 guarantee; the
    scheduler docstring says so).  The resumed request must still
    finish, agree closely, and leak nothing."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("kvq-preempt")
    jobs = [([3, 1, 4, 3, 1, 4, 3], dict(seed=0)),
            ([7, 2] * 4, dict(temperature=0.9, top_k=5, seed=123))]

    def run(preempt):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 kv_dtype="int8", prefill_chunk=4,
                                 spec=True, spec_k=4,
                                 warm_buckets=False).start()
        try:
            futs = [sch.submit(p, 24, **kw) for p, kw in jobs]
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
                time.sleep(0.05)
                sch.request_preempt()
            outs = [f.result(240) for f in futs]
            snap = sch.metrics()
            sch.check_kv()
            return outs, snap
        finally:
            sch.close()

    base, _ = run(preempt=False)
    preempted, snap = run(preempt=True)
    assert snap["preempts"] >= 1, "no preemption actually happened"
    assert [len(s) for s in preempted] == [len(s) for s in base]
    matched = total = 0
    for a, b in zip(base, preempted):
        matched += sum(x == y for x, y in zip(a, b))
        total += len(a)
    assert matched / total >= 0.75, \
        "resumed int8 stream diverged far beyond quantization " \
        "noise (%d/%d)" % (matched, total)


def test_int8_warm_radix_resubmit_parity(f32):
    """A warm radix resubmit under int8 reproduces the cold stream
    exactly: the matched blocks hold the SAME quantized rows the
    cold run wrote, and the cold tail attends over their dequantized
    staging — the values every decode step reads through the
    dequant-fused gather."""
    from veles_tpu import prng
    from veles_tpu.serving import InferenceScheduler
    # the weights from a seed of the test's own: the matched blocks
    # are REUSED and so exact, but the cold tail's rows are recomputed
    # over dequantized keys where the cold run's prefill read float32
    # staging, so a deeper layer's tail rows agree within quantization
    # noise only, and whether a near-tie flips a token depends on the
    # weights: drawn from the process's stream they depended on which
    # tests the worker had run before (a failure after
    # test_prng_ops.py, on the parent's tree too)
    prng.get().seed(6)
    fw = _tiny_fw("kvq-warm")
    rng = numpy.random.default_rng(6)
    prompt = rng.integers(0, 12, (24,)).tolist()
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, kv_dtype="int8",
                             prefill_chunk=4, prefix_cache=True,
                             spec=True, spec_k=2,
                             warm_buckets=False).start()
    try:
        cold = sch.submit(prompt, 12, seed=7).result(240)
        warm = sch.submit(prompt, 12, seed=7).result(240)
        snap = sch.metrics()
        assert snap["prefix_cache_hits"] >= 1, "resubmit never hit"
        assert warm == cold
        sch.check_kv()
    finally:
        sch.close()


def test_int8_check_kv_clean_under_churn(f32):
    """Mixed int8 traffic with cancels, preempts and injected step
    delays retires or fails every request without leaking a block, a
    scale row or a refcount — the invariant sweep stays clean with
    the prefix cache live."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    fw = _tiny_fw("kvq-churn")
    rng = numpy.random.default_rng(7)
    warm_p = rng.integers(0, 12, (16,)).tolist()
    sch = InferenceScheduler(fw, max_slots=3, window=48,
                             block_size=4, kv_blocks=24,
                             kv_dtype="int8", prefill_chunk=8,
                             prefix_cache=True, spec=True, spec_k=2,
                             warm_buckets=False,
                             request_timeout=60.0).start()
    try:
        sch.submit(warm_p, 6, seed=0).result(240)   # seed the trie
        faults.load("serving.scheduler.step=delay:0.002x20")
        futs = []
        for i in range(12):
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
        assert done + failed == 12
        assert done >= 8
        faults.clear()
        sch.check_kv()
        assert sch.metrics()["active_slots"] == 0
    finally:
        sch.close()
    sch.check_kv()


def test_fused_verify_scheduler_stream_parity(f32, fused_verify):
    """With the fused verify enabled, spec-on decoding still equals
    spec-off decoding bit-for-bit (greedy AND seeded) — the fused
    kernel keeps the PR 9 parity contract while skipping the
    in-step pool round-trip."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("kvq-fused")
    jobs = [([3, 1, 4, 3, 1, 4, 3, 1], dict(seed=0)),
            ([7, 2, 7, 2, 7, 2], dict(temperature=0.9, top_k=5,
                                      seed=11))]

    def run(spec):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 prefill_chunk=4, spec=spec,
                                 spec_k=3,
                                 warm_buckets=False).start()
        try:
            outs = [sch.submit(p, 20, **kw).result(240)
                    for p, kw in jobs]
            snap = sch.metrics()
            sch.check_kv()
            return outs, snap
        finally:
            sch.close()

    off, _ = run(False)
    on, snap = run(True)
    assert snap["spec_drafted_tokens"] > 0, "verify never ran"
    assert on == off


# -- quality gate --------------------------------------------------------------

def test_kv_quant_ce_bound_on_trained_chain(f32, spec_trained_chain):
    """The declared int8-KV quality bound HOLDS, measured (not
    logged) on a briefly-trained tiny chain (the session-scoped
    conftest fixture — trained ONCE for test_spec/test_kv_quant/
    test_tp) through the real verify path: CE delta within
    KV_QUANT_CE_TOLERANCE and near-total greedy top-1 agreement.
    quality.py records the same numbers at bench scale."""
    from veles_tpu.serving.kv_quality import (
        KV_QUANT_CE_TOLERANCE, kv_quant_quality)
    fw, pattern = spec_trained_chain
    rng = numpy.random.default_rng(8)
    seqs = [([p % 12 for p in pattern] * 8)[:48],
            rng.integers(0, 12, (48,)).tolist()]
    rec = kv_quant_quality(fw, seqs, block_size=8)
    assert rec["kv_quant_within_tolerance"], rec
    assert rec["kv_quant_ce_delta"] <= KV_QUANT_CE_TOLERANCE
    assert rec["kv_quant_top1_agreement"] >= 0.9, rec


# -- config / plumbing ---------------------------------------------------------

def test_kv_dtype_validation_and_metrics(f32):
    """Junk kv_dtype is a loud client error; the metrics
    snapshot advertises kv_dtype and the measured bytes-per-token
    (int8 strictly under fp32); the config key is declared."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("kvq-plumb")
    with pytest.raises(ValueError):
        InferenceScheduler(fw, max_slots=2, window=64,
                           kv_dtype="int4")
    assert root.common.serving.kv_dtype == "fp32"
    assert root.common.serving.fused_verify is False
    bpt = {}
    for dt in ("fp32", "int8"):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 kv_dtype=dt, spec=False,
                                 warm_buckets=False).start()
        try:
            snap = sch.metrics()
            assert snap["kv_dtype"] == dt
            bpt[dt] = snap["kv_bytes_per_token"]
        finally:
            sch.close()
    assert bpt["int8"] < bpt["fp32"]
    # REST plumbing: the kwarg exists and lands on the scheduler knob
    import inspect
    from veles_tpu.restful_api import RESTfulAPI
    assert "serving_kv_dtype" in inspect.signature(
        RESTfulAPI.__init__).parameters


def test_int8_decode_weights_complete(f32):
    """A chain built with int8_decode=True serves through the int8
    weight-only decode MLP/proj (ops/gemm.int8_matmul — per-column
    scales fused in the epilogue) and decodes deterministically."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("kvq-w8", blocks=1, int8_decode=True)
    assert fw[1].export_config().get("int8_decode") is True

    def run():
        sch = InferenceScheduler(fw, max_slots=1, window=64,
                                 block_size=4,
                                 kv_dtype="int8", prefill_chunk=0,
                                 spec=False, prefix_cache=False,
                                 warm_buckets=False).start()
        try:
            return sch.submit([3, 1, 4, 1], 5, seed=0).result(240)
        finally:
            sch.close()

    a = run()
    b = run()
    assert a == b and len(a) == 9
