"""Continuous-batching serving subsystem (``veles_tpu/serving/``):
batched/chunked prefill parity, slot-step shapes, the paged KV cache
(block churn, token parity with ``generate()``, memory-proportional
admission), scheduler semantics, admission control, and the REST
concurrency soak."""

import json
import random
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.serving


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _tiny_fw(name, window=16, vocab=12, dim=16, heads=2, blocks=1,
             **block_kwargs):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name=name)
    spec = [{"type": "embedding", "vocab": vocab, "dim": dim}]
    spec += [dict({"type": "transformer_block", "heads": heads,
                   "causal": True}, **block_kwargs)
             for _ in range(blocks)]
    spec += [{"type": "token_logits", "vocab": vocab}]
    fw = make_forwards(
        wf, Array(numpy.zeros((2, window), numpy.int32)), spec)
    dev = Device(backend="numpy")
    for u in fw:
        u.initialize(device=dev)
    return fw


# -- batched prefill ----------------------------------------------------------

def test_prefill_matches_sequential_scan(f32):
    """Batched prefill reproduces the per-token sequential scan's KV
    cache (f32 tolerance) for RAGGED prompt_lens, leaves rows past
    each length at the init_cache zeros, and returns the logits at
    each row's last prompt position."""
    from veles_tpu import dtypes
    from veles_tpu.models.generate import _chain_step
    from veles_tpu.serving import prefill, serving_supported
    fw = _tiny_fw("prefill", blocks=2)
    assert serving_supported(fw)
    window = 10
    padded = numpy.asarray([[3, 1, 4, 1], [5, 9, 0, 0]], numpy.int32)
    lens = [4, 2]
    caches, last = prefill(fw, padded, prompt_lens=lens,
                           window=window)
    params = {i: {n: jnp.asarray(a.map_read().mem)
                  for n, a in u.param_arrays().items()}
              for i, u in enumerate(fw)}
    for n, ln in enumerate(lens):
        ref = {i: u.init_cache(1, window, dtypes.compute_dtype())
               for i, u in enumerate(fw) if hasattr(u, "init_cache")}
        h = None
        for t in range(ln):
            tok = jnp.asarray(padded[n:n + 1, t:t + 1])
            h, ref = _chain_step(fw, params, tok, t, ref)
        for i in ref:
            for part in ("k", "v"):
                numpy.testing.assert_allclose(
                    numpy.asarray(caches[i][part])[n],
                    numpy.asarray(ref[i][part])[0], atol=1e-5,
                    err_msg="row %d layer %d %s" % (n, i, part))
                # rows at/past the length stay zero (a short row's
                # padding never pollutes the slot cache)
                assert not numpy.asarray(caches[i][part])[n, ln:] \
                    .any(), (n, i, part)
        numpy.testing.assert_allclose(
            numpy.asarray(last)[n], numpy.asarray(h)[0, 0],
            atol=1e-4, err_msg="row %d last logits" % n)


def test_prefill_validates(f32):
    from veles_tpu.serving import prefill
    fw = _tiny_fw("prefill-bad")
    padded = numpy.zeros((2, 4), numpy.int32) + 1
    with pytest.raises(ValueError, match="prompt_lens"):
        prefill(fw, padded, prompt_lens=[5, 2])
    with pytest.raises(ValueError, match="window"):
        prefill(fw, padded, window=2)


# -- per-slot step shape ------------------------------------------------------

def test_paged_step_matches_scalar_step(f32):
    """The paged step with all rows at the SAME position equals
    apply_step at that scalar position (the scalar step over a dense
    cache is the all-positions-equal special case), for both the
    transformer block and the embedding — history rows included."""
    fw = _tiny_fw("pagedstep")
    emb, block = fw[0], fw[1]
    eparams = {n: jnp.asarray(a.map_read().mem)
               for n, a in emb.param_arrays().items()}
    bparams = {n: jnp.asarray(a.map_read().mem)
               for n, a in block.param_arrays().items()}
    toks = jnp.asarray([[3], [7]], jnp.int32)
    pos, bs, d = 5, 4, 16
    rows = jnp.asarray([pos, pos], jnp.int32)
    x_scalar = emb.apply_step(eparams, toks, pos)
    x_slots = emb.apply_step_slots(eparams, toks, rows)
    numpy.testing.assert_allclose(numpy.asarray(x_scalar),
                                  numpy.asarray(x_slots), atol=1e-6)
    # the same history twice: dense rows [2, 8, d], and the pool's
    # blocks 1..4 holding them through tables [[1, 2], [3, 4]]
    rng = numpy.random.default_rng(5)
    hist = {part: rng.standard_normal((2, 2 * bs, d)).astype(
        numpy.float32) for part in ("k", "v")}
    for part in hist:
        hist[part][:, pos:] = 0.0
    cache = {part: jnp.asarray(a) for part, a in hist.items()}
    pool = {part: jnp.concatenate(
        [jnp.zeros((1, bs, d), jnp.float32),
         jnp.asarray(a.reshape(4, bs, d))]) for part, a in hist.items()}
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    y_scalar, c_scalar = block.apply_step(bparams, x_scalar, pos,
                                          cache)
    y_paged, p_out = block.apply_step_paged(bparams, x_slots, rows,
                                            tables, pool)
    numpy.testing.assert_allclose(numpy.asarray(y_scalar),
                                  numpy.asarray(y_paged), atol=1e-5)
    for part in ("k", "v"):
        numpy.testing.assert_allclose(
            numpy.asarray(c_scalar[part]),
            numpy.asarray(p_out[part])[1:].reshape(2, 2 * bs, d),
            atol=1e-6)
        assert not numpy.asarray(p_out[part])[0].any()   # trash block


# -- paged KV cache -----------------------------------------------------------

def test_paged_cache_block_churn(f32):
    """Alloc/free under randomized churn never double-frees, leaks or
    double-owns a block; exhaustion returns None; a full drain
    restores the whole pool."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw = _tiny_fw("paged-churn", window=32)
    cache = PagedKVCache(fw, max_slots=4, window=32, block_size=4,
                         kv_blocks=16)
    assert cache.free_blocks == 16 and cache.used_blocks == 0
    rng = random.Random(7)
    live = []
    for _ in range(200):
        if live and (rng.random() < 0.45 or len(live) == 4):
            cache.release(live.pop(rng.randrange(len(live))))
        else:
            slot = cache.alloc(rng.randrange(1, 33))
            if slot is not None:
                live.append(slot)
        cache.check()
    for slot in live:
        cache.release(slot)
    cache.check()
    assert cache.free_blocks == 16 and cache.used_blocks == 0
    assert cache.free_slots == 4
    # double-free is a loud programming error, not silent corruption
    slot = cache.alloc(8)
    cache.release(slot)
    with pytest.raises(ValueError, match="double-freed"):
        cache.release(slot)
    # a request longer than the per-slot table is a programming error
    with pytest.raises(ValueError, match="table width"):
        cache.alloc(60)
    # block exhaustion: slots free but no memory -> no admission
    a = cache.alloc(32)   # 8 blocks
    b = cache.alloc(28)   # 7 blocks -> 1 of 16 left
    assert a is not None and b is not None
    assert cache.free_blocks == 1 and cache.free_slots == 2
    assert cache.alloc(8) is None
    assert cache.alloc(4) is not None
    cache.check()


def test_insert_hands_its_program_ids_of_its_own(f32, monkeypatch):
    """The block ids an insert hands to its asynchronous scatter are a
    copy: ``release`` zeroes the host table row at once, and ids that
    aliased it (``jnp.asarray`` of a numpy view does on the CPU
    backend) would send a scatter that has yet to run to the trash
    block."""
    from veles_tpu.serving import kv_slots
    from veles_tpu.serving.prefill import prefill
    fw = _tiny_fw("insert-ids", window=64)
    cache = kv_slots.PagedKVCache(fw, max_slots=8, window=64,
                                  block_size=4)
    rows, _ = prefill(fw, numpy.full((1, 64), 2, numpy.int32),
                      prompt_lens=[64], window=64)
    seen = []
    inner = kv_slots._insert_blocks

    def spy(pool_k, pool_v, src_k, src_v, ids, start):
        seen.append(ids.unsafe_buffer_pointer())
        return inner(pool_k, pool_v, src_k, src_v, ids, start)
    monkeypatch.setattr(kv_slots, "_insert_blocks", spy)
    # the table at a 64-byte boundary, where a row's view is aliased
    raw = numpy.zeros((cache.tables.size + 16,), numpy.int32)
    off = (-raw.ctypes.data % 64) // 4
    cache.tables = raw[off:off + cache.tables.size].reshape(
        cache.tables.shape)
    for _ in range(8):
        cache.insert(cache.alloc(64), rows, 64)
    lo = cache.tables.ctypes.data
    assert len(seen) == 8
    assert not [p for p in seen if lo <= p < lo + cache.tables.nbytes]


def _reference_sampled(fw, prompt, steps, temperature, top_k, seed):
    """The scan ``generate(kv_cache=True)`` runs (``_chain_step``:
    apply_step over a dense cache, one token at a time) with the
    scheduler's documented key schedule in place of generate()'s own
    (one split a step, which the scheduler's streams differ from by
    design): token ``t`` of a request is drawn with
    ``fold_in(key(seed), t)`` from logits / temperature restricted to
    the top-k."""
    import jax
    from veles_tpu import dtypes
    from veles_tpu.models.generate import _chain_step
    params = {i: {n: jnp.asarray(a.map_read().mem)
                  for n, a in u.param_arrays().items()}
              for i, u in enumerate(fw)}
    caches = {i: u.init_cache(1, len(prompt) + steps,
                              dtypes.compute_dtype())
              for i, u in enumerate(fw) if hasattr(u, "init_cache")}
    seq = list(prompt)
    for pos in range(len(prompt) + steps - 1):
        logits, caches = _chain_step(
            fw, params, jnp.asarray([[seq[pos]]], jnp.int32), pos,
            caches)
        if pos < len(prompt) - 1:
            continue
        z = logits[0, 0].astype(jnp.float32) / temperature
        z = jnp.where(z < jnp.sort(z)[-top_k], -jnp.inf, z)
        key = jax.random.fold_in(jax.random.key(seed),
                                 len(seq) - len(prompt))
        seq.append(int(jax.random.categorical(key, z)))
    return seq


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled-topk"])
@pytest.mark.parametrize("prefill_chunk", [0, 2],
                         ids=["oneshot", "chunked"])
def test_paged_matches_generate(f32, prefill_chunk, sampled):
    """Acceptance: the scheduler (multi-block tables, packed occupancy
    buckets, one-shot or chunked prefill) produces the token streams
    of the offline reference — ``generate()`` for greedy requests,
    its scan under the per-request key schedule for seeded sampling
    with top-k — token for token, ragged prompts decoding
    concurrently."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("paged-parity", blocks=2)
    prompts = [[3, 1, 4], [5], [7, 2, 9, 1], [2, 2], [11, 3, 5]]
    sch = InferenceScheduler(fw, max_slots=3, window=16, block_size=4,
                             prefill_chunk=prefill_chunk).start()
    try:
        if sampled:
            futs = [sch.submit(p, 5, temperature=0.9, top_k=5,
                               seed=13 + i)
                    for i, p in enumerate(prompts)]
        else:
            futs = [sch.submit(p, 5, seed=0) for p in prompts]
        outs = [f.result(240) for f in futs]
    finally:
        sch.close()
    for i, (p, out) in enumerate(zip(prompts, outs)):
        if sampled:
            ref = _reference_sampled(fw, p, 5, 0.9, 5, 13 + i)
        else:
            ref = numpy.asarray(generate(
                fw, numpy.asarray([p], numpy.int32), 5,
                kv_cache=True))[0].tolist()
        assert out == ref, (p, out, ref)


class _Without:
    """A unit seen without one of its methods."""

    def __init__(self, unit, missing):
        self._unit, self._missing = unit, missing

    def __getattr__(self, name):
        if name == self._missing:
            raise AttributeError(name)
        return getattr(self._unit, name)


@pytest.mark.parametrize("missing", [
    "apply_step_paged", "apply_prefill", "causal", "init_cache"])
def test_unservable_chain_is_refused_where_the_scheduler_is_built(
        f32, missing):
    """A chain whose cacheable unit lacks the paged decode step (or
    the batched prefill, or causality, or that has no cacheable unit
    at all) is refused in words, naming the unit, when the scheduler
    is built: there is no second cache to fall back to."""
    from veles_tpu.serving import InferenceScheduler, serving_supported
    fw = _tiny_fw("refused-" + missing)
    block = fw[1]
    if missing == "causal":
        block.causal = False
        chain = fw
    elif missing == "init_cache":
        chain = [fw[0], fw[2]]
    else:
        chain = [fw[0], _Without(block, missing), fw[2]]
    want = {"causal": "not causal",
            "init_cache": "no cacheable unit"}.get(missing, missing)
    assert not serving_supported(chain)
    with pytest.raises(ValueError, match=want) as err:
        InferenceScheduler(chain, max_slots=2, window=16)
    if missing != "init_cache":
        assert block.name in str(err.value)


def test_the_kv_option_is_gone(f32):
    """Which KV cache is no decision any more: the scheduler and the
    REST unit refuse the argument outright and the configuration has
    no such key."""
    from veles_tpu.restful_api import RESTfulAPI
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("no-kv-option")
    with pytest.raises(TypeError, match="kv"):
        InferenceScheduler(fw, max_slots=2, window=16, kv="dense")
    with pytest.raises(TypeError, match="serving_kv"):
        RESTfulAPI(None, forwards=fw, serving_kv="dense")
    assert "kv" not in root.common.serving
    assert not hasattr(fw[1], "apply_step_slots")


def test_paged_memory_admission(f32):
    """Admission is memory-proportional: a request queues while the
    block pool is exhausted (even with slots free) and joins once
    blocks release; an over-pool request is a client error at
    submit."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("paged-mem", window=16)
    sch = InferenceScheduler(fw, max_slots=4, window=16,
                             block_size=4, kv_blocks=3,
                             prefill_chunk=0).start()
    try:
        with pytest.raises(ValueError, match="kv_blocks"):
            sch.submit([1] * 8, 6)            # 14 tokens > 12-token pool
        a = sch.submit([1, 2, 3, 4], 4)       # 8 tokens = 2 blocks
        b = sch.submit([5, 6, 7], 5)          # 8 tokens = 2 blocks
        assert len(a.result(240)) == 8
        assert len(b.result(240)) == 8        # admitted after a freed
        snap = sch.metrics()
        assert snap["kv_mode"] == "paged"
        assert snap["kv_blocks_total"] == 3
        assert snap["kv_blocks_used"] == 0    # drained
        assert snap["kv_blocks_free"] == 3
    finally:
        sch.close()


# -- chunked prefill ----------------------------------------------------------

def test_chunked_prefill_matches_oneshot(f32):
    """Chunk-by-chunk prefill reproduces the one-shot pass: identical
    staging K/V rows and last-position logits (the first-token
    edge)."""
    from veles_tpu import dtypes
    from veles_tpu.serving import prefill, prefill_chunk
    fw = _tiny_fw("chunked", blocks=2)
    p = [3, 1, 4, 1, 5, 9, 2]
    w, c = 8, 2
    padded = numpy.zeros((1, w), numpy.int32)
    padded[0, :len(p)] = p
    ref_caches, ref_last = prefill(fw, padded, prompt_lens=[len(p)],
                                   window=w)
    caches = {i: u.init_cache(1, w, dtypes.compute_dtype())
              for i, u in enumerate(fw) if hasattr(u, "init_cache")}
    off = 0
    while off < len(p):
        end = min(off + c, len(p))
        chunk = numpy.zeros((1, c), numpy.int32)
        chunk[0, :end - off] = p[off:end]
        kw = c
        while kw < off + c:
            kw *= 2
        caches, last = prefill_chunk(fw, chunk, off, [end - off],
                                     caches, key_width=min(kw, w))
        off = end
    for i in ref_caches:
        for part in ("k", "v"):
            numpy.testing.assert_allclose(
                numpy.asarray(caches[i][part]),
                numpy.asarray(ref_caches[i][part]), atol=1e-5,
                err_msg="layer %d %s" % (i, part))
    numpy.testing.assert_allclose(numpy.asarray(last),
                                  numpy.asarray(ref_last), atol=1e-4)


def test_chunked_prefill_interleaves_decode(f32):
    """A long prompt joining mid-traffic prefills in chunks: the
    chunk counters move, short in-flight requests keep decoding, and
    the long request's output still equals its solo decode."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("chunked-mix", window=64)
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=8, prefill_chunk=8).start()
    try:
        short = sch.submit([4, 2], 30)
        long_p = list(range(1, 12)) * 3     # 33 tokens, 5 chunks
        long_p = [t % 12 for t in long_p]
        fut = sch.submit(long_p, 6)
        out = fut.result(240)
        ref = numpy.asarray(generate(
            fw, numpy.asarray([long_p], numpy.int32), 6,
            kv_cache=True))[0].tolist()
        assert out == ref
        assert len(short.result(240)) == 32
        snap = sch.metrics()
        assert snap["prefill_chunks"] >= 5
        assert snap["prefill_chunk_tokens"] >= 33
    finally:
        sch.close()


# -- scheduler ----------------------------------------------------------------

def test_scheduler_greedy_parity_ragged(f32):
    """Acceptance: slot-scheduled decode (batched prefill + shared
    step) produces IDENTICAL greedy output to the sequential-scan
    generate() path, for ragged prompts decoding concurrently."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("sched", blocks=2)
    sch = InferenceScheduler(fw, max_slots=3, window=16).start()
    try:
        prompts = [[3, 1, 4], [5], [7, 2, 9, 1], [2, 2], [1]]
        futs = [sch.submit(p, 5) for p in prompts]
        outs = [f.result(120) for f in futs]
        for p, out in zip(prompts, outs):
            ref = numpy.asarray(generate(
                fw, numpy.asarray([p], numpy.int32), 5,
                kv_cache=True))[0].tolist()
            assert out == ref, (p, out, ref)
        snap = sch.metrics()
        assert snap["requests_completed"] == len(prompts)
        assert snap["tokens_generated"] == 5 * len(prompts)
        assert snap["ttft_ms_p50"] is not None
    finally:
        sch.close()


def test_scheduler_moe_chain(f32):
    """MoE-FFN blocks serve through the same slot path."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("schedmoe", n_experts=3, top_k=2)
    sch = InferenceScheduler(fw, max_slots=2, window=16).start()
    try:
        out = sch.submit([3, 1, 4], 4).result(120)
        ref = numpy.asarray(generate(
            fw, numpy.asarray([[3, 1, 4]], numpy.int32), 4,
            kv_cache=True))[0].tolist()
        assert out == ref
    finally:
        sch.close()


def test_scheduler_sampling_and_stop(f32):
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("schedsample")
    sch = InferenceScheduler(fw, max_slots=2, window=16).start()
    try:
        # per-seed reproducibility survives interleaving with other
        # traffic (per-request PRNG streams)
        futs = [sch.submit([3, 1], 6, temperature=0.8, top_k=4,
                           seed=11) for _ in range(3)]
        futs.append(sch.submit([5, 9, 2], 6))  # greedy noise traffic
        outs = [f.result(120) for f in futs[:3]]
        assert outs[0] == outs[1] == outs[2]
        assert all(0 <= t < 12 for t in outs[0])
        # a generated stop token ends the request there (stop kept)
        g = sch.submit([3, 1, 4], 5).result(120)
        stop = g[4]
        st = sch.submit([3, 1, 4], 5, stop_token=stop).result(120)
        assert st == g[:g.index(stop, 3) + 1]
        # validation errors are client errors, raised at submit
        with pytest.raises(ValueError, match="window"):
            sch.submit([1] * 10, 10)
        with pytest.raises(ValueError, match="top_k"):
            sch.submit([1], 2, top_k=3)
        with pytest.raises(ValueError, match="steps"):
            sch.submit([1], 0)
    finally:
        sch.close()


def test_scheduler_admission_control(f32):
    """Queue-depth cap rejects (503 material) and queued requests past
    their deadline expire (408 material) while the slot stays busy."""
    from veles_tpu.serving import (
        DeadlineExceededError, InferenceScheduler, QueueFullError)
    fw = _tiny_fw("schedadm", window=256)
    sch = InferenceScheduler(fw, max_slots=1, window=256,
                             max_queue=2).start()
    try:
        # occupy the single slot for a while
        busy = sch.submit([1, 2, 3], 200)
        time.sleep(0.05)  # let it admit
        q1 = sch.submit([1], 4)
        q2 = sch.submit([2], 4, timeout=0.01)  # expires in-queue
        with pytest.raises(QueueFullError):
            sch.submit([3], 4)
        with pytest.raises(DeadlineExceededError):
            q2.result(120)
        assert len(busy.result(240)) == 203
        assert len(q1.result(240)) == 5
        snap = sch.metrics()
        assert snap["requests_rejected"] == 1
        assert snap["requests_expired"] == 1
    finally:
        sch.close()


def test_scheduler_close_fails_pending(f32):
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    fw = _tiny_fw("schedclose", window=256)
    sch = InferenceScheduler(fw, max_slots=1, window=256).start()
    fut = sch.submit([1, 2], 200)
    sch.close()
    with pytest.raises(SchedulerError):
        fut.result(10)
    with pytest.raises(SchedulerError):
        sch.submit([1], 2)


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

    def post(payload, timeout=120):
        req = urllib.request.Request(
            "http://127.0.0.1:%d/generate" % api.port,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return json.load(urllib.request.urlopen(req, timeout=timeout))

    return api, loader, post


@pytest.mark.slow
def test_rest_serving_concurrent_soak(f32):
    """Acceptance: with the serving subsystem enabled, N concurrent
    /generate clients complete in < 2x the single-client wall-clock
    (vs ~Nx under the old decode lock), and every client's greedy
    output stays exactly its solo decode.  ``slow`` since PR 19: the
    wall-clock ratio is a soak-grade assertion (the parity half is
    covered by the scheduler/REST parity tests that stay in tier-1)
    — run with ``pytest -m slow``."""
    n_clients, steps = 4, 16
    api, loader, post = _serve_api("soak-serving", max_slots=4)
    try:
        assert api.scheduler_ is not None, "scheduler did not engage"
        prompts = [[3, 1, 4], [5], [7, 2], [1, 9, 2, 4]]
        # warm every prefill bucket + the slot step (compile time must
        # not pollute the timing), and grab the solo references
        refs = [post({"prompt": p, "steps": steps})["tokens"]
                for p in prompts]
        t0 = time.perf_counter()
        solo = post({"prompt": prompts[0], "steps": steps})["tokens"]
        t_single = time.perf_counter() - t0
        assert solo == refs[0]

        replies = [None] * n_clients
        errors = []

        def client(i):
            try:
                replies[i] = post(
                    {"prompt": prompts[i], "steps": steps})["tokens"]
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
            assert not t.is_alive(), "client blocked: server deadlock"
        t_concurrent = time.perf_counter() - t0
        assert not errors, errors
        for i in range(n_clients):
            assert replies[i] == refs[i], "client %d corrupted" % i
        # the overlap assertion: 4 clients in < 2x one client's time
        # (the old lock serialized them to ~4x); generous slack for
        # slow CI but far below the serialized bound
        assert t_concurrent < 2.0 * t_single + 0.5, \
            "no overlap: %d clients took %.3fs vs single %.3fs" % (
                n_clients, t_concurrent, t_single)
        # metrics surfaced over HTTP
        snap = json.load(urllib.request.urlopen(
            "http://127.0.0.1:%d/serving/metrics" % api.port,
            timeout=30))
        assert snap["requests_completed"] >= n_clients + len(prompts)
        assert snap["tokens_generated"] >= steps * n_clients
        assert 0.0 < snap["slot_occupancy"] <= 1.0
        assert snap["ttft_ms_p50"] is not None
        # operators watch block headroom for admission pressure: all
        # requests drained, so every block is either back in the free
        # pool or RESIDENT in the radix prefix cache (ON by default
        # since PR 10) — none left slot-private
        assert snap["kv_mode"] == "paged"
        resident = snap.get("prefix_cache_blocks_resident", 0)
        assert snap["kv_blocks_used"] == resident
        assert snap["kv_blocks_free"] + resident \
            == snap["kv_blocks_total"] > 0
        assert snap["queue_depth"] == 0
        api.scheduler_.check_kv()
    finally:
        api.stop()
        loader.close()


def test_rest_serving_error_mapping(f32):
    """Scheduler client errors surface as HTTP client errors: an
    over-window request 400s, and the serving events reach the JSONL
    event ring (the L8 status plumbing)."""
    from veles_tpu.logger import events
    api, loader, post = _serve_api("serving-errors")
    try:
        assert api.scheduler_ is not None
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"prompt": [1] * 20, "steps": 20})  # > window 24
        assert e.value.code == 400
        post({"prompt": [3, 1], "steps": 3})
        assert any(ev["name"] == "serving.request"
                   for ev in list(events.ring)), \
            "serving metrics did not reach the event sink"
    finally:
        api.stop()
        loader.close()


def test_rest_generate_validation_and_caps(f32):
    """Malformed /generate bodies are CLIENT errors (400 with a
    message), not 500s from the blanket handler, and the configurable
    max_steps/max_batch caps reject oversize requests before they pay
    a giant alloc + compile (ADVICE r5)."""
    api, loader, post = _serve_api("serving-validate",
                                   max_steps=8, max_batch=2)
    try:
        def expect_400(payload, needle):
            with pytest.raises(urllib.error.HTTPError) as e:
                post(payload)
            assert e.value.code == 400, payload
            body = e.value.read().decode(errors="replace")
            assert needle in body, (needle, body)

        expect_400({"steps": 2}, "prompt")                # missing
        expect_400({"prompt": 7, "steps": 2}, "prompt")   # scalar
        expect_400({"prompt": "hi", "steps": 2}, "prompt")
        expect_400({"prompt": [3, [1]], "steps": 2}, "flat")  # ragged
        expect_400({"prompt": [3, 1]}, "steps")           # missing
        expect_400({"prompt": [3, 1], "steps": "many"}, "steps")
        expect_400({"prompt": [3, 1], "steps": -1}, "steps")
        expect_400({"prompt": [3, 1], "steps": 2, "stop": "eos"},
                   "stop")
        expect_400({"prompt": [3, 1], "steps": 99}, "max_steps")
        expect_400({"prompt": [[3], [1], [4]], "steps": 2},
                   "max_batch")
        # a well-formed request inside the caps still answers
        assert len(post({"prompt": [3, 1], "steps": 2})["tokens"]) == 4
    finally:
        api.stop()
        loader.close()


def test_rest_serving_off_falls_back(f32):
    """serving=False pins the legacy serialized decode path — the
    endpoint still answers (regression guard for the fallback)."""
    api, loader, post = _serve_api("serving-off", serving=False)
    try:
        assert api.scheduler_ is None
        a = post({"prompt": [3, 1, 4], "steps": 4})
        b = post({"prompt": [3, 1, 4], "steps": 4})
        assert a["tokens"] == b["tokens"] and len(a["tokens"]) == 7
    finally:
        api.stop()
        loader.close()
