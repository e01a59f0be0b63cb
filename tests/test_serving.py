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


def test_chunked_prefill_interleaves_decode(f32, monkeypatch):
    """A long prompt joining mid-traffic prefills in chunks: the
    chunk counters move, short in-flight requests keep decoding, and
    the long request's output still equals its solo decode."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.serving import scheduler as sched_mod
    # a ridge as small as this window: 33 tokens go in 16 + 16 + 8
    monkeypatch.setattr(sched_mod, "PREFILL_WIDEST", 16)
    fw = _tiny_fw("chunked-mix", window=64)
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=8, prefill_chunk=8).start()
    try:
        short = sch.submit([4, 2], 30)
        long_p = list(range(1, 12)) * 3     # 33 tokens, 3 chunks
        long_p = [t % 12 for t in long_p]
        fut = sch.submit(long_p, 6)
        out = fut.result(240)
        ref = numpy.asarray(generate(
            fw, numpy.asarray([long_p], numpy.int32), 6,
            kv_cache=True))[0].tolist()
        assert out == ref
        assert len(short.result(240)) == 32
        snap = sch.metrics()
        assert snap["prefill_chunks"] == 3
        assert snap["prefill_chunk_tokens"] == 33
    finally:
        sch.close()


# -- how wide the next chunk is -----------------------------------------------

def _widths(p_len, narrowest, widest):
    """[(offset, width)] of a cold prompt's chunks, as the scheduler's
    ticks ask for them."""
    from veles_tpu.serving.scheduler import chunk_width
    out, off = [], 0
    while off < p_len:
        out.append((off, chunk_width(p_len - off, off, narrowest,
                                     widest)))
        off += out[-1][1]
    return out


@pytest.mark.parametrize("p_len, widths", [
    (65, [128]), (128, [128]), (129, [256]), (200, [256]),
    (256, [256]), (257, [256, 64]), (300, [256, 64]),
    (320, [256, 64]), (321, [256, 128]), (384, [256, 128]),
    (385, [256, 256]), (513, [256, 256, 64]),
    (1000, [256] * 4), (1024, [256] * 4),
    (1100, [256] * 4 + [128])])
def test_chunk_width_examples(p_len, widths):
    """The rule at the default sizes (narrowest 64, widest 256): one
    stream of the weights for up to 256 positions."""
    assert [w for _, w in _widths(p_len, 64, 256)] == widths


@pytest.mark.parametrize("narrowest, widest", [
    (64, 256), (64, 128), (64, 64), (8, 256), (2, 8), (16, 16),
    (512, 512)], ids=lambda v: str(v))
def test_chunk_width_rule(narrowest, widest):
    """For every prompt length 1 ... 1,100: each offset is a multiple
    of its chunk's width (and so of every later one), widths never
    rise, the last chunk covers the tail and no earlier one passes
    it, every width is a power of two in [narrowest, widest], and
    the width changes only where the length passes a multiple of
    ``narrowest`` -- so all prompts of one block count (the
    benchmark's warm-up sweep) meet the same programs.  A chain
    capped at its narrowest (a scanning unit) gets it throughout."""
    before = None
    for p_len in range(1, 1101):
        chunks = _widths(p_len, narrowest, widest)
        for (off, w), nxt in zip(chunks, chunks[1:] + [None]):
            assert off % w == 0, (p_len, chunks)
            assert narrowest <= w <= widest and w & (w - 1) == 0
            if nxt is not None:
                assert nxt[1] <= w and off + w == nxt[0] < p_len
            else:
                assert off < p_len <= off + w
                # no wider than the tail needs
                assert w == narrowest or w // 2 < p_len - off
        widths = [w for _, w in chunks]
        if narrowest == widest:
            assert set(widths) == {narrowest}
        if before is not None and (p_len - 1) % narrowest:
            assert widths == before, (p_len, widths, before)
        before = widths


def test_chunk_width_narrows_to_an_unaligned_offset():
    """An offset that is no multiple of the bucket halves the width
    until it is (a caller that starts past 0)."""
    from veles_tpu.serving.scheduler import chunk_width
    assert chunk_width(500, 64, 64, 256) == 64
    assert chunk_width(500, 128, 64, 256) == 128
    assert chunk_width(500, 768, 64, 256) == 256


def test_widest_chunk_follows_the_chain(f32):
    """The cap comes from the chain's layer types: products over the
    chunk's positions go as wide as the ridge, a unit that says it
    scans them (``prefill_scans``) keeps the configured width; a
    configured width past the ridge is its own cap; chunking off
    stays off."""
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.serving.scheduler import PREFILL_WIDEST, widest_chunk
    fw = _tiny_fw("widest")
    assert widest_chunk(fw, 64) == PREFILL_WIDEST == 256
    assert widest_chunk(fw, 512) == 512
    fw[1].prefill_scans = True
    try:
        assert widest_chunk(fw, 64) == 64
    finally:
        del fw[1].prefill_scans
    for chunk, widest in ((8, 256), (0, 0)):
        sch = InferenceScheduler(fw, max_slots=1, window=16,
                                 prefill_chunk=chunk)
        assert sch.prefill_widest == widest
        assert sch.metrics()["prefill_widest"] == widest


@pytest.mark.parametrize("p_len", [5, 9, 11, 13, 16, 23, 32])
def test_mixed_width_chunks_match_oneshot(f32, p_len):
    """Chunks of the widths the rule gives (narrowest 2, widest 8, so
    8 + 4 + 2 all occur) leave the staging rows and the first-token
    logits that one-shot prefill leaves."""
    from veles_tpu import dtypes
    from veles_tpu.serving import prefill, prefill_chunk
    fw = _tiny_fw("mixed-widths", window=32, blocks=2)
    rng = numpy.random.default_rng(p_len)
    p = rng.integers(0, 12, p_len).tolist()
    w = 32
    padded = numpy.zeros((1, w), numpy.int32)
    padded[0, :p_len] = p
    ref_caches, ref_last = prefill(fw, padded, prompt_lens=[p_len],
                                   window=w)
    caches = {i: u.init_cache(1, w, dtypes.compute_dtype())
              for i, u in enumerate(fw) if hasattr(u, "init_cache")}
    chunks = _widths(p_len, 2, 8)
    for off, c in chunks:
        end = min(off + c, p_len)
        chunk = numpy.zeros((1, c), numpy.int32)
        chunk[0, :end - off] = p[off:end]
        kw = c
        while kw < off + c:
            kw *= 2
        caches, last = prefill_chunk(fw, chunk, off, [end - off],
                                     caches, key_width=min(kw, w))
    for i in ref_caches:
        for part in ("k", "v"):
            numpy.testing.assert_allclose(
                numpy.asarray(caches[i][part]),
                numpy.asarray(ref_caches[i][part]), atol=1e-5,
                err_msg="layer %d %s, chunks %s" % (i, part, chunks))
    numpy.testing.assert_allclose(numpy.asarray(last),
                                  numpy.asarray(ref_last), atol=1e-4)


def _spy_on_chunks(monkeypatch, widest=None):
    """Record (offset, width, live positions) of every chunk the
    scheduler dispatches; ``widest`` stands in for the ridge of a chip
    whose chunks are as small as these tests' windows."""
    from veles_tpu.serving import scheduler as sched_mod
    seen = []
    real = sched_mod.prefill_chunk

    def spy(forwards, chunk, offset, chunk_lens, caches, **kwargs):
        seen.append((int(offset), int(chunk.shape[1]),
                     int(chunk_lens[0])))
        return real(forwards, chunk, offset, chunk_lens, caches,
                    **kwargs)
    monkeypatch.setattr(sched_mod, "prefill_chunk", spy)
    if widest is not None:
        monkeypatch.setattr(sched_mod, "PREFILL_WIDEST", widest)
    return seen


def test_scheduler_prefills_in_the_rules_widths(f32, monkeypatch):
    """The scheduler's ticks ask for exactly the rule's widths (one
    chunk a pass), count their live positions, and the stream is
    generate()'s; prompts up to the narrowest width stay one-shot."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    seen = _spy_on_chunks(monkeypatch, widest=16)
    fw = _tiny_fw("rule-widths", window=64)
    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=4,
                             spec=False, prefix_cache=False).start()
    try:
        assert sch.prefill_widest == 16
        rng = numpy.random.default_rng(7)
        for p_len in (4, 5, 16, 17, 27, 41):
            del seen[:]
            p = rng.integers(0, 12, p_len).tolist()
            before = sch.metrics()
            out = sch.submit(p, 3).result(240)
            ref = numpy.asarray(generate(
                fw, numpy.asarray([p], numpy.int32), 3,
                kv_cache=True))[0].tolist()
            assert out == ref, p_len
            want = _widths(p_len, 4, 16) if p_len > 4 else []
            assert [(o, w) for o, w, _ in seen] == want, p_len
            assert sum(n for _, _, n in seen) == (p_len if want else 0)
            snap = sch.metrics()
            assert snap["prefill_chunks"] - before["prefill_chunks"] \
                == len(want)
            assert snap["prefill_chunk_tokens"] \
                - before["prefill_chunk_tokens"] == (
                    p_len if want else 0)
    finally:
        sch.close()


def test_a_joiners_first_chunk_waits_for_the_pass_after_its_admission(
        f32, monkeypatch):
    """A pass that admits (the joiner's staging rows are built on the
    loop thread) dispatches no chunk for the joiner: its first chunk
    goes out in the next pass -- unless an OLDER request is mid-prefill,
    whose chunk the admission never holds back."""
    from veles_tpu import faults
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.serving import scheduler as sched_mod
    monkeypatch.setattr(sched_mod, "PREFILL_WIDEST", 8)
    log = []

    def logged(name):
        real = getattr(InferenceScheduler, name)

        def wrapper(self, *args, **kwargs):
            log.append(name)
            return real(self, *args, **kwargs)
        monkeypatch.setattr(InferenceScheduler, name, wrapper)
    for name in ("_reap", "_begin_admit", "_prefill_tick"):
        logged(name)        # _reap runs once a pass, before the rest
    fw = _tiny_fw("first-chunk-next-pass", window=64)
    sch = InferenceScheduler(fw, max_slots=2, window=64, block_size=4,
                             prefill_chunk=4, spec=False,
                             prefix_cache=False).start()
    try:
        rng = numpy.random.default_rng(5)
        lone = rng.integers(0, 12, 7).tolist()
        assert len(sch.submit(lone, 2).result(240)) == 9
        passes = " ".join(log).split("_reap")
        at = next(i for i, p in enumerate(passes) if "_begin_admit" in p)
        assert "_prefill_tick" not in passes[at]
        assert "_prefill_tick" in passes[at + 1]
        # 40 positions go in five chunks of 8: the second request is
        # admitted while the first still prefills, and that pass ticks
        del log[:]
        faults.inject("serving.scheduler.prefill", "delay", arg=0.05)
        try:
            first = sch.submit(rng.integers(0, 12, 40).tolist(), 2)
            time.sleep(0.08)            # mid-prefill by now
            second = sch.submit(lone, 2)
            assert len(first.result(240)) == 42
            assert len(second.result(240)) == 9
        finally:
            faults.clear()
        passes = " ".join(log).split("_reap")
        admitting = [p for p in passes if "_begin_admit" in p]
        assert len(admitting) == 2
        assert "_prefill_tick" not in admitting[0]
        assert "_prefill_tick" in admitting[1]
    finally:
        sch.close()


def test_preempt_resume_over_mixed_widths_is_bit_identical(
        f32, monkeypatch):
    """A preempted request re-prefills prompt + generated prefix
    through the same ticks, in other widths than its first prefill
    (the sequence is longer): the resumed stream, greedy and seeded,
    equals the uninterrupted one."""
    from veles_tpu.serving import InferenceScheduler
    seen = _spy_on_chunks(monkeypatch, widest=16)
    fw = _tiny_fw("resume-widths", window=64, blocks=2)
    rng = numpy.random.default_rng(11)
    prompts = [(rng.integers(0, 12, 21).tolist(), dict()),
               (rng.integers(0, 12, 9).tolist(),
                dict(temperature=0.9, top_k=5, seed=123))]

    def run(preempt):
        del seen[:]
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4, prefill_chunk=4,
                                 spec=False, prefix_cache=False).start()
        try:
            futs = [sch.submit(p, 24, **kw) for p, kw in prompts]
            if preempt:
                deadline = time.monotonic() + 60
                while sch.metrics()["slot_busy_steps"] < 6:
                    assert time.monotonic() < deadline
                    time.sleep(0.01)
                sch.request_preempt()
            outs = [f.result(120) for f in futs]
            return outs, sch.metrics(), list(seen)
        finally:
            sch.close()

    base, _, first = run(preempt=False)
    assert [(o, w) for o, w, _ in first] == \
        _widths(21, 4, 16) + _widths(9, 4, 16)
    resumed, snap, chunks = run(preempt=True)
    assert snap["preempt_resumes"] >= 1
    assert resumed == base
    # the resume's chunks follow the rule over ITS sequence
    again = chunks[len(first):]
    assert again and again[0][0] == 0
    total = sum(n for _, _, n in again)
    assert [(o, w) for o, w, _ in again] == _widths(total, 4, 16)
    assert total > min(len(p) for p, _ in prompts)


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
