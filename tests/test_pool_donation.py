"""The serving cache's device state is DONATED to every program that
returns it anew (``serving/kv_slots.py``, ``serving/engine.py``): after
each such call every leaf that went in is deleted and nothing was
copied (``pool_copies`` / ``veles_serving_pool_copies_total`` stay 0),
for compute-dtype and int8 pools, one chip and a tp=2 mesh, and the
LFM2 chain's conv state; the metadata readers still
answer on consumed leaves; a step that fails after consuming its input
fails the in-flight requests and the next request is served from
re-zeroed pools."""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import faults
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.serving

WINDOW, BLOCK = 32, 4


@contextlib.contextmanager
def _float32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    try:
        yield
    finally:
        root.common.precision.compute_dtype = saved


@pytest.fixture
def f32():
    with _float32():
        yield


@pytest.fixture(scope="module")
def chain():
    from veles_tpu import prng
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    prng.get("default").seed(30)
    spec = [{"type": "embedding", "vocab": 12, "dim": 16}]
    spec += [{"type": "transformer_block", "heads": 2,
              "causal": True}] * 2
    spec += [{"type": "token_logits", "vocab": 12}]
    with _float32():
        fw = make_forwards(
            AcceleratedWorkflow(None, name="pool-donation"),
            Array(numpy.zeros((2, WINDOW), numpy.int32)), spec)
        for u in fw:
            u.initialize(device=Device(backend="numpy"))
    return fw


_leaves = jax.tree.leaves


def _copies_total():
    from veles_tpu.telemetry import metrics
    return metrics.counter("veles_serving_pool_copies_total").value


def _consumed(cache, before, swaps):
    """Every leaf that went in is gone, nothing was copied, and the
    cache holds live leaves of the same shapes."""
    assert all(a.is_deleted() for a in before)
    assert cache.pool_copies == 0 and cache.pool_swaps == swaps
    after = _leaves(cache.pools)
    assert not any(a.is_deleted() for a in after)
    assert [a.shape for a in after] == [a.shape for a in before]


def _paged(fw, kv_dtype, tp):
    """(cache, params, tp context) with one prompt prefilled and
    inserted into slot 0."""
    from veles_tpu.serving import ServingTP
    from veles_tpu.serving.kv_slots import PagedKVCache
    from veles_tpu.serving.prefill import prefill
    from veles_tpu.serving.weights import ServingWeights
    ctx = ServingTP(tp) if tp else None
    params = ServingWeights(fw, tp=ctx).params
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK, kv_dtype=kv_dtype, tp=ctx)
    prompt = [3, 1, 4, 1, 5, 9, 2]
    slot = cache.alloc(len(prompt) + 8)
    padded = numpy.zeros((1, 8), numpy.int32)
    padded[0, :len(prompt)] = prompt
    rows, _ = prefill(fw, padded, prompt_lens=[len(prompt)], window=8,
                      tp=ctx, params=params)
    before = _leaves(cache.pools)
    cache.insert(slot, rows, len(prompt))
    _consumed(cache, before, swaps=len(cache.pools))
    return cache, params, slot, len(prompt)


def _step_args(cache, slot, pos, width=1):
    toks = numpy.full((1, width), 5, numpy.int32)
    tables = cache.table_rows([slot], 4)
    zeros = numpy.zeros((1,), numpy.int32)
    return toks, numpy.asarray([pos], numpy.int32), tables, zeros


def _call_step(fw, cache, params, slot, pos):
    from veles_tpu.serving.engine import paged_decode_step
    toks, pos, tables, z = _step_args(cache, slot, pos)
    nxt = paged_decode_step(fw, cache, toks, pos, tables,
                            z.astype(numpy.float32), z,
                            z.astype(numpy.uint32), z, params=params)
    return 1, numpy.asarray(nxt)


def _call_verify(fw, cache, params, slot, pos):
    from veles_tpu.serving.engine import verify_step_paged
    toks, pos, tables, z = _step_args(cache, slot, pos, width=3)
    nxt = verify_step_paged(fw, cache, toks, pos,
                            numpy.asarray([3], numpy.int32), tables,
                            z.astype(numpy.float32), z,
                            z.astype(numpy.uint32), z, params=params)
    return 1, numpy.asarray(nxt)


def _call_insert(fw, cache, params, slot, pos):
    from veles_tpu.serving.prefill import prefill
    other = cache.alloc(8)
    rows, _ = prefill(fw, numpy.full((1, 8), 2, numpy.int32),
                      prompt_lens=[6], window=8, tp=cache.tp_,
                      params=params)
    cache.insert(other, rows, 6)
    return len(cache.pools), None


def _call_import(fw, cache, params, slot, pos):
    ids = [int(b) for b in cache.tables[slot, :2]]
    record = cache.export_blocks(ids)
    want = {i: {n: a.copy() for n, a in layer.items()}
            for i, layer in record.items()}
    fresh = cache.take_free_blocks(2)
    cache.import_blocks(fresh, record)
    # what went in is what is resident, scales included
    got = cache.export_blocks(fresh)
    for i, layer in want.items():
        for name, a in layer.items():
            numpy.testing.assert_array_equal(got[i][name], a)
    return len(cache.pools), None


CALLS = {"paged_decode_step": _call_step,
         "verify_step_paged": _call_verify,
         "insert": _call_insert, "import_blocks": _call_import}


@pytest.mark.parametrize("tp", [0, 2], ids=["tp0", "tp2"])
@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("call", sorted(CALLS))
def test_state_returning_call_consumes_its_input(chain, f32, call,
                                                 kv_dtype, tp):
    cache, params, slot, n = _paged(chain, kv_dtype, tp)
    before = _leaves(cache.pools)
    had = cache.pool_swaps
    swaps, out = CALLS[call](chain, cache, params, slot, n)
    _consumed(cache, before, had + swaps)
    # the programs that only READ the pools donate nothing
    live = _leaves(cache.pools)
    cache.export_blocks([1])
    assert not any(a.is_deleted() for a in live)
    if out is not None:
        assert out.shape[0] == 1


def test_fused_verify_reads_the_pool_before_it_writes_it(chain, f32):
    """The fused verify gathers the PRE-scatter pool and scatters the
    same donated pool: the tokens and the pools are those of the
    two-pass verify."""
    got = {}
    for fused in (False, True):
        root.common.serving.fused_verify = fused
        try:
            cache, params, slot, n = _paged(chain, "fp32", 0)
            before = _leaves(cache.pools)
            had = cache.pool_swaps
            _, nxt = _call_verify(chain, cache, params, slot, n)
            _consumed(cache, before, had + 1)
            got[fused] = (nxt, [numpy.asarray(a)
                                for a in _leaves(cache.pools)])
        finally:
            root.common.serving.fused_verify = False
    numpy.testing.assert_array_equal(got[True][0], got[False][0])
    for a, b in zip(got[True][1], got[False][1]):
        numpy.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("call", ["paged_decode_step", "insert"])
def test_lfm2_kv_pools_and_conv_state_are_donated(f32, call):
    """The LFM2 chain: paged K/V pools and per-slot conv state go in
    donated together, and the routed layers' counts alias nothing."""
    import test_lfm2 as lfm2
    from veles_tpu.serving.engine import paged_decode_step
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw = lfm2._chain("lfm2-donation")
    params = lfm2._params(fw)
    cache = PagedKVCache(fw, max_slots=2, window=lfm2.WINDOW,
                         block_size=lfm2.BLOCK)
    assert cache.state_units and len(cache.state_units) \
        < len(cache.pools)
    prompt = list(range(1, 12))
    slot = cache.alloc(len(prompt) + 8)
    rows, _ = lfm2._prefilled(fw, params, prompt, chunked=False)
    before = _leaves(cache.pools)
    if call == "insert":
        cache.insert(slot, rows, len(prompt))
        swaps = len(cache.pools)
    else:
        z = numpy.zeros((1,), numpy.int32)
        nxt = paged_decode_step(
            fw, cache, numpy.full((1, 1), 5, numpy.int32),
            numpy.asarray([len(prompt)], numpy.int32),
            cache.table_rows([slot], 8), z.astype(numpy.float32), z,
            z.astype(numpy.uint32), z, params=params,
            slots=numpy.asarray([slot], numpy.int32))
        assert numpy.asarray(nxt).shape == (1,)
        assert numpy.asarray(cache.step_counts["moe"]).shape[1] == 4
        swaps = 1
    kinds = {"conv" if i in cache.state_units else "kv"
             for i in cache.pools}
    assert kinds == {"conv", "kv"}
    _consumed(cache, before, swaps)


def test_metadata_readers_answer_on_consumed_leaves(chain, f32):
    """``state_bytes`` (the HTTP thread's reader) sees a cache whose
    leaves a step in flight has consumed: the numbers of the live one.
    The values of such a leaf raise; its metadata does not."""
    cache, params, slot, n = _paged(chain, "int8", 0)
    want = cache.state_bytes()
    per_token = cache.bytes_per_token()
    stale = {i: dict(layer) for i, layer in cache.pools.items()}
    _call_step(chain, cache, params, slot, n)
    assert cache.state_bytes() == want and want["kv"] > 0
    live, cache.pools = cache.pools, stale   # launch seen, swap not yet
    assert all(a.is_deleted() for a in _leaves(cache.pools))
    assert cache.state_bytes() == want
    assert cache.bytes_per_token() == per_token
    assert cache.pools_lost()
    with pytest.raises(RuntimeError, match="deleted"):
        numpy.asarray(_leaves(cache.pools)[0])
    cache.pools = live
    assert not cache.pools_lost()
    cache.check()


def test_reset_pools_zeroes_lost_leaves_in_place_of_them(chain, f32):
    cache, params, slot, n = _paged(chain, "fp32", 2)
    shardings = [a.sharding for a in _leaves(cache.pools)]
    _leaves(cache.pools)[1].delete()
    assert cache.pools_lost()
    cache.reset_pools()
    assert not cache.pools_lost()
    after = _leaves(cache.pools)
    assert [a.sharding for a in after] == shardings
    assert not any(numpy.asarray(a).any() for a in after)
    # and the zeroed pools serve: a step runs and consumes them
    _call_step(chain, cache, params, slot, 0)
    assert all(a.is_deleted() for a in after)


def test_copies_counter_counts_a_leaf_that_came_back_alive(chain):
    from veles_tpu.serving.kv_slots import PagedKVCache
    from veles_tpu.serving.metrics import ServingMetrics

    total = _copies_total
    cache = PagedKVCache(chain, max_slots=1, window=WINDOW,
                         block_size=BLOCK)
    alive, gone = jnp.zeros((2,)), jnp.zeros((2,))
    gone.delete()
    cache.note_swap(gone)
    assert (cache.pool_swaps, cache.pool_copies) == (1, 0)
    cache.note_swap(alive)
    cache.note_swap(alive)
    assert (cache.pool_swaps, cache.pool_copies) == (3, 2)
    stats, was = ServingMetrics(), total()
    idle = (dict.fromkeys(("parked", "admit"), 0.0), 0, 0, 0.0)
    stats.record_loop_pass(*idle, pool_copies=cache.pool_copies)
    stats.record_loop_pass(*idle, pool_copies=cache.pool_copies)
    assert total() == was + 2          # the growth, once
    stats.record_loop_pass(*idle)
    assert total() == was + 2


def test_a_second_holder_of_a_pool_is_refused_by_the_layout(chain, f32):
    """Trap 1: no program is handed one array as both halves of a
    pair; a cache layout that is not the K/V pair is refused in words
    where the cache is built."""
    from veles_tpu.serving.kv_slots import PagedKVCache

    class Odd:
        def init_cache(self, batch, max_len, dtype):
            return {"state": jnp.zeros((batch, max_len, 4), dtype)}

        def apply_step_paged(self, *args):
            raise AssertionError("never stepped")

    with pytest.raises(ValueError, match="one donated pair"):
        PagedKVCache([Odd()], max_slots=1, window=8, block_size=4)


# -- through the scheduler ----------------------------------------------------

SCHEDULERS = {
    "paged": dict(spec=False),
    "paged-int8": dict(kv_dtype="int8", spec=False),
    "paged-spec": dict(spec=True, spec_k=3),
    "paged-tp2": dict(tp=2, spec=False),
    "paged-tp2-int8-spec": dict(tp=2, kv_dtype="int8",
                                spec=True, spec_k=3),
}


@pytest.mark.parametrize("name", sorted(SCHEDULERS))
def test_scheduler_never_copies_its_pools(chain, f32, name):
    """Traffic through the whole scheduler (chunked prefill, warm
    prefix, speculation's verify, a preempt-free mix): every
    state-returning call wrote in place, said three ways."""
    from veles_tpu.serving import InferenceScheduler
    kw = dict(SCHEDULERS[name], block_size=BLOCK, prefill_chunk=4)
    was = _copies_total()
    sch = InferenceScheduler(chain, max_slots=2, window=WINDOW,
                             warm_buckets=False, **kw).start()
    try:
        assert sch.metrics()["pools_in_place"] is None   # no call yet
        base = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        futs = [sch.submit(base + [k], 6, seed=k) for k in range(4)]
        outs = [f.result(240) for f in futs]
        assert all(len(o) == len(base) + 7 for o in outs)
        sch.check_kv()
        snap = sch.metrics()
        assert snap["pools_in_place"] is True
        cache = sch.cache_
        assert cache.pool_swaps > 10 and cache.pool_copies == 0
    finally:
        sch.close()
    assert _copies_total() == was


def _reference(chain, prompt, steps, **kw):
    from veles_tpu.serving import InferenceScheduler
    sch = InferenceScheduler(chain, max_slots=2, window=WINDOW,
                             warm_buckets=False, **kw).start()
    try:
        return sch.submit(prompt, steps, seed=1).result(240)
    finally:
        sch.close()


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["cold", "prefix"])
def test_step_that_consumed_its_input_and_failed(chain, f32,
                                                 monkeypatch,
                                                 prefix_cache):
    """Trap 4: the step's call fails AFTER the pools went in.  The
    in-flight requests fail with the error, every resident prefix is
    forgotten, the pools are zeroed, and the next request gets exactly
    the tokens of a fresh server."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    from veles_tpu.serving import scheduler as sched_mod
    kw = dict(block_size=BLOCK, prefill_chunk=4,
              spec=False, prefix_cache=prefix_cache)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
    want = _reference(chain, prompt, 6, **kw)
    real, calls = sched_mod.paged_decode_step, []

    def failing(forwards, cache, *args, **kwargs):
        calls.append(len(calls))
        if len(calls) == 8:
            for leaf in jax.tree.leaves(cache.pools):
                leaf.delete()      # consumed, and nothing came back
            raise RuntimeError("device fell over mid-step")
        return real(forwards, cache, *args, **kwargs)

    monkeypatch.setattr(sched_mod, "paged_decode_step", failing)
    sch = InferenceScheduler(chain, max_slots=2, window=WINDOW,
                             warm_buckets=False, **kw).start()
    try:
        if prefix_cache:    # a finished request leaves its prefix behind
            assert sch.submit(prompt, 3, seed=1).result(240)[:9] == prompt
            assert sch.metrics()["prefix_cache_blocks_resident"] > 0
        doomed = [sch.submit(prompt[:5 + k], 20, seed=k)
                  for k in range(2)]
        for f in doomed:
            with pytest.raises(SchedulerError, match="KV pools were "
                               "lost.*device fell over"):
                f.result(240)
        assert len(calls) >= 8
        cache = sch.cache_
        assert not cache.pools_lost()
        # the loop thread forgets the prefixes right after it has
        # failed the requests
        deadline = time.monotonic() + 30
        while cache.free_blocks != cache.capacity_blocks \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert cache.free_blocks == cache.capacity_blocks
        if prefix_cache:
            assert sch.metrics()["prefix_cache_blocks_resident"] == 0
        sch.check_kv()
        assert sch.submit(prompt, 6, seed=1).result(240) == want
        sch.check_kv()
        assert sch.metrics()["pools_in_place"] is True
    finally:
        sch.close()


def test_step_that_failed_before_dispatch_keeps_the_pools(chain, f32):
    """A call that raises before it dispatches has consumed nothing:
    its requests fail with the error, the pools stay as they are (the
    other slot's prefix cache included) and the loop serves on."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    kw = dict(block_size=BLOCK, prefill_chunk=4,
              spec=False, prefix_cache=True)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
    want = _reference(chain, prompt, 6, **kw)
    sch = InferenceScheduler(chain, max_slots=2, window=WINDOW,
                             warm_buckets=False, **kw).start()
    try:
        assert sch.submit(prompt, 6, seed=1).result(240) == want
        resident = sch.metrics()["prefix_cache_blocks_resident"]
        assert resident > 0
        faults.inject("serving.scheduler.step", "exception", after=2,
                      times=1)
        with pytest.raises(SchedulerError, match="injected"):
            sch.submit(prompt[:6], 20, seed=2).result(240)
        faults.clear()
        assert sch.metrics()["prefix_cache_blocks_resident"] \
            == resident
        sch.check_kv()
        assert sch.submit(prompt, 6, seed=1).result(240) == want
    finally:
        faults.clear()
        sch.close()
