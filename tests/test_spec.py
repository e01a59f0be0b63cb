"""Speculative decoding + radix prefix cache (``serving/spec.py``,
``serving/prefix_cache.py``, ``engine.verify_step_paged``): spec-on /
spec-off bit-identical token parity (greedy AND seeded, through
preempt→resume and chunked prefill), accept-rate > 0 on repetitive
prompts with a clean KV sweep after rollbacks, trie
refcount/eviction invariants, warm-resubmit parity with near-zero
prefill work, cold-block-only admission, and the mixed warm/cold
fault soak."""

import time

import numpy
import pytest

from veles_tpu import faults
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array

pytestmark = pytest.mark.spec


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


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2, blocks=2):
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


# -- proposer + acceptance rule (host-side units) -----------------------------

def test_ngram_proposer():
    """Prompt lookup drafts the continuation of the most recent
    earlier occurrence of the trailing n-gram, longest n first, and
    degrades to no draft when nothing repeats."""
    from veles_tpu.serving import NgramProposer
    p = NgramProposer(k=4, max_ngram=3)
    # trailing [1, 2] recurs at the start; continuation was [3, 4]
    assert p.propose([1, 2, 3, 4, 9, 1, 2]) == [3, 4, 9, 1]
    # the MOST RECENT occurrence wins over the older one
    assert p.propose([5, 7, 1, 5, 8, 2, 5]) == [8, 2, 5]
    # nothing repeats -> no draft (caller falls back to plain decode)
    assert p.propose([1, 2, 3, 4, 5]) == []
    # k and max_tokens both cap the draft
    assert p.propose([6, 1, 2, 3, 4, 5, 6], max_tokens=2) == [1, 2]
    assert len(p.propose([2, 2, 2, 2, 2, 2, 2, 2])) <= 4
    with pytest.raises(ValueError):
        NgramProposer(k=0)


def test_accept_drafts():
    """The acceptance rule: longest matched prefix plus the free
    correction sample — exactly what sequential decode would emit."""
    from veles_tpu.serving import accept_drafts
    # all drafts match: every sample accepted (k + 1 tokens)
    assert accept_drafts([5, 6], [5, 6, 7]) == [5, 6, 7]
    # first draft wrong: only the correction token
    assert accept_drafts([9, 6], [5, 6, 7]) == [5]
    # second draft wrong: match + correction, tail rolled back
    assert accept_drafts([5, 9], [5, 6, 7]) == [5, 6]
    # no drafts: the plain decode token
    assert accept_drafts([], [4]) == [4]


# -- speculative decoding through the scheduler -------------------------------

def _run_sched(fw, submits, window=64, check=False, **kw):
    from veles_tpu.serving import InferenceScheduler
    sch = InferenceScheduler(fw, max_slots=3, window=window,
                             warm_buckets=False, **kw).start()
    try:
        futs = [sch.submit(p, steps, **skw)
                for p, steps, skw in submits]
        outs = [f.result(240) for f in futs]
        snap = sch.metrics()
        if check:
            sch.check_kv()
        return outs, snap
    finally:
        sch.close()


@pytest.fixture(params=["float32", "bfloat16"])
def compute_dtype(request):
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = request.param
    yield request.param
    root.common.precision.compute_dtype = saved


def test_spec_token_parity(compute_dtype):
    """Acceptance: spec-on produces streams BIT-IDENTICAL to
    spec-off — greedy and seeded sampling, one-shot and chunked
    prefill, repetitive and non-repetitive prompts decoding
    concurrently — and the KV block sweep is clean after the
    rollbacks.  Under bfloat16 too: an accepted token comes from the
    verify step's scores and a plain one from the decode step's, and
    both sum them in float32 over bfloat16 operands."""
    fw = _tiny_fw("spec-parity-" + compute_dtype)
    prompts = [[3, 1, 4, 3, 1, 4, 3, 1], [5, 2] * 6, [7] * 5,
               [1, 2, 3, 4], [9, 8, 9, 8, 9]]
    submits = [(p, 12, dict(seed=0)) for p in prompts]
    submits += [(p, 10, dict(temperature=0.9, top_k=5, seed=41 + i))
                for i, p in enumerate(prompts)]

    base, _ = _run_sched(fw, submits, block_size=4,
                         prefill_chunk=0, spec=False)
    spec, snap = _run_sched(fw, submits, block_size=4,
                            prefill_chunk=0, spec=True, spec_k=4,
                            check=True)
    assert spec == base
    assert snap["spec_drafted_tokens"] > 0
    # chunked prefill underneath changes nothing
    chunked, snap2 = _run_sched(fw, submits,
                                block_size=4, prefill_chunk=4,
                                spec=True, spec_k=4, check=True)
    assert chunked == base


def test_spec_accept_rate_on_repetitive_prompts(f32,
                                                spec_trained_chain):
    """Repetitive prompts must actually accept drafts (the whole
    point), the emitted streams still match spec-off, and rollback
    accounting balances drafted = accepted + rolled back.  Runs on
    the session-scoped TRAINED chain (conftest) — a model that has
    learned its text is the regime the proposer exists for, and
    sharing the fixture keeps tier-1 from training per test."""
    fw, pattern = spec_trained_chain
    prompts = [(pattern * 3)[:18], [2, 9] * 9, [3] * 12]
    submits = [(p, 16, dict(seed=0)) for p in prompts]
    base, _ = _run_sched(fw, submits, block_size=4,
                         prefill_chunk=0, spec=False)
    spec, snap = _run_sched(fw, submits, block_size=4,
                            prefill_chunk=0, spec=True, spec_k=4,
                            check=True)
    assert spec == base
    assert snap["spec_drafted_tokens"] > 0
    assert snap["spec_accept_rate"] is not None
    assert snap["spec_accepted_tokens"] \
        + snap["spec_rollback_tokens"] == snap["spec_drafted_tokens"]
    # untrained greedy decode settles into a cycle the n-gram
    # proposer predicts — some drafts MUST land on these prompts
    assert snap["spec_accepted_tokens"] > 0


def test_spec_preempt_resume_parity(f32):
    """Mid-stream preempt → resume with spec decoding on stays
    bit-identical to the uninterrupted run (greedy AND seeded): the
    draw counter len(generated) survives eviction, and the verify
    step folds the same counters the sequential steps would."""
    fw = _tiny_fw("spec-preempt")
    prompts = [([3, 1, 4, 3, 1, 4, 3], dict(seed=0)),
               ([7, 2] * 4, dict(temperature=0.9, top_k=5,
                                 seed=123))]

    def run(preempt):
        from veles_tpu.serving import InferenceScheduler
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 prefill_chunk=4, spec=True,
                                 spec_k=4,
                                 warm_buckets=False).start()
        try:
            futs = [sch.submit(p, 24, **kw) for p, kw in prompts]
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
    assert preempted == base


# -- radix prefix cache: trie unit invariants ---------------------------------

def test_prefix_trie_invariants():
    """Match pins, release unpins, double release raises, evicting a
    referenced or inner block raises, and LRU eviction walks
    refcount-0 leaves oldest-first."""
    from veles_tpu.serving import RadixPrefixCache
    pc = RadixPrefixCache(block_size=2)
    taken, rejected = pc.insert([1, 2, 3, 4, 5, 6], [10, 11, 12])
    assert taken == [10, 11, 12] and rejected == []
    assert pc.resident == 3
    # duplicate donation: incumbents keep the path, dupes rejected
    taken, rejected = pc.insert([1, 2, 3, 4, 9, 9], [20, 21, 22])
    assert taken == [22] and rejected == [20, 21]
    # longest-prefix match pins the path
    h = pc.match([1, 2, 3, 4, 7, 7, 7])
    assert h.blocks == [10, 11]
    assert pc.shared_blocks() == 2
    # a pinned block cannot be evicted, an inner one neither
    node = pc._walk([1, 2])[0]
    with pytest.raises(ValueError, match="live reference"):
        pc._evict_node(pc._walk([1, 2, 3, 4])[1])
    pc.release(h)
    with pytest.raises(ValueError, match="double-released"):
        pc.release(h)
    with pytest.raises(ValueError, match="children"):
        pc._evict_node(node)
    # double free through a fresh handle underflows loudly
    h2 = pc.match([1, 2])
    h2.nodes[0].refs = 0
    with pytest.raises(ValueError, match="double-freed"):
        pc.release(h2)
    # LRU eviction: leaves only, oldest stamp first
    pc2 = RadixPrefixCache(block_size=1)
    pc2.insert([1, 2], [31, 32])          # chain 1 -> 2
    pc2.insert([5], [35])                 # later leaf
    freed = pc2.evict(2)
    assert freed == [32, 31], "leaf-first, oldest-first"
    assert pc2.evict(5) == [35]
    assert pc2.resident == 0
    assert pc2.evictions == 3
    # max_blocks caps the walk (>= 1 cold token stays)
    pc3 = RadixPrefixCache(block_size=2)
    pc3.insert([1, 2, 3, 4], [41, 42])
    assert pc3.peek([1, 2, 3, 4], max_blocks=1) == 1


def test_prefix_trie_evictable_accounting():
    """evictable_blocks counts exactly what evict() could free:
    whole unpinned chains, nothing under a pinned node's own
    count."""
    from veles_tpu.serving import RadixPrefixCache
    pc = RadixPrefixCache(block_size=1)
    pc.insert([1, 2, 3], [11, 12, 13])
    assert pc.evictable_blocks() == 3
    h = pc.match([1, 2])
    # 11, 12 pinned; only the 13 leaf is freeable
    assert pc.evictable_blocks() == 1
    assert pc.evict(10) == [13]
    pc.release(h)
    assert pc.evictable_blocks() == 2


# -- radix prefix cache through the scheduler ---------------------------------

def test_prefix_warm_resubmit_parity(f32):
    """Acceptance: a warm resubmit produces BIT-IDENTICAL output
    (greedy and seeded) with near-zero prefill work — only the cold
    tail runs through the chunked path — and the shared-block sweep
    stays clean."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("pfx-warm")
    rng = numpy.random.default_rng(0)
    prompt = rng.integers(0, 12, (24,)).tolist()

    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=8,
                             prefix_cache=False,
                             warm_buckets=False).start()
    try:
        ref = sch.submit(prompt, 8, seed=0).result(240)
    finally:
        sch.close()

    sch = InferenceScheduler(fw, max_slots=2, window=64,
                             block_size=4, prefill_chunk=8,
                             prefix_cache=True).start()
    try:
        cold = sch.submit(prompt, 8, seed=0).result(240)
        cold_work = sch.metrics()["prefill_chunk_tokens"]
        warm = sch.submit(prompt, 8, seed=0).result(240)
        snap = sch.metrics()
        warm_work = snap["prefill_chunk_tokens"] - cold_work
        assert cold == ref, "prefix cache changed the COLD stream"
        assert warm == ref, "warm resubmit diverged"
        # 24-token prompt, 4-token blocks: (24-1)//4 = 5 blocks warm,
        # so at most one block of cold tail re-prefills
        assert cold_work >= len(prompt)
        assert warm_work <= sch.block_size, \
            "warm resubmit re-prefilled %d tokens" % warm_work
        assert snap["prefix_cache_hits"] == 1
        assert snap["prefix_cache_misses"] == 1
        assert snap["prefix_cache_blocks_resident"] > 0
        # seeded sampling is warm-stable too
        s1 = sch.submit(prompt, 8, temperature=0.8, top_k=4,
                        seed=7).result(240)
        s2 = sch.submit(prompt, 8, temperature=0.8, top_k=4,
                        seed=7).result(240)
        assert s1 == s2
        sch.check_kv()
    finally:
        sch.close()
    sch.check_kv()  # close released every pin and private block


def test_prefix_admission_counts_cold_blocks_only(f32):
    """Acceptance (satellite): a warm request must claim only
    ``ceil(cold_tokens / block_size)`` NEW blocks — it admits into a
    pool whose free list alone could never hold its full budget, so
    cache hits raise the concurrent-stream ceiling."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("pfx-admit")
    prompt = list(range(1, 12)) * 2   # 22 tokens
    # pool of 9 blocks (36 tokens): one request of 22 + 6 = 28 tokens
    # needs 7 blocks; after it completes it donates its written full
    # blocks — floor((28-1)/4) = 6 resident — and a warm twin matches
    # floor((22-1)/4) = 5 of them, needing only 7 - 5 = 2 new blocks
    sch = InferenceScheduler(fw, max_slots=2, window=32,
                             block_size=4, kv_blocks=9,
                             prefill_chunk=8, prefix_cache=True,
                             prefix_evict=False).start()
    try:
        first = sch.submit(prompt, 6, seed=0).result(240)
        snap = sch.metrics()
        resident = snap["prefix_cache_blocks_resident"]
        assert resident == 6
        assert snap["kv_blocks_free"] == 9 - resident
        # free list (3) < full budget (7): ONLY the cold-block
        # admission math lets this in
        warm = sch.submit(prompt, 6, seed=0).result(240)
        assert warm == first
        snap = sch.metrics()
        assert snap["prefix_cache_hits"] == 1
        sch.check_kv()
    finally:
        sch.close()


def test_prefix_eviction_under_pressure(f32):
    """Refcount-0 resident blocks are LRU-evicted when an admission
    needs them; with eviction disabled the same pressure queues the
    request instead (and the pool never corrupts either way)."""
    from veles_tpu.serving import InferenceScheduler
    fw = _tiny_fw("pfx-evict")
    a = [1, 2, 3] * 6                  # 18 tokens
    b = [9, 8, 7] * 6
    sch = InferenceScheduler(fw, max_slots=2, window=32,
                             block_size=4, kv_blocks=7,
                             prefill_chunk=8,
                             prefix_cache=True).start()
    try:
        sch.submit(a, 6, seed=0).result(240)
        snap = sch.metrics()
        assert snap["prefix_cache_blocks_resident"] == 5
        # b needs 6 of 7 blocks; only 2 are free -> evicts residents
        sch.submit(b, 6, seed=0).result(240)
        snap = sch.metrics()
        assert snap["prefix_cache_evictions"] >= 4
        sch.check_kv()
    finally:
        sch.close()


def test_prefix_mixed_soak_with_faults(f32):
    """Mixed warm/cold traffic with scheduler faults injected
    (delays + exceptions at `serving.scheduler.*` points) finishes
    or fails every request WITHOUT leaking a block or a refcount —
    the sweep passes with live residents after the storm."""
    from veles_tpu.serving import InferenceScheduler, SchedulerError
    fw = _tiny_fw("pfx-soak")
    rng = numpy.random.default_rng(3)
    warm_p = rng.integers(0, 12, (16,)).tolist()
    sch = InferenceScheduler(fw, max_slots=3, window=48,
                             block_size=4, kv_blocks=24,
                             prefill_chunk=8, prefix_cache=True,
                             spec=True, spec_k=2, warm_buckets=False,
                             request_timeout=60.0).start()
    try:
        sch.submit(warm_p, 6, seed=0).result(240)   # seed the trie
        faults.load("serving.scheduler.step=delay:0.002x20;"
                    "serving.scheduler.prefill=exception@3x2")
        futs = []
        for i in range(16):
            p = warm_p if i % 2 else \
                rng.integers(0, 12, (rng.integers(4, 20),)).tolist()
            futs.append(sch.submit(p, 6, seed=i,
                                   **(dict(temperature=0.8, top_k=4)
                                      if i % 3 == 0 else {})))
            if i == 7:
                sch.request_preempt()
        done = failed = 0
        for f in futs:
            try:
                f.result(240)
                done += 1
            except SchedulerError:
                failed += 1
        assert done + failed == 16
        assert failed >= 1, "the injected prefill faults never fired"
        assert done >= 8
        faults.clear()
        snap = sch.metrics()
        assert snap["prefix_cache_hits"] >= 1
        sch.check_kv()
        # everything drained: no slot holds blocks, residents only
        assert snap["active_slots"] == 0
    finally:
        sch.close()
    sch.check_kv()
