"""Model-based speculative drafting (``serving/draft.py``, the
engine's hidden-state lane, the scheduler's drafter arbitration and
adaptive draft length): Medusa-head training against the frozen
target, model-drafter streams BIT-IDENTICAL to spec-off (greedy and
seeded, through chunked prefill and preempt→resume), per-drafter
accept-rate accounting, the EMA draft-length controller shrinking
under rejection and growing back, and the memoized trailing-n-gram
index matching the scan proposer exactly."""

import time
import types

import numpy
import pytest

from veles_tpu import faults
from veles_tpu.config import root

pytestmark = pytest.mark.spec


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _run_sched(fw, submits, check=False, **kw):
    from veles_tpu.serving import InferenceScheduler
    sch = InferenceScheduler(fw, max_slots=3, window=64,
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


# -- the memoized trailing-n-gram index ---------------------------------------

def test_ngram_index_matches_scan():
    """The incremental index returns EXACTLY the scan proposer's
    drafts on random append-only streams — same trailing-gram
    priority, same most-recent-occurrence tie-break — and survives a
    context rewrite by rebuilding."""
    from veles_tpu.serving import NgramIndex, NgramProposer
    p = NgramProposer(k=4, max_ngram=3)
    rng = numpy.random.RandomState(7)
    for trial in range(5):
        ctx = []
        ix = NgramIndex(p.max_ngram, p.min_ngram)
        for _ in range(60):
            ctx.append(int(rng.randint(0, 5)))
            assert p.propose(ctx, index=ix) == p.propose(ctx), ctx
    # a SHORTER context than what was indexed triggers the rebuild
    ix = NgramIndex(3, 1)
    long = [1, 2, 3, 1, 2, 3, 1, 2]
    assert p.propose(long, index=ix) == p.propose(long)
    short = [4, 5, 4]
    assert p.propose(short, index=ix) == p.propose(short)


# -- head construction + training against the frozen target -------------------

def test_draft_head_trains(f32, spec_trained_chain,
                           spec_trained_head):
    """``from_chain`` sizes the head off the LM-head weights, the
    teacher-forced loss actually falls, ``propose`` emits [B, k]
    in-vocab ids on any batch size (pow2 padding), and the head
    round-trips through pickle."""
    import pickle
    from veles_tpu.serving import MedusaDraftHead, draft_supported
    fw, _ = spec_trained_chain
    head, losses = spec_trained_head
    assert draft_supported(fw)
    assert head.k == 4 and head.d_model == 16 and head.vocab == 12
    assert losses[-1] < losses[0]
    hid = numpy.random.RandomState(0).randn(3, 16)
    out = head.propose(hid)
    assert out.shape == (3, 4)
    assert out.dtype == numpy.int32
    assert (out >= 0).all() and (out < 12).all()
    twin = pickle.loads(pickle.dumps(head))
    assert (twin.propose(hid) == out).all()
    with pytest.raises(ValueError):
        MedusaDraftHead(0, 8, 8)


def test_draft_head_dim_mismatch_rejected(f32, spec_trained_chain):
    """A head sized for a different model must be refused at
    scheduler construction, not fail mid-decode."""
    from veles_tpu.serving import InferenceScheduler, MedusaDraftHead
    fw, _ = spec_trained_chain
    wrong = MedusaDraftHead(4, 8, 12)     # d_model 8 != chain's 16
    with pytest.raises(ValueError):
        InferenceScheduler(fw, max_slots=2, window=64,
                           warm_buckets=False, spec=True, spec_k=4,
                           drafter="model", draft_head=wrong)


# -- bit-parity through the scheduler -----------------------------------------

def test_model_drafter_parity(f32, spec_trained_chain,
                              spec_trained_head):
    """Acceptance: the MODEL drafter produces streams BIT-IDENTICAL
    to spec-off — greedy and seeded, through chunked prefill —
    while actually drafting (per-drafter accept accounting shows
    model drafts landed).  One-shot (chunk 0) model-drafter parity
    rides test_adaptive_k_shrinks_under_bad_drafts."""
    fw, pattern = spec_trained_chain
    head, _ = spec_trained_head
    prompts = [(pattern * 3)[:18], [2, 9] * 6, [3, 1, 4, 1]]
    submits = [(p, 14, dict(seed=0)) for p in prompts]
    submits += [(p, 10, dict(temperature=0.9, top_k=5,
                             seed=31 + i))
                for i, p in enumerate(prompts)]
    for chunk in (8,):
        base, _ = _run_sched(fw, submits, block_size=4,
                             prefill_chunk=chunk, spec=False)
        mod, snap = _run_sched(fw, submits,
                               block_size=4, prefill_chunk=chunk,
                               spec=True, spec_k=4, drafter="model",
                               draft_head=head, check=True)
        assert mod == base
        by = snap["spec_accept_rate_by_drafter"]
        assert by.get("model") is not None
        assert snap["spec_accepted_tokens"] \
            + snap["spec_rollback_tokens"] \
            == snap["spec_drafted_tokens"]


def test_model_drafter_preempt_resume_parity(f32,
                                             spec_trained_chain,
                                             spec_trained_head):
    """Mid-stream preempt → resume with the model drafter stays
    bit-identical: the carried hidden state is dropped with the
    slot (the n-gram fallback covers the first post-resume step)
    and re-earned from the next verify."""
    from veles_tpu.serving import InferenceScheduler
    fw, pattern = spec_trained_chain
    prompts = [((pattern * 2)[:7], dict(seed=0)),
               ([7, 2] * 4, dict(temperature=0.9, top_k=5,
                                 seed=123))]
    head, _ = spec_trained_head

    def run(preempt):
        sch = InferenceScheduler(fw, max_slots=2, window=64,
                                 block_size=4,
                                 prefill_chunk=4, spec=True,
                                 spec_k=4, drafter="model",
                                 draft_head=head,
                                 warm_buckets=False).start()
        try:
            futs = [sch.submit(p, 20, **kw) for p, kw in prompts]
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
            faults.clear()
            sch.close()

    base, _ = run(preempt=False)
    preempted, snap = run(preempt=True)
    assert snap["preempts"] >= 1, "no preemption actually happened"
    assert preempted == base


# -- the adaptive draft-length controller -------------------------------------

def test_adapt_draft_k_controller(f32, spec_trained_chain):
    """The EMA controller in isolation: rejection walks draft_k
    down the power-of-two ladder to draft_k_min, acceptance walks
    it back to spec_k, and the blend weight makes one good verify
    insufficient to re-grow after sustained rejection."""
    from veles_tpu.serving import InferenceScheduler
    fw, _ = spec_trained_chain
    sch = InferenceScheduler(fw, max_slots=1, window=64,
                             warm_buckets=False, spec=True,
                             spec_k=8, draft_k_min=1)
    req = types.SimpleNamespace(accept_ema={}, draft_k=8)
    for want in (4, 2, 1, 1):          # full rejection: 8→4→2→1⌊
        sch._adapt_draft_k(req, req.draft_k, 0, "model")
        assert req.draft_k == want
    # one perfect verify blends to 0.5 — NOT above draft_grow
    sch._adapt_draft_k(req, 1, 1, "model")
    assert req.draft_k == 1
    for _ in range(6):                 # sustained acceptance re-grows
        sch._adapt_draft_k(req, req.draft_k, req.draft_k, "model")
    assert req.draft_k == 8
    # per-drafter EMAs are independent
    assert "ngram" not in req.accept_ema
    snap = sch.stats.snapshot()
    assert snap["spec_draft_k_min_seen"] == 1
    assert snap["spec_draft_k_last"] == 8


def test_adaptive_k_shrinks_under_bad_drafts(f32,
                                             spec_trained_chain):
    """An UNTRAINED head (zero un-embedding → it always drafts
    token 0) rejects at verify, so the controller must shrink the
    slot's draft length below spec_k and the model drafter's accept
    rate must read low — while the stream still matches spec-off."""
    from veles_tpu.serving import MedusaDraftHead
    fw, pattern = spec_trained_chain
    garbage = MedusaDraftHead.from_chain(fw, 4, seed=3)
    submits = [((pattern * 2)[:10], 14, dict(seed=0))]
    base, _ = _run_sched(fw, submits, block_size=4,
                         prefill_chunk=0, spec=False)
    mod, snap = _run_sched(fw, submits, block_size=4,
                           prefill_chunk=0, spec=True, spec_k=4,
                           drafter="model", draft_head=garbage)
    assert mod == base
    assert snap["spec_draft_k_min_seen"] < 4
    rate = snap["spec_accept_rate_by_drafter"].get("model")
    assert rate is not None and rate < 0.5


# -- drafter knob fallbacks ---------------------------------------------------

def test_model_drafter_requires_head(f32, spec_trained_chain):
    """drafter="model" without a head degrades to the n-gram
    proposer (documented fallback) instead of failing; an unknown
    drafter name is rejected loudly."""
    fw, pattern = spec_trained_chain
    submits = [((pattern * 2)[:8], 8, dict(seed=0))]
    outs, snap = _run_sched(fw, submits, block_size=4,
                            prefill_chunk=0, spec=True, spec_k=4,
                            drafter="model")
    assert len(outs[0]) == 16
    assert "model" not in snap["spec_accept_rate_by_drafter"]
    with pytest.raises(ValueError):
        _run_sched(fw, submits, spec=True, drafter="banana")
