"""The decode loop's launch-ahead (``scheduler._step_paged``): step N+1
is launched from step N's device-resident tokens before the host has
read them, and N is landed one launch later.  On the CPU, tiny chains,
counts and identities only, never a time limit:

- every stream, greedy or seeded, equals the SERIAL loop's token for
  token (the same scheduler made to land each step right after its
  launch, which is the loop as it was before), and ``generate()``;
- a row whose request left its slot while its step was in flight (stop
  token, cancel, deadline) is discarded: nothing is emitted after the
  stop token, ``rows_discarded`` says 1, and the slot's next tenant
  decodes as on a fresh server, on the LFM2 chain too, whose per-slot
  conv state the discarded row wrote;
- a preempt lands the flight first and the resumed stream is whole;
- a step that fails at its launch or at its landing fails the riders
  of both steps, and the next request is served;
- the counters: ``steps_ahead`` > 0 on the plain path, exactly 0 under
  speculation, ``steps_total`` the number of launches once settled,
  ``steps_greedy`` those of them that carried no sampled row.
"""

import contextlib
import time

import jax
import numpy
import pytest

from veles_tpu import faults
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.serving import scheduler as sched_mod
from veles_tpu.serving.scheduler import (
    DeadlineExceededError, InferenceScheduler, RequestCancelledError,
    SchedulerError)

pytestmark = pytest.mark.serving

WINDOW, BLOCK, VOCAB = 64, 4, 12


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
def gpt():
    from veles_tpu import prng
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    prng.get("default").seed(32)
    spec = [{"type": "embedding", "vocab": VOCAB, "dim": 16}]
    spec += [{"type": "transformer_block", "heads": 2,
              "causal": True}] * 2
    spec += [{"type": "token_logits", "vocab": VOCAB}]
    with _float32():
        fw = make_forwards(
            AcceleratedWorkflow(None, name="decode-ahead"),
            Array(numpy.zeros((2, WINDOW), numpy.int32)), spec)
        for u in fw:
            u.initialize(device=Device(backend="numpy"))
    return fw


@pytest.fixture(scope="module")
def lfm2():
    import test_lfm2
    with _float32():
        return test_lfm2._chain("lfm2-decode-ahead")


@pytest.fixture
def chain(request, gpt, lfm2):
    return {"gpt": gpt, "lfm2": lfm2}[request.param]


def _scheduler(fw, serial=False, **kwargs):
    """A started scheduler; ``serial``: every step is landed right
    after its launch, so nothing is ever in flight at a launch: the
    loop as it was before launch-ahead, the reference of this file."""
    kw = dict(max_slots=2, window=WINDOW, block_size=BLOCK,
              prefill_chunk=8, warm_buckets=False, spec=False,
              prefix_cache=False)
    kw.update(kwargs)
    sch = InferenceScheduler(fw, **kw)
    if serial:
        ahead = sch._step_paged

        def step_then_land(cache, active):
            ahead(cache, active)
            sch._land(cache)
        sch._step_paged = step_then_land
    return sch.start()


def _settle(sch):
    """Wait until the loop has flushed its last pass and parked."""
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if sch._phases is not None and sch._phases.current == "parked":
            assert sch._flight is None   # never parks on a flight
            return
        time.sleep(0.01)
    raise AssertionError("the loop did not park")


def _after_launch(monkeypatch, k, act):
    """Call ``act()`` on the loop thread right after the ``k``-th
    decode launch from now: that step is in flight then.  Returns the
    list the launches are counted in."""
    real, calls = sched_mod.paged_decode_step, []

    def hooked(*args, **kwargs):
        out = real(*args, **kwargs)
        calls.append(1)
        if len(calls) == k:
            act()
        return out
    monkeypatch.setattr(sched_mod, "paged_decode_step", hooked)
    return calls


BURST = [([3, 1, 4], 9), ([5], 14), ([7, 2, 9, 1, 5, 9, 2, 6, 5, 3], 6),
         ([2, 2], 17), ([11, 3, 5, 8], 11), ([1, 1, 2, 3, 5, 8], 3),
         ([4], 2)]


def _run_burst(fw, sampled, serial, **kwargs):
    sch = _scheduler(fw, serial=serial, **kwargs)
    try:
        futs = [sch.submit(p, n, stream=True, seed=7 + i,
                           **(dict(temperature=0.9, top_k=5)
                              if sampled else {}))
                for i, (p, n) in enumerate(BURST)]
        streamed = [list(s) for s in futs]
        final = [s.future.result(240) for s in futs]
        _settle(sch)
        sch.check_kv()
        return streamed, final, sch.metrics(), sch.stats
    finally:
        sch.close()


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded"])
@pytest.mark.parametrize("chain", ["gpt", "lfm2"], indirect=True)
def test_burst_streams_equal_the_serial_loops(chain, f32, sampled):
    """Seven requests of mixed lengths over two slots: joins and ends
    every few steps, so the loop keeps falling out of launch-ahead and
    back into it.  Streamed and final tokens equal the serial loop's,
    and the greedy ones ``generate()``'s."""
    streamed, final, snap, stats = _run_burst(chain, sampled, False)
    s_streamed, s_final, s_snap, s_stats = _run_burst(
        chain, sampled, True)
    assert final == s_final and streamed == s_streamed
    for (prompt, steps), out, toks in zip(BURST, final, streamed):
        assert out == prompt + toks and len(toks) == steps
    # a token a row-step either way (which rows share a launch hangs
    # on when each request joined); launches ran ahead here, none did
    # in the serial loop
    launches = sum(n - 1 for _, n in BURST)
    assert stats.steps_launched <= launches
    assert 0 < stats.steps_ahead < stats.steps_launched
    assert snap["steps_ahead_share"] == round(
        stats.steps_ahead / stats.steps_launched, 4)
    assert s_stats.steps_ahead == 0 and s_snap["steps_ahead_share"] == 0
    assert snap["rows_discarded"] == s_snap["rows_discarded"] == 0
    assert snap["slot_busy_steps"] == s_snap["slot_busy_steps"] \
        == launches


def test_greedy_streams_equal_generate(gpt, f32):
    from veles_tpu.models.generate import generate
    _, final, _, _ = _run_burst(gpt, False, False)
    for (prompt, steps), out in zip(BURST, final):
        want = numpy.asarray(generate(
            gpt, numpy.asarray([prompt], numpy.int32), steps,
            kv_cache=True))[0].tolist()
        assert out == want


def _stream_with_a_late_first(fw, steps=16):
    """(prompt, seed, generated, k): a seeded request whose ``k``-th
    generated token (2 <= k <= steps - 4) occurs there first: a stop
    token that is met mid-stream, with steps to spare."""
    sch = _scheduler(fw, serial=True, max_slots=1)
    try:
        for seed in range(40):
            prompt = [1 + seed % 7, 3, 2]
            out = sch.submit(prompt, steps, temperature=0.9, top_k=6,
                             seed=seed).result(240)[len(prompt):]
            for k in range(2, steps - 3):
                if out[k] not in out[:k]:
                    return prompt, seed, out, k
    finally:
        sch.close()
    raise AssertionError("no stream with a late first occurrence")


@pytest.mark.parametrize("chain", ["gpt", "lfm2"], indirect=True)
def test_stop_token_met_while_the_next_step_is_in_flight(chain, f32):
    """The step after the stop token was launched before the host saw
    the stop token.  Its row is discarded: nothing is emitted after
    the stop token, the count says 1, and the next tenant of the ONE
    slot (its blocks, and on the LFM2 chain its conv state, were
    written by the discarded row) decodes as on a fresh server."""
    prompt, seed, out, k = _stream_with_a_late_first(chain)
    sampler = dict(temperature=0.9, top_k=6)
    tenant = ([2, 7, 1, 8, 2, 8], 12, dict(seed=5, **sampler))
    sch = _scheduler(chain, serial=True, max_slots=1)
    try:
        want_next = sch.submit(tenant[0], tenant[1],
                               **tenant[2]).result(240)
    finally:
        sch.close()
    sch = _scheduler(chain, max_slots=1)
    try:
        first = sch.submit(prompt, 16, stop_token=out[k], stream=True,
                           seed=seed, **sampler)
        second = sch.submit(tenant[0], tenant[1], **tenant[2])
        assert list(first) == out[:k + 1]
        assert first.future.result(240) == prompt + out[:k + 1]
        assert second.result(240) == want_next
        _settle(sch)
        snap = sch.metrics()
        assert snap["rows_discarded"] == 1
        # the discarded row is no token and no busy slot-step
        assert snap["slot_busy_steps"] == k + (tenant[1] - 1)
        assert sch.stats.steps_launched == snap["slot_busy_steps"] + 1
        sch.check_kv()
    finally:
        sch.close()


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_request_leaves_with_its_row_in_flight(gpt, f32, monkeypatch,
                                               how):
    """Two requests share the batch; one is cancelled (or expires)
    right after a launch.  The reap drops it with its row in flight,
    the landing discards that row, and the other stream is whole."""
    keep, gone = ([3, 1, 4, 1], 14), ([5, 9, 2], 14)
    sch = _scheduler(gpt, serial=True)
    try:
        want = sch.submit(keep[0], keep[1], seed=1).result(240)
    finally:
        sch.close()
    sch = _scheduler(gpt)
    held = {}

    def leave():
        req = [r for r in sch._active.values()
               if r.prompt == gone[0]][0]
        held["tokens"] = len(req.generated)
        if how == "cancel":
            req.cancelled = True
        else:
            req.deadline = time.monotonic() - 1.0
    try:
        _after_launch(monkeypatch, 5, leave)
        stays = sch.submit(keep[0], keep[1], seed=1)
        leaves = sch.submit(gone[0], gone[1], seed=2)
        error = RequestCancelledError if how == "cancel" \
            else DeadlineExceededError
        with pytest.raises(error):
            leaves.result(240)
        assert stays.result(240) == want
        _settle(sch)
        snap = sch.metrics()
        assert snap["rows_discarded"] == 1
        assert snap["requests_cancelled" if how == "cancel"
                    else "requests_expired"] == 1
        # what it had when it left: the token in flight never reached it
        assert 0 < held["tokens"] < gone[1]
        sch.check_kv()
    finally:
        sch.close()


def test_preempt_lands_the_flight_and_the_resume_is_whole(
        gpt, f32, monkeypatch):
    reqs = [([3, 1, 4, 1, 5], 15), ([9, 2, 6], 15)]
    sch = _scheduler(gpt, serial=True)
    try:
        want = [sch.submit(p, n, seed=i).result(240)
                for i, (p, n) in enumerate(reqs)]
    finally:
        sch.close()
    sch = _scheduler(gpt)
    try:
        _after_launch(monkeypatch, 6, lambda: sch.request_preempt(1))
        futs = [sch.submit(p, n, stream=True, seed=i)
                for i, (p, n) in enumerate(reqs)]
        assert [p + list(s) for (p, _), s in zip(reqs, futs)] == want
        assert [s.future.result(240) for s in futs] == want
        _settle(sch)
        snap = sch.metrics()
        assert snap["preempts"] == 1 and snap["preempt_resumes"] == 1
        # landed before the eviction: no token lost, none drawn twice
        assert snap["rows_discarded"] == 0
        sch.check_kv()
    finally:
        sch.close()


@pytest.mark.parametrize("where", ["launch", "landing"])
def test_failed_step_fails_the_riders_of_both_steps(
        gpt, f32, monkeypatch, where):
    """``launch``: the injected fault fires with a step in flight.
    ``landing``: the device loses step N (its tokens and the pools it
    returned, which N+1 has consumed by then), seen when N is read.
    Either way both riders fail, nobody else does, and the next
    request gets the tokens of a fresh server."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
    sch = _scheduler(gpt, serial=True)
    try:
        want = sch.submit(prompt, 6, seed=1).result(240)
    finally:
        sch.close()
    sch = _scheduler(gpt)
    try:
        assert sch.submit(prompt, 6, seed=1).result(240) == want
        _settle(sch)
        if where == "launch":
            faults.inject("serving.scheduler.step", "exception",
                          after=4, times=1)
            match = "injected"
        else:
            real, calls = sched_mod.paged_decode_step, []

            def losing(forwards, cache, toks, *args, **kwargs):
                out = real(forwards, cache, toks, *args, **kwargs)
                calls.append(1)
                if len(calls) == 5:
                    assert isinstance(toks, jax.Array)   # ran ahead
                    toks.delete()
                    for leaf in jax.tree.leaves(cache.pools):
                        leaf.delete()
                return out
            monkeypatch.setattr(sched_mod, "paged_decode_step", losing)
            match = "KV pools were lost.*deleted"
        doomed = [sch.submit(prompt[:4 + i], 20, seed=i)
                  for i in range(2)]
        for f in doomed:
            with pytest.raises(SchedulerError, match=match):
                f.result(240)
        faults.clear()
        _settle(sch)
        assert not sch.cache_.pools_lost()
        assert sch.cache_.free_blocks == sch.cache_.capacity_blocks
        sch.check_kv()
        assert sch.submit(prompt, 6, seed=1).result(240) == want
        assert sch.metrics()["requests_completed"] == 2
    finally:
        faults.clear()
        sch.close()


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_steps_run_ahead_on_the_plain_path_only(gpt, f32, monkeypatch,
                                                spec):
    """``steps_total`` is the number of launches once the loop has
    settled, whichever path launched them; launches run ahead on the
    plain path and never under speculation, whose drafts read the
    host's tokens."""
    launches = []
    for name in ("paged_decode_step", "verify_step_paged"):
        real = getattr(sched_mod, name)

        def counting(*args, _real=real, **kwargs):
            launches.append(1)
            return _real(*args, **kwargs)
        monkeypatch.setattr(sched_mod, name, counting)
    sch = _scheduler(gpt, spec=spec, spec_k=3)
    try:
        # repeating text, so that the n-gram drafter drafts
        futs = [sch.submit([3, 1, 4, 3, 1, 4, 3, 1], 13, seed=i)
                for i in range(3)]
        for f in futs:
            f.result(240)
        _settle(sch)
        stats = sch.stats
        assert stats.steps_launched == len(launches) > 0
        if spec:
            assert stats.steps_ahead == 0
            assert sch.metrics()["steps_ahead_share"] == 0
        else:
            assert 2 * 12 <= stats.steps_launched <= 3 * 12
            # all but the step after each of three joins and three ends
            assert stats.steps_ahead >= stats.steps_launched - 6
    finally:
        sch.close()


def test_counters_reach_the_registry_with_the_pass(gpt, f32):
    from veles_tpu.telemetry import metrics

    def read():
        return {name: metrics.counter(
            "veles_serving_%s_total" % name).value
            for name in ("steps", "steps_ahead", "rows_discarded")}
    before = read()
    sch = _scheduler(gpt, max_slots=1)
    try:
        sch.submit([3, 1, 4], 10, seed=0).result(240)
        _settle(sch)
        got = {k: v - before[k] for k, v in read().items()}
        # nine launches; the first follows no step
        assert got == {"steps": 9, "steps_ahead": 8,
                       "rows_discarded": 0}
        assert sch.metrics()["steps_ahead_share"] == round(8 / 9, 4)
    finally:
        sch.close()
    text = metrics.render_prometheus()
    assert "veles_serving_steps_ahead_total" in text
    assert "veles_serving_rows_discarded_total" in text


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("traffic", ["greedy", "mixed"])
def test_greedy_steps_are_the_launches_with_no_sampled_row(
        gpt, f32, monkeypatch, traffic, spec):
    """``veles_serving_steps_greedy_total`` counts the launches whose
    every packed row is greedy (their sampler takes the argmax alone):
    all of them under greedy traffic, and short of ``steps_total`` by
    exactly the launches that carried a sampled row, on the decode
    path and the verify path alike."""
    from veles_tpu.telemetry import metrics
    carried = []
    # where each entry point takes the packed temperatures
    for name, at in (("paged_decode_step", 5), ("verify_step_paged", 6)):
        real = getattr(sched_mod, name)

        def counting(*args, _real=real, _at=at, **kwargs):
            carried.append(bool((args[_at] > 0).any()))
            return _real(*args, **kwargs)
        monkeypatch.setattr(sched_mod, name, counting)

    def read():
        return {name: metrics.counter(
            "veles_serving_%s_total" % name).value
            for name in ("steps", "steps_greedy")}
    before = read()
    sch = _scheduler(gpt, spec=spec, spec_k=3)
    try:
        sampled = dict(temperature=0.9, top_k=5)
        futs = [sch.submit([3, 1, 4, 3, 1, 4, 3, 1], n, seed=i,
                           **(sampled if traffic == "mixed" and i == 1
                              else {}))
                for i, n in enumerate((13, 6, 9))]
        for f in futs:
            f.result(240)
        _settle(sch)
        got = {k: v - before[k] for k, v in read().items()}
        stats = sch.stats
        assert got["steps"] == stats.steps_launched == len(carried) > 0
        assert got["steps_greedy"] == stats.steps_greedy \
            == carried.count(False)
        if traffic == "greedy":
            assert got["steps_greedy"] == got["steps"]
        else:
            assert 0 < carried.count(True) <= 6
        assert sch.metrics()["greedy_steps_share"] == round(
            stats.steps_greedy / stats.steps_launched, 4)
    finally:
        sch.close()
    assert "veles_serving_steps_greedy_total" in \
        metrics.render_prometheus()


@pytest.mark.parametrize("weights", ["committed", "uncommitted"])
def test_a_step_launched_ahead_compiles_nothing_new(gpt, f32, weights,
                                                    monkeypatch):
    """The warm-up ladder launches every bucket from host arrays; a
    step launched from the device array of the step before it must
    hit the same executables (``compiles_in_window.serve`` stays 0 on
    the chip for it).  The serial loop first: it fills the executable
    caches the two schedulers share with everything host launches need
    (the process's very first call, on pools nothing has returned yet,
    is an entry of its own).  A server's weights are the units' own
    COMMITTED buffers (float32) or UNCOMMITTED casts and hand-overs
    (the benchmark's cells): host tokens are placed as the step leaves
    its own either way, so the pools stay committed exactly where the
    weights are and no other program that takes them (the block
    insert) meets a second signature."""
    import jax.numpy as jnp
    from veles_tpu.serving.weights import ServingWeights
    from veles_tpu.telemetry import compile_summary

    class Uncommitted(ServingWeights):
        def __init__(self, forwards, tp=None):
            super(Uncommitted, self).__init__(forwards, tp=tp)
            self.params = jax.tree.map(
                lambda a: jnp.asarray(numpy.asarray(a)), self.params)

    def compiles():
        return [compile_summary().get(name, {}).get("compiles", 0)
                for name in ("serving.paged_step",
                             "serving.kv_insert_blocks")]
    if weights == "uncommitted":
        monkeypatch.setattr(sched_mod, "ServingWeights", Uncommitted)
    for serial in (True, False):
        was = compiles()
        sch = _scheduler(gpt, serial=serial, window=16,
                         warm_buckets=True)
        try:
            futs = [sch.submit([3, 1, 4, 1][:1 + i], 9, seed=i)
                    for i in range(3)]
            for f in futs:
                f.result(240)
            _settle(sch)
            ahead = sch.stats.steps_ahead
            held = {leaf.committed for tree in
                    (sch.weights_.params, sch.cache_.pools)
                    for leaf in jax.tree.leaves(tree)}
        finally:
            sch.close()
        assert held == {weights == "committed"}
    assert ahead > 0 and compiles() == was and min(was) > 0
