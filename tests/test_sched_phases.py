"""The scheduler loop's phase account (``scheduler._LoopPhases``) and
the device-side names: counts and identities on the CPU, never a time
limit.  What a phase COSTS is read on the chip, by the per-layer
metrics of ``benchmark/metrics/sched_*.json`` and beside them.

- the nine ``veles_serving_loop_<phase>_seconds_total`` partition the
  loop thread's time: they sum to ``loop_seconds`` + ``loop_parked``
  and to the wall time between two scrapes;
- the seven parts lie inside their phases, and the dry account charges
  every stretch between the switch that saw the tail ready and the next
  dispatch to its phase, ``parked`` never;
- ``veles_serving_steps_total`` is the number of decode launches,
  ``first_tokens`` the number of requests served, and both repeat;
- one flush a pass reaches the registry, and the annotations do
  nothing without a profiler session;
- the benchmark's own ``/metrics`` parser sees every counter under the
  name its metric file gives (the twenty-one ``ratio`` files of this
  account);
- the jitted serving entry points and the attention's gather + GEMM
  carry their names into the lowered text.
"""

import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.serving import scheduler as scheduler_mod
from veles_tpu.serving.metrics import ServingMetrics
from veles_tpu.serving.scheduler import (
    PARTS, PHASES, InferenceScheduler, _LoopPhases)
from veles_tpu.telemetry import metrics

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPT = [3, 1, 4, 3, 1, 4]
PHASE_COUNTERS = ["veles_serving_loop_%s_seconds_total" % p
                  for p in PHASES]
#: the per-layer metrics this account feeds (BENCHMARK.json, all on
#: the benchmark's ``ratio`` reader)
RATIO_METRICS = (
    "sched_step_share_pct", "sched_prefill_share_pct",
    "sched_admit_ms_per_pass", "sched_pack_ms_per_step",
    "sched_emit_ms_per_step", "sched_observe_ms_per_step",
    "decode_step_ms", "decode_step_after_prefill_ms",
    "queue_wait_ms_mean", "prefill_wait_ms_mean")
#: ... and those of its parts and of the dry account (PR 37)
PART_METRICS = (
    "device_dry_pct", "dry_admit_pct", "dry_prefill_pct", "dry_step_pct",
    "sched_stage_ms_per_admission", "sched_admit_queue_ms_per_pass",
    "sched_reap_ms_per_pass", "step_resolve_ms", "step_launch_ms",
    "step_land_wait_ms", "prefill_first_wait_ms")


def counter_of(stretch):
    return "veles_serving_loop_%s_seconds_total" % stretch.replace(".", "_")


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _tiny_fw(name, window=64, vocab=12, dim=16, heads=2):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name=name)
    fw = make_forwards(
        wf, Array(numpy.zeros((2, window), numpy.int32)), [
            {"type": "embedding", "vocab": vocab, "dim": dim},
            {"type": "transformer_block", "heads": heads,
             "causal": True},
            {"type": "token_logits", "vocab": vocab}])
    dev = Device(backend="numpy")
    for u in fw:
        u.initialize(device=dev)
    return fw


def _scheduler(name, **kwargs):
    return InferenceScheduler(
        _tiny_fw(name), max_slots=2, window=64,
        block_size=4, prefill_chunk=4, warm_buckets=False, spec=False,
        **kwargs)


def scrape():
    """The benchmark driver's rule (``serve_closed.counters``) over the
    registry's exposition: ``veles_serving_*`` lines holding ``_total``,
    summed over their label sets."""
    out = {}
    for line in metrics.render_prometheus().splitlines():
        if not line.startswith("veles_serving_") or "_total" not in line:
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{")[0]
        out[name] = out.get(name, 0.0) + float(value)
    return out


def delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def settle(sch):
    """Wait until the loop has flushed its last pass and parked."""
    passes = -1.0
    while True:
        time.sleep(0.05)
        now = scrape().get("veles_serving_loop_passes_total", 0.0)
        if now == passes and not sch._working:
            return
        passes = now


def read_idle(sch):
    """(wall clock, counters as they WILL stand once the loop's next
    flush has added the wait it is in) of an idle scheduler."""
    settle(sch)
    account = sch._phases
    now, waiting = time.perf_counter(), account.elapsed()
    assert account.current == "parked"
    out = scrape()
    for phase, took in account.seconds.items():
        out["veles_serving_loop_%s_seconds_total" % phase] += took
    out["veles_serving_loop_parked_seconds_total"] += waiting
    return now, out


def test_phases_partition_the_loop_threads_time(f32):
    sch = _scheduler("phases-partition").start()
    try:
        sch.submit(PROMPT, 8, seed=0).result(240)   # compile, settle
        t0, before = read_idle(sch)
        for rep in range(6):
            futs = [sch.submit(PROMPT, 24, seed=i) for i in range(4)]
            for f in futs:
                f.result(240)
            time.sleep(0.02 * rep)                  # some parked time
        t1, after = read_idle(sch)
        got, wall = delta(after, before), t1 - t0
    finally:
        sch.close()
    phases = sum(got[name] for name in PHASE_COUNTERS)
    assert all(got[name] >= 0 for name in PHASE_COUNTERS)
    loop, parked = (got["veles_serving_loop_seconds_total"],
                    got["veles_serving_loop_parked_seconds_total"])
    assert loop > 0 and parked > 0
    # (the wait an idle loop is in reaches loop_parked at its next
    # flush: read_idle has added it to the nine, not to loop_seconds)
    assert phases == pytest.approx(loop + parked, rel=0.01)
    # exhaustive: no instant of the loop thread is charged to nothing
    assert phases == pytest.approx(wall, rel=0.02)
    for name in ("step", "pack", "emit", "observe", "prefill", "admit"):
        assert got["veles_serving_loop_%s_seconds_total" % name] > 0
    assert got["veles_serving_loop_draft_seconds_total"] == 0
    assert got["veles_serving_loop_step_after_prefill_seconds_total"] \
        <= got["veles_serving_loop_step_seconds_total"]


class _Clock(object):
    """``time`` for ``_LoopPhases`` alone: the test moves it."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


class _Tail(object):
    """A dispatch's result whose ``is_ready()`` follows a script."""

    def __init__(self, *script):
        self.script, self.polls = list(script), 0

    def is_deleted(self):
        return False

    def is_ready(self):
        self.polls += 1
        return self.script.pop(0)


def test_the_dry_spell_runs_from_the_switch_that_saw_it_to_the_dispatch(
        monkeypatch):
    """The account alone, under an injected clock (powers of two, so
    every sum is exact) and a scripted tail."""
    clock = _Clock()
    monkeypatch.setattr(scheduler_mod, "time", clock)
    account = _LoopPhases()

    def run(seconds, stretch):
        clock.now += seconds
        account.switch(stretch)
    tail = _Tail(False, False, True)
    account.dispatched(tail)              # in admit, nothing dry yet
    run(1, "pack")                        # admit 1; the tail: not ready
    run(2, "step.resolve")                # pack 2; not ready
    run(4, "step.call")                   # resolve 4; READY: dry from here
    assert account.is_dry and not any(account.dry.values())
    run(8, "emit")                        # call 8: dry
    run(16, "parked")                     # emit 16: dry
    run(32, "admit.queue")                # parked 32: never charged
    run(64, "prefill")                    # admit.queue 64: dry
    clock.now += 128
    later = _Tail(False)
    account.dispatched(later)             # 128 of this prefill: dry
    assert not account.is_dry
    run(256, "prefill.first")             # the rest of it: not
    run(512, "observe")
    assert tail.polls == 3 and later.polls == 2   # one a switch, no more
    assert account.dry == dict(
        dict.fromkeys(PHASES[1:], 0.0), step=8.0, emit=16.0, admit=64.0,
        prefill=128.0)
    assert account.seconds == dict(
        dict.fromkeys(PHASES, 0.0), admit=65.0, pack=2.0, step=12.0,
        emit=16.0, parked=32.0, prefill=128.0 + 256.0 + 512.0)
    assert account.parts == dict(
        dict.fromkeys(PARTS, 0.0), **{
            "step.resolve": 4.0, "step.call": 8.0, "admit.queue": 64.0,
            "prefill.first": 512.0})
    # a launch once, whether or not the engine said ``calling``
    assert account.steps == 1
    before = scrape()
    stats = ServingMetrics()
    stats.record_loop_pass(*account.drain(), passes=0)
    got = delta(scrape(), before)
    by_phase = [got["veles_serving_loop_dry_%s_seconds_total" % phase]
                for phase in PHASES[1:]]
    assert sum(by_phase) == got["veles_serving_loop_dry_seconds_total"] \
        == 216.0
    assert "veles_serving_loop_dry_parked_seconds_total" not in got
    assert got["veles_serving_loop_seconds_total"] == 991.0
    assert got["veles_serving_loop_step_launch_seconds_total"] == 12.0
    assert got[counter_of("step.resolve")] == 4.0
    assert stats.snapshot()["dry_share"] == round(216.0 / 991.0, 4)
    assert not any(account.dry.values()) and not any(
        account.parts.values())


def test_a_lap_polls_inside_a_long_stretch(monkeypatch):
    """The staging rows' stretch: the device runs dry inside it, and a
    lap (the stretch ends, another of its name begins) sees it."""
    clock = _Clock()
    monkeypatch.setattr(scheduler_mod, "time", clock)
    account = _LoopPhases()
    tail = _Tail(False, False, True)
    account.dispatched(tail)
    account.switch("admit.stage")         # not ready
    clock.now += 4
    account.lap()                         # not ready
    clock.now += 8
    account.lap()                         # READY: dry from this lap on
    clock.now += 16
    account.lap()                         # 16: dry
    clock.now += 32
    account.dispatched(_Tail())           # 32 of the open stretch: dry
    clock.now += 64
    account.switch("admit")
    assert tail.polls == 3 and account.current == "admit"
    assert account.dry["admit"] == 48.0 == sum(account.dry.values())
    assert account.parts["admit.stage"] == 124.0 \
        == account.seconds["admit"]
    assert account.steps == 0


def test_a_deleted_tail_never_raises_out_of_a_switch(f32):
    """``is_ready()`` on a buffer a later call donated raises: the
    switch forgets such a tail and starts no spell; the loop lives."""
    donating = jax.jit(lambda x: x + 1, donate_argnums=(0,))

    def donated():
        gone = jnp.zeros((4,)) + 1
        donating(gone)
        with pytest.raises(RuntimeError, match="deleted"):
            gone.is_ready()
        return gone
    account = _LoopPhases()
    account.dispatched(donated())
    account.switch("pack")
    assert account.tail is None and not account.is_dry
    gone = jnp.zeros((4,)) + 1
    account.dispatched(gone)
    gone.delete()                   # deleted by hand: no poll at all
    account.switch("emit")
    assert account.tail is None and not account.is_dry
    account.close()
    # ... and in a running loop: the next pass meets the deleted tail
    sch = _scheduler("phases-deleted-tail").start()
    try:
        want = sch.submit(PROMPT, 8, seed=3).result(240)
        settle(sch)
        gone = donated()
        sch._phases.tail, sch._phases.is_dry = gone, False
        assert sch.submit(PROMPT, 8, seed=3).result(240) == want
        settle(sch)
        assert sch._phases.tail is not gone
    finally:
        sch.close()


def test_parts_lie_inside_their_phases_and_admissions_are_exact(f32):
    sch = _scheduler("phases-parts").start()
    try:
        sch.submit(PROMPT, 8, seed=0).result(240)   # compile, settle
        settle(sch)
        before = scrape()
        # longer than the chunk of 4: staging rows, chunks, first tokens
        futs = [sch.submit(PROMPT + [i], 12, seed=i) for i in range(5)]
        for f in futs:
            f.result(240)
        settle(sch)
        got = delta(scrape(), before)
        snapshot = sch.metrics()
    finally:
        sch.close()
    assert got["veles_serving_loop_admissions_total"] == 5 \
        == got["veles_serving_first_tokens_total"]
    for part in PARTS:
        phase = part.partition(".")[0]
        assert 0 < got[counter_of(part)] <= got[counter_of(phase)], part
    for phase in ("admit", "prefill", "step"):
        inside = sum(got[counter_of(p)] for p in PARTS
                     if p.startswith(phase + "."))
        assert inside <= got[counter_of(phase)] * (1 + 1e-9), phase
    # every instant of a step is its launch or its landing
    assert got[counter_of("step.resolve")] + got[counter_of("step.call")] \
        + got[counter_of("step.land")] == pytest.approx(
            got[counter_of("step")], rel=1e-6)
    assert got["veles_serving_loop_step_launch_seconds_total"] \
        == pytest.approx(got[counter_of("step.resolve")]
                         + got[counter_of("step.call")], rel=1e-6)
    # the dry account: by phase it sums to the whole, inside the loop's
    # seconds, and each phase's inside that phase's
    dry = {phase: got["veles_serving_loop_dry_%s_seconds_total" % phase]
           for phase in PHASES[1:]}
    assert sum(dry.values()) == pytest.approx(
        got["veles_serving_loop_dry_seconds_total"], rel=1e-6)
    assert 0 < got["veles_serving_loop_dry_seconds_total"] \
        <= got["veles_serving_loop_seconds_total"]
    for phase, took in dry.items():
        assert 0 <= took <= got[counter_of(phase)] * (1 + 1e-9), phase
    assert 0 < snapshot["dry_share"] <= 1


def test_step_and_request_counts_are_exact_and_repeat(f32, monkeypatch):
    launches = []
    real = scheduler_mod.paged_decode_step

    def counting(*args, **kwargs):
        launches.append(1)
        return real(*args, **kwargs)
    monkeypatch.setattr(scheduler_mod, "paged_decode_step", counting)
    sch = _scheduler("phases-counts").start()

    def soak(concurrent):
        settle(sch)
        before, seen = scrape(), len(launches)
        if concurrent:
            futs = [sch.submit(PROMPT, 12, seed=i) for i in range(4)]
            for f in futs:
                f.result(240)
        else:                       # one at a time: nothing races
            for i, steps in enumerate((12, 5, 9)):
                sch.submit(PROMPT + [i], steps, seed=i).result(240)
        settle(sch)
        return delta(scrape(), before), len(launches) - seen
    try:
        first, launched = soak(False)
        assert first["veles_serving_steps_total"] == launched \
            == (12 - 1) + (5 - 1) + (9 - 1)
        assert first["veles_serving_first_tokens_total"] == 3
        # each request's last prompt chunk lands in the pass of its
        # first decode step
        assert first["veles_serving_steps_after_prefill_total"] == 3
        again, launched_again = soak(False)
        counts = ("veles_serving_steps_total",
                  "veles_serving_steps_after_prefill_total",
                  "veles_serving_first_tokens_total",
                  "veles_serving_slot_busy_steps_total")
        assert launched_again == launched
        assert [again[c] for c in counts] == [first[c] for c in counts]
        mixed, launched = soak(True)
        assert mixed["veles_serving_steps_total"] == launched
        assert mixed["veles_serving_first_tokens_total"] == 4
        assert 0 < mixed["veles_serving_steps_after_prefill_total"] \
            <= mixed["veles_serving_steps_total"]
        assert mixed["veles_serving_loop_passes_total"] >= launched
        assert mixed["veles_serving_queue_wait_seconds_total"] > 0
        assert mixed["veles_serving_prefill_wait_seconds_total"] > 0
    finally:
        sch.close()


def test_one_flush_a_pass_and_silent_annotations(f32):
    """No profiler session is active: the ``veles.sched.*`` annotations
    must do nothing (the soak finishing IS that proof), and the account
    reaches the registry once a pass, not once a phase."""
    sch = _scheduler("phases-flush")
    calls = {"pass": 0, "flush": 0, "phases": 0}
    real_pass, real_flush = sch._pass, sch.stats.record_loop_pass

    def counted_pass(cache):
        calls["pass"] += 1
        return real_pass(cache)

    def counted_flush(*args, **kwargs):
        calls["flush"] += 1
        return real_flush(*args, **kwargs)
    sch._pass, sch.stats.record_loop_pass = counted_pass, counted_flush
    sch.start()
    try:
        before = scrape()
        futs = [sch.submit(PROMPT, 16, seed=i) for i in range(4)]
        for f in futs:
            f.result(240)
        settle(sch)
        got = delta(scrape(), before)
        # every pass that found work flushed once; the pass that is
        # parked now has not
        assert got["veles_serving_loop_passes_total"] \
            == calls["flush"] == calls["pass"] - 1 > 0
        account = sch._phases
        real_switch = account.switch

        def counted_switch(name):
            calls["phases"] += 1
            return real_switch(name)
        account.switch = counted_switch
        flushed = calls["flush"]
        sch.submit(PROMPT, 16, seed=9).result(240)
        settle(sch)
        # the phases are many a pass, and none of them is a flush
        assert calls["phases"] > 4 * (calls["flush"] - flushed) > 0
    finally:
        sch.close()
    # close() flushed what the last wait took, as no pass
    assert calls["flush"] == calls["pass"]
    assert scrape()["veles_serving_loop_passes_total"] \
        - before["veles_serving_loop_passes_total"] == calls["pass"] - 1
    assert account.current == "admit" and not any(
        account.seconds.values())


@pytest.fixture(scope="module")
def serve_record():
    """The benchmark's own tiny serve run: what its driver's
    ``/metrics`` parser kept of the window."""
    from benchmark.tests.test_benchmark import _serve_run
    ok, values, record = _serve_run()
    assert ok, values
    return record


def per_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def read_ratio(name, record):
    """(the manifest's entry, the ``ratio`` reader's number) of one
    metric whose data file names two counters ``GET /metrics`` prints."""
    from benchmark.readers import ratio
    manifest = per_layer()
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "ratio" and name in manifest
    assert spec["params"]["num"] in record["counters"], name
    assert spec["params"]["den"] in record["counters"], name
    return manifest[name], ratio.read(record, spec["params"])


def test_the_benchmarks_parser_reads_every_ratio_metric(serve_record):
    """The driver's ``/metrics`` parser and the ``ratio`` reader give
    every one of the ten metrics of the phases a finite number."""
    manifest = per_layer()
    read = {name: read_ratio(name, serve_record)[1]
            for name in RATIO_METRICS}
    maybe = read.pop("decode_step_after_prefill_ms")
    assert maybe is None or 0 <= maybe < float("inf")
    for name, value in read.items():
        assert value is not None and 0 <= value < float("inf"), name
        if manifest[name]["unit"] == "%":
            assert value <= 100, name
    assert read["sched_step_share_pct"] \
        + read["sched_prefill_share_pct"] <= 100
    assert read["decode_step_ms"] > 0


@pytest.mark.parametrize("name", PART_METRICS)
def test_the_benchmarks_parser_reads_a_part_or_dry_metric(
        name, serve_record):
    entry, value = read_ratio(name, serve_record)
    assert value is not None and 0 <= value < float("inf")
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower" and len(entry["workloads"]) == 4
    if entry["unit"] == "%":
        assert value <= 100
    coarser = {"step_resolve_ms": "step_launch_ms",
               "step_launch_ms": "decode_step_ms",
               "step_land_wait_ms": "decode_step_ms",
               "sched_admit_queue_ms_per_pass": "sched_admit_ms_per_pass",
               "sched_reap_ms_per_pass": "sched_admit_ms_per_pass",
               "dry_admit_pct": "device_dry_pct",
               "dry_prefill_pct": "device_dry_pct",
               "dry_step_pct": "device_dry_pct"}.get(name)
    if coarser:     # a part of what the coarser metric bounds
        assert value <= read_ratio(coarser, serve_record)[1]
    else:
        assert value > 0


def test_the_benchmarks_parser_reads_the_greedy_step_share(serve_record):
    """The benchmark's serve traffic is greedy: every launch's sampler
    took the argmax alone (PR 38); a program without the counter, as
    the parent of PR 38 is, gives the reader nothing to read."""
    from benchmark.readers import ratio
    entry, value = read_ratio("sampler_greedy_steps_pct", serve_record)
    assert entry["source"] == "program_counter"
    assert entry["better"] == "higher" and len(entry["workloads"]) == 4
    assert value == 100
    parent = dict(serve_record, counters={
        k: v for k, v in serve_record["counters"].items()
        if k != "veles_serving_steps_greedy_total"})
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "sampler_greedy_steps_pct.json")) as f:
        assert ratio.read(parent, json.load(f)["params"]) is None


# -- device-side names --------------------------------------------------------

def _closure(fn):
    from veles_tpu.models.generate import _StepClosure
    return _StepClosure(fn)


def _entry_points():
    from veles_tpu.serving import engine, kv_slots
    prefill = importlib.import_module("veles_tpu.serving.prefill")
    one = jnp.zeros((2,), jnp.float32)
    pool = jnp.zeros((4, 2, 8), jnp.float32)
    return {
        # the steps donate their tenth argument, the cache's pools
        "serving.paged_step": lambda: (engine._paged_step_cached(
            "names", _closure(lambda *a: a[9] + 1)), (one,) * 10),
        "serving.verify_step": lambda: (engine._verify_step_cached(
            "names", _closure(lambda *a: a[9] + 1)), (one,) * 10),
        "serving.prefill": lambda: (prefill._prefill_cached(
            "names", _closure(lambda x: x + 1)), (one,)),
        "serving.prefill_chunk": lambda: (prefill._chunk_cached(
            "names", _closure(lambda x: x + 1)), (one,)),
        "serving.sample_first": lambda: (engine._sample_first_jit, (
            jnp.zeros((1, 12), jnp.float32), jnp.zeros((1,)),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.uint32),
            jnp.zeros((1,), jnp.int32))),
        "serving.kv_insert_blocks": lambda: (kv_slots._insert_blocks, (
            pool, pool, jnp.zeros((1, 4, 8)), jnp.zeros((1, 4, 8)),
            jnp.zeros((2,), jnp.int32), jnp.int32(0))),
    }


@pytest.mark.parametrize("name", sorted(_entry_points()))
def test_serving_entry_point_is_named_in_the_lowered_module(name):
    """``track_jit`` knows the entry point as ``serving.x``; its
    compiled module is ``jit_serving_x``, what the profiler's ``XLA
    Modules`` line shows."""
    fn, args = _entry_points()[name]()
    text = fn.lower(*args).as_text()
    assert "module @jit_" + name.replace(".", "_") + " " in text


def test_paged_decode_attention_scope_is_in_the_ops_metadata():
    from veles_tpu.ops.paged_attention import paged_decode_attention
    b, d, bs, nb, t = 2, 16, 4, 8, 2
    args = (jnp.zeros((b, 1, d)), jnp.zeros((b, 1, d)),
            jnp.zeros((b, 1, d)), jnp.zeros((nb, bs, d)),
            jnp.zeros((nb, bs, d)), jnp.zeros((b, t), jnp.int32),
            jnp.zeros((b,), jnp.int32))
    lowered = jax.jit(paged_decode_attention, static_argnums=(7,)) \
        .lower(*args, 2)
    assert "veles_paged_decode_attention" in lowered.as_text(
        debug_info=True)
