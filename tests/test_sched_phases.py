"""The scheduler loop's phase account (``scheduler._LoopPhases``) and
the device-side names: counts and identities on the CPU, never a time
limit.  What a phase COSTS is read on the chip, by the per-layer
metrics of ``benchmark/metrics/sched_*.json`` and beside them.

- the nine ``veles_serving_loop_<phase>_seconds_total`` partition the
  loop thread's time: they sum to ``loop_seconds`` + ``loop_parked``
  and to the wall time between two scrapes;
- ``veles_serving_steps_total`` is the number of decode launches,
  ``first_tokens`` the number of requests served, and both repeat;
- one flush a pass reaches the registry, and the annotations do
  nothing without a profiler session;
- the benchmark's own ``/metrics`` parser sees every counter under the
  name its metric file gives (the ten ``ratio`` files of this account);
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
from veles_tpu.serving.scheduler import PHASES, InferenceScheduler
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


def test_the_benchmarks_parser_reads_every_ratio_metric():
    """Through the benchmark's own tiny serve run: the driver's
    ``/metrics`` parser and the ``ratio`` reader give every one of the
    ten metrics of this account a finite number."""
    from benchmark.readers import ratio
    from benchmark.tests.test_benchmark import _serve_run
    ok, values, record = _serve_run()
    assert ok, values
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = {m["name"]: m for m in json.load(f)["per_layer"]}
    read = {}
    for name in RATIO_METRICS:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "ratio" and name in manifest
        assert spec["params"]["num"] in record["counters"], name
        assert spec["params"]["den"] in record["counters"], name
        read[name] = ratio.read(record, spec["params"])
    maybe = read.pop("decode_step_after_prefill_ms")
    assert maybe is None or 0 <= maybe < float("inf")
    for name, value in read.items():
        assert value is not None and 0 <= value < float("inf"), name
        if manifest[name]["unit"] == "%":
            assert value <= 100, name
    assert read["sched_step_share_pct"] \
        + read["sched_prefill_share_pct"] <= 100
    assert read["decode_step_ms"] > 0


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
