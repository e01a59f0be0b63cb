"""JIT compile tracking — make XLA (re)compilation a first-class
metric.

Every jitted entry point in the framework (fused workflow segments,
trainer steps, serving prefill / slot decode, the ``generate()``
decode family) is wrapped with :func:`track_jit`; the wrapper detects
compilations by watching the jitted callable's executable-cache size
grow across a call (``jax.jit`` exposes ``_cache_size()``), so

- first-call compile time per entry point becomes a gauge,
- recompile counts (new shapes / dtypes hitting the same entry point)
  become a counter — the "why is the server stalling" answer that raw
  wall timers can't give,
- each detected compile also lands in the span log as a
  ``jit.compile`` event, so Chrome traces show compile gaps inline.

:func:`maybe_profiler_trace` is the opt-in ``jax.profiler`` toggle:
set ``root.common.trace.profiler_dir`` and every ``Workflow.run()``
writes a TensorBoard-loadable device trace alongside the host spans.
"""

import contextlib
import functools
import threading
import time
import types

from veles_tpu.logger import events
from veles_tpu.telemetry.registry import metrics


def _compile_metrics():
    return (
        metrics.counter(
            "veles_jit_compiles_total",
            "XLA compilations per jitted entry point (first call + "
            "every recompile on a new shape/dtype); cache=\"hit\" "
            "marks compiles satisfied by the persistent compilation "
            "cache (fast executable loads), cache=\"cold\" real "
            "XLA compiles", ("fn", "cache")),
        metrics.counter(
            "veles_jit_calls_total",
            "calls into tracked jitted entry points", ("fn",)),
        metrics.histogram(
            "veles_jit_compile_seconds",
            "wall time of calls that triggered an XLA compilation "
            "(trace + compile + first dispatch)", ("fn",)),
        metrics.gauge(
            "veles_jit_first_compile_seconds",
            "wall time of the FIRST compiling call per entry point",
            ("fn",)),
    )


# -- cost accounting (XLA cost_analysis / memory_analysis) -------------------

#: fields every cost record carries; absent backend support → None
COST_KEYS = ("flops", "bytes_accessed", "temp_bytes", "argument_bytes",
             "output_bytes", "generated_code_bytes")

_cost_lock = threading.Lock()
_cost_records = {}   # entry-point name -> {COST_KEYS: float|int|None}
_cost_captured = set()


def _cost_gauges():
    return {
        "flops": metrics.gauge(
            "veles_jit_cost_flops",
            "XLA cost_analysis flops of the first compiled executable "
            "per entry point (roofline numerator)", ("fn",)),
        "bytes_accessed": metrics.gauge(
            "veles_jit_cost_bytes_accessed",
            "XLA cost_analysis bytes accessed per executed step "
            "(HBM-roofline denominator)", ("fn",)),
        "temp_bytes": metrics.gauge(
            "veles_jit_memory_temp_bytes",
            "XLA memory_analysis peak temp allocation of the compiled "
            "executable", ("fn",)),
        "argument_bytes": metrics.gauge(
            "veles_jit_memory_argument_bytes",
            "XLA memory_analysis argument bytes of the compiled "
            "executable", ("fn",)),
        "output_bytes": metrics.gauge(
            "veles_jit_memory_output_bytes",
            "XLA memory_analysis output bytes of the compiled "
            "executable", ("fn",)),
        "generated_code_bytes": metrics.gauge(
            "veles_jit_memory_code_bytes",
            "XLA memory_analysis generated-code size of the compiled "
            "executable", ("fn",)),
    }


def _cost_enabled():
    from veles_tpu.config import root
    return bool(root.common.telemetry.get("cost_analysis", True))


def _nonneg(v):
    """cost_analysis reports -1 for 'unknown' on some backends — that
    is an absence, not a value."""
    try:
        v = float(v)
    except (TypeError, ValueError):
        return None
    return v if v >= 0 else None


def _capture_cost(name, fn, args, kwargs):
    """Record cost/memory analysis for ``name``'s executable.  Uses
    the AOT ``lower().compile()`` path (the lowering is cached from
    the call that just compiled; runs ONCE per entry-point name per
    process).  Holds no reference to ``args`` beyond this frame —
    ``lower`` reads avals, not buffers, so donated inputs are fine.
    Every absence (old jax, backend without cost analysis, sharded
    lowering quirks) degrades to ``None`` fields, never an error."""
    rec = dict.fromkeys(COST_KEYS)
    compiled = None
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception:
        pass
    if compiled is not None:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                rec["flops"] = _nonneg(ca.get("flops"))
                rec["bytes_accessed"] = _nonneg(
                    ca.get("bytes accessed"))
        except Exception:
            pass
        try:
            ma = compiled.memory_analysis()
            rec["temp_bytes"] = _nonneg(
                getattr(ma, "temp_size_in_bytes", None))
            rec["argument_bytes"] = _nonneg(
                getattr(ma, "argument_size_in_bytes", None))
            rec["output_bytes"] = _nonneg(
                getattr(ma, "output_size_in_bytes", None))
            rec["generated_code_bytes"] = _nonneg(
                getattr(ma, "generated_code_size_in_bytes", None))
        except Exception:
            pass
    gauges = _cost_gauges()
    for key, value in rec.items():
        if value is not None:
            gauges[key].labels(name).set(value)
    with _cost_lock:
        _cost_records[name] = rec
    return rec


def cost_summary():
    """Per-entry-point cost digest — ``{name: {flops, bytes_accessed,
    temp_bytes, argument_bytes, output_bytes, generated_code_bytes}}``
    with explicit ``None`` for anything the backend couldn't report.
    bench.py records it next to throughput as the roofline
    denominator."""
    with _cost_lock:
        return {name: dict(rec) for name, rec in _cost_records.items()}


# -- persistent-compilation-cache hit detection ------------------------------
#
# jax reports persistent-cache hits through jax.monitoring
# ("/jax/compilation_cache/cache_hits"); one process-wide listener
# keeps a running count and _TrackedJit diffs it around each call to
# label the detected compile "hit" (fast executable load from
# jax_compilation_cache_dir) vs "cold" (a real XLA compile).

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_hits_lock = threading.Lock()
_cache_hits = 0
_listener_installed = False


def _persistent_cache_hits():
    with _hits_lock:
        return _cache_hits


def _install_cache_listener():
    global _listener_installed
    with _hits_lock:
        if _listener_installed:
            return
        _listener_installed = True
    try:
        import jax

        def _on_event(event, **kwargs):
            global _cache_hits
            if event == _CACHE_HIT_EVENT:
                with _hits_lock:
                    _cache_hits += 1

        jax.monitoring.register_event_listener(_on_event)
    except Exception:  # pragma: no cover - jax without monitoring
        pass


class _TrackedJit:
    """Callable proxy over a jitted function counting compiles.

    Transparent: attribute access (``_cache_size``, ``lower``,
    ``clear_cache``...) delegates to the wrapped callable."""

    def __init__(self, name, fn):
        self.name = name
        self.fn = fn
        functools.update_wrapper(self, fn, updated=())
        compiles, calls, hist, first = _compile_metrics()
        self._compiles_family = compiles
        self._calls = calls.labels(name)
        self._hist = hist.labels(name)
        self._first = first.labels(name)
        self._seen_compile = False
        _install_cache_listener()

    def _cache_len(self):
        probe = getattr(self.fn, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_len()
        hits_before = _persistent_cache_hits()
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        self._calls.inc()
        if before is not None:
            after = self._cache_len()
            if after is not None and after > before:
                dt = time.perf_counter() - t0
                kind = "hit" \
                    if _persistent_cache_hits() > hits_before \
                    else "cold"
                self._compiles_family.labels(self.name, kind).inc(
                    after - before)
                self._hist.observe(dt)
                with _cost_lock:  # first-compile latch: one winner
                    first_compile = not self._seen_compile
                    self._seen_compile = True
                if first_compile:
                    self._first.set(dt)
                events.record("jit.compile", "single", fn=self.name,
                              duration=dt)
                # cost/memory accounting once per entry-point NAME per
                # process (same-name rebuilds share the record): pay
                # the one AOT recompile only for the first executable.
                # Claim the name under the lock — two threads racing
                # here would each pay the AOT compile — but release it
                # before the slow _capture_cost (which re-takes it to
                # store the record).
                if _cost_enabled():
                    with _cost_lock:
                        first = self.name not in _cost_captured
                        if first:
                            _cost_captured.add(self.name)
                    if first:
                        _capture_cost(self.name, self.fn, args, kwargs)
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


def track_jit(name, fn):
    """Wrap a jitted callable so its compiles are counted under
    ``name``.  Same-name wrappers share the metric series (an LRU
    cache re-jitting a cleared entry keeps accumulating into one
    series).  The wrapper holds no global reference: its lifetime is
    the wrapped callable's, so dropping the jit handle still frees
    the compiled executables and everything their closures pin."""
    return _TrackedJit(name, fn)


def trace_named(name, fn):
    """``fn`` under the name a device trace should show it by: jax
    calls a jitted function's compiled module ``jit_<fn.__name__>``,
    and the serving closures are all ``step`` / ``fn`` / ``pair``.
    ``track_jit("serving.paged_step", jax.jit(trace_named(
    "serving.paged_step", closure.fn)))`` compiles to module
    ``jit_serving_paged_step``, so the profiler's ``XLA Modules`` line
    splits device time by entry point.  A renamed copy: ``fn`` itself
    keeps its name."""
    out = types.FunctionType(fn.__code__, fn.__globals__,
                             name.replace(".", "_"), fn.__defaults__,
                             fn.__closure__)
    out.__kwdefaults__ = fn.__kwdefaults__
    out.__qualname__ = out.__name__
    return out


def compile_summary():
    """Per-entry-point compile digest — ``{name: {compiles,
    compiles_persistent_hit, calls, first_compile_s,
    compile_seconds_total}}`` plus a ``total`` rollup; what
    ``bench.py`` records next to throughput.  ``compiles`` counts
    every executable materialization; ``compiles_persistent_hit`` the
    subset served by the on-disk compilation cache (cheap loads, not
    real XLA compiles)."""
    out = {}
    total_compiles = 0
    total_hits = 0
    total_seconds = 0.0
    fam_compiles = metrics.get("veles_jit_compiles_total")
    fam_calls = metrics.get("veles_jit_calls_total")
    fam_hist = metrics.get("veles_jit_compile_seconds")
    fam_first = metrics.get("veles_jit_first_compile_seconds")
    if fam_compiles is None:
        return {"total": {"compiles": 0, "compile_seconds": 0.0}}
    per_fn = {}
    for (name, kind), child in fam_compiles.children().items():
        agg = per_fn.setdefault(name, {"cold": 0, "hit": 0})
        agg[kind] = agg.get(kind, 0) + int(child.value)
    for name, agg in sorted(per_fn.items()):
        compiles = agg["cold"] + agg["hit"]
        hist = fam_hist.labels(name)
        calls = fam_calls.labels(name)
        first = fam_first.labels(name)
        total_compiles += compiles
        total_hits += agg["hit"]
        total_seconds += hist.sum
        out[name] = {
            "compiles": compiles,
            "compiles_persistent_hit": agg["hit"],
            "calls": int(calls.value),
            "first_compile_s": round(first.value, 4),
            "compile_seconds_total": round(hist.sum, 4),
        }
    out["total"] = {"compiles": total_compiles,
                    "compiles_persistent_hit": total_hits,
                    "compile_seconds": round(total_seconds, 4)}
    return out


@contextlib.contextmanager
def maybe_profiler_trace():
    """When ``root.common.trace.profiler_dir`` names a directory, run
    the block under ``jax.profiler.trace`` (device-side timeline for
    TensorBoard/Perfetto); otherwise a no-op."""
    from veles_tpu.config import root
    trace_dir = root.common.trace.get("profiler_dir")
    if not trace_dir:
        yield
        return
    import jax.profiler
    with jax.profiler.trace(str(trace_dir)):
        yield
