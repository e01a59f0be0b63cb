"""Per-request distributed tracing for the serving fleet.

PAPER.md's blueprint centers on an inspectable dataflow graph — the
reference system could say what every unit was doing and why — and the
training side rebuilt that as spans + Chrome-trace export.  The
serving fleet (router retries/hedges, priority preemption, chunked
prefill, speculative verify, radix admission, SSE proxying) only
exposed *aggregate* Prometheus families; this module adds the
Dapper-style per-request axis, so "why did THIS request take 3 s at
p99" is answerable across router → replica → scheduler:

- a **trace id** is minted at the edge (router or a direct REST /
  OpenAI-facade hit) or accepted from the client via the
  ``X-Veles-Trace`` header (:data:`TRACE_HEADER`), sanitized
  (:func:`clean_trace_id` — header/JSONL material, so no whitespace
  or control bytes survive), and echoed on EVERY reply including
  structured errors and SSE terminal frames;
- the router records a ``router.request`` begin/end pair per routed
  request and a ``router.attempt`` begin/end pair per forward attempt
  (retries and hedges each get their own child span, tagged with the
  attempt number and replica id);
- the scheduler records phase spans at the boundaries it already
  owns — queue wait, admission (cold vs prefix-warm, blocks
  claimed), each prefill chunk, batched decode/verify boundaries
  (ONE ``req.step`` span per boundary carrying per-request token
  counts in its ``traces`` map — per-slot spans would multiply the
  hot-path cost by occupancy), preempt/resume, first token, retire —
  all through the existing JSONL event sink
  (:data:`veles_tpu.logger.events`), which is what lets
  ``python -m veles_tpu.telemetry.trace_export --request <id>``
  merge router + N replica logs into one parented Chrome trace;
- a process-wide **in-flight registry** (:func:`register` /
  :func:`inflight_table`) lets the flight recorder and
  ``GET /debug/requests`` enumerate live requests (trace id, phase,
  age, blocks held) without the scheduler/router importing the
  recorder.

Tracing is ON by default (``root.common.reqtrace.enabled``) with
bounded overhead: every record is one dict append to the bounded
in-memory ring (plus a JSONL line only when a file sink is open), the
per-boundary decode span amortizes over the whole batch: the tier-1
``tracing_overhead`` test counts one event a boundary at any
occupancy, and the scheduler loop charges the time to its ``observe``
phase (``veles_serving_loop_observe_seconds_total``).
"""

import os
import re
import threading
import weakref

from veles_tpu.logger import events

#: the propagation/echo header (case-insensitive on the wire)
TRACE_HEADER = "X-Veles-Trace"

#: client-supplied ids are header AND log material: strip anything
#: outside this set so a hostile header can't inject CRLF into a
#: reply or structure into the JSONL sink
_SAFE = re.compile(r"[^A-Za-z0-9._:-]")
_MAX_ID = 64


def new_trace_id():
    """A fresh 16-hex trace id (64 random bits — collision-safe at
    fleet request rates, short enough to grep by hand)."""
    return os.urandom(8).hex()


def clean_trace_id(raw):
    """Sanitize a client-supplied trace id; ``None`` when nothing
    usable survives (caller then mints a fresh one)."""
    if raw is None:
        return None
    s = _SAFE.sub("", str(raw).strip())[:_MAX_ID]
    return s or None


def ensure_trace_id(raw=None):
    """The edge mint: the sanitized client id when one was sent,
    else a fresh one."""
    return clean_trace_id(raw) or new_trace_id()


def enabled():
    """Whether request tracing emits span events
    (``root.common.reqtrace.enabled``, default True).  Trace ids are
    minted and echoed regardless — only the event emission is gated,
    so correlation headers keep working even with tracing off."""
    from veles_tpu.config import root
    return bool(root.common.reqtrace.get("enabled", True))


def record(trace, phase, sink=None, **attrs):
    """One request-phase event: ``req.<phase>`` single carrying the
    ``trace`` id (the exporter's merge key).  A ``duration`` attr (in
    seconds) renders as a backdated complete slice in the Chrome
    trace — emit at the END of the phase with the measured wall
    time."""
    if trace is None:
        return None
    return (sink or events).record("req." + phase, "single",
                                   trace=str(trace), **attrs)


def record_step(traces, sink=None, **attrs):
    """One BATCHED decode/verify boundary: ``traces`` maps each
    participating request's trace id to the tokens it emitted at this
    boundary (0 for a slot whose drafts all rejected).  One span per
    boundary keeps tracing cost independent of occupancy; the
    ``--request`` exporter projects out the one id it is following."""
    if not traces:
        return None
    return (sink or events).record("req.step", "single",
                                   traces=dict(traces), **attrs)


# -- live in-flight registry --------------------------------------------------
#
# Schedulers and routers register themselves (weakly — a closed
# scheduler must not be pinned alive by forensics plumbing); the
# flight recorder and debug surfaces read the merged table.

_providers = {}
_plock = threading.Lock()


def register(name, obj, attr="debug_requests"):
    """Register a live in-flight provider: ``obj.<attr>()`` must
    return a list of row dicts (see
    :meth:`InferenceScheduler.debug_requests`).  Held by weakref —
    dead providers drop out of :func:`inflight_table` silently."""
    with _plock:
        _providers[id(obj)] = (str(name), weakref.ref(obj), str(attr))


def inflight_table():
    """The merged live in-flight request table across every
    registered provider — what a flight-recorder bundle embeds next
    to the thread stacks, so a hang dump shows WHICH requests were
    stuck, not just where the threads stood.  Every provider guards
    itself: a dying scheduler must not break a crash dump."""
    with _plock:
        items = list(_providers.items())
    out = []
    for key, (name, ref, attr) in items:
        obj = ref()
        if obj is None:
            with _plock:
                _providers.pop(key, None)
            continue
        try:
            rows = getattr(obj, attr)()
        except Exception:
            continue
        for row in rows:
            row = dict(row)
            row.setdefault("source", name)
            out.append(row)
    return out
