"""Continuous-batching inference scheduler.

Requests queue on :meth:`InferenceScheduler.submit` (any thread) and
are decoded by ONE background loop (all jax work — ``Array.devmem``
uploads and the compile caches are not thread-safe against concurrent
mutation, and a single loop is what lets every in-flight request share
one compiled step):

1. **admit** — while capacity allows, the oldest queued request
   claims a slot of the paged KV cache
   (:class:`serving.kv_slots.PagedKVCache`).  Admission is
   memory-proportional: the request also claims its whole block
   budget (``ceil((prompt + steps) / block_size)`` blocks), so short
   requests pack many more concurrent streams into the same HBM than
   a window-sized row per slot would;
2. **prefill** — prompts up to ``prefill_chunk`` prefill in ONE
   compiled pass; longer prompts prefill in CHUNKS, at most one chunk
   per loop iteration, INTERLEAVED with the decode step below
   (Sarathi-style chunked prefill) — a joining long prompt stalls
   in-flight decode streams by one chunk per iteration, not by its
   whole prefill, which flattens the TTFT tail of short requests
   stuck behind long ones.  A chunk is as wide as it can be while it
   still costs one stream of the weights (:func:`chunk_width`: the
   power-of-two bucket of the positions the request has left, from
   ``prefill_chunk`` up to ``PREFILL_WIDEST``; a chain with a unit
   that scans a chunk position by position stays at
   ``prefill_chunk``), so a prompt streams the weights once per 256
   positions and not once per 64.  A joiner's first chunk goes out in
   the pass AFTER its admission (unless an older request is still
   prefilling): the admission's own host time and the readback that
   ends a prompt's last chunk never fall into one gap between two
   tokens of the decoding streams.  Either way the K/V staging row
   is inserted into the cache and the first token samples from the
   final logits (the TTFT edge);
3. **step** — active slots advance one token through the shared
   compiled step.  It packs ONLY the active slots into a
   power-of-two occupancy bucket and bounds attention by a
   power-of-two block bucket over the deepest request
   (:func:`serving.engine.paged_decode_step`), so a half-empty batch
   of shallow requests pays neither full-batch nor full-window
   compute.  The step is LAUNCHED here and LANDED (its tokens read,
   emitted, counted) one launch later: while the batch stays the
   same, step N+1 goes out from step N's device-resident tokens
   before the host has read them, so the device runs back to back
   through the host's launch and the loop's own work
   (``InferenceScheduler._step_paged``; speculation drafts from the
   host's tokens and lands every step at once);
4. **retire** — a slot that generated its stop token or hit its step
   limit completes its future and frees slot + blocks where its token
   lands, and the next queued request joins.

Admission control: a full queue raises :class:`QueueFullError` (HTTP
503) at submit; a request still queued past its deadline fails with
:class:`DeadlineExceededError` (HTTP 408).  Greedy requests keep
exact determinism (each request's attention sees only its own cache
rows/blocks, and sampling is row-wise, so token streams are
independent of slot placement, packing order and co-tenants);
sampled requests are reproducible per seed — though the stream
differs from the single-user ``generate()`` path's (one fold per
generated token here vs one split per lockstep buffer position
there).

Request lifecycle (fault tolerance): every request carries a
whole-request **deadline** (``root.common.serving.request_timeout``,
overridable per submit) enforced at chunk/decode boundaries — an
expired request frees its slot and blocks and fails with
:class:`DeadlineExceededError` carrying the tokens generated so far
(HTTP 408 material).  A client that went away can :meth:`cancel` its
future; the loop releases the resources at the next boundary.  The
scheduler can **preempt** an active request
(:meth:`request_preempt`): its blocks return to the pool, its
generated-token prefix is kept, and on re-admission prompt + prefix
re-prefill through the chunked-prefill path and decoding continues —
the token stream is bit-identical to the uninterrupted run because
token ``t`` is always drawn with ``fold_in(key(seed), t)`` regardless
of slot or cache placement.  A **watchdog** thread detects a stuck
decode step (``root.common.serving.watchdog`` seconds) and fails
pending requests instead of hanging their clients; block-pressure
**load shedding** (``shed_block_factor``) turns hopeless submits into
deterministic 503s before they queue; and :meth:`drain` closes
admission (503 + Retry-After), finishes everything in flight and
signals ``drained`` — the rolling-restart hook behind ``POST
/drain``.  Injection points (``serving.scheduler.*`` — see
:mod:`veles_tpu.faults`) let tier-1 exercise every one of these paths
deterministically.

Decode speed (both off by default): **speculative
decoding** (``spec`` + ``spec_k``) drafts up to k tokens per slot by
n-gram prompt lookup (:mod:`veles_tpu.serving.spec`) and scores the
pending token plus all drafts in ONE batched verify pass
(:func:`serving.engine.verify_step_paged`) — the accepted prefix
plus the correction sample reproduces the spec-off stream
bit-for-bit (greedy AND seeded; the verify samples fold the same
per-request draw counters), rejected tails roll back logically
(their K/V rows sit past the accepted length, masked until
overwritten), and the occupancy/depth bucket ladder grows a draft
axis: ONE fixed ``spec_k``-wide verify executable per (B, T) for
n-gram-only schedulers (shorter draft sets pad and ``lens`` masks
them — the pre-PR 20 compile count), while model-drafter schedulers
(``draft_head`` attached) key the width on the power-of-two bucket
of the widest per-slot adaptive ``draft_k`` so collapsed-accept-rate
batches stop paying ``spec_k``-wide sampling.  The **radix
prefix cache** (``prefix_cache`` + ``prefix_evict``;
:mod:`veles_tpu.serving.prefix_cache`) makes KV blocks
cross-request: finished requests donate their written blocks,
admission longest-prefix-matches the trie so warm prompts gather
the resident rows and chunk-prefill only the cold tail, claim only
``ceil(cold_tokens / block_size)`` new blocks (cache hits raise max
concurrent streams), and refcount-0 residents LRU-evict under pool
pressure.

Delivery and QoS (the streaming/priority layer, see
:mod:`veles_tpu.serving.streams`): ``submit(..., stream=True)``
returns a :class:`~veles_tpu.serving.streams.TokenStream` the decode
loop pushes every ACCEPTED token into at the same boundary it appends
to ``generated`` — per-token latency for clients, spec bursts back to
back, nothing emitted twice across a preempt→resume.  Every request
carries a **priority class** (``low`` / ``normal`` / ``high``, default
normal): the queue is ordered by class (FIFO within one), block-
pressure shedding trips EARLIER for lower classes (the 503's
Retry-After also grows as the class drops), a full queue evicts the
youngest queued lower-class request to seat a higher one, and a
high-class arrival that cannot admit preempts the youngest active
LOWER-class request through the generalized
:meth:`request_preempt` victim selection — the victim resumes
bit-identically (the PR 7 contract), it just waits out the burst.
Per-class TTFT/preempt/shed counters ride
``veles_serving_class_*``.

Config knobs (``root.common.serving.*``, overridable per scheduler):
``block_size`` (tokens per KV block, default 16), ``kv_blocks``
(pool capacity in blocks; default
``max_slots · ceil(window / block_size)``),
``kv_dtype`` ("fp32" default — the bit-parity baseline — or "int8":
paged pools stored quantized with per-row scales beside the block
tables, roughly halving bytes per cached token so the same HBM
budget decodes ~2x the concurrent streams; quality-gated by
``serving/kv_quality.py`` and ``quality.py``'s kv_quant record.
Under int8 a preempt→resume continues within quantization noise
rather than bit-identically — the re-prefill computes deeper
layers from f32 staging attention where the original decode read
dequantized keys — while a warm radix resubmit REUSES its matched
blocks exactly and recomputes only the cold tail (over dequantized
keys: the same noise, one block deep); the fp32 default keeps every
PR 7 bit-exactness contract),
``prefill_chunk`` (the NARROWEST chunk in tokens and the longest
prompt that prefills one-shot, rounded up to a power of two; 0
disables chunking, default 64; how wide a chunk is above it follows
from the request's own length and the chain's layer types,
:func:`chunk_width` and :func:`widest_chunk`), ``request_timeout`` /
``watchdog`` / ``shed_block_factor`` (lifecycle knobs above; 0
disables each), ``spec`` / ``spec_k`` (speculative decoding),
``fused_verify`` (score the spec run single-pass instead of the
scatter-then-gather two-pass — allclose, not bit-identical, so the
parity baseline keeps it off; int8 pools always verify fused),
``prefix_cache`` / ``prefix_evict`` (the radix cache above).

Scale-out (both off by default): **tensor-parallel serving**
(``tp`` / ``root.common.serving.tp``; :mod:`veles_tpu.serving.tp`)
shards every jitted step — chunked prefill, the paged decode step,
the spec verify step and the ``serving.kv_*`` block movers — over a
``{"tp": N}`` mesh with Megatron column/row weight splits and
HEAD-WISE paged pools (each chip stores ``[blocks, bs, d/tp]``, int8
scales replicated), so the per-chip HBM of a ``kv_blocks`` budget
drops by the mesh factor and a model too wide for one chip still
serves; block tables, admission, the radix trie, drafting and this
loop stay replicated host logic.  **Disaggregated prefill/decode**
(``role`` / ``root.common.serving.role``): a ``"prefill"``-role
scheduler accepts only :meth:`submit_prefill` — it chunk-prefills,
gathers the finished blocks raw (scales riding along) and parks the
record for ``GET /serving/kv_export/<handle>``; a ``"decode"``-role
scheduler adopts such records via :meth:`submit_imported` — blocks
scatter straight into its own table and the first token samples from
the exported last-position logits, so the stream is identical to the
colocated path (fp32 bit-exact; int8 blocks import unrequantized —
byte-identical resident state).  ``"both"`` (default) keeps the
single-replica colocated shape; the router routes by role.

Observability: every request carries a **trace id**
(``submit(trace=...)``; minted when absent, propagated from the
``X-Veles-Trace`` header by the REST layer and router) and the
scheduler records its phase timeline — queue wait, admission (cold
vs prefix-warm, blocks claimed), each prefill chunk, batched
decode/verify boundaries (one span per boundary, per-request token
counts), preempt/resume, first token, retire — through
:mod:`veles_tpu.telemetry.reqtrace` into the JSONL event sink
(``trace_export --request <id>`` rebuilds the timeline).
:meth:`debug_requests` is the live in-flight table behind ``GET
/debug/requests``; per-class SLO good/bad counts and multi-window
burn rates (``root.common.slo.*``) ride ``stats.slo``.

Phase account (always on): every instant of the loop thread is charged
to exactly one of ``PHASES`` — ``parked`` (waiting for work), ``admit``
(the loop's own bookkeeping; the phase wherever no block names
another), ``prefill``, ``aux``, ``draft``, ``pack`` (a step's inputs),
``step`` (a step's launch and the wait for a launched step's tokens,
under launch-ahead those of the step BEFORE it: the one phase that
waits on the device; a wait alone adds seconds and no step), ``emit``
and ``observe`` (statistics, metering, request
tracing, gauges, the account's own flush).  Seven stretches inside
three of them have names of their own, the ``PARTS``: ``admit.queue``
(from asking for the scheduler's lock to releasing it, less
``parked``), ``admit.reap`` (lost pools, the reap, owed preemptions),
``admit.stage`` (a joiner's sequence and its staging rows),
``step.resolve`` (a launch up to the compiled step in hand),
``step.call`` (the rest of the launch), ``step.land`` (the wait for a
launched step's tokens) and ``prefill.first`` (the wait for a first
token); a part's seconds are its phase's seconds too, and what no part
names is the phase's remainder.  ``with self._phases(name)``
marks a stretch; :class:`_LoopPhases` keeps plain floats on the loop
thread and one ``ServingMetrics.record_loop_pass`` a pass moves them
into ``veles_serving_loop_<phase>_seconds_total`` and
``veles_serving_loop_<phase>_<part>_seconds_total`` (self times: a
nested block stops the outer clock).  Each stretch is also a
``veles.sched.<phase>`` or ``veles.sched.<phase>.<part>``
``jax.profiler.TraceAnnotation`` with the same
boundaries: under a profiler session the stretches lie on the host
plane beside the device's operations, and without one the annotation
does nothing, so no switch guards it.

The dry account rides the same switches: every dispatch site on the
loop thread hands the account its newest result
(``_LoopPhases.dispatched``), a switch polls it (``is_ready()``, no
wait), and from the first switch that finds it ready until the next
dispatch every stretch is charged to
``veles_serving_loop_dry_<phase>_seconds_total`` too: the seconds in
which the device had nothing left to do, by what the host was doing.
A lower bound (the stretch in which the device ran dry is not
charged); ``parked`` is never charged.  docs/observability.md,
"Profiling the serving loop".
"""

import collections
import concurrent.futures
import itertools
import os
import threading
import time

import numpy

from veles_tpu import faults
from veles_tpu.logger import Logger
from veles_tpu.telemetry import reqtrace
from veles_tpu.telemetry.spans import annotation
from veles_tpu.serving.engine import (
    first_tokens, paged_decode_step, verify_step_paged,
    verify_supported)
from veles_tpu.serving.kv_host import HostKVTier
from veles_tpu.serving.kv_slots import (
    PagedKVCache, blocks_only_refusal, slot_state_units, stacked_units)
from veles_tpu.serving.metrics import (
    LOOP_PARTS, LOOP_PHASES, ServingMetrics)
from veles_tpu.serving.prefill import (
    chunked_supported, prefill, prefill_chunk, serving_refusal,
    serving_window)
from veles_tpu.serving.prefix_cache import RadixPrefixCache
from veles_tpu.serving.draft import draft_supported
from veles_tpu.serving.spec import (
    NgramIndex, NgramProposer, accept_drafts)
from veles_tpu.serving.streams import TokenStream
from veles_tpu.serving.weights import ServingWeights

#: priority classes, lowest to highest; ints in [0, 2] also accepted
PRIORITIES = {"low": 0, "normal": 1, "high": 2}
CLASS_NAMES = ("low", "normal", "high")
#: block-pressure shed trips at shed_block_factor x this fraction —
#: the LOW class sheds at half the documented budget, NORMAL at
#: exactly it (the pre-priority contract, unchanged), HIGH gets 1.5x
#: headroom so an overload sacrifices low-class work first
_SHED_FRAC = (0.5, 1.0, 1.5)
#: class-aware Retry-After seconds on a shed 503 (a shed low-class
#: client should back off longest — its work is what the overload
#: sacrifices first)
_RETRY_AFTER = (4, 2, 1)

#: process-unique default replica ids for metric labels (one per
#: scheduler built without an explicit fleet identity)
_SCHED_SEQ = itertools.count(1)


def resolve_priority(value):
    """Normalize a client priority (class name or int) to [0, 2];
    ``None`` means normal.  Raises ``ValueError`` on junk — a typo'd
    priority must be a client error, not silently-normal service."""
    if value is None:
        return PRIORITIES["normal"]
    if isinstance(value, str):
        try:
            return PRIORITIES[value.lower()]
        except KeyError:
            raise ValueError(
                "priority must be one of %s (or an int in [0, 2])"
                % "/".join(CLASS_NAMES))
    if isinstance(value, bool) or not isinstance(value, int):
        raise ValueError("priority must be a class name or int")
    if not 0 <= value <= 2:
        raise ValueError("priority int must be in [0, 2]")
    return value


class SchedulerError(Exception):
    """Base serving failure (maps to HTTP 500)."""
    http_status = 500


class QueueFullError(SchedulerError):
    """Admission control: queue-depth cap hit or block-pressure shed
    (HTTP 503; ``retry_after`` seeds the Retry-After header)."""
    http_status = 503
    retry_after = 1


class DrainingError(QueueFullError):
    """Admission closed for a graceful drain (HTTP 503) — the caller
    should retry against another replica."""
    retry_after = 5


class DeadlineExceededError(SchedulerError):
    """The request crossed its deadline — still queued
    (``tokens_generated == 0``) or mid-decode (HTTP 408; the partial
    count rides the error so clients know what they paid for)."""
    http_status = 408

    def __init__(self, message, tokens_generated=0):
        super(DeadlineExceededError, self).__init__(message)
        self.tokens_generated = int(tokens_generated)


class RequestCancelledError(SchedulerError):
    """The request was cancelled (client disconnect/abandon); its
    slot and KV blocks were released at the next boundary."""


class RoleMismatchError(SchedulerError):
    """The request phase does not match this replica's role (a
    decode submit on a prefill specialist or vice versa) — HTTP 409:
    the router should have dispatched it to the right pool."""
    http_status = 409


#: how long an unclaimed KV export survives (seconds) and how many
#: payload BYTES one prefill replica parks at once (the
#: ``kv_export_bytes`` knob's default) — the handoff is immediate in
#: a healthy fleet; these bound a crashed decode pool's leak.  A byte
#: budget replaces the old flat count-64 cap: records are whole
#: prompts of KV, so counting records let 64 long-prompt exports pin
#: unbounded host RAM while starving nothing
EXPORT_TTL = 120.0
EXPORT_BYTES = 256 << 20

#: cap on the per-replica cache-topology advertisement
#: (``prefix_digests`` in the metrics scrape) — breadth-first, so
#: the shallow, most shareable prefixes survive the cut
_DIGEST_MAX = 512


def _bucket(n, floor, cap):
    """Pad widths/counts to power-of-two buckets so the compiled
    executable count stays O(log) across arbitrary clients."""
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return min(b, cap)


#: the widest prefill chunk, in positions: the power of two at the
#: chip's ridge for compute-dtype weights (a v5e does 197e12 FLOP/s
#: against 819e9 B/s, so bf16 weights are paid for by their STREAM up
#: to ~240 rows).  Up to here a chunk costs about what the narrowest
#: one costs; past it the stall a chunk puts in front of every
#: decoding stream grows with its width
PREFILL_WIDEST = 256


def chunk_width(remaining, offset, narrowest, widest):
    """Width of a prefilling request's next chunk: the power-of-two
    bucket of the ``remaining`` positions between ``narrowest`` and
    ``widest`` (powers of two), halved until ``offset`` is a multiple
    of it.  A function of the request's own length and offset and of
    nothing else, so every prompt of one length meets the same
    compiled programs whoever else is decoding; every threshold is a
    multiple of ``narrowest``.  From offset 0 on the widths never
    rise along a prompt, so each offset is a multiple of every later
    width (``prefill_chunk()``'s tiling contract)."""
    width = _bucket(remaining, narrowest, widest)
    while offset % width:
        width //= 2
    return width


def widest_chunk(forwards, narrowest):
    """The widest chunk the chain prefills in: ``PREFILL_WIDEST``
    where a chunk is products over its positions (one stream of the
    weights whatever its width), ``narrowest`` where a unit says its
    chunk is a SCAN over positions (``prefill_scans``: its time grows
    with every position, and so would the stall in front of the
    decoding streams); 0 where chunking is off."""
    if not narrowest or any(getattr(u, "prefill_scans", False)
                            for u in forwards):
        return narrowest
    return max(narrowest, PREFILL_WIDEST)


def _serving_conf(name, default):
    from veles_tpu.config import root
    return root.common.serving.get(name, default)


def _metering_enabled():
    """``root.common.tsdb.metering`` — gates the per-tenant usage
    attribution (token counts at retire, KV-block-seconds and
    compute-seconds at step boundaries)."""
    from veles_tpu.config import root
    return bool(root.common.tsdb.get("metering", True))


#: the phases of a loop pass (module docstring, "Phase account"):
#: parked, admit, prefill, aux, draft, pack, step, emit, observe; one
#: ``veles_serving_loop_<phase>_seconds_total`` each (serving/metrics.py,
#: which says what each counts)
PHASES = tuple(LOOP_PHASES)
#: the parts, ``<phase>.<part>``: stretches with a name of their own
#: inside admit (queue, reap, stage), prefill (first) and step (resolve,
#: call, land); one ``veles_serving_loop_<phase>_<part>_seconds_total``
#: each, whose seconds are their phase's seconds too
PARTS = tuple(LOOP_PARTS)
#: every stretch the account can be in -> the phase it is charged to
_PHASE_OF = {s: s.partition(".")[0] for s in PHASES + PARTS}
#: ... -> its span's name
_SPAN_OF = {s: "veles.sched." + s for s in PHASES + PARTS}


def _newest(row_caches):
    """The array asked for last of a staging dict's ({layer: {name:
    array}}): the device runs in order, so it is ready last."""
    return list(list(row_caches.values())[-1].values())[-1]


class _Phase(object):
    """``with phases("pack")`` / ``with phases("step.land")``: one
    stretch of a phase or of one of its parts; leaving it resumes the
    stretch it interrupted."""

    __slots__ = ("account", "name", "outer")

    def __init__(self, account, name):
        self.account, self.name = account, name

    def __enter__(self):
        self.outer = self.account.switch(self.name)
        return self

    def __exit__(self, *exc):
        self.account.switch(self.outer)
        return False


class _LoopPhases(object):
    """The loop thread's flat phase account.  Exactly one stretch is
    current at any instant: a phase (``admit``, the loop's own
    bookkeeping, where no block says otherwise) or one of its
    ``PARTS``; switching charges the seconds since the last switch to
    the phase of the stretch that was current, and to the part if it
    was one, in plain floats, and moves the ``veles.sched.<stretch>``
    annotation with it, so the spans in a profiler trace and the
    counters share their boundaries.  An inner block stops the outer
    stretch's clock and leaving it resumes it: the numbers are self
    times.

    The dry account rides the same switches.  ``tail`` is the result
    of the newest dispatch (:meth:`dispatched`); a switch that finds it
    ready starts a dry spell AT that switch, and from then on every
    stretch is charged in full to ``dry[phase]`` too, until the next
    dispatch (which charges what has run of its own stretch and ends
    the spell).  The stretch in which the device ran dry is not
    charged, so the account is a lower bound, short by at most one
    stretch a spell; ``parked`` is never charged.

    Loop thread only: no lock, no registry; :meth:`drain` hands a
    pass's totals to ``ServingMetrics.record_loop_pass``, once a
    pass."""

    def __init__(self):
        self._reset()
        self.current = "admit"
        self.tail = None           # the newest dispatch's result
        self.is_dry = False        # ... is known to be ready
        self._since = time.perf_counter()
        self._span = annotation("veles.sched.admit")
        self._span.__enter__()

    def _reset(self):
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self.parts = dict.fromkeys(PARTS, 0.0)
        self.dry = dict.fromkeys(PHASES[1:], 0.0)
        self.steps = self.steps_after_prefill = 0
        self.step_after_prefill_seconds = 0.0
        self.prefilled = False     # a prefill phase ran this pass
        self.admissions = 0        # requests that entered _begin_admit
        # launch-ahead (InferenceScheduler._step_paged), flushed with
        # the pass: steps launched from the tokens of a step nobody
        # had read yet, and rows of a landed step that were discarded
        self.steps_ahead = self.rows_discarded = 0
        # steps launched with every packed row at temperature 0: their
        # sampler took the argmax alone (engine.sample_slots)
        self.steps_greedy = 0

    def __call__(self, name):
        return _Phase(self, name)

    def elapsed(self):
        """Seconds since the current stretch was entered or resumed."""
        return time.perf_counter() - self._since

    def switch(self, name):
        """Make ``name`` the current stretch; returns the one it was.
        A step is counted where its ``step.resolve`` stretch ends: a
        launch once, whether or not the engine said when it had its
        executable (:meth:`calling`), and a landing never."""
        was = self.current
        if name != was:
            self._turn(was, name)
        return was

    def lap(self):
        """A boundary inside the current stretch, for one that is long
        and dispatches as it goes (the staging rows): the stretch ends
        and another of its name begins, so the dry account polls here
        too.  Not for ``step.resolve``, whose end counts a step."""
        self._turn(self.current, self.current)

    def _turn(self, was, name):
        now = time.perf_counter()
        took = now - self._since
        phase = _PHASE_OF[was]
        self.seconds[phase] += took
        if was != phase:
            self.parts[was] += took
        if phase == "step":
            launched = was == "step.resolve"
            self.steps += launched
            if self.prefilled:
                self.steps_after_prefill += launched
                self.step_after_prefill_seconds += took
        dry, tail = self.is_dry, self.tail
        if dry:
            if phase != "parked":
                self.dry[phase] += took
        elif tail is not None:
            # a tail some later call donated is forgotten: its
            # ``is_ready()`` raises (and kills the process outright
            # after an explicit ``delete()``)
            try:
                if tail.is_deleted():
                    self.tail = None
                else:
                    self.is_dry = tail.is_ready()
            except Exception:
                self.tail = None
        if _PHASE_OF[name] == "prefill":
            self.prefilled = True
        self.current, self._since = name, now
        self._span.__exit__(None, None, None)
        self._span = annotation(_SPAN_OF[name])
        self._span.__enter__()

    def calling(self):
        """The launch has its executable (the engine's ``resolved``
        hook): what is left of it is ``step.call``."""
        self.switch("step.call")

    def dispatched(self, tail):
        """Work went to the device's queue in the current stretch, and
        ``tail`` is its newest result: ends a dry spell, charging what
        has run of this stretch.  Hand over an array that no later
        call donates (a switch forgets a tail it finds deleted).
        ``None``: work whose results are donated on or read back at
        once; no spell starts before the next hand-over."""
        dry, self.is_dry, self.tail = self.is_dry, False, tail
        if dry and self.current != "parked":
            self.dry[_PHASE_OF[self.current]] += self.elapsed()

    def drain(self):
        """The totals since the last drain, as the arguments of
        ``ServingMetrics.record_loop_pass``; the pass ends here."""
        out = (self.seconds, self.steps, self.steps_after_prefill,
               self.step_after_prefill_seconds, self.steps_ahead,
               self.steps_greedy, self.rows_discarded, self.parts,
               self.dry, self.admissions)
        self._reset()
        return out

    def close(self):
        self.switch("admit")
        self._span.__exit__(None, None, None)


class _Request(object):
    __slots__ = ("prompt", "steps", "temperature", "top_k",
                 "stop_token", "seed", "deadline", "future", "slot",
                 "generated", "cancelled", "preempts", "t_submit",
                 "t_admit", "t_first", "pf_seq", "pf_caches",
                 "pf_off", "pf_width", "pf_chunk", "pf_widest",
                 "pf_matched",
                 "prefix_handle", "priority", "sink", "trace",
                 "tenant", "export_only", "kv_import", "hid",
                 "draft_k", "accept_ema", "gram_ix")

    def __init__(self, prompt, steps, temperature, top_k, stop_token,
                 seed, deadline, priority=1, sink=None, trace=None,
                 tenant=None):
        self.prompt = prompt
        self.steps = steps
        self.temperature = temperature
        self.top_k = top_k
        self.stop_token = stop_token
        self.seed = seed
        self.deadline = deadline
        self.priority = int(priority)   # 0 low / 1 normal / 2 high
        self.sink = sink                # TokenStream._push (or None)
        self.trace = trace              # request trace id (reqtrace)
        self.tenant = tenant            # bounded tenant label (or None)
        self.future = concurrent.futures.Future()
        self.slot = None
        self.generated = []
        self.cancelled = False   # client gone — reap at next boundary
        self.preempts = 0        # times evicted (resume re-prefills)
        self.t_submit = time.monotonic()
        self.t_admit = None
        self.t_first = None
        # chunked-prefill progress (None while queued / one-shot);
        # pf_seq is the token sequence being prefilled — the prompt,
        # plus the generated prefix when resuming after a preemption
        self.pf_seq = None
        self.pf_caches = None
        self.pf_off = 0
        self.pf_width = 0
        self.pf_chunk = 0        # narrowest chunk of this prefill
        self.pf_widest = 0       # ... and its widest (chunk_width)
        self.pf_matched = 0      # warm prefix blocks heading the slot
        self.prefix_handle = None  # pinned radix-cache match
        self.export_only = False  # prefill-role: stop after export
        self.kv_import = None     # decode-role: adopted export record
        # speculative-drafting state (spec mode): the last hidden
        # state the verify/decode lane returned for this request
        # (None until the first post-prefill step — the model drafter
        # falls back to n-gram there), the accept-rate-adaptive draft
        # length (set at admission), per-drafter accept-rate EMAs,
        # and the memoized trailing-ngram index
        self.hid = None
        self.draft_k = 0
        self.accept_ema = {}
        self.gram_ix = None

    def fail(self, error):
        """Set the future's exception unless a racing path (watchdog,
        cancel) beat us to it."""
        if not self.future.done():
            try:
                self.future.set_exception(error)
            except concurrent.futures.InvalidStateError:
                pass


class _Flight(object):
    """A decode step that was launched and has not been read: its
    packed slot order, the request of each row, the device arrays it
    will hand over (a bucket of tokens; the hidden state and the
    units' counts of the step, ``cache.step_counts``, where the chain
    has them) and its launch time.  Loop thread
    only; at most one exists (``InferenceScheduler._flight``)."""

    __slots__ = ("slots", "reqs", "nxt", "hid", "counts", "t0")

    def __init__(self, slots, reqs, nxt, hid, counts):
        self.slots, self.reqs = slots, reqs
        self.nxt, self.hid, self.counts = nxt, hid, counts
        self.t0 = time.perf_counter()


class InferenceScheduler(Logger):
    """Continuous-batching decode service over a forward chain.

    ``max_slots`` — concurrent requests decoding per step;
    ``window`` — per-request length bound, ``prompt_len + steps <=
    window`` (default: the chain's positional table);
    ``max_queue`` — waiting-request cap beyond the slots (503 above);
    ``queue_timeout`` — default admission deadline in seconds (408
    for requests still queued past it);
    ``prefill_bucket`` — smallest compiled prefill width;
    ``block_size`` / ``kv_blocks`` / ``prefill_chunk`` —
    paged-cache and chunked-prefill knobs (None defers to
    ``root.common.serving.*``; see the module docstring)."""

    def __init__(self, forwards, max_slots=4, window=None,
                 max_queue=32, queue_timeout=30.0, prefill_bucket=8,
                 block_size=None, kv_blocks=None, kv_dtype=None,
                 prefill_chunk=None, warm_buckets=None,
                 request_timeout=None, watchdog=None,
                 shed_block_factor=None, spec=None, spec_k=None,
                 drafter=None, draft_head=None, draft_k_min=None,
                 draft_ema=None, prefix_cache=None, prefix_evict=None,
                 tp=None, role=None, replica_id=None,
                 kv_host_bytes=None, kv_export_bytes=None):
        super(InferenceScheduler, self).__init__()
        refused = serving_refusal(forwards)
        if refused:
            raise ValueError(
                "chain cannot serve through the slot scheduler: %s "
                "(see serving.prefill.serving_refusal)" % refused)
        window = window or serving_window(forwards)
        if not window or int(window) < 2:
            raise ValueError(
                "no usable decode window: pass window= (the chain has "
                "no learned positional table to derive it from)")
        self.forwards = forwards
        self.max_slots = int(max_slots)
        self.window = int(window)
        self.max_queue = int(max_queue)
        self.queue_timeout = float(queue_timeout)
        self.prefill_bucket = int(prefill_bucket)
        #: units that keep ONE fixed state per slot beside the paged
        #: K/V (serving/kv_slots.py).  What follows does not carry
        #: such a state and is refused in words when asked for by
        #: argument; asked for by the configuration's default alone it
        #: is turned off and said so (`_without_state`)
        self._state_units = slot_state_units(forwards)
        #: units whose cache is a STACK of cache layers behind one
        #: block table: the same options move one layer's K and V pair
        #: and are refused or turned off the same way
        self._stack_units = stacked_units(forwards)
        self.block_size = int(
            block_size or _serving_conf("block_size", 16))
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.blocks_per_slot = -(-self.window // self.block_size)
        if kv_blocks is None:
            kv_blocks = _serving_conf("kv_blocks", None)
        self.kv_blocks = int(
            kv_blocks or self.max_slots * self.blocks_per_slot)
        #: KV pool storage dtype: "fp32" (compute-dtype pools; the
        #: parity baseline — token streams byte-identical to PR 5-11)
        #: or "int8" (per-row scales beside the block tables, ~half
        #: the bytes per cached token → ~2x streams per HBM budget;
        #: quality-gated, see serving/kv_quality.py).
        asked = kv_dtype
        kv_dtype = kv_dtype or _serving_conf("kv_dtype", "fp32")
        if kv_dtype == "int8" and not self._without_state(
                "kv_dtype='int8'", True if asked else None, True):
            kv_dtype = "fp32"
        if kv_dtype not in ("fp32", "int8"):
            raise ValueError("kv_dtype must be 'fp32' or 'int8'")
        self.kv_dtype = kv_dtype
        chunk = prefill_chunk if prefill_chunk is not None \
            else _serving_conf("prefill_chunk", 64)
        chunk = int(chunk or 0)
        if chunk and not chunked_supported(forwards):
            self.info("chain cannot prefill in chunks; long prompts "
                      "will prefill one-shot")
            chunk = 0
        #: chunk widths ride compiled executables — power-of-two.
        #: The narrowest chunk (and the one-shot limit) is configured;
        #: the widest follows from the chain's layer types
        self.prefill_chunk = _bucket(chunk, 1, 1 << 30) if chunk else 0
        self.prefill_widest = widest_chunk(forwards, self.prefill_chunk)
        self.warm_buckets = bool(
            _serving_conf("warm_buckets", True)
            if warm_buckets is None else warm_buckets)
        #: whole-request deadline default in seconds (0/None = none
        #: beyond the legacy queue_timeout) — per-submit overridable
        self.request_timeout = float(
            _serving_conf("request_timeout", 120.0)
            if request_timeout is None else request_timeout)
        #: stuck-decode-loop threshold (0 disables the watchdog)
        self.watchdog = float(_serving_conf("watchdog", 300.0)
                              if watchdog is None else watchdog)
        #: shed new submits once the queue's committed block budget
        #: exceeds factor x kv_blocks (0 disables)
        self.shed_block_factor = float(
            _serving_conf("shed_block_factor", 4.0)
            if shed_block_factor is None else shed_block_factor)
        #: speculative decoding (serving/spec.py): draft up to spec_k
        #: tokens per slot by n-gram prompt lookup and score them in
        #: ONE batched verify pass — output streams stay bit-
        #: identical (greedy and per-seed sampling), accepted drafts
        #: are pure latency win.
        spec = bool(self._without_state(
            "speculative decoding (spec)", spec,
            _serving_conf("spec", False)))
        self.spec_k = int(_serving_conf("spec_k", 4)
                          if spec_k is None else spec_k)
        if spec and self.spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if spec and not verify_supported(forwards):
            self.info("chain cannot run the paged verify step; "
                      "speculative decoding disabled")
            spec = False
        self.spec = spec
        self._proposer = NgramProposer(k=self.spec_k) if spec \
            else None
        #: draft source: "ngram" (prompt lookup, zero weights — the
        #: PR 9 baseline) or "model" (Medusa heads over the target's
        #: last hidden state, serving/draft.py — pass the trained
        #: head as ``draft_head``).  Arbitrated PER SLOT at runtime:
        #: the model head needs a hidden state (absent on the first
        #: step after prefill/resume) and per-drafter accept-rate
        #: EMAs pick whichever source earns its drafts; either way
        #: acceptance keeps streams bit-identical to spec-off
        drafter_ = str(_serving_conf("drafter", "ngram")
                       if drafter is None else drafter)
        if drafter_ not in ("ngram", "model"):
            raise ValueError("drafter must be 'ngram' or 'model'")
        if drafter_ == "model" and spec:
            if draft_head is None:
                self.info("drafter='model' needs a trained "
                          "draft_head; falling back to n-gram")
                drafter_ = "ngram"
            elif not draft_supported(forwards):
                self.info("chain has no hidden-state lane for the "
                          "model drafter; falling back to n-gram")
                drafter_ = "ngram"
        self.drafter = drafter_ if spec else "ngram"
        self._draft_head = draft_head \
            if spec and self.drafter == "model" else None
        if self._draft_head is not None:
            d, v = forwards[-1].weights.mem.shape
            if (self._draft_head.d_model,
                    self._draft_head.vocab) != (d, v):
                raise ValueError(
                    "draft_head sized (d=%d, vocab=%d) but the chain "
                    "serves (d=%d, vocab=%d)"
                    % (self._draft_head.d_model,
                       self._draft_head.vocab, d, v))
        #: accept-rate-adaptive draft length (spec mode): per-slot
        #: EMA of accepted/drafted with weight ``draft_ema`` shrinks
        #: the slot's draft k (halving, floor ``draft_k_min``) while
        #: acceptance is poor and grows it back toward spec_k while
        #: acceptance is high — the verify width then buckets to the
        #: power of two covering the longest live draft, so cold
        #: slots stop paying the full-k verify
        self.draft_k_min = int(_serving_conf("draft_k_min", 1)
                               if draft_k_min is None else draft_k_min)
        self.draft_k_min = max(1, min(self.draft_k_min, self.spec_k))
        self.draft_ema = float(_serving_conf("draft_ema", 0.5)
                               if draft_ema is None else draft_ema)
        if not 0.0 < self.draft_ema <= 1.0:
            raise ValueError("draft_ema must be in (0, 1]")
        self.draft_shrink = float(_serving_conf("draft_shrink", 0.5))
        self.draft_grow = float(_serving_conf("draft_grow", 0.8))
        #: cross-request radix prefix cache (serving/prefix_cache.py)
        #: — needs chunked prefill for the cold tail and a
        #: power-of-two block size (the staging/chunk tilings assume
        #: it)
        pfx = bool(self._without_state(
            "the radix prefix cache (prefix_cache): a hit hands over "
            "K/V blocks and no state,", prefix_cache,
            _serving_conf("prefix_cache", False)))
        if pfx and (not self.prefill_chunk
                    or self.block_size & (self.block_size - 1)):
            self.info("prefix cache needs chunked prefill and a "
                      "power-of-two block size; disabled")
            pfx = False
        self.prefix_cache = pfx
        self.prefix_evict = bool(
            _serving_conf("prefix_evict", True)
            if prefix_evict is None else prefix_evict)
        #: host-RAM overflow tier byte budget (serving/kv_host.py):
        #: prefix-cache evictions demote block contents to host RAM
        #: instead of dropping them, and matching admissions promote
        #: them back.  0 disables (the tier-1 baseline); needs the
        #: prefix cache (the tier is keyed by its token paths)
        hb = int(self._without_state(
            "the host KV tier (kv_host_bytes)", kv_host_bytes,
            _serving_conf("kv_host_bytes", 0)) or 0)
        if hb and not pfx:
            self.info("kv_host_bytes needs the prefix cache; host "
                      "tier disabled")
            hb = 0
        self.kv_host_bytes = hb
        #: parked-export byte budget (replaces the flat count cap):
        #: oldest unclaimed records pay when a new park would
        #: overflow it, counted as expiries
        self.kv_export_bytes = int(
            _serving_conf("kv_export_bytes", EXPORT_BYTES)
            if kv_export_bytes is None else kv_export_bytes
            or EXPORT_BYTES)
        #: tensor-parallel mesh size (0 = off): shards the jitted
        #: steps over a {"tp": N} mesh — Megatron weight splits +
        #: head-wise paged pools, per-chip kv_blocks HBM / N
        #: (serving/tp.py; module docstring).  Needs N devices and a
        #: chain whose blocks declare tp layouts.
        if tp is not None and int(tp) == 1:
            tp = 0
        tp = int(self._without_state("tp", tp,
                                     _serving_conf("tp", 0)) or 0)
        if tp == 1:
            tp = 0
        self.tp_ = None
        if tp:
            from veles_tpu.serving.tp import ServingTP, tp_supported
            import jax
            if len(jax.devices()) < tp:
                # not a degrade to take quietly: the caller sized the
                # model and its pools for 1/tp of them per chip
                raise ValueError("tp=%d needs %d devices, found %d"
                                 % (tp, tp, len(jax.devices())))
            if not tp_supported(forwards, tp):
                self.info("chain does not divide over tp=%d (heads/"
                          "d_model/hidden divisibility, or a MoE/"
                          "int8-weight block); serving unsharded", tp)
                tp = 0
            else:
                self.tp_ = ServingTP(tp)
        self.tp = tp
        #: disaggregation role (module docstring): "prefill" accepts
        #: only submit_prefill and parks KV exports; "decode" adopts
        #: them via submit_imported; "both" is the colocated default
        role = str(role or _serving_conf("role", "both")).lower()
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                "role must be 'prefill', 'decode' or 'both'")
        if role != "both":
            self._without_state(
                "role=%r (block export and import)" % role, True, True)
        self.role = role
        #: identity for the per-replica metric labels (satellite of
        #: the last-scheduler-wins gauge fix): the fleet's replica id
        #: when the REST layer passes one, else a process-unique name
        self.replica_id = str(replica_id) if replica_id \
            else "sched%d" % next(_SCHED_SEQ)
        self.stats = ServingMetrics(replica=self.replica_id)
        self._exports = {}           # handle -> export record (lock)
        self._exports_bytes = 0      # parked payload bytes (lock)
        self._exports_claimed = {}   # handle -> fetch time (lock) —
        #                              what tells a double-fetch race
        #                              (409) from a junk handle (404)
        #: per-request tracing (telemetry/reqtrace.py), read ONCE at
        #: construction — the per-boundary gate must be an attribute
        #: test, not a config-tree walk
        self._tron = reqtrace.enabled()
        #: per-tenant metering gate (root.common.tsdb.metering), read
        #: ONCE for the same reason — the step boundary is the hot
        #: path (its cost to the loop: the ``observe`` phase)
        self._metering = _metering_enabled()
        self._queue = collections.deque()
        self._active = {}            # slot -> _Request (decoding)
        self._prefilling = []        # admitted, mid-chunked-prefill
        self._admitting = []         # popped from queue, prefill in
        #                              progress this very iteration —
        #                              cancel() must still see them
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._closed = False
        self._draining = False
        self._drained = threading.Event()
        self._preempts_owed = []     # eviction demands (class bound
        #                              per entry; None = any victim)
        self._aux = collections.deque()  # embed/score jobs (loop-run)
        self._prefix_jobs = collections.deque()  # tiered-KV prefix
        #                              export/import jobs (loop-run,
        #                              one per boundary like _aux)
        self._queued_blocks = 0      # block budget committed in-queue
        self._beat = None            # loop-iteration heartbeat stamp
        self._working = False        # loop mid-iteration (not parked)
        self._tripped_beat = None    # last beat the watchdog fired on
        self._thread = None
        self._watchdog_thread = None
        self._ready = threading.Event()
        self.cache_ = None           # set by the loop thread
        #: the frozen device parameters every jitted entry point of
        #: this server takes (serving/weights.py): built by start(),
        #: dropped by close()
        self.weights_ = None
        self._phases = None          # _LoopPhases, the loop thread's
        self._flight = None          # _Flight: the step not yet read
        self._landed_at = 0.0        # perf_counter of the last landing
        self.prefix_ = None          # radix cache (loop thread too)
        #: host KV tier — constructed HERE (no device dependencies)
        #: so the reference is immutable across threads; only the
        #: loop thread mutates its contents
        self.host_ = HostKVTier(self.kv_host_bytes,
                                self.block_size) \
            if self.kv_host_bytes > 0 else None

    def _without_state(self, what, asked, default):
        """The value of an option that per-slot state is not carried
        through: ``asked`` (the argument; None: not given) or else the
        configuration's ``default``.  On a chain with such state
        (``_state_units``), or with a stack of cache layers behind one
        block table (``_stack_units``), an option that was asked for is
        refused in words, and one that only the configuration's default
        turns on is turned off and logged; never run wrongly."""
        value = default if asked is None else asked
        if not value or not (self._state_units or self._stack_units):
            return value
        if asked is not None:
            raise blocks_only_refusal(what, self._state_units,
                                      self._stack_units)
        self.info("%s off: not carried for %s", what, ", ".join(sorted(
            list(self._state_units.values())
            + list(self._stack_units.values()))))
        return type(value)()

    # -- client side ----------------------------------------------------

    def start(self):
        """Build the serving weights (single-threaded — Array.devmem's
        lazy upload is not re-entrant; serving/weights.py), start the
        decode loop and block until it is READY — cache built and the
        paged-step bucket ladder compiled — so traffic never eats
        warmup compiles as decode stalls."""
        with self._lock:  # two racing start()s must not spawn two loops
            if self._thread is not None:
                started = True
            else:
                started = False
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name="serving-scheduler")
        if started:
            self._ready.wait(600)
            return self
        try:
            t0 = time.monotonic()
            self.weights_ = ServingWeights(self.forwards, tp=self.tp_)
            self.stats.set_weights(self.weights_.bytes_by_dtype,
                                   self.weights_.leaves_cast)
            self.info("serving weights: %d leaves cast, resident %s, "
                      "built in %.2fs", self.weights_.leaves_cast,
                      self.weights_.bytes_by_dtype,
                      time.monotonic() - t0)
            self._thread.start()
        except BaseException:
            self._drop_weights()
            with self._lock:  # release the claim so start() can retry
                self._thread = None
            raise
        self._ready.wait(600)
        if self.watchdog > 0 and self._watchdog_thread is None:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True,
                name="serving-watchdog")
            self._watchdog_thread.start()
        # flight-recorder / debug surface: a hang dump can enumerate
        # this scheduler's live requests (weakly held — close() needs
        # no deregistration)
        reqtrace.register("scheduler", self)
        return self

    def submit(self, prompt, steps, temperature=0.0, top_k=0,
               seed=None, stop_token=None, timeout=None,
               priority=None, stream=False, trace=None,
               resume_tokens=None, tenant=None):
        """Queue one sequence for decoding; returns a Future whose
        result is the full token list (prompt + generated, ending at
        the first generated stop token if one fired).  ``timeout``
        overrides the whole-request deadline (default
        ``request_timeout``; it covers queueing AND decoding — expiry
        mid-decode frees the slot/blocks and fails the future with
        :class:`DeadlineExceededError`).

        ``resume_tokens`` adopts an already-generated prefix — the
        mid-stream-failover lane: the request admits with
        ``generated`` pre-populated, re-prefills prompt + prefix
        through the chunked path (exactly the preempt→resume
        machinery) and samples its next token at draw counter
        ``len(resume_tokens)``, so the continued stream is
        bit-identical to an uninterrupted run of the same
        prompt/seed/params (fp32; int8 pools continue within the
        documented quantization-noise contract).  ``steps`` stays
        the request's TOTAL generation budget — the resumed prefix
        counts against it — and a stream sink receives only the
        NEWLY drawn tokens.

        ``priority`` ("low"/"normal"/"high" or 0–2, default normal)
        sets the request's QoS class: admission order, shed
        threshold/Retry-After, and preemption victimhood are all
        class-aware (module docstring).  ``stream=True`` returns a
        :class:`~veles_tpu.serving.streams.TokenStream` (its
        ``.future`` is the same future the plain path returns)
        yielding tokens as they are accepted.  ``trace`` attaches a
        request trace id (the ``X-Veles-Trace`` propagation value —
        sanitized here; None mints a fresh one): every phase span the
        scheduler records for this request carries it, which is what
        ``trace_export --request`` merges on.

        Raises ``ValueError`` on malformed requests (client errors),
        :class:`QueueFullError` when admission control rejects (queue
        depth, block-pressure shed, or :class:`DrainingError` once a
        drain began)."""
        if self.role == "prefill":
            raise RoleMismatchError(
                "prefill-role replica serves POST /serving/prefill "
                "only — decode requests belong on the decode pool")
        prio = resolve_priority(priority)
        prompt = [int(t) for t in prompt]
        steps = int(steps)
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        resume = [int(t) for t in resume_tokens] \
            if resume_tokens else []
        if len(resume) >= steps:
            raise ValueError(
                "resume_tokens already cover the %d-step budget "
                "(%d resumed) — nothing left to generate"
                % (steps, len(resume)))
        if len(prompt) + steps > self.window:
            raise ValueError(
                "prompt_len + steps = %d exceeds the serving window "
                "(%d)" % (len(prompt) + steps, self.window))
        need = -(-(len(prompt) + steps) // self.block_size)
        if need > self.kv_blocks:
            raise ValueError(
                "request needs %d KV blocks > pool capacity %d "
                "(kv_blocks)" % (need, self.kv_blocks))
        temperature = float(temperature or 0.0)
        top_k = int(top_k or 0)
        if top_k and not temperature:
            raise ValueError(
                "top_k only applies to sampling — set temperature > 0")
        if seed is None:
            # unpinned sampling must draw fresh tokens per request
            seed = int.from_bytes(os.urandom(4), "little")
        ttl = float(timeout or self.request_timeout
                    or self.queue_timeout or 0)
        trace = reqtrace.ensure_trace_id(trace)
        ts = TokenStream(prompt) if stream else None
        if ts is not None:
            ts.trace = trace
        req = _Request(
            prompt, steps, temperature, top_k,
            int(stop_token) if stop_token is not None else None,
            int(seed) & 0xFFFFFFFF,
            time.monotonic() + ttl if ttl > 0 else None,
            priority=prio, sink=ts._push if ts is not None else None,
            trace=trace,
            tenant=str(tenant) if tenant is not None else None)
        if resume:
            # the failover-resume lane rides the preempt→resume
            # machinery: the adopted prefix re-prefills with the
            # prompt and the next draw folds counter len(resume) —
            # the sink sees only tokens drawn HERE
            req.generated = resume
        self._admission_enqueue(req)
        if ts is not None:
            ts._bind(self, req.future)
            return ts
        return req.future

    def _admission_enqueue(self, req):
        """Admission control + enqueue for one built request — the
        shared tail of :meth:`submit`, :meth:`submit_prefill` and
        :meth:`submit_imported` (drain/queue-cap/block-pressure
        checks under the wake lock)."""
        prio = req.priority
        need = self._blocks_for(req)
        cls = CLASS_NAMES[prio]
        with self._wake:
            if self._closed:
                raise SchedulerError("scheduler is closed")
            if self._draining:
                # rolling restart: this replica finishes what it has
                # and takes nothing new — callers retry elsewhere
                self.stats.record_reject(len(self._queue))
                raise DrainingError("scheduler is draining")
            if len(self._queue) >= self.max_queue \
                    and not self._evict_queued_locked(prio):
                self.stats.record_reject(len(self._queue))
                err = QueueFullError(
                    "serving queue full (%d waiting)"
                    % len(self._queue))
                err.retry_after = _RETRY_AFTER[prio]
                raise err
            if self.shed_block_factor > 0 \
                    and self._queued_blocks + need \
                    > self.shed_block_factor * _SHED_FRAC[prio] \
                    * self.kv_blocks:
                # block-pressure shed, LOW class first: each class
                # trips at its own fraction of the factor, so as
                # pressure builds the overload sacrifices low-class
                # work while high-class admission still has headroom
                # — and a shed low client backs off longer
                self.stats.record_shed(self._queued_blocks, cls=cls,
                                       trace=req.trace)
                err = QueueFullError(
                    "overloaded: %d KV blocks committed in-queue "
                    "(pool %d, %s-class shed at factor %.1f)"
                    % (self._queued_blocks, self.kv_blocks, cls,
                       self.shed_block_factor * _SHED_FRAC[prio]))
                err.retry_after = _RETRY_AFTER[prio]
                raise err
            self.stats.record_submit(cls=cls)
            self._enqueue_locked(req)
            self._queued_blocks += need
            self._wake.notify()

    def submit_prefill(self, prompt, seed=None, timeout=None,
                       priority=None, trace=None):
        """Queue one prompt for PREFILL-ONLY service (the
        disaggregated fleet's prefill half; roles "prefill"/"both"):
        the prompt rides the normal admission + chunked-prefill path,
        but instead of decoding, the finished KV blocks are gathered
        RAW (scales included under int8) together with the
        last-position logits and parked under a handle for ``GET
        /serving/kv_export/<handle>``.  The returned future resolves
        to ``{"handle", "prompt_tokens", "blocks"}``.  No sampler
        parameters here — sampling is the decode replica's business
        (it draws from the exported logits with ITS
        temperature/seed, which is what keeps the handed-off stream
        identical to the colocated one)."""
        if self.role == "decode":
            raise RoleMismatchError(
                "decode-role replica imports KV (POST "
                "/serving/kv_import) — prefill belongs on the "
                "prefill pool")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if len(prompt) > self.window:
            raise ValueError(
                "prompt of %d tokens exceeds the serving window (%d)"
                % (len(prompt), self.window))
        prio = resolve_priority(priority)
        ttl = float(timeout or self.request_timeout
                    or self.queue_timeout or 0)
        trace = reqtrace.ensure_trace_id(trace)
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        req = _Request(
            prompt, 1, 0.0, 0, None, int(seed) & 0xFFFFFFFF,
            time.monotonic() + ttl if ttl > 0 else None,
            priority=prio, trace=trace)
        req.export_only = True
        self._admission_enqueue(req)
        return req.future

    def kv_export(self, handle):
        """Claim one parked export record (one-shot — the fetch
        consumes it), or None when the handle is unknown/expired/
        already fetched (:meth:`kv_export_status` tells those
        apart).  The record is the host-side numpy form;
        ``serving/disagg.encode_export`` is the wire envelope."""
        now = time.monotonic()
        with self._lock:
            self._sweep_exports_locked(now)
            rec = self._exports.pop(str(handle), None)
            if rec is not None:
                self._exports_bytes -= rec.get("bytes", 0)
                self._exports_claimed[str(handle)] = now
                self.stats.record_kv_export_fetched()
                self.stats.set_kv_exports_pending(len(self._exports))
            return rec

    def kv_export_status(self, handle):
        """One-shot-fetch disambiguation for the REST layer:
        ``"pending"`` (parked, fetchable), ``"fetched"`` (already
        claimed — a second fetch is a 409 race, not a missing
        record) or ``"unknown"`` (never parked, or expired and
        swept)."""
        with self._lock:
            if str(handle) in self._exports:
                return "pending"
            if str(handle) in self._exports_claimed:
                return "fetched"
            return "unknown"

    def _sweep_exports_locked(self, now=None):
        """TTL housekeeping over the parked export records (caller
        holds the lock): GC expired records, prune the claimed-handle
        memory, and keep the pending gauge honest.  Returns how many
        records expired.  Piggybacked on the decode loop (idle
        replicas sweep on a 1 s condition-wait timeout), so a
        crashed decode pool's unfetched handoffs stop rotting until
        the cap."""
        now = time.monotonic() if now is None else now
        stale = [h for h, r in self._exports.items()
                 if now - r["t"] > EXPORT_TTL]
        for h in stale:
            self._exports_bytes -= self._exports[h].get("bytes", 0)
            del self._exports[h]
        if stale:
            self.stats.record_kv_export_expired(len(stale))
            self.stats.set_kv_exports_pending(len(self._exports))
        dead = [h for h, t in self._exports_claimed.items()
                if now - t > 2 * EXPORT_TTL]
        for h in dead:
            del self._exports_claimed[h]
        return len(stale)

    def submit_imported(self, export, steps, temperature=0.0,
                        top_k=0, seed=None, stop_token=None,
                        timeout=None, priority=None, stream=False,
                        trace=None):
        """Adopt a prefill replica's export record (the decoded form
        of ``GET /serving/kv_export/<handle>``; roles
        "decode"/"both") and decode ``steps`` tokens: admission
        claims the full prompt+steps block budget, the exported
        blocks scatter straight into the slot's table (no prefill
        pass at all — the decode replica's TTFT is one block
        scatter), and the first token samples from the exported
        logits with the caller's sampler settings — the stream is
        identical to a colocated ``submit`` of the same prompt
        (fp32 bit-exact; int8 byte-identical resident KV).  Raises
        ``ValueError`` on a record that doesn't match this replica's
        pool layout (kv_dtype / block_size / window)."""
        if self.role == "prefill":
            raise RoleMismatchError(
                "prefill-role replica exports KV — imports belong "
                "on the decode pool")
        prompt = [int(t) for t in export.get("prompt", ())]
        steps = int(steps)
        if not prompt or int(export.get("length", -1)) != len(prompt):
            raise ValueError("export record prompt/length mismatch")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        if str(export.get("kv_dtype")) != self.kv_dtype:
            raise ValueError(
                "export kv_dtype %r != this replica's %r — "
                "disaggregated pools must share a storage dtype"
                % (export.get("kv_dtype"), self.kv_dtype))
        if int(export.get("block_size", 0)) != self.block_size:
            raise ValueError(
                "export block_size %s != this replica's %d"
                % (export.get("block_size"), self.block_size))
        if len(prompt) + steps > self.window:
            raise ValueError(
                "prompt_len + steps = %d exceeds the serving window "
                "(%d)" % (len(prompt) + steps, self.window))
        need = -(-(len(prompt) + steps) // self.block_size)
        if need > self.kv_blocks:
            raise ValueError(
                "request needs %d KV blocks > pool capacity %d "
                "(kv_blocks)" % (need, self.kv_blocks))
        temperature = float(temperature or 0.0)
        top_k = int(top_k or 0)
        if top_k and not temperature:
            raise ValueError(
                "top_k only applies to sampling — set temperature > 0")
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        prio = resolve_priority(priority)
        ttl = float(timeout or self.request_timeout
                    or self.queue_timeout or 0)
        trace = reqtrace.ensure_trace_id(trace)
        ts = TokenStream(prompt) if stream else None
        if ts is not None:
            ts.trace = trace
        req = _Request(
            prompt, steps, temperature, top_k,
            int(stop_token) if stop_token is not None else None,
            int(seed) & 0xFFFFFFFF,
            time.monotonic() + ttl if ttl > 0 else None,
            priority=prio, sink=ts._push if ts is not None else None,
            trace=trace)
        req.kv_import = export
        self._admission_enqueue(req)
        if ts is not None:
            ts._bind(self, req.future)
            return ts
        return req.future

    def _submit_prefix_job(self, kind, payload):
        if not self.prefix_cache:
            raise ValueError(
                "prefix %s needs the prefix cache enabled" % kind)
        fut = concurrent.futures.Future()
        with self._wake:
            if self._closed:
                raise SchedulerError("scheduler is closed")
            if len(self._prefix_jobs) >= self.max_queue:
                raise QueueFullError(
                    "prefix-transfer queue full (%d waiting)"
                    % len(self._prefix_jobs))
            self._prefix_jobs.append((kind, payload, fut))
            self._wake.notify()
        return fut

    def submit_prefix_export(self, tokens):
        """Queue a peer-prefix read (the fleet-wide prefix store's
        GET half): the future resolves to an export-shaped record —
        no logits, prompt truncated to the covered prefix — holding
        the RAW blocks of the longest resident prefix of ``tokens``
        across BOTH tiers (device trie, then its host-tier
        extension), or None when nothing is resident.  Works on a
        draining replica: reads don't extend its in-flight set,
        and a drained peer's warm state is exactly what's worth
        rescuing."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("tokens must be non-empty")
        return self._submit_prefix_job("export", tokens)

    def submit_prefix_import(self, record):
        """Queue a peer-prefix adoption (the router ships a
        :meth:`submit_prefix_export` record from the replica that
        had it): new chunks take freshly claimed device blocks and
        join the trie, so the triggering request — and every later
        one — admits warm here.  The future resolves to ``{"blocks":
        adopted}``.  Raises ``ValueError`` on a record that doesn't
        match this replica's pool layout."""
        if str(record.get("kv_dtype")) != self.kv_dtype:
            raise ValueError(
                "prefix record kv_dtype %r != this replica's %r"
                % (record.get("kv_dtype"), self.kv_dtype))
        if int(record.get("block_size", 0)) != self.block_size:
            raise ValueError(
                "prefix record block_size %s != this replica's %d"
                % (record.get("block_size"), self.block_size))
        prompt = [int(t) for t in record.get("prompt", ())]
        if not prompt or int(record.get("length", -1)) != len(prompt):
            raise ValueError("prefix record prompt/length mismatch")
        if len(prompt) % self.block_size:
            raise ValueError("prefix record must be block-aligned")
        return self._submit_prefix_job("import", record)

    def _enqueue_locked(self, req, front=False):
        """Insert one request into the class-ordered queue (highest
        class first, FIFO within a class); ``front=True`` requeues a
        preempted victim at the head of ITS class so it resumes
        before later same-class arrivals."""
        q = self._queue
        if front:
            i = 0
            while i < len(q) and q[i].priority > req.priority:
                i += 1
        else:
            i = len(q)
            while i > 0 and q[i - 1].priority < req.priority:
                i -= 1
        q.insert(i, req)

    def _evict_queued_locked(self, prio):
        """Depth-cap relief for a higher-class arrival: shed the
        YOUNGEST queued strictly-lower-class request (it loses the
        least wait) and report whether a seat opened.  The victim
        gets the same structured 503 + its class's Retry-After a
        front-door shed would have given it."""
        victim = None
        for req in reversed(self._queue):
            if req.priority < prio:
                victim = req
                break
        if victim is None:
            return False
        self._queue.remove(victim)
        self._queued_blocks -= self._blocks_for(victim)
        vcls = CLASS_NAMES[victim.priority]
        self.stats.record_shed(self._queued_blocks, cls=vcls,
                               trace=victim.trace)
        err = QueueFullError(
            "shed while queued: a higher-priority request took the "
            "last queue seat")
        err.retry_after = _RETRY_AFTER[victim.priority]
        victim.fail(err)
        return True

    def _budget_tokens(self, req):
        """The token span a request's block budget must cover: prompt
        + decode steps, or just the prompt for a prefill-export
        request (it never decodes here — the decode replica claims
        the steps' blocks on ITS pool)."""
        if req.export_only:
            return len(req.prompt)
        return len(req.prompt) + req.steps

    def _blocks_for(self, req):
        """The block budget a request commits."""
        return -(-self._budget_tokens(req) // self.block_size)

    def cancel(self, future, reason="cancelled by client"):
        """Cancel the request behind ``future`` (client disconnected
        or gave up): a queued request fails immediately; an in-flight
        one is reaped at the next chunk/decode boundary, returning its
        slot and KV blocks to the pool.  Returns True when the future
        belonged to this scheduler and was still unfinished."""
        victim = None
        with self._wake:
            for req in self._queue:
                if req.future is future:
                    self._queue.remove(req)
                    self._queued_blocks -= self._blocks_for(req)
                    victim = req
                    break
            else:
                for req in list(self._prefilling) \
                        + list(self._active.values()) \
                        + list(self._admitting):
                    if req.future is future:
                        req.cancelled = True
                        victim = req
                        self._wake.notify()
                        break
        if victim is None:
            return False
        if victim.slot is None and not victim.cancelled:
            # was queued: no device state to release — fail right here
            victim.fail(RequestCancelledError(reason))
            self.stats.record_cancel(len(victim.generated),
                                     trace=victim.trace)
        return True

    def request_preempt(self, n=1, below=None):
        """Ask the loop to evict ``n`` active requests at the next
        decode boundary: victim selection takes the LOWEST priority
        class first, youngest within it (it loses the least
        re-prefill work).  ``below`` bounds victimhood to requests of
        priority strictly under it (a demand with no qualifying
        victim is dropped); ``None`` preempts from any class.  Each
        victim's blocks return to the pool, its generated prefix is
        kept, and it requeues at the front of its class to resume via
        re-prefill — the mechanism priority scheduling drives."""
        with self._wake:
            self._preempts_owed.extend(
                [None if below is None else int(below)] * int(n))
            self._wake.notify()

    def submit_embed(self, rows):
        """Queue ONE batched embedding job (``/v1/embeddings``):
        ``rows`` are non-empty token lists; the future resolves to a
        list of pooled unit-norm vectors (see
        :func:`serving.openai_api.embed_pool`).  The job runs on the
        decode loop BETWEEN decode boundaries — embeddings share the
        engine without breaking the one-jax-thread invariant."""
        return self._submit_aux("embed", rows)

    def submit_score(self, rows):
        """Queue ONE batched classifier-scoring job
        (``/v1/classify``): the future resolves to per-row class
        log-probabilities from the full chain's last-position
        logits."""
        return self._submit_aux("score", rows)

    def _submit_aux(self, kind, rows):
        rows = [[int(t) for t in r] for r in rows]
        if not rows or any(not r for r in rows):
            raise ValueError("input must be non-empty token rows")
        widest = max(len(r) for r in rows)
        if widest > self.window:
            raise ValueError(
                "input row of %d tokens exceeds the serving window "
                "(%d)" % (widest, self.window))
        if kind == "embed":
            from veles_tpu.serving.openai_api import embed_supported
            if not embed_supported(self.forwards):
                raise ValueError("chain cannot serve embeddings")
        fut = concurrent.futures.Future()
        with self._wake:
            if self._closed:
                raise SchedulerError("scheduler is closed")
            if self._draining:
                raise DrainingError("scheduler is draining")
            if len(self._aux) >= self.max_queue:
                self.stats.record_reject(len(self._aux))
                raise QueueFullError(
                    "aux queue full (%d waiting)" % len(self._aux))
            self._aux.append((kind, rows, fut))
            self._wake.notify()
        return fut

    def _aux_tick(self):
        """Run ONE queued embed/score job (one jitted pass) at this
        boundary — like a prefill chunk, it delays in-flight decode by
        a single bounded pass, not by the whole aux backlog."""
        with self._lock:
            if not self._aux:
                return
            kind, rows, fut = self._aux.popleft()
        if fut.done():   # consumer already gave up
            return
        from veles_tpu.serving.openai_api import (
            pooled_embeddings, score_rows)
        # read back at once: nothing of it is left to poll
        self._phases.dispatched(None)
        try:
            faults.fire("serving.scheduler.aux")
            if kind == "embed":
                out = pooled_embeddings(
                    self.forwards, rows, self.window,
                    params=self.weights_.params)
            else:
                out = score_rows(
                    self.forwards, rows, self.window, tp=self.tp_,
                    params=self.weights_.params)
        except Exception as e:
            fut.set_exception(
                e if isinstance(e, SchedulerError)
                else SchedulerError(repr(e)))
            return
        try:
            fut.set_result(out)
        except concurrent.futures.InvalidStateError:
            pass

    def _prefix_tick(self, cache):
        """Run ONE queued prefix export/import job at this boundary —
        the same decode-stall bound as a prefill chunk or an aux
        pass."""
        with self._lock:
            if not self._prefix_jobs:
                return
            kind, payload, fut = self._prefix_jobs.popleft()
        if fut.done():   # consumer already gave up
            return
        self._phases.dispatched(None)   # a gather read back, or pools
        try:
            if kind == "export":
                out = self._prefix_export_job(cache, payload)
            else:
                out = self._prefix_import_job(cache, payload)
        except Exception as e:
            fut.set_exception(
                e if isinstance(e, SchedulerError)
                else SchedulerError(repr(e)))
            self._recover_pools(cache, e)
            return
        try:
            fut.set_result(out)
        except concurrent.futures.InvalidStateError:
            pass

    def _prefix_export_job(self, cache, tokens):
        """Gather the longest resident prefix of ``tokens`` — the
        device trie walk, then its host-tier extension (already host
        numpy, the gather is free) — into an export-shaped record."""
        if self.prefix_ is None:
            return None
        bs = self.block_size
        ids = self.prefix_.resident_prefix(tokens)
        layers = cache.export_blocks(ids) if ids else None
        if self.host_ is not None:
            entries = self.host_.match(tokens, len(ids))
            for e in entries:
                if layers is None:
                    layers = {i: {nm: a.mem.copy()
                                  for nm, a in row.items()}
                              for i, row in e.layers.items()}
                    continue
                if set(e.layers) != set(layers):
                    break  # defensive: mismatched chain shape
                layers = {i: {nm: numpy.concatenate(
                    [layers[i][nm], e.layers[i][nm].mem])
                    for nm in layers[i]} for i in layers}
        if layers is None:
            return None
        blocks = next(iter(next(iter(
            layers.values())).values())).shape[0]
        covered = blocks * bs
        from veles_tpu.serving.disagg import mint_handle
        return {
            "handle": mint_handle(),
            "prompt": [int(t) for t in tokens[:covered]],
            "length": covered,
            "kv_dtype": self.kv_dtype,
            "block_size": bs,
            "layers": layers,
        }

    def _prefix_import_job(self, cache, record):
        """Adopt a peer's prefix record: chunks already resident
        keep their incumbents; the new consecutive extension
        scatters into freshly claimed blocks and joins the trie.
        Fires the promote fault point — a peer import IS a
        promotion into the device tier, just from a remote source."""
        pfx = self.prefix_
        if pfx is None:
            raise SchedulerError("no prefix cache on this replica")
        bs = self.block_size
        tokens = record["prompt"]
        total = int(record["length"]) // bs
        dev = pfx.resident_prefix(tokens)
        n_new = total - len(dev)
        ids = None
        while n_new > 0:
            ids = cache.take_free_blocks(n_new)
            if ids is not None:
                break
            n_new -= 1  # adopt the longest extension that fits
        if not n_new or ids is None:
            return {"blocks": 0}
        try:
            faults.fire("scheduler.kv.promote")
            sliced = {i: {nm: a[len(dev):len(dev) + n_new]
                          for nm, a in layer.items()}
                      for i, layer in record["layers"].items()}
            cache.import_blocks(ids, sliced)
        except Exception:
            cache.reclaim(ids)
            raise
        covered = (len(dev) + n_new) * bs
        _, rejected = pfx.insert(
            [int(t) for t in tokens[:covered]], dev + ids)
        if rejected:
            cache.reclaim(rejected)
        self._sync_prefix_gauges()
        return {"blocks": n_new}

    def drain(self, timeout=None):
        """Begin a graceful drain: admission closes (submits raise
        :class:`DrainingError` — 503 + Retry-After material), every
        queued and in-flight request runs to completion, then the
        ``drained`` event sets.  With ``timeout`` the call blocks for
        the drain to finish and returns whether it did; otherwise it
        returns immediately."""
        with self._wake:
            first = not self._draining
            self._draining = True
            if not (self._queue or self._active or self._prefilling
                    or self._aux):
                self._drained.set()
            self._wake.notify()
        if first:
            self.stats.record_drain()
            self.info("draining: admission closed, %d in flight",
                      self.in_flight)
        if timeout is not None:
            return self._drained.wait(timeout)
        return self._drained.is_set()

    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        return self._drained.is_set()

    @property
    def in_flight(self):
        """Requests the scheduler still owes an answer (queued +
        prefilling + decoding)."""
        with self._lock:
            return len(self._queue) + len(self._prefilling) \
                + len(self._active) + len(self._admitting) \
                + len(self._aux)

    def _kv_snapshot(self):
        # "kv_mode": the one layout; dashboards and the benchmark's
        # ``served_by`` record read the key
        out = {"kv_mode": "paged",
               "prefill_chunk": self.prefill_chunk,
               "prefill_widest": self.prefill_widest,
               "prefilling": len(self._prefilling),
               "tp": self.tp,
               "role": self.role,
               "replica": self.replica_id,
               "kv_exports_pending": len(self._exports)}
        weights = self.weights_
        out["weights_dtype"] = \
            weights.dtype if weights is not None else None
        cache = self.cache_
        # did every call that returns the cache's device state write
        # it in place (``note_swap``, serving/kv_slots.py)?  None before the
        # first such call (the warm-up's first step, where it runs)
        out["pools_in_place"] = cache.pool_copies == 0 \
            if cache is not None and cache.pool_swaps else None
        out["kv_dtype"] = self.kv_dtype
        out["kv_bytes_per_token"] = \
            cache.bytes_per_token() if cache is not None else None
        out["kv_block_size"] = self.block_size
        out["kv_blocks_total"] = self.kv_blocks
        # the loop thread owns the free lists; these reads are
        # monitoring-grade (len() is atomic enough for a gauge)
        out["kv_blocks_used"] = \
            cache.used_blocks if cache is not None else 0
        out["kv_blocks_free"] = \
            cache.free_blocks if cache is not None \
            else self.kv_blocks
        # what is resident for it: the paged K/V pools, and the
        # per-slot state beside them (0 for a chain with none)
        out["state_bytes"] = \
            cache.state_bytes() if cache is not None else None
        out["state_units"] = sorted(self._state_units.values())
        out["spec"] = self.spec
        out["spec_k"] = self.spec_k if self.spec else 0
        out["drafter"] = self.drafter if self.spec else None
        out["draft_k_min"] = self.draft_k_min if self.spec else 0
        pfx = self.prefix_
        out["prefix_cache"] = pfx is not None
        if pfx is not None:  # loop-owned; monitoring-grade reads
            total = pfx.hits + pfx.misses
            out["prefix_cache_hits"] = pfx.hits
            out["prefix_cache_misses"] = pfx.misses
            out["prefix_cache_evictions"] = pfx.evictions
            out["prefix_cache_hit_blocks"] = pfx.hit_blocks
            out["prefix_cache_blocks_resident"] = pfx.resident
            out["prefix_cache_blocks_shared"] = pfx.shared_blocks()
            out["prefix_cache_hit_rate"] = \
                round(pfx.hits / total, 4) if total else None
            # the cache-topology advertisement: rolling path digests
            # of every resident prefix, BOTH tiers (a host-resident
            # prefix is promotable, so it is routable warmth too).
            # The router matches prompts against these to route on
            # who actually holds the longest prefix
            digs = pfx.path_digests(_DIGEST_MAX)
            host = self.host_
            if host is not None:
                digs.extend(host.digests()[:max(
                    0, _DIGEST_MAX - len(digs))])
                out["kv_host_blocks"] = host.blocks
                out["kv_host_bytes"] = host.bytes
                out["kv_host_promotions"] = host.promotions
                out["kv_host_demotions"] = host.demotions
                out["kv_host_evictions"] = host.evictions
            out["prefix_digests"] = digs
        return out

    def metrics(self):
        with self._lock:
            depth, active = len(self._queue), len(self._active)
            draining = self._draining
            queued_blocks = self._queued_blocks
        snap = self.stats.snapshot(queue_depth=depth,
                                   active_slots=active,
                                   max_slots=self.max_slots,
                                   kv=self._kv_snapshot())
        snap["window"] = self.window
        snap["draining"] = draining
        snap["drained"] = self._drained.is_set()
        snap["queued_kv_blocks"] = queued_blocks
        snap["tenants"] = self.stats.tenant_usage_snapshot()
        return snap

    def debug_requests(self):
        """Live in-flight request table (``GET /debug/requests`` and
        the flight-recorder bundle): one row per request the
        scheduler still owes an answer, with its trace id, phase,
        class, age and the KV blocks it holds.  Monitoring-grade
        reads — the loop thread owns the cache tables, so block
        counts are len()/int-read consistent, not transactional."""
        now = time.monotonic()
        cache = self.cache_
        with self._lock:
            rows = [("queued", r) for r in self._queue] \
                + [("admitting", r) for r in self._admitting] \
                + [("prefill", r) for r in self._prefilling] \
                + [("decode", r) for r in self._active.values()]
        out = []
        for phase, req in rows:
            blocks = shared = 0
            if req.slot is not None and cache is not None:
                blocks = int(cache.n_blocks[req.slot])
                shared = int(cache.n_shared[req.slot])
            row = {
                "trace": req.trace,
                "phase": phase,
                "cls": CLASS_NAMES[req.priority],
                "tenant": req.tenant,
                "age_s": round(now - req.t_submit, 3),
                "prompt_tokens": len(req.prompt),
                "tokens": len(req.generated),
                "steps": req.steps,
                "blocks": blocks,
                "blocks_shared": shared,
                "blocks_budget": self._blocks_for(req),
                "preempts": req.preempts,
                "stream": req.sink is not None,
                "deadline_in_s": round(req.deadline - now, 3)
                if req.deadline is not None else None,
            }
            if phase == "prefill":
                row["prefill_off"] = req.pf_off
            out.append(row)
        return out

    def check_kv(self):
        """Invariant sweep over the paged cache INCLUDING the prefix
        cache's resident blocks (tests/soaks): every block is
        exactly one of {trash, free, resident, slot-private} and
        every slot's shared prefix is resident.  Host tables only: a
        step in flight holds no block and moves none (what a row it
        will discard does is on the device alone), so any thread may
        ask and nothing is landed for it."""
        cache = self.cache_
        if cache is None:
            return
        cache.check(resident=self.prefix_.resident_blocks()
                    if self.prefix_ is not None else ())

    def close(self):
        """Stop the loop, fail every unfinished request, and return
        every in-flight slot/block to the cache (a close with traffic
        in flight must not leak KV blocks — ``cache_.check()`` holds
        afterward)."""
        with self._wake:
            if self._closed:
                return
            self._closed = True
            self._wake.notify()
        loop_dead = True
        if self._thread is not None:
            self._thread.join(30)
            loop_dead = not self._thread.is_alive()
        err = SchedulerError("scheduler closed")
        with self._lock:
            pending = list(self._queue) + list(self._prefilling) \
                + list(self._active.values()) + list(self._admitting)
            aux = list(self._aux) + list(self._prefix_jobs)
            self._queue.clear()
            self._prefilling = []
            self._active.clear()
            self._admitting = []
            self._aux.clear()
            self._prefix_jobs.clear()
            self._exports.clear()
            self._exports_bytes = 0
            self._exports_claimed.clear()
            self._queued_blocks = 0
        host = self.host_
        if host is not None:
            host.clear()   # release the Watcher's host bytes
        for _, _, fut in aux:
            if not fut.done():
                try:
                    fut.set_exception(err)
                except concurrent.futures.InvalidStateError:
                    pass
        cache = self.cache_ if loop_dead else None
        for req in pending:
            if req.slot is not None and cache is not None:
                # the loop thread is dead (joined above): releasing
                # its cache bookkeeping from here cannot race it
                self._release_slot(req, cache)
            req.fail(err)
        if cache is not None:
            self._sync_kv_gauges(cache)
        if loop_dead:   # a live loop still steps on them
            self._drop_weights()
        self._drained.set()
        with self._lock:  # claim the watchdog before joining it
            wd, self._watchdog_thread = self._watchdog_thread, None
        if wd is not None:
            wd.join(5)

    def _drop_weights(self):
        weights, self.weights_ = self.weights_, None
        if weights is not None:
            weights.close()
            self.stats.set_weights(
                dict.fromkeys(weights.bytes_by_dtype, 0), 0)

    # -- decode loop ----------------------------------------------------

    def _make_cache(self):
        return PagedKVCache(self.forwards, self.max_slots, self.window,
                            block_size=self.block_size,
                            kv_blocks=self.kv_blocks,
                            kv_dtype=self.kv_dtype, tp=self.tp_)

    def _warm_paged(self, cache):
        """Compile the paged step's (occupancy, depth) bucket ladder
        BEFORE traffic: a bucket's first compile would otherwise land
        inside live serving as a multi-second decode stall (exactly
        the tail latency the buckets exist to remove).  The dummy
        batches are all padding rows — token 0 at position 0 through
        an all-zero block table, i.e. reads and writes confined to
        the reserved trash block."""
        buckets = sorted({_bucket(n, 1, self.max_slots)
                          for n in range(1, self.max_slots + 1)})
        depths = sorted({_bucket(n, 1, cache.blocks_per_slot)
                         for n in range(1, cache.blocks_per_slot + 1)})
        # n-gram-only schedulers verify at ONE fixed spec_k width, so
        # warmup compiles one executable per (B, T) — the pre-PR 20
        # count.  Only model-drafter schedulers (draft head attached)
        # ride the adaptive pow2 width ladder (see _step_verify), and
        # only they warm it.
        if not self.spec:
            ks = []
        elif self._draft_head is not None:
            ks = sorted({_bucket(n, 1, self.spec_k)
                         for n in range(1, self.spec_k + 1)})
        else:
            ks = [_bucket(self.spec_k, 1, self.spec_k)]
        want_h = self._draft_head is not None
        t0 = time.monotonic()
        for b in buckets:
            for t in depths:
                paged_decode_step(
                    self.forwards, cache,
                    numpy.zeros((b,), numpy.int32),
                    numpy.zeros((b,), numpy.int32),
                    numpy.zeros((b, t), numpy.int32),
                    numpy.zeros((b,), numpy.float32),
                    numpy.zeros((b,), numpy.int32),
                    numpy.zeros((b,), numpy.uint32),
                    numpy.zeros((b,), numpy.int32),
                    want_hidden=want_h,
                    params=self.weights_.params)
                for kk in ks:
                    # the verify ladder rides the same dummy trash-
                    # block convention, one executable per (B, T, k)
                    verify_step_paged(
                        self.forwards, cache,
                        numpy.zeros((b, kk + 1), numpy.int32),
                        numpy.zeros((b,), numpy.int32),
                        numpy.ones((b,), numpy.int32),
                        numpy.zeros((b, t), numpy.int32),
                        numpy.zeros((b,), numpy.float32),
                        numpy.zeros((b,), numpy.int32),
                        numpy.zeros((b,), numpy.uint32),
                        numpy.zeros((b,), numpy.int32),
                        want_hidden=want_h,
                        params=self.weights_.params)
        self.info("paged-step warmup: %d occupancy x %d depth x "
                  "%d spec buckets in %.2fs", len(buckets),
                  len(depths), len(ks) + 1, time.monotonic() - t0)

    def _loop(self):
        try:
            cache = self._make_cache()
            if self.prefix_cache:
                self.prefix_ = RadixPrefixCache(self.block_size)
            if self.warm_buckets:
                self._warm_paged(cache)
            self.cache_ = cache
            self.stats.set_kv_dtype(self.kv_dtype,
                                    cache.bytes_per_token())
            self.stats.set_state_bytes(cache.state_bytes())
        except Exception as e:  # surface init failures to clients
            with self._wake:
                self._closed = True
                pending = list(self._queue)
                self._queue.clear()
            self._drop_weights()   # close() returns early from here on
            self._ready.set()
            for req in pending:
                req.future.set_exception(SchedulerError(repr(e)))
            raise
        self._ready.set()
        phases = self._phases = _LoopPhases()
        try:
            while self._pass(cache):
                with phases("observe"):
                    self.stats.record_loop_pass(
                        *phases.drain(), pool_copies=cache.pool_copies)
        finally:
            # the tokens of a step still in flight belong to their
            # requests before close() fails what is unfinished
            self._land(cache)
            # what the last pass and the wait before close() took
            phases.close()
            self.stats.record_loop_pass(*phases.drain(), passes=0)

    def _idle_locked(self):
        # (a step in flight whose riders have all left their slots
        # is landed by the next pass: the loop never parks on one)
        return not (self._closed or self._queue or self._active
                    or self._prefilling or self._preempts_owed
                    or self._aux or self._prefix_jobs
                    or self._flight is not None)

    def _pass(self, cache):
        """One pass of the loop: park until there is work, then one
        reap + admit + chunk + decode step.  False once closed.
        Every instant is charged to one of ``PHASES``: ``admit``
        where no block names another."""
        phases = self._phases
        # the wait for the lock (the HTTP threads hold it in submit())
        # and the work under it
        with phases("admit.queue"), self._wake:
            self._working = False
            if self._idle_locked():
                with phases("parked"):
                    while self._idle_locked():
                        if self._draining:
                            self._drained.set()
                        # parked KV exports keep a 1 s
                        # housekeeping tick alive so their TTL is
                        # enforced even on an idle prefill replica
                        # (no decode work ever wakes it)
                        self._wake.wait(
                            1.0 if self._exports else None)
                        if self._exports:
                            self._sweep_exports_locked()
            if self._closed:
                return False
            # the watchdog measures from here: one iteration =
            # one reap + admit + chunk + decode step
            self._working = True
            self._beat = time.monotonic()
            self._expire_locked()
            if self._exports:
                self._sweep_exports_locked()
            admits = []
            while self._queue and self._can_admit(
                    cache, self._queue[0]):
                req = self._queue.popleft()
                self._queued_blocks -= self._blocks_for(req)
                if not self._admit_claim(cache, req):
                    # a racing claim in this same batch consumed
                    # the headroom the peek counted — requeue at
                    # the front and retry next boundary
                    self._queue.appendleft(req)
                    self._queued_blocks += self._blocks_for(req)
                    break
                admits.append(req)
                self._admitting.append(req)
            # priority preemption: the head of the class-ordered
            # queue outranks an active lower-class request but
            # could not admit — owe ONE eviction at this boundary
            # (one per iteration bounds thrash; the victim's
            # freed blocks seat the head at the next boundary)
            if self._queue and not self._preempts_owed:
                head = self._queue[0]
                if head.priority > 0 \
                        and not self._can_admit(cache, head) \
                        and any(r.priority < head.priority
                                for r in self._active.values()):
                    self._preempts_owed.append(head.priority)
        # jax work OUTSIDE the lock: submit() must never block on
        # a device step
        faults.fire("serving.scheduler.loop")
        with phases("admit.reap"):
            # the except paths below recover at once; this catches a
            # call that failed where none could (a promotion, under
            # the lock)
            self._recover_pools(cache)
            self._reap(cache)
            self._do_preempts(cache)
        with phases("observe"):
            self._sync_kv_gauges(cache)
        # a pass that admits is the loop's longest (the joiner's
        # staging rows are built on this thread, ``_begin_admit``),
        # and a chunk that is a prompt's last ends in the first
        # token's readback: where nothing older waits to prefill, the
        # joiners' first chunk goes out in the NEXT pass, so the
        # decoding streams never wait for both in one gap
        fresh = bool(admits) and not self._prefilling
        for req in admits:
            if req.slot is not None:   # else: failed by a recovery
                self._begin_admit(req, cache)
            with self._lock:
                self._admitting.remove(req)
        if self._aux:
            with phases("aux"):
                self._aux_tick()
        if self._prefix_jobs:
            with phases("aux"):
                self._prefix_tick(cache)
        if self._prefilling and not fresh:
            with phases("prefill"):
                self._prefill_tick(cache)
        if self._active or self._flight is not None:
            self._step(cache)
        return True

    def _can_admit(self, cache, req):
        """Admission sizing for the head-of-queue request.  A warm
        prompt (prefix-cache hit) needs only its COLD blocks —
        ``ceil(cold_tokens / block_size)`` plus decode headroom — so
        cache hits raise the concurrent-stream ceiling; evictable
        refcount-0 resident blocks count as headroom too."""
        total = self._budget_tokens(req)
        if not cache.free_slots:
            return False
        need = cache.blocks_needed(total)
        head = cache.free_blocks
        if self.prefix_ is not None:
            if req.kv_import is None:   # imports never match warm
                seq = list(req.prompt) + list(req.generated)
                need -= self.prefix_.peek(
                    seq,
                    max_blocks=(len(seq) - 1) // cache.block_size)
            if self.prefix_evict:
                head += self.prefix_.evictable_blocks()
        return need <= head

    def _admit_claim(self, cache, req):
        """Claim a slot + blocks for one admitted request: pin the
        longest resident prefix (capped so >= 1 token stays cold —
        the first-token logits must come from somewhere), evict
        cold residents if the free list is short, then alloc with
        the matched blocks heading the table."""
        total = self._budget_tokens(req)
        handle = None
        # an IMPORT scatters into its leading table blocks — they
        # must be privately owned, never prefix-cache residents, so
        # imports skip the warm match entirely
        if self.prefix_ is not None and req.kv_import is None:
            seq = list(req.prompt) + list(req.generated)
            if self.host_ is not None:
                # promote the host-tier extension FIRST so the match
                # below pins (and the hit stats count) the full warm
                # prefix; net-zero on the free list — each promoted
                # block replaces a cold private block the admission
                # would have claimed anyway
                self._promote_host(cache, seq)
            handle = self.prefix_.match(
                seq, max_blocks=(len(seq) - 1) // cache.block_size)
            self.stats.record_prefix_lookup(len(handle),
                                            cache.block_size)
            if not len(handle):
                handle = None
        matched = len(handle) if handle is not None else 0
        need_new = cache.blocks_needed(total) - matched
        if self.prefix_ is not None and self.prefix_evict \
                and need_new > cache.free_blocks:
            freed = self._evict_prefix(cache,
                                       need_new - cache.free_blocks)
            if freed:
                cache.reclaim(freed)
                self.stats.record_prefix_evict(len(freed))
        slot = cache.alloc(
            total, shared=handle.blocks if handle is not None else ())
        if slot is None:
            if handle is not None:
                self.prefix_.release(handle)
            return False
        req.slot = slot
        req.prefix_handle = handle
        req.pf_matched = matched
        return True

    def _release_slot(self, req, cache, finished=False):
        """Return one request's slot, blocks and prefix pins.  A
        request that FINISHED cleanly donates the full blocks of its
        prompt + generated stream to the prefix cache (insert-on-
        release) — the warm state future identical prefixes match."""
        pfx = self.prefix_
        if req.slot is None:
            if req.prefix_handle is not None:
                pfx.release(req.prefix_handle)
                req.prefix_handle = None
            return
        if pfx is None:
            cache.release(req.slot)
        else:
            donate = 0
            seq = None
            if finished:
                seq = list(req.prompt) + list(req.generated)
                # the FINAL token was sampled but never fed back, so
                # its K/V row was never written — donate only blocks
                # fully covered by written positions [0, len - 1)
                # (the same bound the admission match caps at)
                donate = (len(seq) - 1) // cache.block_size \
                    - req.pf_matched
            shared, donated = cache.release(req.slot,
                                            donate=max(0, donate))
            if req.prefix_handle is not None:
                pfx.release(req.prefix_handle)
                req.prefix_handle = None
            if seq is not None and (shared or donated):
                _, rejected = pfx.insert(seq, shared + donated)
                if rejected:  # an identical twin donated first
                    cache.reclaim(rejected)
            self._sync_prefix_gauges()
        req.slot = None
        req.pf_matched = 0
        # the hidden the draft head conditions on is per-position
        # host state — a resume re-prefills and re-earns it, and a
        # finished request must not pin a d_model float vector
        req.hid = None

    def _sync_prefix_gauges(self):
        if self.prefix_ is not None:
            self.stats.set_prefix_blocks(self.prefix_.resident,
                                         self.prefix_.shared_blocks())

    def _sync_host_gauges(self):
        if self.host_ is not None:
            self.stats.set_kv_host(self.host_.blocks,
                                   self.host_.bytes)

    def _evict_prefix(self, cache, n):
        """Trie eviction with host-tier demotion: before the device
        blocks go back to the free list, their contents (and int8
        scales) are gathered and parked in the host tier keyed by
        the token path each block completed.  Best-effort — a failed
        demotion only loses warmth, never blocks the eviction the
        admission is waiting on."""
        if self.host_ is None:
            return self.prefix_.evict(n)
        pairs = self.prefix_.evict_with_paths(n)
        if not pairs:
            return []
        demoted = 0
        self._phases.dispatched(None)   # a gather, read back at once
        try:
            layers = cache.export_blocks([b for b, _ in pairs])
            for j, (bid, path) in enumerate(pairs):
                one = {i: {nm: a[j:j + 1]
                           for nm, a in layer.items()}
                       for i, layer in layers.items()}
                if self.host_.put(path, one):
                    demoted += 1
        except Exception as e:
            self.info("host-tier demotion failed: %r", e)
        if demoted:
            self.stats.record_kv_host(demoted=demoted)
        self._sync_host_gauges()
        return [b for b, _ in pairs]

    def _promote_host(self, cache, seq):
        """Promote the host-tier extension of ``seq``'s device-
        resident prefix back into freshly claimed device blocks and
        re-insert them into the trie — the admission's match then
        rides the ordinary warm staging-gather path, and only the
        genuinely cold tail prefills.  Returns blocks promoted (0 on
        any failure: the request simply admits colder)."""
        bs = self.block_size
        limit = (len(seq) - 1) // bs  # >= 1 token must stay cold
        dev = self.prefix_.resident_prefix(seq, limit)
        entries = self.host_.match(seq, len(dev),
                                   max_blocks=limit - len(dev))
        while entries:
            ids = cache.take_free_blocks(len(entries))
            if ids is not None:
                break
            entries.pop()  # promote the longest extension that fits
        if not entries:
            return 0
        try:
            faults.fire("scheduler.kv.promote")
            merged = {
                i: {nm: numpy.concatenate(
                    [e.layers[i][nm].mem for e in entries])
                    for nm in entries[0].layers[i]}
                for i in entries[0].layers}
            cache.import_blocks(ids, merged)
        except Exception as e:
            cache.reclaim(ids)
            self.info("host-tier promotion failed: %r", e)
            return 0
        self._phases.dispatched(None)
        covered = (len(dev) + len(entries)) * bs
        _, rejected = self.prefix_.insert(list(seq[:covered]),
                                          dev + ids)
        if rejected:  # cannot happen short of a digest collision
            cache.reclaim(rejected)
        self.host_.pop(entries)
        self.stats.record_kv_host(promoted=len(entries))
        self._sync_host_gauges()
        self._sync_prefix_gauges()
        return len(entries)

    def _reap(self, cache):
        """Boundary sweep over the in-flight set: release the slot and
        blocks of every request that was cancelled, crossed its
        deadline mid-decode, or whose future a watchdog trip already
        failed — the other half of the deadline/disconnect contract
        (the future's error alone would still leak KV blocks)."""
        now = time.monotonic()
        with self._lock:
            flight = list(self._prefilling) \
                + list(self._active.values())
        for req in flight:
            if req.future.done():      # watchdog/cancel raced ahead
                self._drop_inflight(req, cache)
            elif req.cancelled:
                self._drop_inflight(req, cache)
                self.stats.record_cancel(len(req.generated),
                                         trace=req.trace)
                req.fail(RequestCancelledError(
                    "cancelled after %d generated tokens"
                    % len(req.generated)))
            elif req.deadline is not None and now > req.deadline:
                self._drop_inflight(req, cache)
                age_ms = (now - req.t_submit) * 1e3
                self.stats.record_expire(age_ms,
                                         tokens=len(req.generated),
                                         trace=req.trace)
                req.fail(DeadlineExceededError(
                    "deadline exceeded after %.0f ms (%d tokens "
                    "generated)" % (age_ms, len(req.generated)),
                    tokens_generated=len(req.generated)))

    def _drop_inflight(self, req, cache):
        """Remove one admitted request from the in-flight set and
        return its slot + blocks to the cache (loop thread only)."""
        with self._lock:
            if req in self._prefilling:
                self._prefilling.remove(req)
            self._active.pop(req.slot, None)
        self._release_slot(req, cache)
        req.pf_seq = req.pf_caches = None
        with self._phases("observe"):
            self._sync_kv_gauges(cache)

    def _do_preempts(self, cache):
        """Evict owed preemptions at this decode boundary: lowest
        priority class first, youngest within it (it loses the least
        re-prefill work — exactly what a priority scheduler should
        sacrifice for a higher-class arrival).  A demand bounded to
        ``below`` with no strictly-lower-class victim is dropped.
        The victim keeps its generated prefix and requeues at the
        front of ITS class, so it resumes as soon as its own freed
        blocks (or better) are available.  The victim's re-prefill
        reads its ``generated``, so a step in flight lands first."""
        if self._preempts_owed:
            self._land(cache)
        while True:
            with self._lock:
                if not self._preempts_owed:
                    return
                if not self._active:
                    del self._preempts_owed[:]  # no targets: demand
                    return                      # dies here
                below = self._preempts_owed.pop(0)
                victims = [r for r in self._active.values()
                           if below is None or r.priority < below]
                if not victims:
                    continue   # bounded demand, no qualifying victim
                req = max(victims,
                          key=lambda r: (-r.priority, r.t_admit,
                                         r.slot))
                self._active.pop(req.slot, None)
            self._release_slot(req, cache)
            req.preempts += 1
            with self._phases("observe"):
                self.stats.record_preempt(
                    len(req.generated),
                    cls=CLASS_NAMES[req.priority], trace=req.trace)
                self._sync_kv_gauges(cache)
            with self._lock:
                self._enqueue_locked(req, front=True)
                self._queued_blocks += self._blocks_for(req)

    def _watchdog_loop(self):
        """Detect a stuck decode iteration and fail the pending
        futures — clients get a fast 5xx instead of a hung socket;
        when (if) the loop unsticks, :meth:`_reap` returns the
        zombies' slots and blocks to the pool."""
        period = max(0.02, min(1.0, self.watchdog / 8.0))
        while True:
            time.sleep(period)
            with self._lock:
                if self._closed:
                    return
                beat, working = self._beat, self._working
                tripped = self._tripped_beat
            if not working or beat is None or beat == tripped:
                continue
            stalled = time.monotonic() - beat
            if stalled <= self.watchdog:
                continue
            with self._lock:
                self._tripped_beat = beat
                victims = [r for r in list(self._queue)
                           + list(self._prefilling)
                           + list(self._active.values())
                           + list(self._admitting)
                           if not r.future.done()]
            err = SchedulerError(
                "decode loop stalled %.1fs (watchdog %.1fs) — "
                "request failed instead of hanging" % (stalled,
                                                       self.watchdog))
            for req in victims:
                req.fail(err)
            self.stats.record_watchdog_trip(len(victims), stalled)
            self.warning(
                "decode loop stalled %.1fs — failed %d pending "
                "requests", stalled, len(victims))

    def _sync_kv_gauges(self, cache):
        self.stats.set_kv_blocks(cache.used_blocks, cache.free_blocks)

    def _expire_locked(self):
        now = time.monotonic()
        kept = collections.deque()
        while self._queue:
            req = self._queue.popleft()
            if req.future.done():
                # a watchdog trip failed it while queued — drop it
                self._queued_blocks -= self._blocks_for(req)
            elif req.deadline is not None and now > req.deadline:
                self._queued_blocks -= self._blocks_for(req)
                queued_ms = (now - req.t_submit) * 1e3
                self.stats.record_expire(queued_ms,
                                         tokens=len(req.generated),
                                         trace=req.trace)
                req.fail(DeadlineExceededError(
                    "queued %.0f ms without a free slot" % queued_ms,
                    tokens_generated=len(req.generated)))
            else:
                kept.append(req)
        self._queue = kept

    def _staging_width(self, p_len, chunk):
        """Width of the batch-1 staging K/V row a prompt prefills
        into: the power-of-two bucket of the prompt, floored so it
        tiles both the chunk width and the block size."""
        floor = max(self.prefill_bucket, self.block_size, chunk or 1)
        return _bucket(p_len, floor, 1 << 30)

    def _begin_admit(self, req, cache):
        """Route one joining request: short sequences prefill
        one-shot; long ones start the chunked-prefill ride-along.  A
        preempted request resumes here — its prefill sequence is
        prompt + the kept generated prefix, so the re-prefill rebuilds
        exactly the K/V its decode steps had written before eviction."""
        req.t_admit = time.monotonic()
        self._phases.admissions += 1
        if req.kv_import is not None and not req.preempts:
            # disaggregated handoff: the exported blocks ARE the
            # prefill — scatter them in and go straight to decode.
            # A preempt-resume of an imported request falls through
            # to the normal re-prefill below instead (its blocks
            # were freed; the chain recomputes the identical K/V)
            with self._phases("prefill"):
                self._admit_import(req, cache)
            return
        with self._phases("admit.stage"):
            self._stage(req, cache)

    def _stage(self, req, cache):
        """``admit.stage``: the joiner's sequence and its staging
        rows; what prefills at once does so as ``prefill``."""
        seq = list(req.prompt) + list(req.generated)
        if req.preempts and req.generated:
            self.stats.record_resume(len(seq))
        req.pf_seq = seq
        p_len = len(seq)
        if self._tron:
            # the queue-wait span [submit, admit] plus the admission
            # decision: cold vs prefix-warm and the blocks claimed —
            # the first two entries of a request's phase timeline
            need = self._blocks_for(req)
            with self._phases("observe"):
                reqtrace.record(
                    req.trace, "queue",
                    duration=req.t_admit - req.t_submit,
                    cls=CLASS_NAMES[req.priority],
                    tenant=req.tenant,
                    resume=bool(req.preempts))
                reqtrace.record(
                    req.trace, "admit", slot=req.slot, tokens=p_len,
                    warm_blocks=req.pf_matched,
                    blocks_claimed=max(0, need - req.pf_matched),
                    resume=bool(req.preempts))
        if req.pf_matched:
            with self._phases("prefill"):
                self._admit_warm(req, cache)
            return
        chunk = self.prefill_chunk
        if not chunk or p_len <= chunk:
            with self._phases("prefill"):
                self._admit_oneshot(req, cache)
            return
        req.pf_chunk, req.pf_widest = chunk, self.prefill_widest
        # the first chunk is the prompt's widest: the staging row
        # tiles it, and so every narrower one after it
        req.pf_width = self._staging_width(
            p_len, chunk_width(p_len, 0, chunk, req.pf_widest))
        req.pf_off = 0
        try:
            self._staging_rows(req)
        except Exception as e:
            self._retire(req, cache, error=e)
            return
        with self._lock:  # close() swaps the list under the same lock
            self._prefilling.append(req)

    def _staging_rows(self, req):
        """A fresh batch-1 staging row a cacheable layer, allocated
        and zeroed on the device from this thread, with a lap after
        each layer: the stretch is the loop's longest (milliseconds of
        host time for microseconds of fills), and the device runs dry
        INSIDE it, which one poll at its end would never charge.  The
        fills are handed over together, last (no program donates a
        staging row), so a spell that a lap starts counts their own
        few microseconds on the device as dry."""
        from veles_tpu import dtypes
        phases, rows = self._phases, {}
        for i, u in enumerate(self.forwards):
            if hasattr(u, "init_cache"):
                rows[i] = u.init_cache(1, req.pf_width,
                                       dtypes.compute_dtype())
                phases.lap()
        req.pf_caches = rows
        phases.dispatched(_newest(rows))

    def _admit_warm(self, req, cache):
        """Prefix-cache hit: the matched blocks already hold the K/V
        of tokens [0, matched · block_size) — GATHER them into the
        staging row and ride the chunked-prefill path for the cold
        tail only (near-zero TTFT when the tail is short).  The
        chunk narrows to block_size so every offset stays
        chunk-aligned from the warm boundary."""
        bs = self.block_size
        p_len = len(req.pf_seq)
        req.pf_chunk = req.pf_widest = min(self.prefill_chunk, bs)
        req.pf_width = self._staging_width(p_len, self.prefill_chunk)
        req.pf_off = req.pf_matched * bs
        try:
            with self._phases("admit.stage"):
                self._staging_rows(req)
            req.pf_caches = cache.load_staging(
                req.pf_caches, req.prefix_handle.blocks)
            self._phases.dispatched(_newest(req.pf_caches))
        except Exception as e:
            self._retire(req, cache, error=e)
            return
        with self._lock:
            self._prefilling.append(req)

    def _admit_oneshot(self, req, cache):
        """Prefill one joining request's sequence (prompt, plus the
        generated prefix on resume) in a single compiled pass and emit
        its next token (the TTFT edge)."""
        p_len = len(req.pf_seq)
        width = self._staging_width(p_len, 0)
        # the SEQUENCE array stays inside the positional table; the
        # staging cache may be wider (insert trims it back)
        p_w = min(width, max(self.window, p_len))
        padded = numpy.zeros((1, p_w), numpy.int32)
        padded[0, :p_len] = req.pf_seq
        try:
            faults.fire("serving.scheduler.prefill")
            row_caches, last = prefill(
                self.forwards, padded, prompt_lens=[p_len],
                window=width, tp=self.tp_,
                params=self.weights_.params)
        except Exception as e:
            self._retire(req, cache, error=e)
            return
        self._phases.dispatched(last)
        if self._tron:
            # the prefill phase so far: the input build and the call
            dt = self._phases.elapsed()
            with self._phases("observe"):
                reqtrace.record(req.trace, "prefill", duration=dt,
                                tokens=p_len)
        self._finish_admit(req, cache, row_caches, last)

    def _prefill_tick(self, cache):
        """Advance the oldest mid-prefill request by ONE chunk (as
        wide as :func:`chunk_width` says for what it has left) — the
        per-iteration decode-stall bound; the decode step for every
        in-flight stream runs right after, in the same iteration."""
        with self._lock:
            if not self._prefilling:  # reaped between check and tick
                return
            req = self._prefilling[0]
        p_len = len(req.pf_seq)
        off = req.pf_off
        c = chunk_width(p_len - off, off, req.pf_chunk, req.pf_widest)
        end = min(off + c, p_len)
        clen = end - off
        padded = numpy.zeros((1, c), numpy.int32)
        padded[0, :clen] = req.pf_seq[off:end]
        kw = _bucket(off + c, c, req.pf_width)
        try:
            faults.fire("serving.scheduler.prefill")
            req.pf_caches, last = prefill_chunk(
                self.forwards, padded, off, [clen], req.pf_caches,
                key_width=kw, tp=self.tp_,
                params=self.weights_.params)
        except Exception as e:
            with self._lock:
                if req in self._prefilling:
                    self._prefilling.remove(req)
            self._retire(req, cache, error=e)
            return
        self._phases.dispatched(last)
        # the prefill phase so far: the input build and the DISPATCH
        # of the chunk (no readback here: its device time rides in the
        # next step phase, veles_serving_steps_after_prefill_total)
        dt = self._phases.elapsed() if self._tron else None
        with self._phases("observe"):
            self.stats.record_prefill_chunk(clen)
            if self._tron:
                reqtrace.record(req.trace, "prefill_chunk",
                                duration=dt, off=off, tokens=clen)
        req.pf_off = end
        if end >= p_len:
            with self._lock:
                if req in self._prefilling:
                    self._prefilling.remove(req)
            self._finish_admit(req, cache, req.pf_caches, last)

    def _finish_admit(self, req, cache, row_caches, last):
        """Insert the prefilled staging row and emit the next token:
        draw 0 on a fresh admission, draw ``len(generated)`` on a
        preempt-resume — exactly the counter the decode step would
        have folded, so the resumed stream never forks."""
        try:
            # a warm admission skips its shared prefix blocks — they
            # are the prefix cache's, and already hold exactly these
            # rows
            cache.insert(req.slot, row_caches, len(req.pf_seq),
                         from_block=req.pf_matched)
        except Exception as e:
            self._retire(req, cache, error=e)
            self._recover_pools(cache, e)
            return
        self._phases.dispatched(None)   # the pools: the next step's
        if req.export_only:
            # prefill-role terminus: the blocks now hold the whole
            # prompt's K/V — gather them raw + the first-token
            # logits, park the record, and hand the blocks back
            self._retire_export(req, cache, last)
            return
        req.pf_caches = None
        req.pf_seq = None
        self._activate(req, cache, last)

    def _activate(self, req, cache, last):
        """Emit the first token from the last-position logits (draw
        ``len(generated)`` of the request's stream) and join the
        active decode set — the shared tail of a finished prefill
        and an adopted KV import."""
        first = first_tokens(
            last, [req.temperature], [req.top_k], [req.seed],
            counts=[len(req.generated)])
        self._phases.dispatched(first)
        # the wait for the chunk, for whatever was queued before it,
        # and for the sampler
        with self._phases("prefill.first"):
            tok = int(numpy.asarray(first)[0])
        with self._phases("emit"):
            self._emit(req, tok)
        if req.t_first is None:  # TTFT is the FIRST first-token only
            req.t_first = time.monotonic()
            with self._phases("observe"):
                self.stats.record_first_token(
                    (req.t_first - req.t_submit) * 1e3,
                    (req.t_admit - req.t_submit) * 1e3,
                    cls=CLASS_NAMES[req.priority])
                if self._tron:
                    reqtrace.record(
                        req.trace, "first_token",
                        ttft_ms=round(
                            (req.t_first - req.t_submit) * 1e3, 3))
        with self._lock:
            self._active[req.slot] = req
        self._maybe_finish(req, cache)

    def _admit_import(self, req, cache):
        """Adopt a KV export record (disaggregated decode half): the
        exported blocks scatter RAW into the slot's leading table
        blocks — byte-identical resident state to the exporting
        replica, no prefill pass — and the first token samples from
        the exported logits with this request's sampler settings
        (draw 0 of its stream, the exact fold the colocated path
        uses)."""
        imp = req.kv_import
        try:
            faults.fire("serving.scheduler.kv_import")
            n = cache.blocks_needed(imp["length"])
            ids = [int(b) for b in cache.tables[req.slot, :n]]
            cache.import_blocks(ids, imp["layers"])
        except Exception as e:
            self._retire(req, cache, error=e)
            self._recover_pools(cache, e)
            return
        self._phases.dispatched(None)
        if self._tron:
            with self._phases("observe"):
                reqtrace.record(
                    req.trace, "queue",
                    duration=req.t_admit - req.t_submit,
                    cls=CLASS_NAMES[req.priority],
                    tenant=req.tenant, resume=False)
                reqtrace.record(
                    req.trace, "kv_import", slot=req.slot,
                    tokens=int(imp["length"]), blocks=len(ids))
        last = numpy.asarray(imp["logits"],
                             numpy.float32).reshape(1, -1)
        self._activate(req, cache, last)

    def _retire_export(self, req, cache, last):
        """Finish a prefill-export request: gather the slot's blocks
        raw (scales riding along under int8) plus the last-position
        logits into a handle-addressed record, then release the slot
        — donating the prompt's blocks to the prefix cache like any
        finished request, so repeat prompts prefill warm on this
        replica too."""
        p_len = len(req.pf_seq)
        # the gather below is read to the host: a failed step in
        # flight surfaces at its own landing, not as this request's
        self._land(cache)
        try:
            faults.fire("serving.scheduler.kv_export")
            n = cache.blocks_needed(p_len)
            ids = [int(b) for b in cache.tables[req.slot, :n]]
            from veles_tpu.serving.disagg import mint_handle
            handle = mint_handle()
            record = {
                "handle": handle,
                "prompt": list(req.prompt),
                "length": p_len,
                "kv_dtype": self.kv_dtype,
                "block_size": self.block_size,
                "logits": numpy.asarray(last,
                                        numpy.float32)[0].copy(),
                "layers": cache.export_blocks(ids),
                "t": time.monotonic(),
            }
        except Exception as e:
            self._retire(req, cache, error=e)
            return
        with self._phases("emit"):
            req.pf_caches = None
            req.pf_seq = None
            with self._lock:
                self._active.pop(req.slot, None)
            self._release_slot(req, cache, finished=True)
            with self._phases("observe"):
                self._sync_kv_gauges(cache)
            now = time.monotonic()
            from veles_tpu.serving.disagg import record_nbytes
            record["bytes"] = record_nbytes(record)
            with self._lock:
                self._sweep_exports_locked(now)
                capped = 0
                while self._exports and self._exports_bytes \
                        + record["bytes"] > self.kv_export_bytes:
                    # oldest unclaimed record pays for the byte budget
                    oldest = min(self._exports,
                                 key=lambda h: self._exports[h]["t"])
                    self._exports_bytes -= \
                        self._exports[oldest].get("bytes", 0)
                    del self._exports[oldest]
                    capped += 1
                if capped:
                    # a cap eviction is an unfetched loss like an
                    # expiry, just paid early — same alertable series
                    self.stats.record_kv_export_expired(capped)
                self._exports[handle] = record
                self._exports_bytes += record["bytes"]
                self.stats.set_kv_exports_pending(len(self._exports))
            if self._tron:
                with self._phases("observe"):
                    reqtrace.record(
                        req.trace, "kv_export", tokens=p_len, blocks=n,
                        total_s=round(now - req.t_submit, 6))
            if not req.future.done():
                try:
                    req.future.set_result({
                        "handle": handle, "prompt_tokens": p_len,
                        "blocks": n})
                except concurrent.futures.InvalidStateError:
                    pass

    def _step(self, cache):
        """Advance every active request one token through the shared
        compiled step; a finished one retires where its token lands
        (:meth:`_land_flight`)."""
        with self._lock:
            active = dict(self._active)
        flight = self._flight
        try:
            faults.fire("serving.scheduler.step")
            self._step_paged(cache, active)
        except Exception as e:
            # a step fails where it is launched or, once the device
            # runs it, where its tokens are read: one launch later.
            # By then the step after it has consumed what it returned,
            # so the riders of both are failed
            riders = list(active.values())
            for ridden in (flight, self._flight):
                if ridden is not None:
                    riders.extend(ridden.reqs)
            self._step_failed(cache, e, riders)

    def _step_failed(self, cache, error, riders):
        """Every request that rode the failed batch is failed with
        the error (or all of them, if the pools went with it); the
        loop lives on for the next request."""
        self.exception("decode step failed: %r", error)
        self._flight = None
        if not self._recover_pools(cache, error):
            for req in riders:
                if req.slot is not None:   # not retired meanwhile
                    self._retire(req, cache, error=error)

    def _land(self, cache):
        """Land the step in flight, if there is one, before something
        that changes the row set or reads what it will emit (a
        preempt, a KV export, the loop's end)."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        try:
            self._land_flight(cache, flight)
        except Exception as e:
            self._step_failed(cache, e, flight.reqs)

    def _recover_pools(self, cache, error=None):
        """The other half of donating the cache's device state
        (serving/kv_slots.py): a call that raised BEFORE dispatch has
        consumed nothing, but one that failed after consuming its
        input leaves deleted leaves behind, and no later call may
        carry on with them.  If any leaf is deleted: fail every
        request that holds a slot (its rows are gone), forget every
        resident prefix, zero the pools.  True when it did."""
        if not cache.pools_lost():
            return False
        err = SchedulerError(
            "the KV pools were lost to a call that failed after "
            "consuming them (%r): request failed, pools zeroed"
            % (error,))
        with self._lock:
            victims = list(self._active.values()) \
                + self._prefilling + self._admitting
            self._prefilling = []
        victims = [r for r in victims if r.slot is not None]
        # zeroed before anyone is told: a client that sees its request
        # fail may send the next one at once
        cache.reset_pools()
        self._phases.dispatched(None)
        for req in victims:
            self._retire(req, cache, error=err)
        pfx = self.prefix_   # loop-owned, like the cache
        if pfx is not None:
            cache.reclaim(pfx.clear())
            self._sync_prefix_gauges()
        self.warning("%s; %d request(s) failed", err, len(victims))
        return True

    def _emit(self, req, tok):
        """Accept one token: append to the request's stream AND push
        it to the live subscription (submit(stream=True)) in the same
        boundary — what makes SSE concatenation bit-identical to the
        batch reply (a preempt-resume re-prefills but never re-emits;
        only newly drawn tokens pass through here)."""
        req.generated.append(tok)
        if req.sink is not None:
            req.sink(tok)

    def _pick_model(self, req):
        """Per-slot drafter arbitration: take the model head unless
        its accept-rate EMA has fallen below the n-gram proposer's.
        Unseen drafters score an optimistic 1.0 (each gets tried
        before being judged), ties go to the model — so a slot whose
        model drafts reject drifts to n-gram and drifts back the
        moment n-gram does worse."""
        em = req.accept_ema.get("model")
        en = req.accept_ema.get("ngram")
        return (1.0 if em is None else em) \
            >= (1.0 if en is None else en)

    def _draft(self, active):
        """Propose draft tokens per slot — capped so accepting every
        draft plus the correction token never exceeds the request's
        step budget (the positions stay inside the blocks claimed at
        admission).  Each slot drafts up to its ADAPTIVE ``draft_k``
        (accept-rate EMA; see __init__) from its arbitrated source:
        the Medusa head batched over every slot with a live hidden
        state, or n-gram prompt lookup through the request's memoized
        trailing-gram index.  Returns ``(drafts, sources)`` —
        {slot: tokens} and {slot: "model"|"ngram"}."""
        drafts, sources = {}, {}
        model_out = {}
        if self._draft_head is not None:
            rows = [s for s in sorted(active)
                    if active[s].hid is not None]
            if rows:
                out = self._draft_head.propose(
                    numpy.stack([active[s].hid for s in rows]))
                for j, slot in enumerate(rows):
                    model_out[slot] = out[j]
        for slot, req in active.items():
            room = req.steps - len(req.generated) - 1
            if room < 1:
                continue
            if req.draft_k < 1:
                req.draft_k = self.spec_k  # start optimistic
            limit = min(req.draft_k, room)
            d = None
            if slot in model_out and self._pick_model(req):
                d = [int(t) for t in model_out[slot][:limit]]
                sources[slot] = "model"
            if not d:
                if req.gram_ix is None:
                    req.gram_ix = NgramIndex(
                        self._proposer.max_ngram,
                        self._proposer.min_ngram)
                d = self._proposer.propose(
                    list(req.prompt) + list(req.generated), limit,
                    index=req.gram_ix)
                sources[slot] = "ngram"
            if d:
                drafts[slot] = d[:limit]
            else:
                sources.pop(slot, None)
        return drafts, sources

    def _adapt_draft_k(self, req, drafted, accepted, drafter):
        """Post-verify accept-rate bookkeeping for one slot: blend
        this verify's accept rate into the slot's per-drafter EMA
        (weight ``draft_ema``), then steer the slot's draft length —
        halve toward ``draft_k_min`` below ``draft_shrink`` (stop
        paying verify width for drafts that keep rejecting), double
        toward ``spec_k`` above ``draft_grow``.  Powers of two only,
        so every length lands on a warmed verify bucket."""
        rate = accepted / drafted
        prev = req.accept_ema.get(drafter)
        ema = rate if prev is None \
            else (1.0 - self.draft_ema) * prev + self.draft_ema * rate
        req.accept_ema[drafter] = ema
        if ema < self.draft_shrink:
            req.draft_k = max(self.draft_k_min, req.draft_k >> 1)
        elif ema > self.draft_grow:
            req.draft_k = min(self.spec_k, req.draft_k << 1)
        self.stats.record_spec(drafted, accepted, drafter=drafter,
                               draft_k=req.draft_k)

    def _meter_step(self, active, cache, dt):
        """Step-boundary usage attribution (PR 17 metering): each
        active request charges its tenant KV-blocks-held x the step's
        wall time, plus an even 1/n split of the step's duration as
        compute-seconds.  Sampled here — not at retire — so a
        long-lived stream's HBM residency accrues while it runs, and
        a preempted request stops being charged the moment its
        blocks are released."""
        if not self._metering or not active or dt <= 0:
            return
        share = dt / len(active)
        usage = {}
        for slot, req in active.items():
            blocks = int(cache.n_blocks[slot])
            rec = usage.setdefault(req.tenant or "anon", [0.0, 0.0])
            rec[0] += blocks * dt
            rec[1] += share
        self.stats.record_tenant_step(usage)

    def _runs_ahead(self, flight, active):
        """May the next step be launched before ``flight`` is read?
        Yes where it is the same batch one token on: the same requests
        in the same slots, none of which ends on its budget with the
        token in flight (all known on the host without that token).
        A stop token cannot be foreseen: its row runs ahead and is
        discarded when it lands (:meth:`_land_flight`)."""
        return len(active) == len(flight.slots) and all(
            active.get(slot) is req
            and len(req.generated) + 1 < req.steps
            for slot, req in zip(flight.slots, flight.reqs))

    def _step_paged(self, cache, active):
        """Packed step: ONLY the active slots ride the batch, padded
        to a power-of-two occupancy bucket; the attended range is the
        power-of-two block bucket of the deepest request.

        Launch-ahead: the step launched here stays IN FLIGHT when
        this returns, and its tokens are read one launch later.  With
        step N in flight and the batch unchanged (:meth:`_runs_ahead`)
        step N+1 is packed from host facts (positions and draw
        counters are N's plus one), takes N's tokens as the device
        array N returned, and is launched BEFORE N is landed: the
        device runs back to back while the host emits N and goes on
        to its next pass.  Otherwise N lands first and the step is
        packed from the host's tokens.  The speculative path drafts
        from the host's tokens (and the hidden state), so under
        ``spec`` every step lands at once and nothing runs ahead."""
        flight, self._flight = self._flight, None
        if self.spec:
            with self._phases("draft"):
                drafts, sources = self._draft(active)
            if drafts:
                self._step_verify(cache, active, drafts, sources)
                return
        ahead = flight is not None and self._runs_ahead(flight, active)
        if flight is not None and not ahead:
            self._land_flight(cache, flight)
            with self._lock:
                active = dict(self._active)
        if not active:
            return
        with self._phases("pack"):
            slots = sorted(active)
            n = len(slots)
            b = _bucket(n, 1, self.max_slots)
            bs = cache.block_size
            # ``ahead``: 1 where each row's newest token is still in
            # flight, so every count below is one more than the host's
            deepest = max(len(active[s].prompt)
                          + len(active[s].generated)
                          for s in slots) + ahead
            t = _bucket(-(-deepest // bs), 1, cache.blocks_per_slot)
            # the same bucket as the step in flight (the same rows), so
            # its [b] device tokens go in as they are: no readback, no
            # upload
            toks = flight.nxt if ahead \
                else numpy.zeros((b,), numpy.int32)
            pos = numpy.zeros((b,), numpy.int32)
            temps = numpy.zeros((b,), numpy.float32)
            topks = numpy.zeros((b,), numpy.int32)
            seeds = numpy.zeros((b,), numpy.uint32)
            counts = numpy.zeros((b,), numpy.int32)
            tables = numpy.zeros((b, t), numpy.int32)
            rows = numpy.full((b,), -1, numpy.int32)   # -1: padding
            rows[:n] = slots
            for j, slot in enumerate(slots):
                req = active[slot]
                drawn = len(req.generated) + ahead
                if not ahead:
                    toks[j] = req.generated[-1]
                pos[j] = len(req.prompt) + drawn - 1
                temps[j] = req.temperature
                topks[j] = req.top_k
                seeds[j] = req.seed
                counts[j] = drawn
            tables[:n] = cache.table_rows(slots, t)
            want_h = self._draft_head is not None
        phases = self._phases
        phases.steps_greedy += not temps.any()
        with phases("step.resolve"):
            got = paged_decode_step(
                self.forwards, cache, toks, pos, tables, temps, topks,
                seeds, counts, want_hidden=want_h,
                params=self.weights_.params, slots=rows,
                resolved=phases.calling)
            nxt, hid = got if want_h else (got, None)
            phases.dispatched(nxt)
            # what the units counted comes with the step
            self._flight = _Flight(slots, [active[s] for s in slots],
                                   nxt, hid, cache.step_counts)
        if ahead:
            phases.steps_ahead += 1
            self._land_flight(cache, flight)
        if self.spec:
            self._land(cache)

    def _land_flight(self, cache, flight):
        """Read a launched step's tokens and hand them on: count the
        step, meter it, emit a token a row, retire what finished.
        The one wait on the device for a step (``step.land``), charged
        to ``step`` as the launch is, and counted as no step.

        A row whose request no longer holds its slot (it ended on a
        stop token with this step already launched, or was cancelled,
        expired or failed meanwhile) is DISCARDED: not emitted, not
        counted as a token or a busy slot-step.  What the row did on
        the device is harmless.  The device runs calls in dispatch
        order, and the row's K/V write (the right row of its own
        sequence, inside blocks it still held at launch) and its
        per-slot state write were dispatched BEFORE any later
        admission's ``insert`` / ``insert_state`` into the blocks or
        slot it gave back, which overwrite them; a prefix block
        promoted at retire lies below the row and holds the same
        values either way."""
        with self._phases("step.land"):
            nxt = numpy.asarray(flight.nxt)
            hid = None if flight.hid is None \
                else numpy.asarray(flight.hid)
            # computed by then: a small copy each, no further wait
            counts = {name: numpy.asarray(rows)
                      for name, rows in flight.counts.items()}
        # the step's own stretch of the clock: from its launch, or
        # from the landing before it where the device ran the two
        # back to back
        now = time.perf_counter()
        dt = now - max(flight.t0, self._landed_at)
        self._landed_at = now
        with self._lock:
            rows = [(j, slot, req) for j, (slot, req)
                    in enumerate(zip(flight.slots, flight.reqs))
                    if self._active.get(slot) is req]
        n, b = len(rows), len(nxt)
        self._phases.rows_discarded += len(flight.slots) - n
        with self._phases("observe"):
            # plain decode: every live row emits exactly one token
            self.stats.record_step(
                n, b, tokens=n, state_units=len(self._state_units),
                **counts)
            self._meter_step({slot: req for _, slot, req in rows},
                             cache, dt)
        with self._phases("emit"):
            for j, slot, req in rows:
                if hid is not None:
                    # hidden of the position just decoded — what the
                    # Medusa heads condition on next iteration
                    req.hid = hid[j]
                self._emit(req, int(nxt[j]))
                self._maybe_finish(req, cache)
        if self._tron:
            with self._phases("observe"):
                emitted = {}
                for _, _, req in rows:  # rows may SHARE a trace id
                    emitted[req.trace] = emitted.get(req.trace, 0) + 1
                reqtrace.record_step(emitted, duration=dt,
                                     mode="decode", slots=n, bucket=b)

    def _step_verify(self, cache, active, drafts, sources):
        """Speculative step: every active slot rides ONE batched
        verify pass — its pending token plus its drafts (slots
        without a draft run a plain width-1 decode inside the same
        batch).  The occupancy/depth buckets grow a power-of-two
        draft-width axis k; acceptance keeps the longest matched
        prefix plus the correction sample, so the emitted stream is
        bit-identical to spec-off decoding while one pass can emit
        up to k + 1 tokens."""
        with self._phases("pack"):
            slots = sorted(active)
            n = len(slots)
            b = _bucket(n, 1, self.max_slots)
            # adaptive draft width — MODEL-DRAFTER schedulers only: the
            # verify runs at the power-of-two bucket of the widest draft
            # BUDGET among drafting slots, so when every slot's EMA
            # controller has shrunk its draft_k the pass stops paying
            # spec_k-wide sampling for one-token drafts.  Keying on
            # draft_k (not raw draft lengths) keeps un-shrunk batches on
            # the spec_k-wide executable; the ladder is bounded at
            # log2(spec_k) + 1 per (B, T) and only exists where a draft
            # head is attached — n-gram-only schedulers keep the ONE
            # fixed-width executable (drafts pad up, ``lens`` masks), so
            # the flipped-on spec default compiles nothing extra.
            if self._draft_head is not None:
                k = _bucket(max(active[s].draft_k for s in drafts),
                            1, self.spec_k)
            else:
                k = _bucket(self.spec_k, 1, self.spec_k)
            bs = cache.block_size
            deepest = max(len(active[s].prompt)
                          + len(active[s].generated) for s in slots) + k
            t = _bucket(-(-deepest // bs), 1, cache.blocks_per_slot)
            toks = numpy.zeros((b, k + 1), numpy.int32)
            pos = numpy.zeros((b,), numpy.int32)
            lens = numpy.ones((b,), numpy.int32)
            temps = numpy.zeros((b,), numpy.float32)
            topks = numpy.zeros((b,), numpy.int32)
            seeds = numpy.zeros((b,), numpy.uint32)
            counts = numpy.zeros((b,), numpy.int32)
            tables = numpy.zeros((b, t), numpy.int32)
            for j, slot in enumerate(slots):
                req = active[slot]
                d = drafts.get(slot, ())[:k]
                toks[j, 0] = req.generated[-1]
                if d:
                    toks[j, 1:1 + len(d)] = d
                pos[j] = len(req.prompt) + len(req.generated) - 1
                lens[j] = 1 + len(d)
                temps[j] = req.temperature
                topks[j] = req.top_k
                seeds[j] = req.seed
                counts[j] = len(req.generated)
            tables[:n] = cache.table_rows(slots, t)
            want_h = self._draft_head is not None
        phases, t0 = self._phases, time.perf_counter()
        phases.steps_greedy += not temps.any()
        with phases("step.resolve"):
            got = verify_step_paged(
                self.forwards, cache, toks, pos, lens, tables, temps,
                topks, seeds, counts, want_hidden=want_h,
                params=self.weights_.params, resolved=phases.calling)
            nxt, hid = got if want_h else (got, None)
            phases.dispatched(nxt)
            with phases("step.land"):   # a verify step lands at once
                if want_h:
                    hid = numpy.asarray(hid)
                nxt = numpy.asarray(nxt)
        dt = time.perf_counter() - t0
        with self._phases("observe"):
            # metered BEFORE acceptance retires finished slots — the
            # step's residency belongs to everyone who rode the batch
            self._meter_step(active, cache, dt)
        emitted = {}
        with self._phases("emit"):
            for j, slot in enumerate(slots):
                req = active[slot]
                d = list(drafts.get(slot, ()))[:k]
                out = accept_drafts(d, nxt[j, :len(d) + 1])
                before = len(req.generated)
                for tok in out:
                    self._emit(req, int(tok))
                    if len(req.generated) >= req.steps \
                            or (req.stop_token is not None
                                and int(tok) == req.stop_token):
                        break
                done = len(req.generated) - before
                if want_h and done > 0:
                    # hidden of the LAST position this verify scored
                    # and kept — row [j, done-1] conditioned the token
                    # now pending, so the Medusa heads read it next
                    # iteration
                    req.hid = hid[j, done - 1]
                if d:
                    self._adapt_draft_k(req, len(d), len(out) - 1,
                                        sources.get(slot, "ngram"))
                emitted[req.trace] = emitted.get(req.trace, 0) + done
                self._maybe_finish(req, cache)
        with self._phases("observe"):
            # recorded AFTER acceptance so goodput counts what the
            # verify actually emitted (a fully-rejected batch is 0
            # good tokens)
            self.stats.record_step(n, b, tokens=sum(emitted.values()))
            if self._tron:
                reqtrace.record_step(emitted, duration=dt,
                                     mode="verify", slots=n, bucket=b,
                                     k=k)

    def _maybe_finish(self, req, cache, error=None):
        done = error is not None \
            or len(req.generated) >= req.steps \
            or (req.stop_token is not None
                and req.generated[-1] == req.stop_token)
        if done:
            self._retire(req, cache, error=error)

    def _retire(self, req, cache, error=None):
        with self._phases("emit"):
            with self._lock:
                self._active.pop(req.slot, None)
            self._release_slot(req, cache, finished=error is None)
            with self._phases("observe"):
                self._sync_kv_gauges(cache)
                if self._metering:
                    # token attribution happens for ERRORS too — the
                    # prefill and decode compute was spent either way,
                    # and a bill that forgets failures undercharges
                    # the tenant causing them
                    self.stats.record_tenant_tokens(
                        req.tenant, prompt=len(req.prompt),
                        generated=len(req.generated))
                if self._tron:
                    # an INSTANT at the retire boundary ("duration"
                    # would backdate it into a request-spanning bar):
                    # total_s is the whole submit->retire wall time as
                    # an attribute
                    reqtrace.record(
                        req.trace, "retire",
                        tokens=len(req.generated),
                        total_s=round(
                            time.monotonic() - req.t_submit, 6),
                        preempts=req.preempts,
                        outcome="ok" if error is None
                        else type(error).__name__)
            if error is not None:
                req.fail(error if isinstance(error, SchedulerError)
                         else SchedulerError(repr(error)))
                return
            if req.future.done():
                # watchdog/cancel failed it first — the tokens are moot
                return
            now = time.monotonic()
            with self._phases("observe"):
                self.stats.record_complete(
                    len(req.generated), now - req.t_submit,
                    (req.t_first - req.t_submit) * 1e3,
                    (req.t_admit - req.t_submit) * 1e3,
                    cls=CLASS_NAMES[req.priority], trace=req.trace)
            try:
                req.future.set_result(list(req.prompt) + req.generated)
            except concurrent.futures.InvalidStateError:
                pass
