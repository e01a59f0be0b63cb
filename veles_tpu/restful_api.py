"""REST inference serving (rebuild of veles/restful_api.py:78 +
loader/restful.py:52).

``RestfulLoader`` queues HTTP request payloads as minibatches;
``RESTfulAPI`` owns the HTTP endpoint (stdlib threading server — the
reference used twisted.web) and completes each pending request with the
forward chain's output for its row.  Graph shape::

    start → repeater → restful_loader → [forwards] → api ─→ repeater
                                         (loop until the feed closes)
"""

import concurrent.futures
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy

from veles_tpu import faults
from veles_tpu.loader.interactive import InteractiveLoader
from veles_tpu.memory import Array
from veles_tpu.telemetry import reqtrace
from veles_tpu.units import Unit


def _status_text(e):
    """Exception → HTTP status-line-safe text: whitespace (incl. the
    newlines of multi-line JAX errors) collapsed to spaces — a raw
    newline would split the status line (header injection) — and
    latin-1 only (send_response_only encodes strict), 200 chars."""
    line = " ".join(str(e).split())[:200] or type(e).__name__
    return line.encode("latin-1", "replace").decode("latin-1")


class RestfulLoader(InteractiveLoader):
    """Interactive loader whose samples carry reply futures
    (ref: veles/loader/restful.py:52)."""

    def init_unpickled(self):
        super(RestfulLoader, self).init_unpickled()
        self._fifo_ = []
        self._feed_lock_ = threading.Lock()
        self.pending_futures_ = []

    def feed_request(self, sample):
        # validate BEFORE registering the future, and register+enqueue
        # atomically — concurrent HTTP threads must keep the reply FIFO
        # aligned with the sample queue, and a rejected sample must not
        # leave an orphan future shifting every later reply
        sample = numpy.asarray(sample, numpy.float32)
        if sample.shape != self.sample_shape:
            raise ValueError("sample shape %s != %s"
                             % (sample.shape, self.sample_shape))
        future = concurrent.futures.Future()
        with self._feed_lock_:
            self._fifo_.append(future)
            self.feed(sample)
        return future

    def run(self):
        super(RestfulLoader, self).run()
        # the futures for exactly the rows just served, in row order
        self.pending_futures_ = self._fifo_[:self.minibatch_size]
        del self._fifo_[:self.minibatch_size]


class RESTfulAPI(Unit):
    """HTTP endpoint unit (ref: veles/restful_api.py:78): POST /api
    ``{"input": [...]}`` → ``{"result": [...]}``.  Runs after the
    forward chain; resolves each request's future with its output row.

    With an LM ``forwards`` chain, POST /generate serves through the
    continuous-batching scheduler (``veles_tpu/serving/``): each
    prompt row is an independent request that joins a decode slot at a
    token boundary, so concurrent clients genuinely interleave — there
    is no decode lock on this path.  Admission control surfaces as
    HTTP 503 (queue full) / 408 (queue deadline), and GET
    /serving/metrics reports TTFT, throughput, queue depth, slot
    occupancy and free/used KV blocks — the memory-pressure headroom
    that predicts admission stalls under the paged cache.  Beam
    requests (and chains the scheduler cannot serve) fall back to the
    serialized legacy decode.
    """

    VIEW_GROUP = "SERVICE"

    def __init__(self, workflow, loader=None, port=0, host="127.0.0.1",
                 request_timeout=30.0, forwards=None, serving=True,
                 max_slots=4, serving_window=None, max_queue=32,
                 max_steps=None, max_batch=None,
                 serving_block_size=None, serving_kv_blocks=None,
                 serving_kv_dtype=None, serving_prefill_chunk=None,
                 serving_spec=None, serving_spec_k=None,
                 serving_prefix_cache=None, serving_warm_buckets=None,
                 serving_tp=None, serving_role=None,
                 serving_kv_host_bytes=None,
                 serving_kv_export_bytes=None,
                 replica_id=None, **kwargs):
        for k in kwargs:   # Unit swallows what it does not know
            if k.startswith("serving_"):
                raise TypeError("RESTfulAPI has no option %r" % k)
        super(RESTfulAPI, self).__init__(workflow, **kwargs)
        self.loader = loader
        #: fleet identity: every reply carries it as X-Veles-Replica
        #: so a fronting router (serving/router.py) can attribute
        #: responses; defaults to pid:port once the server binds
        self.replica_id = replica_id
        self.output = None  # linked from the head forward unit
        self.port = port
        self.host = host
        self.request_timeout = request_timeout
        #: optional callable fired by POST /shutdown (serving workflows
        #: wire their stop request here)
        self.shutdown_callback = None
        #: optional LM forward chain (… → TokenProjection); when set,
        #: POST /generate decodes autoregressively via the serving
        #: scheduler (or models/generate when serving is off)
        self.forwards = forwards
        #: continuous-batching knobs (serving=False pins the legacy
        #: serialized decode path)
        self.serving = bool(serving)
        self.max_slots = int(max_slots)
        self.serving_window = serving_window
        self.max_queue = int(max_queue)
        #: paged-KV / chunked-prefill knobs (None defers to
        #: ``root.common.serving.*`` — see serving/scheduler.py)
        self.serving_block_size = serving_block_size
        self.serving_kv_blocks = serving_kv_blocks
        #: KV pool storage dtype ("fp32"/"int8"; None defers to
        #: ``root.common.serving.kv_dtype``) — int8 roughly doubles
        #: concurrent streams per HBM budget, quality-gated
        self.serving_kv_dtype = serving_kv_dtype
        self.serving_prefill_chunk = serving_prefill_chunk
        #: speculative decoding / radix prefix cache (None defers to
        #: ``root.common.serving.{spec,spec_k,prefix_cache}``)
        self.serving_spec = serving_spec
        self.serving_spec_k = serving_spec_k
        self.serving_prefix_cache = serving_prefix_cache
        #: None defers to root.common.serving.warm_buckets; tests pin
        #: False (the bucket-ladder warmup is the compile hog)
        self.serving_warm_buckets = serving_warm_buckets
        #: tensor-parallel mesh size (None defers to
        #: ``root.common.serving.tp``; 0 = unsharded) — shards the
        #: jitted serving steps so weights + paged pools split over
        #: N chips (serving/tp.py)
        self.serving_tp = serving_tp
        #: disaggregation role (None defers to
        #: ``root.common.serving.role``): "prefill" replicas serve
        #: POST /serving/prefill + GET /serving/kv_export/<handle>
        #: only; "decode" replicas adopt exports via POST
        #: /serving/kv_import; "both" (default) is colocated
        self.serving_role = serving_role
        #: tiered-KV knobs (None defers to
        #: ``root.common.serving.{kv_host_bytes,kv_export_bytes}``):
        #: host-RAM overflow budget for evicted prefix blocks, and
        #: the byte cap on outstanding disagg KV exports
        self.serving_kv_host_bytes = serving_kv_host_bytes
        self.serving_kv_export_bytes = serving_kv_export_bytes
        #: /generate resource caps — an unbounded request would pay a
        #: giant alloc + a multi-second compile before failing; None
        #: defers to root.common.api.{max_steps,max_batch}
        self.max_steps = max_steps
        self.max_batch = max_batch
        self.demand("loader", "output")

    def _cap(self, name, default):
        """Resolve a /generate resource cap: constructor override,
        else ``root.common.api.<name>``, else the built-in default —
        read per request so ``-c`` overrides apply live."""
        value = getattr(self, name)
        if value is None:
            from veles_tpu.config import root
            value = root.common.api.get(name, default)
        return int(value)

    def _validate_prompt(self, prompt):
        """Reject malformed /generate prompts with a client error
        (the decode would otherwise return 200 with tokens conditioned
        on a phantom zero row, or gather a clamped wrong embedding)."""
        if prompt.ndim != 2 or prompt.shape[1] < 1 or not prompt.size:
            return "prompt must be a non-empty token list (or a " \
                   "batch of non-empty lists — ragged is fine)"
        vocab = getattr(self.forwards[0], "vocab", None)
        if vocab is not None and \
                (prompt.min() < 0 or prompt.max() >= int(vocab)):
            return "prompt token ids must be in [0, %d)" % vocab
        return None

    def _validate_rows(self, rows):
        """Vocab-bounds check for parsed token rows (the /v1 paths,
        which skip the numpy padding _validate_prompt works on)."""
        vocab = getattr(self.forwards[0], "vocab", None)
        if vocab is not None:
            for r in rows:
                if min(r) < 0 or max(r) >= int(vocab):
                    return "token ids must be in [0, %d)" % vocab
        return None

    def _decode_beam(self, prompt, steps, beam):
        """Beam-search decode for /generate (serialized like
        :meth:`_decode` — beam search stays off the slot scheduler)."""
        from veles_tpu.models.generate import generate_beam
        with self._legacy_lock_:
            return generate_beam(self.forwards, prompt, steps, beam)

    def _decode(self, prompt, steps, temperature, top_k, seed,
                prompt_lens=None, stop_token=None):
        """Legacy lockstep decode for /generate — the fallback when
        the serving scheduler is off or cannot serve the chain.
        Serialized: decode requests share the chain's param Arrays and
        the compile caches; a novel (batch, prompt_len, steps,
        sampler) shape compiles a fresh executable on first use
        (seconds), so variable-shape clients pay per shape, cached
        thereafter (ragged lengths within one shape reuse the same
        executable — the lens are a traced argument)."""
        import jax

        from veles_tpu.models.generate import generate, \
            kv_cache_eligible
        if seed is None:
            # an unpinned sampling request must draw FRESH tokens per
            # call — a constant default would replay one "sample"
            import os
            seed = int.from_bytes(os.urandom(4), "little")
        key = jax.random.key(int(seed)) if temperature else None
        with self._legacy_lock_:
            return generate(self.forwards, prompt, steps,
                            temperature=temperature, top_k=top_k,
                            key=key,
                            kv_cache=kv_cache_eligible(self.forwards),
                            prompt_lens=prompt_lens,
                            stop_token=stop_token)

    def _generate_scheduled(self, rows, steps, temperature, top_k,
                            seed, stop, priority=None, trace=None,
                            resume_tokens=None, tenant=None):
        """Decode a /generate body through the continuous-batching
        scheduler: every prompt row is its own request (ragged batches
        interleave in the slots like independent clients).  Returns
        per-row token lists, each ending at its first generated stop
        token.  A pinned seed stays reproducible per row (row i draws
        from seed + i).

        Any failure (a row's scheduler error, a timeout, the handler
        thread dying with its client) CANCELS the batch's unfinished
        futures — an abandoned request must hand its slot and KV
        blocks back at the next decode boundary instead of decoding
        for a client that is gone."""
        futures = []
        try:
            for i, row in enumerate(rows):
                futures.append(self.scheduler_.submit(
                    row, steps, temperature=temperature, top_k=top_k,
                    seed=None if seed is None else int(seed) + i,
                    stop_token=stop, timeout=self.request_timeout,
                    priority=priority, trace=trace,
                    resume_tokens=resume_tokens, tenant=tenant))
            # the scheduler enforces the deadline itself (408 with
            # partial-token count); the result wait is only a backstop
            # against a wedged loop with the watchdog disabled
            return [f.result(self.request_timeout + 30.0)
                    for f in futures]
        except BaseException:
            for f in futures:
                if not f.done():
                    self.scheduler_.cancel(f)
            raise

    def init_unpickled(self):
        super(RESTfulAPI, self).init_unpickled()
        self._server_ = None
        self._thread_ = None
        self._legacy_lock_ = threading.Lock()
        self.scheduler_ = None
        #: replica-tier alert engine (telemetry/alerts.py), created
        #: at initialize() when root.common.alerts.enabled
        self.alerts_ = None
        #: replica-tier history store (telemetry/tsdb.py), created
        #: at initialize() when root.common.tsdb.enabled — samples
        #: the process registry; GET /metrics/history queries it
        self.tsdb_ = None
        #: POST /drain latched: /healthz answers 503 "draining" and
        #: the scheduler (if any) stops admitting
        self._draining_ = False

    def initialize(self, **kwargs):
        super(RESTfulAPI, self).initialize(**kwargs)
        if self.forwards is not None and self.serving \
                and self.scheduler_ is None:
            from veles_tpu.serving import (
                InferenceScheduler, serving_supported)
            if serving_supported(self.forwards):
                self.scheduler_ = InferenceScheduler(
                    self.forwards, max_slots=self.max_slots,
                    window=self.serving_window,
                    max_queue=self.max_queue,
                    queue_timeout=self.request_timeout,
                    block_size=self.serving_block_size,
                    kv_blocks=self.serving_kv_blocks,
                    kv_dtype=self.serving_kv_dtype,
                    prefill_chunk=self.serving_prefill_chunk,
                    spec=self.serving_spec,
                    spec_k=self.serving_spec_k,
                    prefix_cache=self.serving_prefix_cache,
                    warm_buckets=self.serving_warm_buckets,
                    tp=self.serving_tp,
                    role=self.serving_role,
                    kv_host_bytes=self.serving_kv_host_bytes,
                    kv_export_bytes=self.serving_kv_export_bytes,
                    replica_id=self.replica_id).start()
                self.info(
                    "serving scheduler: %d slots, window %d, "
                    "queue cap %d, block %d, prefill "
                    "chunk %d, tp=%d, role=%s",
                    self.scheduler_.max_slots,
                    self.scheduler_.window, self.max_queue,
                    self.scheduler_.block_size,
                    self.scheduler_.prefill_chunk,
                    self.scheduler_.tp, self.scheduler_.role)
            else:
                self.info("chain not slot-servable; /generate stays "
                          "on the serialized decode path")
        if self.forwards is not None and self.scheduler_ is None:
            # no scheduler (its start() builds the serving weights
            # leaf by leaf): warm the device params NOW,
            # single-threaded — Array.devmem lazily uploads on first
            # touch and is not thread-safe against the concurrent HTTP
            # handler threads /generate runs on (the upload nulls the
            # buffer before replacing it)
            for u in self.forwards:
                for arr in u.param_arrays().values():
                    arr.devmem
        if self._server_ is not None:
            return
        api = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _admin_ok(self):
                """Admin endpoints (/drain, /shutdown) are loopback-
                only UNLESS root.common.api.admin_token is set and the
                caller presents it as ``Authorization: Bearer`` — the
                remote-router story; constant-time compare so the
                token is not a timing oracle."""
                peer = self.client_address[0]
                if peer in ("127.0.0.1", "::1", "localhost"):
                    return True
                import hmac
                from veles_tpu.config import root
                token = root.common.api.get("admin_token", None)
                if not token:
                    return False
                auth = self.headers.get("Authorization", "")
                return hmac.compare_digest(auth, "Bearer %s" % token)

            def _trace(self):
                """The request's trace id: the sanitized client
                ``X-Veles-Trace`` header (direct hit or forwarded by
                the router) or a freshly minted edge id — cached per
                request so headers and body frames all carry ONE
                id."""
                tid = getattr(self, "_trace_", None)
                if tid is None:
                    tid = self._trace_ = reqtrace.ensure_trace_id(
                        self.headers.get(reqtrace.TRACE_HEADER))
                return tid

            def _tenant(self):
                """The request's resolved tenant id (cached like the
                trace id): a loopback peer's ``X-Veles-Tenant`` is
                trusted — the router forwards its bounded tenant
                label that way — while a direct remote caller
                resolves from its own bearer token."""
                ten = getattr(self, "_tenant_", None)
                if ten is None:
                    from veles_tpu.tenant import resolve_tenant
                    ten = self._tenant_ = resolve_tenant(
                        {k.lower(): v
                         for k, v in self.headers.items()},
                        loopback=self.client_address[0] in
                        ("127.0.0.1", "::1", "localhost"))
                return ten

            def do_GET(self):
                # drop any query string BEFORE trimming the trailing
                # slash — load-balancer probes send /healthz?probe=1
                self._trace_ = None  # fresh id per request
                self._tenant_ = None
                route = self.path.split("?")[0].rstrip("/")
                if route == "/debug/requests":
                    # the LIVE in-flight request table: trace id,
                    # phase, class, age, tokens, blocks held — the
                    # per-request half /debug/state's aggregates lack
                    sch = api.scheduler_
                    self._reply_json({
                        "replica": api.replica_id,
                        "draining": bool(api._draining_),
                        "requests": sch.debug_requests()
                        if sch is not None else [],
                    })
                    return
                if route == "/serving/metrics":
                    if api.scheduler_ is None:
                        self.send_error(404, "no serving scheduler")
                        return
                    self._reply_json(api.scheduler_.metrics())
                    return
                if route.startswith("/serving/kv_export/"):
                    # disaggregated handoff, the wire half: serve one
                    # parked prefill export (one-shot — the fetch
                    # consumes it; the handle is the capability)
                    if api.scheduler_ is None:
                        self.send_error(404, "no serving scheduler")
                        return
                    from veles_tpu.serving.disagg import encode_export
                    handle = route.rsplit("/", 1)[1]
                    rec = api.scheduler_.kv_export(handle)
                    if rec is None:
                        if api.scheduler_.kv_export_status(handle) \
                                == "fetched":
                            # a double-fetch RACE (two routers, a
                            # retry crossing the original) answers a
                            # structured 409, not a crash or a
                            # misleading 404: the record was served
                            # exactly once and the loser must re-run
                            # prefill, not retry the fetch
                            self._reply_error(
                                409, "kv export handle already "
                                "fetched (one-shot)")
                            return
                        self.send_error(
                            404, "unknown or expired kv export "
                            "handle")
                        return
                    if self._wants_binary():
                        # zero-copy binary framing (Accept:
                        # application/x-veles-kv) — the fast path
                        # both disagg handoffs and peer prefix
                        # fetches negotiate; legacy peers keep the
                        # b64-JSON envelope below
                        from veles_tpu.serving.disagg import \
                            encode_export_binary
                        self._reply_binary(encode_export_binary(rec))
                        return
                    self._reply_json(encode_export(rec))
                    return
                if route == "/healthz":
                    # liveness + health-policy state: 200 while the
                    # model is trainable/servable, 503 once the halt
                    # policy latched (the process stays up for
                    # forensics — load balancers just stop routing)
                    # or once a drain began (rolling restarts: the
                    # router stops sending traffic, in-flight work
                    # finishes)
                    import os
                    from veles_tpu.telemetry.health import monitor
                    state = monitor.state()
                    status = state["status"]
                    # "draining" must stay a DISTINCT top-level string
                    # (plus the boolean): a router parses it to route
                    # the replica as draining, which is NOT a health
                    # failure and must not trip its circuit breaker
                    sch = api.scheduler_
                    reply = {"status": status, "pid": os.getpid(),
                             "replica": api.replica_id,
                             "draining": bool(api._draining_),
                             # role-aware routing reads this: the
                             # router sends prefill traffic only to
                             # prefill/both replicas and client
                             # decode only to decode/both
                             "role": sch.role if sch is not None
                             else "both",
                             "tp": sch.tp if sch is not None else 0,
                             "health": state}
                    if api._draining_:
                        status = reply["status"] = "draining"
                        sch = api.scheduler_
                        reply["in_flight"] = \
                            sch.in_flight if sch is not None else 0
                        reply["drained"] = \
                            sch.drained if sch is not None else True
                    self._reply_json(
                        reply,
                        code=503 if status in ("halted", "draining")
                        else 200)
                    return
                if route == "/debug/state":
                    # flight-recorder tail of the LIVE process: recent
                    # span events + recorder/health state, the same
                    # ingredients a crash bundle would dump
                    from veles_tpu.logger import events
                    from veles_tpu.telemetry.flight_recorder import \
                        recorder
                    from veles_tpu.telemetry.health import monitor
                    self._reply_json({
                        "flightrec": recorder.state(),
                        "health": monitor.state(),
                        "events": list(events.ring)[-100:],
                        "logs": list(recorder.log_ring)[-50:],
                    })
                    return
                if route == "/v1/models":
                    # OpenAI-compatible model listing (ecosystem
                    # clients enumerate before they complete)
                    from veles_tpu.serving import openai_api
                    self._reply_json(openai_api.models_reply())
                    return
                if route == "/alerts":
                    # the replica-tier alert engine: firing/pending
                    # instances + the loaded rule set
                    if api.alerts_ is None:
                        self._reply_json({"enabled": False})
                        return
                    self._reply_json(api.alerts_.snapshot())
                    return
                if route == "/metrics/history":
                    # windowed queries over the replica's embedded
                    # history store (?series=...&window=...&agg=...
                    # &label.<k>=<v>&tier=N; no series = catalog)
                    if api.tsdb_ is None:
                        self._reply_json({"enabled": False},
                                         code=503)
                        return
                    from veles_tpu.telemetry.tsdb import \
                        history_query
                    query = self.path.partition("?")[2]
                    self._reply_json(
                        history_query(api.tsdb_, query))
                    return
                if route == "/metrics":
                    # Prometheus text exposition of the process-wide
                    # registry (serving, per-unit, compile series)
                    from veles_tpu.telemetry import metrics as registry
                    blob = registry.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(blob)))
                    self.end_headers()
                    self.wfile.write(blob)
                    return
                self.send_error(404)

            def _reply_json(self, obj, code=200):
                blob = json.dumps(obj, default=str).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                if api.replica_id:
                    self.send_header("X-Veles-Replica",
                                     str(api.replica_id))
                self.send_header(reqtrace.TRACE_HEADER,
                                 self._trace())
                self.send_header("Content-Length", str(len(blob)))
                self.end_headers()
                self.wfile.write(blob)

            def _reply_binary(self, blob, code=200):
                """Raw-bytes reply for the zero-copy KV wire
                (``application/x-veles-kv``): no JSON, no base64 —
                the body IS the frame."""
                from veles_tpu.serving.disagg import \
                    WIRE_CONTENT_TYPE
                self.send_response(code)
                self.send_header("Content-Type", WIRE_CONTENT_TYPE)
                if api.replica_id:
                    self.send_header("X-Veles-Replica",
                                     str(api.replica_id))
                self.send_header(reqtrace.TRACE_HEADER,
                                 self._trace())
                self.send_header("Content-Length", str(len(blob)))
                self.end_headers()
                self.wfile.write(blob)

            def _wants_binary(self):
                from veles_tpu.serving.disagg import \
                    WIRE_CONTENT_TYPE
                return WIRE_CONTENT_TYPE in \
                    (self.headers.get("Accept") or "")

            def _sent_binary(self):
                from veles_tpu.serving.disagg import \
                    WIRE_CONTENT_TYPE
                ctype = (self.headers.get("Content-Type")
                         or "").split(";")[0].strip().lower()
                return ctype == WIRE_CONTENT_TYPE

            def _read_raw(self):
                length = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(length)

            def _reply_error(self, code, message, retry_after=None,
                             **extra):
                """Structured error reply: ``{"error": {"code",
                "message", "trace_id", ...}}``; a 503's Retry-After
                header tells retrying clients (and the router) when
                this replica is worth another attempt, and the trace
                id makes the FAILURE correlatable with the server-
                side phase timeline — not just successes."""
                err = {"code": int(code),
                       "message": str(message or ""),
                       "trace_id": self._trace()}
                err.update({k: v for k, v in extra.items()
                            if v is not None})
                blob = json.dumps({"error": err},
                                  default=str).encode()
                self.send_response(int(code))
                self.send_header("Content-Type", "application/json")
                if api.replica_id:
                    self.send_header("X-Veles-Replica",
                                     str(api.replica_id))
                self.send_header(reqtrace.TRACE_HEADER,
                                 self._trace())
                if retry_after is not None:
                    self.send_header("Retry-After",
                                     str(max(1, int(retry_after))))
                self.send_header("Content-Length", str(len(blob)))
                self.end_headers()
                if getattr(self, "command", None) != "HEAD":
                    self.wfile.write(blob)

            def send_error(self, code, message=None, explain=None):
                # every error path (including the base class's own
                # calls) answers the structured JSON body — ad-hoc
                # HTML error pages are not machine-parseable
                self._reply_error(code, message or explain or "")

            def _read_body(self):
                length = int(self.headers.get("Content-Length", 0))
                return json.loads(self.rfile.read(length) or b"{}")

            def _reply_scheduler_error(self, e):
                """Map a SchedulerError to its structured HTTP reply
                (503 + class-aware Retry-After, 408 + partial-token
                count) — shared by /generate and the /v1 facade."""
                self._reply_error(
                    e.http_status, _status_text(e),
                    retry_after=getattr(e, "retry_after", None),
                    tokens_generated=getattr(e, "tokens_generated",
                                             None),
                    draining=True if api._draining_ else None)

            def _sse_headers(self):
                """Begin a Server-Sent-Events response; the
                connection close delimits the stream (HTTP/1.0 —
                no Content-Length)."""
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                if api.replica_id:
                    self.send_header("X-Veles-Replica",
                                     str(api.replica_id))
                self.send_header(reqtrace.TRACE_HEADER,
                                 self._trace())
                self.end_headers()
                self.close_connection = True

            def _relay_sse(self, ts, chunk_fn, final_fn):
                """Pump one TokenStream onto the wire: one SSE frame
                per accepted token (``chunk_fn(token) -> payload``),
                ``final_fn(error_or_None) -> payload`` as the
                terminal frame, then ``data: [DONE]``.  A client that
                disconnects mid-stream CANCELS the request — its slot
                and KV blocks return to the pool at the next decode
                boundary instead of decoding for nobody."""
                import time as _time

                from veles_tpu.serving.scheduler import SchedulerError
                from veles_tpu.serving.streams import (
                    SSE_DONE, StreamTimeoutError, sse_event)
                # backstop against a wedged loop with the watchdog
                # off: stop waiting, cancel, tell the client
                ts.token_timeout = api.request_timeout + 30.0
                tron = api.scheduler_ is not None \
                    and api.scheduler_._tron
                t0 = _time.monotonic()
                self._sse_headers()
                err = None
                try:
                    for tok in ts:
                        self.wfile.write(sse_event(chunk_fn(tok)))
                        self.wfile.flush()
                except (BrokenPipeError, ConnectionError, OSError):
                    ts.cancel()
                    if tron:
                        reqtrace.record(
                            ts.trace, "stream",
                            duration=_time.monotonic() - t0,
                            tokens=len(ts.tokens),
                            outcome="disconnect")
                    return
                except StreamTimeoutError as e:
                    ts.cancel()
                    err = SchedulerError(_status_text(e))
                except SchedulerError as e:
                    err = e
                try:
                    self.wfile.write(sse_event(final_fn(err)))
                    self.wfile.write(SSE_DONE)
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionError, OSError):
                    pass
                if tron:
                    # the delivery span: how long the wire emission
                    # ran and how many tokens it carried
                    reqtrace.record(
                        ts.trace, "stream",
                        duration=_time.monotonic() - t0,
                        tokens=len(ts.tokens),
                        outcome="ok" if err is None
                        else type(err).__name__)

            def _stream_generate(self, row, steps, temperature,
                                 top_k, seed, stop, priority,
                                 resume=None):
                """SSE for POST /generate {"stream": true}: one
                ``{"token": t}`` frame per accepted token (spec
                bursts arrive back to back), a terminal frame with
                the FULL token list (concatenation check: identical
                to the batch reply) + usage, then [DONE].  With
                ``resume`` (the failover lane) only the NEWLY drawn
                tokens stream — the terminal frame still carries the
                complete prompt + resumed + new list, so a router
                splicing the continuation into an interrupted stream
                delivers a terminal frame byte-identical to the
                uninterrupted run's."""
                from veles_tpu.serving.scheduler import SchedulerError
                resume = resume or []
                try:
                    ts = api.scheduler_.submit(
                        row, steps, temperature=temperature,
                        top_k=top_k,
                        seed=None if seed is None else int(seed),
                        stop_token=stop,
                        timeout=api.request_timeout,
                        priority=priority, stream=True,
                        trace=self._trace(),
                        resume_tokens=resume,
                        tenant=self._tenant())
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return

                def final(err):
                    # terminal/usage frames carry the trace id so a
                    # streamed reply (success OR failure) correlates
                    # with the server-side phase timeline
                    if err is not None:
                        return {"error": {
                            "code": getattr(err, "http_status", 500),
                            "message": _status_text(err),
                            "trace_id": ts.trace,
                            "tokens_generated": len(ts.tokens)}}
                    done = resume + ts.tokens
                    return {"done": True,
                            "tokens": ts.prompt + done,
                            "trace_id": ts.trace,
                            "usage": {
                                "prompt_tokens": len(ts.prompt),
                                "completion_tokens": len(done),
                                "total_tokens": len(ts.prompt)
                                + len(done)}}

                self._relay_sse(ts, lambda t: {"token": t}, final)

            def _v1_completions(self):
                """POST /v1/completions — the OpenAI facade over the
                same scheduler path /generate uses (stream and
                batch)."""
                from veles_tpu.serving import openai_api
                from veles_tpu.serving.scheduler import SchedulerError
                if api.forwards is None:
                    self.send_error(404,
                                    "this endpoint serves no model")
                    return
                try:
                    params = openai_api.parse_completions(
                        self._read_body())
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                rows = params["rows"]
                if len(rows) > api._cap("max_batch", 64):
                    self.send_error(400, "batch of %d prompts "
                                    "exceeds max_batch" % len(rows))
                    return
                if params["steps"] > api._cap("max_steps", 2048):
                    self.send_error(400, "max_tokens %d exceeds "
                                    "max_steps" % params["steps"])
                    return
                err = api._validate_rows(rows)
                if err:
                    self.send_error(400, err)
                    return
                if api.scheduler_ is None:
                    self.send_error(
                        501, "the OpenAI facade needs the serving "
                        "scheduler (serving=False pins legacy "
                        "/generate only)")
                    return
                import time as _time
                cid = openai_api.completion_id()
                created = int(_time.time())
                model = params["model"]
                if params["stream"]:
                    if len(rows) != 1:
                        self.send_error(400, "stream: true needs a "
                                        "single prompt row")
                        return
                    try:
                        ts = api.scheduler_.submit(
                            rows[0], params["steps"],
                            temperature=params["temperature"],
                            top_k=params["top_k"],
                            seed=params["seed"],
                            stop_token=params["stop"],
                            timeout=api.request_timeout,
                            priority=params["priority"],
                            stream=True, trace=self._trace(),
                            tenant=self._tenant())
                    except ValueError as e:
                        self.send_error(400, _status_text(e))
                        return
                    except SchedulerError as e:
                        self._reply_scheduler_error(e)
                        return

                    def chunk(tok):
                        return openai_api.completion_chunk(
                            cid, created, model, 0, [tok])

                    def final(err):
                        if err is not None:
                            return {"error": {
                                "code": getattr(err, "http_status",
                                                500),
                                "message": _status_text(err),
                                "trace_id": ts.trace}}
                        return openai_api.completion_chunk(
                            cid, created, model, 0, [],
                            finish=openai_api.finish_reason(
                                ts.tokens, params["steps"],
                                params["stop"]),
                            usage=openai_api.usage_of(
                                rows, [len(ts.tokens)]),
                            trace_id=ts.trace)

                    self._relay_sse(ts, chunk, final)
                    return
                try:
                    outs = api._generate_scheduled(
                        rows, params["steps"], params["temperature"],
                        params["top_k"], params["seed"],
                        params["stop"], priority=params["priority"],
                        trace=self._trace(),
                        tenant=self._tenant())
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return
                except concurrent.futures.TimeoutError:
                    self._reply_error(408, "decode timed out",
                                      tokens_generated=0)
                    return
                gens = [out[len(r):] for r, out in zip(rows, outs)]
                choices = [openai_api.completion_choice(i, r, g,
                                                        params)
                           for i, (r, g) in enumerate(zip(rows,
                                                          gens))]
                self._reply_json(openai_api.completion_reply(
                    cid, created, model, choices,
                    openai_api.usage_of(rows,
                                        [len(g) for g in gens])))

            def _v1_batch(self, kind):
                """POST /v1/embeddings | /v1/classify — batched
                non-LM scoring through the scheduler's aux lane (the
                decode loop runs the jitted pass between decode
                boundaries)."""
                from veles_tpu.serving import openai_api
                from veles_tpu.serving.scheduler import SchedulerError
                if api.forwards is None or api.scheduler_ is None:
                    self.send_error(404, "no servable model chain")
                    return
                try:
                    body = self._read_body()
                    rows, _ = openai_api.parse_token_rows(
                        body.get("input"), what="input")
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                if len(rows) > api._cap("max_batch", 64):
                    self.send_error(400, "batch of %d rows exceeds "
                                    "max_batch" % len(rows))
                    return
                err = api._validate_rows(rows)
                if err:
                    self.send_error(400, err)
                    return
                model = str(body.get("model")
                            or openai_api.model_id())
                try:
                    if kind == "embed":
                        fut = api.scheduler_.submit_embed(rows)
                    else:
                        fut = api.scheduler_.submit_score(rows)
                    out = fut.result(api.request_timeout + 30.0)
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return
                except concurrent.futures.TimeoutError:
                    self._reply_error(408, "scoring timed out")
                    return
                if kind == "embed":
                    self._reply_json(openai_api.embeddings_reply(
                        model, out, rows))
                else:
                    try:
                        top = int(body.get("top", 5))
                    except (TypeError, ValueError):
                        self.send_error(400, "top must be an int")
                        return
                    self._reply_json(openai_api.classify_reply(
                        model, out, rows, top))

            def _serving_prefill(self):
                """POST /serving/prefill — the disaggregated fleet's
                prefill half (roles "prefill"/"both"): chunk-prefill
                one prompt row, park its raw KV blocks + first-token
                logits under a handle, reply the handle.  The decode
                half fetches the export and POSTs it to
                /serving/kv_import on a decode replica."""
                from veles_tpu.serving.scheduler import SchedulerError
                if api.forwards is None or api.scheduler_ is None:
                    self.send_error(404, "no servable model chain")
                    return
                try:
                    body = self._read_body()
                    prompt = body.get("prompt")
                    if not isinstance(prompt, list) or not prompt \
                            or isinstance(prompt[0], list):
                        self.send_error(
                            400, "prompt must be ONE flat token "
                            "list (prefill export is per-request)")
                        return
                    rows = [[int(t) for t in prompt]]
                except (TypeError, ValueError):
                    self.send_error(400, "prompt must be a flat "
                                    "list of token ids")
                    return
                err = api._validate_rows(rows)
                if err:
                    self.send_error(400, err)
                    return
                try:
                    fut = api.scheduler_.submit_prefill(
                        rows[0], seed=body.get("seed"),
                        timeout=api.request_timeout,
                        priority=body.get("priority"),
                        trace=self._trace())
                    out = fut.result(api.request_timeout + 30.0)
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return
                except concurrent.futures.TimeoutError:
                    self._reply_error(408, "prefill timed out")
                    return
                out["trace_id"] = self._trace()
                self._reply_json(out)

            def _serving_kv_import(self):
                """POST /serving/kv_import — the decode half (roles
                "decode"/"both"): adopt an exported prefill record
                and decode; replies like a single-row /generate."""
                from veles_tpu.serving.disagg import (
                    decode_export, decode_export_binary)
                from veles_tpu.serving.scheduler import SchedulerError
                if api.forwards is None or api.scheduler_ is None:
                    self.send_error(404, "no servable model chain")
                    return
                try:
                    if self._sent_binary():
                        # binary frame: the record is the body, the
                        # sampler parameters ride the frame header's
                        # "extra" dict
                        export, body = decode_export_binary(
                            self._read_raw())
                    else:
                        body = self._read_body()
                        export = decode_export(
                            body.get("export") or {})
                    steps = int(body.get("steps", 0))
                    temperature = float(body.get("temperature")
                                        or 0.0)
                    top_k = int(body.get("top_k") or 0)
                    stop = body.get("stop")
                    stop = int(stop) if stop is not None else None
                except (TypeError, ValueError) as e:
                    self.send_error(400, _status_text(e))
                    return
                if steps > api._cap("max_steps", 2048):
                    self.send_error(400, "steps %d exceeds "
                                    "max_steps" % steps)
                    return
                try:
                    fut = api.scheduler_.submit_imported(
                        export, steps, temperature=temperature,
                        top_k=top_k, seed=body.get("seed"),
                        stop_token=stop,
                        timeout=api.request_timeout,
                        priority=body.get("priority"),
                        trace=self._trace())
                    toks = fut.result(api.request_timeout + 30.0)
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return
                except concurrent.futures.TimeoutError:
                    self._reply_error(408, "decode timed out",
                                      tokens_generated=0)
                    return
                self._reply_json({"tokens": toks})

            def _serving_prefix_export(self):
                """POST /serving/prefix_export — the fleet-wide
                prefix store's read half: body ``{"tokens": [...]}``,
                reply the raw KV blocks of the longest resident
                prefix of those tokens across both tiers (binary
                frame when Accept negotiates it), or 404 when
                nothing is resident.  Unlike /generate this WORKS on
                a draining replica — rescuing a drained peer's warm
                cache is the point."""
                from veles_tpu.serving.disagg import (
                    encode_export, encode_export_binary)
                from veles_tpu.serving.scheduler import SchedulerError
                if api.forwards is None or api.scheduler_ is None:
                    self.send_error(404, "no servable model chain")
                    return
                try:
                    body = self._read_body()
                    tokens = [int(t) for t in body.get("tokens")
                              or ()]
                except (TypeError, ValueError):
                    self.send_error(400, "tokens must be a flat "
                                    "list of token ids")
                    return
                try:
                    fut = api.scheduler_.submit_prefix_export(tokens)
                    rec = fut.result(api.request_timeout + 30.0)
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return
                except concurrent.futures.TimeoutError:
                    self._reply_error(408, "prefix export timed out")
                    return
                if rec is None:
                    self._reply_error(404, "no resident prefix for "
                                      "these tokens")
                    return
                if self._wants_binary():
                    self._reply_binary(encode_export_binary(rec))
                    return
                self._reply_json(encode_export(rec))

            def _serving_prefix_import(self):
                """POST /serving/prefix_import — the write half: the
                router ships a peer's prefix_export record here
                (binary frame, or legacy JSON under ``{"record":
                ...}``); new chunks join this replica's radix cache
                so the request behind the transfer — and every later
                one — admits warm.  Replies ``{"blocks": adopted}``."""
                from veles_tpu.serving.disagg import (
                    decode_export, decode_export_binary)
                from veles_tpu.serving.scheduler import SchedulerError
                if api.forwards is None or api.scheduler_ is None:
                    self.send_error(404, "no servable model chain")
                    return
                try:
                    if self._sent_binary():
                        record, _ = decode_export_binary(
                            self._read_raw())
                    else:
                        record = decode_export(
                            self._read_body().get("record") or {})
                except (TypeError, ValueError) as e:
                    self.send_error(400, _status_text(e))
                    return
                try:
                    fut = api.scheduler_.submit_prefix_import(record)
                    out = fut.result(api.request_timeout + 30.0)
                except ValueError as e:
                    self.send_error(400, _status_text(e))
                    return
                except SchedulerError as e:
                    self._reply_scheduler_error(e)
                    return
                except concurrent.futures.TimeoutError:
                    self._reply_error(408, "prefix import timed out")
                    return
                self._reply_json(out)

            def do_POST(self):
                self._trace_ = None  # fresh id per request
                self._tenant_ = None
                route = self.path.split("?")[0].rstrip("/")
                if route in ("/serving/prefill",
                             "/serving/kv_import"):
                    try:
                        faults.fire("restful.generate")
                        if route == "/serving/prefill":
                            self._serving_prefill()
                        else:
                            self._serving_kv_import()
                    except faults.InjectedHTTPError as e:
                        self._reply_error(
                            e.status, _status_text(e),
                            retry_after=1 if e.status == 503
                            else None)
                    except Exception as e:
                        self.send_error(500, _status_text(e))
                    return
                if route in ("/serving/prefix_export",
                             "/serving/prefix_import"):
                    # deliberately NOT behind restful.generate: a
                    # prefix transfer is cache plumbing, not a
                    # client request — its faults are injected at
                    # the router's router.prefix.fetch point
                    try:
                        if route == "/serving/prefix_export":
                            self._serving_prefix_export()
                        else:
                            self._serving_prefix_import()
                    except Exception as e:
                        self.send_error(500, _status_text(e))
                    return
                if route == "/v1/completions":
                    try:
                        faults.fire("restful.generate")
                        self._v1_completions()
                    except faults.InjectedHTTPError as e:
                        self._reply_error(
                            e.status, _status_text(e),
                            retry_after=1 if e.status == 503
                            else None)
                    except Exception as e:
                        self.send_error(500, _status_text(e))
                    return
                if route in ("/v1/embeddings", "/v1/classify"):
                    try:
                        faults.fire("restful.generate")
                        self._v1_batch("embed"
                                       if route == "/v1/embeddings"
                                       else "score")
                    except faults.InjectedHTTPError as e:
                        self._reply_error(
                            e.status, _status_text(e),
                            retry_after=1 if e.status == 503
                            else None)
                    except Exception as e:
                        self.send_error(500, _status_text(e))
                    return
                if self.path.rstrip("/") == "/serving/tune":
                    # the control plane's knob surface: the
                    # FleetController nudges shed_block_factor here
                    # under KV pressure.  Guarded like /drain — an
                    # open tuner is a shed-policy bypass — and the
                    # factor floors at 0.1 so no tune can disable
                    # admission shedding outright.
                    if not self._admin_ok():
                        self.send_error(
                            403, "tune needs loopback or the admin "
                            "token")
                        return
                    if api.scheduler_ is None:
                        self.send_error(
                            501, "tune needs the serving scheduler")
                        return
                    try:
                        body = self._read_body()
                        factor = body.get("shed_block_factor")
                        if factor is not None:
                            api.scheduler_.shed_block_factor = \
                                max(0.1, float(factor))
                    except (TypeError, ValueError) as e:
                        self.send_error(400, _status_text(e))
                        return
                    self._reply_json({
                        "shed_block_factor":
                            api.scheduler_.shed_block_factor,
                        "kv_blocks": api.scheduler_.kv_blocks})
                    return
                if self.path.rstrip("/") == "/shutdown":
                    # control-plane guard: when serving beyond loopback,
                    # only loopback peers (or a bearer of the admin
                    # token) may stop the workflow — an open /shutdown
                    # is a one-request denial of service
                    if not self._admin_ok():
                        self.send_error(
                            403, "shutdown needs loopback or the "
                            "admin token")
                        return
                    self._reply_json({"ok": True})
                    if api.shutdown_callback is not None:
                        api.shutdown_callback()
                    return
                if self.path.rstrip("/") == "/drain":
                    # rolling-restart hook: stop admitting (new
                    # submits 503 + Retry-After), finish in-flight,
                    # flip /healthz to 503 so the router drains this
                    # replica.  Guarded like /shutdown (an open drain
                    # is a one-request traffic blackhole), but the
                    # admin token lets a REMOTE router drain replicas
                    # it cannot reach over loopback.
                    if not self._admin_ok():
                        self.send_error(
                            403, "drain needs loopback or the admin "
                            "token")
                        return
                    api._draining_ = True
                    reply = {"draining": True}
                    if api.scheduler_ is not None:
                        api.scheduler_.drain()
                        reply["in_flight"] = api.scheduler_.in_flight
                        reply["drained"] = api.scheduler_.drained
                    self._reply_json(reply, code=202)
                    return
                if self.path.rstrip("/") == "/generate":
                    if api.forwards is None:
                        self.send_error(
                            404, "this endpoint serves no LM chain")
                        return
                    try:
                        faults.fire("restful.generate")
                        length = int(
                            self.headers.get("Content-Length", 0))
                        body = json.loads(self.rfile.read(length))
                        raw = body.get("prompt")
                        if not isinstance(raw, list):
                            # a scalar / missing / object prompt is a
                            # CLIENT error, not a 500 (ADVICE r5)
                            self.send_error(
                                400, "prompt must be a token list or "
                                "a batch of token lists")
                            return
                        squeeze = bool(raw) and \
                            not isinstance(raw[0], list)
                        rows = [raw] if squeeze else list(raw)
                        max_batch = api._cap("max_batch", 64)
                        if len(rows) > max_batch:
                            self.send_error(
                                400, "batch of %d prompts exceeds "
                                "max_batch %d" % (len(rows),
                                                  max_batch))
                            return
                        try:
                            lens = [len(r) for r in rows]
                        except TypeError:
                            self.send_error(
                                400, "prompt rows must be flat "
                                "lists of token ids")
                            return
                        if not rows or min(lens, default=0) < 1:
                            self.send_error(
                                400, "prompt rows must be non-empty "
                                "token lists")
                            return
                        # rows may be RAGGED: pad to the widest and
                        # hand the true lengths to the decode
                        width = max(lens)
                        prompt = numpy.zeros((len(rows), width),
                                             numpy.int32)
                        for i, r in enumerate(rows):
                            try:
                                row = numpy.asarray(r, numpy.int32)
                                if row.ndim != 1:
                                    raise ValueError(row.ndim)
                            except (TypeError, ValueError):
                                # nested/mixed rows are CLIENT errors,
                                # not server faults
                                self.send_error(
                                    400, "prompt rows must be flat "
                                    "lists of token ids")
                                return
                            prompt[i, :len(r)] = row
                        err = api._validate_prompt(prompt)
                        if err:
                            self.send_error(400, err)
                            return
                        try:
                            steps = int(body["steps"])
                            if steps < 0:
                                raise ValueError(steps)
                        except (KeyError, TypeError, ValueError):
                            # client error, not a server fault
                            # (ADVICE r5 #1)
                            self.send_error(
                                400, "steps must be a non-negative "
                                "int")
                            return
                        max_steps = api._cap("max_steps", 2048)
                        if steps > max_steps:
                            # an unbounded steps request costs a
                            # giant decode-window alloc + a fresh
                            # multi-second compile — cap it
                            self.send_error(
                                400, "steps %d exceeds max_steps %d"
                                % (steps, max_steps))
                            return
                        try:
                            temperature = float(
                                body.get("temperature", 0.0))
                            top_k = int(body.get("top_k", 0))
                        except (TypeError, ValueError):
                            self.send_error(
                                400, "temperature must be a number "
                                "and top_k an int")
                            return
                        stop = body.get("stop")
                        if stop is not None:
                            try:
                                stop = int(stop)
                            except (TypeError, ValueError):
                                self.send_error(
                                    400, "stop must be an int "
                                    "token id")
                                return
                        ragged = min(lens) != width
                        try:
                            beam = int(body.get("beam", 0))
                        except (TypeError, ValueError):
                            self.send_error(400, "beam must be an int")
                            return
                        if beam < 0:
                            self.send_error(400, "beam must be >= 1")
                            return
                        priority = body.get("priority")
                        if priority is not None:
                            from veles_tpu.serving.scheduler import \
                                resolve_priority
                            try:
                                resolve_priority(priority)
                            except ValueError as e:
                                self.send_error(400, _status_text(e))
                                return
                        resume = body.get("resume_tokens")
                        if resume is not None:
                            # the mid-stream-failover resume lane: a
                            # router re-submits an interrupted
                            # request with the tokens it already
                            # forwarded; the scheduler re-prefills
                            # prompt + prefix and continues at draw
                            # counter len(resume).  Loopback/admin
                            # only — an open resume lane would let
                            # any client bill continuations against
                            # arbitrary fabricated prefixes
                            if not self._admin_ok():
                                self.send_error(
                                    403, "resume_tokens is the "
                                    "loopback/admin failover lane")
                                return
                            try:
                                resume = [int(t) for t in resume]
                            except (TypeError, ValueError):
                                self.send_error(
                                    400, "resume_tokens must be a "
                                    "flat list of token ids")
                                return
                            rerr = api._validate_rows([resume]) \
                                if resume else None
                            if rerr:
                                self.send_error(400, rerr)
                                return
                            if beam or len(rows) != 1 \
                                    or api.scheduler_ is None \
                                    or steps < 1:
                                self.send_error(
                                    400, "resume_tokens needs the "
                                    "serving scheduler, a single "
                                    "prompt row, steps >= 1 and no "
                                    "beam")
                                return
                        if body.get("stream"):
                            # SSE token streaming rides the serving
                            # scheduler only (the legacy lockstep
                            # decode has no incremental tokens)
                            if beam:
                                self.send_error(
                                    400, "stream does not combine "
                                    "with beam search")
                                return
                            if api.scheduler_ is None or steps < 1:
                                self.send_error(
                                    400, "stream: true needs the "
                                    "serving scheduler and steps "
                                    ">= 1")
                                return
                            if len(rows) != 1:
                                self.send_error(
                                    400, "stream: true needs a "
                                    "single prompt row")
                                return
                            self._stream_generate(
                                rows[0], steps, temperature, top_k,
                                body.get("seed"), stop, priority,
                                resume=resume)
                            return
                        if beam:
                            if temperature or top_k:
                                self.send_error(
                                    400, "beam search is deterministic"
                                    " - drop temperature/top_k")
                                return
                            if stop is not None:
                                self.send_error(
                                    400, "beam search decodes fixed "
                                    "length - drop stop")
                                return
                            if ragged:
                                self.send_error(
                                    400, "beam search needs equal-"
                                    "length prompts")
                                return
                            try:
                                toks, scores = api._decode_beam(
                                    prompt, steps, beam)
                            except ValueError as e:
                                # beam > vocab / non-cacheable chain:
                                # the client's request, not our fault
                                self.send_error(400, _status_text(e))
                                return
                            toks = numpy.asarray(toks).tolist()
                            scores = numpy.asarray(scores).tolist()
                            reply = {"tokens": [r[0] for r in toks],
                                     "beams": toks, "scores": scores}
                            if squeeze:
                                reply = {"tokens": toks[0][0],
                                         "beams": toks[0],
                                         "scores": scores[0]}
                            self._reply_json(reply)
                            return
                        if api.scheduler_ is not None and steps >= 1:
                            # continuous batching: rows join decode
                            # slots independently — NO lock, so
                            # concurrent clients interleave
                            from veles_tpu.serving.scheduler import \
                                SchedulerError
                            try:
                                outs = api._generate_scheduled(
                                    rows, steps, temperature, top_k,
                                    body.get("seed"), stop,
                                    priority=priority,
                                    trace=self._trace(),
                                    resume_tokens=resume,
                                    tenant=self._tenant())
                            except ValueError as e:
                                self.send_error(400, _status_text(e))
                                return
                            except SchedulerError as e:
                                # 503s carry Retry-After; a deadline
                                # 408 reports the partial decode the
                                # client paid for before expiry
                                self._reply_error(
                                    e.http_status, _status_text(e),
                                    retry_after=getattr(
                                        e, "retry_after", None),
                                    tokens_generated=getattr(
                                        e, "tokens_generated", None),
                                    draining=True
                                    if api._draining_ else None)
                                return
                            except concurrent.futures.TimeoutError:
                                self._reply_error(
                                    408, "decode timed out",
                                    tokens_generated=0)
                                return
                            self._reply_json(
                                {"tokens": outs[0] if squeeze
                                 else outs})
                            return
                        tokens = api._decode(
                            prompt, steps, temperature, top_k,
                            body.get("seed"),
                            prompt_lens=lens if ragged else None,
                            stop_token=stop)
                        tokens = numpy.asarray(tokens)
                        # each row answers with ITS prompt + steps
                        # tokens (shorter rows decode past their quota
                        # in lockstep; the surplus is sliced off), cut
                        # at the first GENERATED stop token if one was
                        # requested (the stop itself stays in)
                        out = []
                        for i in range(len(rows)):
                            row = tokens[i, :lens[i] + steps]
                            if stop is not None:
                                hits = numpy.nonzero(
                                    row[lens[i]:] == int(stop))[0]
                                if hits.size:
                                    row = row[:lens[i] + hits[0] + 1]
                            out.append(row.tolist())
                        self._reply_json(
                            {"tokens": out[0] if squeeze else out})
                    except faults.InjectedHTTPError as e:
                        # the http_error fault action: REPLY the
                        # injected status as a structured error (a
                        # deliberately-failing replica, not a crash)
                        self._reply_error(
                            e.status, _status_text(e),
                            retry_after=1 if e.status == 503
                            else None)
                    except Exception as e:
                        self.send_error(500, _status_text(e))
                    return
                if self.path.rstrip("/") != "/api":
                    self.send_error(404)
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length))
                    sample = numpy.asarray(body["input"], numpy.float32)
                    future = api.loader.feed_request(sample)
                    result = future.result(api.request_timeout)
                    self._reply_json({"result": result})
                except Exception as e:  # one bad request must not kill
                    self.send_error(500, _status_text(e))  # the server

        self._server_ = ThreadingHTTPServer((self.host, self.port),
                                            Handler)
        self.port = self._server_.server_address[1]
        import os
        self.replica_id = self.replica_id \
            or "pid%d:%d" % (os.getpid(), self.port)
        self._thread_ = threading.Thread(
            target=self._server_.serve_forever, daemon=True,
            name="restful-api")
        self._thread_.start()
        from veles_tpu.config import root as _root
        if self.tsdb_ is None \
                and _root.common.tsdb.get("enabled", True):
            from veles_tpu.telemetry.tsdb import TimeSeriesStore
            self.tsdb_ = TimeSeriesStore(
                name=self.replica_id or "replica").start()
        if self.alerts_ is None \
                and _root.common.alerts.get("enabled", True):
            from veles_tpu.telemetry.alerts import AlertEngine
            self.alerts_ = AlertEngine(
                name=self.replica_id or "replica",
                tsdb=self.tsdb_).start()
        self.info("REST API on http://%s:%d/api", self.host, self.port)

    def run(self):
        futures = getattr(self.loader, "pending_futures_", [])
        if not futures:
            return
        out = self.output
        if isinstance(out, Array):
            out.map_read()
            out = out.mem
        for i, future in enumerate(futures):
            if not future.done():
                future.set_result(numpy.asarray(out[i]).tolist())
        self.loader.pending_futures_ = []

    def stop(self):
        alerts, self.alerts_ = self.alerts_, None
        if alerts is not None:
            alerts.stop()
        tsdb, self.tsdb_ = self.tsdb_, None
        if tsdb is not None:
            tsdb.stop()
        if self.scheduler_ is not None:
            self.scheduler_.close()
            self.scheduler_ = None
        if self._server_ is not None:
            self._server_.shutdown()
            # close the LISTENING socket too: a stopped replica must
            # refuse new connections (fast router failover) instead
            # of letting them rot in the dead server's accept backlog
            self._server_.server_close()
            self._server_ = None
