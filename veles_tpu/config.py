"""Global configuration tree.

TPU-native rebuild of the reference's attribute-autovivifying ``Config``
(ref: veles/config.py:60-152): settings live in a single global tree
``root.*``; reading a missing attribute creates a sub-tree, so user config
files can write ``root.mnist.learning_rate = 0.01`` without declarations.

Layered overrides (ref: veles/config.py:294-308): package defaults →
``/etc/default/veles_tpu`` → ``~/.veles_tpu`` → ``$PWD/site_config.py`` →
the per-run config file → ``-c "root.x=y"`` CLI snippets.
"""

import os
import runpy
from pathlib import Path


class Config:
    """A node in the config tree.  Attribute access autovivifies sub-trees."""

    def __init__(self, path="root"):
        object.__setattr__(self, "_path_", path)
        object.__setattr__(self, "_protected_", set())

    # -- tree behaviour ---------------------------------------------------

    def __getattr__(self, name):
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        child = Config("%s.%s" % (self._path_, name))
        object.__setattr__(self, name, child)
        return child

    def __setattr__(self, name, value):
        if name in self._protected_:
            raise AttributeError(
                "config key %s.%s is protected" % (self._path_, name))
        object.__setattr__(self, name, value)

    def protect(self, *names):
        """Mark keys read-only (ref: veles/config.py:79-84)."""
        self._protected_.update(names)

    def update(self, value):
        """Deep-merge a dict (or another Config) into this node."""
        if isinstance(value, Config):
            value = value.__content__()
        if not isinstance(value, dict):
            raise TypeError("Config.update() needs a dict, got %r" % (value,))
        for k, v in value.items():
            if k in self._protected_:
                raise AttributeError(
                    "config key %s.%s is protected" % (self._path_, k))
            if isinstance(v, dict):
                cur = vars(self).get(k)
                if not isinstance(cur, Config):
                    # a dict merge over a plain leaf replaces it with
                    # a fresh subtree (instead of crashing on
                    # None.update) — seeded from the leaf's own keys
                    # when the leaf was a plain dict, so layered
                    # overrides still MERGE rather than discard
                    node = Config("%s.%s" % (self._path_, k))
                    object.__setattr__(self, k, node)
                    if isinstance(cur, dict):
                        node.update(cur)
                getattr(self, k).update(v)
            else:
                setattr(self, k, v)
        return self

    def __content__(self):
        """The tree below this node as a plain nested dict."""
        out = {}
        for k, v in vars(self).items():
            if k.startswith("_") and k.endswith("_"):
                continue
            out[k] = v.__content__() if isinstance(v, Config) else v
        return out

    def get(self, name, default=None):
        """Read a key without autovivifying; Config-valued (unset) → default."""
        v = vars(self).get(name, default)
        return default if isinstance(v, Config) else v

    def get_dict(self, name, default=None):
        """Read a dict-valued key without autovivifying.  ``update``
        stores nested dicts AS subtrees, so plain ``get`` can't see
        them; this returns the subtree's content, a plain dict value,
        or ``default`` (for unset/None/empty)."""
        v = vars(self).get(name)
        if isinstance(v, Config):
            v = v.__content__()
        return dict(v) if v else default

    def __contains__(self, name):
        v = vars(self).get(name)
        return v is not None and not isinstance(v, Config)

    def __bool__(self):
        # An autovivified (empty) node is falsy so `if root.x.y:` is safe.
        return bool(self.__content__())

    def __iter__(self):
        return iter(self.__content__().items())

    def __repr__(self):
        return "Config(%s: %r)" % (self._path_, self.__content__())

    def print_(self, indent=0, file=None):
        import sys
        file = file or sys.stdout
        for k, v in sorted(vars(self).items()):
            if k.startswith("_") and k.endswith("_"):
                continue
            if isinstance(v, Config):
                print("  " * indent + k + ":", file=file)
                v.print_(indent + 1, file)
            else:
                print("  " * indent + "%s: %r" % (k, v), file=file)


#: The global configuration tree (ref: veles/config.py:152).
root = Config("root")

# -- package defaults (ref: veles/config.py:178-291) ----------------------

root.common.update({
    "dirs": {
        # downloads and the device-power table; the XLA compile cache
        # is placed by accelerated_units.enable_persistent_compile_cache
        "cache": os.path.join(
            os.environ.get("XDG_CACHE_HOME") or str(
                Path(__file__).resolve().parent.parent / ".cache"),
            "veles_tpu"),
        "snapshots": os.path.join(os.getcwd(), "snapshots"),
        "datasets": os.environ.get(
            "VELES_TPU_DATA", os.path.join(os.getcwd(), "data")),
    },
    "precision": {
        # dtype policy: compute dtype for matmuls/convs, accumulation dtype,
        # parameter dtype (replaces the reference's dtype/PRECISION_LEVEL
        # macro layer, ocl/defines.cl:1-69).
        "compute_dtype": "bfloat16",
        "accum_dtype": "float32",
        "param_dtype": "float32",
        # 0 = default XLA; 1/2 map to jax.lax.Precision.HIGH/HIGHEST
        # (replaces Kahan/multipartial PRECISION_LEVEL knobs,
        # ocl/matrix_multiplication_precise.cl:1-46).
        "level": 0,
    },
    "engine": {
        "backend": os.environ.get("VELES_TPU_BACKEND", "auto"),
        # eager: skip jit entirely (debugging, like the reference's
        # numpy fallback); fuse: compile accelerated-unit chains into
        # one XLA program per segment (accelerated_units.py)
        "eager": False,
        "fuse": True,
    },
    "timings": False,
    # device mesh for StandardWorkflow sharding, e.g. {'dp': -1}
    # (models/standard.py); None = single device
    "mesh": None,
    # appended to snapshot file names (ensemble members set 'ens<N>')
    "snapshot_suffix": "",
    # fraction of the train set an ensemble member sees (None = all;
    # set per member by veles_tpu.ensemble)
    "ensemble_train_ratio": None,
    # compilation_cache_dir: persistent XLA compilation cache
    # (jax_compilation_cache_dir) — kills multi-second recompiles
    # across CLI runs; also settable with --compilation-cache
    "trace": {"run": False, "profiler_dir": None,
              "compilation_cache_dir": None},
    # asynchronous input pipeline (loader/prefetch.py): streaming
    # loaders decode batch k+1 and upload it while step k computes;
    # depth = batches prepared ahead (0 disables).  Falls back to the
    # synchronous path for master/slave serving and cross-process
    # meshes automatically.
    "loader": {"prefetch": {"enabled": True, "depth": 2}},
    # REST /generate resource caps (satellite of the input-pipeline
    # PR): oversize requests get a 400 instead of a giant alloc +
    # multi-second compile.  admin_token (also --admin-token) lets a
    # NON-loopback caller hit the admin endpoints (/drain, /shutdown)
    # with "Authorization: Bearer <token>" — unset, they stay
    # loopback-only
    # model_id is the name the OpenAI facade (/v1/models,
    # /v1/completions) serves the chain under
    "api": {"max_steps": 2048, "max_batch": 64, "admin_token": None,
            "model_id": "veles-lm"},
    # multi-replica fleet router (serving/router.py): health-aware
    # load balancing over N engine replicas with per-replica circuit
    # breakers (closed -> open after breaker_failures consecutive
    # failures, half-open single-probe recovery after
    # breaker_cooldown), capped-exponential retry backoff with jitter
    # (retry_delay base, retry_cap cap, retries total attempts, never
    # past the request deadline), straggler hedging for idempotent
    # requests (hedge_delay seconds; 0 disables), prompt-prefix
    # session affinity (first affinity_tokens tokens; 0 disables) and
    # fleet-level shedding (503 + shed_retry_after once no replica is
    # eligible).  request_timeout None defers to
    # root.common.serving.request_timeout.
    "router": {
        "health_interval": 0.5,
        "health_timeout": 1.0,
        "breaker_failures": 3,
        "breaker_cooldown": 2.0,
        "retries": 3,
        "retry_delay": 0.05,
        "retry_cap": 2.0,
        "hedge_delay": 0.0,
        "affinity_tokens": 16,
        "request_timeout": None,
        "shed_retry_after": 2,
        # cache-topology routing (PR 19): prefix_routing routes
        # single-row /generate bodies to the replica advertising the
        # longest resident prefix (falls back to crc32 affinity when
        # nobody is warm); prefix_fetch additionally SHIPS a peer's
        # longer resident prefix onto the chosen replica over the
        # binary KV wire before forwarding, when the peer leads by
        # at least prefix_fetch_min blocks (best-effort — failures
        # admit cold and count prefix_peer_fetch_fails)
        "prefix_routing": True,
        "prefix_fetch": True,
        "prefix_fetch_min": 2,
    },
    # host-side instrumentation (per-unit spans + metric histograms,
    # veles_tpu/telemetry/) — on by default, overhead-gated in CI.
    # cost_analysis: capture XLA cost/memory analysis once per jitted
    # entry point (one extra AOT compile each; degrades to Nones when
    # the backend can't report)
    "telemetry": {"enabled": True, "cost_analysis": True},
    # training-health monitor (telemetry/health.py): policy is what
    # happens on a NaN/Inf step — warn | skip_step (drop the update
    # in-graph) | halt (stop the workflow, keep the process up)
    "health": {
        "enabled": True,
        "policy": "warn",
        "grad_norm_max": None,
        "sync_every": 1,
        "ema_beta": 0.9,
        "divergence_tolerance": 1.5,
        "divergence_patience": 3,
    },
    # crash flight recorder (telemetry/flight_recorder.py): bundle
    # lands in `dir` (default: the snapshot dir) on crash/SIGUSR1
    "flightrec": {"enabled": True, "dir": None, "dump_on_exit": False},
    # alerting engine (telemetry/alerts.py): a low-frequency ticker
    # evaluates declarative rules over the metrics registry with a
    # pending -> firing -> resolved state machine and for_seconds
    # hold-downs.  `defaults` ships the built-in rule set (SLO burn
    # fast+slow, breaker open, health halt, replica unreachable, KV
    # pressure, watchdog stall, prefix-hit collapse, padding waste);
    # `rules` appends user rules as dicts — {"name", "expr", "for",
    # "severity"} with expr = "[func(]family[{k=v}][)] OP number"
    # (see docs/observability.md for the grammar).  webhook_url gets
    # a JSON POST per fire/resolve (best-effort sink, fault point
    # `alerts.webhook`); router and serving replicas each run one
    # engine when enabled, served at GET /alerts
    "alerts": {
        "enabled": True,
        "interval": 1.0,
        "defaults": True,
        "rules": (),
        "webhook_url": None,
    },
    # per-request distributed tracing (telemetry/reqtrace.py): trace
    # ids minted at the edge (or accepted via X-Veles-Trace),
    # propagated router -> replica -> scheduler, phase spans appended
    # to the JSONL event sink.  ON by default; tier-1 counts its events
    # (one a decode boundary; the tracing_overhead marker) and the
    # loop's observe phase times them on the chip.  Disabling stops the
    # span emission only — ids still mint and echo, so client-side
    # correlation keeps working
    "reqtrace": {"enabled": True},
    # serving SLOs (serving/metrics.py SLOTracker): per-priority-class
    # latency objectives in ms — ttft_ms gates submit->first-token at
    # the replica, e2e_ms gates whole-request time (replica-side AND
    # the router's all-attempts fleet tail); None disables a class.
    # target is the success ratio whose complement is the error
    # budget; windows are the trailing burn-rate horizons in seconds
    # (multi-window: pair a fast window for paging with a slow one
    # for ticketing).  Exported as the veles_slo_* families and the
    # "slo" block of /serving/metrics and /router/state
    "slo": {
        "enabled": True,
        "target": 0.99,
        "windows": (60.0, 300.0, 3600.0),
        "ttft_ms": {"low": 5000.0, "normal": 2000.0, "high": 500.0},
        "e2e_ms": {"low": 120000.0, "normal": 60000.0,
                   "high": 30000.0},
    },
    # continuous-batching serving knobs (serving/scheduler.py):
    # kv_blocks None derives the pool that holds max_slots requests
    # of full length (max_slots * ceil(window / block_size));
    # prefill_chunk is the NARROWEST prefill chunk in positions and
    # the longest prompt that prefills one-shot (0 disables chunked
    # prefill); above it the scheduler picks each chunk's width
    # itself, the power-of-two bucket of what the request has left up
    # to the chip's ridge (256 positions; a chain whose chunk scans
    # its positions stays at prefill_chunk), serving/scheduler.py
    # chunk_width; request_timeout is
    # the whole-request deadline in seconds (queued + decoding; 0
    # disables); watchdog is the stuck-decode-loop detector threshold
    # in seconds (0 disables — keep it far above the worst
    # first-compile stall);
    # shed_block_factor sheds new submits (503) once the queue's
    # committed block budget exceeds factor x kv_blocks (0 disables);
    # spec enables speculative decoding (n-gram prompt-lookup drafts
    # + one batched verify pass; spec_k tokens drafted per slot,
    # output streams bit-identical to spec-off); prefix_cache enables
    # the cross-request radix prefix cache over the paged block pools
    # (warm prompts skip prefill for resident leading blocks) with
    # prefix_evict allowing LRU eviction of refcount-0 resident
    # blocks under admission pressure.  Both DEFAULT ON since the
    # PR 10 mixed-priority soak (the "after real-traffic soak" gate
    # PR 9 left open): streams are bit-identical either way, so the
    # knobs are opt-OUT (spec needs a verify-capable chain and
    # prefix_cache needs chunked prefill + a pow2 block size — the
    # scheduler falls back automatically when unsupported)
    # kv_dtype "fp32" keeps the compute-dtype pools (bit-parity
    # baseline); "int8" stores the paged K/V pools quantized with
    # per-row scales beside the block tables — ~half the bytes per
    # cached token, so the same kv_blocks HBM budget decodes ~2x the
    # concurrent streams (quality-gated: serving/kv_quality.py +
    # quality.py kv_quant record).  fused_verify scores the
    # speculative run in ONE pass (no scatter-then-gather round
    # trip); it is allclose rather than bit-identical to the
    # two-pass verify, so the fp32 parity baseline keeps it OFF
    # (int8 pools always verify fused)
    # tp shards the jitted serving steps over a {"tp": N} mesh
    # (Megatron column/row weight splits, head-wise paged K/V pools
    # — per-chip kv_blocks HBM drops by the factor; serving/tp.py);
    # 0 disables.  role disaggregates prefill from decode across a
    # fleet: "prefill" replicas chunk-prefill and export finished KV
    # blocks (GET /serving/kv_export/<handle>), "decode" replicas
    # import them (POST /serving/kv_import) and run the decode loop;
    # "both" (default) keeps the colocated single-replica shape.
    "serving": {
        "tp": 0,
        "role": "both",
        "block_size": 16,
        "kv_blocks": None,
        "kv_dtype": "fp32",
        "fused_verify": False,
        "prefill_chunk": 64,
        "warm_buckets": True,
        "request_timeout": 120.0,
        "watchdog": 300.0,
        "shed_block_factor": 4.0,
        "spec": True,
        "spec_k": 4,
        "prefix_cache": True,
        "prefix_evict": True,
        # tiered KV (PR 19): kv_host_bytes > 0 arms the host-RAM
        # overflow tier — prefix blocks evicted from the device trie
        # demote into host buffers (byte-budgeted, LRU) and promote
        # back when a matching prompt admits; 0 disables (evictions
        # discard, the pre-tier behavior).  kv_export_bytes caps the
        # TOTAL bytes parked in pending disagg KV exports (oldest
        # records expire first once over), replacing the old flat
        # 64-record cap — a byte budget tracks the actual HBM-sized
        # payloads a prefill replica holds for its decode peers
        "kv_host_bytes": 0,
        "kv_export_bytes": 256 << 20,
        # model-based drafting (PR 20): drafter "model" arbitrates a
        # Medusa-style draft head (serving/draft.py, conditioned on
        # the engine's hidden-state lane) against the free n-gram
        # proposer per slot by accept-rate EMA; "ngram" (default)
        # keeps the self-speculative baseline — either way the
        # emitted streams are bit-identical to spec off, drafting
        # moves throughput only.  The EMA controller adapts each
        # slot's draft length between draft_k_min and spec_k along
        # the warmed power-of-two verify buckets: blend weight
        # draft_ema, halve below draft_shrink, double above
        # draft_grow.  tp_overlap swaps the GSPMD-partitioned tp
        # step for an explicit shard_map step whose row-parallel
        # all-reduces are expressed per shard (collective-permute at
        # tp=2), letting XLA schedule the combine against the
        # residual/LN compute — fp32 pools only (int8 per-row scales
        # need full-row amax), bit-identical to the GSPMD step.
        "drafter": "ngram",
        "draft_k_min": 1,
        "draft_ema": 0.5,
        "draft_shrink": 0.5,
        "draft_grow": 0.8,
        "tp_overlap": False,
    },
    # replica supervision (serving/fleet.py): rebalance lets a
    # disaggregated fleet re-role replicas when a whole role pool
    # loses its last live member — a respawn fills the empty pool
    # instead of its own (when its own keeps a member), and the
    # monitor restarts a surplus replica into a pool no respawn is
    # filling.  Off, a dead pool stays dead until a human re-roles
    # the fleet (the pre-rebalance behavior).
    "fleet": {"rebalance": True},
    # fleet control plane (serving/controller.py): a FleetController
    # loop on the router host closes three loops — replica count
    # (scale up on the fast+slow SLO-burn pair or queue pressure,
    # scale down via drain when both windows are quiet), the
    # prefill:decode role ratio (prefill queue wait vs decode slot
    # occupancy, moved through Fleet.restart_as), and KV knobs
    # (shed_block_factor nudges via POST /serving/tune, kv_blocks
    # recommendations as audit events only).  Off by default: the
    # controller only ever acts when an operator arms it.
    # Hysteresis: scale-up needs the burn pair OR mean queue depth
    # >= queue_high; scale-down needs quiet_ticks consecutive calm
    # ticks AND mean slot occupancy <= occupancy_low, and each
    # direction honors its own cooldown.  role_deadband is the
    # minimum normalized pressure gap before a re-role fires.
    "controller": {
        "enabled": False,
        "interval": 2.0,
        "min_replicas": 1,
        "max_replicas": 4,
        "scale_up_cooldown": 10.0,
        "scale_down_cooldown": 30.0,
        "quiet_ticks": 5,
        "queue_high": 4.0,
        "occupancy_low": 0.3,
        "role_deadband": 0.25,
        "kv_pressure_high": 0.85,
        "kv_pressure_low": 0.5,
        "shed_step": 0.5,
        "shed_min": 1.0,
        "shed_max": 8.0,
        "audit_keep": 256,
        "history_window": 30.0,
    },
    # per-tenant admission economics (tenant/admission.py): the
    # router resolves a tenant id from the auth header (hash of the
    # bearer token, or X-Veles-Tenant on loopback) and tags every
    # request with it; with enabled=True it also enforces a
    # per-tenant token bucket (rate tokens/s, burst capacity;
    # exceeding it is a structured 429 + Retry-After) and a
    # weighted-fair concurrency lane (max_concurrent in-flight
    # requests per tenant, 0 = no cap) so a flooding tenant degrades
    # only itself.  label_cardinality bounds the metrics label: the
    # first N distinct tenants keep their own label value, the rest
    # report as "other".
    "tenant": {
        "enabled": False,
        "rate": 0.0,
        "burst": 0.0,
        "max_concurrent": 0,
        "label_cardinality": 8,
    },
    # embedded time-series store (telemetry/tsdb.py): a background
    # ticker samples the metrics registry (replicas) or the federated
    # fleet merge (router) into downsampling tiers of
    # (step_s, retention_s) ring buffers — counters as per-bucket
    # deltas so rates are exact across tier boundaries, gauges as
    # (count, sum, min, max, last) aggregates.  Queryable via
    # GET /metrics/history and TimeSeriesStore.range(); feeds the
    # *_over_time/deriv/drop_vs_baseline alert functions, the
    # controller's history windows and the dashboard sparklines.
    # max_series caps distinct stored series (later arrivals are
    # dropped + counted); max_bytes is the estimated-allocation
    # budget (least-recently-updated whole series evicted when
    # exceeded).  metering gates the scheduler's per-tenant usage
    # attribution (veles_tenant_usage_* families + /tenants/usage)
    # — separate knob so the on-vs-off overhead soak can isolate it.
    "tsdb": {
        "enabled": True,
        "tiers": ((1.0, 600.0), (10.0, 3600.0), (60.0, 86400.0)),
        "max_series": 512,
        "max_bytes": 16 << 20,
        "metering": True,
    },
    # fault injection (veles_tpu/faults/): spec string parsed on first
    # fire(), same grammar as the VELES_FAULTS env var —
    # "point=action[:arg][@after][xtimes][~key];..." (empty = unarmed)
    "faults": {"spec": ""},
    # status dashboard bind address (web_status.py) and the
    # status_url a Launcher pushes run updates to (None = don't)
    "web": {"host": "localhost", "port": 8090, "status_url": None},
    # live matplotlib graphics service (launcher --graphics)
    "graphics": {"enabled": False, "port": 0},
    # report publishing backends; keys under `confluence` are
    # site-supplied (server/space/token/...) — an OPEN config subtree
    "publishing": {"confluence": {}},
})
root.common.protect("dirs")


def _exec_globals():
    g = {"root": root, "Config": Config}
    try:  # genetics tuneables are first-class config values
        from veles_tpu.genetics import Choice, Range
        g["Range"] = Range
        g["Choice"] = Choice
    except ImportError:  # pragma: no cover
        pass
    return g


def apply_config_file(path, extra_globals=None):
    """Execute a per-run config file: plain Python mutating ``root``
    (ref: veles/__main__.py:436-438)."""
    g = _exec_globals()
    if extra_globals:
        g.update(extra_globals)
    runpy.run_path(path, init_globals=g)


def apply_override(snippet):
    """Apply a ``-c "root.x.y = z"`` CLI override
    (ref: veles/__main__.py:474-481)."""
    exec(snippet, _exec_globals())


def load_site_configs():
    """Merge layered site overrides (ref: veles/config.py:294-308)."""
    for p in ("/etc/default/veles_tpu",
              str(Path.home() / ".veles_tpu"),
              os.path.join(os.getcwd(), "site_config.py")):
        if os.path.isfile(p):
            try:
                runpy.run_path(p, init_globals={"root": root})
            except Exception:  # site files must never break startup
                import logging
                logging.getLogger("config").exception(
                    "failed to apply site config %s", p)


def get(cfg, default=None):
    """``get(root.x.y, default)`` — unset (Config) values become default."""
    return default if isinstance(cfg, Config) else cfg
