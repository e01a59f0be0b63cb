"""Continuous-batching inference serving (Orca/vLLM lineage), built
natively on the jitted decode machinery in ``models/generate``.

The decode path this package replaces served one client at a time:
REST ``/generate`` held a single decode lock and prompt prefill was a
per-token scan.  Here:

- :mod:`veles_tpu.serving.prefill` — batched prefill: ONE jitted
  forward over the whole prompt fills the KV cache (TTFT O(1)
  compiled steps instead of O(prompt_len)), and CHUNKED prefill
  (:func:`prefill_chunk`) splits long prompts into chunks (as wide
  as still costs one stream of the weights: ``scheduler.chunk_width``)
  the scheduler interleaves with decode steps (Sarathi-style) so a
  joining long prompt cannot stall in-flight streams;
- :mod:`veles_tpu.serving.kv_slots` — the KV cache: block-PAGED
  (:class:`PagedKVCache` — vLLM PagedAttention lineage: per-layer
  block pools + per-slot block tables, so memory scales with each
  request's actual length and admission is memory-proportional);
- :mod:`veles_tpu.serving.engine` — the shared compiled decode
  steps: per-slot positions, per-slot sampler settings, per-request
  PRNG streams; the step packs only the active slots into
  power-of-two occupancy buckets and bounds attention by a block
  bucket over the deepest request;
- :mod:`veles_tpu.serving.scheduler` — the continuous-batching
  scheduler: requests join free slots and claim their block
  budget at token boundaries and leave on stop-token/step-limit,
  with admission control (queue-depth cap → 503, queue deadline →
  408) and a background decode loop;
- :mod:`veles_tpu.serving.metrics` — per-request TTFT, tokens/sec,
  queue depth, slot occupancy, KV-block occupancy and prefill-chunk
  stalls, exposed through the JSONL event sink
  (:mod:`veles_tpu.logger`) and a ``snapshot()`` dict;
- :mod:`veles_tpu.serving.router` — the multi-replica fleet tier: a
  health-aware asyncio HTTP router (least-outstanding routing with
  prefix/session affinity, per-replica circuit breakers, deadline-
  bounded retries with capped backoff, bounded hedging for
  idempotent requests, fleet-level load shedding) over N engine
  replicas;
- :mod:`veles_tpu.serving.fleet` — replica supervision: spawn N
  replicas (in-process or subprocess handles), respawn the dead, and
  orchestrate zero-downtime rolling restarts (drain → restart →
  re-admit) through the router;
- :mod:`veles_tpu.serving.spec` — speculative decoding: the n-gram
  prompt-lookup draft proposer whose k drafts the batched verify
  step (``engine.verify_step_paged``) scores in ONE model pass —
  accepted prefixes are pure latency win, output streams stay
  bit-identical to spec-off decoding;
- :mod:`veles_tpu.serving.draft` — MODEL-based drafting past the
  n-gram ceiling: Medusa-style per-position heads over the target's
  final hidden state (the engine's ``want_hidden`` lane), trained
  against the frozen target, arbitrated per slot against the free
  n-gram proposer by accept-rate EMA — which also adapts each
  slot's draft length along the warmed verify width buckets;
- :mod:`veles_tpu.serving.prefix_cache` — the cross-request radix
  prefix cache (SGLang lineage) over the paged block pools: finished
  requests donate their KV blocks, warm prompts skip prefill for
  every resident leading block and claim only their cold tail's
  budget;
- :mod:`veles_tpu.serving.streams` — per-request incremental token
  delivery: ``submit(..., stream=True)`` returns a
  :class:`TokenStream` the decode loop pushes accepted tokens into
  (SSE surfaces on REST and the router proxies them chunk by chunk);
- :mod:`veles_tpu.serving.openai_api` — the OpenAI-compatible facade
  (``/v1/completions`` with streaming + usage, ``/v1/models``) and
  the servable non-LM endpoints (batched ``/v1/embeddings`` pooled
  hidden states, ``/v1/classify`` last-position class scores), both
  executed on the decode loop's aux lane;
- :mod:`veles_tpu.serving.tp` — tensor-parallel serving: the jitted
  steps shard over a ``{"tp": N}`` mesh (Megatron column/row weight
  splits, HEAD-WISE paged pools — per-chip ``kv_blocks`` HBM drops
  by the mesh factor) while every host-side structure stays
  replicated, so a model too wide for one chip still serves with
  tp=1-bit-identical greedy streams;
- :mod:`veles_tpu.serving.disagg` — disaggregated prefill/decode
  (DistServe lineage): prefill-role replicas export finished KV
  blocks raw (scales riding along) under a handle, decode-role
  replicas import them and run only the token loop, and the router
  dispatches by role — handoff streams identical to colocated.
"""

from veles_tpu.serving.engine import (  # noqa: F401
    hidden_supported, overlap_supported, paged_decode_step,
    verify_step_paged, verify_supported)
from veles_tpu.serving.kv_slots import PagedKVCache  # noqa: F401
from veles_tpu.serving.prefix_cache import (  # noqa: F401
    RadixPrefixCache)
from veles_tpu.serving.spec import (  # noqa: F401
    NgramIndex, NgramProposer, accept_drafts)
from veles_tpu.serving.draft import (  # noqa: F401
    MedusaDraftHead, draft_supported)
from veles_tpu.serving.kv_quality import (  # noqa: F401
    kv_quant_quality, weight_quant_quality)
from veles_tpu.serving.metrics import (  # noqa: F401
    RouterMetrics, ServingMetrics)
from veles_tpu.serving.prefill import (  # noqa: F401
    chunked_supported, prefill, prefill_chunk, serving_supported)
from veles_tpu.serving.fleet import (  # noqa: F401
    Fleet, LocalReplica, SubprocessReplica, free_port)
from veles_tpu.serving.router import Router  # noqa: F401
from veles_tpu.serving.scheduler import (  # noqa: F401
    CLASS_NAMES, DeadlineExceededError, DrainingError,
    InferenceScheduler, PRIORITIES, QueueFullError,
    RequestCancelledError, RoleMismatchError, SchedulerError,
    resolve_priority)
from veles_tpu.serving.tp import (  # noqa: F401
    ServingTP, per_chip_bytes, tp_allreduce, tp_supported)
from veles_tpu.serving.weights import ServingWeights  # noqa: F401
from veles_tpu.serving.disagg import (  # noqa: F401
    decode_export, encode_export)
from veles_tpu.serving.streams import (  # noqa: F401
    SSE_DONE, StreamTimeoutError, TokenStream, sse_event)
from veles_tpu.serving import openai_api  # noqa: F401
