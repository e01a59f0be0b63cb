"""Traffic kind ``serve_closed_solar``: ``serve_closed``'s closed loop of
streaming clients (its load generator, window, counters and release,
imported as they are) over a ``solar_open2`` chain, this chip's share of
a stated deployment.  What differs is what the model forces:

``build``  the chain is ``benchmark/solar_glue.layer_spec`` (every layer
    told which experts it holds) and its weights are handed over as
    bfloat16 device leaves, one at a time, BEFORE the units initialize,
    so no float32 copy of the model is ever made; the scheduler gets its
    window by argument (the model has no positions) and has the prefix
    cache and speculation off (it refuses both for a chain with per-slot
    state).  A program without the unit fails at once, before anything
    is started.
``window``  ``serve_closed``'s, with the profiler stopped after the
    traffic file's ``trace_seconds`` (``serve_closed_ouro.LeadingPart``):
    the profiler keeps a fixed number of device events, so it is handed
    a leading part of the window that fits.  Counters and end-to-end
    numbers are of the whole window.  The ``window`` line of EVERY run,
    traced or not, also says what its seed's router did (``routing``):
    the runs of this cell differ by their seed's step, and the step
    follows the held experts its rows touch.
``check``  logits over the held vocabulary slice, not tokens, against
    ``benchmark/solar_reference.py`` run layer by layer over each sampled
    request's prompt and served tokens, given the same held experts, in
    float32 and in the int8 control: ``served_gap_vs_int8`` as in
    ``serve_closed`` (the mean gap by which a served token's logit lies
    below the reference's best, over the same mean for the tokens the
    control puts first).  Routing is discontinuous, so the line ``gaps``
    also carries the share of (token, layer) pairs whose margin between
    the last expert chosen and the first left out is under the bfloat16
    step.  With ``control`` every planted fault of the reference
    (``solar_reference.FAULTS``) and the control in the program's place
    are judged too, each an earlier line.
"""

import time

import numpy

from benchmark import compare, reference, solar_glue, solar_reference, \
    solar_weights, traffic as traffic_gen
from benchmark.drivers import serve_closed
from benchmark.drivers.serve_closed import (  # noqa: F401  (the driver)
    REQUEST_TIMEOUT_S, WARM_SEED_OFFSET, LoadGen, counters, failures,
    pad_length, release, sample_for_check)
from benchmark.drivers.serve_closed_ouro import LeadingPart

#: requests of the sample that each planted fault is judged over
FAULT_REQUESTS = 8


def build(ctx):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models import standard
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    shapes, serve = ctx.shapes, ctx.config["serve"]
    spec = solar_glue.layer_spec(shapes)
    missing = sorted({s["type"] for s in spec
                      if s["type"] not in standard.LAYER_TYPES})
    if missing:        # before anything is started: a program without
        raise SystemExit(      # the layer fails at once
            "benchmark: this program has no %s unit" % missing)
    wf = AcceleratedWorkflow(None, name="bench-serve")
    loader = RestfulLoader(wf, sample_shape=(shapes["positions"],),
                           minibatch_size=1, max_wait=1.0)
    loader.initialize(device=ctx.device)
    forwards = standard.make_forwards(wf, loader.minibatch_data, spec)
    handed = solar_glue.hand_over_weights(forwards, ctx.seed, shapes)
    for unit in forwards:
        unit.initialize(device=ctx.device)
    stats = ctx.jax_device.memory_stats() or {}
    ctx.log("weights", handed_bytes=handed,
            bytes_in_use=stats.get("bytes_in_use"),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    api = RESTfulAPI(
        wf, loader=loader, port=0, host="127.0.0.1", serving=True,
        max_slots=serve["max_slots"], max_queue=serve["max_queue"],
        serving_window=serve["window"],
        serving_block_size=serve["block_size"],
        serving_spec=serve["spec"],
        serving_prefix_cache=serve["prefix_cache"], forwards=forwards,
        serving_warm_buckets=False, request_timeout=REQUEST_TIMEOUT_S)
    api.output = forwards[-1].output
    api.initialize()
    return wf, loader, forwards, api


def setup(ctx):
    """``serve_closed.setup`` over this file's ``build``: sweep, ladder,
    then requests of the mix, all from seed + 1000003."""
    from veles_tpu.telemetry import compile_summary
    t0 = time.monotonic()
    wf, loader, forwards, api = build(ctx)
    ctx.log("built", seconds=round(time.monotonic() - t0, 3),
            params=solar_weights.count_params(ctx.shapes))
    state = {"wf": wf, "loader": loader, "forwards": forwards,
             "api": api, "ctx": ctx}
    warm, vocab = ctx.traffic["warmup"], ctx.shapes["vocab"]
    warm_seed = ctx.seed + WARM_SEED_OFFSET

    def stage(name, run, **facts):
        seen = compile_summary()["total"]["compiles"]
        t0 = time.monotonic()
        for reply in run():
            bad = failures(reply["records"])
            if bad:
                raise RuntimeError("warm-up %s: %s" % (name, bad[:3]))
        ctx.log("warm_" + name, seconds=round(time.monotonic() - t0, 3),
                compiled=compile_summary()["total"]["compiles"] - seen,
                **facts)
    try:
        gen = state["gen"] = LoadGen(api.port)
        clients = ctx.traffic["clients"]
        sweep = traffic_gen.sweep_list(ctx.traffic["requests"], vocab,
                                       warm_seed, warm["sweep_steps"])
        stage("sweep", lambda: [gen.loop(sweep, clients)],
              requests=len(sweep))
        rungs = traffic_gen.ladder_list(warm["ladder"], vocab, warm_seed)
        stage("ladder", lambda: (gen.batch(r) for r in rungs),
              batches=len(rungs))
        mix = traffic_gen.request_list(
            ctx.traffic["requests"], warm_seed, vocab)[:warm["requests"]]
        stage("mix", lambda: [gen.loop(mix, clients)],
              requests=len(mix))
    except BaseException:
        release(state)
        raise
    return state


def window(state, seconds, tracer):
    part = state["ctx"].traffic.get("trace_seconds")
    record = serve_closed.window(
        state, seconds, LeadingPart(tracer, part) if part else tracer)
    record["facts"]["routing"] = routing_facts(record["counters"])
    return record


def routing_facts(counters):
    """What the window's decode steps met, from the counter deltas (a
    program without a counter leaves its ratio out)."""
    def ratio(num, den, scale=1.0):
        num, den = (counters.get("veles_serving_" + n + "_total")
                    for n in (num, den))
        return round(scale * num / den, 4) if num is not None and den \
            else None
    facts = {"steps": counters.get("veles_serving_steps_total"),
             "held_experts_touched_per_layer_step": ratio(
                 "moe_experts_touched", "moe_layer_steps"),
             "held_pairs_pct": ratio("moe_held_pairs", "moe_pairs", 100),
             "hottest_share_of_held_pairs_pct": ratio(
                 "moe_hottest_rows", "moe_held_pairs", 100),
             "live_rows_per_step": ratio("slot_busy_steps", "steps")}
    return {k: v for k, v in facts.items() if v is not None}


def reference_cfg(shapes):
    return {"heads": shapes["heads"], "kv_heads": shapes["kv_heads"],
            "head_dim": shapes["head_dim"],
            "conv_kernel": shapes["conv_kernel"],
            "top_k": shapes["experts_per_token"],
            "held_first": shapes["held"][0],
            "held_count": shapes["held"][1],
            "norm_topk_prob": shapes["norm_topk_prob"],
            "routed_scaling_factor": shapes["routed_scaling_factor"],
            "norm_eps": shapes["norm_eps"],
            "rope_theta": shapes["rope_theta"]}


def batch_logits(shapes, seed, sequences, prompt_lens, pad_to,
                 mode="f32", fault=None):
    """The reference over token sequences, layer by layer: a layer's
    leaves are made once, used for every sequence and dropped.  Each
    sequence is padded at its end to ``pad_to`` (under the causal mask,
    the causal convolution and the forward recurrence the padding
    changes no earlier position).  -> ([logits [pad_to, vocab] of each
    sequence, one alive at a time], near ties counted over the real
    tokens, (token, layer) pairs)."""
    import jax
    import jax.numpy as jnp
    cfg = reference_cfg(shapes)
    items = tuple(sorted(cfg.items()))
    layout = solar_weights.chain_layout(shapes)
    share = solar_weights.chosen_share(shapes)
    table = solar_weights.reference_layer(seed, 0, layout, share)
    xs = []
    for tokens in sequences:
        padded = numpy.zeros((pad_to,), numpy.int32)
        padded[:len(tokens)] = tokens
        xs.append(solar_reference.embed(table, jnp.asarray(padded)))
    del table
    near = pairs = 0
    for i, kind in enumerate(shapes["kinds"], start=1):
        layer = solar_weights.reference_layer(seed, i, layout, share)
        run = solar_reference.layer_program(kind, items, mode, fault)
        for j, (x, tokens, p_len) in enumerate(
                zip(xs, sequences, prompt_lens)):
            xs[j], ties = run(layer, x, jnp.int32(p_len))
            near += int(ties[:len(tokens)].sum())
            pairs += len(tokens)
        del layer
    head = solar_weights.reference_layer(seed, len(layout) - 1, layout,
                                         share)
    logits = jax.jit(lambda p, x: solar_reference.head_logits(
        p, x, cfg, mode))

    def each():
        for x in xs:
            yield logits(head, x)
    return each(), near, pairs


def mean_gaps(shapes, seed, sample, pad_to, most, fault=None,
              with_int8=True):
    """Over all served tokens of the sample: the mean (and widest) gap by
    which a served token's logit lies below the reference's best, the
    same for the tokens the int8 control puts first there (None without
    ``with_int8``), and the near-tie share of the float32 pass."""
    sequences = [(r["prompt"] + r["tokens"])[:-1] for r in sample]
    p_lens = [len(r["prompt"]) for r in sample]
    full, near, pairs = batch_logits(shapes, seed, sequences, p_lens,
                                     pad_to, "f32", fault)
    low = batch_logits(shapes, seed, sequences, p_lens, pad_to,
                       "int8")[0] if with_int8 else None
    program, int8 = [], []
    for r in sample:
        logits = next(full)
        count = len(r["tokens"])
        served = numpy.zeros((most,), numpy.int32)
        served[:count] = r["tokens"]
        ours, theirs = reference.served_gaps(
            logits, next(low) if with_int8 else logits,
            len(r["prompt"]) - 1, served)
        program += numpy.asarray(ours)[:count].tolist()
        int8 += numpy.asarray(theirs)[:count].tolist()
    return {"program": (max(program), sum(program) / len(program)),
            "int8": (max(int8), sum(int8) / len(int8))
            if with_int8 else None,
            "near_tie_share": near / max(pairs, 1)}


def check(ctx, record, control=False):
    limits = ctx.traffic["limits"]
    if record["failed"] or not record["records"]:
        return [{"name": "failed_requests",
                 "value": float(record["failed"] or 1), "limit": 0.0}]
    sample = sample_for_check(record["records"], ctx.seed,
                              ctx.traffic["check_requests"])
    pad_to = pad_length(ctx.traffic, ctx.shapes)
    most = ctx.traffic["requests"]["output"]["max"]
    numbers = mean_gaps(ctx.shapes, ctx.seed, sample, pad_to, most)
    (widest, mean), (low_widest, low_mean) = \
        numbers["program"], numbers["int8"]
    ctx.log("gaps", program={"widest": widest, "mean": mean},
            int8={"widest": low_widest, "mean": low_mean},
            near_tie_share=numbers["near_tie_share"],
            requests=len(sample),
            tokens=sum(len(r["tokens"]) for r in sample))
    limit = limits["served_gap_vs_int8"]

    def judged(value):
        return compare.verdict([{"value": value, "limit": limit}])
    if control:
        in_place = low_mean / max(low_mean, 1e-6)   # its own tokens: 1
        ctx.log("control", what="int8 in the program's place",
                served_gap_vs_int8=in_place, limit=limit,
                correct=judged(in_place))
        few = sample[:FAULT_REQUESTS]
        sound = mean_gaps(ctx.shapes, ctx.seed, few, pad_to, most)
        base = max(sound["int8"][1], 1e-6)
        ctx.log("control", what="program", requests=len(few),
                served_gap_vs_int8=sound["program"][1] / base,
                limit=limit, correct=judged(sound["program"][1] / base))
        for fault in solar_reference.FAULTS:
            got = mean_gaps(ctx.shapes, ctx.seed, few, pad_to, most,
                            fault=fault, with_int8=False)
            value = got["program"][1] / base
            # no number at all (a recurrence that overflowed) is
            # written as null: the line stays JSON
            ctx.log("control", what=fault, requests=len(few),
                    served_gap_vs_int8=value if value == value else None,
                    limit=limit, correct=judged(value))
    mismatch = sum(1 for r in record["records"]
                   if r.get("final") is not None
                   and r["final"] != r["tokens"])
    values = {"served_gap_vs_int8": mean / max(low_mean, 1e-6),
              "stream_vs_final_mismatches": float(mismatch)}
    return [{"name": name, "value": values[name], "limit": limit,
             "tokens": sum(len(r["tokens"]) for r in sample)}
            for name, limit in limits.items()]
