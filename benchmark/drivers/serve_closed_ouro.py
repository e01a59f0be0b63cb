"""Traffic kind ``serve_closed_ouro``: ``serve_closed``'s closed loop of
streaming clients (its load generator, window, counters and release,
imported as they are) over a looped ``ouro`` chain.  What differs is
what the model forces:

``build``  the chain is ``benchmark/ouro_glue.layer_spec`` (embedding,
    the looped stack as ONE unit, a plain head) and its weights are
    handed over as bfloat16 device leaves, one at a time, BEFORE the
    units initialize, so no float32 copy of the model is ever made; the
    scheduler gets its window by argument (rotary positions have no
    table) and has the prefix cache and speculation off (it refuses
    both for a stack of cache layers behind one block table).
``window``  ``serve_closed``'s, with the profiler stopped after the
    traffic file's ``trace_seconds``: a decode step is 192 layer
    applications and the profiler keeps a fixed number of device events,
    so it is handed a leading part of the window that fits.  Counters
    and end-to-end numbers are of the whole window.
``check``  logits, not tokens, against ``benchmark/ouro_reference.py``
    run over each sampled request's prompt and served tokens, in float32
    and in the int8 control, from bfloat16 leaves made anew from the
    seed: ``served_gap_vs_int8`` as in ``serve_closed`` (the mean gap by
    which a served token's logit lies below the reference's best, over
    the same mean for the tokens the control puts first).  With
    ``control`` every planted fault of the reference
    (``ouro_reference.FAULTS``) and the control in the program's place
    are judged too, each an earlier line.
"""

import threading
import time

import numpy

from benchmark import compare, ouro_glue, ouro_reference, ouro_weights, \
    reference, traffic as traffic_gen
from benchmark.drivers import serve_closed
from benchmark.drivers.serve_closed import (  # noqa: F401  (the driver)
    REQUEST_TIMEOUT_S, WARM_SEED_OFFSET, LoadGen, counters, failures,
    pad_length, release, sample_for_check)

#: requests of the sample that each planted fault is judged over
FAULT_REQUESTS = 8


def build(ctx):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models import standard
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    shapes, serve = ctx.shapes, ctx.config["serve"]
    spec = ouro_glue.layer_spec(shapes)
    missing = [s["type"] for s in spec
               if s["type"] not in standard.LAYER_TYPES]
    if missing:        # before anything is started: a program without
        raise SystemExit(      # the looped stack fails at once
            "benchmark: this program has no %s unit" % missing)
    wf = AcceleratedWorkflow(None, name="bench-serve")
    loader = RestfulLoader(wf, sample_shape=(shapes["positions"],),
                           minibatch_size=1, max_wait=1.0)
    loader.initialize(device=ctx.device)
    forwards = standard.make_forwards(wf, loader.minibatch_data, spec)
    handed = ouro_glue.hand_over_weights(forwards, ctx.seed, shapes)
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
            params=ouro_weights.count_params(ctx.shapes))
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


class LeadingPart:
    """The harness's tracer, stopped ``seconds`` after it started (or
    when the window closes, if that comes first)."""

    def __init__(self, tracer, seconds):
        self.tracer, self.seconds, self.timer = tracer, seconds, None

    def start(self):
        self.tracer.start()
        if self.tracer.started is not None and self.timer is None:
            self.timer = threading.Timer(self.seconds, self.tracer.stop)
            self.timer.daemon = True
            self.timer.start()

    def stop(self):
        if self.timer is not None:
            self.timer.cancel()
            self.timer.join()
        self.tracer.stop()


def window(state, seconds, tracer):
    part = state["ctx"].traffic.get("trace_seconds")
    return serve_closed.window(
        state, seconds, LeadingPart(tracer, part) if part else tracer)


def reference_logits(chain, shapes, sequences, prompt_lens, pad_to,
                     mode="f32", fault=None):
    """The reference over token sequences, one at a time: each padded at
    its end to ``pad_to`` (under the causal mask the padding changes no
    earlier position).  Yields the logits [pad_to, vocab] of one
    sequence after the other."""
    cfg = {"heads": shapes["heads"], "passes": shapes["passes"],
           "rope_theta": shapes["rope_theta"],
           "norm_eps": shapes["norm_eps"]}
    for tokens, p_len in zip(sequences, prompt_lens):
        padded = numpy.zeros((pad_to,), numpy.int32)
        padded[:len(tokens)] = tokens
        yield ouro_reference.forward_logits(
            chain, padded, cfg, mode, fault, p_len)[0]


def mean_gaps(chain, shapes, sample, pad_to, most, fault=None,
              with_int8=True):
    """Over all served tokens of the sample: the mean (and widest) gap by
    which a served token's logit lies below the reference's best, and
    the same for the tokens the int8 control puts first there (None
    without ``with_int8``)."""
    sequences = [(r["prompt"] + r["tokens"])[:-1] for r in sample]
    p_lens = [len(r["prompt"]) for r in sample]
    full = reference_logits(chain, shapes, sequences, p_lens, pad_to,
                            "f32", fault)
    low = reference_logits(chain, shapes, sequences, p_lens, pad_to,
                           "int8") if with_int8 else None
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
            if with_int8 else None}


def check(ctx, record, control=False):
    limits = ctx.traffic["limits"]
    if record["failed"] or not record["records"]:
        return [{"name": "failed_requests",
                 "value": float(record["failed"] or 1), "limit": 0.0}]
    sample = sample_for_check(record["records"], ctx.seed,
                              ctx.traffic["check_requests"])
    pad_to = pad_length(ctx.traffic, ctx.shapes)
    most = ctx.traffic["requests"]["output"]["max"]
    stats = ctx.jax_device.memory_stats() or {}
    ctx.log("freed", bytes_in_use=stats.get("bytes_in_use"))
    chain = ouro_weights.reference_chain(ctx.seed, ctx.shapes)
    numbers = mean_gaps(chain, ctx.shapes, sample, pad_to, most)
    (widest, mean), (low_widest, low_mean) = \
        numbers["program"], numbers["int8"]
    ctx.log("gaps", program={"widest": widest, "mean": mean},
            int8={"widest": low_widest, "mean": low_mean},
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
        sound = mean_gaps(chain, ctx.shapes, few, pad_to, most)
        base = max(sound["int8"][1], 1e-6)
        ctx.log("control", what="program", requests=len(few),
                served_gap_vs_int8=sound["program"][1] / base,
                limit=limit, correct=judged(sound["program"][1] / base))
        for fault in ouro_reference.FAULTS:
            got = mean_gaps(chain, ctx.shapes, few, pad_to, most,
                            fault=fault, with_int8=False)
            ctx.log("control", what=fault, requests=len(few),
                    program_mean=got["program"][1],
                    served_gap_vs_int8=got["program"][1] / base,
                    limit=limit,
                    correct=judged(got["program"][1] / base))
    del chain
    mismatch = sum(1 for r in record["records"]
                   if r.get("final") is not None
                   and r["final"] != r["tokens"])
    values = {"served_gap_vs_int8": mean / max(low_mean, 1e-6),
              "stream_vs_final_mismatches": float(mismatch)}
    return [{"name": name, "value": values[name], "limit": limit,
             "tokens": sum(len(r["tokens"]) for r in sample)}
            for name, limit in limits.items()]
