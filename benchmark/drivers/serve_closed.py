"""Traffic kind ``serve_closed``: the REST generation server under a
closed loop of streaming clients.

The server is built the way ``chip_smoke.serve()`` builds it (a copy,
sizes from the configuration file): an LM forward chain behind
``RESTfulAPI`` with the scheduler on, paged pools, the prefix cache at
its default, speculation as the configuration file's ``serve.spec`` says,
the warm-up ladder of the program off.  The weights are the benchmark's
own, made on the device from the seed.  The clients are
``benchmark/loadgen.py`` in a child process that never imports JAX, over
real HTTP.

Warm-up, counted as set-up, all from seed + 1000003: a sweep (one short
request for every number of KV blocks a prompt of the pool fills: the
insert and prefill-chunk programs), the traffic file's ladder (the
decode program of every occupancy and depth bucket), then a fixed number
of requests of the mix itself.

The window closes ``seconds`` after the load generator starts it: at
that moment the server's counters are read and the profiler is stopped,
so every per-layer metric is of the window alone; the requests then in
flight are followed to their end for the tails.
"""

import gc
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy

from benchmark import compare, program_glue, reference, stats, \
    traffic as traffic_gen, weights

WARM_SEED_OFFSET = 1000003
REQUEST_TIMEOUT_S = 600.0
HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build(ctx):
    """``chip_smoke.serve()``'s construction over a fresh chain."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    shapes, serve = ctx.shapes, ctx.config["serve"]
    wf = AcceleratedWorkflow(None, name="bench-serve")
    loader = RestfulLoader(wf, sample_shape=(shapes["positions"],),
                           minibatch_size=1, max_wait=1.0)
    loader.initialize(device=ctx.device)
    forwards = make_forwards(wf, loader.minibatch_data,
                             program_glue.layer_spec(shapes))
    for unit in forwards:
        unit.initialize(device=ctx.device)
    chain = weights.make_chain(ctx.seed, shapes)
    program_glue.hand_over_weights(forwards, chain)
    del chain
    api = RESTfulAPI(
        wf, loader=loader, port=0, host="127.0.0.1", serving=True,
        max_slots=serve["max_slots"], max_queue=serve["max_queue"],
        serving_block_size=serve["block_size"],
        serving_spec=serve.get("spec"), forwards=forwards,
        serving_warm_buckets=False, request_timeout=REQUEST_TIMEOUT_S)
    api.output = forwards[-1].output
    api.initialize()
    return wf, loader, forwards, api


class LoadGen:
    """The child process and its line protocol."""

    def __init__(self, port):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "benchmark", "loadgen.py"),
             "127.0.0.1", str(port)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items()
                 if k not in ("BENCH_RUN",)})
        self._read()

    def _read(self):
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the load generator died (exit %s)"
                               % self.proc.poll())
        return json.loads(line)

    def _write(self, **cmd):
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def batch(self, requests):
        self._write(op="batch", requests=requests)
        return self._read()

    def loop(self, requests, clients, seconds=None, at_close=None):
        """The closed loop; ``at_close`` is called the moment the window
        of ``seconds`` closes, while the requests in flight go on."""
        self._write(op="loop", requests=requests, clients=clients,
                    seconds=seconds)
        t0 = self._read()["t0"]
        if at_close is not None:
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            at_close()
        return self._read()

    def close(self):
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write(json.dumps({"op": "quit"}) + "\n")
                self.proc.stdin.flush()
                self.proc.wait(10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def counters(port):
    """Every ``veles_serving_*`` counter of GET /metrics, summed over its
    label sets, and the JSON snapshot of GET /serving/metrics."""
    base = "http://127.0.0.1:%d" % port
    text = urllib.request.urlopen(base + "/metrics", timeout=60) \
        .read().decode()
    out = {}
    for line in text.splitlines():
        if not line.startswith("veles_serving_") or "_total" not in line:
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{")[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            pass
    snap = json.loads(urllib.request.urlopen(
        base + "/serving/metrics", timeout=60).read())
    return out, snap


def failures(records):
    return [r["error"] for r in records if r["error"]]


def setup(ctx):
    from veles_tpu.telemetry import compile_summary
    t0 = time.monotonic()
    wf, loader, forwards, api = build(ctx)
    ctx.log("built", seconds=round(time.monotonic() - t0, 3),
            params=weights.count_params(ctx.shapes))
    state = {"wf": wf, "loader": loader, "forwards": forwards,
             "api": api, "ctx": ctx}
    try:
        gen = state["gen"] = LoadGen(api.port)
        warm, vocab = ctx.traffic["warmup"], ctx.shapes["vocab"]
        warm_seed = ctx.seed + WARM_SEED_OFFSET
        seen = compile_summary()["total"]["compiles"]
        t0 = time.monotonic()
        sweep = traffic_gen.sweep_list(ctx.traffic["requests"], vocab,
                                       warm_seed, warm["sweep_steps"])
        bad = failures(gen.loop(sweep, ctx.traffic["clients"])["records"])
        if bad:
            raise RuntimeError("warm-up sweep: %s" % bad[:3])
        ctx.log("warm_sweep", requests=len(sweep),
                seconds=round(time.monotonic() - t0, 3),
                compiled=compile_summary()["total"]["compiles"] - seen)
        seen = compile_summary()["total"]["compiles"]
        t0 = time.monotonic()
        rungs = traffic_gen.ladder_list(warm["ladder"], vocab, warm_seed)
        for rung in rungs:
            bad = failures(gen.batch(rung)["records"])
            if bad:
                raise RuntimeError("warm-up ladder: %s" % bad[:3])
        ctx.log("warm_ladder", batches=len(rungs),
                seconds=round(time.monotonic() - t0, 3),
                compiled=compile_summary()["total"]["compiles"] - seen)
        seen = compile_summary()["total"]["compiles"]
        t0 = time.monotonic()
        mix = traffic_gen.request_list(
            ctx.traffic["requests"], warm_seed, vocab)[:warm["requests"]]
        bad = failures(gen.loop(mix, ctx.traffic["clients"])["records"])
        if bad:
            raise RuntimeError("warm-up mix: %s" % bad[:3])
        ctx.log("warm_mix", requests=len(mix),
                seconds=round(time.monotonic() - t0, 3),
                compiled=compile_summary()["total"]["compiles"] - seen)
    except BaseException:
        release(state)
        raise
    return state


def window(state, seconds, tracer):
    ctx, gen, api = state["ctx"], state["gen"], state["api"]
    requests = traffic_gen.request_list(
        ctx.traffic["requests"], ctx.seed, ctx.shapes["vocab"])
    closed = {}

    def at_close():
        closed["counters"], closed["snap"] = counters(api.port)
        tracer.stop()
    before, _ = counters(api.port)
    tracer.start()
    reply = gen.loop(requests, ctx.traffic["clients"], seconds, at_close)
    after, snap = closed["counters"], closed["snap"]
    if reply["used_up"]:
        raise RuntimeError("the window used up the pool of %d requests: "
                           "make the pool larger" % len(requests))
    records, t0 = reply["records"], reply["t0"]
    ok = [r for r in records if not r["error"]]
    in_time = [r for r in ok if r["done"] - t0 <= seconds]
    streamed = sum(1 for r in ok for t in r["arrivals"]
                   if t - t0 <= seconds)
    ttft = [1e3 * (r["arrivals"][0] - r["sent"]) for r in ok]
    gaps = [1e3 * (b - a) for r in ok
            for a, b in zip(r["arrivals"], r["arrivals"][1:])]
    out_tokens = sum(len(r["tokens"]) for r in in_time)
    passes, depth = forward_passes(ok, t0 + seconds)
    for r, (prompt, _) in zip(records, requests):
        r["prompt"] = prompt          # records come back in list order
    if [r["index"] for r in records] != list(range(len(records))):
        raise RuntimeError("records out of order")
    return {
        "window_s": seconds, "t0": t0, "attempted": len(records),
        "failed": len(records) - len(ok),
        "end_to_end": {
            "serve_tokens_per_s": stats.rate(streamed, seconds),
            "ttft_p95_ms": stats.percentile(ttft, 95) if ttft else None,
            "itl_p95_ms": stats.percentile(gaps, 95) if gaps else None},
        "processed_tokens_per_s": stats.rate(passes, seconds),
        "mean_context": depth / max(passes, 1),
        "counters": {k: after[k] - before.get(k, 0.0) for k in after},
        "records": ok,
        "facts": {
            "completed_in_window": len(in_time), "gaps": len(gaps),
            "tokens_of_completed_per_s": stats.rate(out_tokens, seconds),
            "ttft_p50_ms": stats.percentile(ttft, 50) if ttft else None,
            "itl_p50_ms": stats.percentile(gaps, 50) if gaps else None,
            "client_think_max_ms": 1e3 * reply["think_max_s"],
            "errors": failures(records)[:3],
            "served_by": {k: snap.get(k) for k in (
                "kv_mode", "kv_dtype", "spec", "prefix_cache",
                "max_slots")}},
    }


def forward_passes(records, close):
    """(token positions the model step passed inside the window, the sum
    of their contexts), from the clients' side: a prompt counts once its
    first token has arrived by ``close`` (a prompt still being prefilled
    then counts nothing), and every later token that arrived by then is
    one decode pass with the whole text before it as context."""
    passes, contexts = 0, 0.0
    for r in records:
        if not r["arrivals"] or r["arrivals"][0] > close:
            continue
        n, later = r["prompt_len"], sum(
            1 for t in r["arrivals"][1:] if t <= close)
        passes += n + later
        contexts += n * n / 2.0 + later * n + later * (later + 1) / 2.0
    return passes, contexts


def release(state):
    """Stop the clients and the server and free the device state."""
    gen = state.pop("gen", None)
    if gen is not None:
        gen.close()
    api = state.pop("api", None)
    if api is not None:
        api.stop()
    loader = state.pop("loader", None)
    if loader is not None:
        loader.close()
    program_glue.free_arrays(
        arr for unit in state.pop("forwards", [])
        for arr in unit.param_arrays().values())
    state.clear()
    gc.collect()


def sample_for_check(records, seed, count):
    """``count`` finished requests drawn from the seed, the longest one
    among them."""
    rng = numpy.random.default_rng(int(seed) + 17)
    longest = max(range(len(records)), key=lambda i: (
        records[i]["prompt_len"] + len(records[i]["tokens"])))
    rest = [i for i in range(len(records)) if i != longest]
    picked = rng.choice(rest, size=min(count - 1, len(rest)),
                        replace=False).tolist() if rest else []
    return [records[i] for i in [longest] + sorted(picked)]


def gap_numbers(shapes, seed, sample, pad_to, most):
    """{"program": (widest, mean), "int8": (widest, mean)} over all served
    tokens of the sample: the gap by which a served token's logit lies
    below the reference's best, the reference run once over each prompt
    with its served tokens; and the same gap of the tokens that the int8
    arithmetic puts first at the same positions (the control; it need not
    decode).  ``most``: the longest output there can be."""
    sequences = [(r["prompt"] + r["tokens"])[:-1] for r in sample]
    program, int8 = [], []
    for r, full, low in zip(
            sample,
            reference.batch_logits(shapes, seed, sequences, pad_to),
            reference.batch_logits(shapes, seed, sequences, pad_to,
                                   "int8")):
        count = len(r["tokens"])
        served = numpy.zeros((most,), numpy.int32)
        served[:count] = r["tokens"]
        ours, theirs = reference.served_gaps(
            full, low, len(r["prompt"]) - 1, served)
        program += numpy.asarray(ours)[:count].tolist()
        int8 += numpy.asarray(theirs)[:count].tolist()
    return {"program": (max(program), sum(program) / len(program)),
            "int8": (max(int8), sum(int8) / len(int8))}


def pad_length(traffic, shapes):
    longest = traffic["requests"]["prompt"]["max"] \
        + traffic["requests"]["output"]["max"]
    return min(-(-longest // 128) * 128, shapes["positions"])


def check(ctx, record, control=False):
    """Over a sample of the finished requests, drawn from the seed with
    the longest in it: the mean gap by which a served token's logit lies
    below the float32 reference's best, as a share of the same mean for
    the tokens the int8 control puts first at the same positions.  The
    control in the program's place reads 1 by construction; the bf16
    program read 0.084 to 0.187 over 18 samples on the chip (PERF.md,
    where the limit's readings are).  The absolute gaps swing with the
    near-ties a sample happens to hold, the same for both, so their ratio
    is what separates the two precisions.  The control is read in every
    run; with ``control`` the number is also judged over further disjoint
    samples, and so is the control in the program's place."""
    limits = ctx.traffic["limits"]
    if record["failed"] or not record["records"]:
        return [{"name": "failed_requests",
                 "value": float(record["failed"] or 1), "limit": 0.0}]
    sample = sample_for_check(record["records"], ctx.seed,
                              ctx.traffic["check_requests"])
    pad_to = pad_length(ctx.traffic, ctx.shapes)
    most = ctx.traffic["requests"]["output"]["max"]
    numbers = gap_numbers(ctx.shapes, ctx.seed, sample, pad_to, most)
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
        # calibration: the same number over further, disjoint samples
        in_place = low_mean / max(low_mean, 1e-6)   # its own tokens: 1
        ctx.log("control", what="int8 in the program's place",
                served_gap_vs_int8=in_place, limit=limit,
                correct=judged(in_place))
        order = numpy.random.default_rng(ctx.seed + 99).permutation(
            len(record["records"]))
        size = ctx.traffic["check_requests"]
        for j in range(len(order) // size):
            other = [record["records"][i]
                     for i in order[j * size:(j + 1) * size]]
            numbers = gap_numbers(ctx.shapes, ctx.seed, other, pad_to,
                                  most)
            m, low = numbers["program"][1], numbers["int8"][1]
            ctx.log("control", what="program", sample=j, program_mean=m,
                    int8_mean=low, served_gap_vs_int8=m / max(low, 1e-6),
                    limit=limit, correct=judged(m / max(low, 1e-6)))
    mismatch = sum(1 for r in record["records"]
                   if r.get("final") is not None
                   and r["final"] != r["tokens"])
    values = {"served_gap_vs_int8": mean / max(low_mean, 1e-6),
              "stream_vs_final_mismatches": float(mismatch)}
    return [{"name": name, "value": values[name], "limit": limit,
             "tokens": sum(len(r["tokens"]) for r in sample)}
            for name, limit in limits.items()]
