"""Traffic kind ``serve_closed_cmdaplus``: ``serve_closed``'s closed loop
of streaming clients (its load generator, window, counters and release,
imported as they are) over a ``cohere2_moe`` chain, this chip's share of
a stated deployment; the Solar driver's leading traced part and routing
facts and the GigaChat driver's ``itl_shape``, imported.  What differs
is what the model and its long prompts force:

``build``  the chain is ``benchmark/cmdaplus_glue.layer_spec`` (every
    layer told its attention kind and the experts it holds, the head tied
    to the table); the weights are handed over as bfloat16 device leaves,
    one at a time, BEFORE the units initialize; the scheduler gets its
    window by argument and has the prefix cache and speculation off (it
    refuses both for a chain with rings).  A program without the units
    fails at once, before anything is started.
``setup``  the warm-up meets every program the mix runs: a SWEEP of one
    short request for each (staging width, last chunk width) the pool's
    prompts give under the scheduler's chunk rule (:func:`sweep_list`:
    prompts of 2,048-14,336 positions prefill in chunks of 256 and a
    last one of 64, 128 or 256 into a staging row of the power of two
    that holds them; each such pair is a set of chunk programs and one
    block insert), then the traffic file's LADDER (:func:`ladder`: the
    decode program of every occupancy bucket at every depth bucket), and
    nothing more: the window compiles nothing, as
    ``compiles_in_window.serve`` shows.
``window``  ``serve_closed_solar.window`` (the profiler handed the
    traffic file's leading ``trace_seconds``; the ``routing`` facts of
    every run), plus the K/V rows that decode attention read in the
    rings and in the full layer a step, and where the gaps between
    tokens lie (``serve_closed_gigachat.itl_shape``).
``check``  logits over the held vocabulary slice, not tokens, against
    ``benchmark/cmdaplus_reference.py`` run layer by layer over each
    sampled request's prompt and served tokens (the hidden states of the
    sequences kept on the host between layers; the last layer computed
    at the positions the head reads alone), given the same held experts,
    in float32 and in the int8 control: ``served_gap_vs_int8`` as in
    ``serve_closed``.  With ``control`` every planted fault of the
    reference (``cmdaplus_reference.FAULTS``) and the control in the
    program's place are judged too, each an earlier line.
"""

import concurrent.futures
import functools
import time

import numpy

from benchmark import cmdaplus_glue, cmdaplus_reference, \
    cmdaplus_weights, compare, reference, traffic as traffic_gen
from benchmark.drivers import serve_closed_solar
from benchmark.drivers.serve_closed import (  # noqa: F401  (the driver)
    REQUEST_TIMEOUT_S, WARM_SEED_OFFSET, LoadGen, counters, failures,
    release, sample_for_check)
from benchmark.drivers.serve_closed_gigachat import itl_shape

#: requests of the sample that each planted fault is judged over
FAULT_REQUESTS = 8
#: the scheduler's chunk rule as the mix meets it: chunks of ``WIDEST``
#: and a last one of the power of two from ``NARROWEST`` that holds
#: what is left, into a staging row of the power of two (at least
#: ``WIDEST``) that holds the prompt; a prompt of ``NARROWEST`` or fewer
#: prefills at once at the power of two from ``ONCE`` that holds it
NARROWEST, WIDEST, ONCE = 64, 256, 16
#: the planted faults that change a sliding layer alone, and the full
#: layer alone: a layer of the other kind runs the sound program,
#: compiled once for the sound pass, in such a fault's pass
SLIDING_FAULTS = ("no_window", "window_half", "window_off_by_one",
                  "rope_not_interleaved", "ring_not_carried")
FULL_FAULTS = ("rope_on_full_layer",)
#: the share of a sequence's rows the reference computes a held expert
#: on, gathered, where no more chose it (twice what even routing gives
#: a held expert: 8 of 128 experts a token); any expert that more rows
#: chose is computed on every row
EXPERT_ROWS = 0.125


def _pow2(n, floor):
    b = floor
    while b < n:
        b *= 2
    return b


def chunk_programs(prompt_len):
    """(staging width, last chunk width) of a prompt's chunked prefill:
    what its chunk programs and its block insert are compiled for; a
    prompt of ``NARROWEST`` positions or fewer, which prefills at once,
    (the width of its one program, 0)."""
    if prompt_len <= NARROWEST:
        return _pow2(prompt_len, ONCE), 0
    left = prompt_len % WIDEST or WIDEST
    return _pow2(prompt_len, WIDEST), _pow2(left, NARROWEST)


def shortest_prompt(programs):
    """The shortest prompt that meets the chunk programs and the block
    insert of (staging width, last chunk width) ``programs``: its chunks
    of ``WIDEST`` pass every key width the longer prompts of that pair
    pass, and its last chunk is theirs."""
    width, _ = programs
    low = width // 2 + 1 if width > WIDEST else 1
    return next(n for n in range(low, width + 1)
                if chunk_programs(n) == programs)


def sweep_list(requests, vocab, seed, steps):
    """One request for each (staging width, last chunk width) that the
    pool's prompts give (:func:`chunk_programs`), of the shortest prompt
    with that pair (:func:`shortest_prompt`)."""
    rng = numpy.random.default_rng(int(seed))
    pairs = sorted({chunk_programs(prompt_len) for prompt_len, _ in
                    traffic_gen.size_pool(requests)})
    return [(rng.integers(0, vocab, shortest_prompt(k)).tolist(), steps)
            for k in pairs]


def ladder_list(ladder, vocab, seed):
    """[(anchor, riders)] a rung: one request of each anchor's ``prompt``
    and ``steps``, and the ``riders``' ``count`` short requests, each
    one step longer than the one before."""
    rng = numpy.random.default_rng(int(seed))
    riders = ladder["riders"]
    return [((rng.integers(0, vocab, a["prompt"]).tolist(), a["steps"]),
             [(rng.integers(0, vocab, riders["prompt"]).tolist(),
               riders["steps"] + i) for i in range(riders["count"])])
            for a in ladder["anchors"]]


def ladder(gen, riders_gen, rungs, port):
    """The decode program of every occupancy bucket at the depth bucket
    of each rung's anchor: the anchor is sent alone, and once the server
    has counted a decode step (the anchor's) the riders, each one chunk
    of prefill, join its decoding one a pass (1, 2, ..., 16 rows) and,
    each one step longer than the one before, leave it one at a time
    before the anchor does (16, ..., 2, 1); the anchor is the deepest row
    throughout, so every step runs at its depth bucket.  -> the replies
    of every batch."""
    def steps():
        return counters(port)[0].get("veles_serving_steps_total", 0.0)
    replies = []
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        for anchor, riders in rungs:
            before = steps()
            alone = pool.submit(gen.batch, [anchor])
            while steps() == before and not alone.done():
                time.sleep(0.01)
            replies += [riders_gen.batch(riders), alone.result()]
    return replies


def build(ctx):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models import standard
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    shapes, serve = ctx.shapes, ctx.config["serve"]
    missing = sorted(set(cmdaplus_glue.TYPES) - set(standard.LAYER_TYPES))
    if missing:        # before anything is started: a program without
        raise SystemExit(      # the units fails at once
            "benchmark: this program has no %s unit" % missing)
    wf = AcceleratedWorkflow(None, name="bench-serve")
    loader = RestfulLoader(wf, sample_shape=(shapes["positions"],),
                           minibatch_size=1, max_wait=1.0)
    loader.initialize(device=ctx.device)
    forwards = standard.make_forwards(wf, loader.minibatch_data,
                                      cmdaplus_glue.layer_spec(shapes))
    handed = cmdaplus_glue.hand_over_weights(forwards, ctx.seed, shapes)
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
    """``serve_closed.setup`` over this file's ``build``, sweep and
    ladder, both from seed + 1000003."""
    from veles_tpu.telemetry import compile_summary
    t0 = time.monotonic()
    wf, loader, forwards, api = build(ctx)
    ctx.log("built", seconds=round(time.monotonic() - t0, 3),
            params=cmdaplus_weights.count_params(ctx.shapes))
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
        sweep = sweep_list(ctx.traffic["requests"], vocab, warm_seed,
                           warm["sweep_steps"])
        stage("sweep", lambda: [gen.loop(sweep, clients)],
              requests=len(sweep))
        rungs = ladder_list(warm["ladder"], vocab, warm_seed)
        riders_gen = LoadGen(api.port)
        try:
            stage("ladder", lambda: ladder(gen, riders_gen, rungs,
                                           api.port), rungs=len(rungs))
        finally:
            riders_gen.close()
    except BaseException:
        release(state)
        raise
    return state


def window(state, seconds, tracer):
    record = serve_closed_solar.window(state, seconds, tracer)
    counters = record["counters"]
    steps = counters.get("veles_serving_steps_total")
    for name in ("window_kv_rows", "full_kv_rows"):
        rows = counters.get("veles_serving_%s_total" % name)
        if rows is not None and steps:
            record["facts"]["routing"][name + "_per_step"] = round(
                rows / steps, 2)
    record["facts"]["itl_shape"] = itl_shape(
        record["records"], record["t0"], seconds, counters)
    return record


def fault_of_layer(kind, fault):
    """``fault`` where it changes a layer of that kind, else None."""
    other = FULL_FAULTS if kind == "sliding" else SLIDING_FAULTS
    return None if fault in other else fault


def reference_cfg(shapes):
    return {"heads": shapes["heads"], "kv_heads": shapes["kv_heads"],
            "head_dim": shapes["head_dim"],
            "window": shapes["sliding_window"],
            "rope_theta": shapes["rope_theta"],
            "top_k": shapes["experts_per_token"],
            "held_first": shapes["held"][0],
            "held_count": shapes["held"][1], "n_shared": shapes["shared"],
            "norm_eps": shapes["norm_eps"],
            "logit_scale": shapes["logit_scale"], "query_block": 128,
            "expert_rows": EXPERT_ROWS, "prefill_chunk": WIDEST}


def pad_for(prompt_len, length, most, positions):
    """The padded length a sequence runs at: the first of the quarters
    of the slot window (one set of the reference's programs each) that
    holds it and the ``most`` positions from its prompt's last."""
    need = max(length, prompt_len - 1 + most)
    for pad in range(positions // 4, positions + 1, positions // 4):
        if need <= pad:
            return pad
    raise ValueError("a sequence of %d positions is longer than %d"
                     % (need, positions))


@functools.lru_cache(maxsize=None)
def head_program(cfg_items, mode, fault):
    """The tied head: (table, final norm, x) -> logits."""
    import jax
    cfg = dict(cfg_items)

    def head(table, final, x):
        return cmdaplus_reference.head_logits(table, final, x, cfg, mode,
                                              fault)
    return jax.jit(head)


def batch_logits(shapes, seed, sequences, prompt_lens, most, modes,
                 fault=None):
    """The reference over token sequences, layer by layer: a layer's
    leaves are made once, used for every sequence and every mode, and
    dropped; the hidden states wait on the host between layers, and the
    last layer's are those of the ``most`` positions from each prompt's
    last on, all that the head reads.  Each sequence is padded at its
    end (:func:`pad_for`; under the causal mask the padding changes no
    earlier position).  -> ({mode: [logits [most, vocab] from each
    prompt's last position on, one alive at a time]}, near ties counted
    over the real tokens, (token, layer) pairs)."""
    import jax.numpy as jnp
    cfg = reference_cfg(shapes)
    items = tuple(sorted(cfg.items()))
    layout = cmdaplus_weights.chain_layout(shapes)
    table = cmdaplus_weights.reference_layer(seed, 0, layout)["weights"]
    pads = [pad_for(p, len(t), most, shapes["positions"])
            for t, p in zip(sequences, prompt_lens)]
    first = []
    for tokens, pad in zip(sequences, pads):
        padded = numpy.zeros((pad,), numpy.int32)
        padded[:len(tokens)] = tokens
        first.append(numpy.asarray(cmdaplus_reference.embed(
            {"weights": table}, jnp.asarray(padded))))
    xs = {mode: list(first) for mode in modes}
    del first
    near = pairs = 0
    kinds = shapes["kinds"]
    for i, kind in enumerate(kinds, start=1):
        layer = cmdaplus_weights.reference_layer(seed, i, layout)
        last = most if i == len(kinds) else None
        for mode in modes:
            run = cmdaplus_reference.layer_program(
                kind, items, mode, fault_of_layer(kind, fault), last)
            for j, (tokens, p_len) in enumerate(
                    zip(sequences, prompt_lens)):
                y, ties = run(layer, jnp.asarray(xs[mode][j]),
                              jnp.int32(p_len))
                xs[mode][j] = numpy.asarray(y)
                if mode == modes[0]:
                    real = len(tokens) - (p_len - 1 if last else 0)
                    near += int(ties[:real].sum())
                    pairs += real
        del layer
    final = cmdaplus_weights.reference_layer(
        seed, len(layout) - 1, layout)["final_norm"]

    def each(mode):
        head = head_program(items, mode, fault)
        for x in xs[mode]:
            yield head(table, final, jnp.asarray(x))
    return {mode: each(mode) for mode in modes}, near, pairs


def mean_gaps(shapes, seed, sample, most, fault=None, with_int8=True):
    """Over all served tokens of the sample: the mean (and widest) gap by
    which a served token's logit lies below the reference's best, the
    same for the tokens the int8 control puts first there (None without
    ``with_int8``), and the near-tie share of the float32 pass."""
    sequences = [(r["prompt"] + r["tokens"])[:-1] for r in sample]
    p_lens = [len(r["prompt"]) for r in sample]
    modes = ("f32", "int8") if with_int8 else ("f32",)
    logits, near, pairs = batch_logits(shapes, seed, sequences, p_lens,
                                       most, modes, fault)
    program, int8 = [], []
    for r in sample:
        full = next(logits["f32"])
        count = len(r["tokens"])
        served = numpy.zeros((most,), numpy.int32)
        served[:count] = r["tokens"]
        ours, theirs = reference.served_gaps(
            full, next(logits["int8"]) if with_int8 else full, 0, served)
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
    most = ctx.traffic["requests"]["output"]["max"]
    numbers = mean_gaps(ctx.shapes, ctx.seed, sample, most)
    (widest, mean), (low_widest, low_mean) = \
        numbers["program"], numbers["int8"]
    ctx.log("gaps", program={"widest": widest, "mean": mean},
            int8={"widest": low_widest, "mean": low_mean},
            near_tie_share=numbers["near_tie_share"],
            requests=len(sample),
            tokens=sum(len(r["tokens"]) for r in sample),
            longest=max(r["prompt_len"] + len(r["tokens"])
                        for r in sample))
    limit = limits["served_gap_vs_int8"]

    def judged(value):
        return compare.verdict([{"value": value, "limit": limit}])
    if control:
        in_place = low_mean / max(low_mean, 1e-6)   # its own tokens: 1
        ctx.log("control", what="int8 in the program's place",
                served_gap_vs_int8=in_place, limit=limit,
                correct=judged(in_place))
        few = sample[:FAULT_REQUESTS]
        sound = mean_gaps(ctx.shapes, ctx.seed, few, most)
        base = max(sound["int8"][1], 1e-6)
        ctx.log("control", what="program", requests=len(few),
                served_gap_vs_int8=sound["program"][1] / base,
                limit=limit, correct=judged(sound["program"][1] / base))
        for fault in cmdaplus_reference.FAULTS:
            got = mean_gaps(ctx.shapes, ctx.seed, few, most, fault=fault,
                            with_int8=False)
            value = got["program"][1] / base
            # no number at all is written as null: the line stays JSON
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
