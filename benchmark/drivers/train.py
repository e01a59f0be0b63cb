"""Traffic kind ``train``: the LM trainer's fused span step.

Built the way ``chip_smoke.train()`` builds it (a copy, sizes from the
configuration file): ``StandardWorkflow`` through
``Launcher.initialize()``; then ``loader.run()`` / ``gd.run()`` pairs as
``bench.py:_drain_spans`` drives them, refusing a span that did not come
from the device-resident set.  The weights are the benchmark's own, made
on the device from the seed in one call and handed to the program's
parameter arrays; the units' own host-side filling is set to a constant
so that it costs a memset and no random draws.

A dispatch of the span step is ONE optimizer step here (``train_ratio``
= one batch of the resident rows): the comparison reads the program's
state after each of the first steps, and the program shows it only
between dispatches.  Set-up drives the ONE compiled step with its state
through its first steps (they are also the warm-up), reading after step
1 the gradient Adam received (its first moment over 1 - beta1), after
every step the loss, and after step ``check_steps`` the change of every
leaf; the window then goes on with that same object.
"""

import gc
import statistics
import time

import numpy

from benchmark import compare, program_glue, reference, stats, weights
from veles_tpu.loader.fullbatch import FullBatchLoader

TRAIN = 2
ADAM_B1 = 0.9


class SeededTokenLoader(FullBatchLoader):
    """Uniform token ids from the seed, resident on the device (copied
    from chip_smoke.py; module-level so the workflow pickles)."""

    def __init__(self, workflow, vocab=None, seq=None, n_train=0, seed=0,
                 **kwargs):
        super(SeededTokenLoader, self).__init__(workflow, **kwargs)
        self.vocab, self.seq = int(vocab), int(seq)
        self.n_train, self.seed = int(n_train), int(seed)

    def load_data(self):
        self.class_lengths[:] = [0, 0, self.n_train]
        self.original_data = weights.token_rows(
            self.seed, self.n_train, self.seq, self.vocab)
        self.original_labels = [0] * self.n_train


def build(ctx):
    """``chip_smoke.train()``'s construction, sizes from the files."""
    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.standard import StandardWorkflow
    config, traffic, shapes = ctx.config, ctx.traffic, ctx.shapes
    hyper = config["train"]
    for stream in ("default", "loader", "trainer"):
        prng.get(stream).seed(ctx.seed % (2 ** 31 - 1))
    launcher = Launcher()
    launcher.device = ctx.device
    rows, batch = traffic["rows"], hyper["batch_sequences"]
    wf = StandardWorkflow(
        launcher, name="bench-train",
        loader_factory=SeededTokenLoader,
        loader_config={
            "vocab": shapes["vocab"], "seq": traffic["sequence"],
            "n_train": rows, "seed": ctx.seed, "minibatch_size": batch,
            "train_ratio": batch / rows,      # one step a dispatch
            "normalization_type": "none"},
        layers=program_glue.layer_spec(
            shapes, remat=hyper.get("remat", False)), loss="next_token",
        solver=hyper["solver"], learning_rate=hyper["learning_rate"],
        lr_schedule=hyper["lr_schedule"],
        lr_schedule_params=dict(hyper["lr_schedule_params"]),
        decision_config={"max_epochs": 10 ** 9,
                         "fail_iterations": 10 ** 9},
        snapshotter_config={"enabled": False})
    launcher.initialize()
    return wf


def one_dispatch(state):
    """One ``loader.run()`` / ``gd.run()`` pair; returns the rows fed."""
    import jax
    loader, gd = state["loader"], state["gd"]
    with jax.profiler.TraceAnnotation("bench.loader.run"):
        loader.run()
    if not loader.span_fresh_ or loader.span_class_ != TRAIN:
        raise RuntimeError(
            "the loader did not serve a training span from the "
            "device-resident set: the numbers would mean nothing")
    fed = numpy.array(loader.span_indices_)
    if len(fed) != 1:
        raise RuntimeError("a span of %d steps, not 1" % len(fed))
    with jax.profiler.TraceAnnotation("bench.gd.run"):
        gd.run()
    return fed


def _norm_fns():
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jax.tree.map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
            tree)
    return (jax.jit(norms), jax.jit(
        lambda a, b: norms(jax.tree.map(jnp.subtract, a, b))))


def first_moment_norms(gd, norms):
    """Per leaf ||m|| / (1 - beta1): after ONE step from zero moments that
    is the norm of the gradient as Adam received it."""
    out = []
    for i in sorted(gd.opt_state):
        tree = {name: slots["m"].devmem
                for name, slots in gd.opt_state[i].items()}
        out.append({k: float(v) / (1.0 - ADAM_B1)
                    for k, v in norms(tree).items()})
    return out


def change_norms(wf, seed, layout, diff_norms):
    out = []
    for i, unit in enumerate(wf.forwards):
        now = {name: arr.devmem
               for name, arr in unit.param_arrays().items()}
        out.append({k: float(v) for k, v in diff_norms(
            now, weights.fresh_layer(seed, i, layout)).items()})
    return out


def setup(ctx):
    if ctx.traffic["sequence"] > ctx.shapes["positions"]:
        raise ValueError("sequence longer than the positions table")
    t0 = time.monotonic()
    wf = build(ctx)
    ctx.log("built", seconds=round(time.monotonic() - t0, 3))
    return observe(ctx, wf)


def observe(ctx, wf):
    """Hand the seed's weights over, then drive the first steps."""
    from veles_tpu.telemetry import compile_summary
    traffic = ctx.traffic
    t0 = time.monotonic()
    chain = weights.make_chain(ctx.seed, ctx.shapes)
    program_glue.hand_over_weights(wf.forwards, chain)
    del chain
    ctx.log("weights", seconds=round(time.monotonic() - t0, 3),
            params=weights.count_params(ctx.shapes))
    state = {"wf": wf, "loader": wf.loader, "gd": wf.gd,
             "batch_sequences": ctx.config["train"]["batch_sequences"],
             "tokens_per_step": ctx.config["train"]["batch_sequences"]
             * traffic["sequence"]}
    norms, diff_norms = _norm_fns()
    layout = weights.chain_layout(ctx.shapes)
    n_check = traffic["check_steps"]
    observed = {"fed": [], "losses": []}
    quiet, step = 0, 0
    while step < n_check or quiet < 2:
        seen = compile_summary()["total"]["compiles"]
        t0 = time.monotonic()
        fed = one_dispatch(state)
        loss = float(wf.gd.loss.map_read().mem)
        step += 1
        compiled = compile_summary()["total"]["compiles"] - seen
        quiet = 0 if compiled else quiet + 1
        ctx.log("warm_step", step=step, compiled=compiled, loss=loss,
                seconds=round(time.monotonic() - t0, 3))
        if step <= n_check:
            observed["fed"].append(fed[0].tolist())
            observed["losses"].append(loss)
        if step == 1:
            observed["grad_norms"] = first_moment_norms(wf.gd, norms)
        if step == n_check:
            observed["change_norms"] = change_norms(
                wf, ctx.seed, layout, diff_norms)
        if step > 12:
            raise RuntimeError("the step still compiles after 12 steps")
    state["observed"] = observed
    return state


def window(state, seconds, tracer):
    gd = state["gd"]
    gd.loss.map_read()                       # nothing in flight
    tracer.start()
    dispatches, t0 = [], time.monotonic()
    while time.monotonic() - t0 < seconds:
        t1 = time.monotonic()
        one_dispatch(state)
        dispatches.append(time.monotonic() - t1)
    loss = float(gd.loss.map_read().mem)     # device work is finished
    window_s = time.monotonic() - t0
    tracer.stop()
    steps = len(dispatches)
    tokens = steps * state["tokens_per_step"]
    if not numpy.isfinite(loss):
        raise RuntimeError("the loss is %r after the window" % (loss,))
    return {
        "window_s": window_s, "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": stats.rate(tokens, window_s)},
        "tokens_per_s": stats.rate(tokens, window_s),
        "sequences": steps * state["batch_sequences"],
        "step_ms": [1e3 * d for d in dispatches],
        "facts": {"steps": steps, "last_loss": loss,
                  "step_ms_p50": statistics.median(dispatches) * 1e3},
        "observed": state["observed"],
    }


def release(state):
    """Free the program's device state, so that the reference fits and
    runs beside nothing."""
    wf = state["wf"]
    gd = wf.gd
    doomed = [arr for u in wf.forwards
              for arr in u.param_arrays().values()]
    doomed += [arr for layer in gd.opt_state.values()
               for slots in layer.values() for arr in slots.values()]
    program_glue.free_arrays(doomed)
    gd._span_step_ = gd._train_step_ = None
    state.clear()
    gc.collect()


def check(ctx, record, control=False):
    """The reference follows the first steps on the rows the program was
    fed; each number compared has its limit in the traffic file.  With
    ``control`` the int8 reference and the reference with half the batch
    left out are put in the program's place and judged too."""
    observed, traffic = record["observed"], ctx.traffic
    rows = weights.token_rows(ctx.seed, traffic["rows"],
                              traffic["sequence"], ctx.shapes["vocab"])
    batches = [rows[numpy.asarray(fed)] for fed in observed["fed"]]
    ref = reference.train_steps(ctx.shapes, ctx.seed, batches,
                                ctx.config["train"])
    if control:
        for what, kwargs in (("int8", {"mode": "int8"}),
                             ("half_batch", {"fault": "half_batch"})):
            other = reference.train_steps(
                ctx.shapes, ctx.seed, batches, ctx.config["train"],
                **kwargs)
            judged = judge(other, ref, traffic["limits"])
            ctx.log("control", what=what, compared=judged,
                    correct=compare.verdict(judged))
    return judge(observed, ref, traffic["limits"])


def judge(observed, ref, limits):
    ref_grad = compare.flatten(ref["grad_norms"])
    grad_gap, grad_leaf = compare.worst_norm_gap(
        compare.flatten(observed["grad_norms"]), ref_grad)
    change_gap, change_leaf = compare.worst_norm_gap(
        compare.flatten(observed["change_norms"]),
        compare.flatten(ref["change_norms"]),
        leave_out=compare.dead_leaves(ref_grad))
    values = {
        "loss_gap": compare.worst_relative(observed["losses"],
                                           ref["losses"]),
        "grad_norm_gap": grad_gap, "change_norm_gap": change_gap}
    where = {"grad_norm_gap": grad_leaf, "change_norm_gap": change_leaf}
    return [{"name": name, "value": values[name], "limit": limit,
             "leaf": where.get(name)}
            for name, limit in limits.items()]
