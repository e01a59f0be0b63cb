#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that veles_tpu still starts on the chip.

Drives the main path once, in ONE process, on one TPU chip, at the full
width of the model the code names for a chip (embedding 32768×1024 → 8 ×
transformer_block(8 heads × 128, causal) → token_logits(32768), window
1024, bf16 compute), weights random from ``--seed``:

- ``kernels`` — every main-path Pallas kernel against its jnp reference,
  with the Mosaic custom call found in the compiled text;
- ``train``  — the ``samples/lm.py`` stack (StandardWorkflow,
  next-token loss, adam, fused GradientDescent) through ``Launcher`` the
  way ``python -m veles_tpu`` runs it, on seeded uniform tokens;
- ``serve``  — the chain just trained behind ``RESTfulAPI`` (scheduler
  on, paged pools, spec + prefix cache at their defaults), POST
  /generate over real HTTP, streamed and not.

``--chips 4`` runs ONLY the one-chip train and the same seed and global
batch under a ``{'dp': 4}`` mesh, and compares the losses.

Each phase prints one JSON line (seconds, compiles and compile seconds
from telemetry/compile_tracker, what it checked, peak device bytes);
the LAST line is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it.  Any failed check raises — the exit code is then
non-zero and no result line is printed.  Without a TPU it exits 1 at
once: nothing here ever selects the CPU, interpret mode or a jnp
stand-in for a kernel.
"""

import argparse
import concurrent.futures
import faulthandler
import functools
import json
import logging
import math
import sys
import time
import urllib.request

import numpy

from veles_tpu.loader.fullbatch import FullBatchLoader

#: the widths bench.py names for a chip (bench_decode / bench_serving);
#: depth and step counts are what a smoke needs, nothing more
CHIP_SIZES = {
    "vocab": 32768, "dim": 1024, "layers": 8, "heads": 8,
    "window": 1024, "batch": 8, "epochs": 2, "spans": 4,
    "max_slots": 4, "block": 16, "spec_k": 4,
    "prompts": (16, 128, 512), "steps": 32,
}

#: whole-script deadline: the driver allows 1200 s, and a hung chip is
#: worse than a failed run — dump every thread and die first
DEADLINE_S = 1150
#: one HTTP round trip may sit behind several first-use compiles
REQUEST_TIMEOUT_S = 600.0


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _require(ok, message, *args):
    if not ok:
        raise SmokeFailure(message % args)


class SeededTokenLoader(FullBatchLoader):
    """Uniform token ids drawn from a seed (the bench.py TokenLoader
    precedent): ``samples/lm.py``'s MarkovLoader builds a [V, V, V]
    transition tensor and cannot make a 32768 vocabulary.  Module-level
    so the workflow pickles like any sample's."""

    def __init__(self, workflow, vocab=None, seq=None, n_valid=0,
                 n_train=0, seed=0, **kwargs):
        super(SeededTokenLoader, self).__init__(workflow, **kwargs)
        self.vocab, self.seq = int(vocab), int(seq)
        self.n_valid, self.n_train = int(n_valid), int(n_train)
        self.seed = int(seed)

    def load_data(self):
        n = self.n_valid + self.n_train
        self.class_lengths[:] = [0, self.n_valid, self.n_train]
        self.original_data = numpy.random.default_rng(
            self.seed).integers(
                0, self.vocab, (n, self.seq)).astype(numpy.int32)
        self.original_labels = [0] * n


def _max_err(got, want):
    """Largest absolute difference, relative to the reference's own
    scale (never below 1): one number a bf16 tolerance can bound for
    outputs and gradients alike."""
    got = numpy.asarray(got, numpy.float32)
    want = numpy.asarray(want, numpy.float32)
    _require(numpy.isfinite(got).all(), "non-finite kernel output")
    return float(numpy.abs(got - want).max()
                 / max(1.0, numpy.abs(want).max()))


# -- phase: kernels -----------------------------------------------------------

def _paged_rig(rng, sizes, k1):
    """A serving-shaped paged-attention problem: every slot deep in
    its own 64-block table, pools in the compute dtype."""
    import jax.numpy as jnp
    b, d, bs = sizes["max_slots"], sizes["dim"], sizes["block"]
    t = sizes["window"] // bs
    num = 1 + b * t                              # block 0 is trash

    def normal(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pk = normal((num, bs, d), jnp.float32).at[0].set(0.0)
    pv = normal((num, bs, d), jnp.float32).at[0].set(0.0)
    tables = jnp.asarray(rng.permutation(numpy.arange(1, num))
                         .reshape(b, t), jnp.int32)
    pos = jnp.asarray(rng.integers(t * bs // 2, t * bs - k1, (b,)),
                      jnp.int32)
    lens = jnp.asarray(rng.integers(1, k1 + 1, (b,)), jnp.int32)
    return (normal((b, k1, d)), normal((b, k1, d)), normal((b, k1, d)),
            pk, pv, tables, pos, lens)


def _kernel_cases(platform, sizes, rng):
    """(name, kernel_fn, reference_fn, args, select) per main-path
    kernel.  ``kernel_fn`` is the entry point the trainer / server
    calls with ``backend=platform``; ``reference_fn`` is the repo's jnp
    formulation of the same math; ``select`` picks what is comparable
    (rows past a verify run's ``lens`` are garbage under both)."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import gemm, paged_attention as pa
    from veles_tpu.ops.attention import attention
    from veles_tpu.ops.pallas_attention import pallas_attention

    b, s = sizes["batch"], sizes["window"]
    h, hd = sizes["heads"], sizes["dim"] // sizes["heads"]
    qkv = tuple(jnp.asarray(rng.standard_normal((b, s, h, hd)),
                            jnp.bfloat16) for _ in range(3))

    def flash(q, k, v):
        return pallas_attention(q, k, v, causal=True, backend=platform)

    def dense(q, k, v):
        return attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                         causal=True)

    def grads(core):
        def loss(q, k, v):
            return jnp.sum(jnp.sin(core(q, k, v).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2))

    yield "attention_fwd", flash, dense, qkv, None
    yield "attention_grad", grads(flash), grads(dense), qkv, None

    heads = sizes["heads"]
    for k1 in (1, sizes["spec_k"] + 1):
        q, kn, vn, pk, pv, tables, pos, lens = _paged_rig(rng, sizes, k1)
        valid = numpy.arange(k1)[None, :] < numpy.asarray(lens)[:, None]

        def ctx_rows(out, valid=valid):
            return numpy.asarray(out[-1], numpy.float32)[valid]

        cd = jnp.bfloat16
        yield ("paged_fp_k%d" % k1,
               functools.partial(pa.paged_verify_attention_fused,
                                 heads=heads, backend=platform),
               functools.partial(pa.paged_verify_attention,
                                 heads=heads),
               (q, kn, vn, pk.astype(cd), pv.astype(cd), tables, pos,
                lens), ctx_rows)
        qpk, sck = pa.quantize_kv_rows(pk)
        qpv, scv = pa.quantize_kv_rows(pv)
        yield ("paged_int8_k%d" % k1,
               functools.partial(pa.paged_verify_attention_q8,
                                 heads=heads, backend=platform),
               functools.partial(pa.paged_verify_attention_q8,
                                 heads=heads, backend="cpu"),
               (q, kn, vn, qpk, qpv, sck, scv, tables, pos, lens),
               ctx_rows)

    d, v = sizes["dim"], sizes["vocab"]
    a = jnp.asarray(rng.standard_normal((1, d)), jnp.bfloat16)
    wq, scale = gemm.int8_weight_quantize(
        jnp.asarray(rng.standard_normal((d, v)) * 0.02, jnp.float32))

    def w8_ref(a, wq, scale):
        return (a.astype(jnp.float32) @ wq.astype(jnp.float32)) \
            * scale[None, :]

    yield ("int8_matmul",
           functools.partial(gemm.int8_matmul, backend=platform),
           w8_ref, (a, wq, scale), None)


def kernels(device, sizes, seed, tol=3e-2):
    """Every main-path Pallas kernel on the chip against its jnp
    reference, and ``tpu_custom_call`` in the compiled text — interpret
    mode has been the only thing these ever ran under."""
    import jax

    from veles_tpu.ops.common import use_interpret
    from veles_tpu.telemetry import track_jit
    platform = device.jax_device.platform
    _require(not use_interpret(platform),
             "platform %r selects interpret-mode kernels", platform)
    errs = {}
    rng = numpy.random.default_rng(seed)
    with jax.default_device(device.jax_device):
        for name, fn, ref, args, select in _kernel_cases(
                platform, sizes, rng):
            jitted = track_jit("smoke." + name, jax.jit(fn))
            got = jitted(*args)
            _require("tpu_custom_call" in jitted.lower(
                *args).compile().as_text(),
                "%s: no Mosaic custom call in the compiled program",
                name)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(ref)(*args)
            if select is not None:
                got, want = select(got), select(want)
            errs[name] = max(
                _max_err(g, w) for g, w in zip(
                    jax.tree.leaves(got), jax.tree.leaves(want)))
            _require(errs[name] <= tol, "%s: max error %g > %g",
                     name, errs[name], tol)
    return {"kernels": len(errs), "max_err": errs}, None


# -- phase: train -------------------------------------------------------------

def lm_layers(sizes):
    """The ``samples/lm.py`` layer spec."""
    spec = [{"type": "embedding", "vocab": sizes["vocab"],
             "dim": sizes["dim"]}]
    spec += [{"type": "transformer_block", "heads": sizes["heads"],
              "causal": True} for _ in range(sizes["layers"])]
    return spec + [{"type": "token_logits", "vocab": sizes["vocab"]}]


def train(device, sizes, seed, mesh=None):
    """The ``samples/lm.py`` training stack run standalone through
    ``Launcher`` the way ``veles_tpu/__main__.py`` runs it: each epoch
    is a validation span (so the FIRST recorded loss is the untrained
    model's) and a training span of ``spans`` optimizer steps, every
    span one dispatch of the fused step.  Returns ``(facts, workflow)``."""
    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.standard import StandardWorkflow
    for stream in ("default", "loader", "trainer"):
        prng.get(stream).seed(seed)
    launcher = Launcher()
    launcher.device = device
    n = sizes["batch"] * sizes["spans"]
    wf = StandardWorkflow(
        launcher, name="smoke-lm", mesh=mesh,
        loader_factory=SeededTokenLoader,
        loader_config={
            "vocab": sizes["vocab"], "seq": sizes["window"],
            "n_valid": n, "n_train": n, "seed": seed,
            "minibatch_size": sizes["batch"],
            "normalization_type": "none"},
        layers=lm_layers(sizes), loss="next_token", solver="adam",
        learning_rate=1e-3, lr_schedule="cosine",
        lr_schedule_params={"total_steps": 3800, "floor": 0.05,
                            "warmup": 150},
        decision_config={"max_epochs": sizes["epochs"],
                         "fail_iterations": sizes["epochs"] + 1},
        # no snapshot round trip: pickling is host code, tier-1's job
        snapshotter_config={"enabled": False})
    loss_curve = next(p for p in wf.plotters if p.name == "loss_curve")
    loss_curve.collect = True        # read gd.loss after every span
    launcher.initialize()
    probe = wf.forwards[1].wq
    before = numpy.array(probe.map_read().mem)
    launcher.run()

    # the plotter reads gd.loss after every span but the last one,
    # whose decision ends the run first
    losses = list(loss_curve.series) + [float(wf.gd.loss.map_read().mem)]
    steps = wf.gd.global_step
    _require(steps >= sizes["epochs"] * sizes["spans"],
             "%d optimizer steps ran", steps)
    _require(len(losses) == 2 * sizes["epochs"]
             and all(math.isfinite(x) for x in losses),
             "losses %s", losses)
    _require(abs(losses[0] - math.log(sizes["vocab"])) < 1.0,
             "untrained loss %g is not near ln(vocab) = %g",
             losses[0], math.log(sizes["vocab"]))
    _require(not numpy.array_equal(before, probe.map_read().mem),
             "training left %s unchanged", probe)
    want = {device.jax_device} if mesh is None \
        else set(mesh.devices.flat)
    for unit in wf.forwards:
        for name, arr in unit.param_arrays().items():
            _require(set(arr.devmem.sharding.device_set) == want,
                     "%s.%s lives on %s, not %s", unit.name, name,
                     arr.devmem.sharding.device_set, want)
    return {"steps": steps, "losses": [round(x, 4) for x in losses],
            "validation_loss": round(
                wf.decision.epoch_metrics["validation_loss"], 4),
            "param_devices": len(want)}, wf


# -- phase: serve -------------------------------------------------------------

def _post(base, path, payload):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S)
    _require(resp.status == 200, "POST %s -> %d", path, resp.status)
    return resp


def _get(base, path):
    resp = urllib.request.urlopen(base + path, timeout=60)
    _require(resp.status == 200, "GET %s -> %d", path, resp.status)
    return resp.read()


def _read_sse(resp):
    """Drain one SSE response → its JSON payloads."""
    events = []
    for line in resp:
        line = line.strip()
        if line == b"data: [DONE]":
            break
        if line.startswith(b"data: "):
            events.append(json.loads(line[6:]))
    return events


def _client(base, sizes, seed):
    """What a user's HTTP client does: greedy /generate for three
    prompt lengths, the first again (a warm prefix-cache admission),
    one streamed; then the two metrics endpoints."""
    from veles_tpu.telemetry import parse_prometheus
    rng = numpy.random.default_rng(seed + 1)
    steps, vocab = sizes["steps"], sizes["vocab"]
    prompts = [rng.integers(0, vocab, (n,)).tolist()
               for n in sizes["prompts"]]
    replies = []
    for prompt in prompts + prompts[:1]:
        toks = json.load(_post(base, "/generate", {
            "prompt": prompt, "steps": steps}))["tokens"]
        _require(toks[:len(prompt)] == prompt
                 and len(toks) == len(prompt) + steps
                 and all(0 <= t < vocab for t in toks),
                 "bad reply to a %d-token prompt: %s", len(prompt),
                 toks[len(prompt):])
        replies.append(toks)
    _require(replies[-1] == replies[0],
             "the repeated prompt decoded differently")
    events = _read_sse(_post(base, "/generate", {
        "prompt": prompts[1], "steps": steps, "stream": True}))
    streamed = [e["token"] for e in events if "token" in e]
    final = [e for e in events if e.get("done")]
    _require(prompts[1] + streamed == replies[1] and final
             and final[0]["tokens"] == replies[1],
             "streamed tokens differ from the unstreamed answer")
    snap = json.loads(_get(base, "/serving/metrics"))
    served = 5 * steps
    _require(snap["tokens_generated"] >= served,
             "/serving/metrics counts %s tokens", snap["tokens_generated"])
    families = {f["name"]: f for f in parse_prometheus(
        _get(base, "/metrics").decode())}
    counted = sum(value for _, _, value in families[
        "veles_serving_tokens_generated_total"]["samples"])
    _require(counted >= served, "/metrics counts %s tokens", counted)
    # the defaults samples/serve.py leaves alone, not a quiet fallback
    # to the dense slot cache or to spec/prefix-cache off
    served_by = {key: snap[key] for key in (
        "kv_mode", "kv_dtype", "spec", "prefix_cache")}
    _require(served_by == {"kv_mode": "paged", "kv_dtype": "fp32",
                           "spec": True, "prefix_cache": True},
             "served by %s", served_by)
    return {"requests": 5, "tokens": served,
            "prefix_cache_hits": snap["prefix_cache_hits"],
            "spec_accepted_tokens": snap["spec_accepted_tokens"]}


def serve(device, sizes, seed, forwards):
    """The trained chain behind ``RESTfulAPI`` as ``samples/serve.py``
    wires it (scheduler on, paged pools, spec and prefix cache at their
    defaults), without the warm-up ladder — the smoke shows the path
    runs; the 42-executable ladder is a set-up cost to time elsewhere.
    The client runs on a thread so a wedged server fails the phase
    instead of hanging it."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.restful_api import RESTfulAPI, RestfulLoader
    wf = AcceleratedWorkflow(None, name="smoke-serve")
    loader = RestfulLoader(wf, sample_shape=(sizes["window"],),
                           minibatch_size=1, max_wait=1.0)
    loader.initialize(device=device)
    api = RESTfulAPI(
        wf, loader=loader, port=0, host="127.0.0.1", serving=True,
        max_slots=sizes["max_slots"], max_queue=32, forwards=forwards,
        serving_warm_buckets=False, request_timeout=REQUEST_TIMEOUT_S)
    api.output = forwards[-1].output
    api.initialize()
    pool = concurrent.futures.ThreadPoolExecutor(1, "smoke-client")
    try:
        facts = pool.submit(
            _client, "http://127.0.0.1:%d" % api.port, sizes,
            seed).result(DEADLINE_S)
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        api.stop()
        loader.close()
    return facts, None


# -- --chips N: one chip against a dp mesh ------------------------------------

def dp_compare(device, sizes, seed, chips=4, tol=2e-2):
    """The same seed and global batch on one device and under a
    ``{'dp': chips}`` mesh — what ``root.common.mesh = {'dp': -1}``
    builds on such a host.  Code that has only ever seen virtual CPU
    devices may put everything on the first: the placement checks are
    the point, the loss comparison (bf16 tolerance) the proof that the
    sharded step computes the same thing."""
    from veles_tpu.parallel import build_mesh
    _require(len(device.jax_devices) >= chips,
             "--chips %d on a host with %d", chips,
             len(device.jax_devices))
    one, _ = train(device, sizes, seed)
    mesh = build_mesh({"dp": chips},
                      devices=device.jax_devices[:chips])
    many, wf = train(device, sizes, seed, mesh=mesh)
    batch_sharding = wf.gd._ensure_shardings()[2]
    shard = batch_sharding.shard_shape(
        tuple(wf.loader.minibatch_data.shape))
    _require(len(batch_sharding.device_set) == chips
             and shard[0] * chips == sizes["batch"],
             "batch sharding %s gives shards of %s", batch_sharding,
             shard)
    _require(abs(one["losses"][0] - many["losses"][0]) <= tol,
             "first losses differ: %s on one chip, %s under dp=%d",
             one["losses"], many["losses"], chips)
    return {"chips": chips, "losses_one_chip": one["losses"],
            "losses_dp": many["losses"],
            "param_devices": many["param_devices"],
            "batch_shard_shape": list(shard)}, None


# -- driver -------------------------------------------------------------------

def run_phase(name, device, fn, *args, **kwargs):
    """Run one phase and print its JSON line."""
    from veles_tpu.telemetry import compile_summary
    before = compile_summary()
    t0 = time.monotonic()
    facts, value = fn(device, *args, **kwargs)
    after = compile_summary()
    line = {"phase": name,
            "seconds": round(time.monotonic() - t0, 3)}
    for key in ("compiles", "compiles_persistent_hit",
                "compile_seconds"):
        line[key] = round(after["total"].get(key, 0)
                          - before["total"].get(key, 0), 3)
    # which entry points the compile seconds went to
    line["compile_seconds_by_fn"] = {
        fn_name: round(rec["compile_seconds_total"] - before.get(
            fn_name, {}).get("compile_seconds_total", 0.0), 3)
        for fn_name, rec in after.items() if fn_name != "total"
        and rec["compiles"] > before.get(fn_name, {}).get("compiles", 0)}
    line.update(facts)
    line["peak_bytes_in_use"] = device.memory_stats().get(
        "peak_bytes_in_use")
    print(json.dumps(line), flush=True)
    return value


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--chips", type=int, default=1,
        help="with N > 1 run ONLY the one-chip vs dp=N train "
             "comparison (needs N chips)")
    args = parser.parse_args(argv)
    import jax
    first = jax.devices()[0]
    if first.platform != "tpu":
        print("chip_smoke: needs a TPU, JAX found %s (%s)"
              % (first.platform, first.device_kind), file=sys.stderr)
        return 1
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    from veles_tpu.accelerated_units import (
        enable_persistent_compile_cache)
    from veles_tpu.backends import Device
    from veles_tpu.logger import setup_logging
    setup_logging(logging.INFO)
    enable_persistent_compile_cache()
    device = Device(backend="tpu")
    if args.chips > 1:
        run_phase("dp_compare", device, dp_compare, CHIP_SIZES,
                  args.seed, chips=args.chips)
    else:
        run_phase("kernels", device, kernels, CHIP_SIZES, args.seed)
        wf = run_phase("train", device, train, CHIP_SIZES, args.seed)
        run_phase("serve", device, serve, CHIP_SIZES, args.seed,
                  wf.forwards)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
