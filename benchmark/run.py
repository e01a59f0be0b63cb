#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

This file knows no cell, configuration or metric by name.  Everything
that belongs to one of them is a file found by the name in
``BENCHMARK.json``, and a later PR extends the benchmark by ADDING files
and manifest entries only, never by editing one that is there:

  a cell          an entry of ``workloads`` (config, traffic, chips, why);
  a configuration ``benchmark/configs/<config>.json``;
  a traffic mix   ``benchmark/traffic/<traffic>.json``: parameters for the
                  driver its ``kind`` names, ``benchmark/drivers/<kind>.py``
                  (a new KIND of traffic is a new file there);
  a per-layer metric  an entry of ``per_layer`` (whose ``workloads`` lists
                  the cells it is read in) plus
                  ``benchmark/metrics/<metric>.json``, which names its
                  reader ``benchmark/readers/<reader>.py`` and the
                  reader's parameters.  A reader that finds nothing to
                  read returns None and the metric is left out.

The run: set-up (build, weights from the seed, warm every shape) ->
the measured window of ``--seconds`` -> peak memory read -> the
program's state freed -> the plain reference over what the window
produced, which decides ``correct``.  Needs a TPU with as many chips as
the cell asks for; anything else exits non-zero with no result line.
The LAST line of standard output is the result; every earlier line
names the device and carries phase seconds and compile counts.
"""

import sys
import time

T_START = time.monotonic()          # process start, for setup_s

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
#: everything a run leaves behind lives here (git-ignored): the compile
#: cache at a FIXED path (the path is part of the cache's key) and the
#: trace of a --trace 1 run until it is read
SCRATCH = os.path.join(ROOT, ".bench_cache")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit("no workload %r in BENCHMARK.json" % (name,))


def metrics_of(manifest, group, cell_name):
    """The manifest's metrics of one group that this cell reports."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


class Context:
    """What a driver gets: the cell's files, the seed, the device, and
    ``log`` for the earlier output lines."""

    def __init__(self, cell, config, traffic, seed, jax_device, device):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.jax_device, self.device = seed, jax_device, device
        self.shapes = config["shapes"]
        self.count = 0

    def log(self, phase, **facts):
        """One earlier line: device first, then the phase's facts."""
        line = {"platform": self.jax_device.platform,
                "device_kind": self.jax_device.device_kind,
                "device_count": self.count, "phase": phase,
                "t_s": round(time.monotonic() - T_START, 3)}
        line.update(facts)
        print(json.dumps(line), flush=True)


class Tracer:
    """The profiler around the window, or around the part of it that the
    driver chooses (``start`` and ``stop`` do nothing with --trace 0 and
    nothing the second time)."""

    def __init__(self, directory, enabled):
        self.directory, self.enabled = directory, enabled
        self.started = self.seconds = None

    def start(self):
        if not self.enabled or self.started is not None:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started = time.monotonic()

    def stop(self):
        if self.started is None or self.seconds is not None:
            return
        import jax
        self.seconds = time.monotonic() - self.started
        jax.profiler.stop_trace()


def compile_facts(before, after):
    """Compiles, cache hits and seconds per entry point between two
    ``telemetry.compile_summary()`` readings."""
    out = {}
    for name, rec in after.items():
        old = before.get(name, {})
        delta = rec.get("compiles", 0) - old.get("compiles", 0)
        if not delta:
            continue
        seconds = "compile_seconds" if name == "total" \
            else "compile_seconds_total"
        out[name] = {
            "compiles": delta,
            "hits": rec.get("compiles_persistent_hit", 0)
            - old.get("compiles_persistent_hit", 0),
            "seconds": round(rec.get(seconds, 0.0)
                             - old.get(seconds, 0.0), 3)}
    return out


def read_per_layer(manifest, cell_name, record):
    """Each per-layer metric of the cell through its own reader."""
    out = {}
    for metric in metrics_of(manifest, "per_layer", cell_name):
        spec = load_json(HERE, "metrics", metric["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(record, spec.get("params", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also judge the comparison's control and "
                        "planted faults (calibration, never the driver's "
                        "runs; earlier lines, each with its `correct`)")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(manifest, args.workload)
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    peaks = load_json(HERE, "peaks.json")

    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu" or len(devices) < cell["chips"]:
        print("benchmark: %s needs %d TPU chip(s); JAX found %d x %s (%s)"
              % (cell["name"], cell["chips"], len(devices),
                 first.platform, first.device_kind), file=sys.stderr)
        return 1
    from benchmark import compare, flops, trace_reduce
    peak = flops.peak_for(first.device_kind, peaks)    # unknown: raises

    from veles_tpu.accelerated_units import enable_persistent_compile_cache
    from veles_tpu.backends import Device
    from veles_tpu.telemetry import compile_summary
    # the program re-places its cache whenever a unit initializes: give it
    # the benchmark's directory as its configured one, so that it stays
    from veles_tpu.config import root
    cache_dir = os.path.join(SCRATCH, "jax_cache")
    root.common.trace.update({"compilation_cache_dir": cache_dir})
    enable_persistent_compile_cache(path=cache_dir)
    device = Device(backend="tpu")
    ctx = Context(cell, config, traffic, args.seed, first, device)
    ctx.count = len(devices)
    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])

    before = compile_summary()
    state = driver.setup(ctx)
    setup_s = time.monotonic() - T_START
    warm = compile_summary()
    ctx.log("setup", seconds=round(setup_s, 3),
            compiles=compile_facts(before, warm))

    tracer = Tracer(os.path.join(SCRATCH, "trace-" + cell["name"]),
                    bool(args.trace))
    record = driver.window(state, args.seconds, tracer)
    tracer.stop()
    after = compile_summary()
    in_window = compile_facts(warm, after)
    record["compiles_in_window"] = in_window.get(
        "total", {}).get("compiles", 0)
    memory_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                      for d in devices[:cell["chips"]])
    ctx.log("window", seconds=round(record["window_s"], 3),
            compiles=in_window, memory_peak_bytes=memory_peak,
            **record.get("facts", {}))

    t0 = time.monotonic()
    driver.release(state)
    compared = driver.check(ctx, record, control=bool(args.control))
    correct = compare.verdict(compared)
    ctx.log("check", seconds=round(time.monotonic() - t0, 3),
            correct=correct)

    device_facts = {"platform": first.platform, "kind": first.device_kind,
                    "count": len(devices),
                    "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"]}
    if args.trace:
        t0 = time.monotonic()
        path = trace_reduce.find_xplane(tracer.directory)
        trace_bytes = os.path.getsize(path)
        lines, spans = trace_reduce.read_xplane(path)
        reduced = trace_reduce.reduce_events(
            lines[:cell["chips"]], spans, tracer.seconds * 1e9)
        shutil.rmtree(tracer.directory, ignore_errors=True)
        if not reduced["busy_s"] > 0:
            print("benchmark: the trace shows no device operation",
                  file=sys.stderr)
            return 1
        record.update(trace=reduced, peak=peak, shapes=ctx.shapes,
                      config=config, traffic=traffic,
                      memory_peak_bytes=memory_peak)
        result["metrics"] = read_per_layer(manifest, cell["name"], record)
        device_facts.update(busy_s=reduced["busy_s"],
                            window_s=tracer.seconds)
        result["breakdown"] = trace_reduce.breakdown(reduced)
        ctx.log("trace", seconds=round(time.monotonic() - t0, 3),
                trace_bytes=trace_bytes,
                device_events=sum(len(l) for l in lines),
                device_span_s=trace_reduce.span_seconds(lines))
    else:
        values = dict(record["end_to_end"], setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", cell["name"])}
    result["device"] = device_facts
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]}
                          for c in compared}
    for c in compared:
        print("compared %s = %.6g (limit %.6g)%s" % (
            c["name"], c["value"], c["limit"],
            "" if c["value"] <= c["limit"] else "  <-- OVER"),
            file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
