"""Quality harness — trains the BASELINE config families to
convergence and records the results next to the reference's published
accuracies (docs/source/manualrst_veles_algorithms.rst:31,51,70).

Zero-egress note: when the real MNIST/CIFAR corpora are absent the
runs use the documented procedural surrogates
(``veles_tpu/datasets/``), whose difficulty is calibrated against the
real tasks (glyph digits: sklearn logreg 6.0% / MLP-100 2.0% val err
at 7k train — real MNIST sits at ~7.5% / ~2%).  The JSON records which
corpus was used, the exact config of every run, and the metrics.

Usage: ``python quality.py [--out QUALITY.json]`` — each run shells
through the real CLI (``python -m veles_tpu``) with ``--result-file``,
so the numbers come from the shipped product path.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: reference published numbers (manualrst_veles_algorithms.rst)
REFERENCE = {
    "mnist_mlp": {"metric": "validation_error_pct", "value": 1.48,
                  "source": "manualrst_veles_algorithms.rst:31"},
    "cifar_conv": {"metric": "validation_error_pct", "value": 17.21,
                   "source": "manualrst_veles_algorithms.rst:51"},
    "mnist_ae": {"metric": "validation_rmse", "value": 0.5478,
                 "source": "manualrst_veles_algorithms.rst:70"},
    "stl10_conv": {"metric": "validation_error_pct", "value": 35.10,
                   "source": "manualrst_veles_algorithms.rst:52"},
    "gtzan_mlp": {"metric": "validation_error_pct", "value": None,
                  "source": "no published GTZAN number in the "
                            "reference docs; the anchor is the "
                            "pipeline config itself "
                            "(veles/genre_recognition.xml, "
                            "BASELINE.json config 5) — the corpus' "
                            "source paper reports 61% accuracy "
                            "(Tzanetakis & Cook 2002, GMM) with this "
                            "feature family"},
}

RUNS = {
    "mnist_mlp": {
        "workflow": "veles_tpu/samples/mnist.py",
        "config": "veles_tpu/samples/mnist_config.py",
        # r5 recipe (VERDICT r4 #5): shift augmentation on the flat
        # minibatch (the augment op reshapes via 'shape') + warmup-
        # then-cosine + longer patience — tuning run measured 1.11%
        # min / 1.24% final (r4 recipe: 1.76 vs the published 1.48)
        "overrides": (
            "root.mnist_tpu.update({"
            "'synthetic_kind': 'glyphs',"
            "'synthetic_train': 60000, 'synthetic_valid': 10000,"
            "'minibatch_size': 128, 'learning_rate': 0.1,"
            "'gradient_moment': 0.9, 'fail_iterations': 60,"
            "'max_epochs': 250, 'snapshot_time_interval': 1e9,"
            "'augment': {'kind': 'image', 'flip': False, 'pad': 2,"
            "            'shape': (28, 28, 1)},"
            "'lr_schedule': 'cosine',"
            "'lr_schedule_params': {'total_steps': 50000,"
            "                       'floor': 0.03, 'warmup': 300}})"),
        "target": "validation_error_pct <= 1.48 (the published "
                  "number, VERDICT r4 #5)",
    },
    "cifar_conv": {
        "workflow": "veles_tpu/samples/cifar.py",
        "config": "veles_tpu/samples/cifar_config.py",
        # r5 recipe (VERDICT r4 #5): the STL-10 machinery at full
        # data — flip + pad-4 crop + warmup-then-cosine + patience 60
        "overrides": (
            "root.cifar_tpu.update({"
            "'synthetic_kind': 'scenes',"
            "'synthetic_train': 50000, 'synthetic_valid': 10000,"
            "'minibatch_size': 128,"  # solver/lr: the sample's adam
            "'fail_iterations': 60, 'max_epochs': 250,"
            "'augment': {'kind': 'image', 'flip': True, 'pad': 4},"
            "'lr_schedule': 'cosine',"
            "'lr_schedule_params': {'total_steps': 70000,"
            "                       'floor': 0.03, 'warmup': 500},"
            "'snapshot_time_interval': 1e9})"),
        "target": "validation_error_pct <= 17.21 (the published "
                  "number, VERDICT r4 #5)",
    },
    "stl10_conv": {
        "workflow": "veles_tpu/samples/cifar.py",
        "config": "veles_tpu/samples/cifar_config.py",
        # the r4 low-data recipe (VERDICT r3 #6): in-graph flip/crop/
        # cutout augmentation + cosine LR + longer patience — measured
        # 23.4% in the round-4 tuning run, well
        # inside (and past) the published 35.10 band the bare recipe
        # missed by 8pp
        "overrides": (
            "root.cifar_tpu.update({"
            "'synthetic_kind': 'scenes', 'synthetic_size': 96,"
            "'synthetic_train': 5000, 'synthetic_valid': 8000,"
            "'minibatch_size': 100,"  # STL-10's low-data regime
            "'fail_iterations': 60, 'max_epochs': 300,"
            "'augment': {'kind': 'image', 'flip': True, 'pad': 8,"
            "            'cutout': 16},"
            "'lr_schedule': 'cosine',"
            # warmup de-risks the strict-relu plateau: without it the
            # default seed can sit at chance for 60+ epochs (the
            # escape is luck)
            "'lr_schedule_params': {'total_steps': 15000,"
            "                       'floor': 0.05, 'warmup': 500},"
            "'snapshot_time_interval': 1e9})"),
        "target": "validation_error_pct at-or-below the 35.10 band",
    },
    "gtzan_mlp": {
        "workflow": "veles_tpu/samples/gtzan.py",
        "config": None,
        # the corpus dir is synthesized by run_one (needs_corpus) via
        # veles_tpu.datasets.tones.generate — {corpus} interpolates it
        "needs_corpus": "tones",
        "overrides": (
            "root.gtzan_tpu.update({"
            "'dataset_dir': '{corpus}', 'max_seconds': 10.0,"
            "'minibatch_size': 50, 'hidden': 100,"
            "'fail_iterations': 50, 'max_epochs': 400,"
            "'snapshot_time_interval': 1e9})"),
        "target": "validation_error_pct in the literature band for "
                  "this feature family (GMM 39% err / MLP 20-30% err "
                  "on real GTZAN)",
    },
    "mnist_ae": {
        "workflow": "veles_tpu/samples/mnist_ae.py",
        "config": None,
        "overrides": (
            "root.mnist_tpu.update({"
            "'synthetic_kind': 'glyphs',"
            "'synthetic_train': 60000, 'synthetic_valid': 10000});"
            "root.mnist_ae_tpu.update({"
            "'normalization': 'linear',"  # the reference's [-1,1] scale
            "'minibatch_size': 128, 'fail_iterations': 30,"
            "'max_epochs': 150, 'snapshot_time_interval': 1e9})"),
        "target": "validation_rmse on the reference's own [-1,1] "
                  "'linear' normalization scale — directly comparable "
                  "to its 0.5478",
    },
}


def run_one(name, spec, timeout=3000):
    result_file = tempfile.NamedTemporaryFile(
        suffix=".json", prefix="quality_%s_" % name, delete=False).name
    overrides = spec["overrides"]
    if spec.get("needs_corpus") == "tones":
        # synthesize the procedural GTZAN-layout wav tree (idempotent;
        # cached per-user with a generator-parameter hash in the path)
        sys.path.insert(0, REPO)
        from veles_tpu.datasets import tones
        corpus = tones.generate()
        overrides = overrides.replace("{corpus}", corpus)
    cmd = [sys.executable, "-m", "veles_tpu", spec["workflow"]]
    if spec["config"]:
        cmd.append(spec["config"])
    cmd += ["-c", overrides, "--result-file", result_file]
    t0 = time.time()
    record = {"command": " ".join(cmd[2:]),
              "reference": REFERENCE[name], "target": spec["target"]}
    try:
        proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired as e:
        # one hung run is a failure of THAT run, not of the whole
        # sweep — record it (with whatever the child said) and let the
        # remaining families measure
        try:
            os.unlink(result_file)
        except OSError:
            pass
        record.update(seconds=round(time.time() - t0, 1), returncode=-1,
                      error="timeout after %ds" % timeout)
        if e.stderr:
            record["stderr_tail"] = e.stderr.decode(
                errors="replace")[-800:]
        return record
    record.update(seconds=round(time.time() - t0, 1),
                  returncode=proc.returncode)
    try:
        if proc.returncode:
            record["stderr_tail"] = proc.stderr.decode(
                errors="replace")[-800:]
            return record
        try:
            with open(result_file) as f:
                record["metrics"] = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            # a run that exited 0 without a readable result file is a
            # failure of THAT run, not of the whole sweep
            record["returncode"] = -1
            record["error"] = "no result file: %s" % e
        return record
    finally:
        try:
            os.unlink(result_file)
        except OSError:
            pass


def derive_metrics(name, metrics):
    """Metrics computed FROM the result file (kept out of the product
    path): the AE's comparison metric is RMSE = sqrt(validation MSE)
    on the loader's normalization scale."""
    if name == "mnist_ae" and "validation_loss" in metrics:
        metrics["validation_rmse"] = round(
            float(metrics["validation_loss"]) ** 0.5, 5)
    return metrics


def run_kv_quant():
    """Int8-KV quality record (in-process — this one measures the
    serving engine, not a trained config family): CE delta and greedy
    top-1 agreement of int8 vs fp32 KV pools on the SAME trained tiny
    chain the spec bench uses, through the real paged verify path
    (``veles_tpu/serving/kv_quality.py``; the bound itself is
    asserted in tier-1 — tests/test_kv_quant.py — this run records
    the measured numbers beside the training families)."""
    import numpy
    sys.path.insert(0, REPO)
    from veles_tpu.backends import Device
    from veles_tpu.serving.kv_quality import kv_quant_quality
    from bench import _spec_trained_chain
    t0 = time.time()
    vocab = 256
    pattern = (numpy.arange(12) * 17 % vocab).tolist()
    fw = _spec_trained_chain(Device(), 64, 2, 2, vocab, 128, 16,
                             pattern, 30, "quality-kv-quant")
    rng = numpy.random.default_rng(0)
    seqs = [(pattern * 11)[:96],           # the text it learned
            rng.integers(0, vocab, (96,)).tolist()]  # and noise
    rec = kv_quant_quality(fw, seqs, block_size=16)
    rec["seconds"] = round(time.time() - t0, 1)
    rec["target"] = ("kv_quant_ce_delta <= the declared tolerance "
                     "(the int8-KV gate; tier-1 asserts it)")
    return rec


def run_weight_quant():
    """Int8 CHECKPOINT-weight quality record (the PR 20
    ``weights_dtype="int8"`` snapshot-load path): CE delta of the
    quantized-weight chain vs its own f32 self on the same trained
    tiny chain and the same verify path as the KV gate —
    ``veles_tpu/serving/kv_quality.weight_quant_quality`` (which
    quantizes the chain in place, so this run builds its own)."""
    import numpy
    sys.path.insert(0, REPO)
    from veles_tpu.backends import Device
    from veles_tpu.serving.kv_quality import weight_quant_quality
    from bench import _spec_trained_chain
    t0 = time.time()
    vocab = 256
    pattern = (numpy.arange(12) * 17 % vocab).tolist()
    fw = _spec_trained_chain(Device(), 64, 2, 2, vocab, 128, 16,
                             pattern, 30, "quality-weight-quant")
    rng = numpy.random.default_rng(0)
    seqs = [(pattern * 11)[:96],           # the text it learned
            rng.integers(0, vocab, (96,)).tolist()]  # and noise
    rec = weight_quant_quality(fw, seqs, block_size=16)
    rec["seconds"] = round(time.time() - t0, 1)
    rec["target"] = ("weight_quant_ce_delta <= the declared "
                     "tolerance (the int8-weight gate; tier-1 "
                     "asserts it)")
    return rec


def summarize(runs):
    """The at-a-glance block: ours vs the reference's published number
    per family."""
    out = {}
    for name, rec in runs.items():
        m = rec.get("metrics") or {}
        ref = REFERENCE[name]
        entry = {"reference": ref["value"], "source": ref["source"]}
        if name == "mnist_ae":
            entry["ours_rmse"] = m.get("validation_rmse")
        else:
            entry["ours"] = m.get("validation_error_pct")
        entry["target"] = rec.get("target")
        if rec.get("returncode"):
            entry["failed"] = True
        out[name] = entry
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="QUALITY_r04.json")
    ap.add_argument("--only", help="run a single config family")
    args = ap.parse_args(argv)
    out = {"corpus": "procedural surrogates (zero-egress; see "
                     "veles_tpu/datasets/)", "runs": {}}
    for name, spec in RUNS.items():
        if args.only and name != args.only:
            continue
        print("== %s" % name, flush=True)
        rec = run_one(name, spec)
        if "metrics" in rec:
            rec["metrics"] = derive_metrics(name, rec["metrics"])
        out["runs"][name] = rec
        print(json.dumps(rec.get("metrics", rec), indent=1), flush=True)
    if not args.only or args.only == "kv_quant":
        print("== kv_quant", flush=True)
        out["kv_quant"] = run_kv_quant()
        print(json.dumps(out["kv_quant"], indent=1), flush=True)
    if not args.only or args.only == "weight_quant":
        print("== weight_quant", flush=True)
        out["weight_quant"] = run_weight_quant()
        print(json.dumps(out["weight_quant"], indent=1), flush=True)
    out["summary"] = summarize(out["runs"])
    with open(os.path.join(REPO, args.out), "w") as f:
        json.dump(out, f, indent=1)
    print("-> %s" % args.out)
    # a failed run is a failed sweep — callers checking $? must see it
    return 1 if any(r.get("returncode") for r in out["runs"].values()) \
        else 0


if __name__ == "__main__":
    sys.exit(main())
