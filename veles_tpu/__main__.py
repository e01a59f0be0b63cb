"""CLI entry point: ``python -m veles_tpu <workflow.py> [config.py]``.

Rebuild of veles/__main__.py:136-867.  The user workflow file implements
the ``run(load, main)`` contract (ref: __main__.py:799-818)::

    def run(load, main):
        load(MnistWorkflow, layers=[100, 10])   # construct or resume
        main()                                   # initialize + run

``load`` returns ``(workflow, restored_from_snapshot)``; ``main``
initializes the launcher-owned workflow and runs it to completion.
"""

import json
import logging
import sys

import numpy

from veles_tpu import prng
from veles_tpu.cmdline import build_parser
from veles_tpu.config import (
    apply_config_file, apply_override, load_site_configs, root)
from veles_tpu.import_file import import_file_as_module
from veles_tpu.launcher import Launcher
from veles_tpu.logger import setup_logging
from veles_tpu.snapshotter import SnapshotterToFile


def _enable_compilation_cache(path):
    """``--compilation-cache DIR``: the explicit placement of the
    persistent XLA compile cache (the first run writes compiled
    executables there, every later launch loads them back —
    compile_tracker labels those loads ``cache="hit"`` in
    ``veles_jit_compiles_total``).  ``JAX_COMPILATION_CACHE_DIR``
    still wins when set; see
    ``accelerated_units.enable_persistent_compile_cache``."""
    from veles_tpu.accelerated_units import (
        enable_persistent_compile_cache)
    import jax
    enable_persistent_compile_cache(path)
    logging.getLogger("Main").info(
        "persistent XLA compilation cache: %s",
        jax.config.jax_compilation_cache_dir)


class Main:
    """ref: veles/__main__.py:136."""

    def __init__(self, argv=None):
        self.argv = list(sys.argv[1:] if argv is None else argv)
        self.args = None
        self.launcher = None
        self.workflow = None
        self.restored = False

    # -- seeding (ref: __main__.py:483) ---------------------------------------

    def _seed_random(self):
        seed = self.args.seed
        if seed is None:
            prng.get().seed(42)
            return
        if seed.startswith("file:"):
            spec = seed[5:]
            path, _, nbytes = spec.partition(":")
            with open(path, "rb") as f:
                data = f.read(int(nbytes) if nbytes else 16)
            prng.get().seed(numpy.frombuffer(data, numpy.uint8))
        else:
            prng.get().seed(int(seed))

    # -- the load/main contract (ref: __main__.py:591-668) --------------------

    def _load(self, workflow_class, **kwargs):
        if self.args.snapshot:
            snap = self.args.snapshot
            if snap.startswith(("sqlite:", "odbc:")):
                # DB resume (ref odbc:// URIs, __main__.py:539-589);
                # optional "#table/prefix" suffix selects the store
                from veles_tpu.snapshotter import SnapshotterToDB
                dsn, _, frag = snap.partition("#")
                table, _, prefix = frag.partition("/")
                if dsn.startswith("odbc:"):
                    dsn = dsn[5:]
                self.workflow = SnapshotterToDB.import_db(
                    dsn, table=table or "veles", prefix=prefix or None)
            else:
                self.workflow = SnapshotterToFile.import_file(snap)
            self.workflow.workflow = self.launcher
            self.restored = True
            logging.getLogger("Main").info(
                "resumed %s from %s", type(self.workflow).__name__,
                self.args.snapshot)
        else:
            self.workflow = workflow_class(self.launcher, **kwargs)
        return self.workflow, self.restored

    def _apply_decision_overrides(self):
        """--decision KEY=VALUE: poke the decision unit directly —
        the ONLY way to extend a resumed run, whose decision carries
        its pickled max_epochs/patience state, not the config's."""
        if not self.args.decision:
            return
        dec = getattr(self.workflow, "decision", None)
        if dec is None:
            raise ValueError(
                "--decision: workflow %s has no decision unit"
                % type(self.workflow).__name__)
        import ast

        from veles_tpu.mutable import Bool
        for kv in self.args.decision:
            key, sep, val = kv.partition("=")
            if not sep or not hasattr(dec, key):
                raise ValueError(
                    "--decision %r: %s has no attribute %r"
                    % (kv, type(dec).__name__, key))
            try:
                parsed = ast.literal_eval(val)
            except (ValueError, SyntaxError):
                parsed = val
            current = getattr(dec, key)
            if isinstance(parsed, str) and not isinstance(current, str):
                # a typo like max_epochs=4O must fail HERE, not as a
                # TypeError an epoch into the resumed training
                raise ValueError(
                    "--decision %r: could not parse %r (current "
                    "value is %r)" % (kv, val, current))
            if isinstance(current, Bool):
                # shared gate Bools are referenced by the graph's
                # gate expressions — REPLACING one would detach them
                current.set(bool(parsed))
            else:
                try:
                    setattr(dec, key, parsed)
                except AttributeError:
                    raise ValueError(
                        "--decision %r: %s.%s is read-only"
                        % (kv, type(dec).__name__, key))
            logging.getLogger("Main").info(
                "decision.%s = %r", key, parsed)

    def _main(self, **kwargs):
        self._apply_decision_overrides()
        self.launcher.initialize(**kwargs)
        if self.args.debug_pickle:
            from veles_tpu.pickle_debug import (
                _try_pickle, explain_pickle_failure)
            log = logging.getLogger("Main")
            if _try_pickle(self.workflow) is None:
                log.info("workflow pickles cleanly")
            else:
                log.error("%s", explain_pickle_failure(self.workflow))
        self.launcher.run()
        if self.args.result_file:
            self.launcher.write_results(self.args.result_file)
        if self.args.export_package:
            self.workflow.package_export(self.args.export_package)
            logging.getLogger("Main").info(
                "package -> %s", self.args.export_package)

    # -- run ------------------------------------------------------------------

    # -- meta-optimization modes (L9; ref: __main__.py:716-734 dispatch) ------

    def _child_argv(self):
        """Flags forwarded to evaluation subprocesses."""
        argv = []
        if self.args.backend:
            argv += ["-a", self.args.backend]
        if self.args.device:
            argv += ["-d", str(self.args.device)]
        for kv in self.args.decision:
            argv += ["--decision", kv]
        for _ in range(self.args.verbose):
            argv += ["-v"]
        return argv

    def _write_json(self, data):
        if self.args.result_file:
            with open(self.args.result_file, "w") as f:
                json.dump(data, f, indent=2, default=str)

    def _run_optimize(self):
        from veles_tpu.genetics import (
            GeneticsOptimizer, SubprocessEvaluator)
        size, _, gens = self.args.optimize.partition(":")
        evaluator = SubprocessEvaluator(
            self.args.workflow, self.args.config,
            base_overrides=self.args.config_override,
            extra_argv=self._child_argv())
        opt = GeneticsOptimizer(
            root, evaluator, size=int(size),
            generations=int(gens) if gens else 4)
        outcome = opt.run()
        logging.getLogger("Main").info(
            "optimization done: best fitness %s with %s",
            outcome["best_fitness"], outcome["best_genes"])
        self._write_json(outcome)
        return 0

    def _run_ensemble_train(self):
        from veles_tpu.ensemble import EnsembleTrainer
        trainer = EnsembleTrainer(
            self.args.workflow, self.args.config,
            size=self.args.ensemble_train,
            train_ratio=self.args.train_ratio,
            base_overrides=self.args.config_override,
            extra_argv=self._child_argv())
        summary = trainer.run(output_path=self.args.result_file)
        return 0 if summary["succeeded"] == summary["size"] else 1

    def _run_ensemble_test(self):
        from veles_tpu.ensemble import EnsembleTester
        tester = EnsembleTester(self.args.ensemble_test,
                                extra_argv=self._child_argv())
        out = tester.run(output_path=self.args.result_file)
        ok = all("error" not in t and t.get("results") is not None
                 for t in out["tests"])
        return 0 if ok else 1

    def run(self):
        parser = build_parser()
        self.args = parser.parse_args(self.argv)
        level = (logging.WARNING, logging.INFO,
                 logging.DEBUG)[min(self.args.verbose + 1, 2)]
        setup_logging(level)
        if self.args.frontend:
            # browser-composed run (ref: __main__.py:258-332): wait for
            # one submission, then execute it in this process.  Must
            # dispatch BEFORE any config is applied — the composed run
            # owns the global root tree, not this invocation's args.
            from veles_tpu.frontend import Frontend
            frontend = Frontend(parser, port=self.args.frontend_port)
            argv = frontend.wait()
            frontend.stop()
            if not argv:
                return 1
            logging.getLogger("Main").info(
                "frontend composed: %s", " ".join(argv))
            return Main(argv).run()
        load_site_configs()
        if self.args.timings:
            root.common.timings = True
        if self.args.events_log:
            from veles_tpu.logger import events
            events.open(self.args.events_log)
        if self.args.config:
            apply_config_file(self.args.config)
        for snippet in self.args.config_override:
            apply_override(snippet)
        if self.args.health_policy:
            root.common.health.policy = self.args.health_policy
        if self.args.flightrec_dir:
            root.common.flightrec.dir = self.args.flightrec_dir
        if self.args.admin_token:
            root.common.api.admin_token = self.args.admin_token
        if self.args.prefetch is not None:
            root.common.loader.prefetch.enabled = self.args.prefetch > 0
            root.common.loader.prefetch.depth = self.args.prefetch
        if self.args.compilation_cache:
            root.common.trace.compilation_cache_dir = \
                self.args.compilation_cache
        cache_dir = root.common.trace.get("compilation_cache_dir")
        if cache_dir:
            _enable_compilation_cache(cache_dir)
        if self.args.dump_config:
            root.print_()
            return 0
        # crash forensics from the first real work onward: faulthandler
        # for native faults, SIGUSR1 for on-demand dumps, excepthook for
        # unhandled Python errors (telemetry/flight_recorder.py)
        if root.common.flightrec.get("enabled", True):
            from veles_tpu.telemetry.flight_recorder import recorder
            recorder.install()
        if self.args.ensemble_test:
            return self._run_ensemble_test()
        if not self.args.workflow:
            parser.print_help()
            return 1
        if self.args.optimize:
            return self._run_optimize()
        if self.args.ensemble_train:
            return self._run_ensemble_train()
        # replace any un-tuned Range() markers with their defaults so a
        # config written for --optimize also runs standalone
        # (ref: genetics/config.py:164 fix_config)
        from veles_tpu.genetics import fix_config
        fix_config(root)
        self._seed_random()
        workers = self.args.workers
        if workers and not self.args.listen:
            parser.error("-w/--workers requires -l/--listen "
                         "(the coordinator spawns the workers)")
        if self.args.export_package and (
                self.args.optimize or self.args.ensemble_train
                or self.args.ensemble_test):
            parser.error("--export-package applies to a single training "
                         "run, not the optimize/ensemble fleet modes")
        if workers and workers.isdigit():
            workers = int(workers)
        # the re-exec tail spawned workers run: same workflow/config/
        # overrides + the shared child flags (ref: launcher.py:75
        # filter_argv role); the spawner appends per-worker -d/-m
        worker_tail = [self.args.workflow]
        if self.args.config:
            worker_tail.append(self.args.config)
        for snippet in self.args.config_override:
            worker_tail += ["-c", snippet]
        worker_tail += self._child_argv()
        self.launcher = Launcher(
            backend=self.args.backend, device_index=self.args.device,
            listen=self.args.listen,
            master_address=self.args.master_address,
            graphics=self.args.graphics or None,
            status_url=self.args.web_status,
            profile_dir=self.args.profile,
            workers=workers, worker_cmd_tail=worker_tail)
        module = import_file_as_module(self.args.workflow)
        if not hasattr(module, "run"):
            print("workflow file must define run(load, main)",
                  file=sys.stderr)
            return 1
        if self.args.visualize:
            # construct only, print DOT
            module.run(self._load, lambda **kw: None)
            print(self.workflow.generate_graph())
            return 0
        module.run(self._load, self._main)
        return 0


def main(argv=None):
    return Main(argv).run()


if __name__ == "__main__":
    sys.exit(main())
