"""Launcher — the composition root (rebuild of veles/launcher.py:100-906).

Owns runtime mode (standalone / coordinator / worker), the device, and
the workflow lifecycle.  The reference parked the main thread in a
Twisted reactor; here standalone runs are a plain synchronous
``workflow.run()`` (the scheduler's worklist already expresses the
graph's control flow) and distributed modes host the asyncio
coordinator/worker services from :mod:`veles_tpu.parallel.coordinator`.
"""

import json
import resource
import time

from veles_tpu.backends import Device
from veles_tpu.logger import Logger
from veles_tpu.memory import Watcher


class Launcher(Logger):
    """ref: veles/launcher.py:100.  Mode detection per launcher.py:333-356:
    ``listen`` → coordinator ("master"), ``master_address`` → worker
    ("slave"), else standalone."""

    def __init__(self, backend=None, device_index=0, listen=None,
                 master_address=None, graphics=None, status_url=None,
                 profile_dir=None, workers=None, worker_cmd_tail=None,
                 **kwargs):
        super(Launcher, self).__init__()
        self._listen = listen
        self._master_address = master_address
        self._backend = backend
        self._device_index = device_index
        self._graphics = graphics
        self._status_url = status_url
        self.device = None
        self.workflow = None
        self.start_time = None
        self.stopped = False
        self.coordinator = None
        self.graphics_server = None
        self.status_notifier = None
        self._profile_dir = profile_dir
        self._profiling = False
        #: worker specs: int (N local), or list/comma-list of host specs
        #: ("localhost" → subprocess, anything else → ssh, ref:
        #: veles/launcher.py:617-842 SSH slave spawn)
        self._workers = workers
        #: the re-exec tail (workflow file, config, -c overrides…) the
        #: CLI assembled for spawned workers
        self._worker_cmd_tail = list(worker_cmd_tail or [])
        self._worker_procs = []

    # -- mode (ref: launcher.py:333-356) --------------------------------------

    @property
    def mode(self):
        if self._listen:
            return "master"
        if self._master_address:
            return "slave"
        return "standalone"

    @property
    def is_standalone(self):
        return self.mode == "standalone"

    @property
    def is_master(self):
        return self.mode == "master"

    @property
    def is_slave(self):
        return self.mode == "slave"

    # -- lifecycle (ref: launcher.py:431-579) ---------------------------------

    def add_ref(self, workflow):
        """Called by the top-level Workflow adopting this launcher as its
        parent."""
        self.workflow = workflow

    def del_ref(self, workflow):
        if self.workflow is workflow:
            self.workflow = None

    def initialize(self, **kwargs):
        from veles_tpu.config import root
        # join the multi-host gang first (no-op unless VELES_TPU_
        # COORDINATOR/NUM_PROCESSES/PROCESS_ID configure one; pod
        # auto-detection needs multihost.initialize(auto=True)) — must
        # precede the first JAX use
        from veles_tpu.parallel import multihost
        pid, nproc = multihost.initialize()
        if nproc > 1:
            self.info("multi-host gang: process %d/%d", pid, nproc)
        if self.device is None:
            self.device = Device(backend=self._backend,
                                 device_index=self._device_index)
        self.info("mode: %s, device: %s", self.mode, self.device)
        # graphics PUB fan-out (ref: launcher starting the graphics
        # server process, veles/launcher.py:431-548); client processes
        # attach with `python -m veles_tpu.graphics_client <endpoint>`
        graphics = self._graphics
        if graphics is None:
            graphics = root.common.graphics.get("enabled", False)
        if graphics and not self.is_slave:
            from veles_tpu.graphics_server import GraphicsServer
            self.graphics_server = GraphicsServer(
                port=int(root.common.graphics.get("port", 0)))
        self.workflow.initialize(device=self.device, **kwargs)

    def run(self):
        """Run to completion (standalone) or serve (distributed)."""
        from veles_tpu.config import root
        self.start_time = time.time()
        status_url = self._status_url \
            or root.common.web.get("status_url")
        if status_url and not self.is_slave:
            from veles_tpu.web_status import StatusNotifier
            self.status_notifier = StatusNotifier(status_url, self)
            self.status_notifier.start()
        if self._profile_dir:
            # device-level trace of the whole run (SURVEY.md §5: the
            # fused programs need jax.profiler, not host wall timers);
            # per-unit TraceAnnotations ride root.common.trace.run
            import jax.profiler
            root.common.trace.run = True
            jax.profiler.start_trace(self._profile_dir)
            self._profiling = True
            self.info("jax.profiler trace -> %s", self._profile_dir)
        try:
            if self.is_standalone:
                self.workflow.run()
            elif self.is_master:
                if self._workers:
                    self._spawn_workers()
                from veles_tpu.parallel.coordinator import serve_master
                serve_master(self)
            else:
                from veles_tpu.parallel.coordinator import serve_worker
                serve_worker(self)
        finally:
            self.stop()

    # -- worker spawning (ref: veles/launcher.py:617-842) ---------------------

    def _spawn_workers(self):
        import shlex
        import socket
        import subprocess
        import sys
        import tempfile
        specs = self._workers
        if isinstance(specs, int):
            specs = ["localhost"] * specs
        elif isinstance(specs, str):
            specs = [s for s in specs.split(",") if s]
        host, _, port = (self._listen or ":5050").rpartition(":")
        port = port or "5050"
        if port == "0":
            # spawned workers need a dialable address before the
            # coordinator binds — an OS-assigned port can't be forwarded
            # to them
            raise ValueError(
                "-l :0 (OS-assigned port) cannot be combined with -w "
                "worker spawning; pick a fixed port")
        local_hosts = ("localhost", "127.0.0.1", "")
        local = [s for s in specs
                 if s.partition("/")[0] in local_hosts]
        if local and self.device is not None \
                and self.device.jax_device.platform == "tpu":
            # a chip belongs to ONE process at a time: this master
            # opened the device in initialize(), so a local worker
            # that needs it fails or hangs at its first JAX call
            raise RuntimeError(
                "-w cannot spawn %d local worker(s) on a TPU host: "
                "the master process already holds the chip(s) and a "
                "second process cannot open them — train across this "
                "host's chips in ONE process with root.common.mesh "
                "= {'dp': -1}, or name workers on other hosts"
                % len(local))
        n_local_devices = len(self.device.jax_devices) \
            if self.device is not None else 1
        local_count = 0
        for i, spec in enumerate(specs):
            # "host/D" pins the worker to device D (ref: veles -n
            # host/0:0x3 device syntax); plain local workers round-robin
            # over this host's devices
            spec, _, dev = spec.partition("/")
            is_local = spec in local_hosts
            if not dev:
                dev = str(local_count % n_local_devices) if is_local \
                    else "0"
            tail = list(self._worker_cmd_tail) + ["-d", dev]
            if is_local:
                tail += ["-m", "%s:%s" % (host or "127.0.0.1", port)]
                cmd = [sys.executable, "-m", "veles_tpu"] + tail
                local_count += 1
            else:
                # a remote worker must dial THIS host, not its own
                # loopback; quote every arg — ssh re-joins argv through
                # the remote shell
                master_host = host if host not in ("", "0.0.0.0") \
                    else socket.getfqdn()
                tail += ["-m", "%s:%s" % (master_host, port)]
                cmd = ["ssh", "-o", "BatchMode=yes", spec,
                       "python3", "-m", "veles_tpu"] + [
                           shlex.quote(a) for a in tail]
            log = tempfile.NamedTemporaryFile(
                mode="wb", suffix=".log", prefix="veles_worker%d_" % i,
                delete=False)
            proc = subprocess.Popen(cmd, stdout=log, stderr=log)
            self._worker_procs.append((proc, log.name))
            self.info("spawned worker %d on %s dev %s (pid %d, log %s)",
                      i, spec or "localhost", dev, proc.pid, log.name)

    def _reap_workers(self, timeout=30.0):
        import subprocess
        for proc, log in self._worker_procs:
            try:
                rc = proc.wait(timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait(5)
            if rc:
                try:
                    with open(log, "rb") as f:
                        tail = f.read()[-500:].decode(errors="replace")
                except OSError:
                    tail = "<no log>"
                self.warning("worker pid %d exited rc=%d: %s",
                             proc.pid, rc, tail)
        self._worker_procs = []

    def boot(self, **kwargs):
        self.initialize(**kwargs)
        self.run()

    def stop(self):
        if self.stopped:
            return
        self.stopped = True
        if self._worker_procs:
            self._reap_workers()
        if self._profiling:
            import jax.profiler
            jax.profiler.stop_trace()
            self._profiling = False
        if self.status_notifier is not None:
            self.status_notifier.stop()
        if self.graphics_server is not None:
            self.graphics_server.close()
        elapsed = time.time() - (self.start_time or time.time())
        self.workflow.stop()
        self.workflow.print_stats()
        used, peak = Watcher.report()
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        self.info("total run time: %.2fs; peak RSS: %.1f MiB; "
                  "peak device mem: %.1f MiB",
                  elapsed, rss / 1024.0, peak / 2 ** 20)

    # -- results (ref: workflow.py:827-849 + --result-file) -------------------

    def write_results(self, path):
        metrics = self.workflow.gather_results()
        metrics["elapsed_sec"] = time.time() - (self.start_time
                                                or time.time())
        # the ensemble aggregator needs to find each instance's snapshot
        # (ref: ensemble/base_workflow.py reads them back for test mode)
        from veles_tpu.snapshotter import SnapshotterBase
        for u in self.workflow.units:
            if isinstance(u, SnapshotterBase) \
                    and getattr(u, "destination", None):
                metrics["Snapshot"] = u.destination
        with open(path, "w") as f:
            json.dump(metrics, f, indent=2, default=str)
        self.info("results -> %s", path)
        return metrics
