"""Multi-host SPMD (SURVEY.md §4: multi-process jax.distributed on one
host; §2.3 "Multi-host / DCN execution").

Two worker processes join one jax.distributed gang (2 virtual CPU
devices each → a 4-device global mesh) and run (a) a sharded global
collective and (b) the FULL sharded flagship train step; the losses
must match bitwise across processes."""

import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_gang_trains():
    coord = "127.0.0.1:%d" % _free_port()
    # workers must come up as pure-CPU multi-process jax, one device
    # each (not this process's eight virtual ones)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    procs = []
    for i in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-u", WORKER, coord, "2", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    deadline = time.time() + 240
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(
                timeout=max(1, deadline - time.time()))[0])
        except subprocess.TimeoutExpired:
            p.kill()
            outs.append(p.communicate()[0])
    proofs = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        lines = [l for l in out.splitlines() if l.startswith("PROOF")]
        assert p.returncode == 0, \
            "worker %d rc=%s:\n%s" % (i, p.returncode, out[-1500:])
        proofs.append(dict(
            l.split(" ", 1)[1].split("=", 1) for l in lines
            if l.startswith(("PROOF sum=", "PROOF loss=",
                             "PROOF resumed_loss="))))
    # gang assembled: 4 global devices, 2 local each
    for i, out in enumerate(outs):
        assert "process %d/2 devices=4 local=2" % i in outs[i]
    # the sharded collective and the full train step agree bitwise
    assert proofs[0]["sum"] == proofs[1]["sum"] == "120.0"
    assert proofs[0]["loss"] == proofs[1]["loss"]
    # the mesh-sharded snapshot resumed across the gang (r4's
    # multi-host-aware mesh rebuild) and kept training in lockstep
    assert "resumed_loss" in proofs[0], outs[0][-800:]
    assert proofs[0]["resumed_loss"] == proofs[1]["resumed_loss"]
    assert float(proofs[0]["resumed_loss"]) != 0.0
