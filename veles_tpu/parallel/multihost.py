"""Multi-host SPMD entry (SURVEY.md §2.3 "Multi-host / DCN execution").

The reference scaled across hosts with its elastic ZeroMQ star (one
process per slave, veles/server.py); the TPU-native equivalent is gang
SPMD: every host process joins one ``jax.distributed`` coordination
service, the mesh spans ALL processes' devices, and XLA routes
collectives over ICI within a slice and DCN across slices.  The elastic
DCN job-queue layer (veles_tpu.parallel.coordinator) remains the
between-gang tier (ensemble/genetics fleets, parameter-server mode).

Wire-up: call :func:`initialize` before the first JAX use — explicitly,
via the ``VELES_TPU_COORDINATOR`` / ``VELES_TPU_NUM_PROCESSES`` /
``VELES_TPU_PROCESS_ID`` environment (the Launcher does this), or rely
on the TPU pod metadata auto-detection jax.distributed already does on
Cloud TPU VMs.
"""

import os


def initialize(coordinator_address=None, num_processes=None,
               process_id=None, local_device_ids=None, auto=False):
    """Join the jax.distributed coordination service.

    Configuration sources, in order: explicit args, the
    ``VELES_TPU_COORDINATOR`` / ``VELES_TPU_NUM_PROCESSES`` /
    ``VELES_TPU_PROCESS_ID`` environment, or — only with ``auto=True`` —
    jax.distributed's own cluster auto-detection (Cloud TPU pod
    metadata, SLURM, …).  With nothing configured and ``auto`` unset
    this is a single-process no-op.

    Returns (process_id, num_processes) after initialization.  Safe to
    call when already initialized (no-op).
    """
    import jax

    if jax.distributed.is_initialized():
        # idempotent: report the live gang's coordinates
        return jax.process_index(), jax.process_count()

    coordinator_address = coordinator_address or os.environ.get(
        "VELES_TPU_COORDINATOR")
    if num_processes is None and "VELES_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["VELES_TPU_NUM_PROCESSES"])
    if process_id is None and "VELES_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["VELES_TPU_PROCESS_ID"])

    if num_processes in (None, 1) and coordinator_address is None \
            and not auto:
        return 0, 1  # single process — nothing to join
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
    return jax.process_index(), jax.process_count()


def global_mesh(axes):
    """A mesh over ALL processes' devices (jax.devices() is global after
    :func:`initialize`)."""
    import jax

    from veles_tpu.parallel.mesh import build_mesh
    return build_mesh(axes, devices=jax.devices())


def global_put(host_array, mesh, spec):
    """Build a global jax.Array from per-process host data (every
    process passes the SAME full ``host_array`` — the replicated-input
    convention; each reference slave also held a full dataset copy)."""
    from jax.sharding import NamedSharding

    from veles_tpu.parallel.sharding import put
    return put(host_array, NamedSharding(mesh, spec))


def process_allgather(value):
    """Host-level allgather of small per-process python values (worker
    status/metrics aggregation without the coordinator tier)."""
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(value)


def sync_global_devices(tag):
    """Barrier across processes (checkpoint rendezvous etc.)."""
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(tag)
