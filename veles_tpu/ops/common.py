"""Shared helpers for the native-kernel modules.

Single source of truth for the accelerator-platform whitelist that
``ops.flash``, ``ops.pallas_attention``, ``ops.pallas_paged`` and
``ops.lrn`` all gate on — three independent copies once drifted
(ADVICE.md r4 #1)."""

import jax

#: platforms whose devices run real Mosaic kernels
ACCEL_PLATFORMS = ("tpu",)


def resolve_backend(backend=None):
    """The platform a computation targets: the caller's device platform
    when known (units pass ``unit.device.jax_device.platform``), else
    the process default backend as a last resort."""
    return backend if backend is not None else jax.default_backend()


def use_interpret(backend=None):
    """True when pallas kernels must run under ``interpret=True`` —
    i.e. the target device is not a TPU.  Keying off the *target*
    platform (not the process default) matters both ways: a
    CPU-targeted program in a TPU-default process must not trace a
    Mosaic kernel, and a TPU-targeted program in a CPU-default process
    must not silently run interpret-mode kernels on the chip."""
    return resolve_backend(backend) not in ACCEL_PLATFORMS
