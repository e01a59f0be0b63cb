"""Array — the host/device data pair (rebuild of veles/memory.py).

The reference's ``Array`` kept a numpy host mirror plus an OpenCL/CUDA
buffer with an explicit ``map_read / map_write / map_invalidate / unmap``
coherence protocol (ref: veles/memory.py:110-511).  On TPU the same object
exists at the *boundary* of jitted programs: loaders fill the host mirror,
``unmap()`` materialises a ``jax.Array`` in HBM, jitted workflow segments
consume and produce jax.Arrays, and ``map_read()`` brings results back for
plotting / snapshotting / metrics.  Inside a jitted segment there is no
map/unmap — XLA owns the buffers — so the protocol's cost disappears from
the hot path by design rather than by discipline.

Coherence is a 3-state machine instead of the reference's mapping
counters:

- ``HOST_DIRTY``  — host mirror newer (after map_write/map_invalidate);
- ``DEV_DIRTY``   — device buffer newer (after a jitted step wrote it);
- ``COHERENT``    — both views agree.

``Watcher`` keeps the global byte accounting the reference printed at
exit (ref: veles/memory.py:56-107, veles/__main__.py:779-797).
"""

import threading

import jax
import numpy

from veles_tpu.distributable import Pickleable

COHERENT = 0
HOST_DIRTY = 1
DEV_DIRTY = 2


class Watcher:
    """Global device-memory byte accounting
    (ref: veles/memory.py:56-107)."""

    _lock = threading.Lock()
    #: device repr -> bytes currently resident
    used = {}
    peak = 0

    @classmethod
    def alloc(cls, device, nbytes):
        with cls._lock:
            key = str(device)
            cls.used[key] = cls.used.get(key, 0) + nbytes
            cls.peak = max(cls.peak, sum(cls.used.values()))

    @classmethod
    def free(cls, device, nbytes):
        with cls._lock:
            key = str(device)
            cls.used[key] = max(0, cls.used.get(key, 0) - nbytes)

    @classmethod
    def total(cls):
        with cls._lock:
            return sum(cls.used.values())

    @classmethod
    def report(cls):
        with cls._lock:
            return dict(cls.used), cls.peak

    @classmethod
    def reset(cls):
        with cls._lock:
            cls.used.clear()
            cls.peak = 0


class Array(Pickleable):
    """Host numpy mirror + device jax.Array (ref: veles/memory.py:110).

    Usage::

        a = Array(numpy.zeros((128, 784), numpy.float32))
        a.initialize(device)          # allocate / upload
        a.map_write(); a.mem[...] = batch; a.unmap()   # host -> HBM
        out = jitted_fn(a.devmem)                       # device compute
        a.devmem = out                                  # adopt result
        a.map_read(); print(a.mem.mean())               # HBM -> host
    """

    def __init__(self, data=None, shape=None, dtype=numpy.float32):
        super(Array, self).__init__()
        if data is not None:
            self._mem = numpy.ascontiguousarray(data)
        elif shape is not None:
            self._mem = numpy.zeros(shape, dtype=dtype)
        else:
            self._mem = None
        self._state = HOST_DIRTY if self._mem is not None else COHERENT

    def init_unpickled(self):
        super(Array, self).init_unpickled()
        self._devmem_ = None
        self._device_ = None
        # snapshots store only the host mirror; device side is re-created
        # by the next initialize() (ref: veles/memory.py:284-292)
        if getattr(self, "_mem", None) is not None:
            self._state = HOST_DIRTY

    # -- host side -----------------------------------------------------------

    @property
    def mem(self):
        """The host numpy mirror.  Call :meth:`map_read`/:meth:`map_write`
        first when a device buffer exists."""
        return self._mem

    @mem.setter
    def mem(self, value):
        self._mem = numpy.ascontiguousarray(value) \
            if value is not None else None
        self._state = HOST_DIRTY

    def reset(self, data=None):
        """Drop both views and optionally adopt new host data
        (ref: veles/memory.py:330)."""
        self._release_devmem()
        self._mem = None if data is None else numpy.ascontiguousarray(data)
        self._state = HOST_DIRTY if data is not None else COHERENT

    # -- device side ---------------------------------------------------------

    @property
    def devmem(self):
        """The device jax.Array (uploads lazily if the host is newer)."""
        if self._state == HOST_DIRTY or self._devmem_ is None:
            self._upload()
        return self._devmem_

    def _aliases_host(self, devmem):
        """True when the host mirror and ``devmem`` share one
        allocation.  XLA:CPU makes this common in BOTH directions:
        ``jax.device_put`` borrows small (≲16 KB) numpy buffers
        zero-copy, and ``numpy.asarray(devmem)`` (map_read) returns a
        view of the device buffer.  Unknown layouts (sharded arrays
        without a host pointer) report True — the safe answer."""
        if self._mem is None or devmem is None:
            return False
        try:
            return devmem.unsafe_buffer_pointer() \
                == self._mem.ctypes.data
        except Exception:
            # no single host pointer (sharded array): only the CPU
            # backend can alias host memory at all, so assume the
            # worst there and nothing elsewhere
            try:
                plat = next(iter(devmem.devices())).platform
            except Exception:
                return True
            return plat == "cpu"

    def donatable_devmem(self):
        """The device buffer, guaranteed safe to DONATE
        (``donate_argnums``).  When host mirror and device buffer
        share an allocation, donation lets XLA reuse — and write its
        own (differently padded) output layout over — memory the host
        side still references or OWNS: glibc's "corrupted size vs.
        prev_size" family, the documented span-step heap corruption
        (ROUND6_NOTES.md).  Detaches with ONE device-side copy, paid
        only on the first step after a host write (init, snapshot
        resume, DCN master/slave apply) — steady-state steps adopt
        pure device outputs (DEV_DIRTY) and return the buffer as-is."""
        dm = self.devmem
        if self._state != COHERENT or not self._aliases_host(dm):
            return dm
        import jax.numpy as jnp
        fresh = jnp.copy(dm)   # device-owned, never host-aliased
        self._release_devmem()
        self._devmem_ = fresh
        Watcher.alloc(self._watch_key(), fresh.nbytes)
        return fresh

    @devmem.setter
    def devmem(self, value):
        """Adopt a jitted-program output as the new device buffer."""
        self._release_devmem()
        self._devmem_ = value
        if value is not None:
            Watcher.alloc(self._watch_key(), value.nbytes)
            self._state = DEV_DIRTY

    def adopt(self, mem, devmem=None, dev_dirty=False):
        """Install a prepared (host mirror, device buffer) pair
        WITHOUT copying or invalidating — the buffer-pool handoff of
        the asynchronous input pipeline (loader/prefetch.py).  Unlike
        the ``mem`` setter (which marks HOST_DIRTY and forces a
        re-upload on the next :attr:`devmem` read), both views are
        taken as already in agreement: consumers get the prefetched
        device handle with no host↔device traffic on the hot path.
        ``dev_dirty=True`` records that only the device side is live
        (a device-gather fill) so :meth:`map_read` still fetches."""
        self._release_devmem()
        self._mem = mem
        self._devmem_ = devmem
        if devmem is not None:
            Watcher.alloc(self._watch_key(), devmem.nbytes)
            self._state = DEV_DIRTY if dev_dirty else COHERENT
        else:
            self._state = HOST_DIRTY

    def _watch_key(self):
        if self._devmem_ is not None:
            try:
                return next(iter(self._devmem_.devices()))
            except Exception:
                pass
        return self._device_.jax_device if self._device_ else "host"

    def _release_devmem(self):
        if self._devmem_ is not None:
            Watcher.free(self._watch_key(), self._devmem_.nbytes)
            self._devmem_ = None

    def release_devmem(self):
        """Give the device buffer back WITHOUT losing the value: the
        host mirror is made current first (one device→host read where
        the device held the only current copy, free otherwise), and
        the next :attr:`devmem` read uploads it again.  For a holder
        of a frozen copy in another dtype or placement (the serving
        weights, serving/weights.py) that must not keep both."""
        self.map_read()
        if self._mem is None:   # no host mirror to come back from
            return
        self._release_devmem()
        self._state = HOST_DIRTY

    def _upload(self):
        if self._mem is None:
            return
        self._release_devmem()
        dev = self._device_.jax_device if self._device_ is not None else None
        if dev is not None:
            self._devmem_ = jax.device_put(self._mem, dev)
        else:
            self._devmem_ = jax.device_put(self._mem)
        Watcher.alloc(self._watch_key(), self._devmem_.nbytes)
        self._state = COHERENT

    def initialize(self, device=None):
        """Bind to a Device (ref: veles/memory.py:347).  The device
        buffer materialises lazily on first :attr:`devmem` access — an
        eager upload here would push every freshly-reset zero buffer
        (layer outputs, minibatch staging) over the host↔HBM link even
        when the fused/span programs never read them."""
        if device is not None:
            if self._devmem_ is not None and self._state != HOST_DIRTY:
                # migrate only if the live buffer is on a DIFFERENT jax
                # device — adopted program outputs (e.g. solver slots
                # born on-device) must not round-trip through the host
                # just because their Array wasn't device-bound yet
                try:
                    cur = next(iter(self._devmem_.devices()))
                except Exception:
                    cur = None
                if cur is not None and cur != device.jax_device:
                    self.map_read()
                    self._release_devmem()
            self._device_ = device
        return self

    # -- coherence protocol (ref: veles/memory.py:371-384) -------------------

    def map_read(self):
        """Make the host mirror current."""
        if self._state == DEV_DIRTY and self._devmem_ is not None:
            self._mem = self._fetch_host(self._devmem_)
            self._state = COHERENT
        return self

    @staticmethod
    def _fetch_host(devmem):
        """Device→host fetch that also works for multi-host arrays:
        fully-replicated global arrays read the local shard.  A
        cross-process *sharded* array is refused — the implicit
        allgather would be a blocking collective inside a host-side
        read, deadlocking any process-divergent code path; callers that
        really want it use multihost.process_allgather explicitly."""
        try:
            return numpy.asarray(devmem)
        except RuntimeError:
            sharding = devmem.sharding
            if getattr(sharding, "is_fully_replicated", False):
                shard = next(iter(devmem.addressable_shards))
                return numpy.asarray(shard.data)
            raise RuntimeError(
                "host read of a cross-process sharded array — gather it "
                "explicitly with veles_tpu.parallel.multihost."
                "process_allgather (an implicit collective here could "
                "deadlock the gang)")

    def map_write(self):
        """Host mirror current *and* about to be written."""
        self.map_read()
        if self._mem is not None and (
                not self._mem.flags.writeable
                or (self._state == COHERENT
                    and self._aliases_host(self._devmem_))):
            # map_read may have adopted a read-only view of the device
            # buffer — writers need their own copy; a WRITEABLE mirror
            # can still share the device buffer's allocation (zero-copy
            # device_put of a small host array), and writing through it
            # would mutate a buffer an asynchronously-dispatched XLA
            # program may still be reading
            self._mem = numpy.array(self._mem)
        self._state = HOST_DIRTY
        return self

    def map_invalidate(self):
        """Host will be fully overwritten — skip the device→host copy."""
        if self._mem is None and self._devmem_ is not None:
            self._mem = numpy.zeros(self._devmem_.shape, self._devmem_.dtype)
        elif self._mem is not None and (
                not self._mem.flags.writeable
                or (self._state == COHERENT
                    and self._aliases_host(self._devmem_))):
            self._mem = numpy.array(self._mem)
        self._state = HOST_DIRTY
        return self

    def unmap(self):
        """Flush host writes to the device buffer."""
        if self._state == HOST_DIRTY:
            self._upload()
        return self

    def __getstate__(self):
        # snapshot must capture the freshest view: a DEV_DIRTY buffer is
        # pulled back to the host first (ref: veles/memory.py:284-292)
        self.map_read()
        return super(Array, self).__getstate__()

    # -- conveniences --------------------------------------------------------

    @property
    def shape(self):
        if self._mem is not None:
            return self._mem.shape
        if self._devmem_ is not None:
            return self._devmem_.shape
        return None

    @property
    def dtype(self):
        if self._mem is not None:
            return self._mem.dtype
        if self._devmem_ is not None:
            return numpy.dtype(self._devmem_.dtype)
        return None

    @property
    def size(self):
        s = self.shape
        return int(numpy.prod(s)) if s is not None else 0

    @property
    def nbytes(self):
        return self.size * (self.dtype.itemsize if self.dtype else 0)

    def __bool__(self):
        return self._mem is not None or self._devmem_ is not None

    def __len__(self):
        s = self.shape
        return s[0] if s else 0

    def __getitem__(self, idx):
        self.map_read()
        return self._mem[idx]

    def __setitem__(self, idx, value):
        self.map_write()
        self._mem[idx] = value

    def __array__(self, dtype=None):
        self.map_read()
        return self._mem if dtype is None else self._mem.astype(dtype)

    def __repr__(self):
        return "<Array shape=%s dtype=%s state=%s>" % (
            self.shape, self.dtype,
            {COHERENT: "coherent", HOST_DIRTY: "host-dirty",
             DEV_DIRTY: "dev-dirty"}[self._state])


def roundup(num, align):
    """Round ``num`` up to a multiple of ``align``
    (ref: veles/numpy_ext.py roundup) — used for batch padding so shapes
    stay static under jit."""
    rem = num % align
    return num if rem == 0 else num + (align - rem)
