"""The serving weights: ONE frozen pytree of device parameters that a
running server hands to every jitted entry point (decode and verify
step, one-shot prefill and prefill chunk, the embeddings pool) where
an offline caller hands ``models/generate._device_params``.

A server's weights do not change, so what the step would do to them on
every call is done once here.  Each unit names, from its own state
(``ForwardBase.compute_dtype_params``), the leaves its traced code
reads only through ``.astype(compute_dtype())``; those are stored in
the compute dtype already, every other leaf (LayerNorm vectors, biases,
int8 checkpoint weights and their scales) stays as the unit holds it.
The operands of every product are bit-identical to a cast inside the
step: the same round-to-nearest of the same float32.  Under float32
compute, and for a unit that names nothing, the pytree is the units'
own device buffers.

A leaf that its unit already holds in the compute dtype (a checkpoint
handed over as bfloat16 device leaves before the unit initialized: no
host mirror, no float32 copy anywhere) is named by nobody and taken as
it is.

A tensor-parallel context (serving/tp.py) places each leaf on its mesh
after the cast, so there is one notion of frozen serving weights.

The float32 device buffer of a leaf that was cast or placed does not
stay beside its twin: the pytree is built leaf by leaf (take the
buffer, cast, place, release), so at most one leaf is held twice at
any moment.  Nothing is lost: ``Array.release_devmem`` makes the host
mirror current first, and a later ``Array.devmem`` (a snapshot, a
trainer stepping the same units once the server has stopped) uploads
float32 again.
"""

import jax
from jax.sharding import PartitionSpec as P

from veles_tpu.memory import Watcher


class ServingWeights:
    """``params``: ``{chain index: {name: jax.Array}}``, built once by
    the thread that constructs this (``Array.devmem``'s lazy upload is
    not re-entrant), then only read.  ``leaves_cast``: how many leaves
    are held in the compute dtype instead of the unit's (0 says the
    mechanism did not engage).  ``bytes_by_dtype``: {dtype name: bytes
    resident on the devices}; ``dtype``: the one that holds most of
    them, which is what the matmuls stream."""

    def __init__(self, forwards, tp=None):
        self.leaves_cast = 0
        self.bytes_by_dtype = {}
        self.params = {}
        self._owned = []
        try:
            for i, u in enumerate(forwards):
                self.params[i] = self._layer(u, tp)
        except BaseException:
            self.close()
            raise
        self.dtype = max(self.bytes_by_dtype,
                         key=self.bytes_by_dtype.get)

    def _layer(self, unit, tp):
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        declared = getattr(unit, "compute_dtype_params", None)
        cast = frozenset(declared()) if declared is not None \
            else frozenset()
        spec_fn = getattr(unit, "tp_param_spec", None) \
            if tp is not None else None
        layer = {}
        for name, arr in unit.param_arrays().items():
            # the CURRENT device value: the host mirror can be stale
            # after training until a map_read
            leaf = mine = arr.devmem
            if name in cast:
                leaf = leaf.astype(cd)
                self.leaves_cast += 1
            if tp is not None:
                spec = spec_fn(name, tp.size) \
                    if spec_fn is not None else None
                leaf = jax.device_put(
                    leaf, tp.sharding(spec if spec is not None
                                      else P()))
            resident = [(sh.device, sh.data.nbytes)
                        for sh in leaf.addressable_shards]
            if leaf is not mine:
                leaf.block_until_ready()
                del mine
                arr.release_devmem()
                for dev, nbytes in resident:
                    Watcher.alloc(dev, nbytes)
                self._owned += resident
            key = str(leaf.dtype)
            self.bytes_by_dtype[key] = self.bytes_by_dtype.get(key, 0) \
                + sum(nbytes for _, nbytes in resident)
            layer[name] = leaf
        return layer

    def close(self):
        """Drop the pytree.  The device buffers go with the last
        reference: the compiled-step caches hold none."""
        self.params = None
        for dev, nbytes in self._owned:
            Watcher.free(dev, nbytes)
        self._owned = []
