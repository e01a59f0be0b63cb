"""Token embedding — the sequence-model input unit (no reference
analogue: sequence models existed only as untested Znicz units,
manualrst_veles_algorithms.rst:115-140; the TPU rebuild makes the
sequence stack first-class per the driver's long-context mandate).
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.models.nn_units import ForwardBase


class Embedding(ForwardBase):
    """[batch, seq] int tokens -> [batch, seq, dim] vectors.

    The gather rides HBM (``jnp.take``); the table is a plain
    parameter so tp/fsdp sharding conventions apply to it like any
    weight matrix."""

    #: minibatch dim 1 is a SEQUENCE dim for this unit — the
    #: trainer sp-shards data dim 1 only when a forward says so
    #: (ADVICE.md r4 #2: sp sharding is opt-in)
    SEQ_DIM1_INPUT = True

    PARAMS = ("weights", "positions")
    MATMUL_PARAMS = PARAMS

    def __init__(self, workflow, vocab=None, dim=None,
                 learned_positions=True, **kwargs):
        from veles_tpu.memory import Array
        super(Embedding, self).__init__(workflow, include_bias=False,
                                        **kwargs)
        if not vocab or not dim:
            raise ValueError("vocab and dim are required")
        self.vocab = int(vocab)
        self.dim = int(dim)
        #: add a learned positional table (sequence tasks are almost
        #: always position-relative; attention alone is permutation-
        #: equivariant without it)
        self.learned_positions = bool(learned_positions)
        self.positions = Array()

    def output_shape_for(self, input_shape):
        return tuple(input_shape) + (self.dim,)

    def fill_params(self):
        self.weights.reset(numpy.zeros((self.vocab, self.dim),
                                       numpy.float32))
        self._fill(self.weights.mem, self.weights_filling,
                   self.weights_stddev or 0.02, self.vocab, self.dim)
        if self.learned_positions:
            seq = int(self.input.shape[1])
            self.positions.reset(numpy.zeros((seq, self.dim),
                                             numpy.float32))
            self._fill(self.positions.mem, self.weights_filling,
                       self.weights_stddev or 0.02, seq, self.dim)

    def param_arrays(self):
        arrs = super(Embedding, self).param_arrays()
        if not self.learned_positions:
            arrs.pop("positions", None)
        return arrs

    def apply(self, params, x):
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        y = jnp.take(params["weights"].astype(cd),
                     x.astype(jnp.int32), axis=0)
        if self.learned_positions:
            y = y + params["positions"].astype(cd)[
                None, :y.shape[1], :]
        return y

    def apply_step(self, params, x, pos):
        """Single-position decode (models/generate.py kv_cache path):
        x [batch, 1] token ids at sequence index ``pos`` (traced
        scalar) — the positional row is gathered dynamically."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        y = jnp.take(params["weights"].astype(cd),
                     x.astype(jnp.int32), axis=0)
        if self.learned_positions:
            row = jax.lax.dynamic_slice(
                params["positions"].astype(cd), (pos, 0),
                (1, self.dim))
            y = y + row[None]
        return y

    def apply_chunk(self, params, x, offset):
        """Chunked-prefill lookup: x [batch, C] token ids occupying
        sequence positions [offset, offset+C) (``offset`` traced).
        The positional rows are gathered per index with clamping, so a
        tail chunk whose padding overruns the learned table reads a
        (masked-off) clamped row instead of shifting valid rows the
        way a clamped dynamic_slice would."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        y = jnp.take(params["weights"].astype(cd),
                     x.astype(jnp.int32), axis=0)
        if self.learned_positions:
            rows = jnp.take(params["positions"].astype(cd),
                            offset + jnp.arange(x.shape[1]), axis=0)
            y = y + rows[None]
        return y

    def apply_step_slots(self, params, x, pos):
        """Per-slot decode step (serving path): x [batch, 1] token
        ids where row n sits at ITS OWN sequence index ``pos[n]``
        ([batch] ints, traced) — each slot's positional row is
        gathered independently."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        y = jnp.take(params["weights"].astype(cd),
                     x.astype(jnp.int32), axis=0)
        if self.learned_positions:
            rows = jnp.take(params["positions"].astype(cd),
                            pos, axis=0)
            y = y + rows[:, None, :]
        return y

    def apply_verify_slots(self, params, x, pos):
        """Speculative-verify lookup: x [batch, K1] token ids where
        row n's position j sits at sequence index ``pos[n] + j``
        ([batch] ints, traced).  Positional rows are gathered per
        index with clamping — bucket-padding positions past the
        learned table read a (masked-off) clamped row, matching
        :meth:`apply_chunk`'s convention."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        y = jnp.take(params["weights"].astype(cd),
                     x.astype(jnp.int32), axis=0)
        if self.learned_positions:
            idx = jnp.clip(
                pos[:, None] + jnp.arange(x.shape[1])[None, :], 0,
                params["positions"].shape[0] - 1)
            y = y + jnp.take(params["positions"].astype(cd), idx,
                             axis=0)
        return y

    def export_config(self):
        return {"vocab": self.vocab, "dim": self.dim,
                "learned_positions": self.learned_positions}
