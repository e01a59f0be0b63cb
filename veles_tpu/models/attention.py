"""Multi-head attention forward unit — the sequence-model entry of the
zoo (no reference analogue: RNN/LSTM existed only untested in the
absent Znicz submodule, manualrst_veles_algorithms.rst:115-140).

This unit's ``apply`` is the single-program formulation (XLA/GSPMD
shards it like any other op), with two exceptions the trainer's mesh
(``sp_mesh_``, handed over by GradientDescent.initialize) decides:

- long contexts where each chip must hold only 1/sp of K/V switch the
  attention core to the RING schedule under ``shard_map`` —
  sequence-sharded training end-to-end, gradients flowing through the
  ppermute ring (ops/attention.py); GSPMD cannot derive that
  communication schedule from the single-program form;
- a Mosaic kernel is opaque to GSPMD ("cannot be automatically
  partitioned"), so on any other mesh the pallas cores run per shard
  under ``shard_map`` too: batch over dp/fsdp, heads over tp."""

import functools

import numpy

from veles_tpu.models.nn_units import ForwardBase


def _batch_axes(mesh):
    """The mesh axes a minibatch's leading dim shards over."""
    return tuple(a for a in ("dp", "fsdp")
                 if mesh.shape.get(a, 1) > 1) or None


def _ring_mha(mesh, q, k, v, causal):
    """The sp-sharded attention core: q/k/v [batch, seq, heads, hd]
    with seq over ``sp`` (and batch over dp/fsdp when present); K/V
    rotate around the ring so each chip only ever holds seq/sp of
    them."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from veles_tpu.ops.attention import ring_attention
    spec = P(_batch_axes(mesh), "sp", None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp",
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _per_shard(mesh, core, q, k, v):
    """Run a kernel core per mesh shard: q/k/v [batch, seq, heads,
    hd] with batch over dp/fsdp and whole heads over tp (attention
    never mixes batch rows or heads, so no collective is needed)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    tp = mesh.shape.get("tp", 1)
    head_axis = "tp" if tp > 1 and q.shape[2] % tp == 0 else None
    spec = P(_batch_axes(mesh), None, head_axis, None)
    # check_vma off: pallas_call's out_shape carries no varying-axes
    # annotation, and there is no collective here for it to check
    return shard_map(core, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def mha_apply(params, x, heads, causal, block_size=None, sp_mesh=None,
              attn_impl=None, backend=None):
    """Multi-head attention forward over [batch, seq, d] — the ONE
    implementation shared by the MultiHeadAttention unit and
    TransformerBlock (params: wq/wk/wv/wo, each [d, d]).  Projections
    run in the compute dtype (bf16 trunk policy); the attention core
    is selected in priority order:

    - ``sp_mesh`` (the trainer's mesh) with an sp axis > 1 → the
      ppermute RING (sequence parallelism is a communication schedule,
      it overrides the rest);
    - ``attn_impl`` "flash" | "blockwise" | "dense" → that core;
    - default (None/"auto") → the framework's NATIVE pallas flash
      kernels on TPU at any sequence length (lane-multiple head_dim;
      ops/pallas_attention.py), else blockwise streaming if
      ``block_size`` says so, else the plain single-program form.

    Under any other ``sp_mesh`` the kernel cores ("flash", "pallas")
    run per shard (:func:`_per_shard`): GSPMD refuses to partition a
    Mosaic call."""
    import jax.numpy as jnp

    from veles_tpu import dtypes
    from veles_tpu.ops.attention import attention
    cd = dtypes.compute_dtype()
    ad = dtypes.accum_dtype()
    prec = dtypes.matmul_precision()
    b, s, d = x.shape
    hd = d // heads

    def proj(w):
        y = jnp.einsum("bsd,de->bse", x.astype(cd), w.astype(cd),
                       precision=prec, preferred_element_type=ad)
        return y.astype(cd).reshape(b, s, heads, hd)

    sp = sp_mesh.shape.get("sp", 1) if sp_mesh is not None else 0
    if sp > 1:
        o = _ring_mha(sp_mesh, proj(params["wq"]), proj(params["wk"]),
                      proj(params["wv"]), causal)
    else:
        impl = attn_impl or "auto"
        if impl == "auto":
            from veles_tpu.ops.common import resolve_backend, \
                ACCEL_PLATFORMS
            # the NATIVE kernels are the default at EVERY length (r5:
            # clamped causal index maps skip dead-block DMAs and
            # 1024-token K blocks fix the long-context bookkeeping —
            # measured past the jax-shipped kernel at 2048, 8192 AND
            # 32768 in round 5, jax 0.4.37).  Odd lengths pad-and-mask
            # inside the kernel.  head_dim off the lane width falls
            # back (the MXU would run mostly idle); attn_impl pins
            # either kernel explicitly.
            if resolve_backend(backend) in ACCEL_PLATFORMS \
                    and hd % 128 == 0:
                impl = "pallas"
            else:
                impl = "blockwise" if block_size else "dense"
        q, k, v = (proj(params[n]) for n in ("wq", "wk", "wv"))
        if impl == "flash":
            from veles_tpu.ops.flash import flash_attention
            core = functools.partial(flash_attention, causal=causal,
                                     backend=backend)
        elif impl == "pallas":
            # the framework's OWN flash kernels (ops/pallas_attention)
            from veles_tpu.ops.pallas_attention import pallas_attention
            core = functools.partial(pallas_attention, causal=causal,
                                     backend=backend)
        elif impl == "blockwise":
            from veles_tpu.ops.attention import blockwise_attention
            core = functools.partial(
                blockwise_attention, block_size=block_size or 512,
                causal=causal)
        elif impl == "dense":
            core = functools.partial(attention, causal=causal)
        else:
            raise ValueError("unknown attn_impl %r" % (attn_impl,))
        if sp_mesh is not None and impl in ("flash", "pallas"):
            o = _per_shard(sp_mesh, core, q, k, v)
        else:
            o = core(q, k, v)
    return jnp.einsum("bsd,de->bse", o.reshape(b, s, d).astype(cd),
                      params["wo"].astype(cd),
                      precision=prec,
                      preferred_element_type=ad).astype(x.dtype)


class MultiHeadAttention(ForwardBase):
    """y = (softmax(QK^T/sqrt(d)) V) Wo with Q/K/V = x·Wq/Wk/Wv.

    x: [batch, seq, model_dim]."""

    #: minibatch dim 1 is a SEQUENCE dim for this unit — the
    #: trainer sp-shards data dim 1 only when a forward says so
    #: (ADVICE.md r4 #2: sp sharding is opt-in)
    SEQ_DIM1_INPUT = True

    PARAMS = ("wq", "wk", "wv", "wo")

    def __init__(self, workflow, heads=4, causal=False,
                 block_size=None, attn_impl=None, **kwargs):
        from veles_tpu.memory import Array
        super(MultiHeadAttention, self).__init__(workflow, **kwargs)
        self.heads = int(heads)
        self.causal = causal
        #: stream K/V in blocks of this many tokens (long sequences:
        #: avoids the [seq, seq] score matrix; ops/attention.py)
        self.block_size = block_size
        #: attention core override: "flash" | "blockwise" | "dense"
        #: (None = auto; see mha_apply)
        self.attn_impl = attn_impl
        for p in self.PARAMS:
            setattr(self, p, Array())

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def fill_params(self):
        d = self.input.shape[-1]
        if d % self.heads:
            raise ValueError("model dim %d not divisible by %d heads"
                             % (d, self.heads))
        for p in self.PARAMS:
            arr = getattr(self, p)
            arr.reset(numpy.zeros((d, d), numpy.float32))
            self._fill(arr.mem, self.weights_filling,
                       self.weights_stddev, d, d)

    def export_config(self):
        cfg = {"heads": self.heads, "causal": self.causal}
        if self.block_size:  # v2 key — omit when unused so plain
            cfg["block_size"] = int(self.block_size)  # packages stay v1
        if self.attn_impl:  # an explicit core pin must survive export
            cfg["attn_impl"] = self.attn_impl
        return cfg

    def apply(self, params, x):
        dev = getattr(self, "device", None)
        return mha_apply(params, x, self.heads, self.causal,
                         self.block_size,
                         sp_mesh=getattr(self, "sp_mesh_", None),
                         attn_impl=getattr(self, "attn_impl", None),
                         backend=dev.jax_device.platform if dev else None)
