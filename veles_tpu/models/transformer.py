"""Transformer block — pre-LN causal attention + FFN with residuals,
as ONE forward unit (the trainer composes forwards linearly, so the
block keeps its residual adds internal; the unit graph stays
embedding → block × N → pool → head).

No reference analogue (sequence models never left the untested Znicz
submodule); this is the long-context-first-class stack the TPU rebuild
adds: the attention core is `ops.attention` (same math the
ring-attention sp path computes chip-locally), and the FFN can be a
top-k mixture of experts whose ``expert_*`` parameters shard over the
``ep`` mesh axis by the standard naming convention
(parallel/sharding.py).
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.models.nn_units import ForwardBase


def _dequant_dot(x, wq, scale, prec, ad):
    """Deferred-dequant matmul against a PRE-QUANTIZED int8
    checkpoint weight (``quantize_weights``): the int8 weight widens
    into the dot and the per-output-column f32 scale multiplies the
    accumulator.  Because the scale is a GLOBAL per-column constant
    (unlike the in-trace ``int8_decode`` epilogue, whose shard-local
    amax is layout-dependent), the dequant commutes with row-parallel
    partial sums — which is what lets int8 checkpoints serve under
    the tp mesh."""
    y = jnp.einsum("bsd,de->bse", x, wq.astype(x.dtype),
                   precision=prec, preferred_element_type=ad)
    return y * scale.astype(y.dtype)


def _layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


class TransformerBlock(ForwardBase):
    """x -> x + MHA(LN(x)) -> + FFN(LN(.)), x: [batch, seq, d].

    ``n_experts`` switches the FFN to a top-k MoE (dense einsum
    dispatch, expert-major params on the ``ep`` axis)."""

    #: minibatch dim 1 is a SEQUENCE dim for this unit — the
    #: trainer sp-shards data dim 1 only when a forward says so
    #: (ADVICE.md r4 #2: sp sharding is opt-in)
    SEQ_DIM1_INPUT = True

    BASE_PARAMS = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                   "ln2_scale", "ln2_bias")
    MATMUL_PARAMS = ("wq", "wk", "wv", "wo", "ffn_w1", "ffn_w2")

    def __init__(self, workflow, heads=4, hidden=None, causal=True,
                 n_experts=0, top_k=2, attn_block_size=None,
                 attn_impl=None, int8_decode=False, **kwargs):
        super(TransformerBlock, self).__init__(workflow,
                                               include_bias=True,
                                               **kwargs)
        self.heads = int(heads)
        self.hidden = hidden  # None -> 4*d at fill time
        self.causal = bool(causal)
        #: stream K/V blockwise for long sequences (ops/attention.py)
        self.attn_block_size = attn_block_size
        #: attention core override: "flash" | "blockwise" | "dense"
        #: (None = auto; models/attention.mha_apply)
        self.attn_impl = attn_impl
        #: int8 weight-only matmuls for the DECODE-side MLP and
        #: output projection (ops/gemm.int8_matmul — per-column
        #: scales fused into the store epilogue).  Decode steps only:
        #: training/prefill keep the policy matmul.  Weights quantize
        #: inside the traced step (frozen serving params fold to
        #: constants under jit)
        self.int8_decode = bool(int8_decode)
        #: int8 CHECKPOINT weights (quantize_weights): the matmul
        #: weights are STORED int8 with per-output-column f32 scales
        #: as extra params — weight HBM halves at rest and on-device,
        #: every decode/prefill path dispatches on the stored dtype
        self.weights_int8 = False
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        if self.n_experts and self.top_k > self.n_experts:
            raise ValueError("top_k %d > n_experts %d"
                             % (self.top_k, self.n_experts))
        if self.n_experts:
            self.PARAMS = self.BASE_PARAMS + (
                "gate", "expert_w1", "expert_b1", "expert_w2",
                "expert_b2")
        else:
            self.PARAMS = self.BASE_PARAMS + (
                "ffn_w1", "ffn_b1", "ffn_w2", "ffn_b2")
        for p in self.PARAMS:
            setattr(self, p, Array())

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def fill_params(self):
        d = self.input.shape[-1]
        if d % self.heads:
            raise ValueError("model dim %d not divisible by %d heads"
                             % (d, self.heads))
        h = int(self.hidden or 4 * d)
        self.hidden = h
        for name in ("ln1_scale", "ln2_scale"):
            getattr(self, name).reset(numpy.ones((d,), numpy.float32))
        for name in ("ln1_bias", "ln2_bias"):
            getattr(self, name).reset(numpy.zeros((d,), numpy.float32))
        for name in ("wq", "wk", "wv", "wo"):
            arr = getattr(self, name)
            arr.reset(numpy.zeros((d, d), numpy.float32))
            self._fill(arr.mem, self.weights_filling,
                       self.weights_stddev, d, d)
        if self.n_experts:
            e = self.n_experts
            self.gate.reset(numpy.zeros((d, e), numpy.float32))
            self._fill(self.gate.mem, self.weights_filling,
                       self.weights_stddev, d, e)
            self.expert_w1.reset(numpy.zeros((e, d, h), numpy.float32))
            self.expert_w2.reset(numpy.zeros((e, h, d), numpy.float32))
            for w, fi, fo in ((self.expert_w1.mem, d, h),
                              (self.expert_w2.mem, h, d)):
                for i in range(e):
                    self._fill(w[i], self.weights_filling,
                               self.weights_stddev, fi, fo)
            self.expert_b1.reset(numpy.zeros((e, h), numpy.float32))
            self.expert_b2.reset(numpy.zeros((e, d), numpy.float32))
        else:
            self.ffn_w1.reset(numpy.zeros((d, h), numpy.float32))
            self._fill(self.ffn_w1.mem, self.weights_filling,
                       self.weights_stddev, d, h)
            self.ffn_b1.reset(numpy.zeros((h,), numpy.float32))
            self.ffn_w2.reset(numpy.zeros((h, d), numpy.float32))
            self._fill(self.ffn_w2.mem, self.weights_filling,
                       self.weights_stddev, h, d)
            self.ffn_b2.reset(numpy.zeros((d,), numpy.float32))

    def compute_dtype_params(self):
        """None under ``int8_decode`` (the decode step quantizes from
        the float32 weight inside the trace) and none for an MoE
        block (its expert weights shard over ``ep``)."""
        if self.int8_decode or self.n_experts:
            return ()
        return super(TransformerBlock, self).compute_dtype_params()

    # -- tensor-parallel serving layout (serving/tp.py) -----------------

    def tp_shardable(self, tp):
        """True when this block's Megatron layout divides over ``tp``
        shards: heads, model dim and FFN hidden all divisible (the
        head-wise K/V pool split and the column/row weight splits
        must land on whole heads / whole columns).  MoE FFNs shard
        over ``ep``, not ``tp`` (they opt out here), and the int8
        weight-only decode path quantizes per column INSIDE the trace
        — its dequant epilogue does not commute with the row-parallel
        partial sums, so it stays single-chip."""
        tp = int(tp)
        if tp < 2:
            return False
        if self.n_experts or self.int8_decode:
            return False
        d = self.wq.mem.shape[0]
        return self.heads % tp == 0 and d % tp == 0 \
            and int(self.hidden or 4 * d) % tp == 0

    def tp_param_spec(self, name, tp):
        """Megatron-style spec for one parameter under a ``tp`` mesh
        axis, or None (replicate): wq/wk/wv and the FFN up-projection
        are COLUMN-parallel (each shard owns whole heads / hidden
        columns, so attention and the activation stay chip-local),
        wo and the FFN down-projection ROW-parallel (their outputs
        are the per-layer cross-chip reductions XLA inserts).  LN
        scales and the output-side biases replicate — they apply
        after the reduction."""
        from jax.sharding import PartitionSpec as P
        if not self.tp_shardable(tp):
            return None
        if name in ("wq", "wk", "wv", "ffn_w1"):
            return P(None, "tp")
        if name in ("wo", "ffn_w2"):
            return P("tp", None)
        if name == "ffn_b1":
            return P("tp")
        # int8-checkpoint dequant scales (quantize_weights): per
        # OUTPUT column, so they split with column-parallel weights
        # and replicate beside row-parallel ones (their outputs keep
        # the full model dim)
        if name in ("wq_scale", "wk_scale", "wv_scale",
                    "ffn_w1_scale"):
            return P("tp")
        return None

    # -- int8 weight checkpoints (snapshotter weights_dtype) ------------

    def quantize_weights(self):
        """Re-store this block's matmul weights in the int8 CHECKPOINT
        format: per-output-column symmetric absmax quantization
        (``ops/gemm.int8_weight_quantize`` — the same scales the
        in-trace decode epilogue computes), the int8 tensor REPLACING
        the f32 one in place and a ``{name}_scale`` f32 vector
        joining ``PARAMS`` beside it.  Weight bytes halve at rest, in
        the snapshot AND in device HBM — unlike ``int8_decode``,
        which re-quantizes from resident f32 weights inside the
        trace.  Every decode/prefill/verify path dispatches on the
        stored dtype (``_dequant_dot``), and the global per-column
        scales commute with the tp row-parallel partial sums, so
        quantized checkpoints still shard.  Idempotent; MoE blocks
        (expert-sharded weights) are not supported."""
        if self.n_experts:
            raise ValueError(
                "int8 weight checkpoints need the dense FFN (MoE "
                "expert weights shard over ep; not supported)")
        if getattr(self, "weights_int8", False):
            return
        from veles_tpu.ops import gemm
        names = ("wq", "wk", "wv", "wo", "ffn_w1", "ffn_w2")
        for name in names:
            arr = getattr(self, name)
            arr.map_read()
            wq, scale = gemm.int8_weight_quantize(
                jnp.asarray(arr.mem, jnp.float32))
            arr.reset(numpy.asarray(wq))
            sarr = Array(numpy.asarray(scale, numpy.float32))
            dev = getattr(self, "device", None)
            if dev is not None:
                sarr.initialize(dev)
            setattr(self, name + "_scale", sarr)
        self.PARAMS = tuple(self.PARAMS) \
            + tuple(n + "_scale" for n in names)
        self.weights_int8 = True

    def _mha(self, params, x):
        from veles_tpu.models.attention import mha_apply
        dev = getattr(self, "device", None)
        return mha_apply(
            {k: params[k] for k in ("wq", "wk", "wv", "wo")}, x,
            self.heads, self.causal, self.attn_block_size,
            sp_mesh=getattr(self, "sp_mesh_", None),
            attn_impl=getattr(self, "attn_impl", None),
            backend=dev.jax_device.platform if dev else None)

    def _w8_matmul(self, x, w):
        """Weight-only int8 matmul of a decode activation ``x``
        [b, s, d1] by ``w`` [d1, d2]: quantize per output column,
        accumulate int8 products, dequant fused in the epilogue
        (ops/gemm.py).  Returns [b, s, d2] f32."""
        from veles_tpu import dtypes
        from veles_tpu.ops import gemm
        b, s, d1 = x.shape
        wq, scale = gemm.int8_weight_quantize(w)
        dev = getattr(self, "device", None)
        out = gemm.int8_matmul(
            x.reshape(b * s, d1).astype(dtypes.compute_dtype()),
            wq, scale,
            backend=dev.jax_device.platform if dev else None)
        return out.reshape(b, s, -1)

    def _ffn(self, params, x, w8=False):
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        if self.n_experts:
            from veles_tpu.models.moe import moe_apply
            return moe_apply(params, x, self.top_k, "strict_relu")
        if w8:   # decode-side weight-only int8 (see int8_decode)
            h1 = self._w8_matmul(x, params["ffn_w1"])
            h1 = jnp.maximum(
                h1 + params["ffn_b1"].astype(jnp.float32),
                0.0).astype(cd)
            y = self._w8_matmul(h1, params["ffn_w2"])
            return (y + params["ffn_b2"].astype(
                jnp.float32)).astype(x.dtype)
        if params["ffn_w1"].dtype == jnp.int8:   # int8 checkpoint
            h1 = jnp.einsum("bsd,dh->bsh", x.astype(cd),
                            params["ffn_w1"].astype(cd),
                            preferred_element_type=jnp.float32) \
                * params["ffn_w1_scale"].astype(jnp.float32)
        else:
            h1 = jnp.einsum("bsd,dh->bsh", x.astype(cd),
                            params["ffn_w1"].astype(cd),
                            preferred_element_type=jnp.float32)
        h1 = jnp.maximum(
            h1 + params["ffn_b1"].astype(jnp.float32), 0.0).astype(cd)
        if params["ffn_w2"].dtype == jnp.int8:   # int8 checkpoint
            y = jnp.einsum("bsh,hd->bsd", h1,
                           params["ffn_w2"].astype(cd),
                           preferred_element_type=jnp.float32) \
                * params["ffn_w2_scale"].astype(jnp.float32)
        else:
            y = jnp.einsum("bsh,hd->bsd", h1,
                           params["ffn_w2"].astype(cd),
                           preferred_element_type=jnp.float32)
        return (y + params["ffn_b2"].astype(jnp.float32)).astype(x.dtype)

    def apply(self, params, x):
        h = x + self._mha(params, _layer_norm(
            x, params["ln1_scale"], params["ln1_bias"]))
        return h + self._ffn(params, _layer_norm(
            h, params["ln2_scale"], params["ln2_bias"]))

    # -- single-token decode (models/generate.py kv_cache path) ---------

    def init_cache(self, batch, max_len, dtype):
        """Zeroed K/V decode buffers, [batch, max_len, d] each (d from
        the filled ``wq``; rows are written by :meth:`apply_step`)."""
        d = self.wq.mem.shape[0]
        return {"k": jnp.zeros((batch, max_len, d), dtype),
                "v": jnp.zeros((batch, max_len, d), dtype)}

    def _qkv(self, params, x):
        """LN1 + q/k/v projections in the decode conventions (the
        projection dtypes apply_step documents — shared by the
        single-token, per-slot and batched-prefill steps so all three
        produce identical K/V rows)."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        ad = dtypes.accum_dtype()
        prec = dtypes.matmul_precision()
        ln = _layer_norm(x, params["ln1_scale"], params["ln1_bias"])

        def proj(name):
            w = params[name]
            if w.dtype == jnp.int8:   # int8 checkpoint weight
                y = _dequant_dot(ln.astype(cd), w,
                                 params[name + "_scale"], prec, ad)
            else:
                y = jnp.einsum("bsd,de->bse", ln.astype(cd),
                               w.astype(cd), precision=prec,
                               preferred_element_type=ad)
            return y.astype(cd)

        return proj("wq"), proj("wk"), proj("wv")

    def _attn_tail(self, params, x, o, w8=False):
        """Output projection + residual + FFN half over an attention
        context ``o`` [b, s, d] (the shared tail of every decode-step
        variant; the paged step computes ``o`` in
        ``ops.paged_attention``).  ``w8`` switches the projection and
        MLP to the int8 weight-only path (decode steps with
        ``int8_decode`` set)."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        ad = dtypes.accum_dtype()
        prec = dtypes.matmul_precision()
        if w8:
            attn = self._w8_matmul(o, params["wo"]).astype(x.dtype)
        elif params["wo"].dtype == jnp.int8:   # int8 checkpoint
            attn = _dequant_dot(o.astype(cd), params["wo"],
                                params["wo_scale"], prec,
                                ad).astype(x.dtype)
        else:
            attn = jnp.einsum("bsd,de->bse", o.astype(cd),
                              params["wo"].astype(cd), precision=prec,
                              preferred_element_type=ad).astype(x.dtype)
        y = x + attn
        return y + self._ffn(params, _layer_norm(
            y, params["ln2_scale"], params["ln2_bias"]), w8=w8)

    def _attn_out(self, params, x, probs, vh):
        """probs·V + the shared tail."""
        b, s, d = x.shape
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, vh).reshape(b, s, d)
        return self._attn_tail(params, x, o)

    def apply_prefill(self, params, x, cache, lens=None):
        """Batched prompt prefill: consume ALL of x [batch, P, d] in
        ONE pass, writing every position's K/V into cache rows
        [0, P) — the O(1)-compiled-steps replacement for scanning
        :meth:`apply_step` over the prompt.  Same projection/attention
        conventions as apply_step, so the cache rows and outputs match
        the per-token scan (f32).

        ``lens`` (optional [batch] ints, traced): ragged prompts —
        K/V rows at or past each row's length are ZEROED (exactly the
        rows a per-row sequential prefill would have left at the
        init_cache zeros), and output rows past the length are
        garbage the caller must not read.  Valid rows are unaffected:
        the causal mask keeps queries q < lens[n] away from the
        zeroed keys."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        b, p, d = x.shape
        h = self.heads
        hd = d // h
        q, k_new, v_new = self._qkv(params, x)
        if lens is not None:
            keep = (jnp.arange(p)[None, :] < lens[:, None])[..., None]
            k_new = jnp.where(keep, k_new, 0).astype(k_new.dtype)
            v_new = jnp.where(keep, v_new, 0).astype(v_new.dtype)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, 0))
        qh = q.reshape(b, p, h, hd)
        kh = k_new.astype(cd).reshape(b, p, h, hd)
        vh = v_new.astype(cd).reshape(b, p, h, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) \
            * (1.0 / jnp.sqrt(hd))
        mask = (jnp.arange(p)[None, :]
                <= jnp.arange(p)[:, None])[None, None]
        logits = jnp.where(mask, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return self._attn_out(params, x, probs, vh), \
            {"k": ck, "v": cv}

    def apply_prefill_chunk(self, params, x, cache, offset,
                            chunk_lens=None, key_width=None):
        """CHUNKED prefill continuation: consume x [b, C, d] — the
        prompt's positions [offset, offset+C) (``offset`` a traced
        scalar, a multiple of C) — writing the chunk's K/V into cache
        rows [offset, offset+C) and attending each query over cached
        keys [0, key_width) with the causal mask ``key ≤ offset + q``.
        Chunk-for-chunk the same math as :meth:`apply_prefill` (which
        is the offset-0, single-chunk special case), so running the
        chunks sequentially reproduces the one-shot cache rows and
        last-position logits.

        ``chunk_lens`` (optional [b] ints, traced): rows whose prompt
        ends inside this chunk — K/V rows at or past
        ``offset + chunk_lens[n]`` are ZEROED (matching the staging
        cache's init zeros) and output rows past the length are
        garbage the caller must not read.  ``key_width`` (static int,
        default the cache width) bounds the attended key range — the
        caller buckets it to a power of two ≥ offset + C so shallow
        chunks don't pay full-window attention."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        b, c, d = x.shape
        h = self.heads
        hd = d // h
        q, k_new, v_new = self._qkv(params, x)
        if chunk_lens is not None:
            keep = (jnp.arange(c)[None, :]
                    < chunk_lens[:, None])[..., None]
            k_new = jnp.where(keep, k_new, 0).astype(k_new.dtype)
            v_new = jnp.where(keep, v_new, 0).astype(v_new.dtype)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype),
            (jnp.int32(0), offset, jnp.int32(0)))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype),
            (jnp.int32(0), offset, jnp.int32(0)))
        kw = int(key_width or ck.shape[1])
        qh = q.reshape(b, c, h, hd)
        kh = ck[:, :kw].astype(cd).reshape(b, kw, h, hd)
        vh = cv[:, :kw].astype(cd).reshape(b, kw, h, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) \
            * (1.0 / jnp.sqrt(hd))
        mask = (jnp.arange(kw)[None, :]
                <= (offset + jnp.arange(c))[:, None])[None, None]
        logits = jnp.where(mask, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return self._attn_out(params, x, probs, vh), \
            {"k": ck, "v": cv}

    def init_block_pool(self, num_blocks, block_size, dtype,
                        kv_dtype="fp32"):
        """Zeroed paged K/V pools, [num_blocks, block_size, d] each —
        the block-granular counterpart of :meth:`init_cache` (see
        serving/kv_slots.PagedKVCache).  ``kv_dtype="int8"`` stores
        the pools as int8 with per-row f32 dequant scales
        ([num_blocks, block_size], keys ``k_scale``/``v_scale``)
        living beside them — zero scales make the trash block's
        garbage dequantize to exact 0.0."""
        base = self.init_cache(num_blocks, block_size, dtype)
        if kv_dtype == "fp32":
            return base
        if kv_dtype != "int8":
            raise ValueError("kv_dtype must be 'fp32' or 'int8'")
        return {
            "k": jnp.zeros(base["k"].shape, jnp.int8),
            "v": jnp.zeros(base["v"].shape, jnp.int8),
            "k_scale": jnp.zeros((num_blocks, block_size),
                                 jnp.float32),
            "v_scale": jnp.zeros((num_blocks, block_size),
                                 jnp.float32),
        }

    def _backend(self):
        dev = getattr(self, "device", None)
        return dev.jax_device.platform if dev else None

    def apply_step_paged(self, params, x, pos, tables, pool,
                         attend=None):
        """Decode ONE position PER ROW against a PAGED KV pool: x
        [batch, 1, d] with row n at sequence index ``pos[n]``, reading
        and writing through ``tables`` [batch, T] physical block ids
        (serving/kv_slots.PagedKVCache).  Row-for-row the same math as
        :meth:`apply_step` (its all-positions-equal special case over
        a dense cache) restricted to the gathered blocks — token
        parity with ``generate()`` is tested.  An INT8 pool
        (``k_scale`` beside the buffers) quantizes the new row on the
        scatter and dequantizes fused into the gather
        (ops/paged_attention.py q8 paths; the pallas kernel on
        accelerator targets).

        ``attend``: what stands in for
        ``ops.paged_attention.paged_decode_attention`` over fp32
        pools, same arguments and results (a tp step's per-shard
        form: ``ServingTP.decode_attention``)."""
        from veles_tpu.ops.paged_attention import (
            paged_decode_attention, paged_decode_attention_q8)
        q, k_new, v_new = self._qkv(params, x)
        w8 = self.int8_decode
        if "k_scale" in pool:
            pk, pv, sk, sv, o = paged_decode_attention_q8(
                q, k_new, v_new, pool["k"], pool["v"],
                pool["k_scale"], pool["v_scale"], tables, pos,
                self.heads, backend=self._backend())
            return self._attn_tail(params, x, o, w8=w8), \
                {"k": pk, "v": pv, "k_scale": sk, "v_scale": sv}
        pk, pv, o = (attend or paged_decode_attention)(
            q, k_new, v_new, pool["k"], pool["v"], tables, pos,
            self.heads)
        return self._attn_tail(params, x, o, w8=w8), \
            {"k": pk, "v": pv}

    def apply_step_paged_local(self, params, x, pos, tables, pool,
                               tp):
        """PER-SHARD decode step body for the collective-overlap tp
        path (``engine._make_paged_step_tp`` runs it under shard_map
        over the ``tp`` mesh axis): ``params`` are this shard's
        Megatron slices (wq/wk/wv/ffn_w1 column slices → local heads
        and hidden columns, wo/ffn_w2 row slices), ``pool`` this
        shard's head-wise K/V slice.  Identical math to
        :meth:`apply_step_paged` — the two GSPMD-implicit per-layer
        reductions become EXPLICIT ``tp_allreduce`` calls
        (serving/tp.py) the compiler can issue asynchronously while
        the pool writeback proceeds.  fp32 pools only (the int8
        per-row amax must span the full feature axis)."""
        from veles_tpu import dtypes
        from veles_tpu.ops.paged_attention import paged_decode_attention
        from veles_tpu.serving.tp import tp_allreduce
        cd = dtypes.compute_dtype()
        ad = dtypes.accum_dtype()
        prec = dtypes.matmul_precision()
        heads_local = self.heads // int(tp)
        q, k_new, v_new = self._qkv(params, x)
        pk, pv, o = paged_decode_attention(
            q, k_new, v_new, pool["k"], pool["v"], tables, pos,
            heads_local)
        # row-parallel output projection: the partial sum reduces
        # EXPLICITLY — issued before the residual/FFN consume it, so
        # the cross-chip hop can overlap the pool scatter above
        if params["wo"].dtype == jnp.int8:   # int8 checkpoint
            partial = _dequant_dot(o.astype(cd), params["wo"],
                                   params["wo_scale"], prec, ad)
        else:
            partial = jnp.einsum("bsd,de->bse", o.astype(cd),
                                  params["wo"].astype(cd),
                                  precision=prec,
                                  preferred_element_type=ad)
        attn = tp_allreduce(partial, "tp", int(tp)).astype(x.dtype)
        y = x + attn
        ln2 = _layer_norm(y, params["ln2_scale"], params["ln2_bias"])
        if params["ffn_w1"].dtype == jnp.int8:
            h1 = jnp.einsum("bsd,dh->bsh", ln2.astype(cd),
                            params["ffn_w1"].astype(cd),
                            preferred_element_type=jnp.float32) \
                * params["ffn_w1_scale"].astype(jnp.float32)
        else:
            h1 = jnp.einsum("bsd,dh->bsh", ln2.astype(cd),
                            params["ffn_w1"].astype(cd),
                            preferred_element_type=jnp.float32)
        h1 = jnp.maximum(
            h1 + params["ffn_b1"].astype(jnp.float32), 0.0).astype(cd)
        if params["ffn_w2"].dtype == jnp.int8:
            p2 = jnp.einsum("bsh,hd->bsd", h1,
                            params["ffn_w2"].astype(cd),
                            preferred_element_type=jnp.float32) \
                * params["ffn_w2_scale"].astype(jnp.float32)
        else:
            p2 = jnp.einsum("bsh,hd->bsd", h1,
                            params["ffn_w2"].astype(cd),
                            preferred_element_type=jnp.float32)
        ffn = tp_allreduce(p2, "tp", int(tp))
        out = y + (ffn + params["ffn_b2"].astype(
            jnp.float32)).astype(x.dtype)
        return out, {"k": pk, "v": pv}

    def apply_verify_paged(self, params, x, pos, lens, tables, pool):
        """Speculative-decoding VERIFY step: score a width-K1 token
        run per row — x [batch, K1, d], row n's position j at
        sequence index ``pos[n] + j``, ``lens`` [batch] marking how
        many positions are real (padding scatters to the trash
        block) — against the paged pool in ONE pass.  Position-for-
        position the arithmetic of :meth:`apply_step_paged` (which
        spells its one query a row as two plain products), so
        accepting the matched prefix of the scored run reproduces
        sequential decode.

        INT8 pools always take the fused q8 verify (quantizing
        scatter + dequant-fused attend); fp32 pools take the PR 9
        two-pass path unless ``root.common.serving.fused_verify`` is
        set — the fused single-pass variant is allclose, not
        bit-identical, so the parity baseline stays two-pass."""
        from veles_tpu.ops.paged_attention import (
            paged_verify_attention, paged_verify_attention_fused,
            paged_verify_attention_q8)
        q, k_new, v_new = self._qkv(params, x)
        w8 = self.int8_decode
        if "k_scale" in pool:
            pk, pv, sk, sv, o = paged_verify_attention_q8(
                q, k_new, v_new, pool["k"], pool["v"],
                pool["k_scale"], pool["v_scale"], tables, pos, lens,
                self.heads, backend=self._backend())
            return self._attn_tail(params, x, o, w8=w8), \
                {"k": pk, "v": pv, "k_scale": sk, "v_scale": sv}
        from veles_tpu.config import root
        if root.common.serving.get("fused_verify", False):
            pk, pv, o = paged_verify_attention_fused(
                q, k_new, v_new, pool["k"], pool["v"], tables, pos,
                lens, self.heads, backend=self._backend())
        else:
            pk, pv, o = paged_verify_attention(
                q, k_new, v_new, pool["k"], pool["v"], tables, pos,
                lens, self.heads)
        return self._attn_tail(params, x, o, w8=w8), \
            {"k": pk, "v": pv}

    def apply_step(self, params, x, pos, cache):
        """Decode ONE position: x [batch, 1, d] at sequence index
        ``pos`` (traced scalar); returns (y, cache') with this step's
        K/V written into the cache — O(max_len) work per token vs
        re-running :meth:`apply` over the whole buffer (O(seq²)).
        Exact for causal blocks: cache rows past ``pos`` hold zeros
        that the mask excludes.  Mirrors mha_apply's dense-core
        conventions (projection dtypes, 1/sqrt(hd) scaling, softmax
        over the key axis) so greedy decode is token-for-token
        identical in f32; the scores, softmax and sums are float32
        over compute-dtype operands, as in every paged decode and
        verify step (``ops.paged_attention.grouped_attend``)."""
        from veles_tpu.ops.paged_attention import grouped_attend
        b, _, d = x.shape
        h = self.heads
        q, k_new, v_new = self._qkv(params, x)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, pos, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, pos, 0))
        o = grouped_attend(q.reshape(b, 1, h, d // h), ck, cv,
                           jnp.full((b, 1), pos), h)
        return self._attn_tail(params, x, o), {"k": ck, "v": cv}

    def export_config(self):
        cfg = {"heads": self.heads, "hidden": int(self.hidden),
               "causal": self.causal, "n_experts": self.n_experts,
               "top_k": self.top_k}
        if self.attn_block_size:  # v2 key — omit when unused
            cfg["attn_block_size"] = int(self.attn_block_size)
        if self.attn_impl:  # an explicit core pin must survive export
            cfg["attn_impl"] = self.attn_impl
        if self.int8_decode:  # v2 key — omit when unused
            cfg["int8_decode"] = True
        if getattr(self, "weights_int8", False):  # v3 key — the
            # int8-checkpoint trace differs; the flag keys _arch_sig
            cfg["weights_int8"] = True
        return cfg


class MeanPoolSeq(ForwardBase):
    """[batch, seq, d] -> [batch, d] mean over the sequence axis."""

    PARAMS = ()

    def fill_params(self):
        pass

    def output_shape_for(self, input_shape):
        return (input_shape[0], input_shape[-1])

    def apply(self, params, x):
        return x.mean(axis=1)

    def export_config(self):
        return {}


class TokenProjection(ForwardBase):
    """Per-token logits head: [batch, seq, d] → [batch, seq, vocab]
    (the LM head — scored per position by EvaluatorNextToken; the
    pooled classifier head remains ``mean_pool_seq`` + softmax).
    With a ``tp`` mesh axis the vocab dim column-shards by the
    standard convention (parallel/sharding.py)."""

    PARAMS = ("weights", "bias")
    MATMUL_PARAMS = ("weights",)
    SEQ_DIM1_INPUT = True
    #: position-wise: safe to apply to a [batch, 1, d] decode step
    #: unchanged (models/generate.py kv_cache chain dispatch)
    DECODE_POINTWISE = True

    def __init__(self, workflow, vocab=None, **kwargs):
        super(TokenProjection, self).__init__(workflow,
                                              include_bias=True,
                                              **kwargs)
        if vocab is None:
            raise ValueError("vocab is required")
        self.vocab = int(vocab)

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.vocab,)

    def fill_params(self):
        d = self.input.shape[-1]
        self.weights.reset(numpy.zeros((d, self.vocab), numpy.float32))
        self._fill(self.weights.mem, self.weights_filling,
                   self.weights_stddev, d, self.vocab)
        self.bias.reset(numpy.zeros((self.vocab,), numpy.float32))

    def apply(self, params, x):
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        y = jnp.einsum("bsd,dv->bsv", x.astype(cd),
                       params["weights"].astype(cd),
                       precision=dtypes.matmul_precision(),
                       preferred_element_type=jnp.float32)
        # logits stay f32: the CE loss needs full precision and the
        # [b, s, vocab] tensor is the last thing the chain produces
        return y + params["bias"].astype(jnp.float32)

    def export_config(self):
        return {"vocab": self.vocab}
