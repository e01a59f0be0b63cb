"""The LFM2 decoder layer (``lfm2_moe``): a gated short convolution or
grouped-query attention as the OPERATOR, a gated three-matrix FFN that
is dense or routed over experts, RMSNorm before each, no bias anywhere.

    h = x + Op(RMS(x; operator_norm))
    y = h + FFN(RMS(h; ffn_norm))

``operator="conv"``: ``[B, C, X] = split3(u · conv_in)``, ``z = B * X``,
``c_t = sum_j conv_taps[j] * z[t - (K-1) + j]`` (depthwise, causal,
``K = conv_kernel`` taps), ``out = (C * c) · conv_out``.  Its state is
the last ``K`` rows of ``z``: fixed per request, whatever the length.
``operator="attention"``: ``heads`` query heads over ``kv_heads``
key/value heads of ``dim // heads``, RMSNorm over each head of q and k,
rotary positions (rotate-half) over the whole head, causal softmax.
``ffn="dense"``: ``w2 · (silu(w1 · u) * w3 · u)``.  ``ffn="routed"``:
scores ``s = sigmoid(u · router)`` in float32; the ``top_k`` experts of
``s + expert_bias`` are chosen, weighted by ``s`` there, renormalised;
the (token, expert) pairs are sorted by expert and the three expert
products are ONE grouped product each (``jax.lax.ragged_dot``), so the
work is the routed pairs', not tokens x experts.

The serving roles are written once each: the operator over a run of
positions that continues a state (:meth:`_conv`, the attention through
``ops.paged_attention.grouped_attend``), of which a decode step is the
run of one.  The residual stream is float32 inside the layer; matmul
operands are the compute dtype with float32 accumulation.

A conv layer's cache is per-SLOT state (``cache_kind == "slot"``), an
attention layer's is paged rows: ``serving/kv_slots.PagedKVCache`` asks.
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.models.nn_units import ForwardBase


def rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def _dot(x, w):
    """x [..., k] · w [k, n]: compute-dtype operands, float32 out."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    return jnp.einsum("...k,kn->...n", x.astype(cd), w.astype(cd),
                      precision=dtypes.matmul_precision(),
                      preferred_element_type=jnp.float32)


def rotary(x, positions, theta):
    """x [b, s, heads, hd] at ``positions`` [b, s]; rotate-half."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = positions.astype(jnp.float32)[..., None] * inv
    angle = jnp.concatenate([angle, angle], axis=-1)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(angle) \
        + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angle)


def clamp_swiglu(a, b, limit):
    """The two products of a SwiGLU clamped: ``min(a, limit)`` and
    ``clip(b, -limit, limit)``."""
    return jnp.minimum(a, limit), jnp.clip(b, -limit, limit)


def routed_ffn(params, u, top_k, norm_topk, scaling, live=None,
               held=None, swiglu_limit=None):
    """u [n, d] float32 -> ([n, d] float32, int32 counts of this call:
    1, routed pairs, experts touched, rows on the hottest expert).
    ``live`` [n] bool: rows that are not live take the experts of row
    0, so they open no expert of their own, and count nothing.

    ``held`` = (first, count): the router is as wide as the model's
    experts, ``expert_w*`` hold experts [first, first + count) alone,
    and only the chosen pairs that fall there are computed; the others
    enter no group of the grouped products and add nothing (an
    expert-parallel layer's share, without its exchange).  The pairs
    still count all the live rows' choices, the experts touched and the
    hottest rows are of the held experts, and a fifth count gives the
    pairs that fell on them.  None: every expert is here, four
    counts.

    ``swiglu_limit`` (None: no clamp) clamps each expert's two
    products before they meet (:func:`clamp_swiglu`).  ``params``
    without ``expert_bias``: the choice is of ``s`` alone."""
    from veles_tpu import dtypes
    cd = dtypes.compute_dtype()
    n, d = u.shape
    n_groups = params["expert_w1"].shape[0]
    s = jax.nn.sigmoid(jnp.matmul(
        u, params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    # a router with no selection bias (Command A+) holds no leaf for it
    bias = params.get("expert_bias")
    _, chosen = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), top_k)
    if live is None:
        live = jnp.ones((n,), bool)
    else:
        chosen = jnp.where(live[:, None], chosen, chosen[:1])
    gate = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-6)
    gate = gate * scaling
    if held is None:
        flat = chosen.reshape(-1)
    else:       # the absent experts' pairs sort past the last group
        here = (chosen >= held[0]) & (chosen < held[0] + held[1])
        flat = jnp.where(here, chosen - held[0], n_groups).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_groups,), jnp.int32).at[flat].add(
        1, mode="drop")
    rows = u.astype(cd)[order // top_k]

    def grouped(x, w):
        return jax.lax.ragged_dot(
            x, w.astype(cd), sizes,
            precision=dtypes.matmul_precision(),
            preferred_element_type=jnp.float32)
    a = grouped(rows, params["expert_w1"])
    b = grouped(rows, params["expert_w3"])
    if swiglu_limit is not None:
        a, b = clamp_swiglu(a, b, swiglu_limit)
    hid = (jax.nn.silu(a) * b).astype(cd)
    out = grouped(hid, params["expert_w2"])[jnp.argsort(order)]
    out = out.reshape(n, top_k, d)
    if held is not None:    # rows past the groups hold no product:
        out = jnp.where(here[..., None], out, 0.0)   # they add nothing
    out = (out * gate[..., None]).sum(axis=1)
    lives = jnp.repeat(live.astype(jnp.int32), top_k)
    counted = jnp.zeros((n_groups,), jnp.int32).at[flat].add(
        lives, mode="drop")
    counts = [jnp.int32(1), counted.sum() if held is None
              else lives.sum(), (counted > 0).sum().astype(jnp.int32),
              counted.max()]
    return out, jnp.stack(counts + ([] if held is None
                                    else [counted.sum()]))


class Lfm2Block(ForwardBase):
    """One LFM2 decoder layer, x [batch, seq, dim] -> the same."""

    SEQ_DIM1_INPUT = True
    causal = True
    MATMUL_PARAMS = ("conv_in", "conv_out", "wq", "wk", "wv", "wo",
                     "ffn_w1", "ffn_w3", "ffn_w2",
                     "expert_w1", "expert_w3", "expert_w2")
    OPERATOR_PARAMS = {
        "conv": ("conv_in", "conv_taps", "conv_out"),
        "attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    FFN_PARAMS = {
        "dense": ("ffn_w1", "ffn_w3", "ffn_w2"),
        "routed": ("router", "expert_bias", "expert_w1", "expert_w3",
                   "expert_w2")}

    def __init__(self, workflow, dim=None, operator="conv", ffn="dense",
                 hidden=None, heads=None, kv_heads=None, conv_kernel=3,
                 n_experts=0, top_k=0, norm_topk_prob=True,
                 routed_scaling_factor=1.0, rope_theta=1e6,
                 norm_eps=1e-5, **kwargs):
        super(Lfm2Block, self).__init__(workflow, include_bias=False,
                                        **kwargs)
        if operator not in self.OPERATOR_PARAMS \
                or ffn not in self.FFN_PARAMS:
            raise ValueError("operator is 'conv' or 'attention', ffn "
                             "'dense' or 'routed'")
        if not dim or not hidden:
            raise ValueError("dim and hidden are required")
        self.dim, self.hidden = int(dim), int(hidden)
        self.operator, self.ffn = operator, ffn
        self.heads = int(heads or 0)
        self.kv_heads = int(kv_heads or self.heads)
        self.conv_kernel = int(conv_kernel)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(norm_eps)
        if operator == "attention" and (
                not self.heads or self.dim % self.heads
                or self.heads % self.kv_heads):
            raise ValueError(
                "attention needs heads dividing dim %d and kv_heads "
                "dividing heads" % self.dim)
        if ffn == "routed" and not 0 < self.top_k <= self.n_experts:
            raise ValueError("routed ffn needs 0 < top_k <= n_experts")
        #: what the serving cache keeps for this layer: paged K/V rows
        #: or one fixed state per slot (serving/kv_slots.PagedKVCache)
        self.cache_kind = "slot" if operator == "conv" else "paged"
        self.PARAMS = ("operator_norm", "ffn_norm") \
            + self.OPERATOR_PARAMS[operator] + self.FFN_PARAMS[ffn]
        for p in self.PARAMS:
            setattr(self, p, Array())

    @property
    def head_dim(self):
        return self.dim // self.heads

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self):
        d, h, e = self.dim, self.hidden, self.n_experts
        kvd = self.kv_heads * self.head_dim if self.heads else 0
        hd = self.head_dim if self.heads else 0
        shapes = {
            "operator_norm": (d,), "ffn_norm": (d,),
            "conv_in": (d, 3 * d), "conv_taps": (self.conv_kernel, d),
            "conv_out": (d, d), "wq": (d, d), "wk": (d, kvd),
            "wv": (d, kvd), "wo": (d, d), "q_norm": (hd,),
            "k_norm": (hd,), "ffn_w1": (d, h), "ffn_w3": (d, h),
            "ffn_w2": (h, d), "router": (d, e), "expert_bias": (e,),
            "expert_w1": (e, d, h), "expert_w3": (e, d, h),
            "expert_w2": (e, h, d)}
        return {name: shapes[name] for name in self.PARAMS}

    def fill_params(self):
        for name, shape in self.param_shapes().items():
            arr = getattr(self, name)
            if name.endswith("_norm"):
                arr.reset(numpy.ones(shape, numpy.float32))
                continue
            arr.reset(numpy.zeros(shape, numpy.float32))
            if name == "expert_bias":
                continue
            for w in (arr.mem if len(shape) == 3 else [arr.mem]):
                self._fill(w, self.weights_filling, self.weights_stddev,
                           w.shape[0], w.shape[-1])

    # -- the operator over a run of positions -----------------------------

    def _conv(self, params, u, prev, lens=None):
        """u [b, c, d] continuing ``prev`` [b, K, d], the last K rows of
        z before the run -> (out [b, c, d], the last K rows of z at
        each row's ``lens`` [b], default c)."""
        from veles_tpu import dtypes
        b, c, d = u.shape
        k = self.conv_kernel
        gate_b, gate_c, x = jnp.split(_dot(u, params["conv_in"]), 3,
                                      axis=-1)
        # z is rounded to what the state holds, so a run that was cut
        # in chunks sees the rows a whole run sees
        z = (gate_b * x).astype(dtypes.compute_dtype())
        zz = jnp.concatenate([prev.astype(z.dtype), z], axis=1)
        taps = params["conv_taps"].astype(jnp.float32)
        conv = sum(taps[j] * zz[:, j + 1:j + 1 + c].astype(jnp.float32)
                   for j in range(k))
        out = _dot(gate_c * conv, params["conv_out"])
        ends = jnp.full((b,), c, jnp.int32) if lens is None else lens
        last = ends[:, None] + jnp.arange(k)[None, :]
        state = jnp.take_along_axis(zz, last[..., None], axis=1)
        return out, state.astype(prev.dtype)

    def _qkv(self, params, u, positions):
        b, s, _ = u.shape
        hd = self.head_dim

        def heads_of(name, n, norm):
            y = _dot(u, params[name]).reshape(b, s, n, hd)
            return rotary(rms_norm(y, params[norm], self.norm_eps),
                          positions, self.rope_theta)
        return (heads_of("wq", self.heads, "q_norm"),
                heads_of("wk", self.kv_heads, "k_norm"),
                _dot(u, params["wv"]))

    def _ffn(self, params, u, live=None):
        if self.ffn == "dense":
            return _dot(jax.nn.silu(_dot(u, params["ffn_w1"]))
                        * _dot(u, params["ffn_w3"]),
                        params["ffn_w2"]), None
        b, s, d = u.shape
        out, counts = routed_ffn(
            params, u.reshape(b * s, d), self.top_k,
            self.norm_topk_prob, self.routed_scaling_factor,
            live=None if live is None else jnp.repeat(live, s))
        return out.reshape(b, s, d), counts

    def _tail(self, params, x, op_out, live=None):
        h = x.astype(jnp.float32) + op_out
        out, counts = self._ffn(
            params, rms_norm(h, params["ffn_norm"], self.norm_eps),
            live)
        return h + out, counts

    def _normed(self, params, x):
        return rms_norm(x, params["operator_norm"], self.norm_eps)

    # -- roles -----------------------------------------------------------

    def init_cache(self, batch, max_len, dtype):
        """Attention: zeroed K/V rows [batch, max_len, kv_heads * hd].
        Conv: the zero state [batch, K, dim], whatever ``max_len``."""
        if self.operator == "conv":
            return {"conv": jnp.zeros(
                (batch, self.conv_kernel, self.dim), dtype)}
        kvd = self.kv_heads * self.head_dim
        return {"k": jnp.zeros((batch, max_len, kvd), dtype),
                "v": jnp.zeros((batch, max_len, kvd), dtype)}

    def apply(self, params, x):
        from veles_tpu import dtypes
        b, s, _ = x.shape
        y, _ = self.apply_prefill(
            params, x, self.init_cache(b, s, dtypes.compute_dtype()))
        return y

    def apply_prefill(self, params, x, cache, lens=None):
        """The whole prompt in one run: the chunk at offset 0."""
        return self.apply_prefill_chunk(
            params, x, cache, jnp.int32(0), chunk_lens=lens,
            key_width=x.shape[1])

    def apply_prefill_chunk(self, params, x, cache, offset,
                            chunk_lens=None, key_width=None):
        """x [b, C, d] at positions [offset, offset + C) continuing
        ``cache`` (TransformerBlock.apply_prefill_chunk's contract: K/V
        rows at or past a row's ``chunk_lens`` are zeroed; the conv
        state stops at it)."""
        from veles_tpu.ops.paged_attention import staged_chunk_attend
        b, c, _ = x.shape
        u = self._normed(params, x)
        if self.operator == "conv":
            op, state = self._conv(params, u, cache["conv"], chunk_lens)
            return self._tail(params, x, op)[0], {"conv": state}
        positions = offset + jnp.arange(c)[None, :] \
            + jnp.zeros((b, 1), jnp.int32)
        q, k_new, v_new = self._qkv(params, u, positions)
        ctx, rows = staged_chunk_attend(
            q, k_new.reshape(b, c, -1), v_new, cache, offset,
            chunk_lens, key_width, self.kv_heads)
        return self._tail(params, x, _dot(ctx, params["wo"]))[0], rows

    def apply_step_paged(self, params, x, pos, tables, pool,
                         slots=None):
        """One position a row (the run of one) against the serving
        cache: x [B, 1, d], row n at ``pos[n]`` in slot ``slots[n]``
        (-1: a padding row, which reads and writes the trash row or
        block and counts no routed pair).  A routed layer's counts
        ride the returned pool under ``"moe"``."""
        from veles_tpu.ops.paged_attention import paged_decode_attention
        b = x.shape[0]
        live = jnp.ones((b,), bool) if slots is None else slots >= 0
        u = self._normed(params, x)
        if self.operator == "conv":
            state = pool["conv"]
            row = jnp.arange(b) if slots is None else jnp.where(
                live, slots, state.shape[0] - 1)
            op, new = self._conv(params, u, state[row])
            out = {"conv": state.at[row].set(new)}
        else:
            q, k_new, v_new = self._qkv(params, u, pos[:, None])
            pk, pv, ctx = paged_decode_attention(
                q.reshape(b, 1, -1), k_new.reshape(b, 1, -1), v_new,
                pool["k"], pool["v"], tables, pos, self.heads,
                kv_heads=self.kv_heads)
            op = _dot(ctx, params["wo"])
            out = {"k": pk, "v": pv}
        y, counts = self._tail(params, x, op, live)
        if counts is not None:
            out["moe"] = counts
        return y, out

    def export_config(self):
        return {name: getattr(self, name) for name in (
            "dim", "operator", "ffn", "hidden", "heads", "kv_heads",
            "conv_kernel", "n_experts", "top_k", "norm_topk_prob",
            "routed_scaling_factor", "rope_theta", "norm_eps")}


class NormedTokenLogits(ForwardBase):
    """RMSNorm then per-token logits without a bias:
    [batch, seq, d] -> [batch, seq, vocab] float32."""

    PARAMS = ("embedding_norm", "weights")
    MATMUL_PARAMS = ("weights",)
    SEQ_DIM1_INPUT = True
    DECODE_POINTWISE = True

    def __init__(self, workflow, vocab=None, norm_eps=1e-5, **kwargs):
        super(NormedTokenLogits, self).__init__(
            workflow, include_bias=False, **kwargs)
        if vocab is None:
            raise ValueError("vocab is required")
        self.vocab = int(vocab)
        self.norm_eps = float(norm_eps)
        self.embedding_norm = Array()

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.vocab,)

    def fill_params(self):
        d = self.input.shape[-1]
        self.embedding_norm.reset(numpy.ones((d,), numpy.float32))
        self.weights.reset(numpy.zeros((d, self.vocab), numpy.float32))
        self._fill(self.weights.mem, self.weights_filling,
                   self.weights_stddev, d, self.vocab)

    def apply(self, params, x):
        return _dot(rms_norm(x, params["embedding_norm"], self.norm_eps),
                    params["weights"])

    def export_config(self):
        return {"vocab": self.vocab, "norm_eps": self.norm_eps}
