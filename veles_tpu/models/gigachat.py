"""The GigaChat3.5 decoder layer (``gigachat3_5``): multi-head latent
attention (MLA) or a Gated DeltaNet linear-attention operator as the
OPERATOR, then a dense or routed FFN, in the sandwich (``pre_post``)
form with zero-centred RMSNorms.  ``RMS0(x; w) = x / rms(x) * (1 + w)``
(eps ``norm_eps``); the residual stream is float32 inside the layer.

    h = x + RMS0(Op(RMS0(x; input_norm)); op_norm)
    y = h + RMS0(FFN(RMS0(h; ffn_norm)); ffn_out_norm)

``operator="mla"`` (H heads, N + R query/key width a head, latent width
C, value width V; u the normed input):

    c_q = RMS0(u W_dq; q_norm);  [q_nope | q_pe] = c_q W_uq
    [c_kv | k_pe] = u W_dkv;  c_kv <- RMS0(c_kv; kv_norm)
    q_pe, k_pe: YaRN rotary over interleaved pairs (2i, 2i+1)
    (:func:`yarn_rotary`); k_pe is ONE head shared by all H
    [k_nope | v] = c_kv W_ukv                       (N + V a head)
    score = (q_nope.k_nope + q_pe.k_pe) (N + R)^-1/2 mscale^2, causal,
    mscale = 0.1 ln(factor) + 1
    Op = (ctx * 2 sigmoid(u W_g)) W_o

Its cache is paged rows of ONE array a token, ``latent`` =
``[c_kv | k_pe]`` (C + R wide, in the compute dtype).  A prefill chunk
expands the rows it attends through ``W_ukv``
(``ops/paged_attention.latent_chunk_attend``); a decode step reads only
the latent rows, in absorbed form: ``q_lat = q_nope W_uk^T`` is scored
against ``c_kv``, ``q_pe`` against ``k_pe``, the context is taken in
latent space and then multiplied by ``W_uv``
(``ops/paged_attention.paged_latent_attention``, the Pallas call
``veles_mla_decode`` on the chip).

``operator="gdn"`` (Hk key heads, Hv value heads, width K):

    [q~ | k~ | v~] = u [W_q | W_k | W_v]  (Hk K, Hk K, Hv K wide), a
    depthwise causal conv of ``conv_kernel`` taps (``conv_taps``) over
    the three, then SiLU
    per head q <- q / |q|_2 * K^-1/2, k <- k / |k|_2 (eps 1e-6); value
    head j reads key head floor(j / (Hv / Hk))
    beta = sigmoid(u W_b), g = -exp(A_log) softplus(u W_a + dt_bias),
    alpha = exp(g): scalars a value head, float32
    S <- alpha S;  S <- S + beta k (x) (v - S^T k);  o = S^T q
    (``models/solar.delta_step`` with alpha over every key channel)
    o <- RMS0(o; o_norm) * 2 sigmoid(u W_z) a head;  Op = concat(o) W_o

Its cache is per-SLOT state (``cache_kind == "slot"``): ``conv``, the
last ``conv_kernel - 1`` rows of [q~ | k~ | v~] in the compute dtype,
and ``S`` [Hv, K, K], float32 whatever the compute dtype, which a decode
step on the chip advances in place through the Pallas call
``veles_delta_state`` (``ops/pallas_delta.py``).

FFN: every SwiGLU is clamped, ``E(u) = (silu(min(u w1, L)) *
clip(u w3, -L, L)) w2`` (L = ``swiglu_limit``).  ``ffn="dense"``: one
such of ``hidden``.  ``ffn="routed"``: ``s = sigmoid(u router)`` over
ALL ``n_experts``, the ``top_k`` largest of ``s + expert_bias`` chosen,
weighted by ``s`` there, renormalised, times ``routed_scaling_factor``,
of which the experts ``held`` here are computed
(``models/lfm2.routed_ffn``), plus one shared expert of
``shared_hidden``, ungated.

Named scopes on the step and chunk programs' ops:
``veles_gigachat_mla``, ``veles_gigachat_gdn_conv``,
``veles_gigachat_gdn_state``, ``veles_gigachat_held_experts``,
``veles_gigachat_shared_expert``.  ``models/gigachat_reference.py`` is
the plain reference of the same equations.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.models.lfm2 import (
    NormedTokenLogits, _dot, clamp_swiglu, rms_norm, routed_ffn)
from veles_tpu.models.nn_units import ForwardBase
from veles_tpu.models.solar import delta_step, pool_step


def rms0(x, weight, eps):
    """The zero-centred RMSNorm: x / rms(x) * (1 + weight), float32."""
    return rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast,
                  beta_slow):
    """YaRN's frequencies of ``dim / 2`` rotated pairs (numpy, float32):
    the plain ``theta^(-2i/dim)`` where a pair turns more than
    ``beta_fast`` times over ``original_max`` positions, divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    ramp between."""
    def dim_at(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (numpy.arange(0, dim, 2, dtype=numpy.float32)
                            / dim)
    ramp = numpy.clip((numpy.arange(dim // 2, dtype=numpy.float32) - low)
                      / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(
        numpy.float32)


def yarn_mscale(factor, mscale):
    """The attention factor of YaRN: 0.1 mscale ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_rotary(x, positions, inv_freq):
    """x [b, s, heads, R] at ``positions`` [b, s]: pair (2i, 2i+1)
    turned by ``positions * inv_freq[i]`` (float32 out)."""
    angle = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def swiglu(u, w1, w3, w2, limit):
    """The clamped SwiGLU on compute-dtype operands, float32 out."""
    a, b = clamp_swiglu(_dot(u, w1), _dot(u, w3), limit)
    return _dot(jax.nn.silu(a) * b, w2)


class GigaChatBlock(ForwardBase):
    """One GigaChat3.5 decoder layer, x [batch, seq, dim] -> the same."""

    SEQ_DIM1_INPUT = True
    causal = True
    MATMUL_PARAMS = ("wq_down", "wq_up", "wkv_down", "wkv_up", "wg",
                     "wq", "wk", "wv", "wa", "wb", "wz", "wo",
                     "ffn_w1", "ffn_w3", "ffn_w2",
                     "expert_w1", "expert_w3", "expert_w2",
                     "shared_w1", "shared_w3", "shared_w2")
    NORMS = ("input_norm", "op_norm", "ffn_norm", "ffn_out_norm")
    OPERATOR_PARAMS = {
        "mla": ("wq_down", "q_norm", "wq_up", "wkv_down", "kv_norm",
                "wkv_up", "wg", "wo"),
        "gdn": ("wq", "wk", "wv", "conv_taps", "wa", "wb", "A_log",
                "dt_bias", "wz", "o_norm", "wo")}
    FFN_PARAMS = {
        "dense": ("ffn_w1", "ffn_w3", "ffn_w2"),
        "routed": ("router", "expert_bias", "expert_w1", "expert_w3",
                   "expert_w2", "shared_w1", "shared_w3", "shared_w2")}

    def __init__(self, workflow, dim=None, operator="gdn", ffn="routed",
                 hidden=None, heads=64, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                 v_head_dim=128, linear_key_heads=32,
                 linear_value_heads=64, linear_head_dim=128,
                 conv_kernel=4, n_experts=0, top_k=0, held=None,
                 shared_hidden=None, norm_topk_prob=True,
                 routed_scaling_factor=1.0, swiglu_limit=10.0,
                 rope_theta=1e5, rope_factor=1.0,
                 rope_original_max=None, rope_beta_fast=32.0,
                 rope_beta_slow=1.0, mscale_all_dim=1.0, norm_eps=1e-6,
                 **kwargs):
        super(GigaChatBlock, self).__init__(workflow, include_bias=False,
                                            **kwargs)
        if operator not in self.OPERATOR_PARAMS \
                or ffn not in self.FFN_PARAMS:
            raise ValueError("operator is 'mla' or 'gdn', ffn 'dense' or "
                             "'routed'")
        if not dim or not hidden:
            raise ValueError("dim and hidden are required")
        self.dim, self.hidden = int(dim), int(hidden)
        self.operator, self.ffn = operator, ffn
        self.heads = int(heads)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), \
            int(kv_lora_rank)
        self.qk_nope_dim, self.qk_rope_dim = int(qk_nope_dim), \
            int(qk_rope_dim)
        self.v_head_dim = int(v_head_dim)
        self.linear_key_heads = int(linear_key_heads)
        self.linear_value_heads = int(linear_value_heads)
        self.linear_head_dim = int(linear_head_dim)
        self.conv_kernel = int(conv_kernel)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.held = (0, self.n_experts) if held is None \
            else (int(held[0]), int(held[1]))
        self.shared_hidden = int(shared_hidden or self.hidden)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.swiglu_limit = float(swiglu_limit)
        self.rope_theta, self.rope_factor = float(rope_theta), \
            float(rope_factor)
        self.rope_original_max = int(rope_original_max or 4096)
        self.rope_beta_fast = float(rope_beta_fast)
        self.rope_beta_slow = float(rope_beta_slow)
        self.mscale_all_dim = float(mscale_all_dim)
        self.norm_eps = float(norm_eps)
        if operator == "gdn" and \
                self.linear_value_heads % self.linear_key_heads:
            raise ValueError("gdn needs linear_key_heads dividing "
                             "linear_value_heads")
        if ffn == "routed":
            if not 0 < self.top_k <= self.n_experts:
                raise ValueError("the routed ffn needs 0 < top_k <= "
                                 "n_experts")
            first, count = self.held
            if first < 0 or count < 1 \
                    or first + count > self.n_experts:
                raise ValueError(
                    "held = (first, count) names experts [first, first "
                    "+ count) of the %d routed" % self.n_experts)
        #: what the serving cache keeps for this layer: one fixed state
        #: per slot, or paged rows of ONE latent array a token
        #: (serving/kv_slots.PagedKVCache)
        self.cache_kind = "slot" if operator == "gdn" else "paged"
        #: the slot state holds a matrix the delta rule advances, and
        #: whether the decode step this layer traced last advanced it
        #: through the Pallas call (``SolarBlock.delta_state``)
        self.delta_state = operator == "gdn"
        self.state_in_place = False
        if operator == "mla":
            #: the names of a paged layer's row arrays: not a K and V
            #: pair (serving/kv_slots.latent_units)
            self.cache_rows = ("latent",)
        #: a delta-rule chunk scans its positions, ~0.1 ms each beside the
        #: ~15 ms weight stream any chunk costs: 256 wide (widest_chunk)
        self.prefill_scans, self.prefill_scan_widest = operator == "gdn", 256
        self.PARAMS = self.NORMS + self.OPERATOR_PARAMS[operator] \
            + self.FFN_PARAMS[ffn]
        for p in self.PARAMS:
            setattr(self, p, Array())

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_dim

    def param_shapes(self):
        d, h, sh = self.dim, self.hidden, self.shared_hidden
        heads, nope, rope = self.heads, self.qk_nope_dim, self.qk_rope_dim
        hk, hv, k = (self.linear_key_heads, self.linear_value_heads,
                     self.linear_head_dim)
        held = self.held[1]
        mla = self.operator == "mla"
        shapes = {
            "input_norm": (d,), "op_norm": (d,), "ffn_norm": (d,),
            "ffn_out_norm": (d,),
            "wq_down": (d, self.q_lora_rank),
            "q_norm": (self.q_lora_rank,),
            "wq_up": (self.q_lora_rank, heads * (nope + rope)),
            "wkv_down": (d, self.latent_width),
            "kv_norm": (self.kv_lora_rank,),
            "wkv_up": (self.kv_lora_rank,
                       heads * (nope + self.v_head_dim)),
            "wg": (d, heads * self.v_head_dim),
            "wo": (heads * self.v_head_dim if mla else hv * k, d),
            "wq": (d, hk * k), "wk": (d, hk * k), "wv": (d, hv * k),
            "conv_taps": (self.conv_kernel, (2 * hk + hv) * k),
            "wa": (d, hv), "wb": (d, hv), "A_log": (hv,),
            "dt_bias": (hv,), "wz": (d, hv * k), "o_norm": (k,),
            "ffn_w1": (d, h), "ffn_w3": (d, h), "ffn_w2": (h, d),
            "router": (d, self.n_experts),
            "expert_bias": (self.n_experts,),
            "expert_w1": (held, d, h), "expert_w3": (held, d, h),
            "expert_w2": (held, h, d), "shared_w1": (d, sh),
            "shared_w3": (d, sh), "shared_w2": (sh, d)}
        return {name: shapes[name] for name in self.PARAMS}

    def fill_params(self):
        """Matrices by the unit's filling; the zero-centred norms 0 (a
        scale of 1); ``A_log`` = log U(1, 16) and ``dt_bias`` the inverse
        softplus of dt in logU(1e-3, 0.1), the family's convention; conv
        taps U(-1/2, 1/2); ``expert_bias`` 0."""
        for name, shape in self.param_shapes().items():
            arr = getattr(self, name)
            arr.reset(numpy.zeros(shape, numpy.float32))
            if name.endswith("_norm") or name == "expert_bias":
                continue
            if name == "A_log":
                self.prng.fill(arr.mem, 1.0, 16.0)
                arr.mem[...] = numpy.log(arr.mem)
            elif name == "dt_bias":
                self.prng.fill(arr.mem, numpy.log(1e-3), numpy.log(0.1))
                dt = numpy.exp(arr.mem)
                arr.mem[...] = dt + numpy.log(-numpy.expm1(-dt))
            elif name == "conv_taps":
                self.prng.fill(arr.mem, -0.5, 0.5)
            else:
                for w in (arr.mem if len(shape) == 3 else [arr.mem]):
                    self._fill(w, self.weights_filling,
                               self.weights_stddev, w.shape[0],
                               w.shape[-1])

    # -- the operators over a run of positions ---------------------------

    def _inv_freq(self):
        return yarn_inv_freq(self.qk_rope_dim, self.rope_theta,
                             self.rope_factor, self.rope_original_max,
                             self.rope_beta_fast, self.rope_beta_slow)

    def mla_scale(self):
        """The MLA softmax's scale: (N + R)^-1/2 mscale^2."""
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5 \
            * yarn_mscale(self.rope_factor, self.mscale_all_dim) ** 2

    def _mla_project(self, params, u, positions):
        """-> (q_nope [b, c, H, N], q_pe [b, c, H, R], the new latent
        rows [b, c, C + R]), float32."""
        b, c, _ = u.shape
        nope, lat = self.qk_nope_dim, self.kv_lora_rank
        inv = self._inv_freq()
        c_q = rms0(_dot(u, params["wq_down"]), params["q_norm"],
                   self.norm_eps)
        q = _dot(c_q, params["wq_up"]).reshape(b, c, self.heads, -1)
        kv = _dot(u, params["wkv_down"])
        c_kv = rms0(kv[..., :lat], params["kv_norm"], self.norm_eps)
        k_pe = yarn_rotary(kv[..., None, lat:], positions, inv)[:, :, 0]
        return q[..., :nope], yarn_rotary(q[..., nope:], positions, inv), \
            jnp.concatenate([c_kv, k_pe], axis=-1)

    def _gated_out(self, params, u, ctx):
        b, c = u.shape[:2]
        gate = 2.0 * jax.nn.sigmoid(_dot(u, params["wg"]))
        return _dot(ctx.reshape(b, c, -1) * gate, params["wo"])

    def _gdn(self, params, u, conv, S, lens=None, step=delta_step):
        """u [b, c, d] continuing ``conv`` [b, K - 1, (2 Hk + Hv) K],
        the last rows of [q~ | k~ | v~] before the run, and ``S``
        [b, Hv, K, K] -> (out [b, c, d], both states at each row's
        ``lens`` [b], default c).  A run of one position takes its state
        through ``step`` (``SolarBlock._kda``'s convention)."""
        from veles_tpu import dtypes
        b, c, _ = u.shape
        hk, hv, hd = (self.linear_key_heads, self.linear_value_heads,
                      self.linear_head_dim)
        taps_n = self.conv_kernel
        with jax.named_scope("veles_gigachat_gdn_conv"):
            # rounded to what the state holds, so a run that was cut in
            # chunks convolves the rows a whole run convolves
            new = jnp.concatenate(
                [_dot(u, params[w]) for w in ("wq", "wk", "wv")],
                axis=-1).astype(dtypes.compute_dtype())
            rows = jnp.concatenate([conv.astype(new.dtype), new], axis=1)
            taps = params["conv_taps"].astype(jnp.float32)
            mixed = jax.nn.silu(sum(
                taps[j] * rows[:, j:j + c].astype(jnp.float32)
                for j in range(taps_n)))
            ends = jnp.full((b,), c, jnp.int32) if lens is None else lens
            last = ends[:, None] + jnp.arange(taps_n - 1)[None, :]
            conv_out = jnp.take_along_axis(
                rows, last[..., None], axis=1).astype(conv.dtype)
        q = mixed[..., :hk * hd].reshape(b, c, hk, hd)
        k = mixed[..., hk * hd:2 * hk * hd].reshape(b, c, hk, hd)
        v = mixed[..., 2 * hk * hd:].reshape(b, c, hv, hd)
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
            * hd ** -0.5
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        # value head j reads key head j // (Hv / Hk)
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
        g = -jnp.exp(params["A_log"].astype(jnp.float32)) \
            * jax.nn.softplus(_dot(u, params["wa"])
                              + params["dt_bias"].astype(jnp.float32))
        alpha = jnp.broadcast_to(jnp.exp(g)[..., None], (b, c, hv, hd))
        beta = jax.nn.sigmoid(_dot(u, params["wb"]))
        with jax.named_scope("veles_gigachat_gdn_state"):
            if c == 1 and lens is None:
                S, o = step(S, q[:, 0], k[:, 0], v[:, 0], alpha[:, 0],
                            beta[:, 0])
                o = o[:, None]
            else:
                def position(S, at):
                    t, q, k, v, alpha, beta = at
                    new, o = delta_step(S, q, k, v, alpha, beta)
                    # rows past ``lens`` leave the state where it stood
                    return jnp.where((t < ends)[:, None, None, None],
                                     new, S), o
                S, o = jax.lax.scan(
                    position, S,
                    (jnp.arange(c),) + tuple(
                        jnp.moveaxis(x, 1, 0)
                        for x in (q, k, v, alpha, beta)))
                o = jnp.moveaxis(o, 0, 1)
        gate = 2.0 * jax.nn.sigmoid(_dot(u, params["wz"]))
        o = rms0(o, params["o_norm"], self.norm_eps).reshape(
            b, c, hv * hd) * gate
        return _dot(o, params["wo"]), conv_out, S

    def _tail(self, params, x, op_out, live=None):
        eps = self.norm_eps
        h = x.astype(jnp.float32) + rms0(op_out, params["op_norm"], eps)
        b, s, d = h.shape
        u = rms0(h, params["ffn_norm"], eps)
        if self.ffn == "dense":
            out, counts = swiglu(u, params["ffn_w1"], params["ffn_w3"],
                                 params["ffn_w2"], self.swiglu_limit), None
        else:
            with jax.named_scope("veles_gigachat_held_experts"):
                out, counts = routed_ffn(
                    params, u.reshape(b * s, d), self.top_k,
                    self.norm_topk_prob, self.routed_scaling_factor,
                    live=None if live is None else jnp.repeat(live, s),
                    held=self.held, swiglu_limit=self.swiglu_limit)
            with jax.named_scope("veles_gigachat_shared_expert"):
                out = out.reshape(b, s, d) + swiglu(
                    u, params["shared_w1"], params["shared_w3"],
                    params["shared_w2"], self.swiglu_limit)
        return h + rms0(out, params["ffn_out_norm"], eps), counts

    def _normed(self, params, x):
        return rms0(x, params["input_norm"], self.norm_eps)

    # -- roles -----------------------------------------------------------

    def init_cache(self, batch, max_len, dtype):
        """MLA: zeroed latent rows [batch, max_len, C + R].  GDN: the
        zero state whatever ``max_len``: the conv rows in ``dtype``, the
        matrix state in float32."""
        if self.operator == "gdn":
            hk, hv, k = (self.linear_key_heads, self.linear_value_heads,
                         self.linear_head_dim)
            return {"conv": jnp.zeros(
                (batch, self.conv_kernel - 1, (2 * hk + hv) * k), dtype),
                "S": jnp.zeros((batch, hv, k, k), jnp.float32)}
        return {"latent": jnp.zeros((batch, max_len, self.latent_width),
                                    dtype)}

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
        ``cache`` (TransformerBlock.apply_prefill_chunk's contract:
        latent rows at or past a row's ``chunk_lens`` are zeroed; both
        GDN states stop at it)."""
        from veles_tpu.ops.paged_attention import latent_chunk_attend
        b, c, _ = x.shape
        u = self._normed(params, x)
        if self.operator == "gdn":
            op, conv, S = self._gdn(params, u, cache["conv"], cache["S"],
                                    chunk_lens)
            return self._tail(params, x, op)[0], {"conv": conv, "S": S}
        with jax.named_scope("veles_gigachat_mla"):
            positions = offset + jnp.arange(c)[None, :] \
                + jnp.zeros((b, 1), jnp.int32)
            q_nope, q_pe, latent = self._mla_project(params, u, positions)
            ctx, rows = latent_chunk_attend(
                q_nope, q_pe, latent, cache, offset, chunk_lens,
                key_width, params["wkv_up"], self.mla_scale())
            op = self._gated_out(params, u, ctx)
        return self._tail(params, x, op)[0], rows

    def apply_step_paged(self, params, x, pos, tables, pool,
                         slots=None):
        """One position a row (the run of one) against the serving
        cache: x [B, 1, d], row n at ``pos[n]`` in slot ``slots[n]``
        (-1: a padding row, which reads and writes the trash row or
        block and counts nothing).  A routed layer's counts ride the
        returned pool under ``"moe"``."""
        from veles_tpu.ops.paged_attention import paged_latent_attention
        b = x.shape[0]
        live = jnp.ones((b,), bool) if slots is None else slots >= 0
        u = self._normed(params, x)
        if self.operator == "gdn":
            row = jnp.arange(b) if slots is None else jnp.where(
                live, slots, pool["S"].shape[0] - 1)
            step, in_place = pool_step(row, slots)
            op, conv, S = self._gdn(params, u, pool["conv"][row],
                                    pool["S"], step=step)
            self.state_in_place = in_place
            out = {"conv": pool["conv"].at[row].set(conv), "S": S}
        else:
            with jax.named_scope("veles_gigachat_mla"):
                q_nope, q_pe, latent = self._mla_project(
                    params, u, pos[:, None])
                latent_pool, ctx = paged_latent_attention(
                    q_nope[:, 0], q_pe[:, 0], latent[:, 0],
                    pool["latent"], tables, pos, params["wkv_up"],
                    self.mla_scale())
                op = self._gated_out(params, u, ctx[:, None])
            out = {"latent": latent_pool}
        y, counts = self._tail(params, x, op, live)
        if counts is not None:
            out["moe"] = counts
        return y, out

    def export_config(self):
        return {name: getattr(self, name) for name in (
            "dim", "operator", "ffn", "hidden", "heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_dim", "qk_rope_dim", "v_head_dim",
            "linear_key_heads", "linear_value_heads", "linear_head_dim",
            "conv_kernel", "n_experts", "top_k", "held", "shared_hidden",
            "norm_topk_prob", "routed_scaling_factor", "swiglu_limit",
            "rope_theta", "rope_factor", "rope_original_max",
            "rope_beta_fast", "rope_beta_slow", "mscale_all_dim",
            "norm_eps")}


class ZeroCenteredTokenLogits(NormedTokenLogits):
    """The zero-centred RMSNorm then per-token logits without a bias:
    [batch, seq, d] -> [batch, seq, vocab] float32 (``NormedTokenLogits``
    with ``RMS0``: its norm weights start at 0, a scale of 1)."""

    def fill_params(self):
        super(ZeroCenteredTokenLogits, self).fill_params()
        self.embedding_norm.mem[...] = 0.0

    def apply(self, params, x):
        return _dot(rms0(x, params["embedding_norm"], self.norm_eps),
                    params["weights"])
