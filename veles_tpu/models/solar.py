"""The Solar-Open2 decoder layer (``solar_open2``): a gated delta-rule
linear-attention operator (KDA, arXiv:2510.26692) or grouped-query
attention WITHOUT positions as the OPERATOR, then a routed FFN of which
this chip holds a range of the experts, plus one shared expert.  RMSNorm
before each, eps 1e-5, no bias but the KDA output gate's.

    h = x + Op(RMS(x; input_norm))
    y = h + FFN(RMS(h; post_norm))

``operator="kda"`` (H heads, key and value width ``head_dim`` = K, ``u``
the normed input):

    q~ = u Wq, k~ = u Wk, v~ = u Wv            (each H*K wide)
    q_t = silu(sum_j taps_q[j] * q~[t - 3 + j])    depthwise causal conv
    of ``conv_kernel`` = 4 taps, no bias; likewise k, v (``conv_taps``
    holds the taps of [q | k | v] side by side)
    per head:  q <- q / |q|_2 * K^-1/2,  k <- k / |k|_2   (eps 1e-6
    inside the root)
    a = (u F_down) F_up                        (d -> low_rank -> H*K)
    g = -exp(A_log[h]) * softplus(a + dt_bias), alpha = exp(g) in
    (0, 1)^K a head: the decay, per key CHANNEL, float32
    beta = 2 * sigmoid(u Wb) a head, in (0, 2), float32
    S in R^{K x K} a head, float32, zero at the start of a request:
        S <- diag(alpha_t) S
        S <- S + beta_t k_t (x) (v_t - S^T k_t)
        o_t = S^T q_t
    o <- RMS(o; o_norm[K]) * sigmoid((u G_down) G_up + b_g) a head
    Op = concat_h(o) Wo

Its cache is per-SLOT state of two arrays (``cache_kind == "slot"``):
``conv``, the last 3 rows of [q~ | k~ | v~] in the compute dtype, and
``S``, float32 whatever the compute dtype.  On the chip a decode step
advances ``S`` in its pool in place, through the Pallas call
``veles_delta_state`` (``ops/pallas_delta.py``); elsewhere it gathers
the rows' states, runs :func:`delta_step` and scatters them back
(:func:`pool_step`).

``operator="gqa"``: ``heads`` query heads over ``kv_heads`` K/V heads of
``head_dim``, NO rotary and no positions, causal softmax at scale
K^-1/2, then ``ctx * sigmoid(u Wg)`` (H*K wide) before ``Wo``.  Its
cache is paged K/V rows.

FFN: ``s = sigmoid(u router)`` in float32 over ALL ``n_experts``; the
``top_k`` largest of ``s + expert_bias`` are chosen, weighted by ``s``
there, renormalised, times ``routed_scaling_factor``;
``out = sum_{chosen e} w_e E_e(u) + E_shared(u)``,
``E(u) = w2 (silu(w1 u) * w3 u)``.  ``held = (first, count)``: the layer
routes over all the experts, holds the weights of experts
``[first, first + count)`` alone and computes ``E_e`` only for chosen
``e`` among them: what the absent experts would add is left out (their
chips would add it), the shared expert is computed here in full
(``models/lfm2.routed_ffn``).

The serving roles are written once each, as ``Lfm2Block``'s: the
operator over a run of positions that continues a state, of which a
decode step is the run of one.  The residual stream is float32 inside
the layer; matmul operands are the compute dtype with float32
accumulation; the recurrence is float32 on the vector units (no matrix
unit's rounding).
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.models.lfm2 import _dot, rms_norm, routed_ffn
from veles_tpu.models.nn_units import ForwardBase
from veles_tpu.ops import pallas_delta


def delta_step(S, q, k, v, alpha, beta):
    """One position of the gated delta rule on every row and head:
    S [b, H, K, V]; q, k, alpha [b, H, K]; v [b, H, V]; beta [b, H]
    -> (S', o [b, H, V]), all float32."""
    S = alpha[..., None] * S
    pred = (S * k[..., None]).sum(axis=-2)                   # S^T k
    S = S + (beta[..., None] * k)[..., None] \
        * (v - pred)[..., None, :]
    return S, (S * q[..., None]).sum(axis=-2)                # S^T q


def pool_step(row, slots=None):
    """``delta_step`` as a decode step takes it over a slot pool: ->
    ``(step, in_place)``, ``step`` being ``(pool, q, k, v, alpha, beta)
    -> (pool', o)`` for the rows whose states lie in ``row`` [b] of the
    pool (``slots``: the same with -1 for a padding row, whose ``row`` is
    the trash).  On the chip ``step`` is the Pallas call, which reads and
    writes each live state once, in place (``ops/pallas_delta.py``), and
    ``in_place`` is True; elsewhere the rows' states are gathered,
    advanced and scattered back."""
    if pallas_delta.fused():
        return functools.partial(pallas_delta.step_call(),
                                 row if slots is None else slots), True

    def step(pool, *args):
        S, o = delta_step(pool[row], *args)
        return pool.at[row].set(S), o
    return step, False


class SolarBlock(ForwardBase):
    """One Solar-Open2 decoder layer, x [batch, seq, dim] -> the same."""

    SEQ_DIM1_INPUT = True
    causal = True
    MATMUL_PARAMS = ("wq", "wk", "wv", "wg", "wo", "decay_down",
                     "decay_up", "gate_down", "gate_up", "wb",
                     "expert_w1", "expert_w3", "expert_w2",
                     "shared_w1", "shared_w3", "shared_w2")
    OPERATOR_PARAMS = {
        "kda": ("wq", "wk", "wv", "wo", "conv_taps", "decay_down",
                "decay_up", "A_log", "dt_bias", "wb", "gate_down",
                "gate_up", "gate_bias", "o_norm"),
        "gqa": ("wq", "wk", "wv", "wg", "wo")}
    FFN_PARAMS = ("router", "expert_bias", "expert_w1", "expert_w3",
                  "expert_w2", "shared_w1", "shared_w3", "shared_w2")

    def __init__(self, workflow, dim=None, operator="kda", hidden=None,
                 heads=None, kv_heads=None, head_dim=None,
                 conv_kernel=4, low_rank=128, n_experts=0, top_k=0,
                 held=None, norm_topk_prob=True,
                 routed_scaling_factor=1.0, norm_eps=1e-5, **kwargs):
        super(SolarBlock, self).__init__(workflow, include_bias=False,
                                         **kwargs)
        if operator not in self.OPERATOR_PARAMS:
            raise ValueError("operator is 'kda' or 'gqa'")
        if not dim or not hidden or not heads or not head_dim:
            raise ValueError("dim, hidden, heads and head_dim are "
                             "required")
        self.dim, self.hidden = int(dim), int(hidden)
        self.operator = operator
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.kv_heads = int(kv_heads or self.heads)
        self.conv_kernel, self.low_rank = int(conv_kernel), int(low_rank)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        #: (first, count): the experts whose weights are here
        self.held = (0, self.n_experts) if held is None \
            else (int(held[0]), int(held[1]))
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_eps = float(norm_eps)
        if operator == "gqa" and self.heads % self.kv_heads:
            raise ValueError("gqa needs kv_heads dividing heads")
        if not 0 < self.top_k <= self.n_experts:
            raise ValueError("the routed ffn needs 0 < top_k <= "
                             "n_experts")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(
                "held = (first, count) names experts [first, first + "
                "count) of the %d routed" % self.n_experts)
        #: what the serving cache keeps for this layer: one fixed state
        #: per slot or paged K/V rows (serving/kv_slots.PagedKVCache)
        self.cache_kind = "slot" if operator == "kda" else "paged"
        #: the slot state holds a matrix the delta rule advances: a
        #: decode step on the chip advances it in place through the
        #: Pallas call (ops/pallas_delta.py)
        self.delta_state = operator == "kda"
        #: whether the decode step this layer traced last advanced its
        #: state through that call (the serving cache counts it)
        self.state_in_place = False
        #: a chunk of the delta rule is a scan over its positions
        #: (``_kda``), so its time grows with the chunk's width: the
        #: scheduler keeps such a chain's chunks at their narrowest
        #: (serving/scheduler.widest_chunk)
        self.prefill_scans = operator == "kda"
        self.PARAMS = ("input_norm", "post_norm") \
            + self.OPERATOR_PARAMS[operator] + self.FFN_PARAMS
        for p in self.PARAMS:
            setattr(self, p, Array())

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self):
        d, h, r = self.dim, self.hidden, self.low_rank
        wide = self.heads * self.head_dim
        kvd = self.kv_heads * self.head_dim if self.operator == "gqa" \
            else wide
        held = self.held[1]
        shapes = {
            "input_norm": (d,), "post_norm": (d,),
            "wq": (d, wide), "wk": (d, kvd), "wv": (d, kvd),
            "wg": (d, wide), "wo": (wide, d),
            "conv_taps": (self.conv_kernel, 3 * wide),
            "decay_down": (d, r), "decay_up": (r, wide),
            "A_log": (self.heads,), "dt_bias": (wide,),
            "wb": (d, self.heads), "gate_down": (d, r),
            "gate_up": (r, wide), "gate_bias": (wide,),
            "o_norm": (self.head_dim,),
            "router": (d, self.n_experts),
            "expert_bias": (self.n_experts,),
            "expert_w1": (held, d, h), "expert_w3": (held, d, h),
            "expert_w2": (held, h, d), "shared_w1": (d, h),
            "shared_w3": (d, h), "shared_w2": (h, d)}
        return {name: shapes[name] for name in self.PARAMS}

    def fill_params(self):
        """Matrices by the unit's filling; norm vectors 1; ``A_log`` =
        log U(1, 16) and ``dt_bias`` the inverse softplus of dt in
        logU(1e-3, 0.1), the family's convention; conv taps
        U(-1/2, 1/2); ``expert_bias`` and ``gate_bias`` 0."""
        for name, shape in self.param_shapes().items():
            arr = getattr(self, name)
            arr.reset(numpy.zeros(shape, numpy.float32))
            if name.endswith("_norm"):
                arr.mem[...] = 1.0
            elif name == "A_log":
                self.prng.fill(arr.mem, 1.0, 16.0)
                arr.mem[...] = numpy.log(arr.mem)
            elif name == "dt_bias":
                self.prng.fill(arr.mem, numpy.log(1e-3), numpy.log(0.1))
                dt = numpy.exp(arr.mem)
                arr.mem[...] = dt + numpy.log(-numpy.expm1(-dt))
            elif name == "conv_taps":
                self.prng.fill(arr.mem, -0.5, 0.5)
            elif name not in ("expert_bias", "gate_bias"):
                for w in (arr.mem if len(shape) == 3 else [arr.mem]):
                    self._fill(w, self.weights_filling,
                               self.weights_stddev, w.shape[0],
                               w.shape[-1])

    # -- the operators over a run of positions ---------------------------

    def _kda(self, params, u, conv, S, lens=None, step=delta_step):
        """u [b, c, d] continuing ``conv`` [b, K - 1, 3 H K], the last
        rows of [q~ | k~ | v~] before the run, and ``S`` [b, H, K, K]
        -> (out [b, c, d], both states at each row's ``lens`` [b],
        default c).  A run of one position takes its state through
        ``step`` (``delta_step``'s arguments and results: a decode step
        hands the whole pool and :func:`pool_step`)."""
        from veles_tpu import dtypes
        b, c, _ = u.shape
        heads, hd, taps_n = self.heads, self.head_dim, self.conv_kernel
        with jax.named_scope("veles_solar_kda_conv"):
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
        q, k, v = (x.reshape(b, c, heads, hd)
                   for x in jnp.split(mixed, 3, axis=-1))
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
            * hd ** -0.5
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        a = _dot(_dot(u, params["decay_down"]), params["decay_up"])
        alpha = jnp.exp(
            -jnp.exp(params["A_log"].astype(jnp.float32))[:, None]
            * jax.nn.softplus(
                a + params["dt_bias"].astype(jnp.float32)).reshape(
                    b, c, heads, hd))
        beta = 2.0 * jax.nn.sigmoid(_dot(u, params["wb"]))
        with jax.named_scope("veles_solar_kda_state"):
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
        gate = jax.nn.sigmoid(
            _dot(_dot(u, params["gate_down"]), params["gate_up"])
            + params["gate_bias"].astype(jnp.float32))
        o = rms_norm(o, params["o_norm"], self.norm_eps).reshape(
            b, c, heads * hd) * gate
        return _dot(o, params["wo"]), conv_out, S

    def _qkv(self, params, u):
        b, s, _ = u.shape
        return (_dot(u, params["wq"]).reshape(b, s, self.heads,
                                              self.head_dim),
                _dot(u, params["wk"]), _dot(u, params["wv"]))

    def _gated_out(self, params, u, ctx):
        with jax.named_scope("veles_solar_gqa"):
            return _dot(ctx * jax.nn.sigmoid(_dot(u, params["wg"])),
                        params["wo"])

    def _tail(self, params, x, op_out, live=None):
        h = x.astype(jnp.float32) + op_out
        b, s, d = h.shape
        u = rms_norm(h, params["post_norm"], self.norm_eps)
        with jax.named_scope("veles_solar_held_experts"):
            out, counts = routed_ffn(
                params, u.reshape(b * s, d), self.top_k,
                self.norm_topk_prob, self.routed_scaling_factor,
                live=None if live is None else jnp.repeat(live, s),
                held=self.held)
        with jax.named_scope("veles_solar_shared_expert"):
            shared = _dot(jax.nn.silu(_dot(u, params["shared_w1"]))
                          * _dot(u, params["shared_w3"]),
                          params["shared_w2"])
        return h + out.reshape(b, s, d) + shared, counts

    def _normed(self, params, x):
        return rms_norm(x, params["input_norm"], self.norm_eps)

    # -- roles -----------------------------------------------------------

    def init_cache(self, batch, max_len, dtype):
        """GQA: zeroed K/V rows [batch, max_len, kv_heads * head_dim].
        KDA: the zero state whatever ``max_len``: the conv rows in
        ``dtype``, the matrix state in float32."""
        if self.operator == "kda":
            wide = self.heads * self.head_dim
            return {"conv": jnp.zeros(
                (batch, self.conv_kernel - 1, 3 * wide), dtype),
                "S": jnp.zeros((batch, self.heads, self.head_dim,
                                self.head_dim), jnp.float32)}
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
        rows at or past a row's ``chunk_lens`` are zeroed; both KDA
        states stop at it)."""
        from veles_tpu.ops.paged_attention import staged_chunk_attend
        u = self._normed(params, x)
        if self.operator == "kda":
            op, conv, S = self._kda(params, u, cache["conv"],
                                    cache["S"], chunk_lens)
            return self._tail(params, x, op)[0], {"conv": conv, "S": S}
        ctx, rows = staged_chunk_attend(
            *self._qkv(params, u), cache, offset, chunk_lens, key_width,
            self.kv_heads)
        return self._tail(params, x,
                          self._gated_out(params, u, ctx))[0], rows

    def apply_step_paged(self, params, x, pos, tables, pool,
                         slots=None):
        """One position a row (the run of one) against the serving
        cache: x [B, 1, d], row n at ``pos[n]`` in slot ``slots[n]``
        (-1: a padding row, which reads and writes the trash row or
        block and counts nothing).  The routed layer's counts ride the
        returned pool under ``"moe"``."""
        from veles_tpu.ops.paged_attention import paged_decode_attention
        b = x.shape[0]
        live = jnp.ones((b,), bool) if slots is None else slots >= 0
        u = self._normed(params, x)
        if self.operator == "kda":
            row = jnp.arange(b) if slots is None else jnp.where(
                live, slots, pool["S"].shape[0] - 1)
            step, in_place = pool_step(row, slots)
            op, conv, S = self._kda(params, u, pool["conv"][row],
                                    pool["S"], step=step)
            self.state_in_place = in_place
            out = {"conv": pool["conv"].at[row].set(conv), "S": S}
        else:
            q, k_new, v_new = self._qkv(params, u)
            pk, pv, ctx = paged_decode_attention(
                q.reshape(b, 1, -1), k_new, v_new, pool["k"],
                pool["v"], tables, pos, self.heads,
                kv_heads=self.kv_heads)
            op = self._gated_out(params, u, ctx)
            out = {"k": pk, "v": pv}
        y, out["moe"] = self._tail(params, x, op, live)
        return y, out

    def export_config(self):
        return {name: getattr(self, name) for name in (
            "dim", "operator", "hidden", "heads", "kv_heads",
            "head_dim", "conv_kernel", "low_rank", "n_experts", "top_k",
            "held", "norm_topk_prob", "routed_scaling_factor",
            "norm_eps")}
