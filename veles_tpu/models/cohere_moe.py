"""The Command A+ decoder layer (``cohere2_moe``): ONE LayerNorm feeding
grouped-query attention and a routed FFN side by side (the parallel
block), attention over a sliding window with interleaved rotary, or over
every position with no positions at all.  ``LN(x; g) = (x - mean(x)) /
sqrt(var(x) + eps) * g`` (weight only, no bias; eps ``norm_eps``); the
residual stream is float32 inside the layer.

    u  = LN(x; input_norm)
    x' = x + Attn(u) + F(u)

Attention (H query heads over G K/V heads of width D, u the normed
input, no bias, no QK norm):

    q = u W_q [H D],  k = u W_k [G D],  v = u W_v [G D]
    attention="sliding":  q, k <- rotary over interleaved pairs
    (2i, 2i+1) of every head's D, theta ``rope_theta``
    (``models/gigachat.yarn_rotary`` at the plain frequencies); query i
    sees key j iff j <= i and i - j < ``window`` (the query itself and
    the ``window - 1`` keys before it)
    attention="full":     no positions (NoPE); key j seen iff j <= i
    score = q_h . k_{h // (H / G)} / sqrt(D);  Attn = concat_h(softmax
    (score) v) W_o

FFN: ``s = sigmoid(u router)`` in float32 over ALL ``n_experts``, the
``top_k`` largest chosen (no selection bias), weighted by ``s`` there
and renormalised (``norm_topk_prob``); of those, the experts ``held``
here are computed (``models/lfm2.routed_ffn``): R = sum of the held
chosen ``g_e E_e(u)``, ``E(u) = (silu(u w1) * u w3) w2``.  Beside them
``n_shared`` shared experts of ``shared_hidden``, averaged:
``S = mean_i E^sh_i(u)``, and ``F = (R + S) / 2``: the reading this
repo takes of ``shared_expert_combination_strategy: "average"``, the
routed mixture and the shared experts' mean weighted alike.

The cache: a ``sliding`` layer keeps a per-slot RING of ``window`` K/V
rows (``cache_kind == "window"``, ``serving/kv_slots.PagedKVCache``):
position p at row p mod window, so a slot never needs more rows
(``ops/paged_attention.ring_chunk_attend``, ``ring_decode_attention``).
A ``full`` layer keeps paged K/V rows behind the block table.

The head (:class:`CohereTiedLogits`) is tied: ``logit_scale *
LN(x; final_norm) E^T`` over the embedding's own table, ONE array on the
device for both.

Named scopes on the step and chunk programs' ops:
``veles_cmda_window_attn``, ``veles_cmda_full_attn``,
``veles_cmda_held_experts``, ``veles_cmda_shared_experts``.
``models/cohere_moe_reference.py`` is the plain reference of the same
equations.
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.models.gigachat import yarn_rotary
from veles_tpu.models.lfm2 import _dot, routed_ffn
from veles_tpu.models.nn_units import ForwardBase


def layer_norm(x, weight, eps):
    """Mean-subtracted LayerNorm with a weight and no bias, float32."""
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def plain_inv_freq(dim, theta):
    """The rotary frequencies of ``dim / 2`` pairs, theta^(-2i/dim)."""
    return (1.0 / theta ** (numpy.arange(0, dim, 2, dtype=numpy.float32)
                            / dim)).astype(numpy.float32)


class CohereMoeBlock(ForwardBase):
    """One Command A+ decoder layer, x [batch, seq, dim] -> the same."""

    SEQ_DIM1_INPUT = True
    causal = True
    MATMUL_PARAMS = ("wq", "wk", "wv", "wo", "expert_w1", "expert_w3",
                     "expert_w2", "shared_w1", "shared_w3", "shared_w2")
    PARAMS = ("input_norm", "wq", "wk", "wv", "wo", "router",
              "expert_w1", "expert_w3", "expert_w2", "shared_w1",
              "shared_w3", "shared_w2")

    def __init__(self, workflow, dim=None, attention="sliding",
                 hidden=None, heads=None, kv_heads=None, head_dim=None,
                 window=4096, rope_theta=50000.0, n_experts=0, top_k=0,
                 held=None, n_shared=0, shared_hidden=None,
                 norm_topk_prob=True, norm_eps=1e-5, **kwargs):
        super(CohereMoeBlock, self).__init__(workflow, include_bias=False,
                                             **kwargs)
        if attention not in ("sliding", "full"):
            raise ValueError("attention is 'sliding' or 'full'")
        if not dim or not hidden or not heads or not head_dim:
            raise ValueError("dim, hidden, heads and head_dim are "
                             "required")
        self.dim, self.hidden = int(dim), int(hidden)
        self.attention = attention
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.kv_heads = int(kv_heads or self.heads)
        self.window = int(window)
        self.rope_theta = float(rope_theta)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.held = (0, self.n_experts) if held is None \
            else (int(held[0]), int(held[1]))
        self.n_shared = int(n_shared)
        self.shared_hidden = int(shared_hidden or self.hidden)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.norm_eps = float(norm_eps)
        if self.heads % self.kv_heads:
            raise ValueError("kv_heads must divide heads")
        if self.window < 1:
            raise ValueError("the window needs at least one position")
        if not 0 < self.top_k <= self.n_experts:
            raise ValueError("the routed ffn needs 0 < top_k <= "
                             "n_experts")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(
                "held = (first, count) names experts [first, first + "
                "count) of the %d routed" % self.n_experts)
        if self.n_shared < 1:
            raise ValueError("the block averages n_shared >= 1 shared "
                             "experts")
        #: what the serving cache keeps for this layer: a ring of
        #: ``window`` K/V rows a slot, or paged K/V rows
        #: (serving/kv_slots.PagedKVCache)
        self.cache_kind = "window" if attention == "sliding" else "paged"
        for p in self.PARAMS:
            setattr(self, p, Array())

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self):
        d, h, sh = self.dim, self.hidden, self.shared_hidden
        wide, kvd = self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        held, n = self.held[1], self.n_shared
        return {"input_norm": (d,), "wq": (d, wide), "wk": (d, kvd),
                "wv": (d, kvd), "wo": (wide, d),
                "router": (d, self.n_experts),
                "expert_w1": (held, d, h), "expert_w3": (held, d, h),
                "expert_w2": (held, h, d), "shared_w1": (n, d, sh),
                "shared_w3": (n, d, sh), "shared_w2": (n, sh, d)}

    def fill_params(self):
        """Matrices by the unit's filling, one a leading expert; the
        norm weight 1."""
        for name, shape in self.param_shapes().items():
            arr = getattr(self, name)
            arr.reset(numpy.zeros(shape, numpy.float32))
            if name == "input_norm":
                arr.mem[...] = 1.0
                continue
            for w in (arr.mem if len(shape) == 3 else [arr.mem]):
                self._fill(w, self.weights_filling, self.weights_stddev,
                           w.shape[0], w.shape[-1])

    # -- the layer over a run of positions -------------------------------

    def _qkv(self, params, u, positions):
        """-> q [b, c, H, D], k and v [b, c, G D], float32; rotary on a
        sliding layer's q and k."""
        b, c, _ = u.shape
        q = _dot(u, params["wq"]).reshape(b, c, self.heads, self.head_dim)
        k, v = _dot(u, params["wk"]), _dot(u, params["wv"])
        if self.attention == "sliding":
            inv = plain_inv_freq(self.head_dim, self.rope_theta)
            q = yarn_rotary(q, positions, inv)
            k = yarn_rotary(k.reshape(b, c, self.kv_heads, -1), positions,
                            inv).reshape(b, c, -1)
        return q, k, v

    def _scope(self):
        return "veles_cmda_window_attn" if self.attention == "sliding" \
            else "veles_cmda_full_attn"

    def _ffn(self, params, u, live=None):
        """F(u) = (R + S) / 2 -> ([b, s, d] float32, the routed counts)."""
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        b, s, d = u.shape
        with jax.named_scope("veles_cmda_held_experts"):
            routed, counts = routed_ffn(
                params, u.reshape(b * s, d), self.top_k,
                self.norm_topk_prob, 1.0,
                live=None if live is None else jnp.repeat(live, s),
                held=self.held)
        with jax.named_scope("veles_cmda_shared_experts"):
            uc = u.astype(cd)

            def each(w):
                return jnp.einsum("bsd,ndh->bsnh", uc, w.astype(cd),
                                  precision=dtypes.matmul_precision(),
                                  preferred_element_type=jnp.float32)
            hid = (jax.nn.silu(each(params["shared_w1"]))
                   * each(params["shared_w3"])).astype(cd)
            shared = jnp.einsum(
                "bsnh,nhd->bsd", hid, params["shared_w2"].astype(cd),
                precision=dtypes.matmul_precision(),
                preferred_element_type=jnp.float32) / self.n_shared
        return 0.5 * (routed.reshape(b, s, d) + shared), counts

    def _out(self, params, x, u, ctx, live=None):
        b, c = u.shape[:2]
        attn = _dot(ctx.reshape(b, c, -1), params["wo"])
        ffn, counts = self._ffn(params, u, live)
        return x.astype(jnp.float32) + attn + ffn, counts

    # -- roles -----------------------------------------------------------

    def init_cache(self, batch, max_len, dtype):
        """Zeroed K/V rows [batch, rows, G D]: ``max_len`` rows on a full
        layer, the ring's ``window`` on a sliding one whatever
        ``max_len``."""
        kvd = self.kv_heads * self.head_dim
        rows = self.window if self.attention == "sliding" else max_len
        return {"k": jnp.zeros((batch, rows, kvd), dtype),
                "v": jnp.zeros((batch, rows, kvd), dtype)}

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
        ``cache``: a full layer's staging rows
        (``TransformerBlock.apply_prefill_chunk``'s contract), a sliding
        layer's ring holding the positions before ``offset``; positions
        at or past a row's ``chunk_lens`` are written to neither."""
        from veles_tpu.ops.paged_attention import (
            ring_chunk_attend, staged_chunk_attend)
        b, c, _ = x.shape
        u = layer_norm(x, params["input_norm"], self.norm_eps)
        positions = offset + jnp.arange(c)[None, :] \
            + jnp.zeros((b, 1), jnp.int32)
        with jax.named_scope(self._scope()):
            q, k, v = self._qkv(params, u, positions)
            if self.attention == "sliding":
                ctx, rows = ring_chunk_attend(q, k, v, cache, offset,
                                              chunk_lens, self.kv_heads)
            else:
                ctx, rows = staged_chunk_attend(
                    q, k, v, cache, offset, chunk_lens, key_width,
                    self.kv_heads, by_head=True)
        return self._out(params, x, u, ctx)[0], rows

    def apply_step_paged(self, params, x, pos, tables, pool, slots=None,
                         depth=None):
        """One position a row against the serving cache: x [B, 1, d], row
        n at ``pos[n]`` in slot ``slots[n]`` (-1: a padding row, which
        reads and writes the trash ring or block and counts nothing).
        ``depth``: the positions the step's block bucket covers (a
        sliding layer reads ``min(depth, window)`` ring rows; default the
        whole ring).  The routed counts ride the returned pool under
        ``"moe"``."""
        from veles_tpu.ops.paged_attention import (
            paged_decode_attention, ring_decode_attention)
        b = x.shape[0]
        live = jnp.ones((b,), bool) if slots is None else slots >= 0
        u = layer_norm(x, params["input_norm"], self.norm_eps)
        with jax.named_scope(self._scope()):
            q, k, v = self._qkv(params, u, pos[:, None])
            if self.attention == "sliding":
                ring = pool["k"].shape[1]
                rows = jnp.arange(b) if slots is None else jnp.where(
                    live, slots, pool["k"].shape[0] - 1)
                pk, pv, ctx = ring_decode_attention(
                    q[:, 0], k[:, 0], v[:, 0], pool["k"], pool["v"], rows,
                    pos, self.kv_heads, min(int(depth or ring), ring))
            else:
                pk, pv, ctx = paged_decode_attention(
                    q.reshape(b, 1, -1), k, v, pool["k"], pool["v"],
                    tables, pos, self.heads, kv_heads=self.kv_heads)
        y, counts = self._out(params, x, u, ctx, live)
        return y, {"k": pk, "v": pv, "moe": counts}

    def export_config(self):
        return {name: getattr(self, name) for name in (
            "dim", "attention", "hidden", "heads", "kv_heads", "head_dim",
            "window", "rope_theta", "n_experts", "top_k", "held",
            "n_shared", "shared_hidden", "norm_topk_prob", "norm_eps")}


class CohereTiedLogits(ForwardBase):
    """LayerNorm then per-token logits over the embedding's own table,
    times ``logit_scale``: [batch, seq, d] -> [batch, seq, vocab]
    float32.  The table is the chain's first unit's ``weights``
    (``models/standard.make_forwards`` calls :meth:`tie`), ONE array
    for the embedding and the head: this unit holds the norm alone."""

    PARAMS = ("final_norm",)
    MATMUL_PARAMS = ("table",)
    SEQ_DIM1_INPUT = True
    DECODE_POINTWISE = True

    def __init__(self, workflow, vocab=None, logit_scale=1.0,
                 norm_eps=1e-5, **kwargs):
        super(CohereTiedLogits, self).__init__(
            workflow, include_bias=False, **kwargs)
        if vocab is None:
            raise ValueError("vocab is required")
        self.vocab = int(vocab)
        self.logit_scale = float(logit_scale)
        self.norm_eps = float(norm_eps)
        self.final_norm = Array()
        self.tied = None

    def tie(self, embedding):
        """Read the logits off ``embedding.weights`` [vocab, d]."""
        if getattr(embedding, "vocab", None) != self.vocab:
            raise ValueError("a tied head needs the embedding's %d rows"
                             % self.vocab)
        self.tied = embedding

    def param_arrays(self):
        if self.tied is None:
            raise ValueError("%s is not tied to an embedding" % self.name)
        return {"final_norm": self.final_norm,
                "table": self.tied.weights}

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.vocab,)

    def fill_params(self):
        self.final_norm.reset(numpy.ones((self.input.shape[-1],),
                                         numpy.float32))

    def apply(self, params, x):
        from veles_tpu import dtypes
        cd = dtypes.compute_dtype()
        u = layer_norm(x, params["final_norm"], self.norm_eps)
        return jnp.einsum("...d,vd->...v", u.astype(cd),
                          params["table"].astype(cd),
                          precision=dtypes.matmul_precision(),
                          preferred_element_type=jnp.float32) \
            * self.logit_scale

    def export_config(self):
        return {"vocab": self.vocab, "logit_scale": self.logit_scale,
                "norm_eps": self.norm_eps}
