"""The looped decoder stack (``model_type`` ``ouro``; the LoopLM family,
"Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): ``layers`` decoder layers whose SAME weights are run
``passes`` times a token, with sandwich norms, the final norm after
every pass and a scalar exit gate a position.

    h = E[tokens]                                    (the unit before)
    for r in 0 .. R-1:                 # R = passes (total_ut_steps)
        for l in 0 .. L-1:             # L = layers, the same weights
            a = Attn_l(RMS(h; attn_in_norm_l))
                # heads of dim // heads, rotate-half rotary over the
                # whole head, causal softmax; the keys and values of
                # pass r, layer l are cache layer r*L + l: its own rows
            h = h + RMS(a; attn_out_norm_l)          # OUTPUT normed
            m = W2_l(silu(W1_l u) * W3_l u),  u = RMS(h; ffn_in_norm_l)
            h = h + RMS(m; ffn_out_norm_l)
        h = RMS(h; final_norm)         # after EVERY pass; the normed
                                       # stream is the next pass's input
        g_r = sigmoid(gate_w . h + gate_b)
    logits = W_head h                  # the unit after, no norm of its own
    p_r = g_r * prod_{j<r}(1 - g_j)  (r < R-1),  p_{R-1} = prod_{j<R-1}(1 - g_j)

The exit distribution ``p`` is REPORTED (a decode step's counts, below)
and never applied: every token runs all ``passes`` and takes the last
pass's stream.  No bias anywhere but the gate's; no QK-norm.

The whole stack is ONE unit: its parameters are stacked ``[L, ...]`` and
every serving role is one layer body under ``jax.lax.scan`` over the
layers inside a scan over the passes, so a compiled program holds one
layer however deep the stack and however often it is run.  The roles
are written once: a run of positions continuing a cache
(:meth:`apply_prefill_chunk`; ``apply`` and ``apply_prefill`` ARE that
at offset 0) and the run of one against the paged pools
(:meth:`apply_step_paged`), which are CARRIED through both scans and
scattered in place.

Its cache is ``passes * layers`` cache layers behind ONE block table
(``cache_kind == "stack"``): pools ``[R*L, blocks, block, dim]``,
staging ``[R*L, batch, width, dim]``; ``serving/kv_slots.PagedKVCache``
asks.  The residual stream is float32; matmul operands are the compute
dtype with float32 accumulation.
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.models.lfm2 import _dot, rms_norm, rotary
from veles_tpu.models.nn_units import ForwardBase

#: key of a decode step's counts in the pool the step returns
COUNTS = "stack"


def exit_distribution(gates):
    """gates [R, ...] in (0, 1) -> p [R, ...]: ``p_r = g_r * prod_{j<r}
    (1 - g_j)`` below the last pass, which takes what is left, so the
    R shares of a position sum to 1."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(gates * before)[:-1], before[-1:]], axis=0)


class OuroStack(ForwardBase):
    """The looped stack, x [batch, seq, dim] -> the same (float32)."""

    SEQ_DIM1_INPUT = True
    causal = True
    #: what the serving cache keeps for this unit: ``cache_layers``
    #: layers of paged K/V rows behind one block table
    cache_kind = "stack"
    NORMS = ("attn_in_norm", "attn_out_norm", "ffn_in_norm",
             "ffn_out_norm")
    MATMUL_PARAMS = ("wq", "wk", "wv", "wo", "ffn_w1", "ffn_w3",
                     "ffn_w2")
    #: leaves with a leading layer axis, scanned over
    STACKED = NORMS + MATMUL_PARAMS
    PARAMS = STACKED + ("final_norm", "gate_w", "gate_b")

    def __init__(self, workflow, dim=None, layers=None, passes=None,
                 heads=None, hidden=None, rope_theta=1e6, norm_eps=1e-6,
                 **kwargs):
        super(OuroStack, self).__init__(workflow, include_bias=False,
                                        **kwargs)
        if not (dim and layers and passes and heads and hidden):
            raise ValueError(
                "dim, layers, passes, heads and hidden are required")
        self.dim, self.hidden = int(dim), int(hidden)
        self.layers, self.passes = int(layers), int(passes)
        self.heads = int(heads)
        if self.dim % self.heads or (self.dim // self.heads) % 2:
            raise ValueError("heads must divide dim %d into even heads"
                             % self.dim)
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(norm_eps)
        for p in self.PARAMS:
            setattr(self, p, Array())

    @property
    def head_dim(self):
        return self.dim // self.heads

    @property
    def cache_layers(self):
        """K/V row pairs a cached token holds: one a layer application."""
        return self.passes * self.layers

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self):
        n, d, h = self.layers, self.dim, self.hidden
        shapes = {name: (n, d) for name in self.NORMS}
        shapes.update(wq=(n, d, d), wk=(n, d, d), wv=(n, d, d),
                      wo=(n, d, d), ffn_w1=(n, d, h), ffn_w3=(n, d, h),
                      ffn_w2=(n, h, d), final_norm=(d,), gate_w=(d,),
                      gate_b=(1,))
        return shapes

    def fill_params(self):
        for name, shape in self.param_shapes().items():
            arr = getattr(self, name)
            if name.endswith("_norm"):
                arr.reset(numpy.ones(shape, numpy.float32))
                continue
            arr.reset(numpy.zeros(shape, numpy.float32))
            if name == "gate_b":
                continue
            for w in (arr.mem if len(shape) == 3 else [arr.mem]):
                self._fill(w, self.weights_filling, self.weights_stddev,
                           w.shape[0], w.shape[-1])

    # -- one layer, and the two scans around it --------------------------

    def _layer(self, p, h, positions, attend):
        """One application of one layer: ``p`` its leaves, h [b, s, d]
        float32 at ``positions`` [b, s]; ``attend(q, k, v)`` -> (the
        context [b, s, d] of q [b, s, heads, hd] once k [b, s, d] and
        v [b, s, d] are in the cache, the cache).  -> (h', the
        cache)."""
        b, s, _ = h.shape
        eps = self.norm_eps
        with jax.named_scope("veles_ouro_layer"):
            u = rms_norm(h, p["attn_in_norm"], eps)

            def heads_of(name):
                # the barrier keeps the product a plain [rows, dim]
                # one: merged with the split into heads, the compiler
                # wants the whole stacked matrix transposed, a copy of
                # it on every call
                y = jax.lax.optimization_barrier(_dot(u, p[name]))
                return rotary(y.reshape(b, s, self.heads, self.head_dim),
                              positions, self.rope_theta)
            ctx, cache = attend(heads_of("wq"),
                                heads_of("wk").reshape(b, s, -1),
                                _dot(u, p["wv"]))
            h = h + rms_norm(_dot(ctx, p["wo"]), p["attn_out_norm"], eps)
            u = rms_norm(h, p["ffn_in_norm"], eps)
            m = _dot(jax.nn.silu(_dot(u, p["ffn_w1"]))
                     * _dot(u, p["ffn_w3"]), p["ffn_w2"])
            return h + rms_norm(m, p["ffn_out_norm"], eps), cache

    def _loop(self, params, x, positions, state, rows, attend):
        """The stack run ``passes`` times over x [b, s, d].  ``state``
        is carried through every layer application (the pools of a
        decode step), ``rows`` ([R*L, ...] leaves or None) is sliced
        one cache layer an application (the staging of a prefill);
        ``attend(state, row, index, q, k, v)`` -> (ctx, (state', row'))
        for cache layer ``index``.  -> (h, state', rows', gates
        [R, b, s] float32)."""
        n = self.layers
        stacked = {name: params[name] for name in self.STACKED}
        rows = jax.tree.map(
            lambda a: a.reshape((self.passes, n) + a.shape[1:]), rows)

        def one_layer(carry, per):
            p, row, index = per
            h, (st, row) = self._layer(
                p, carry[0], positions,
                lambda q, k, v: attend(carry[1], row, index, q, k, v))
            return (h, st), row

        def one_pass(carry, per):
            r, rows_r = per
            with jax.named_scope("veles_ouro_pass"):
                (h, st), rows_r = jax.lax.scan(
                    one_layer, carry,
                    (stacked, rows_r, r * n + jnp.arange(n)))
                h = rms_norm(h, params["final_norm"], self.norm_eps)
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,d->bs", h, params["gate_w"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
                    + params["gate_b"].astype(jnp.float32)[0])
            return (h, st), (rows_r, gate)
        (h, state), (rows, gates) = jax.lax.scan(
            one_pass, (x.astype(jnp.float32), state),
            (jnp.arange(self.passes), rows))
        rows = jax.tree.map(
            lambda a: a.reshape((self.cache_layers,) + a.shape[2:]),
            rows)
        return h, state, rows, gates

    # -- roles -----------------------------------------------------------

    def init_cache(self, batch, max_len, dtype):
        """Zeroed K/V rows of every cache layer:
        [passes * layers, batch, max_len, dim] each."""
        shape = (self.cache_layers, batch, max_len, self.dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def apply(self, params, x):
        return self.apply_with_exit(params, x)[0]

    def apply_with_exit(self, params, x):
        """-> (y [b, s, d], the exit distribution p [R, b, s])."""
        from veles_tpu import dtypes
        b, s, _ = x.shape
        y, _, gates = self._chunk(
            params, x, self.init_cache(b, s, dtypes.compute_dtype()),
            jnp.int32(0), None, s)
        return y, exit_distribution(gates)

    def apply_prefill(self, params, x, cache, lens=None):
        """The whole prompt in one run: the chunk at offset 0."""
        return self.apply_prefill_chunk(
            params, x, cache, jnp.int32(0), chunk_lens=lens,
            key_width=x.shape[1])

    def apply_prefill_chunk(self, params, x, cache, offset,
                            chunk_lens=None, key_width=None):
        """x [b, C, d] at positions [offset, offset + C) continuing
        ``cache`` (TransformerBlock.apply_prefill_chunk's contract: K/V
        rows at or past a row's ``chunk_lens`` are zeroed)."""
        y, cache, _ = self._chunk(params, x, cache, offset, chunk_lens,
                                  key_width)
        return y, cache

    def _chunk(self, params, x, cache, offset, chunk_lens, key_width):
        from veles_tpu.ops.paged_attention import grouped_attend
        b, c, _ = x.shape
        kw = int(key_width or cache["k"].shape[-2])
        positions = offset + jnp.arange(c)[None, :] \
            + jnp.zeros((b, 1), jnp.int32)
        keep = None if chunk_lens is None else (
            jnp.arange(c)[None, :] < chunk_lens[:, None])[..., None]
        at = (jnp.int32(0), offset, jnp.int32(0))

        def attend(state, row, index, q, k, v):
            if keep is not None:
                k, v = jnp.where(keep, k, 0), jnp.where(keep, v, 0)
            ck = jax.lax.dynamic_update_slice(
                row["k"], k.astype(row["k"].dtype), at)
            cv = jax.lax.dynamic_update_slice(
                row["v"], v.astype(row["v"].dtype), at)
            return grouped_attend(
                q, ck[:, :kw], cv[:, :kw], positions,
                self.heads), (state, {"k": ck, "v": cv})
        y, _, cache, gates = self._loop(params, x, positions, None,
                                        cache, attend)
        return y, cache, gates

    def apply_step_paged(self, params, x, pos, tables, pool,
                         slots=None):
        """One position a row (the run of one) against the serving
        cache: x [B, 1, d], row n at ``pos[n]`` (``slots[n]`` -1: a
        padding row, whose all-zero table reads and writes each cache
        layer's trash block and which counts nothing).  The pools are
        carried through the passes and the layers and scattered in
        place.  The step's counts ride the returned pool under
        ``"stack"``: float32 [passes run, live rows, the live rows'
        exit mass of each pass]."""
        from veles_tpu.ops.paged_attention import paged_decode_attention
        b = x.shape[0]
        lead = pool["k"].shape[:2]
        blocks = lead[1]

        def attend(state, _, index, q, k, v):
            # cache layer ``index`` is blocks [index * blocks, ...) of
            # the pools seen flat: the table's ids shifted there
            pk, pv, ctx = paged_decode_attention(
                q.reshape(b, 1, -1), k, v, state[0], state[1],
                tables + index * blocks, pos, self.heads,
                kv_heads=self.heads)
            return ctx, ((pk, pv), None)
        flat = (lead[0] * blocks,) + pool["k"].shape[2:]
        y, (pk, pv), _, gates = self._loop(
            params, x, pos[:, None],
            (pool["k"].reshape(flat), pool["v"].reshape(flat)), None,
            attend)
        live = jnp.ones((b,), bool) if slots is None else slots >= 0
        mass = (exit_distribution(gates[:, :, 0])
                * live[None, :]).sum(axis=1)
        counts = jnp.concatenate([
            jnp.stack([jnp.float32(self.passes),
                       live.sum().astype(jnp.float32)]), mass])
        return y, {"k": pk.reshape(pool["k"].shape),
                   "v": pv.reshape(pool["v"].shape), COUNTS: counts}

    def export_config(self):
        return {name: getattr(self, name) for name in (
            "dim", "layers", "passes", "heads", "hidden", "rope_theta",
            "norm_eps")}


class PlainTokenLogits(ForwardBase):
    """Per-token logits with neither a norm nor a bias of its own (the
    stack norms its last pass itself):
    [batch, seq, d] -> [batch, seq, vocab] float32."""

    PARAMS = ("weights",)
    MATMUL_PARAMS = ("weights",)
    SEQ_DIM1_INPUT = True
    DECODE_POINTWISE = True

    def __init__(self, workflow, vocab=None, **kwargs):
        super(PlainTokenLogits, self).__init__(
            workflow, include_bias=False, **kwargs)
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

    def apply(self, params, x):
        return _dot(x, params["weights"])

    def export_config(self):
        return {"vocab": self.vocab}
