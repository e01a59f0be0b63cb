"""The GigaChat3.5 decoder layer (``veles_tpu/models/gigachat.py``)
against the repo's plain reference (``models/gigachat_reference.py``) at
a tiny size on the CPU, through every role the scheduler uses: MLA of 4
heads (nope 8 + rope 8, latent 16, values 8) over a paged pool of latent
rows, Gated DeltaNet of 2 key heads under 4 value heads, a dense layer,
16 experts top-4 of which experts 4-9 are held with a shared expert;
4 layers (GDN dense, MLA, GDN, MLA)."""

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import dtypes
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.models import gigachat_reference as ref

pytestmark = pytest.mark.serving

DIM, VOCAB, WINDOW, BLOCK, CHUNK = 32, 40, 64, 4, 8
HEADS, Q_LORA, KV_LORA, NOPE, ROPE, V_DIM = 4, 16, 16, 8, 8, 8
HK, HV, K_DIM = 2, 4, 8
DENSE, EXPERT, EXPERTS, TOP_K, HELD = 24, 12, 16, 4, (4, 6)
LAYERS = [("gdn", "dense"), ("mla", "routed"), ("gdn", "routed"),
          ("mla", "routed")]
KINDS = [k for k, _ in LAYERS]
FFNS = [f for _, f in LAYERS]
#: YaRN over a short original context, so that within these 64 positions
#: it turns other pairs than the plain rotary does (at the published
#: 32,768 the two differ only past positions this size never reaches)
ROPE_CFG = dict(rope_theta=1e4, rope_factor=8.0, rope_original_max=64,
                rope_beta_fast=32.0, rope_beta_slow=1.0,
                mscale_all_dim=1.0)
CFG = dict(heads=HEADS, q_lora_rank=Q_LORA, kv_lora_rank=KV_LORA,
           qk_nope_dim=NOPE, qk_rope_dim=ROPE, v_head_dim=V_DIM,
           linear_key_heads=HK, linear_value_heads=HV,
           linear_head_dim=K_DIM, conv_kernel=4, top_k=TOP_K,
           held_first=HELD[0], held_count=HELD[1], norm_topk_prob=True,
           routed_scaling_factor=2.5, swiglu_limit=10.0, norm_eps=1e-6,
           **ROPE_CFG)
#: float32 program against the float32 reference: the two differ by the
#: order of float32 sums alone (a handful of ulps through 4 layers, the
#: absorbed decode's reassociation and up to 40 recurrent steps).  A
#: bfloat16 state, latent row or residual stream reads 85 times over it
#: or more
#: (``test_a_lower_precision_fails_the_tolerance``)
ATOL = 1e-4


@contextlib.contextmanager
def _float32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    try:
        yield
    finally:
        root.common.precision.compute_dtype = saved


@pytest.fixture
def f32():
    with _float32():
        yield


def _spec(layers=LAYERS, held=HELD, limit=10.0):
    spec = [dict(type="embedding", vocab=VOCAB, dim=DIM,
                 learned_positions=False)]
    spec += [dict(type="gigachat_block", dim=DIM, operator=op, ffn=ffn,
                  hidden=DENSE if ffn == "dense" else EXPERT,
                  shared_hidden=EXPERT, heads=HEADS, q_lora_rank=Q_LORA,
                  kv_lora_rank=KV_LORA, qk_nope_dim=NOPE,
                  qk_rope_dim=ROPE, v_head_dim=V_DIM,
                  linear_key_heads=HK, linear_value_heads=HV,
                  linear_head_dim=K_DIM, n_experts=EXPERTS, top_k=TOP_K,
                  held=held, routed_scaling_factor=2.5,
                  swiglu_limit=limit, **ROPE_CFG)
             for op, ffn in layers]
    return spec + [dict(type="zc_token_logits", vocab=VOCAB)]


def _chain(name, seed=0, **kwargs):
    """The tiny chain, filled by the units themselves, then norm vectors
    off 0 (a scale off 1), an ``expert_bias`` that differs from expert to
    expert by as much as the scores do and a decay strong enough to
    matter in 40 positions, so that none is an identity the comparison
    could not see."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    fw = make_forwards(AcceleratedWorkflow(None, name=name),
                       Array(numpy.zeros((2, WINDOW), numpy.int32)),
                       _spec(**kwargs))
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    rng = numpy.random.default_rng(seed)
    for u in fw[1:]:
        for n, a in u.param_arrays().items():
            if n.endswith("_norm"):
                a.mem[...] = 0.1 * rng.standard_normal(a.mem.shape)
            if n == "expert_bias":
                a.mem[...] = 0.4 * rng.standard_normal(a.mem.shape)
            if n == "dt_bias":
                a.mem[...] = rng.uniform(-3.0, 0.0, a.mem.shape)
    return fw


def _params(fw):
    return {i: {n: jnp.asarray(a.mem)
                for n, a in u.param_arrays().items()}
            for i, u in enumerate(fw)}


def _reference_logits(params, tokens, cfg=CFG, **kwargs):
    logits, _ = ref.forward_logits(
        [params[i] for i in range(len(params))], KINDS, FFNS, tokens, cfg,
        **kwargs)
    return numpy.asarray(logits)


@pytest.fixture(scope="module")
def chain():
    with _float32():
        fw = _chain("gigachat-tiny")
    return fw, _params(fw)


def test_full_forward_matches_the_reference(chain, f32):
    fw, params = chain
    toks = numpy.random.default_rng(1).integers(0, VOCAB, (2, 40))
    h = toks
    for i, u in enumerate(fw):
        h = u.apply(params[i], h)
    assert h.dtype == jnp.float32
    for row in range(2):
        numpy.testing.assert_allclose(
            numpy.asarray(h[row]), _reference_logits(params, toks[row]),
            atol=ATOL)


@pytest.mark.parametrize("layer", [1, 2, 3], ids=["gdn_dense", "mla",
                                                   "gdn_routed"])
def test_one_block_matches_the_reference_layer(chain, f32, layer):
    """One layer of each kind alone, on a stream of unit scale."""
    fw, params = chain
    x = numpy.random.default_rng(5).standard_normal((1, 24, DIM))
    got = fw[layer].apply(params[layer], jnp.asarray(x, jnp.float32))
    want, _, _, _ = ref.layer_apply(
        params[layer], jnp.asarray(x[0], jnp.float32), KINDS[layer - 1],
        FFNS[layer - 1], CFG)
    numpy.testing.assert_allclose(got[0], want, atol=2e-5)


def _prefilled(fw, params, prompt, chunked):
    """(staging caches, last logits) of one prompt: one-shot, or chunk
    by chunk of ``CHUNK``."""
    from veles_tpu.serving.prefill import prefill, prefill_chunk
    p_len = len(prompt)
    width = max(CHUNK, 1 << (p_len - 1).bit_length())
    if not chunked:
        padded = numpy.zeros((1, width), numpy.int32)
        padded[0, :p_len] = prompt
        return prefill(fw, padded, prompt_lens=[p_len], window=width,
                       params=params)
    caches = {i: u.init_cache(1, width, dtypes.compute_dtype())
              for i, u in enumerate(fw) if hasattr(u, "init_cache")}
    off = 0
    while off < p_len:
        piece = prompt[off:off + CHUNK]
        padded = numpy.zeros((1, CHUNK), numpy.int32)
        padded[0, :len(piece)] = piece
        caches, last = prefill_chunk(fw, padded, off, [len(piece)],
                                     caches, params=params)
        off += CHUNK
    return caches, last


@pytest.mark.parametrize("p_len", [19, 16, 5],
                         ids=["boundary_inside", "boundary_at_end",
                              "under_a_chunk"])
def test_chunked_prefill_equals_one_shot(chain, f32, p_len):
    fw, params = chain
    prompt = numpy.random.default_rng(p_len).integers(
        0, VOCAB, p_len).tolist()
    whole, last = _prefilled(fw, params, prompt, chunked=False)
    cut, last_cut = _prefilled(fw, params, prompt, chunked=True)
    numpy.testing.assert_allclose(last_cut, last, atol=ATOL)
    numpy.testing.assert_allclose(
        last[0], _reference_logits(params, prompt)[-1], atol=ATOL)
    for i in whole:
        for name in whole[i]:
            numpy.testing.assert_allclose(cut[i][name], whole[i][name],
                                          atol=ATOL, err_msg=name)
    # the latent layer's ONE row a token, zero past the prompt; the GDN
    # state two arrays, each of its own shape and dtype
    assert sorted(whole[2]) == ["latent"]
    assert whole[2]["latent"].shape[-1] == KV_LORA + ROPE
    assert not numpy.asarray(whole[2]["latent"][0, p_len:]).any()
    assert whole[1]["conv"].shape == (1, 3, (2 * HK + HV) * K_DIM)
    assert whole[1]["S"].shape == (1, HV, K_DIM, K_DIM)
    assert whole[1]["S"].dtype == jnp.float32


def _decode(fw, params, prompt, steps, between=lambda name, a: a):
    """One-shot prefill, the staging row inserted into slot 1, then
    ``steps`` decode steps through the units' paged step -> (the text,
    the logits of every position from the prompt's last on)."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    assert cache.state_units == {1: "gigachat_block1",
                                 3: "gigachat_block3"}
    assert cache.latent_units == {2: "gigachat_block2",
                                  4: "gigachat_block4"}
    cache.alloc(8)                        # slot 0 is another request's
    slot = cache.alloc(len(prompt) + steps)
    assert slot == 1
    rows, last = _prefilled(fw, params, prompt, chunked=False)
    cache.insert(slot, rows, len(prompt))
    text, got = list(prompt), [numpy.asarray(last[0])]
    tables = jnp.asarray(cache.table_rows([slot], 16))
    for _ in range(steps):
        text.append(int(got[-1].argmax()))
        h = jnp.asarray([[text[-1]]], jnp.int32)
        pos = jnp.asarray([len(text) - 1], jnp.int32)
        for i, u in enumerate(fw):
            if hasattr(u, "init_cache"):
                h, out = u.apply_step_paged(
                    params[i], h, pos, tables, cache.pools[i],
                    slots=jnp.asarray([slot], jnp.int32))
                if u.ffn == "routed":
                    assert out.pop("moe").shape == (5,)
                cache.pools[i] = {n: between(n, a)
                                  for n, a in out.items()}
            else:
                h = u.apply(params[i], h)
        got.append(numpy.asarray(h[0, 0]))
    return text, numpy.stack(got)


def test_prefill_then_paged_decode_steps_match_the_reference(chain, f32):
    fw, params = chain
    prompt = numpy.random.default_rng(7).integers(0, VOCAB, 11).tolist()
    text, got = _decode(fw, params, prompt, 24)
    want = _reference_logits(params, text)[len(prompt) - 1:]
    numpy.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("what", ["state_S_bfloat16", "latent_bfloat16",
                                  "residual_stream_bfloat16"])
def test_a_lower_precision_fails_the_tolerance(chain, f32, what):
    """The tolerance is tight enough to refuse the matrix state held in
    bfloat16 between steps, latent rows cached in bfloat16, and a
    residual stream rounded to bfloat16 between layers."""
    fw, params = chain
    rng = numpy.random.default_rng(7)
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
    if what != "residual_stream_bfloat16":
        name = what.split("_")[-2]
        prompt = rng.integers(0, VOCAB, 11).tolist()
        text, got = _decode(fw, params, prompt, 24,
                            lambda n, a: bf16(a) if n == name else a)
        want = _reference_logits(params, text)[len(prompt) - 1:]
    else:
        toks = rng.integers(0, VOCAB, (1, 40))
        h = toks
        for i, u in enumerate(fw):
            h = u.apply(params[i], h if i < 2 else bf16(h))
        got, want = numpy.asarray(h[0]), _reference_logits(params, toks[0])
    assert numpy.abs(got - want).max() > 50 * ATOL


# -- the two MLA paths and the rotary ----------------------------------------

def _latent_rows(rng, b, length):
    return jnp.asarray(rng.standard_normal((b, length, KV_LORA + ROPE)),
                       jnp.float32)


def test_absorbed_latent_decode_equals_the_expanded_form(chain, f32):
    """A decode step's absorbed attention over the paged latent rows
    (``paged_latent_attention``) gives the context the expanded form of
    a prefill chunk gives its last query over the same rows
    (``latent_chunk_attend``), per head."""
    from veles_tpu.ops.paged_attention import (
        latent_chunk_attend, paged_latent_attention)
    _, params = chain
    w = params[2]["wkv_up"]
    rng = numpy.random.default_rng(3)
    length = 13
    rows = _latent_rows(rng, 1, length)
    q_nope = jnp.asarray(rng.standard_normal((1, 1, HEADS, NOPE)),
                         jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((1, 1, HEADS, ROPE)),
                       jnp.float32)
    scale = 0.3
    # expanded: the staging row holds the first 12, the chunk writes the
    # 13th at offset 12 and attends over all 13
    staging = {"latent": jnp.zeros((1, 16, KV_LORA + ROPE), jnp.float32)
               .at[:, :length - 1].set(rows[:, :length - 1])}
    want, staged = latent_chunk_attend(
        q_nope, q_pe, rows[:, length - 1:], staging, jnp.int32(12), None,
        16, w, scale)
    # absorbed: the same rows in blocks 3, 1, 2, 5 of a pool, the 13th
    # written by the step itself
    pool = jnp.zeros((6, BLOCK, KV_LORA + ROPE), jnp.float32)
    tables = jnp.asarray([[3, 1, 2, 5]], jnp.int32)
    for t in range(length - 1):
        pool = pool.at[tables[0, t // BLOCK], t % BLOCK].set(rows[0, t])
    new_pool, got = paged_latent_attention(
        q_nope[:, 0], q_pe[:, 0], rows[:, length - 1], pool, tables,
        jnp.asarray([length - 1], jnp.int32), w, scale)
    numpy.testing.assert_allclose(got, want[:, 0], atol=2e-5)
    numpy.testing.assert_allclose(new_pool[5, 0], rows[0, length - 1])
    numpy.testing.assert_allclose(staged["latent"][0, :length], rows[0])


@pytest.mark.parametrize("per_chunk", [1, 3, 8])
def test_the_latent_kernel_equals_the_gathered_form(per_chunk):
    """The Pallas call (interpret mode here) against the jnp gather it
    stands in for, at ragged lengths: one row, a block boundary, rows
    reaching into a chunk the other rows skip."""
    from veles_tpu.ops.paged_attention import latent_decode_attend
    from veles_tpu.ops.pallas_latent import pallas_latent_attend
    rng = numpy.random.default_rng(per_chunk)
    pool = jnp.asarray(rng.standard_normal((20, BLOCK, 24)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, HEADS, 24)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, 20, (3, 7)), jnp.int32)
    lens = jnp.asarray([1, 8, 27], jnp.int32)
    got = pallas_latent_attend(q, pool, tables, lens, 0.3, 16,
                               per_chunk=per_chunk, interpret=True)
    with _float32():
        want = latent_decode_attend(q, pool, tables, lens, 0.3, 16)
    numpy.testing.assert_allclose(got, want, atol=2e-5)


def _numpy_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """YaRN's frequencies as its paper's code writes them (arXiv:
    2309.00071; the DeepSeek-V3 ``yarn_find_correction_range``)."""
    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extrapolation = 1.0 / base ** (numpy.arange(0, dim, 2) / dim)
    interpolation = extrapolation / factor
    mask = 1.0 - numpy.clip((numpy.arange(dim // 2) - low) / (high - low),
                            0, 1)
    return interpolation * (1 - mask) + extrapolation * mask


@pytest.mark.parametrize("dim", [8, 64])
def test_yarn_rotary_against_its_formulas(dim):
    """Frequencies, the attention factor and the turn of interleaved
    pairs against a direct numpy transcription: pair (2i, 2i+1) as the
    complex number x_2i + i x_2i+1 times exp(i p f_i); program and
    reference alike."""
    from veles_tpu.models import gigachat
    want = _numpy_yarn(dim, 1e5, 8.0, 32768, 32.0, 1.0)
    got = gigachat.yarn_inv_freq(dim, 1e5, 8.0, 32768, 32.0, 1.0)
    numpy.testing.assert_allclose(got, want, rtol=1e-6)
    numpy.testing.assert_allclose(
        ref.yarn_inv_freq(dim, 1e5, 8.0, 32768, 32.0, 1.0), want,
        rtol=1e-6)
    assert want[0] == 1.0 and want[-1] == pytest.approx(
        1e5 ** (-(dim - 2) / dim) / 8)
    assert gigachat.yarn_mscale(8.0, 1.0) == pytest.approx(
        0.1 * math.log(8) + 1) == pytest.approx(1.20794, abs=1e-5)
    rng = numpy.random.default_rng(dim)
    x = rng.standard_normal((1, 5, 3, dim)).astype(numpy.float32)
    positions = numpy.asarray([[0, 1, 7, 300, 5000]])
    turned = x[..., 0::2] + 1j * x[..., 1::2]
    turned = turned * numpy.exp(1j * positions[..., None, None] * want)
    expect = numpy.stack([turned.real, turned.imag], -1).reshape(x.shape)
    numpy.testing.assert_allclose(
        gigachat.yarn_rotary(jnp.asarray(x), jnp.asarray(positions),
                             got), expect, atol=1e-3)
    cfg = dict(CFG, qk_rope_dim=dim, rope_theta=1e5,
               rope_original_max=32768)
    numpy.testing.assert_allclose(
        ref.yarn_rotary(jnp.asarray(x[0]), jnp.asarray(positions[0]),
                        cfg), expect[0], atol=1e-3)


# -- the held share and the clamp --------------------------------------------

def _routed_layer(rng, n=12, d=16, h=8, e=16, gain=0.3):
    p = {"router": rng.standard_normal((d, e)) * 0.3,
         "expert_bias": rng.standard_normal((e,)) * 0.1,
         "expert_w1": rng.standard_normal((e, d, h)) * gain,
         "expert_w3": rng.standard_normal((e, d, h)) * gain,
         "expert_w2": rng.standard_normal((e, h, d)) * gain,
         "shared_w1": rng.standard_normal((d, h)) * gain,
         "shared_w3": rng.standard_normal((d, h)) * gain,
         "shared_w2": rng.standard_normal((h, d)) * gain}
    p = {name: jnp.asarray(a, jnp.float32) for name, a in p.items()}
    return p, jnp.asarray(rng.standard_normal((n, d)), jnp.float32)


def _share_of(p, first, count):
    return dict(p, **{w: p[w][first:first + count]
                      for w in ("expert_w1", "expert_w3", "expert_w2")})


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer(
        f32):
    """256 experts top-8, ONE held a share (the cell's 16 of 256 at the
    same ratio), scaled 2.5 and clamped at a limit that binds: the routed
    parts that the 16 x 16 shares give, summed, plus the shared expert
    ONCE, are what the uncut reference gives for the whole layer, and
    so says the reference of its own shares."""
    from veles_tpu.models import gigachat
    from veles_tpu.models.lfm2 import routed_ffn
    p, u = _routed_layer(numpy.random.default_rng(11), e=256, gain=1.0)
    limit = 2.0
    cfg = dict(CFG, held_first=0, held_count=256, top_k=8,
               swiglu_limit=limit)
    whole, _, clamped = ref.routed_ffn(p, u, cfg, "f32", None)
    assert int(clamped) > 0                     # the clamp binds here
    shared = gigachat.swiglu(u, p["shared_w1"], p["shared_w3"],
                             p["shared_w2"], limit)
    parts, ref_parts, pairs = 0, 0, 0
    for first in range(0, 256, 16):
        got, counts = routed_ffn(_share_of(p, first, 16), u, 8, True, 2.5,
                                 held=(first, 16), swiglu_limit=limit)
        want, _, _ = ref.routed_ffn(
            _share_of(p, first, 16), u,
            dict(cfg, held_first=first, held_count=16), "f32", None)
        numpy.testing.assert_allclose(got, want, atol=5e-5)
        parts, ref_parts = parts + got, ref_parts + want
        pairs += int(counts[4])
    assert pairs == 12 * 8             # every pair fell on ONE share
    numpy.testing.assert_allclose(parts + shared, whole + shared,
                                  atol=2e-4)
    numpy.testing.assert_allclose(ref_parts, whole, atol=2e-4)
    want_shared, _ = ref.swiglu(u, p["shared_w1"], p["shared_w3"],
                                p["shared_w2"], "f32", limit)
    numpy.testing.assert_allclose(shared, want_shared, atol=2e-5)


def test_routed_ffn_without_a_limit_is_the_program_it_was(f32):
    """``swiglu_limit=None`` (LFM2's, Solar's) traces the equations it
    traced before the option: those of a limit, less the two clamps;
    and a limit that never binds changes no number."""
    from veles_tpu.models.lfm2 import routed_ffn
    p, u = _routed_layer(numpy.random.default_rng(13))

    def eqns(limit):
        jaxpr = jax.make_jaxpr(lambda p, u: routed_ffn(
            p, u, TOP_K, True, 1.0, held=(2, 8),
            swiglu_limit=limit))(p, u)
        return [e.primitive.name for e in jaxpr.eqns]
    plain, clamped = eqns(None), eqns(10.0)
    # the limit adds ``min`` and ``jnp.clip`` (a nested jit) after the
    # two first grouped products, and nothing else
    assert "min" not in plain
    at = clamped.index("min")
    assert clamped[at + 1] == "jit"
    assert clamped[:at] + clamped[at + 2:] == plain
    a, _ = routed_ffn(p, u, TOP_K, True, 1.0, held=(2, 8))
    b, _ = routed_ffn(p, u, TOP_K, True, 1.0, held=(2, 8),
                      swiglu_limit=1e30)
    numpy.testing.assert_array_equal(numpy.asarray(a), numpy.asarray(b))


# -- through the scheduler ---------------------------------------------------

def _counters():
    from veles_tpu.telemetry import metrics
    out = {}
    for line in metrics.render_prometheus().splitlines():
        if line.startswith("veles_serving_") and "_total" in line:
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def _counter(moved, name):
    return sum(v for k, v in moved.items() if k.split("{")[0] == name)


@pytest.fixture(scope="module")
def served(chain):
    """Three requests of different lengths through the scheduler on TWO
    slots: the first two interleave in packed steps (the long prompt is
    chunked), the short one finishes and the third takes over its slot,
    its latent blocks and its state rows."""
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = chain
    rng = numpy.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (19, 5, 16)]
    steps = [30, 6, 24]
    with _float32():
        before = _counters()
        sched = InferenceScheduler(
            fw, max_slots=2, window=WINDOW, block_size=BLOCK,
            prefill_chunk=CHUNK, spec=False, prefix_cache=False,
            warm_buckets=False).start()
        try:
            futures = [sched.submit(p, n) for p, n in zip(prompts, steps)]
            out = [list(f.result(300)) for f in futures]
            snap = sched.metrics()
        finally:
            sched.close()
    moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()}
    tokens = [o[len(p):] if len(o) > n else o
              for o, p, n in zip(out, prompts, steps)]
    return prompts, steps, tokens, snap, moved


def _gaps(params, prompts, tokens, **kwargs):
    out = []
    for prompt, toks in zip(prompts, tokens):
        text = prompt + toks
        rows = _reference_logits(params, text[:-1],
                                 prompt_len=len(prompt),
                                 **kwargs)[len(prompt) - 1:]
        out += (rows.max(-1) - rows[numpy.arange(len(toks)),
                                    toks]).tolist()
    return numpy.asarray(out)


def test_served_streams_are_the_references_and_the_cache_says_so(
        chain, served, f32):
    _, params = chain
    prompts, steps, tokens, snap, moved = served
    assert [len(t) for t in tokens] == steps
    assert _gaps(params, prompts, tokens).max() < ATOL
    assert snap["prefix_cache"] is False and snap["spec"] is False
    # the delta-rule chunk names its own widest (widest_chunk)
    assert snap["prefill_chunk"] == CHUNK
    assert snap["prefill_widest"] == 256
    # two latent layers x 24 values x 4 B a token (float32 here)
    assert snap["kv_bytes_per_token"] == 2 * (KV_LORA + ROPE) * 4
    assert snap["state_bytes"]["kv"] == 2 * (2 * 16 + 1) * BLOCK \
        * (KV_LORA + ROPE) * 4
    assert snap["state_bytes"]["S"] == 2 * 3 * HV * K_DIM * K_DIM * 4
    assert snap["pools_in_place"] is True
    steps_n = _counter(moved, "veles_serving_steps_total")
    rows = _counter(moved, "veles_serving_slot_busy_steps_total")
    assert steps_n > 0
    assert _counter(moved, "veles_serving_state_rows_total") == 2 * rows
    # both GDN layers' states advanced by the delta rule, none of them
    # through the Pallas call off the chip
    assert _counter(moved, "veles_serving_delta_state_rows_total") \
        == 2 * rows
    assert _counter(moved, "veles_serving_delta_state_fused_total") == 0
    # every decode step a live row reads its whole length in each of
    # the two latent layers: prompt .. prompt + n - 1 rows, + 1 each
    want = sum(len(p) + j for p, n in zip(prompts, steps)
               for j in range(1, n))
    assert _counter(moved, "veles_serving_latent_rows_total") == 2 * want
    assert _counter(moved, "veles_serving_moe_layer_steps_total") \
        == 3 * steps_n
    held = _counter(moved, "veles_serving_moe_held_pairs_total")
    assert 0 < held < _counter(moved, "veles_serving_moe_pairs_total")
    assert _counter(moved, "veles_serving_pool_copies_total") == 0


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8_in_place",))
def test_planted_fault_reads_as_wrong(chain, served, f32, fault):
    """The comparison that decides the cell's ``correct``, at this size:
    the mean gap of the served tokens under a reference with ONE fault
    planted, over the same mean for the tokens the int8 control puts
    first.  Sound, the float32 program reads 0; every fault reads over
    the limit, and so does the control in the program's place."""
    _, params = chain
    prompts, _, tokens, _, _ = served
    low = []
    for prompt, toks in zip(prompts, tokens):
        text = (prompt + toks)[:-1]
        full = _reference_logits(params, text)[len(prompt) - 1:]
        coarse = _reference_logits(
            params, text, mode="int8")[len(prompt) - 1:].argmax(-1)
        low += (full.max(-1)
                - full[numpy.arange(len(toks)), coarse]).tolist()
    int8_mean = numpy.mean(low)
    assert int8_mean > 0
    assert _gaps(params, prompts, tokens).mean() / int8_mean < 0.01
    if fault == "int8_in_place":
        value = 1.0
    else:
        value = _gaps(params, prompts, tokens, fault=fault,
                      cfg=dict(CFG, prefill_chunk=CHUNK)).mean() \
            / int8_mean
    assert not value <= 0.5, value


# -- what a latent layer and per-slot state are not carried through ---------

REFUSALS = {
    "prefix_cache": dict(prefix_cache=True),
    "spec": dict(spec=True),
    "tp": dict(tp=2),
    "kv_int8": dict(kv_dtype="int8"),
    "export_import": dict(role="prefill"),
    "host_tier": dict(kv_host_bytes=1 << 20),
}


@pytest.mark.parametrize("option", sorted(REFUSALS))
def test_option_refused_for_a_latent_only_chain(option):
    """A chain of latent layers alone (no per-slot state) is refused the
    options that move K and V pairs, in its own words."""
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw = _chain("gigachat-mla-only", layers=[("mla", "dense")] * 2)
    with pytest.raises(ValueError, match="rows are not a K and V pair "
                       r"\(gigachat_block1, gigachat_block2\)"):
        InferenceScheduler(fw, max_slots=2, window=WINDOW,
                           block_size=BLOCK, prefill_chunk=CHUNK,
                           **REFUSALS[option])


def test_the_paged_cache_holds_a_latent_layer_beside_slot_state(chain):
    """Pools, the trash block, ``bytes_per_token`` and the refusals of
    the block moves, under the default (bfloat16) compute dtype: a
    latent row is 24 values = 48 B a token and layer (the cell's 576:
    1,152 B)."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, _ = chain
    assert dtypes.compute_dtype() == jnp.bfloat16
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW, block_size=BLOCK)
    assert cache.pools[2]["latent"].shape == (2 * 16 + 1, BLOCK, 24)
    assert cache.pools[2]["latent"].dtype == jnp.bfloat16
    assert cache.pools[1]["S"].dtype == jnp.float32
    assert cache.bytes_per_token() == 2 * 24 * 2
    for move in (lambda: cache.export_blocks([1]),
                 lambda: cache.import_blocks([1], {}),
                 lambda: cache.load_staging({}, [1])):
        with pytest.raises(ValueError, match="per-slot state"):
            move()
    mla_only = _chain("gigachat-mla-cache", layers=[("mla", "dense")])
    alone = PagedKVCache(mla_only, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    assert alone.bytes_per_token() == 24 * 2
    with pytest.raises(ValueError, match="not a K and V pair"):
        alone.export_blocks([1])
    with pytest.raises(ValueError, match="not a K and V pair"):
        PagedKVCache(mla_only, max_slots=2, window=WINDOW,
                     block_size=BLOCK, kv_dtype="int8")
    # insert, then release: the slot's blocks back on the free list,
    # the trash block never handed out
    slot = alone.alloc(9)
    rows = {1: {"latent": jnp.ones((1, 12, 24), jnp.bfloat16)}}
    alone.insert(slot, rows, 9)
    ids = alone.tables[slot, :3]
    assert 0 not in ids
    pool = numpy.asarray(alone.pools[1]["latent"], numpy.float32)
    assert (pool[ids] == 1).all() and not pool[0].any()
    alone.release(slot)
    alone.check()
    assert alone.free_blocks == alone.capacity_blocks
    # at the published widths: 512 + 64 values, 1,152 B a token
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wide = make_forwards(
        AcceleratedWorkflow(None, name="gigachat-wide"),
        Array(numpy.zeros((1, 16), numpy.int32)),
        [dict(type="gigachat_block", dim=7168, operator="mla",
              ffn="dense", hidden=16)])
    assert PagedKVCache(wide, max_slots=1, window=16,
                        block_size=16).bytes_per_token() == 1152


def test_padding_rows_leave_live_rows_alone(chain, f32):
    """A packed step of ONE live row in a bucket of 4: the padding rows
    (slot -1, a table of trash block 0) write the trash block and the
    trash state row, never a live one."""
    fw, params = chain
    rng = numpy.random.default_rng(2)
    unit, p = fw[2], params[2]
    pool = {"latent": jnp.asarray(rng.standard_normal((5, BLOCK, 24)),
                                  jnp.float32)}
    tables = jnp.asarray([[3, 4], [0, 0], [0, 0], [0, 0]], jnp.int32)
    x = jnp.ones((4, 1, DIM), jnp.float32)
    slots = jnp.asarray([1, -1, -1, -1], jnp.int32)
    _, out = unit.apply_step_paged(
        p, x, jnp.asarray([5, 0, 0, 0], jnp.int32), tables, pool,
        slots=slots)
    new, old = numpy.asarray(out["latent"]), numpy.asarray(pool["latent"])
    changed = numpy.argwhere((new != old).any(-1))
    assert {tuple(c) for c in changed} <= {(4, 1), (0, 0)}
    assert (4, 1) in {tuple(c) for c in changed}
    assert out["moe"][1] == TOP_K
