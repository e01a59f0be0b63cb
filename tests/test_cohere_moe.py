"""The Command A+ decoder layer (``veles_tpu/models/cohere_moe.py``)
against the repo's plain reference (``models/cohere_moe_reference.py``)
at a tiny size on the CPU, through every role the scheduler uses: d 64,
8 query heads over 2 K/V heads of 8, a window of 8 positions, blocks of
4, two periods (sliding, sliding, sliding, full) x 2, 8 experts top-2 of
which experts 2-5 are held, 2 shared experts averaged, a tied head over
a table of 32 rows."""

import contextlib

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import dtypes
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.models import cohere_moe_reference as ref

pytestmark = pytest.mark.serving

DIM, VOCAB, WINDOW, BLOCK, CHUNK, SLOT_WINDOW = 64, 32, 8, 4, 4, 64
HEADS, KV_HEADS, HEAD_DIM = 8, 2, 8
EXPERT, EXPERTS, TOP_K, HELD, SHARED = 24, 8, 2, (2, 4), 2
KINDS = ["sliding", "sliding", "sliding", "full"] * 2
CFG = dict(heads=HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
           window=WINDOW, rope_theta=50.0, top_k=TOP_K,
           held_first=HELD[0], held_count=HELD[1], n_shared=SHARED,
           norm_eps=1e-5, logit_scale=1.0, query_block=8)
#: float32 program against the float32 reference: the two differ by the
#: order of float32 sums alone (a handful of ulps through 8 layers).  A
#: bfloat16 ring, paged row or residual stream reads 50 times over it
#: or more (``test_a_lower_precision_fails_the_tolerance``)
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


def _spec(kinds=KINDS, held=HELD, window=WINDOW):
    spec = [dict(type="embedding", vocab=VOCAB, dim=DIM,
                 learned_positions=False)]
    spec += [dict(type="cohere_moe_block", dim=DIM, attention=kind,
                  hidden=EXPERT, heads=HEADS, kv_heads=KV_HEADS,
                  head_dim=HEAD_DIM, window=window, rope_theta=50.0,
                  n_experts=EXPERTS, top_k=TOP_K, held=held,
                  n_shared=SHARED, shared_hidden=EXPERT)
             for kind in kinds]
    return spec + [dict(type="tied_token_logits", vocab=VOCAB)]


def _chain(name, seed=0, **kwargs):
    """The tiny chain, filled by the units themselves, then the norm
    weights off 1 and the table at a scale the head can tell apart, so
    that none is an identity the comparison could not see."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    fw = make_forwards(AcceleratedWorkflow(None, name=name),
                       Array(numpy.zeros((2, SLOT_WINDOW), numpy.int32)),
                       _spec(**kwargs))
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    rng = numpy.random.default_rng(seed)
    fw[0].weights.mem[...] = rng.standard_normal(fw[0].weights.mem.shape)
    for u in fw[1:]:
        for n, a in u.param_arrays().items():
            if n.endswith("_norm"):
                a.mem[...] = 1.0 + 0.2 * rng.standard_normal(a.mem.shape)
    return fw


def _params(fw):
    return {i: {n: jnp.asarray(a.mem)
                for n, a in u.param_arrays().items()}
            for i, u in enumerate(fw)}


def _reference_logits(params, tokens, cfg=CFG, **kwargs):
    chain = [params[0]] + [params[i] for i in range(1, len(params) - 1)] \
        + [{"final_norm": params[len(params) - 1]["final_norm"]}]
    logits, _ = ref.forward_logits(chain, KINDS, tokens, cfg, **kwargs)
    return numpy.asarray(logits)


@pytest.fixture(scope="module")
def chain():
    with _float32():
        fw = _chain("cohere-tiny")
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


@pytest.mark.parametrize("layer", [1, 4], ids=["sliding", "full"])
def test_one_block_matches_the_reference_layer(chain, f32, layer):
    """One layer of each kind alone, on a stream of unit scale, longer
    than the window."""
    fw, params = chain
    x = numpy.random.default_rng(5).standard_normal((1, 24, DIM))
    got = fw[layer].apply(params[layer], jnp.asarray(x, jnp.float32))
    want, _ = ref.layer_apply(params[layer],
                              jnp.asarray(x[0], jnp.float32),
                              KINDS[layer - 1], CFG)
    numpy.testing.assert_allclose(got[0], want, atol=2e-5)


def _prefilled(fw, params, prompt, chunk=None):
    """(staging caches, last logits) of one prompt: one-shot, or chunk
    by chunk of ``chunk``."""
    from veles_tpu.serving.prefill import prefill, prefill_chunk
    p_len = len(prompt)
    width = max(chunk or 1, 1 << (p_len - 1).bit_length())
    if not chunk:
        padded = numpy.zeros((1, width), numpy.int32)
        padded[0, :p_len] = prompt
        return prefill(fw, padded, prompt_lens=[p_len], window=width,
                       params=params)
    caches = {i: u.init_cache(1, width, dtypes.compute_dtype())
              for i, u in enumerate(fw) if hasattr(u, "init_cache")}
    off = 0
    while off < p_len:
        piece = prompt[off:off + chunk]
        padded = numpy.zeros((1, chunk), numpy.int32)
        padded[0, :len(piece)] = piece
        caches, last = prefill_chunk(fw, padded, off, [len(piece)],
                                     caches, params=params)
        off += chunk
    return caches, last


@pytest.mark.parametrize("p_len", [19, 16, 5],
                         ids=["boundary_inside", "boundary_at_end",
                              "under_the_window"])
def test_chunked_prefill_equals_one_shot(chain, f32, p_len):
    """Chunks of 4 that cross the window of 8, against one pass: the
    same last logits (the reference's), the same paged rows, and the
    same ring: position p at row p mod 8, rows past the prompt zero."""
    fw, params = chain
    prompt = numpy.random.default_rng(p_len).integers(
        0, VOCAB, p_len).tolist()
    whole, last = _prefilled(fw, params, prompt)
    cut, last_cut = _prefilled(fw, params, prompt, chunk=CHUNK)
    numpy.testing.assert_allclose(last_cut, last, atol=ATOL)
    numpy.testing.assert_allclose(
        last[0], _reference_logits(params, prompt)[-1], atol=ATOL)
    for i in whole:
        for name in whole[i]:
            numpy.testing.assert_allclose(cut[i][name], whole[i][name],
                                          atol=ATOL, err_msg=name)
    assert whole[1]["k"].shape == (1, WINDOW, KV_HEADS * HEAD_DIM)
    assert whole[4]["k"].shape[1] >= p_len
    # a ring row holds the newest position that falls on it
    k_full = numpy.asarray(whole[4]["k"][0])
    assert not k_full[p_len:].any()
    ring = numpy.asarray(whole[1]["k"][0])
    written = {p % WINDOW for p in range(p_len)}
    for r in range(WINDOW):
        assert ring[r].any() == (r in written)


def _decode(fw, params, prompt, steps, between=lambda name, a: a,
            chunk=None):
    """Prefill, the staging rows inserted into slot 1, then ``steps``
    decode steps through the units' paged step -> (the text, the logits
    of every position from the prompt's last on)."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    cache = PagedKVCache(fw, max_slots=2, window=SLOT_WINDOW,
                         block_size=BLOCK)
    assert cache.window_units == {i: "cohere_moe_block%d" % i
                                  for i in (1, 2, 3, 5, 6, 7)}
    cache.alloc(8)                        # slot 0 is another request's
    slot = cache.alloc(len(prompt) + steps)
    assert slot == 1
    rows, last = _prefilled(fw, params, prompt, chunk)
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
                    slots=jnp.asarray([slot], jnp.int32),
                    depth=16 * BLOCK)
                assert out.pop("moe").shape == (5,)
                cache.pools[i] = {n: between(n, a)
                                  for n, a in out.items()}
            else:
                h = u.apply(params[i], h)
        got.append(numpy.asarray(h[0, 0]))
    return text, numpy.stack(got)


@pytest.mark.parametrize("chunk", [None, CHUNK], ids=["one_shot",
                                                      "chunked"])
def test_prefill_then_paged_decode_steps_match_the_reference(
        chain, f32, chunk):
    """Decode past three windows: every ring wraps more than twice."""
    fw, params = chain
    prompt = numpy.random.default_rng(7).integers(0, VOCAB, 11).tolist()
    text, got = _decode(fw, params, prompt, 3 * WINDOW + 6, chunk=chunk)
    want = _reference_logits(params, text)[len(prompt) - 1:]
    numpy.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("what", ["ring_bfloat16", "paged_bfloat16",
                                  "residual_stream_bfloat16"])
def test_a_lower_precision_fails_the_tolerance(chain, f32, what):
    """The tolerance is tight enough to refuse the sliding layers' rings
    held in bfloat16 between steps, the full layers' paged rows in
    bfloat16, and a residual stream rounded to bfloat16 between
    layers."""
    fw, params = chain
    rng = numpy.random.default_rng(7)
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
    if what != "residual_stream_bfloat16":
        # a ring holds WINDOW rows a slot, a paged pool BLOCK rows a block
        rows = WINDOW if what == "ring_bfloat16" else BLOCK
        prompt = rng.integers(0, VOCAB, 11).tolist()
        text, got = _decode(fw, params, prompt, 20,
                            lambda n, a: bf16(a) if a.shape[1] == rows
                            else a)
        want = _reference_logits(params, text)[len(prompt) - 1:]
    else:
        toks = rng.integers(0, VOCAB, (1, 40))
        h = toks
        for i, u in enumerate(fw):
            h = u.apply(params[i], h if i < 2 else bf16(h))
        got, want = numpy.asarray(h[0]), _reference_logits(params, toks[0])
    assert numpy.abs(got - want).max() > 50 * ATOL


# -- the ring, the window and the rotary ----------------------------------

def test_ring_positions_name_what_each_row_holds():
    from veles_tpu.ops.paged_attention import ring_positions
    got = numpy.asarray(ring_positions(jnp.asarray([-1, 2, 9, 17]), 8, 8))
    assert (got[0] < 0).all()
    assert got[1].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert got[2].tolist() == [8, 9, 2, 3, 4, 5, 6, 7]
    assert got[3].tolist() == [16, 17, 10, 11, 12, 13, 14, 15]
    # the first rows alone: a step whose bucket covers 4 positions
    assert numpy.asarray(ring_positions(jnp.asarray([3]), 4, 8)).tolist() \
        == [[0, 1, 2, 3]]


def test_window_decode_reads_only_the_buckets_rows(chain, f32):
    """A decode step whose block bucket covers 4 positions reads 4 ring
    rows and gives what a read of the whole ring gives; the padding row
    writes the trash ring alone."""
    from veles_tpu.ops.paged_attention import ring_decode_attention
    rng = numpy.random.default_rng(4)
    ring = jnp.asarray(rng.standard_normal((3, WINDOW, 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, HEADS, HEAD_DIM)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    rows, pos = jnp.asarray([0, 2]), jnp.asarray([3, 0])
    k4, v4, c4 = ring_decode_attention(q, kn, kn, ring, ring, rows, pos,
                                       KV_HEADS, 4)
    k8, v8, c8 = ring_decode_attention(q, kn, kn, ring, ring, rows, pos,
                                       KV_HEADS, WINDOW)
    numpy.testing.assert_allclose(c4, c8, atol=1e-6)
    numpy.testing.assert_array_equal(k4, k8)
    changed = numpy.argwhere((numpy.asarray(k4) != numpy.asarray(ring))
                             .any(-1))
    assert {tuple(c) for c in changed} == {(0, 3), (2, 0)}


def test_the_window_is_the_query_and_the_keys_before_it(f32):
    """The sound window: query i sees keys (i - 8, i].  The reference's
    ``window_off_by_one`` (one key more) and ``window_half`` read
    otherwise, the program reads as the sound reference."""
    fw = _chain("cohere-window", kinds=["sliding"])
    params = _params(fw)
    x = numpy.random.default_rng(8).standard_normal((1, 20, DIM))
    got = numpy.asarray(fw[1].apply(params[1],
                                    jnp.asarray(x, jnp.float32))[0])
    xs = jnp.asarray(x[0], jnp.float32)
    sound = numpy.asarray(ref.layer_apply(params[1], xs, "sliding",
                                          CFG)[0])
    numpy.testing.assert_allclose(got, sound, atol=2e-5)
    for fault in ("window_off_by_one", "window_half", "no_window"):
        off = numpy.asarray(ref.layer_apply(params[1], xs, "sliding", CFG,
                                            fault=fault)[0])
        # the first 8 positions see no more than the window either way
        # (half a window: from position 4 on)
        first = 4 if fault == "window_half" else WINDOW
        numpy.testing.assert_allclose(off[:first], sound[:first],
                                      atol=2e-5)
        assert numpy.abs(off[first:] - sound[first:]).max() > 1e-3


def test_interleaved_rotary_against_its_formula():
    """Pair (2i, 2i+1) as the complex number x_2i + i x_2i+1 times
    exp(i p theta^(-2i/D)): program and reference alike."""
    from veles_tpu.models.cohere_moe import plain_inv_freq
    from veles_tpu.models.gigachat import yarn_rotary
    rng = numpy.random.default_rng(2)
    x = rng.standard_normal((1, 5, 3, 16)).astype(numpy.float32)
    positions = numpy.asarray([[0, 1, 7, 300, 4095]])
    inv = 1.0 / 50000.0 ** (numpy.arange(0, 16, 2) / 16)
    numpy.testing.assert_allclose(plain_inv_freq(16, 50000.0), inv,
                                  rtol=1e-6)
    turned = (x[..., 0::2] + 1j * x[..., 1::2]) \
        * numpy.exp(1j * positions[..., None, None] * inv)
    expect = numpy.stack([turned.real, turned.imag], -1).reshape(x.shape)
    numpy.testing.assert_allclose(
        yarn_rotary(jnp.asarray(x), jnp.asarray(positions),
                    plain_inv_freq(16, 50000.0)), expect, atol=1e-3)
    numpy.testing.assert_allclose(
        ref.rotary(jnp.asarray(x[0]), jnp.asarray(positions[0]),
                   50000.0), expect[0], atol=1e-3)


def test_layer_norm_subtracts_the_mean_and_adds_no_bias():
    from veles_tpu.models.cohere_moe import layer_norm
    rng = numpy.random.default_rng(3)
    x = 3.0 + rng.standard_normal((4, 16)).astype(numpy.float32)
    w = rng.standard_normal(16).astype(numpy.float32)
    want = (x - x.mean(-1, keepdims=True)) \
        / numpy.sqrt(x.var(-1, keepdims=True) + 1e-5) * w
    numpy.testing.assert_allclose(layer_norm(jnp.asarray(x), w, 1e-5),
                                  want, atol=1e-5)
    numpy.testing.assert_allclose(ref.norm(jnp.asarray(x), w, 1e-5), want,
                                  atol=1e-5)
    # a zero weight gives zero: no bias
    assert not numpy.asarray(layer_norm(jnp.asarray(x),
                                        numpy.zeros(16), 1e-5)).any()


# -- the held share ---------------------------------------------------------

def _routed_layer(rng, n=12, d=16, h=8, e=16, gain=0.5):
    p = {"router": rng.standard_normal((d, e)) * 0.5,
         "expert_w1": rng.standard_normal((e, d, h)) * gain,
         "expert_w3": rng.standard_normal((e, d, h)) * gain,
         "expert_w2": rng.standard_normal((e, h, d)) * gain,
         "shared_w1": rng.standard_normal((4, d, h)) * gain,
         "shared_w3": rng.standard_normal((4, d, h)) * gain,
         "shared_w2": rng.standard_normal((4, h, d)) * gain}
    p = {name: jnp.asarray(a, jnp.float32) for name, a in p.items()}
    return p, jnp.asarray(rng.standard_normal((n, d)), jnp.float32)


def test_four_shares_and_the_shared_mean_once_are_the_uncut_layer(f32):
    """128 experts top-8 and 4 shared, 32 held a share (the cell's 16 of
    128 at twice the ratio): the held parts of R that the four shares
    give, summed, plus the shared experts' mean counted ONCE, under the
    half, are the uncut reference's F; and so says the reference of its
    own shares."""
    from veles_tpu.models.cohere_moe import CohereMoeBlock
    from veles_tpu.models.lfm2 import routed_ffn
    p, u = _routed_layer(numpy.random.default_rng(11), e=128)
    cfg = dict(CFG, held_first=0, held_count=128, top_k=8, n_shared=4)
    whole, _ = ref.ffn(p, u, cfg, "f32", None)
    parts, ref_parts, pairs = 0, 0, 0
    for first in range(0, 128, 32):
        share = dict(p, **{w: p[w][first:first + 32]
                           for w in ("expert_w1", "expert_w3",
                                     "expert_w2")})
        got, counts = routed_ffn(share, u, 8, True, 1.0,
                                 held=(first, 32))
        want, _ = ref.routed_ffn(
            share, u, dict(cfg, held_first=first, held_count=32), "f32",
            None)
        numpy.testing.assert_allclose(got, want, atol=5e-5)
        parts, ref_parts = parts + got, ref_parts + want
        pairs += int(counts[4])
    assert pairs == 12 * 8             # every pair fell on ONE share
    shared = ref.shared_ffn(p, u, cfg, "f32", None)
    numpy.testing.assert_allclose(0.5 * (parts + shared), whole,
                                  atol=2e-4)
    numpy.testing.assert_allclose(ref_parts, 2 * whole - shared,
                                  atol=2e-4)
    # the program's block: the same F from its own shared experts
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    unit = CohereMoeBlock(AcceleratedWorkflow(None, name="cohere-ffn"),
                          dim=16, hidden=8, heads=2, head_dim=8,
                          n_experts=128, top_k=8, n_shared=4)
    f, _ = unit._ffn(p, u[None])
    numpy.testing.assert_allclose(f[0], whole, atol=2e-4)


@pytest.mark.parametrize("rows", [1.0, 0.3, 0.01])
def test_the_references_experts_on_their_rows_or_on_every_row(rows):
    """The reference computes a held expert on the rows that chose it
    (gathered, up to a share of them) or, past that share, on every row
    under its gate: the same sum either way."""
    p, u = _routed_layer(numpy.random.default_rng(14), n=40)
    cfg = dict(CFG, held_first=2, held_count=8, top_k=4)
    dense, _ = ref.routed_ffn(p, u, dict(cfg, expert_rows=1.0), "f32",
                              None)
    got, _ = ref.routed_ffn(p, u, dict(cfg, expert_rows=rows), "f32", None)
    numpy.testing.assert_allclose(got, dense, atol=1e-5)


def test_a_router_without_a_bias_chooses_by_score_alone(f32):
    """``routed_ffn`` with no ``expert_bias`` leaf is the one with a zero
    bias, token for token."""
    from veles_tpu.models.lfm2 import routed_ffn
    p, u = _routed_layer(numpy.random.default_rng(12))
    a, ca = routed_ffn(p, u, 4, True, 1.0, held=(2, 8))
    b, cb = routed_ffn(dict(p, expert_bias=jnp.zeros((16,))), u, 4, True,
                       1.0, held=(2, 8))
    numpy.testing.assert_array_equal(numpy.asarray(a), numpy.asarray(b))
    numpy.testing.assert_array_equal(numpy.asarray(ca), numpy.asarray(cb))


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
    slots, in chunks of at most 4 positions: the first two interleave in
    packed steps at different depths, the short one finishes and the
    third takes over its slot, its ring and its blocks; every request
    decodes past three windows."""
    from veles_tpu.serving import scheduler as sched_mod
    fw, _ = chain
    rng = numpy.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (19, 5, 13)]
    steps = [30, 26, 28]
    widest = sched_mod.PREFILL_WIDEST
    sched_mod.PREFILL_WIDEST = CHUNK
    with _float32():
        before = _counters()
        sched = sched_mod.InferenceScheduler(
            fw, max_slots=2, window=SLOT_WINDOW, block_size=BLOCK,
            prefill_chunk=CHUNK, spec=False, prefix_cache=False,
            warm_buckets=False).start()
        try:
            futures = [sched.submit(p, n) for p, n in zip(prompts, steps)]
            out = [list(f.result(300)) for f in futures]
            snap = sched.metrics()
        finally:
            sched.close()
            sched_mod.PREFILL_WIDEST = widest
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
    # two full layers x K and V x 16 values x 4 B a token (float32 here)
    kvd = KV_HEADS * HEAD_DIM
    assert snap["kv_bytes_per_token"] == 2 * 2 * kvd * 4
    blocks = 2 * (SLOT_WINDOW // BLOCK) + 1
    assert snap["state_bytes"]["kv"] == 2 * 2 * blocks * BLOCK * kvd * 4
    # six rings of 8 rows a slot, the trash ring among them
    assert snap["state_bytes"]["window_kv"] == 6 * 2 * 3 * WINDOW * kvd * 4
    assert snap["pools_in_place"] is True
    steps_n = _counter(moved, "veles_serving_steps_total")
    assert steps_n > 0
    lengths = [len(p) + j for p, n in zip(prompts, steps)
               for j in range(1, n)]
    assert _counter(moved, "veles_serving_full_kv_rows_total") \
        == 2 * sum(lengths)
    assert _counter(moved, "veles_serving_window_kv_rows_total") \
        == 6 * sum(min(n, WINDOW) for n in lengths)
    assert _counter(moved, "veles_serving_moe_layer_steps_total") \
        == 8 * steps_n
    held = _counter(moved, "veles_serving_moe_held_pairs_total")
    assert 0 < held < _counter(moved, "veles_serving_moe_pairs_total")
    assert _counter(moved, "veles_serving_pool_copies_total") == 0
    assert _counter(moved, "veles_serving_prefill_chunk_total") >= 8


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


# -- what a ring is not carried through --------------------------------------

REFUSALS = {
    "prefix_cache": dict(prefix_cache=True),
    "spec": dict(spec=True),
    "tp": dict(tp=2),
    "kv_int8": dict(kv_dtype="int8"),
    "export_import": dict(role="prefill"),
    "host_tier": dict(kv_host_bytes=1 << 20),
}


@pytest.mark.parametrize("option", sorted(REFUSALS))
def test_option_refused_for_a_ring(chain, option):
    """A chain with sliding-window rings is refused the options that
    move blocks, in its own words."""
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw = _chain("cohere-refused", kinds=["sliding", "full"])
    with pytest.raises(ValueError, match="sliding-window layer's ring "
                       r"of K/V rows a slot \(cohere_moe_block1\)"):
        InferenceScheduler(fw, max_slots=2, window=SLOT_WINDOW,
                           block_size=BLOCK, prefill_chunk=CHUNK,
                           **REFUSALS[option])


def test_a_rings_bytes_are_its_rows_whatever_the_length(chain):
    """Under the default (bfloat16) compute dtype: a ring is 8 rows a
    slot and layer whatever the window of the slots; admission takes
    the full layers' blocks alone (a request 7 times the window is
    admitted on them), bytes_per_token counts the full layers alone,
    and the block moves refuse the chain in words."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, _ = chain
    assert dtypes.compute_dtype() == jnp.bfloat16
    kvd = KV_HEADS * HEAD_DIM
    sizes = []
    for window in (16, 64):
        cache = PagedKVCache(fw, max_slots=2, window=window,
                             block_size=BLOCK, kv_blocks=window // BLOCK)
        assert cache.pools[1]["k"].shape == (3, WINDOW, kvd)
        assert cache.pools[4]["k"].shape == (window // BLOCK + 1, BLOCK,
                                             kvd)
        sizes.append(cache.state_bytes())
        assert cache.bytes_per_token() == 2 * 2 * kvd * 2
    assert sizes[0]["window_kv"] == sizes[1]["window_kv"] \
        == 6 * 2 * 3 * WINDOW * kvd * 2
    assert sizes[1]["kv"] == 4 * sizes[0]["kv"] - 3 * 2 * 2 * BLOCK \
        * kvd * 2
    # the long request takes its 14 blocks of the full layers, the ring
    # nothing: the pool of 16 blocks holds it
    slot = cache.alloc(7 * WINDOW)
    assert slot is not None and cache.free_blocks == 16 - 14
    assert cache.alloc(3 * BLOCK) is None       # no two of them
    for move in (lambda: cache.export_blocks([1]),
                 lambda: cache.import_blocks([1], {}),
                 lambda: cache.load_staging({}, [1])):
        with pytest.raises(ValueError, match="ring of K/V rows a slot"):
            move()
    with pytest.raises(ValueError, match="ring of K/V rows a slot"):
        PagedKVCache(fw, max_slots=2, window=16, block_size=BLOCK,
                     kv_dtype="int8")


def test_an_insert_moves_a_power_of_two_of_blocks(chain):
    """A prompt of 5 blocks inserts 8 (3 of them the trash block 0), a
    ring whole; releasing the slot zeroes nothing and the next occupant
    reads none of it (its rows are past its newest position)."""
    from veles_tpu.serving import kv_slots
    fw, _ = chain
    cache = kv_slots.PagedKVCache(fw, max_slots=2, window=SLOT_WINDOW,
                                  block_size=BLOCK)
    seen = []
    inner = kv_slots._insert_blocks

    def spy(pool_k, pool_v, src_k, src_v, ids, start):
        seen.append(numpy.asarray(ids).tolist())
        return inner(pool_k, pool_v, src_k, src_v, ids, start)
    kv_slots._insert_blocks = spy
    try:
        slot = cache.alloc(20)
        rows = {i: {n: jnp.ones((1, WINDOW if i in cache.window_units
                                 else 32, KV_HEADS * HEAD_DIM),
                                jnp.bfloat16) for n in ("k", "v")}
                for i in cache.pools}
        cache.insert(slot, rows, 20)
    finally:
        kv_slots._insert_blocks = inner
    assert [len(ids) for ids in seen] == [8, 8]
    assert seen[0][5:] == [0, 0, 0] and 0 not in seen[0][:5]
    ring = numpy.asarray(cache.pools[1]["k"], numpy.float32)
    assert (ring[slot] == 1).all() and not ring[1 - slot].any()
    cache.release(slot)
    cache.check()
    assert (numpy.asarray(cache.pools[1]["k"], numpy.float32)[slot]
            == 1).all()


def test_padding_rows_leave_live_rows_alone(chain, f32):
    """A packed step of ONE live row in a bucket of 4: the padding rows
    (slot -1) write the trash ring of a sliding layer, never a live
    one."""
    fw, params = chain
    rng = numpy.random.default_rng(2)
    unit, p = fw[1], params[1]
    pool = {n: jnp.asarray(rng.standard_normal((3, WINDOW, 16)),
                           jnp.float32) for n in ("k", "v")}
    x = jnp.ones((4, 1, DIM), jnp.float32)
    slots = jnp.asarray([1, -1, -1, -1], jnp.int32)
    _, out = unit.apply_step_paged(
        p, x, jnp.asarray([13, 0, 0, 0], jnp.int32),
        jnp.zeros((4, 4), jnp.int32), pool, slots=slots, depth=16)
    new, old = numpy.asarray(out["k"]), numpy.asarray(pool["k"])
    changed = {tuple(c) for c in numpy.argwhere((new != old).any(-1))}
    assert changed == {(1, 13 % WINDOW), (2, 0)}
    assert out["moe"][1] == TOP_K


def test_the_tied_head_reads_the_embeddings_table(chain, f32):
    """One table on the device for the embedding and the head: the
    serving weights hold it once, and a head that read a table of its
    own would give other logits."""
    from veles_tpu.serving.weights import ServingWeights
    fw, params = chain
    assert fw[-1].param_arrays()["table"] is fw[0].weights
    weights = ServingWeights(fw)
    try:
        last = len(fw) - 1
        assert weights.params[last]["table"] is weights.params[0]["weights"]
    finally:
        weights.close()
    x = jnp.asarray(numpy.random.default_rng(6).standard_normal(
        (1, 3, DIM)), jnp.float32)
    got = fw[-1].apply(params[len(fw) - 1], x)
    want = ref.head_logits(params[0]["weights"],
                           params[len(fw) - 1]["final_norm"], x[0], CFG)
    numpy.testing.assert_allclose(got[0], want, atol=1e-4)
    other = dict(params[len(fw) - 1],
                 table=params[0]["weights"][::-1])
    assert numpy.abs(fw[-1].apply(other, x)[0] - want).max() > 1e-2
