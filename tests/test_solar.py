"""The Solar-Open2 decoder layer (``veles_tpu/models/solar.py``) against
the repo's plain reference (``models/solar_reference.py``) at a tiny size
on the CPU, through every role the scheduler uses: 4 heads of 8 (wider
than the stream, as the model's 64 x 128 over 4096), 2 KV heads, 16
experts top-4 of which experts 4-9 are held, a shared expert, 5 layers
(GQA, KDA, KDA, KDA, GQA)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import dtypes
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.models import solar_reference as ref

pytestmark = pytest.mark.serving

DIM, VOCAB, WINDOW, BLOCK, CHUNK = 32, 40, 64, 4, 8
HEADS, KV_HEADS, HEAD_DIM, HIDDEN, RANK = 4, 2, 8, 24, 8
EXPERTS, TOP_K, HELD = 16, 4, (4, 6)
KINDS = ["gqa", "kda", "kda", "kda", "gqa"]
CFG = dict(heads=HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
           conv_kernel=4, top_k=TOP_K, held_first=HELD[0],
           held_count=HELD[1], norm_topk_prob=True,
           routed_scaling_factor=1.0, norm_eps=1e-5, rope_theta=1e4)
#: float32 program against the float32 reference: the two differ by the
#: order of float32 sums alone (a handful of ulps through 5 layers and
#: up to 40 recurrent steps).  A bfloat16 state or residual stream reads
#: 100 times over it (``test_a_lower_precision_fails_the_tolerance``)
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


def _spec(kinds=KINDS, held=HELD):
    spec = [dict(type="embedding", vocab=VOCAB, dim=DIM,
                 learned_positions=False)]
    spec += [dict(type="solar_block", dim=DIM, operator=op, hidden=HIDDEN,
                  heads=HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
                  low_rank=RANK, n_experts=EXPERTS, top_k=TOP_K,
                  held=held) for op in kinds]
    return spec + [dict(type="rms_token_logits", vocab=VOCAB)]


def _chain(name, seed=0, **kwargs):
    """The tiny chain, filled by the units themselves, then norm vectors
    off 1, non-zero ``expert_bias`` and ``gate_bias`` and a decay strong
    enough to matter in 40 positions, so that none is an identity the
    comparison could not see."""
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
                a.mem[...] = 1 + 0.1 * rng.standard_normal(a.mem.shape)
            if n in ("expert_bias", "gate_bias"):
                a.mem[...] = 0.1 * rng.standard_normal(a.mem.shape)
            if n == "dt_bias":
                a.mem[...] = rng.uniform(-3.0, 0.0, a.mem.shape)
    return fw


def _params(fw):
    return {i: {n: jnp.asarray(a.mem)
                for n, a in u.param_arrays().items()}
            for i, u in enumerate(fw)}


def _reference_logits(params, tokens, cfg=CFG, **kwargs):
    logits, _ = ref.forward_logits(
        [params[i] for i in range(len(params))], KINDS, tokens, cfg,
        **kwargs)
    return numpy.asarray(logits)


@pytest.fixture(scope="module")
def chain():
    with _float32():
        fw = _chain("solar-tiny")
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


@pytest.mark.parametrize("operator", ["kda", "gqa"])
def test_one_block_matches_the_reference_layer(chain, f32, operator):
    """One layer of each operator alone, on a stream of unit scale."""
    fw, params = chain
    i = 1 + KINDS.index(operator)
    x = numpy.random.default_rng(5).standard_normal((1, 24, DIM))
    got = fw[i].apply(params[i], jnp.asarray(x, jnp.float32))
    want, _ = ref.layer_apply(params[i], jnp.asarray(x[0], jnp.float32),
                              operator, CFG)
    numpy.testing.assert_allclose(got[0], want, atol=2e-5)


def _prefilled(fw, params, prompt, chunked):
    """(staging caches, last logits) of one prompt: one-shot, or chunk
    by chunk of ``CHUNK``; ``chunked`` a width: in the scheduler's
    widths between ``CHUNK`` and it (``chunk_width``)."""
    from veles_tpu.serving.prefill import prefill, prefill_chunk
    from veles_tpu.serving.scheduler import chunk_width
    p_len = len(prompt)
    width = max(CHUNK, 1 << (p_len - 1).bit_length())
    if not chunked:
        padded = numpy.zeros((1, width), numpy.int32)
        padded[0, :p_len] = prompt
        return prefill(fw, padded, prompt_lens=[p_len], window=width,
                       params=params)
    caches = {i: u.init_cache(1, width, dtypes.compute_dtype())
              for i, u in enumerate(fw) if hasattr(u, "init_cache")}
    widest, off = CHUNK if chunked is True else chunked, 0
    while off < p_len:
        c = chunk_width(p_len - off, off, CHUNK, widest)
        piece = prompt[off:off + c]
        padded = numpy.zeros((1, c), numpy.int32)
        padded[0, :len(piece)] = piece
        caches, last = prefill_chunk(fw, padded, off, [len(piece)],
                                     caches, params=params)
        off += c
    return caches, last


@pytest.mark.parametrize("p_len", [19, 16, 8, 5],
                         ids=["boundary_inside", "boundary_at_end",
                              "one_chunk", "under_a_chunk"])
def test_chunked_prefill_equals_one_shot(chain, f32, p_len):
    fw, params = chain
    prompt = numpy.random.default_rng(p_len).integers(
        0, VOCAB, p_len).tolist()
    whole, last = _prefilled(fw, params, prompt, chunked=False)
    cut, last_cut = _prefilled(fw, params, prompt, chunked=True)
    numpy.testing.assert_allclose(last_cut, last, atol=ATOL)
    numpy.testing.assert_allclose(
        last[0], _reference_logits(params, prompt)[-1], atol=ATOL)
    assert sorted(cut) == sorted(whole) == [1, 2, 3, 4, 5]
    for i in whole:
        assert sorted(cut[i]) == sorted(whole[i])
        for name in whole[i]:
            numpy.testing.assert_allclose(cut[i][name], whole[i][name],
                                          atol=ATOL, err_msg=name)
    # rows at or past the prompt are zero; the state is two arrays, each
    # of its own shape and dtype
    assert not numpy.asarray(whole[1]["k"][0, p_len:]).any()
    assert whole[2]["conv"].shape == (1, 3, 3 * HEADS * HEAD_DIM)
    assert whole[2]["S"].shape == (1, HEADS, HEAD_DIM, HEAD_DIM)
    assert whole[2]["S"].dtype == jnp.float32


def test_ragged_rows_of_one_prefill_stop_at_their_own_length(chain, f32):
    """Two prompts of different lengths in ONE prefill: each row's state
    is what the row alone leaves, whatever the padding after it."""
    from veles_tpu.serving.prefill import prefill
    fw, params = chain
    rng = numpy.random.default_rng(9)
    padded = rng.integers(0, VOCAB, (2, 16)).astype(numpy.int32)
    lens = [16, 7]
    both, last = prefill(fw, padded, prompt_lens=lens, window=16,
                         params=params)
    for row, p_len in enumerate(lens):
        alone, last_alone = _prefilled(
            fw, params, padded[row, :p_len].tolist(), chunked=False)
        numpy.testing.assert_allclose(last[row], last_alone[0],
                                      atol=ATOL)
        for name in ("conv", "S"):
            numpy.testing.assert_allclose(
                both[2][name][row], alone[2][name][0], atol=ATOL,
                err_msg=name)


def _decode(fw, params, prompt, steps, between=lambda name, a: a):
    """One-shot prefill, the staging row inserted into slot 1, then
    ``steps`` decode steps through the units' paged step -> (the text,
    the logits of every position from the prompt's last on).
    ``between`` alters a state array after each step (the tests of a
    lower precision)."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    assert cache.state_units == {2: "solar_block2", 3: "solar_block3",
                                 4: "solar_block4"}
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


@pytest.mark.parametrize("what", ["state_S_bfloat16",
                                  "residual_stream_bfloat16"])
def test_a_lower_precision_fails_the_tolerance(chain, f32, what):
    """The tolerance is tight enough to refuse the matrix state held in
    bfloat16 between steps, and a residual stream rounded to bfloat16
    between layers."""
    fw, params = chain
    rng = numpy.random.default_rng(7)
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
    if what == "state_S_bfloat16":
        prompt = rng.integers(0, VOCAB, 11).tolist()
        text, got = _decode(fw, params, prompt, 24,
                            lambda n, a: bf16(a) if n == "S" else a)
        want = _reference_logits(params, text)[len(prompt) - 1:]
    else:
        toks = rng.integers(0, VOCAB, (1, 40))
        h = toks
        for i, u in enumerate(fw):
            h = u.apply(params[i], h if i < 2 else bf16(h))
        got, want = numpy.asarray(h[0]), _reference_logits(params, toks[0])
    assert numpy.abs(got - want).max() > 100 * ATOL


def _counters():
    """{name, or name{labels}: value} of the process's counters."""
    from veles_tpu.telemetry import metrics
    out = {}
    for line in metrics.render_prometheus().splitlines():
        if line.startswith("veles_serving_") and "_total" in line:
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def _counter(moved, name):
    return sum(v for k, v in moved.items() if k.split("{")[0] == name)


def _served(fw, prompts, steps, **kwargs):
    """-> (served tokens, the scheduler's snapshot, what the process's
    counters moved by)."""
    from veles_tpu.serving.scheduler import InferenceScheduler
    before = _counters()
    sched = InferenceScheduler(
        fw, window=WINDOW, block_size=BLOCK, prefill_chunk=CHUNK,
        spec=False, prefix_cache=False, warm_buckets=False,
        **kwargs).start()
    try:
        futures = [sched.submit(p, n) for p, n in zip(prompts, steps)]
        out = [list(f.result(300)) for f in futures]
        snap = sched.metrics()
    finally:
        sched.close()
    moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()}
    return [o[len(p):] if len(o) > n else o
            for o, p, n in zip(out, prompts, steps)], snap, moved


@pytest.fixture(scope="module")
def served(chain):
    """Three requests of different lengths through the scheduler on TWO
    slots: the first two interleave in packed steps (the long prompt is
    chunked), the short one finishes and the third takes over its slot
    and its state rows."""
    fw, _ = chain
    rng = numpy.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (19, 5, 16)]
    steps = [30, 6, 24]
    with _float32():
        tokens, snap, moved = _served(fw, prompts, steps, max_slots=2)
    return prompts, steps, tokens, snap, moved


def _gaps(params, prompts, tokens, **kwargs):
    """Per served token, how far its logit lies below the reference's
    best, the reference run over the prompt and the served tokens."""
    out = []
    for prompt, toks in zip(prompts, tokens):
        text = prompt + toks
        rows = _reference_logits(params, text[:-1],
                                 prompt_len=len(prompt),
                                 **kwargs)[len(prompt) - 1:]
        out += (rows.max(-1) - rows[numpy.arange(len(toks)),
                                    toks]).tolist()
    return numpy.asarray(out)


def test_a_scanning_chain_keeps_its_chunk_width(chain, served):
    """A delta-rule layer's chunk is a scan over its positions, and it
    says so (``prefill_scans``): the scheduler's widest chunk is the
    configured one whatever the prompt's length, so the chain's chunks
    are what they were: 19 positions in 8 + 8 + 3, 16 in 8 + 8, 5
    one-shot."""
    from veles_tpu.serving.scheduler import widest_chunk
    fw, _ = chain
    assert [getattr(u, "prefill_scans", False) for u in fw] == \
        [False] + [op == "kda" for op in KINDS] + [False]
    assert widest_chunk(fw, 64) == 64 and widest_chunk(fw, CHUNK) == CHUNK
    assert widest_chunk(fw[:2] + fw[-1:], 64) == 256   # GQA alone
    _, _, _, snap, _ = served
    assert snap["prefill_chunk"] == snap["prefill_widest"] == CHUNK
    assert snap["prefill_chunks"] == 5
    assert snap["prefill_chunk_tokens"] == 19 + 16


def test_packed_steps_slot_reuse_and_no_state_leak(chain, served, f32):
    _, params = chain
    prompts, steps, tokens, snap, _ = served
    assert [len(t) for t in tokens] == steps
    # greedy tokens are the reference's own argmax at every position of
    # all three requests: the third's state started from zero
    assert _gaps(params, prompts, tokens).max() < ATOL
    assert snap["prefix_cache"] is False and snap["spec"] is False
    assert snap["state_units"] == ["solar_block2", "solar_block3",
                                   "solar_block4"]
    wide = HEADS * HEAD_DIM
    # KV: 2 GQA layers x (k + v) x 2 heads x 8 x 4 B a token; state: 3
    # KDA layers x (2 slots + trash) x (3 conv rows x 3 x 32, float32
    # under this fixture; 4 x 8 x 8 float32 whatever the compute dtype)
    assert snap["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert snap["state_bytes"] == {
        "kv": 2 * 2 * (2 * 16 + 1) * BLOCK * 16 * 4,
        "conv": 3 * 3 * 3 * 3 * wide * 4,
        "S": 3 * 3 * HEADS * HEAD_DIM * HEAD_DIM * 4}
    assert snap["pools_in_place"] is True


def test_counters_of_the_state_and_of_the_held_experts(served):
    *_, moved = served
    steps = _counter(moved, "veles_serving_steps_total")
    rows = _counter(moved, "veles_serving_slot_busy_steps_total")
    layers, kda = len(KINDS), KINDS.count("kda")
    assert steps > 0
    # counted on the host: live rows x the layers that keep a state
    assert _counter(moved, "veles_serving_state_rows_total") \
        == kda * rows
    # each KDA layer's matrix advanced by the delta rule, none of them
    # through the Pallas call off the chip (ops/pallas_delta.py)
    assert _counter(moved, "veles_serving_delta_state_rows_total") \
        == kda * rows
    assert _counter(moved, "veles_serving_delta_state_fused_total") == 0
    assert _counter(moved, "veles_serving_moe_layer_steps_total") \
        == layers * steps
    # all the live rows' choices, and those of them on experts 4-9
    pairs = _counter(moved, "veles_serving_moe_pairs_total")
    held = _counter(moved, "veles_serving_moe_held_pairs_total")
    assert pairs == TOP_K * layers * rows
    assert 0 < held < pairs
    touched = _counter(moved, "veles_serving_moe_experts_touched_total")
    assert touched <= min(held, HELD[1] * layers * steps)
    assert _counter(moved, "veles_serving_moe_hottest_rows_total") <= held
    assert _counter(moved, "veles_serving_pool_copies_total") == 0


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8_in_place",))
def test_planted_fault_reads_as_wrong(chain, served, f32, fault):
    """The comparison that decides the cell's ``correct``, at this size:
    the mean gap of the served tokens under a reference with ONE fault
    planted, over the same mean for the tokens the int8 control puts
    first.  Sound, the float32 program reads 0; every fault reads over
    the limit (or no number at all: without the L2 norms the recurrence
    overflows), and so does the control in the program's place."""
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
        value = 1.0           # its own tokens against itself
    else:
        value = _gaps(params, prompts, tokens, fault=fault,
                      cfg=dict(CFG, prefill_chunk=CHUNK)).mean() \
            / int8_mean
    assert not value <= 0.5, value


# -- the held share ------------------------------------------------------------

def _routed_layer(rng, n=12, d=16, h=8, e=16):
    p = {"router": rng.standard_normal((d, e)) * 0.3,
         "expert_bias": rng.standard_normal((e,)) * 0.1,
         "expert_w1": rng.standard_normal((e, d, h)) * 0.3,
         "expert_w3": rng.standard_normal((e, d, h)) * 0.3,
         "expert_w2": rng.standard_normal((e, h, d)) * 0.3,
         "shared_w1": rng.standard_normal((d, h)) * 0.3,
         "shared_w3": rng.standard_normal((d, h)) * 0.3,
         "shared_w2": rng.standard_normal((h, d)) * 0.3}
    p = {name: jnp.asarray(a, jnp.float32) for name, a in p.items()}
    return p, jnp.asarray(rng.standard_normal((n, d)), jnp.float32)


def _share_of(p, first, count):
    return dict(p, **{w: p[w][first:first + count]
                      for w in ("expert_w1", "expert_w3", "expert_w2")})


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(f32):
    """16 experts, top-4, 2 held a share: the routed parts that the 8
    shares give, summed, plus the shared expert ONCE, are what the uncut
    reference gives for the whole layer; and so says the reference of
    its own shares."""
    from veles_tpu.models.lfm2 import routed_ffn
    p, u = _routed_layer(numpy.random.default_rng(11))
    cfg = dict(CFG, held_first=0, held_count=16)
    whole, _ = ref.routed_ffn(p, u, cfg, "f32", None)
    shared = ref.gated_ffn(u, p["shared_w1"], p["shared_w3"],
                           p["shared_w2"], "f32")
    parts, ref_parts, pairs = 0, 0, 0
    for first in range(0, 16, 2):
        got, counts = routed_ffn(_share_of(p, first, 2), u, TOP_K, True,
                                 1.0, held=(first, 2))
        want, _ = ref.routed_ffn(
            _share_of(p, first, 2), u,
            dict(cfg, held_first=first, held_count=2), "f32", None)
        numpy.testing.assert_allclose(got, want, atol=2e-5)
        assert counts.shape == (5,) and counts[1] == 12 * TOP_K
        parts, ref_parts = parts + got, ref_parts + want
        pairs += int(counts[4])
    assert pairs == 12 * TOP_K         # every pair fell on ONE share
    numpy.testing.assert_allclose(parts + shared, whole + shared,
                                  atol=5e-5)
    numpy.testing.assert_allclose(ref_parts, whole, atol=5e-5)


@pytest.mark.parametrize("live", [None, 3], ids=["all_live", "3_live"])
def test_routed_ffn_without_held_is_as_it_was_bit_for_bit(f32, live):
    """No ``held``: the arithmetic LFM2 has (every expert here, four
    counts).  Told that it holds them ALL, the layer gives the same
    numbers bit for bit and the pairs again as its fifth count."""
    from veles_tpu.models.lfm2 import routed_ffn
    p, u = _routed_layer(numpy.random.default_rng(13))
    live = None if live is None else jnp.arange(12) < live
    plain, counts = routed_ffn(p, u, TOP_K, True, 1.0, live=live)
    told, counts5 = routed_ffn(p, u, TOP_K, True, 1.0, live=live,
                               held=(0, 16))
    want, _ = ref.routed_ffn(p, u, dict(CFG, held_first=0,
                                        held_count=16), "f32", None)
    rows = slice(None) if live is None else slice(0, 3)
    numpy.testing.assert_allclose(plain[rows], want[rows], atol=2e-5)
    numpy.testing.assert_array_equal(numpy.asarray(plain),
                                     numpy.asarray(told))
    assert counts.shape == (4,)
    assert counts5.tolist() == counts.tolist() + [int(counts[1])]


def test_padding_rows_leave_live_state_alone(chain, f32):
    """A packed step of ONE live row in a bucket of 4: the padding rows
    (slot -1) write the trash row, not a live slot's state, and count
    no state row and no routed pair."""
    fw, params = chain
    unit, p = fw[2], params[2]
    rng = numpy.random.default_rng(2)
    pool = {"conv": jnp.asarray(rng.standard_normal(
        (5, 3, 3 * HEADS * HEAD_DIM)), jnp.float32),
        "S": jnp.asarray(rng.standard_normal(
            (5, HEADS, HEAD_DIM, HEAD_DIM)), jnp.float32)}
    x = jnp.ones((4, 1, DIM), jnp.float32)
    slots = jnp.asarray([2, -1, -1, -1], jnp.int32)
    y, out = unit.apply_step_paged(p, x, jnp.zeros((4,), jnp.int32),
                                   None, pool, slots=slots)
    for name in ("conv", "S"):
        new, old = numpy.asarray(out[name]), numpy.asarray(pool[name])
        numpy.testing.assert_array_equal(new[[0, 1, 3]], old[[0, 1, 3]])
        assert not numpy.allclose(new[2], old[2])
    numpy.testing.assert_array_equal(
        numpy.asarray(out["conv"])[2, :2], numpy.asarray(pool["conv"])[2, 1:])
    assert set(out) == {"conv", "S", "moe"}
    assert out["moe"][1] == TOP_K


# -- what per-slot state is not carried through: refused in words ------------

REFUSALS = {
    "prefix_cache": dict(prefix_cache=True),
    "spec": dict(spec=True),
    "tp": dict(tp=2),
    "kv_int8": dict(kv_dtype="int8"),
    "export_import": dict(role="prefill"),
    "import": dict(role="decode"),
    "host_tier": dict(kv_host_bytes=1 << 20),
}


@pytest.mark.parametrize("option", sorted(REFUSALS))
def test_option_refused_for_a_chain_with_matrix_state(chain, option):
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = chain
    with pytest.raises(ValueError, match="per-slot state .*solar_block2"):
        InferenceScheduler(fw, max_slots=2, window=WINDOW,
                           block_size=BLOCK, prefill_chunk=CHUNK,
                           **REFUSALS[option])


def test_cache_refuses_block_moves_for_matrix_state(chain, f32):
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, _ = chain
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    assert cache.pools[2]["S"].shape == (3, HEADS, HEAD_DIM, HEAD_DIM)
    for move in (lambda: cache.export_blocks([1]),
                 lambda: cache.import_blocks([1], {}),
                 lambda: cache.load_staging({}, [1])):
        with pytest.raises(ValueError, match="per-slot state"):
            move()


@pytest.mark.parametrize("held", [(-1, 4), (14, 4), (0, 0)])
def test_a_held_range_outside_the_experts_is_refused(held):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    with pytest.raises(ValueError, match="held"):
        make_forwards(AcceleratedWorkflow(None, name="solar-bad"),
                      Array(numpy.zeros((2, WINDOW), numpy.int32)),
                      _spec(held=held))


def test_the_state_S_is_float32_under_the_bfloat16_default(chain):
    """The serving cache under the default compute dtype: the conv rows
    are bfloat16, the matrix state float32 beside them, both with the
    trash row."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, _ = chain
    assert dtypes.compute_dtype() == jnp.bfloat16
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    assert cache.pools[2]["conv"].dtype == jnp.bfloat16
    assert cache.pools[2]["S"].dtype == jnp.float32
    assert cache.pools[1]["k"].dtype == jnp.bfloat16
    assert cache.state_bytes()["S"] == 3 * 3 * HEADS * HEAD_DIM ** 2 * 4
    config = fw[2].export_config()
    assert config["held"] == HELD and config["operator"] == "kda"
