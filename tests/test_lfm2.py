"""The LFM2 decoder layer (``veles_tpu/models/lfm2.py``) against the
repo's plain reference (``models/lfm2_reference.py``) at a tiny size on
the CPU, through every role the scheduler uses: 4 query / 2 KV heads, 8
experts top-2 with a non-zero ``expert_bias``, 5 layers holding every
kind of layer (conv + dense, attention + routed, conv + routed)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import dtypes
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.models import lfm2_reference as ref

pytestmark = pytest.mark.serving

DIM, VOCAB, WINDOW, BLOCK, CHUNK = 32, 40, 64, 4, 8
CFG = dict(heads=4, kv_heads=2, conv_kernel=3, top_k=2,
           norm_topk_prob=True, routed_scaling_factor=1.0,
           rope_theta=1e6, norm_eps=1e-5)
KINDS = [("conv", "dense"), ("attention", "routed"), ("conv", "routed"),
         ("conv", "routed"), ("attention", "routed")]


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


def _spec(kinds=KINDS):
    spec = [dict(type="embedding", vocab=VOCAB, dim=DIM,
                 learned_positions=False)]
    spec += [dict(type="lfm2_block", dim=DIM, operator=op, ffn=ffn,
                  hidden=48 if ffn == "dense" else 24, heads=4,
                  kv_heads=2, n_experts=8, top_k=2)
             for op, ffn in kinds]
    return spec + [dict(type="rms_token_logits", vocab=VOCAB)]


def _chain(name, seed=0, kinds=KINDS):
    """The tiny chain, filled by the units themselves, then norm
    vectors off 1 and a non-zero expert_bias so that neither is an
    identity the comparison could not see."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    fw = make_forwards(AcceleratedWorkflow(None, name=name),
                       Array(numpy.zeros((2, WINDOW), numpy.int32)),
                       _spec(kinds))
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    rng = numpy.random.default_rng(seed)
    for u in fw[1:]:
        for n, a in u.param_arrays().items():
            if n.endswith("_norm"):
                a.mem[...] = 1 + 0.1 * rng.standard_normal(a.mem.shape)
            if n == "expert_bias":
                a.mem[...] = 0.1 * rng.standard_normal(a.mem.shape)
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
        fw = _chain("lfm2-tiny")
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
            atol=5e-5)


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
    numpy.testing.assert_allclose(last_cut, last, atol=5e-5)
    numpy.testing.assert_allclose(
        last[0], _reference_logits(params, prompt)[-1], atol=5e-5)
    assert sorted(cut) == sorted(whole) == [1, 2, 3, 4, 5]
    for i in whole:
        assert sorted(cut[i]) == sorted(whole[i])
        for name in whole[i]:
            numpy.testing.assert_allclose(cut[i][name], whole[i][name],
                                          atol=5e-5, err_msg=name)
    # rows at or past the prompt are zero, the state is its last rows
    assert not numpy.asarray(whole[2]["k"][0, p_len:]).any()
    assert numpy.asarray(whole[1]["conv"]).shape == (1, 3, DIM)


@pytest.mark.parametrize("p_len", [45, 39], ids=["32+16", "32+8"])
def test_mixed_width_chunks_equal_one_shot(chain, f32, p_len):
    """The scheduler's widths (narrowest ``CHUNK``, widest four of it):
    the conv state and the K/V rows cross boundaries between chunks of
    DIFFERENT widths as they cross those between equal ones."""
    from veles_tpu.serving.scheduler import widest_chunk
    fw, params = chain
    assert widest_chunk(fw, 64) == 256   # products: as wide as the ridge
    prompt = numpy.random.default_rng(p_len).integers(
        0, VOCAB, p_len).tolist()
    whole, last = _prefilled(fw, params, prompt, chunked=False)
    cut, last_cut = _prefilled(fw, params, prompt, chunked=4 * CHUNK)
    numpy.testing.assert_allclose(last_cut, last, atol=5e-5)
    for i in whole:
        for name in whole[i]:
            numpy.testing.assert_allclose(cut[i][name], whole[i][name],
                                          atol=5e-5, err_msg=name)


def test_prefill_then_paged_decode_steps_match_the_reference(chain, f32):
    """One-shot prefill, the staging row inserted into a slot, then 24
    decode steps through the units' paged step: the logits of every
    position against the reference's full forward over the same text."""
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, params = chain
    rng = numpy.random.default_rng(7)
    prompt = rng.integers(0, VOCAB, 11).tolist()
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    assert cache.state_units == {1: "lfm2_block1", 3: "lfm2_block3",
                                 4: "lfm2_block4"}
    cache.alloc(8)                        # slot 0 is another request's
    slot = cache.alloc(len(prompt) + 24)
    assert slot == 1
    rows, last = _prefilled(fw, params, prompt, chunked=False)
    cache.insert(slot, rows, len(prompt))
    text, got = list(prompt), [numpy.asarray(last[0])]
    tables = jnp.asarray(cache.table_rows([slot], 16))
    for _ in range(24):
        text.append(int(got[-1].argmax()))
        h = jnp.asarray([[text[-1]]], jnp.int32)
        pos = jnp.asarray([len(text) - 1], jnp.int32)
        for i, u in enumerate(fw):
            if hasattr(u, "init_cache"):
                h, cache.pools[i] = u.apply_step_paged(
                    params[i], h, pos, tables, cache.pools[i],
                    slots=jnp.asarray([slot], jnp.int32))
                cache.pools[i].pop("moe", None)
            elif hasattr(u, "apply_step_slots"):
                h = u.apply_step_slots(params[i], h, pos)
            else:
                h = u.apply(params[i], h)
        got.append(numpy.asarray(h[0, 0]))
    want = _reference_logits(params, text)[len(prompt) - 1:]
    numpy.testing.assert_allclose(numpy.stack(got), want, atol=1e-4)


def _served(fw, prompts, steps, **kwargs):
    from veles_tpu.serving.scheduler import InferenceScheduler
    sched = InferenceScheduler(
        fw, window=WINDOW, block_size=BLOCK, prefill_chunk=CHUNK,
        spec=False, prefix_cache=False, warm_buckets=False,
        **kwargs).start()
    try:
        futures = [sched.submit(p, n) for p, n in zip(prompts, steps)]
        out = [list(f.result(300)) for f in futures]
        return [o[len(p):] if len(o) > n else o
                for o, p, n in zip(out, prompts, steps)], sched.metrics()
    finally:
        sched.close()


@pytest.fixture(scope="module")
def served(chain):
    """Three requests of different lengths through the scheduler on TWO
    slots: the first two share packed steps, the short one finishes and
    the third takes over its slot and its state rows."""
    fw, _ = chain
    rng = numpy.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (19, 5, 16)]
    steps = [30, 6, 24]
    with _float32():
        tokens, snap = _served(fw, prompts, steps, max_slots=2)
    return prompts, steps, tokens, snap


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


def test_packed_steps_slot_reuse_and_no_state_leak(chain, served, f32):
    _, params = chain
    prompts, steps, tokens, snap = served
    assert [len(t) for t in tokens] == steps
    # greedy tokens are the reference's own argmax at every position of
    # all three requests: the third's state started from zero
    assert _gaps(params, prompts, tokens).max() < 1e-4
    assert snap["prefix_cache"] is False and snap["spec"] is False
    assert snap["state_units"] == ["lfm2_block1", "lfm2_block3",
                                   "lfm2_block4"]
    # KV: 2 attention layers x (k + v) x 2 heads x 8 x 4 B a token;
    # state: 3 conv layers x (2 slots + trash) x 3 rows x 32 x 4 B
    assert snap["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert snap["state_bytes"]["conv"] == 3 * 3 * 3 * DIM * 4
    assert snap["state_bytes"]["kv"] == 2 * 2 * (2 * 16 + 1) * 4 * 16 * 4


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8_in_place",))
def test_planted_fault_reads_as_wrong(chain, served, f32, fault):
    """The comparison that decides the cell's ``correct``, at this size:
    the mean gap of the served tokens under a reference with ONE fault
    planted, over the same mean for the tokens the int8 control puts
    first.  Sound, the float32 program reads 0; every fault reads over
    the limit, and so does the control in the program's place."""
    _, params = chain
    prompts, _, tokens, _ = served
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
    assert value > 0.5, value


def test_routed_ffn_against_a_loop_over_experts_uneven_routing(f32):
    """Uneven routing with an expert that gets no token: expert 5's
    bias keeps it out, expert 2's draws nearly every token."""
    from veles_tpu.models.lfm2 import routed_ffn
    rng = numpy.random.default_rng(11)
    n, d, h, e, k = 12, 16, 8, 6, 2
    p = {"router": rng.standard_normal((d, e)) * 0.3,
         "expert_bias": numpy.array([0, 0, 5.0, 0, 0, -9.0]),
         "expert_w1": rng.standard_normal((e, d, h)) * 0.3,
         "expert_w3": rng.standard_normal((e, d, h)) * 0.3,
         "expert_w2": rng.standard_normal((e, h, d)) * 0.3}
    p = {name: jnp.asarray(a, jnp.float32) for name, a in p.items()}
    u = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    cfg = dict(CFG, top_k=k)
    want, _ = ref.routed_ffn(p, u, cfg, "f32", None)
    gates, _ = ref.route(p, u, cfg)
    chosen = numpy.asarray(gates) > 0
    assert not chosen[:, 5].any() and chosen[:, 2].all()
    got, counts = routed_ffn(p, u, k, True, 1.0)
    numpy.testing.assert_allclose(got, want, atol=2e-5)
    assert counts.tolist() == [1, n * k, int(chosen.any(0).sum()), n]
    # rows that are not live open no expert of their own, count nothing
    live = jnp.arange(n) < 3
    part, counts = routed_ffn(p, u, k, True, 1.0, live=live)
    numpy.testing.assert_allclose(part[:3], want[:3], atol=2e-5)
    assert counts.tolist() == [1, 3 * k, int(chosen[:3].any(0).sum()), 3]


def test_padding_rows_leave_live_state_alone(chain, f32):
    """A packed step of ONE live row in a bucket of 4: the padding rows
    (slot -1) write the trash row, not a live slot's state."""
    fw, params = chain
    unit, p = fw[1], params[1]
    state = jnp.asarray(numpy.random.default_rng(2).standard_normal(
        (5, 3, DIM)), jnp.float32)
    x = jnp.ones((4, 1, DIM), jnp.float32)
    slots = jnp.asarray([2, -1, -1, -1], jnp.int32)
    y, out = unit.apply_step_paged(p, x, jnp.zeros((4,), jnp.int32),
                                   None, {"conv": state}, slots=slots)
    new, state = numpy.asarray(out["conv"]), numpy.asarray(state)
    numpy.testing.assert_array_equal(new[[0, 1, 3]], state[[0, 1, 3]])
    assert not numpy.allclose(new[2], state[2])
    numpy.testing.assert_array_equal(new[2, :2], state[2, 1:])


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
def test_option_refused_for_a_chain_with_per_slot_state(chain, option):
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = chain
    with pytest.raises(ValueError, match="lfm2_block"):
        InferenceScheduler(fw, max_slots=2, window=WINDOW,
                           block_size=BLOCK, prefill_chunk=CHUNK,
                           **REFUSALS[option])


def test_defaults_turn_off_what_state_is_not_carried_through(chain):
    """Asked for by the configuration's defaults alone (speculation and
    the prefix cache are on there), they are off for this chain."""
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = chain
    assert root.common.serving.get("spec") \
        and root.common.serving.get("prefix_cache")
    sched = InferenceScheduler(fw, max_slots=2, window=WINDOW,
                               block_size=BLOCK, prefill_chunk=CHUNK)
    assert (sched.spec, sched.prefix_cache, sched.tp, sched.kv_dtype,
            sched.kv_host_bytes, sched.role) \
        == (False, False, 0, "fp32", 0, "both")


@pytest.mark.parametrize("option", sorted(set(REFUSALS) - {"tp"}))
def test_same_option_on_the_dense_chain_as_before(option,
                                                  spec_trained_chain):
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = spec_trained_chain
    sched = InferenceScheduler(fw, max_slots=2, block_size=8,
                               **REFUSALS[option])
    assert sched._state_units == {}
    got = {"prefix_cache": sched.prefix_cache, "spec": sched.spec,
           "kv_int8": sched.kv_dtype == "int8",
           "export_import": sched.role == "prefill",
           "import": sched.role == "decode",
           # the host tier hangs on the prefix cache, as before
           "host_tier": sched.kv_host_bytes == (1 << 20)
           or not sched.prefix_cache}
    assert got[option]


def test_cache_refuses_block_moves_for_per_slot_state(chain, f32):
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, _ = chain
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    for move in (lambda: cache.export_blocks([1]),
                 lambda: cache.import_blocks([1], {}),
                 lambda: cache.load_staging({}, [1])):
        with pytest.raises(ValueError, match="per-slot state"):
            move()
    for kwargs in (dict(kv_dtype="int8"), dict(tp=object())):
        with pytest.raises(ValueError, match="lfm2_block1"):
            PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK, **kwargs)


# -- weights that never exist in float32 ---------------------------------------

def test_device_leaves_in_the_stored_dtype_are_taken_as_they_are():
    """A unit handed bfloat16 device leaves before it initializes fills
    nothing and keeps no host mirror; the serving weights take each leaf
    as it is; a chain that filled itself in float32 has its matrices
    cast, once, as every other chain."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.serving.weights import ServingWeights
    assert dtypes.compute_dtype() == jnp.bfloat16
    fw = make_forwards(AcceleratedWorkflow(None, name="lfm2-bf16"),
                       Array(numpy.zeros((2, WINDOW), numpy.int32)),
                       _spec())
    key = jax.random.key(0)
    float32 = ("router", "expert_bias", "conv_taps", "q_norm", "k_norm",
               "operator_norm", "ffn_norm", "embedding_norm")
    handed = {}
    shapes = [{"weights": (VOCAB, DIM)}] \
        + [u.param_shapes() for u in fw[1:-1]] \
        + [{"embedding_norm": (DIM,), "weights": (DIM, VOCAB)}]
    for i, (u, layer) in enumerate(zip(fw, shapes)):
        for name, shape in layer.items():
            leaf = jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32).astype(
                jnp.float32 if name in float32 else jnp.bfloat16)
            getattr(u, name).devmem = handed[i, name] = leaf
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    weights = ServingWeights(fw)
    try:
        assert weights.leaves_cast == 0
        for (i, name), leaf in handed.items():
            assert weights.params[i][name] is leaf
            assert getattr(fw[i], name).mem is None     # no host copy
        assert weights.dtype == "bfloat16"
    finally:
        weights.close()
    assert fw[2].compute_dtype_params() == ()
    filled = _chain("lfm2-f32-filled")
    assert sorted(filled[2].compute_dtype_params()) == sorted(
        ["wq", "wk", "wv", "wo", "expert_w1", "expert_w3", "expert_w2"])
    assert sorted(filled[1].compute_dtype_params()) == sorted(
        ["conv_in", "conv_out", "ffn_w1", "ffn_w3", "ffn_w2"])

