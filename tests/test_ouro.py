"""The looped decoder stack (``veles_tpu/models/ouro.py``) against the
repo's plain reference (``models/ouro_reference.py``) at a tiny size on
the CPU, through every role the scheduler uses: hidden 64, 4 heads of
16, FFN 160, 3 layers run 4 times a token, vocabulary 128."""

import contextlib

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import dtypes
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.models import ouro_reference as ref

pytestmark = pytest.mark.serving

DIM, HEADS, FFN, LAYERS, PASSES, VOCAB = 64, 4, 160, 3, 4, 128
WINDOW, BLOCK, CHUNK = 64, 4, 8
CFG = dict(heads=HEADS, passes=PASSES, rope_theta=1e6, norm_eps=1e-6)


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


def _spec(passes=PASSES, layers=LAYERS):
    return [dict(type="embedding", vocab=VOCAB, dim=DIM,
                 learned_positions=False),
            dict(type="ouro_stack", dim=DIM, layers=layers,
                 passes=passes, heads=HEADS, hidden=FFN),
            dict(type="plain_token_logits", vocab=VOCAB)]


def _chain(name, seed=0, **spec):
    """The tiny chain, filled by the units themselves, then the norm
    vectors off 1 and the gate's bias off 0 so that neither is an
    identity the comparison could not see."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    fw = make_forwards(AcceleratedWorkflow(None, name=name),
                       Array(numpy.zeros((2, WINDOW), numpy.int32)),
                       _spec(**spec))
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    rng = numpy.random.default_rng(seed)
    for n, a in fw[1].param_arrays().items():
        if n.endswith("_norm"):
            a.mem[...] = 1 + 0.1 * rng.standard_normal(a.mem.shape)
        if n == "gate_b":
            a.mem[...] = -0.5
    return fw


def _params(fw):
    return {i: {n: jnp.asarray(a.mem)
                for n, a in u.param_arrays().items()}
            for i, u in enumerate(fw)}


def _reference(params, tokens, cfg=CFG, **kwargs):
    logits, p = ref.forward_logits(
        [params[i] for i in range(len(params))], tokens, cfg, **kwargs)
    return numpy.asarray(logits), numpy.asarray(p)


@pytest.fixture(scope="module")
def chain():
    with _float32():
        fw = _chain("ouro-tiny")
    return fw, _params(fw)


def test_full_forward_matches_the_reference(chain, f32):
    """The one-shot forward: logits and the exit distribution."""
    fw, params = chain
    toks = numpy.random.default_rng(1).integers(0, VOCAB, (2, 40))
    h = fw[0].apply(params[0], toks)
    h, p = fw[1].apply_with_exit(params[1], h)
    logits = fw[2].apply(params[2], h)
    assert logits.dtype == jnp.float32 and p.shape == (PASSES, 2, 40)
    numpy.testing.assert_allclose(numpy.asarray(p).sum(0), 1, atol=1e-6)
    for row in range(2):
        want, want_p = _reference(params, toks[row])
        numpy.testing.assert_allclose(numpy.asarray(logits[row]), want,
                                      atol=5e-5)
        numpy.testing.assert_allclose(numpy.asarray(p[:, row]), want_p,
                                      atol=1e-5)
    # the gate is no constant: the passes' shares differ by position
    assert numpy.asarray(p[0]).std() > 1e-4


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
    caches = {1: fw[1].init_cache(1, width, dtypes.compute_dtype())}
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
        last[0], _reference(params, prompt)[0][-1], atol=5e-5)
    assert sorted(cut) == sorted(whole) == [1]
    for name in ("k", "v"):
        assert whole[1][name].shape[0] == PASSES * LAYERS
        numpy.testing.assert_allclose(cut[1][name], whole[1][name],
                                      atol=5e-5, err_msg=name)
        # rows at or past the prompt are zero in every cache layer
        assert not numpy.asarray(whole[1][name][:, 0, p_len:]).any()
    # pass 0's rows of a layer are not pass 1's
    k = numpy.asarray(whole[1]["k"])
    assert numpy.abs(k[0, 0, :p_len] - k[LAYERS, 0, :p_len]).max() > 1e-2


@pytest.mark.parametrize("p_len", [45, 39], ids=["32+16", "32+8"])
def test_mixed_width_chunks_equal_one_shot(chain, f32, p_len):
    """The scheduler's widths (narrowest ``CHUNK``, widest four of it):
    every cache layer of the stack holds one-shot prefill's rows after
    chunks of DIFFERENT widths."""
    from veles_tpu.serving.scheduler import widest_chunk
    fw, params = chain
    assert widest_chunk(fw, 64) == 256   # products: as wide as the ridge
    prompt = numpy.random.default_rng(p_len).integers(
        0, VOCAB, p_len).tolist()
    whole, last = _prefilled(fw, params, prompt, chunked=False)
    cut, last_cut = _prefilled(fw, params, prompt, chunked=4 * CHUNK)
    numpy.testing.assert_allclose(last_cut, last, atol=5e-5)
    for name in ("k", "v"):
        numpy.testing.assert_allclose(cut[1][name], whole[1][name],
                                      atol=5e-5, err_msg=name)


def test_prefill_then_paged_decode_steps_match_the_reference(chain, f32):
    """One-shot prefill, the staging inserted into a slot in ONE
    dispatch, then 24 decode steps through the unit's paged step: the
    logits of every position against the reference's full forward over
    the same text, and the step's counts against its exit
    distribution."""
    from veles_tpu.serving import kv_slots
    fw, params = chain
    rng = numpy.random.default_rng(7)
    prompt = rng.integers(0, VOCAB, 11).tolist()
    cache = kv_slots.PagedKVCache(fw, max_slots=2, window=WINDOW,
                                  block_size=BLOCK)
    assert cache.stack_units == {1: "ouro_stack1"}
    assert cache.state_units == {}
    item = numpy.dtype(dtypes.compute_dtype()).itemsize
    assert cache.bytes_per_token() == PASSES * LAYERS * 2 * DIM * item
    assert cache.pools[1]["k"].shape == (PASSES * LAYERS, 2 * 16 + 1,
                                         BLOCK, DIM)
    cache.alloc(8)                        # slot 0 is another request's
    slot = cache.alloc(len(prompt) + 24)
    assert slot == 1
    rows, last = _prefilled(fw, params, prompt, chunked=False)
    calls = []
    real = kv_slots._insert_stack_blocks
    kv_slots._insert_stack_blocks = \
        lambda *a: calls.append(1) or real(*a)
    try:
        cache.insert(slot, rows, len(prompt))
    finally:
        kv_slots._insert_stack_blocks = real
    # one dispatch an admission, not one a cache layer; in place
    assert calls == [1] and (cache.pool_swaps, cache.pool_copies) == (1, 0)
    text, got, mass = list(prompt), [numpy.asarray(last[0])], []
    tables = jnp.asarray(cache.table_rows([slot], 16))
    for _ in range(24):
        text.append(int(got[-1].argmax()))
        h = fw[0].apply_step_slots(
            params[0], jnp.asarray([[text[-1]]], jnp.int32),
            jnp.asarray([len(text) - 1], jnp.int32))
        h, pool = fw[1].apply_step_paged(
            params[1], h, jnp.asarray([len(text) - 1], jnp.int32),
            tables, cache.pools[1], slots=jnp.asarray([slot], jnp.int32))
        counts = numpy.asarray(pool.pop("stack"))
        cache.pools[1] = pool
        assert counts[:2].tolist() == [PASSES, 1]
        mass.append(counts[2:])
        got.append(numpy.asarray(fw[2].apply(params[2], h)[0, 0]))
    want, want_p = _reference(params, text)
    numpy.testing.assert_allclose(numpy.stack(got),
                                  want[len(prompt) - 1:], atol=1e-4)
    numpy.testing.assert_allclose(numpy.stack(mass),
                                  want_p[:, len(prompt):].T, atol=1e-5)


def test_padding_rows_write_each_cache_layers_trash_block(chain, f32):
    """A packed step of ONE live row in a bucket of 4: the padding rows
    (slot -1, an all-zero table) write block 0 of every cache layer and
    count nothing."""
    fw, params = chain
    pool = fw[1].init_cache(9, BLOCK, dtypes.compute_dtype())
    x = jnp.ones((4, 1, DIM), jnp.float32)
    tables = jnp.asarray([[3, 5], [0, 0], [0, 0], [0, 0]], jnp.int32)
    pos = jnp.asarray([5, 0, 0, 0], jnp.int32)
    _, out = fw[1].apply_step_paged(
        params[1], x, pos, tables, pool,
        slots=jnp.asarray([1, -1, -1, -1], jnp.int32))
    k = numpy.asarray(out["k"])
    written = numpy.abs(k).max(axis=-1) > 0   # [cache layer, block, row]
    assert written[:, 5, 1].all() and written[:, 0, 0].all()
    written[:, 5, 1] = written[:, 0, 0] = False
    assert not written.any()
    counts = numpy.asarray(out["stack"])
    assert counts[:2].tolist() == [PASSES, 1]
    numpy.testing.assert_allclose(counts[2:].sum(), 1, atol=1e-6)


def _counters():
    """{name, or name{labels}: value} of the process's counters."""
    from veles_tpu.telemetry import metrics
    out = {}
    for line in metrics.render_prometheus().splitlines():
        if line.startswith("veles_serving_") and "_total" in line:
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def _served(fw, prompts, steps, **kwargs):
    """-> (served tokens, the scheduler's snapshot, what the process's
    counters moved by, the scheduler)."""
    from veles_tpu.serving.scheduler import InferenceScheduler
    before = _counters()
    sched = InferenceScheduler(
        fw, window=WINDOW, block_size=BLOCK, prefill_chunk=CHUNK,
        warm_buckets=False, **kwargs).start()
    try:
        futures = [sched.submit(p, n) for p, n in zip(prompts, steps)]
        out = [list(f.result(300)) for f in futures]
        snap = sched.metrics()
    finally:
        sched.close()
    moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()}
    return [o[len(p):] if len(o) > n else o
            for o, p, n in zip(out, prompts, steps)], snap, moved, sched


@pytest.fixture(scope="module")
def served(chain):
    """Three requests of different lengths through the scheduler on TWO
    slots: the first two share packed steps, the short one finishes and
    the third takes over its slot and its blocks."""
    fw, _ = chain
    rng = numpy.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (19, 5, 16)]
    steps = [30, 6, 24]
    with _float32():
        tokens, snap, moved, sched = _served(fw, prompts, steps,
                                             max_slots=2)
    return prompts, steps, tokens, snap, moved, sched


def _gaps(params, prompts, tokens, **kwargs):
    """Per served token, how far its logit lies below the reference's
    best, the reference run over the prompt and the served tokens."""
    out = []
    for prompt, toks in zip(prompts, tokens):
        text = prompt + toks
        rows = _reference(params, text[:-1], prompt_len=len(prompt),
                          **kwargs)[0][len(prompt) - 1:]
        out += (rows.max(-1) - rows[numpy.arange(len(toks)),
                                    toks]).tolist()
    return numpy.asarray(out)


def _counter(moved, name):
    return sum(v for k, v in moved.items() if k.split("{")[0] == name)


def test_served_tokens_are_the_references_argmax(chain, served, f32):
    _, params = chain
    prompts, steps, tokens, snap, _, sched = served
    assert [len(t) for t in tokens] == steps
    assert _gaps(params, prompts, tokens).max() < 1e-4
    # the defaults that do not carry the stack were turned off
    assert snap["prefix_cache"] is False and snap["spec"] is False
    assert (sched.spec, sched.prefix_cache, sched.tp, sched.kv_dtype,
            sched.kv_host_bytes, sched.role) \
        == (False, False, 0, "fp32", 0, "both")
    assert snap["kv_bytes_per_token"] == PASSES * LAYERS * 2 * DIM * 4
    assert snap["state_bytes"] == {
        "kv": PASSES * LAYERS * 2 * (2 * 16 + 1) * BLOCK * DIM * 4,
        "conv": 0}
    assert snap["pools_in_place"] is True


def test_counters_of_the_loop_sum(served):
    """Every live row of every decode step ran all the passes, and its
    exit distribution sums to 1: mass = rows, by pass and in all."""
    *_, moved, _ = served
    rows = _counter(moved, "veles_serving_stack_rows_total")
    steps = _counter(moved, "veles_serving_steps_total")
    assert rows > 0 and _counter(
        moved, "veles_serving_stack_passes_total") == PASSES * steps
    numpy.testing.assert_allclose(
        _counter(moved, "veles_serving_stack_exit_mass_total"), rows,
        rtol=1e-5)
    before = _counter(
        moved, "veles_serving_stack_exit_mass_before_last_total")
    assert 0 < before < rows
    by_pass = [k for k, v in moved.items() if v > 0 and k.startswith(
        "veles_serving_stack_exit_mass_total{")]
    assert len(by_pass) == PASSES
    assert _counter(moved, "veles_serving_pool_copies_total") == 0


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8_in_place",))
def test_planted_fault_reads_as_wrong(chain, served, f32, fault):
    """The comparison that decides the cell's ``correct``, at this size:
    the mean gap of the served tokens under a reference with ONE fault
    planted, over the same mean for the tokens the int8 control puts
    first.  Sound, the float32 program reads 0; every fault reads over
    the limit, and so does the control in the program's place."""
    _, params = chain
    prompts, _, tokens, *_ = served
    low = []
    for prompt, toks in zip(prompts, tokens):
        text = (prompt + toks)[:-1]
        full = _reference(params, text)[0][len(prompt) - 1:]
        coarse = _reference(
            params, text, mode="int8")[0][len(prompt) - 1:].argmax(-1)
        low += (full.max(-1)
                - full[numpy.arange(len(toks)), coarse]).tolist()
    int8_mean = numpy.mean(low)
    assert int8_mean > 0
    assert _gaps(params, prompts, tokens).mean() / int8_mean < 0.01
    value = 1.0 if fault == "int8_in_place" else _gaps(
        params, prompts, tokens, fault=fault).mean() / int8_mean
    assert value > 0.5, value


def _lowered_step_size(passes, layers):
    from veles_tpu.serving.engine import _make_paged_step
    with _float32():
        fw = _chain("ouro-lowered-%d-%d" % (passes, layers),
                    passes=passes, layers=layers)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            _params(fw))
        pool = jax.ShapeDtypeStruct(
            (passes * layers, 9, BLOCK, DIM), jnp.float32)

        def vec(dtype, *shape):
            return jax.ShapeDtypeStruct((4,) + shape, dtype)
        text = jax.jit(_make_paged_step(fw)).lower(
            params, vec(jnp.int32), vec(jnp.int32), vec(jnp.int32, 2),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.uint32),
            vec(jnp.int32), vec(jnp.int32),
            {1: {"k": pool, "v": pool}}).as_text()
    return len(text.splitlines())


def test_lowered_step_holds_one_layer_body():
    """The step program's size grows neither with the passes nor with
    the layers: both are loops IN the program."""
    base = _lowered_step_size(2, 3)
    assert _lowered_step_size(4, 3) == base
    assert _lowered_step_size(4, 6) == base


# -- what the stack of cache layers is not carried through: refused in words --

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
def test_option_refused_for_a_chain_with_a_stack(chain, option):
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = chain
    with pytest.raises(ValueError, match="stack of cache layers.*"
                       "ouro_stack1"):
        InferenceScheduler(fw, max_slots=2, window=WINDOW,
                           block_size=BLOCK, prefill_chunk=CHUNK,
                           **REFUSALS[option])


def test_cache_refuses_block_moves_for_a_stack(chain, f32):
    from veles_tpu.serving.kv_slots import PagedKVCache
    fw, _ = chain
    cache = PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK)
    for move in (lambda: cache.export_blocks([1]),
                 lambda: cache.import_blocks([1], {}),
                 lambda: cache.load_staging({}, [1])):
        with pytest.raises(ValueError, match="stack of cache layers"):
            move()
    for kwargs in (dict(kv_dtype="int8"), dict(tp=object())):
        with pytest.raises(ValueError, match="ouro_stack1"):
            PagedKVCache(fw, max_slots=2, window=WINDOW,
                         block_size=BLOCK, **kwargs)


def test_window_comes_by_argument(chain):
    """A chain with no position table bounds no window: the scheduler
    asks for one, by the one argument there is."""
    from veles_tpu.serving.prefill import serving_window
    from veles_tpu.serving.scheduler import InferenceScheduler
    fw, _ = chain
    assert serving_window(fw) is None
    with pytest.raises(ValueError, match="pass window="):
        InferenceScheduler(fw, max_slots=2, block_size=BLOCK)
