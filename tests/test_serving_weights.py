"""The serving weights (``veles_tpu/serving/weights.py``): a running
server holds its matmul weights in the compute dtype, cast once when it
starts.  The step's products see the same bits; the units say which
leaves; the float32 device buffers are released and nothing is lost."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import dtypes
from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.memory import Array, Watcher

pytestmark = pytest.mark.serving

DIM, HEADS, VOCAB, WINDOW, BLOCK = 16, 2, 12, 32, 4


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


def _chain(name, blocks=1, **block_kwargs):
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name=name)
    spec = [{"type": "embedding", "vocab": VOCAB, "dim": DIM}]
    spec += [dict({"type": "transformer_block", "heads": HEADS,
                   "causal": True}, **block_kwargs)
             for _ in range(blocks)]
    spec += [{"type": "token_logits", "vocab": VOCAB}]
    fw = make_forwards(
        wf, Array(numpy.zeros((2, WINDOW), numpy.int32)), spec)
    for u in fw:
        u.initialize(device=Device(backend="numpy"))
    return fw


def _seeded_chain(name, seed, **kwargs):
    """:func:`_chain` with its weights drawn from ``seed``, not from
    where the process-wide generator stands: that is wherever the tests
    that ran before it in this worker process left it, so a test of the
    TOKENS of an untrained chain in bfloat16 met other weights under
    the six-worker run than alone, and among them some on which two
    programs of different shapes break a near tie differently (about 1
    draw in 30).  The generator is left as it was found."""
    from veles_tpu import prng
    generator = prng.get()
    with generator.preserve_state():
        generator.seed(seed)
        return _chain(name, **kwargs)


def _leaves(unit, cast):
    """The unit's parameters as device arrays: as it holds them, or
    with the leaves it declares already in the compute dtype."""
    named = frozenset(unit.compute_dtype_params()) if cast \
        else frozenset()
    return {name: jnp.asarray(arr.mem).astype(
                dtypes.compute_dtype() if name in named
                else arr.mem.dtype)
            for name, arr in unit.param_arrays().items()}


def _calls(unit, rng):
    """{method name: (function of params, the unit's other inputs)} for
    every method of ``unit`` that a server's jitted entry points call
    (serving/engine.py, serving/prefill.py, openai_api.embed_pool)."""
    cd = dtypes.compute_dtype()
    b, k1, c = 2, 3, 8
    toks = jnp.asarray(rng.integers(0, VOCAB, (b, c)), jnp.int32)
    pos = jnp.asarray([5, 2], jnp.int32)
    if hasattr(unit, "vocab") and hasattr(unit, "positions"):
        return {
            "apply": lambda p: unit.apply(p, toks),
            "apply_chunk": lambda p: unit.apply_chunk(
                p, toks, jnp.int32(8)),
            "apply_step_slots": lambda p: unit.apply_step_slots(
                p, toks[:, :1], pos),
            "apply_verify_slots": lambda p: unit.apply_verify_slots(
                p, toks[:, :k1], pos)}
    x = jnp.asarray(rng.standard_normal((b, c, DIM)), cd)
    if not hasattr(unit, "init_cache"):
        return {"apply": lambda p: unit.apply(p, x)}
    blocks = WINDOW // BLOCK
    tables = jnp.asarray(
        1 + numpy.arange(b * blocks).reshape(b, blocks), jnp.int32)
    lens = jnp.asarray([c, c - 3], jnp.int32)

    def filled(shape):
        return jnp.asarray(rng.standard_normal(shape), cd)
    pool = {n: filled(a.shape) for n, a in unit.init_block_pool(
        1 + b * blocks, BLOCK, cd).items()}
    cache = {n: filled(a.shape)
             for n, a in unit.init_cache(b, WINDOW, cd).items()}
    return {
        "apply_step_paged": lambda p: unit.apply_step_paged(
            p, x[:, :1], pos, tables, pool),
        "apply_prefill": lambda p: unit.apply_prefill(
            p, x, unit.init_cache(b, WINDOW, cd), lens=lens),
        "apply_prefill_chunk": lambda p: unit.apply_prefill_chunk(
            p, x, cache, jnp.int32(8), chunk_lens=lens,
            key_width=16),
        "apply_verify_paged": lambda p: unit.apply_verify_paged(
            p, x[:, :k1], pos, jnp.asarray([k1, 1], jnp.int32),
            tables, pool)}


STEP_METHODS = [
    (0, "apply"), (0, "apply_chunk"), (0, "apply_step_slots"),
    (0, "apply_verify_slots"), (1, "apply_step_paged"),
    (1, "apply_prefill"), (1, "apply_prefill_chunk"),
    (1, "apply_verify_paged"), (2, "apply")]


@pytest.mark.parametrize("index,method", STEP_METHODS)
def test_precast_leaves_give_the_same_bits(index, method):
    """(a) Embedding, block and head: every method the server calls
    returns bit-identical arrays from float32 leaves and from the
    declared leaves already in the compute dtype."""
    assert dtypes.compute_dtype() == jnp.bfloat16
    unit = _chain("bits-%d-%s" % (index, method))[index]
    assert unit.compute_dtype_params()
    call = jax.jit(_calls(unit, numpy.random.default_rng(3))[method])
    plain = jax.tree_util.tree_leaves(call(_leaves(unit, False)))
    cast = jax.tree_util.tree_leaves(call(_leaves(unit, True)))
    assert len(plain) == len(cast) and plain
    for a, b in zip(plain, cast):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert numpy.asarray(a).tobytes() == numpy.asarray(b).tobytes()


def _convert_only(unit):
    """The leaves of ``unit`` that the jaxprs of its server-side
    methods use at all, and use ONLY as the operand of a
    ``convert_element_type`` to the compute dtype."""
    from jax.extend.core import Var
    cd = numpy.dtype(dtypes.compute_dtype())
    params = _leaves(unit, False)
    names = sorted(params)          # a dict flattens by sorted key
    used, other = set(), set()
    for call in _calls(unit, numpy.random.default_rng(4)).values():
        jaxpr = jax.make_jaxpr(call)(params).jaxpr
        leaf = dict(zip(jaxpr.invars, names))
        assert len(leaf) == len(names)
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, Var) or v not in leaf:
                    continue
                if eqn.primitive.name == "convert_element_type" \
                        and eqn.params["new_dtype"] == cd:
                    used.add(leaf[v])
                else:
                    other.add(leaf[v])
        other.update(leaf[v] for v in jaxpr.outvars
                     if isinstance(v, Var) and v in leaf)
    return used - other


def _quantized(fw):
    fw[1].quantize_weights()
    return fw


DECLARATIONS = [
    ("embedding", lambda: _chain("decl-emb"), 0,
     {"weights", "positions"}),
    ("block", lambda: _chain("decl-block"), 1,
     {"wq", "wk", "wv", "wo", "ffn_w1", "ffn_w2"}),
    ("head", lambda: _chain("decl-head"), 2, {"weights"}),
    ("block_weights_int8", lambda: _quantized(_chain("decl-w8")), 1,
     set()),
    ("block_int8_decode",
     lambda: _chain("decl-w8d", int8_decode=True), 1, set()),
    ("block_moe", lambda: _chain("decl-moe", n_experts=2, top_k=1), 1,
     set()),
]


@pytest.mark.parametrize("name,build,index,expect",
                         DECLARATIONS, ids=[d[0] for d in DECLARATIONS])
def test_declared_leaves_are_the_convert_only_ones(name, build, index,
                                                   expect):
    """(b) What a unit declares is what its traced code reads only
    through ``.astype(compute_dtype())``: exactly that set for the
    dense embedding, block and head; never more for the variants that
    declare none (int8 checkpoint weights, in-trace int8 decode,
    MoE)."""
    unit = build()[index]
    declared = set(unit.compute_dtype_params())
    assert declared == expect
    found = _convert_only(unit)
    assert declared <= found
    if expect:
        assert declared == found


@pytest.mark.parametrize("index", [0, 1, 2])
def test_float32_compute_declares_none(f32, index):
    """(b) Under float32 compute nothing is named, and the pytree is
    the units' own buffers: nothing copied, nothing released."""
    from veles_tpu.serving import ServingWeights
    fw = _chain("decl-f32-%d" % index)
    assert fw[index].compute_dtype_params() == ()
    assert _convert_only(fw[index]) == set()
    weights = ServingWeights(fw)
    try:
        assert weights.leaves_cast == 0
        assert list(weights.bytes_by_dtype) == ["float32"]
        for name, arr in fw[index].param_arrays().items():
            assert weights.params[index][name] is arr._devmem_
    finally:
        weights.close()


def _resident(fw):
    return sum(arr._devmem_.nbytes for u in fw
               for arr in u.param_arrays().values()
               if arr._devmem_ is not None)


@pytest.mark.parametrize("held", ["host", "device_only"])
def test_scheduler_serves_from_compute_dtype_leaves(held):
    """(c) A scheduler on the tiny chain serves what ``generate``
    serves, from compute-dtype leaves, with the float32 device
    buffers released; every float32 value is still on the units once
    it has closed.  ``device_only``: the device held the only current
    copy, as after training or the benchmark's hand-over."""
    from veles_tpu.models.generate import generate
    from veles_tpu.serving import InferenceScheduler
    # seed 11: the smallest margin between the first and the second
    # logit along both served texts is 0.18, against a bfloat16 noise
    # of about 0.01 at these sizes
    fw = _seeded_chain("served-" + held, 11, blocks=2)
    original = {(i, n): numpy.array(a.mem)
                for i, u in enumerate(fw)
                for n, a in u.param_arrays().items()}
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    want = [numpy.asarray(generate(
        fw, numpy.asarray([p], numpy.int32), 6))[0].tolist()
        for p in prompts]
    if held == "device_only":
        for (i, n), value in original.items():
            arr = fw[i].param_arrays()[n]
            arr.devmem = jnp.asarray(value) + 0     # a program's output
            arr._mem = numpy.zeros_like(value)      # a stale mirror
    named = {(i, n) for i, u in enumerate(fw)
             for n in u.compute_dtype_params()}
    assert len(named) == 2 + 6 * 2 + 1
    sch = InferenceScheduler(fw, max_slots=2, window=WINDOW,
                             block_size=BLOCK, spec=False,
                             warm_buckets=False).start()
    try:
        got = [sch.submit(p, 6, seed=0).result(240) for p in prompts]
        snap = sch.metrics()
        for (i, n) in original:
            leaf = sch.weights_.params[i][n]
            arr = fw[i].param_arrays()[n]
            if (i, n) in named:
                assert leaf.dtype == jnp.bfloat16
                assert arr._devmem_ is None
            else:
                assert leaf is arr._devmem_
                assert leaf.dtype == jnp.float32
        assert sch.weights_.leaves_cast == len(named)
    finally:
        sch.close()
    assert got == want
    assert snap["weights_dtype"] == "bfloat16"
    assert sch.weights_ is None
    for (i, n), value in original.items():
        arr = fw[i].param_arrays()[n]
        arr.map_read()
        assert arr.mem.dtype == numpy.float32
        numpy.testing.assert_array_equal(arr.mem, value)


def test_weight_series_on_metrics():
    """The counter and the gauge the mechanism brings: bytes by stored
    dtype while the server runs, 0 once it has closed; leaves cast."""
    from veles_tpu.serving import InferenceScheduler
    from veles_tpu.telemetry import metrics

    def series(name, replica=None):
        out = {}
        for line in metrics.render_prometheus().splitlines():
            if line.startswith(name) and (
                    replica is None or 'replica="%s"' % replica in line):
                key, _, value = line.rpartition(" ")
                out[key] = float(value)
        return out
    fw = _chain("series", blocks=2)
    cast_before = sum(series(
        "veles_serving_weight_leaves_cast_total").values())
    sch = InferenceScheduler(fw, max_slots=2, window=WINDOW,
                             block_size=BLOCK, spec=False,
                             warm_buckets=False,
                             replica_id="series-replica").start()
    try:
        held = series("veles_serving_weight_bytes", "series-replica")
        by_dtype = {k.split('dtype="')[1].split('"')[0]: v
                    for k, v in held.items()}
        assert by_dtype == {k: float(v) for k, v
                            in sch.weights_.bytes_by_dtype.items()}
        assert set(by_dtype) == {"bfloat16", "float32"}
        assert by_dtype["bfloat16"] == 2 * sum(
            fw[i].param_arrays()[n].size for i, u in enumerate(fw)
            for n in type(u).MATMUL_PARAMS)
        assert sum(series(
            "veles_serving_weight_leaves_cast_total").values()) \
            == cast_before + 15
    finally:
        sch.close()
    assert set(series("veles_serving_weight_bytes",
                      "series-replica").values()) == {0.0}


class _Rows(FullBatchLoader):
    def load_data(self):
        self.class_lengths[:] = [0, 0, 16]
        self.original_data = numpy.random.default_rng(0).integers(
            0, VOCAB, (16, WINDOW)).astype(numpy.int32)
        self.original_labels = [0] * 16


def _trainable(name):
    """The tiny chain inside a trainer (bench._spec_trained_chain's
    construction): (forwards, loader, gd)."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.models.evaluator import EvaluatorNextToken
    from veles_tpu.models.gd import GradientDescent
    from veles_tpu.models.standard import make_forwards
    dev = Device(backend="numpy")
    wf = AcceleratedWorkflow(None, name=name)
    loader = _Rows(wf, minibatch_size=4, normalization_type="none")
    loader.initialize(device=dev)
    spec = [{"type": "embedding", "vocab": VOCAB, "dim": DIM},
            {"type": "transformer_block", "heads": HEADS,
             "causal": True},
            {"type": "token_logits", "vocab": VOCAB}]
    fw = make_forwards(wf, loader.minibatch_data, spec)
    for u in fw:
        u.initialize(device=dev)
    ev = EvaluatorNextToken(wf)
    ev.output = fw[-1].output
    ev.tokens = loader.minibatch_data
    ev.loader = loader
    ev.initialize(device=dev)
    gd = GradientDescent(wf, forwards=fw, evaluator=ev, loader=loader,
                         solver="sgd", learning_rate=0.05)
    gd.initialize(device=dev)
    return fw, loader, gd


def test_close_frees_the_weights_and_training_goes_on():
    """(d) Train, serve, train in one process: while the server runs
    the Watcher counts the compute-dtype twins in place of the float32
    buffers; after ``close()`` it counts what the units hold and no
    more; a ``gd`` step on the same units then steps float32
    parameters from the values the server read."""
    from veles_tpu.serving import InferenceScheduler
    fw, loader, gd = _trainable("train-serve-train")
    try:
        loader.run()
        gd.run()
        gd.loss.map_read()
        arrays = [a for u in fw for a in u.param_arrays().values()]
        for arr in arrays:
            arr.devmem
        base = Watcher.total() - _resident(fw)      # everything else
        trained = [numpy.array(a.map_read().mem) for a in arrays]
        full = _resident(fw)
        named = sum(u.param_arrays()[n].nbytes for u in fw
                    for n in u.compute_dtype_params())
        assert named and full == sum(a.nbytes for a in arrays)
        sch = InferenceScheduler(fw, max_slots=2, window=WINDOW,
                                 block_size=BLOCK, spec=False,
                                 warm_buckets=False).start()
        try:
            sch.submit([3, 1, 4], 4, seed=0).result(240)
            assert _resident(fw) == full - named
            assert Watcher.total() - base == full - named + named // 2
        finally:
            sch.close()
        assert Watcher.total() - base == _resident(fw) == full - named
        loader.run()
        gd.run()
        gd.loss.map_read()
        assert _resident(fw) == full
        moved = 0
        for arr, before in zip(arrays, trained):
            assert arr.devmem.dtype == jnp.float32
            after = arr.map_read().mem
            assert numpy.isfinite(after).all()
            # one SGD step from the served values: small, not a reset
            assert numpy.abs(after - before).max() < 0.5
            moved += int((after != before).any())
        assert moved >= len(arrays) - 2
    finally:
        loader.stop()
