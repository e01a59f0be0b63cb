"""Main-path kernels compiled for a DESCRIBED TPU v5e at real widths.

The only file that describes the chip.  The TPU compiler is installed
beside the CPU backend and compiles for a topology that is described,
not attached: a kernel the Mosaic lowering refuses (block shapes off the
(8, 128) tiling, VMEM budget, unsupported ops) fails HERE instead of on
the first chip run.  Interpret-mode parity lives with each kernel's own
tests; nothing below runs a kernel, so nothing below is a result or a
time.

The topology is described inside a module-scoped fixture and nowhere
else — only one process may load libtpu, and every xdist worker imports
every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-device executable is written to the persistent
    cache but cannot be read back without a chip (the next compile
    warns and redoes it) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _attention(grad, shape):
    from veles_tpu.ops.pallas_attention import pallas_attention
    fwd = functools.partial(pallas_attention, causal=True, backend="tpu")
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: fwd(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    else:
        fn = fwd
    return fn, [(shape, jnp.bfloat16)] * 3


def _paged(quant, k1):
    """The serving decode (K1 = 1) / verify (K1 = spec_k + 1) shapes:
    B 8, d 1024, 8 heads, block 16, a 1024-block pool, 64-block
    tables (window 1024); pools in the compute dtype or int8."""
    from veles_tpu.ops.pallas_paged import pallas_paged_attend
    b, d, heads, bs, nb, t = 8, 1024, 8, 16, 1024, 64
    pool = ((nb, bs, d), jnp.int8 if quant else jnp.bfloat16)
    args = [((b, k1, d), jnp.bfloat16), pool, pool,
            ((b, t), jnp.int32), ((b, k1), jnp.int32)]
    if quant:
        args += [((nb, bs), jnp.float32)] * 2

        def fn(q, pk, pv, tables, qpos, sk, sv):
            return pallas_paged_attend(q, pk, pv, tables, qpos, heads,
                                       scale_k=sk, scale_v=sv,
                                       backend="tpu")
    else:
        def fn(q, pk, pv, tables, qpos):
            return pallas_paged_attend(q, pk, pv, tables, qpos, heads,
                                       backend="tpu")
    return fn, args


def _int8_matmul():
    from veles_tpu.ops.gemm import int8_matmul
    return (functools.partial(int8_matmul, backend="tpu"),
            [((1, 1024), jnp.bfloat16), ((1024, 32768), jnp.int8),
             ((32768,), jnp.float32)])


def _flash():
    from veles_tpu.ops.flash import flash_attention
    return (functools.partial(flash_attention, causal=True,
                              backend="tpu"),
            [((4, 2048, 16, 128), jnp.bfloat16)] * 3)


def _routed_ffn():
    """One routed FFN at LFM2-24B-A2B's widths, a decode step's 8 rows:
    the three grouped products (``jax.lax.ragged_dot``) compile to the
    chip's own grouped-matmul calls."""
    from veles_tpu.models.lfm2 import routed_ffn
    e, d, h = 64, 2048, 1536

    def fn(u, router, bias, w1, w3, w2):
        return routed_ffn({"router": router, "expert_bias": bias,
                           "expert_w1": w1, "expert_w3": w3,
                           "expert_w2": w2}, u, 4, True, 1.0)
    return fn, [((8, d), jnp.float32), ((d, e), jnp.float32),
                ((e,), jnp.float32), ((e, d, h), jnp.bfloat16),
                ((e, d, h), jnp.bfloat16), ((e, h, d), jnp.bfloat16)]


def _mla_decode():
    """The latent decode attention at the GigaChat cell's widths: 32
    rows, 64 heads over 576-wide latent rows, a 4,097-block pool of 16,
    tables of 128 blocks."""
    from veles_tpu.ops.pallas_latent import pallas_latent_attend

    def fn(q, pool, tables, lens):
        return pallas_latent_attend(q, pool, tables, lens, 0.1, 512,
                                    backend="tpu")
    return fn, [((32, 64, 576), jnp.bfloat16),
                ((4097, 16, 576), jnp.bfloat16), ((32, 128), jnp.int32),
                ((32,), jnp.int32)]


CASES = {
    "attention_fwd_4x2048x16x128":
        functools.partial(_attention, False, (4, 2048, 16, 128)),
    "attention_grad_4x2048x16x128":
        functools.partial(_attention, True, (4, 2048, 16, 128)),
    "attention_fwd_8x1024x8x128":
        functools.partial(_attention, False, (8, 1024, 8, 128)),
    "attention_grad_8x1024x8x128":
        functools.partial(_attention, True, (8, 1024, 8, 128)),
    "paged_fp_k1": functools.partial(_paged, False, 1),
    "paged_fp_k5": functools.partial(_paged, False, 5),
    "paged_int8_k1": functools.partial(_paged, True, 1),
    "paged_int8_k5": functools.partial(_paged, True, 5),
    "int8_matmul_1x1024x32768": _int8_matmul,
    "flash_4x2048x16x128": _flash,
    "lfm2_routed_ffn_8x2048_64x1536": _routed_ffn,
    "mla_decode_32x64x576": _mla_decode,
}


#: ``name=`` of the Pallas calls (ops/pallas_attention.py)
KERNEL_NAMES = {
    "attention_fwd": ("veles_attn_fwd",),
    "attention_grad": ("veles_attn_fwd", "veles_attn_bwd_dq",
                       "veles_attn_bwd_dkv"),
    "mla_decode": ("veles_mla_decode",),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, args = CASES[case]()
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    # the attention kernels keep their names through the compiler:
    # what a device trace shows them by
    for name in KERNEL_NAMES.get(case.rsplit("_", 1)[0], ()):
        assert name in text, name


def test_attention_kernel_partitions_over_a_dp_mesh(topo,
                                                    no_compile_cache):
    """GSPMD refuses to partition a Mosaic call, so under the
    trainer's mesh ``mha_apply`` runs the kernel per shard: the d1024
    block's attention, forward and backward, global batch 8 over the
    four described chips."""
    import numpy
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from veles_tpu.models.attention import mha_apply
    mesh = Mesh(numpy.array(topo.devices), ("dp",))
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    params = {n: jax.ShapeDtypeStruct((1024, 1024), jnp.float32,
                                      sharding=rep)
              for n in ("wq", "wk", "wv", "wo")}

    def loss(params, x):
        return mha_apply(params, x, 8, True, sp_mesh=mesh,
                         backend="tpu").astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss)).lower(params, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


# -- lowered, not compiled: the serving steps at the benchmark's widths -------

def _opt67_chain(**cut):
    """The serve cell's chain (benchmark/configs/opt-6.7b-8l.json) with
    no weights made: every parameter a lazily-zero host array that
    nothing touches, so the units can say which leaves they declare.
    ``cut``: shapes to replace (fewer ``layers``, a narrower ``vocab``)
    where a test looks at neither."""
    import json
    import os

    import numpy

    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "opt-6.7b-8l.json")) as f:
        s = dict(json.load(f)["shapes"], **cut)
    d, h, v = s["dim"], s["ffn"], s["vocab"]
    spec = [{"type": "embedding", "vocab": v, "dim": d}]
    spec += [{"type": "transformer_block", "heads": s["heads"],
              "hidden": h, "causal": True}] * s["layers"]
    spec += [{"type": "token_logits", "vocab": v}]
    fw = make_forwards(
        AcceleratedWorkflow(None, name="opt67-lowering"),
        Array(numpy.zeros((1, s["positions"]), numpy.int32)), spec)
    block = {"ln1_scale": (d,), "ln1_bias": (d,), "wq": (d, d),
             "wk": (d, d), "wv": (d, d), "wo": (d, d),
             "ln2_scale": (d,), "ln2_bias": (d,), "ffn_w1": (d, h),
             "ffn_b1": (h,), "ffn_w2": (h, d), "ffn_b2": (d,)}
    layout = [{"weights": (v, d), "positions": (s["positions"], d)}] \
        + [block] * s["layers"] + [{"weights": (d, v), "bias": (v,)}]
    for unit, leaves in zip(fw, layout):
        for name, shape in leaves.items():
            getattr(unit, name).reset(numpy.zeros(shape, numpy.float32))
    return fw, s


def _abstract_params(fw, cast):
    return {i: {name: jax.ShapeDtypeStruct(
                    arr.shape, jnp.bfloat16
                    if cast and name in u.compute_dtype_params()
                    else jnp.float32)
                for name, arr in u.param_arrays().items()}
            for i, u in enumerate(fw)}


def _lower_paged_step(fw, s, params):
    from veles_tpu.serving.engine import _make_paged_step
    b, t, block, blocks = 8, 64, 16, 8 * 128 + 1
    pool = jax.ShapeDtypeStruct((blocks, block, s["dim"]), jnp.bfloat16)
    pools = {i: {"k": pool, "v": pool} for i, u in enumerate(fw)
             if hasattr(u, "init_cache")}

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct((b,) + shape, dtype)
    return b, jax.jit(_make_paged_step(fw)).lower(
        params, vec(jnp.int32), vec(jnp.int32), vec(jnp.int32, t),
        vec(jnp.float32), vec(jnp.int32), vec(jnp.uint32),
        vec(jnp.int32), vec(jnp.int32), pools)


def _lower_prefill_chunk(fw, s, params):
    from veles_tpu.serving.prefill import _make_chunk_fn
    c, width = 64, 1024
    stage = jax.ShapeDtypeStruct((1, width, s["dim"]), jnp.bfloat16)
    caches = {i: {"k": stage, "v": stage} for i, u in enumerate(fw)
              if hasattr(u, "init_cache")}
    return c, jax.jit(_make_chunk_fn(fw, width)).lower(
        params, jax.ShapeDtypeStruct((1, c), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32), caches)


def _weight_casts(text, rows, dim):
    """The converts of the lowered module that take a float32 function
    argument larger than [rows, dim] to bfloat16: a weight cast inside
    the step."""
    import math
    import re
    found = []
    for operand, shape in re.findall(
            r"stablehlo\.convert (%\w+) : \(tensor<([0-9x]+)xf32>\)"
            r" -> tensor<[0-9x]+xbf16>", text):
        size = math.prod(int(n) for n in shape.split("x"))
        if operand.startswith("%arg") and size > rows * dim:
            found.append(shape)
    return found


@pytest.mark.parametrize("lower", [_lower_paged_step,
                                   _lower_prefill_chunk])
def test_serving_step_casts_no_weight(lower):
    """With the leaves the units declare already in the compute dtype
    (what ``serving/weights.ServingWeights`` hands a running server)
    the lowered decode step and prefill chunk hold no float32 → bf16
    convert of a parameter; from float32 leaves they hold one for
    every matmul weight and table, so this would catch the mechanism
    falling silent."""
    from veles_tpu import dtypes
    assert dtypes.compute_dtype() == jnp.bfloat16
    fw, s = _opt67_chain()
    declared = sum(len(u.compute_dtype_params()) for u in fw)
    assert declared == 2 + 6 * s["layers"] + 1
    rows, lowered = lower(fw, s, _abstract_params(fw, True))
    assert _weight_casts(lowered.as_text(), rows, s["dim"]) == []
    rows, lowered = lower(fw, s, _abstract_params(fw, False))
    assert len(_weight_casts(lowered.as_text(), rows, s["dim"])) \
        == declared


# -- compiled: the programs that return the KV pools write them in place ------

def _pool_programs(one_chip):
    """{name: (jitted entry point, abstract arguments on the described
    chip, positions of the pool arguments)} at the OPT cell's pool shape
    and widths, two layers deep under a narrow head (more layers add
    nothing to see; the sampler's sort over 50272 logits alone takes
    the compiler 23 s)."""
    import math

    from veles_tpu.models.generate import _StepClosure
    from veles_tpu.serving import engine, kv_slots
    fw, s = _opt67_chain(layers=2, vocab=1024)
    d, b, t, block, blocks = s["dim"], 8, 64, 16, 8 * 128 + 1

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)
    pool = arr(jnp.bfloat16, blocks, block, d)
    pools = {i: {"k": pool, "v": pool} for i, u in enumerate(fw)
             if hasattr(u, "init_cache")}
    step = engine._paged_step_cached(
        "pools-in-place", _StepClosure(engine._make_paged_step(fw)))
    stage = arr(jnp.bfloat16, 1, 1024, d)
    return math.prod(pool.shape), {
        "paged_step": (step, on_chip((
            _abstract_params(fw, True), arr(jnp.int32, b),
            arr(jnp.int32, b), arr(jnp.int32, b, t),
            arr(jnp.float32, b), arr(jnp.int32, b), arr(jnp.uint32, b),
            arr(jnp.int32, b), arr(jnp.int32, b), pools)), 2 * len(pools)),
        "kv_insert_blocks": (kv_slots._insert_blocks, on_chip((
            pool, pool, stage, stage, arr(jnp.int32, 17),
            arr(jnp.int32))), 2),
    }


@pytest.mark.parametrize("name", ["kv_insert_blocks", "paged_step"])
def test_pools_are_written_in_place_on_v5e(name, one_chip,
                                           no_compile_cache):
    """Compiled for the described chip, the decode step and the block
    insert alias every donated pool to its output and hold no copy of
    a whole pool (PERF.md, PR 30: without the donation the step held
    one ``copy`` a pool, 41 % of its device time)."""
    import re
    size, programs = _pool_programs(one_chip)
    fn, args, n_pools = programs[name]
    text = fn.lower(*args).compile().as_text()
    head = text.split("\n", 1)[0]
    assert head.count("-alias)") == n_pools, head[:400]
    shape = r"bf16\[1025,16,4096\]"
    assert size == 1025 * 16 * 4096
    assert re.findall(r"= %s\S* copy\(" % shape, text) == []


def _solar_programs(one_chip):
    """{name: (jitted entry point, abstract arguments on the described
    chip, number of donated state leaves)} of a ``solar_open2`` chain at
    its cell's widths (benchmark/configs/solar-open2-250b-ep8.json), one
    GQA and one KDA layer deep under a narrow head, 16 rows at the
    64-block bucket: paged K/V rows beside a per-slot state of two
    arrays, the float32 one 4.2 MB a slot."""
    import json
    import os

    import numpy

    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.memory import Array
    from veles_tpu.models.generate import _StepClosure
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.serving import engine, kv_slots
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "solar-open2-250b-ep8.json")) as f:
        s = dict(json.load(f)["shapes"], vocab=1024)
    block = dict(
        type="solar_block", dim=s["dim"], hidden=s["expert_ffn"],
        heads=s["heads"], kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], n_experts=s["experts"],
        top_k=s["experts_per_token"], held=tuple(s["held"]))
    fw = make_forwards(
        AcceleratedWorkflow(None, name="solar-lowering"),
        Array(numpy.zeros((1, s["positions"]), numpy.int32)),
        [dict(type="embedding", vocab=s["vocab"], dim=s["dim"],
              learned_positions=False),
         dict(block, operator="gqa"), dict(block, operator="kda"),
         dict(type="rms_token_logits", vocab=s["vocab"])])

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree.map(lambda a: arr(a.dtype, *a.shape), tree)
    params = {0: {"weights": arr(jnp.bfloat16, s["vocab"], s["dim"])},
              3: {"embedding_norm": arr(jnp.float32, s["dim"]),
                  "weights": arr(jnp.bfloat16, s["dim"], s["vocab"])}}
    for i in (1, 2):
        params[i] = {
            name: arr(jnp.bfloat16 if name in fw[i].MATMUL_PARAMS
                      else jnp.float32, *shape)
            for name, shape in fw[i].param_shapes().items()}
    b, t, slots = 16, 64, 16
    pools = {1: abstract(jax.eval_shape(
        lambda: fw[1].init_cache(slots * t + 1, 16, jnp.bfloat16))),
        2: abstract(jax.eval_shape(
            lambda: fw[2].init_cache(slots + 1, 16, jnp.bfloat16)))}
    staged = abstract(jax.eval_shape(
        lambda: fw[2].init_cache(1, 256, jnp.bfloat16)))
    step = engine._paged_step_cached(
        "solar-in-place", _StepClosure(engine._make_paged_step(fw)))
    return {
        "paged_step": (step, (
            params, arr(jnp.int32, b), arr(jnp.int32, b),
            arr(jnp.int32, b, t), arr(jnp.float32, b),
            arr(jnp.int32, b), arr(jnp.uint32, b), arr(jnp.int32, b),
            arr(jnp.int32, b), pools), 4),
        "kv_insert_state": (kv_slots._insert_state, (
            pools[2], staged, arr(jnp.int32)), 2)}


def _on_the_chip(monkeypatch):
    """Trace the serving step as the chip runs it: its delta-rule
    layers advance their state through the Pallas call."""
    from veles_tpu.ops import pallas_delta
    monkeypatch.setattr(pallas_delta, "_use_interpret",
                        lambda backend=None: False)


def _delta_state_calls(text, pool, gathered):
    """Assert that no fusion, gather, scatter, copy or loop of a compiled
    module reads a ``pool``-shaped parameter (a float32 state pool) and
    that no ``gathered``-shaped array of the live rows' states is made;
    -> how many ``veles_delta_state`` calls the module holds."""
    import re
    params = re.findall(r"%%(\S+) = %s\S* parameter\(" % pool, text)
    assert params
    for name in params:
        readers = set(re.findall(r" ([a-z][a-z-]*)\([^()]*%%%s[,)]"
                                 % re.escape(name), text))
        assert not readers & {"fusion", "gather", "scatter", "copy",
                              "copy-start", "dynamic-slice",
                              "dynamic-update-slice", "while"}, readers
    assert not re.search(r"\b%s" % gathered, text)
    return len(re.findall(r"%veles_delta_state\S* = .*custom-call\(",
                          text))


@pytest.mark.parametrize("name", ["kv_insert_state", "paged_step"])
def test_matrix_state_is_written_in_place_on_v5e(name, one_chip,
                                                 no_compile_cache,
                                                 monkeypatch):
    """Compiled for the described chip at the cell's widths, the decode
    step of a chain that keeps a float32 matrix state a slot beside its
    paged rows, and the insert of a prefilled state, alias every donated
    leaf to its output and copy no state pool whole (74 MB a layer).
    The grouped products are the chip's own calls.  The step's state
    layer reads and writes its pool through ONE Pallas call: no gather
    of the live rows' states, no scatter back (the jnp step moved each
    state six times, ``PERF.md`` §5)."""
    import re
    _on_the_chip(monkeypatch)
    fn, args, donated = _solar_programs(one_chip)[name]
    text = fn.lower(*args).compile().as_text()
    head = text.split("\n", 1)[0]
    assert head.count("-alias)") == donated, head[:400]
    for pool in (r"f32\[17,64,128,128\]", r"bf16\[17,3,24576\]"):
        assert re.findall(r"= %s\S* copy\(" % pool, text) == []
    if name == "paged_step":
        assert "custom-call" in text
        assert _delta_state_calls(text, r"f32\[17,64,128,128\]",
                                  r"f32\[16,64,128,128\]") == 1
        # the program's named scopes ride every op's metadata: what a
        # reader of the device trace can tell the parts of a layer by
        for scope in ("veles_solar_kda_conv", "veles_solar_kda_state",
                      "veles_solar_gqa", "veles_solar_held_experts",
                      "veles_solar_shared_expert"):
            assert scope in text, scope


#: lowers the Solar cell's decode step as the chip runs it, in a process
#: of its own, and prints the digest of its lowered text
_LOWER_SOLAR_STEP = """
import hashlib, sys
sys.path[:0] = [%r, %r]
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from veles_tpu.ops import pallas_delta
pallas_delta._use_interpret = lambda backend=None: False
import test_tpu_compile
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
fn, args, _ = test_tpu_compile._solar_programs(
    SingleDeviceSharding(topo.devices[0]))["paged_step"]
text = fn.lower(*args).as_text()
assert text.count("veles_delta_state") >= 1
print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_solar_step_lowers_to_the_same_text_in_two_processes(topo):
    """The persistent compile cache keys a program on its lowered text,
    and a Pallas call's text carries its whole Mosaic module, the
    locations of the frames that traced it included: two processes
    (with other hash seeds) lowering the Solar step from the same code
    read the same text, Mosaic payload and all, or every run of the
    cell would compile its step programs again."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    code = _LOWER_SOLAR_STEP % (here, os.path.dirname(here))
    digests = []
    for seed in ("1", "2"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=seed,
                   ALLOW_MULTIPLE_LIBTPU_LOAD="1")
        run = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert run.returncode == 0, run.stderr[-2000:]
        digests.append(run.stdout.split()[-1])
    assert digests[0] == digests[1]


def test_latent_and_gdn_step_compiles_on_v5e(one_chip, no_compile_cache,
                                             monkeypatch):
    """The GigaChat cell's decode step at its widths (5 layers, a 4-row
    bucket of 8 blocks), the latent attention through its Pallas call:
    it compiles for the described chip, aliases every donated pool
    leaf, copies no GDN state pool whole, holds ONE ``veles_mla_decode``
    call and ONE ``veles_delta_state`` call a GDN layer (each the only
    reader and writer of its state pool) and carries the five named
    scopes."""
    import json
    import os
    import re

    import numpy

    from benchmark import gigachat_glue, gigachat_weights
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.ops import common, pallas_latent
    from veles_tpu.serving import engine
    monkeypatch.setattr(common, "use_interpret", lambda backend=None: False)
    monkeypatch.setattr(pallas_latent, "_use_interpret",
                        lambda backend=None: False)
    _on_the_chip(monkeypatch)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "gigachat3.5-432b-ep16.json")) as f:
        shapes = json.load(f)["shapes"]
    fw = make_forwards(AcceleratedWorkflow(None, name="gigachat-lowering"),
                       Array(numpy.zeros((1, 2048), numpy.int32)),
                       gigachat_glue.layer_spec(shapes))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = {i: {name: arr(jnp.float32 if name in gigachat_weights.FLOAT32
                            else jnp.bfloat16, *shape)
                  for name, shape in leaves.items()}
              for i, leaves in enumerate(
                  gigachat_weights.chain_layout(shapes))}
    pools = {}
    for i, u in enumerate(fw):
        if hasattr(u, "init_cache"):
            got = jax.eval_shape(lambda u=u: u.init_cache(
                5 if u.cache_kind == "slot" else 33, 16, jnp.bfloat16))
            pools[i] = {k: arr(v.dtype, *v.shape) for k, v in got.items()}
    b, t = 4, 8
    step = jax.jit(engine._make_paged_step(fw), donate_argnums=(9,))
    text = step.lower(
        params, arr(jnp.int32, b), arr(jnp.int32, b), arr(jnp.int32, b, t),
        arr(jnp.float32, b), arr(jnp.int32, b), arr(jnp.uint32, b),
        arr(jnp.int32, b), arr(jnp.int32, b), pools).compile().as_text()
    head = text.split("\n", 1)[0]
    assert head.count("-alias)") == 1 + 2 * 4, head[:400]
    for pool in (r"f32\[5,64,128,128\]", r"bf16\[5,3,16384\]"):
        assert re.findall(r"= %s\S* copy\(" % pool, text) == []
    assert len(re.findall(r"%veles_mla_decode\S* = .*custom-call\(",
                          text)) == 1
    # each of the four GDN layers advances its pool through its own call
    assert _delta_state_calls(text, r"f32\[5,64,128,128\]",
                              r"f32\[4,64,128,128\]") == 4
    for scope in ("veles_gigachat_mla", "veles_gigachat_gdn_conv",
                  "veles_gigachat_gdn_state", "veles_gigachat_held_experts",
                  "veles_gigachat_shared_expert"):
        assert scope in text, scope


def test_ring_and_full_layer_step_compiles_on_v5e(one_chip,
                                                   no_compile_cache):
    """The Command A+ cell's decode step at its widths (4 layers: three
    sliding-window layers on rings, one full layer on paged rows; a 4-row
    bucket of 8 blocks): it compiles for the described chip, aliases
    every donated pool leaf (a K and a V array a layer), copies no ring
    pool whole, and carries the four named scopes."""
    import json
    import os
    import re

    import numpy

    from benchmark import cmdaplus_glue, cmdaplus_weights
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.serving import engine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "command-a-plus-ep8.json")) as f:
        shapes = json.load(f)["shapes"]
    fw = make_forwards(AcceleratedWorkflow(None, name="cmda-lowering"),
                       Array(numpy.zeros((1, 128), numpy.int32)),
                       cmdaplus_glue.layer_spec(shapes))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layout = cmdaplus_weights.chain_layout(shapes)
    params = {i: {name: arr(jnp.float32 if name in cmdaplus_weights.FLOAT32
                            else jnp.bfloat16, *shape)
                  for name, shape in leaves.items()}
              for i, leaves in enumerate(layout[:-1])}
    params[len(fw) - 1] = {"final_norm": arr(jnp.float32, shapes["dim"]),
                           "table": params[0]["weights"]}
    pools = {}
    for i, u in enumerate(fw):
        if hasattr(u, "init_cache"):
            got = jax.eval_shape(lambda u=u: u.init_cache(
                5 if u.cache_kind == "window" else 33, 16, jnp.bfloat16))
            pools[i] = {k: arr(v.dtype, *v.shape) for k, v in got.items()}
    b, t = 4, 8
    step = jax.jit(engine._make_paged_step(fw, block_size=16),
                   donate_argnums=(9,))
    text = step.lower(
        params, arr(jnp.int32, b), arr(jnp.int32, b), arr(jnp.int32, b, t),
        arr(jnp.float32, b), arr(jnp.int32, b), arr(jnp.uint32, b),
        arr(jnp.int32, b), arr(jnp.int32, b), pools).compile().as_text()
    head = text.split("\n", 1)[0]
    assert head.count("-alias)") == 2 * 4, head[:400]
    assert re.findall(r"= bf16\[5,4096,1024\]\S* copy\(", text) == []
    for scope in ("veles_cmda_window_attn", "veles_cmda_full_attn",
                  "veles_cmda_held_experts", "veles_cmda_shared_experts"):
        assert scope in text, scope


# -- compiled: the decode steps relay no gathered rows and copy no weight ------

def _looped_program(one_chip):
    """(jitted step, abstract arguments on the described chip, number
    of pool arguments) of the looped stack (benchmark/configs/
    ouro-2.6b.json under a narrow head) at 8 rows and the 32-block
    bucket."""
    import json
    import os

    import numpy

    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.memory import Array
    from veles_tpu.models.generate import _StepClosure
    from veles_tpu.models.standard import make_forwards
    from veles_tpu.serving import engine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        s = dict(json.load(f)["shapes"], vocab=1024)
    d, n = s["dim"], s["layers"]
    fw = make_forwards(
        AcceleratedWorkflow(None, name="ouro-lowering"),
        Array(numpy.zeros((1, s["positions"]), numpy.int32)),
        [dict(type="embedding", vocab=s["vocab"], dim=d,
              learned_positions=False),
         dict(type="ouro_stack", dim=d, layers=n, passes=s["passes"],
              heads=s["heads"], hidden=s["ffn"]),
         dict(type="plain_token_logits", vocab=s["vocab"])])

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    stack = {name: arr(jnp.bfloat16 if name in fw[1].MATMUL_PARAMS
                       else jnp.float32, *shape)
             for name, shape in fw[1].param_shapes().items()}
    params = {0: {"weights": arr(jnp.bfloat16, s["vocab"], d)}, 1: stack,
              2: {"weights": arr(jnp.bfloat16, d, s["vocab"])}}
    b, t, blocks = 8, 32, 8 * 32 + 1
    pool = arr(jnp.bfloat16, s["passes"] * n, blocks, 16, d)
    step = engine._paged_step_cached(
        "looped-in-place", _StepClosure(engine._make_paged_step(fw)))
    return step, (
        params, arr(jnp.int32, b), arr(jnp.int32, b),
        arr(jnp.int32, b, t), arr(jnp.float32, b), arr(jnp.int32, b),
        arr(jnp.uint32, b), arr(jnp.int32, b), arr(jnp.int32, b),
        {1: {"k": pool, "v": pool}}), 2


def _opt67_tp2_program(topo):
    """The same for OPT's step as a cache built with ``tp=2`` runs it
    (``engine.paged_decode_step``'s GSPMD form: weights Megatron-wise,
    pools head-wise, the attention per shard), two layers deep on two
    of the described chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veles_tpu.models.generate import _StepClosure
    from veles_tpu.serving import engine
    from veles_tpu.serving.tp import HEADWISE, ServingTP
    fw, s = _opt67_chain(layers=2, vocab=1024)
    ctx = ServingTP(2, devices=topo.devices)
    b, t = 8, 64

    def arr(dtype, *shape, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(ctx.mesh, spec))
    params = {i: {name: arr(a.dtype, *a.shape,
                            spec=u.tp_param_spec(name, 2) or P()
                            if hasattr(u, "tp_param_spec") else P())
                  for name, a in layer.items()}
              for (i, layer), u in zip(
                  _abstract_params(fw, True).items(), fw)}
    pool = arr(jnp.bfloat16, 8 * 128 + 1, 16, s["dim"], spec=HEADWISE)
    pools = {i: {"k": pool, "v": pool} for i, u in enumerate(fw)
             if hasattr(u, "init_cache")}
    step = engine._paged_step_cached(
        "tp2-in-place", _StepClosure(engine._make_paged_step(
            fw, attend=ctx.decode_attention)))
    return step, (
        params, arr(jnp.int32, b), arr(jnp.int32, b),
        arr(jnp.int32, b, t), arr(jnp.float32, b), arr(jnp.int32, b),
        arr(jnp.uint32, b), arr(jnp.int32, b), arr(jnp.int32, b),
        pools), 2 * len(pools)


def _big_copies(text):
    """Shapes of the compiled module's ``copy`` ops of 2**22 elements
    or more: a pool, a stacked or transposed weight, a layer's gathered
    rows relaid (or widened) head by head."""
    import math
    import re
    copied = re.findall(r"= \w+\[([0-9,]+)\]\S* copy\(", text)
    return [shape for shape in copied
            if math.prod(int(x) for x in shape.split(",")) >= 1 << 22]


@pytest.mark.parametrize("program", ["looped", "opt67", "opt67_tp2"])
def test_decode_step_is_in_place_and_holds_no_big_copy(
        program, topo, one_chip, no_compile_cache):
    """The decode steps of both full-head chains, compiled for the
    described chip at their cells' widths (the looped stack whole, OPT
    two layers deep at 8 rows and the 64-block bucket, on one chip and
    as a mesh of two partitions it): every pool aliases its output,
    and no ``copy`` of 2**22 elements or more is left.  Each was seen: a pool (PR 30); a stacked ``[48, ...]``
    weight and a layer's widened gathered rows while the looped step
    was written (134 MB of traffic a layer application, 1.6 GB a
    step); and in OPT's step, until its attention took the looped
    stack's form, four gathered operands ``bf16[1024,8,32,128]``
    relaid head by head and a transposed ``wq`` ``bf16[4096,4096]``
    (PERF.md, PR 34).  The mesh of two sums across the chips twice a
    block, after each row-parallel product, and not a third time for
    the attention's scores (six all-reduces where the partitioner is
    left the attention: ``ServingTP.decode_attention``)."""
    import re
    if program == "looped":
        step, args, n_pools = _looped_program(one_chip)
    elif program == "opt67":
        step, args, n_pools = _pool_programs(one_chip)[1]["paged_step"]
    else:
        step, args, n_pools = _opt67_tp2_program(topo)
    text = step.lower(*args).compile().as_text()
    assert text.split("\n", 1)[0].count("-alias)") == n_pools
    assert _big_copies(text) == []
    assert len(re.findall(r" all-reduce(-start)?\(", text)) \
        == (4 if program == "opt67_tp2" else 0)
