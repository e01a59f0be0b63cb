"""int8 weight checkpoints (``models/transformer.quantize_weights``,
``snapshotter weights_dtype="int8"``, the CE quality gate in
``serving/kv_quality.weight_quant_quality``): quantized chains serve
with spec-on == spec-off bit-parity, the gate's CE delta stays
within the declared tolerance, per-chip weight bytes actually drop,
the transform is idempotent and export_config-visible (so the
engine's executable cache splits fp32/int8 chains), and the
snapshot import path quantizes at load time."""

import numpy
import pytest

from veles_tpu.config import root
from veles_tpu.serving.kv_quality import WEIGHT_QUANT_CE_TOLERANCE

pytestmark = pytest.mark.spec


@pytest.fixture
def f32():
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    yield
    root.common.precision.compute_dtype = saved


@pytest.fixture(scope="module")
def w8_chain():
    """A module-OWNED trained tiny chain (the session fixture must
    stay f32 — the gate quantizes in place).  Trained under f32 at
    the conftest sizes, then gated + quantized ONCE; the tests below
    read the record and serve the quantized chain."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import _spec_trained_chain
    from veles_tpu.backends import Device
    from veles_tpu.models.generate import _device_params
    from veles_tpu.serving import per_chip_bytes, weight_quant_quality
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    try:
        pattern = [3, 1, 4, 1, 5, 9, 2, 6]
        fw = _spec_trained_chain(
            Device(backend="numpy"), 16, 2, 2, 12, 64, 8,
            pattern, 12, "w8-trained")
        bytes_fp32 = per_chip_bytes(_device_params(fw))
        seqs = [(pattern * 10)[:64],
                numpy.random.RandomState(0).randint(
                    0, 12, size=64).tolist()]
        rec = weight_quant_quality(fw, seqs, block_size=16)
        bytes_int8 = per_chip_bytes(_device_params(fw))
    finally:
        root.common.precision.compute_dtype = saved
    yield fw, rec, bytes_fp32, bytes_int8


def test_weight_quant_gate(w8_chain):
    """The CE delta of the quantized chain vs its f32 self must sit
    within the declared tolerance, and the record carries the
    fields quality.py stores."""
    _, rec, _, _ = w8_chain
    assert rec["weight_quant_within_tolerance"], rec
    assert rec["weight_quant_ce_delta"] <= WEIGHT_QUANT_CE_TOLERANCE
    assert rec["weight_quant_blocks"] == 2
    assert rec["weight_quant_positions"] > 0


def test_weight_bytes_drop_and_idempotent(w8_chain):
    """int8 storage must actually shrink the device footprint
    (~4x on the matmul weights — int8 payload + one f32 scale per
    output column), re-quantizing is a no-op, and export_config
    carries the format so ``_arch_sig`` splits the executable
    caches."""
    fw, _, bytes_fp32, bytes_int8 = w8_chain
    assert bytes_int8 < 0.6 * bytes_fp32, (bytes_fp32, bytes_int8)
    block = fw[1]
    n_params = len(block.PARAMS)
    block.quantize_weights()         # idempotent
    assert len(block.PARAMS) == n_params
    assert block.export_config()["weights_int8"] is True
    assert block.wq.mem.dtype == numpy.int8
    assert block.wq_scale.mem.dtype == numpy.float32


def test_w8_spec_parity(f32, w8_chain):
    """ON the quantized chain, spec-on streams stay bit-identical
    to spec-off (greedy and seeded): the dequantized matmuls are
    deterministic, so the verify contract holds unchanged."""
    from veles_tpu.serving import InferenceScheduler
    fw, _, _, _ = w8_chain
    prompts = [[3, 1, 4, 1, 5, 9], [2, 6, 3, 1]]
    submits = [(p, 10, dict(seed=0)) for p in prompts]
    submits += [(p, 8, dict(temperature=0.9, top_k=5, seed=7))
                for p in prompts]

    def run(**kw):
        sch = InferenceScheduler(fw, max_slots=3, window=64,
                                 warm_buckets=False,
                                 block_size=4, prefill_chunk=0,
                                 **kw).start()
        try:
            futs = [sch.submit(p, steps, **skw)
                    for p, steps, skw in submits]
            outs = [f.result(240) for f in futs]
            sch.check_kv()
            return outs
        finally:
            sch.close()

    assert run(spec=False) == run(spec=True, spec_k=4)


def test_moe_rejected():
    """MoE blocks (expert-sharded weights) must refuse the int8
    checkpoint format loudly instead of mangling expert tensors."""
    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
    from veles_tpu.models.standard import make_forwards
    wf = AcceleratedWorkflow(None, name="w8-moe")
    fw = make_forwards(
        wf, Array(numpy.zeros((2, 16), numpy.int32)),
        [{"type": "embedding", "vocab": 8, "dim": 8},
         {"type": "transformer_block", "heads": 2, "causal": True,
          "n_experts": 2},
         {"type": "token_logits", "vocab": 8}])
    dev = Device(backend="numpy")
    for u in fw:
        u.initialize(device=dev)
    with pytest.raises(ValueError):
        fw[1].quantize_weights()


class _FakeBlock:
    def __init__(self):
        self.quantized = 0

    def quantize_weights(self):
        self.quantized += 1


class _FakeWorkflow:
    def __init__(self):
        self.units = [_FakeBlock(), object()]


def test_snapshot_import_quantizes(tmp_path):
    """``SnapshotterToFile.import_file(path, weights_dtype="int8")``
    quantizes every unit exposing ``quantize_weights`` at LOAD time —
    the on-disk pickle stays f32 — and rejects unknown dtypes."""
    import pickle
    from veles_tpu.snapshotter import SnapshotterToFile

    path = str(tmp_path / "snap.pickle")
    with open(path, "wb") as f:
        pickle.dump(_FakeWorkflow(), f)
    obj = SnapshotterToFile.import_file(path)
    assert obj.units[0].quantized == 0
    obj = SnapshotterToFile.import_file(path, weights_dtype="int8")
    assert obj.units[0].quantized == 1
    obj = SnapshotterToFile.import_file(path, weights_dtype="fp32")
    assert obj.units[0].quantized == 0
    with pytest.raises(ValueError):
        SnapshotterToFile.import_file(path, weights_dtype="int4")
