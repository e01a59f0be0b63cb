"""chip_smoke.py's phases at tiny size on the virtual CPU devices (d 32,
2 layers, vocab 64, window 64) — the same functions the chip run calls,
so a wrong path, argument or wiring fails here and costs no chip time —
and the one-process-per-chip guards that go with it.  The ``kernels``
phase is not rehearsed: it exists to refuse anything but a real chip
(interpret-mode parity lives with each kernel's own tests, the Mosaic
compiles in test_tpu_compile.py)."""

import types

import pytest

import chip_smoke
from veles_tpu.backends import Device

TINY = dict(chip_smoke.CHIP_SIZES, vocab=64, dim=32, layers=2, heads=2,
            window=64, prompts=(4, 8, 24), steps=8)


@pytest.fixture(scope="module")
def device():
    return Device(backend="numpy")


@pytest.fixture(scope="module")
def trained(device):
    facts, wf = chip_smoke.train(device, TINY, 0)
    return facts, wf


def test_train_phase(trained, device):
    facts, wf = trained
    assert facts["steps"] == TINY["epochs"] * TINY["spans"]
    assert len(facts["losses"]) == 2 * TINY["epochs"]
    assert facts["param_devices"] == 1
    # span serving engaged: the whole run was one dispatch per span
    assert wf.loader.span_serving and wf.gd._span_step_ is not None


def test_serve_phase(trained, device):
    facts, _ = chip_smoke.serve(device, TINY, 0, trained[1].forwards)
    assert facts["requests"] == 5
    assert facts["tokens"] == 5 * TINY["steps"]


def test_dp_compare_on_four_virtual_devices(device):
    facts, _ = chip_smoke.dp_compare(device, TINY, 0, chips=4)
    assert facts["param_devices"] == 4
    assert facts["batch_shard_shape"] == [TINY["batch"] // 4,
                                          TINY["window"]]
    assert len(facts["losses_dp"]) == len(facts["losses_one_chip"])


def test_kernels_phase_refuses_interpret_mode(device):
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        chip_smoke.kernels(device, TINY, 0)


def test_main_without_a_tpu_exits_nonzero_before_any_phase(
        monkeypatch, capsys):
    def boom(*args, **kwargs):
        raise AssertionError("a phase ran without a TPU")
    monkeypatch.setattr(chip_smoke, "run_phase", boom)
    assert chip_smoke.main([]) == 1
    assert chip_smoke.main(["--chips", "4"]) == 1
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def test_launcher_refuses_local_workers_on_a_tpu_host():
    """A chip belongs to one process: the master opened it, so local
    ``-w`` workers must be refused BEFORE any is spawned."""
    from veles_tpu.launcher import Launcher
    launcher = Launcher(listen=":5999", workers=2)
    launcher.device = types.SimpleNamespace(
        jax_device=types.SimpleNamespace(platform="tpu"),
        jax_devices=[object()])
    with pytest.raises(RuntimeError, match="holds the chip"):
        launcher._spawn_workers()
    assert launcher._worker_procs == []
