"""Test harness config.

All tests run on CPU with 8 virtual XLA devices so mesh/sharding tests
exercise real multi-device code paths without TPU hardware
(SURVEY.md §4: the JAX equivalent of the reference's loopback
master+slave-in-one-process tests, veles/tests/test_network.py:52-149).
"""

import os
import sys

import pytest

# hard-set, not setdefault: the ambient environment may select the TPU
# and tests must stay on virtual CPUs
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()


@pytest.fixture(scope="session")
def spec_trained_chain():
    """ONE briefly-trained tiny LM chain for the WHOLE session
    (bench._spec_trained_chain at the test_kv_quant sizes: d=16,
    2 layers, 2 heads, vocab 12, window 64, trained 12 steps to
    continue a cyclic pattern) — shared by test_spec, test_kv_quant
    and test_tp so tier-1 trains it once instead of per test.
    Yields ``(forwards, pattern)``; the weights are frozen after
    training (schedulers only read them), so any number of tests may
    build schedulers over the same chain, and identical param shapes
    mean they all share the compiled step executables too.  Trains
    under f32 so the downstream parity/quality assertions see the
    same weights the pre-fixture tests trained."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import _spec_trained_chain
    from veles_tpu.backends import Device
    from veles_tpu.config import root
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    try:
        pattern = [3, 1, 4, 1, 5, 9, 2, 6]
        fw = _spec_trained_chain(
            Device(backend="numpy"), 16, 2, 2, 12, 64, 8,
            [p % 12 for p in pattern], 12, "session-trained")
    finally:
        # restore BEFORE yielding — a session fixture's teardown
        # runs at session END, and holding f32 for the rest of the
        # run would contaminate every bf16-default test after the
        # first user; consumers pin their own f32 fixture per test
        root.common.precision.compute_dtype = saved
    yield fw, pattern


@pytest.fixture(scope="session")
def spec_trained_head(spec_trained_chain):
    """ONE trained Medusa draft head (k=4) over the session chain,
    fit on the same cyclic pattern the chain learned — shared by
    test_draft and test_tp so tier-1 trains it once.  Frozen after
    training (schedulers only call ``propose``), trained under f32
    to match the chain's weights."""
    import numpy
    from veles_tpu.config import root
    from veles_tpu.serving import MedusaDraftHead
    fw, pattern = spec_trained_chain
    saved = root.common.precision.get("compute_dtype", "bfloat16")
    root.common.precision.compute_dtype = "float32"
    try:
        head = MedusaDraftHead.from_chain(fw, 4, seed=0)
        corpus = numpy.asarray(
            ([p % 12 for p in pattern] * 40)[:256])
        losses = head.train(fw, corpus, steps=40, batch=8, window=32)
    finally:
        root.common.precision.compute_dtype = saved
    yield head, losses


def pytest_runtest_protocol(item, nextitem):
    """Single retry for ``@pytest.mark.flaky`` tests — the quarantine
    for KNOWN environment flakes (jax-0.4.37 XLA:CPU nondeterminism,
    see ROUND6_NOTES.md; 1-core wall-clock ratio gates), so fleet
    soaks get a stable tier-1 signal.  The first attempt runs unlogged; only a failure
    triggers the one rerun (full setup/teardown), whose reports are
    what the terminal and exit code see.  Anything without the marker
    takes the stock protocol."""
    if item.get_closest_marker("flaky") is None:
        return None
    from _pytest.runner import runtestprotocol
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for report in reports:
        item.ihook.pytest_runtest_logreport(report=report)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
