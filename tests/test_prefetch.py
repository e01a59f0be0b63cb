"""Asynchronous input pipeline tests (loader/prefetch.py).

The pipeline's contract is EXACT equivalence with the synchronous
serving path: bit-identical trained weights, an identical
Decision-observed flag sequence, clean teardown on halt and mid-epoch
exceptions, and ``depth=0`` degrading to the synchronous path — plus
the actual point of it all: the trainer's input wait collapses when a
slow host decode overlaps device compute.
"""

import threading
import time

import numpy
import pytest

from veles_tpu import prng as prng_mod
from veles_tpu.backends import Device
from veles_tpu.loader.base import Loader, TRAIN
from veles_tpu.models.decision import DecisionGD
from veles_tpu.models.standard import build_mlp_classifier
from veles_tpu.workflow import Workflow


class StreamLoader(Loader):
    """Deterministic streaming loader (NOT a FullBatchLoader: every
    minibatch goes through fill_minibatch on the host, like the
    image/text/hdf5 loaders)."""

    def __init__(self, workflow, n_valid=20, n_train=70, features=8,
                 classes=3, decode_ms=0.0, fail_after=None, **kwargs):
        super(StreamLoader, self).__init__(workflow, **kwargs)
        self.sizes = (0, n_valid, n_train)
        self.features = features
        self.classes = classes
        self.decode_ms = decode_ms
        #: raise after this many fills (mid-epoch crash simulation).
        #: A mutable box: the prefetch worker runs fill_minibatch
        #: against a stage view whose attribute WRITES stay local, so
        #: a plain counter attribute would never advance
        self.fail_after = fail_after
        self.fill_counter = [0]

    def load_data(self):
        total = sum(self.sizes)
        self.class_lengths[:] = list(self.sizes)
        rng = numpy.random.default_rng(0)
        self._base = rng.normal(
            size=(total, self.features)).astype(numpy.float32)
        self._base[:, 0] = numpy.arange(total)
        self._lab = (numpy.arange(total) % self.classes).astype(
            numpy.int32)

    def create_minibatch_data(self):
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size, self.features), numpy.float32))

    def fill_minibatch(self):
        self.fill_counter[0] += 1
        if self.fail_after is not None \
                and self.fill_counter[0] > self.fail_after:
            raise RuntimeError("injected decode failure")
        if self.decode_ms:
            time.sleep(self.decode_ms / 1e3)
        idx = self.minibatch_indices.mem[:self.minibatch_size]
        self.minibatch_data.mem[:self.minibatch_size] = self._base[idx]
        self.minibatch_labels.mem[:self.minibatch_size] = \
            self._lab[idx]


#: threads alive before the test: another test file of this xdist
#: worker may have driven ``loader.run()`` by hand and never stopped its
#: loader, and its pipeline is not this file's to judge
_OTHERS = set()


@pytest.fixture(autouse=True)
def _threads_of_other_tests():
    _OTHERS.clear()
    _OTHERS.update(threading.enumerate())


def _prefetch_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("prefetch-") and t not in _OTHERS]


def _reseed():
    for key, seed in (("default", 42), ("loader", 7), ("trainer", 5)):
        prng_mod.get(key).seed(seed)


def _train(prefetch, max_epochs=3, minibatch_size=32, **loader_kw):
    """One full training run on the streaming loader; returns the
    per-wave flag/attr sequence the Decision unit observed and the
    final weights."""
    _reseed()
    dev = Device(backend="numpy")
    wf = Workflow(None, name="wf-prefetch-%s" % prefetch)
    loader = StreamLoader(wf, minibatch_size=minibatch_size,
                          prefetch=prefetch,
                          name="stream-%s" % prefetch, **loader_kw)
    _, layers, _, gd = build_mlp_classifier(
        dev, loader, hidden=(16,), classes=3, workflow=wf,
        gradient_moment=0.9)
    decision = DecisionGD(wf, max_epochs=max_epochs)
    decision.loader = loader
    decision.trainer = gd
    decision.initialize()
    seq = []
    for _ in range(1000):
        if decision.complete:
            break
        loader.run()
        gd.run()
        decision.run()
        seq.append((loader.minibatch_class, loader.minibatch_size,
                    loader.minibatch_offset, loader.epoch_number,
                    bool(loader.last_minibatch),
                    bool(loader.epoch_ended),
                    bool(loader.train_ended)))
    weights = []
    for u in layers:
        for arr in u.param_arrays().values():
            arr.map_read()
            weights.append(numpy.array(arr.mem))
    metrics = dict(decision.epoch_metrics)
    loader.stop()
    return seq, weights, metrics


def test_bit_exact_weights_and_flag_parity():
    """Prefetch on vs off: identical Decision-observed flag sequence
    AND bit-identical trained weights over multi-epoch streaming
    training (tail minibatches included: 70 train / 20 valid @ 32)."""
    seq_off, w_off, m_off = _train(prefetch=0)
    seq_on, w_on, m_on = _train(prefetch=3)
    assert seq_off == seq_on
    assert len(seq_off) > 6  # multi-epoch, multi-class walk
    assert len(w_off) == len(w_on)
    for a, b in zip(w_off, w_on):
        assert numpy.array_equal(a, b)  # BIT-identical, not allclose
    assert m_off == m_on


def test_depth_zero_is_synchronous():
    wf = Workflow(None, name="wf")
    loader = StreamLoader(wf, minibatch_size=32, prefetch=0)
    loader.initialize()
    loader.run()
    assert loader.prefetch_ is False  # decided off, no pipeline
    assert not _prefetch_threads()


def test_failed_minibatches_force_sync():
    """Refiled distributed minibatches cannot be produced ahead —
    the loader must fall back to the synchronous path."""
    wf = Workflow(None, name="wf")
    loader = StreamLoader(wf, minibatch_size=32, prefetch=2)
    loader.initialize()
    loader.failed_minibatches.append((32, 32))
    loader.run()
    assert loader.prefetch_ is False


def test_prefetch_engages_and_overlaps():
    """The tier-1-safe overlap smoke test: a slow decode (15 ms) with
    simulated downstream work — with prefetch the trainer's measured
    input wait collapses (the decode runs during the simulated step),
    without it every wave pays the full decode."""
    from veles_tpu.telemetry import metrics

    def waves(prefetch, label):
        wf = Workflow(None, name=label)
        loader = StreamLoader(wf, minibatch_size=32, n_valid=0,
                              n_train=320, decode_ms=15.0,
                              prefetch=prefetch, name=label)
        loader.initialize()
        for _ in range(12):
            loader.run()
            time.sleep(0.015)   # the device step the decode overlaps
        loader.stop()
        hist = metrics.histogram(
            "veles_input_wait_seconds",
            labelnames=("loader", "mode")).labels(
            label, "prefetch" if prefetch else "sync")
        return hist.summary()

    sync = waves(0, "overlap-sync")
    pf = waves(2, "overlap-prefetch")
    assert pf["sum"] < 0.5 * sync["sum"], (sync, pf)
    assert not _prefetch_threads()


def test_mid_epoch_exception_clean_shutdown():
    """A decode crash inside the worker re-raises on the MAIN thread
    at the next pop, and the pipeline tears itself down first — the
    flight recorder's thread dump must show no orphaned workers."""
    wf = Workflow(None, name="wf")
    loader = StreamLoader(wf, minibatch_size=32, prefetch=2,
                          fail_after=4, name="crashy")
    loader.initialize()
    with pytest.raises(RuntimeError, match="injected decode failure"):
        for _ in range(20):
            loader.run()
    deadline = time.time() + 5.0
    while _prefetch_threads() and time.time() < deadline:
        time.sleep(0.05)
    assert not _prefetch_threads()
    loader.stop()  # idempotent after the eager close


def test_halt_teardown_joins_workers():
    """Workflow halt (stop()) joins the pipeline threads promptly
    even mid-decode."""
    wf = Workflow(None, name="wf")
    loader = StreamLoader(wf, minibatch_size=32, decode_ms=20.0,
                          prefetch=3, name="halty")
    loader.initialize()
    for _ in range(3):
        loader.run()
    assert loader.prefetch_ not in (None, False)
    assert loader.prefetch_.alive
    wf.stop()   # the halt path: Workflow.stop -> every unit's stop()
    deadline = time.time() + 5.0
    while _prefetch_threads() and time.time() < deadline:
        time.sleep(0.05)
    assert not _prefetch_threads()
    assert loader.prefetch_ is None


def test_prefetched_devmem_is_ready_on_device():
    """The trainer-facing contract: after a prefetched wave the
    minibatch Arrays hold an already-on-device handle that matches
    the host mirror (no re-upload on .devmem)."""
    wf = Workflow(None, name="wf")
    loader = StreamLoader(wf, minibatch_size=32, prefetch=2)
    loader.initialize()
    for _ in range(5):
        loader.run()
        dev = loader.minibatch_data._devmem_
        assert dev is not None   # installed at pop, not lazily
        assert numpy.array_equal(numpy.asarray(dev),
                                 loader.minibatch_data.mem)
    loader.stop()


def test_shuffle_parity_across_epochs():
    """The shadow shuffle replays onto loader.shuffled_indices at the
    first batch of each epoch — served train indices must match the
    synchronous run's across a reshuffle boundary."""

    def run(prefetch, epochs=3):
        _reseed()
        wf = Workflow(None, name="wf")
        l = StreamLoader(wf, minibatch_size=32, prefetch=prefetch,
                         name="shuf-%s" % prefetch)
        l.initialize()
        orders = []
        for _ in range(200):
            l.run()
            if l.minibatch_class == TRAIN:
                orders.append(numpy.array(
                    l.minibatch_indices.mem[:l.minibatch_size]))
            if l.train_ended and l.epoch_number >= epochs:
                break
        l.stop()
        return orders

    off = run(0)
    on = run(2)
    assert len(off) == len(on)
    for a, b in zip(off, on):
        assert numpy.array_equal(a, b)
