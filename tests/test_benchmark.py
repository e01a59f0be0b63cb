"""Tier-1 collects the benchmark's own tests (``benchmark/tests``: the
yardstick's arithmetic, the manifest's lint, the reference, whole tiny
runs of both drivers, sound and broken): the lint guards every entry a
PR adds to ``BENCHMARK.json``."""

from benchmark.tests.test_benchmark import *  # noqa: F401,F403
