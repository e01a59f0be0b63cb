"""Tier-1 collects the benchmark's own tests (``benchmark/tests``: the
yardstick's arithmetic, the manifest's lint, the references, whole tiny
runs of the drivers, sound and broken): the lint guards every entry a
PR adds to ``BENCHMARK.json``."""

from benchmark.tests.test_benchmark import *  # noqa: F401,F403
from benchmark.tests.test_lfm2 import *  # noqa: F401,F403
from benchmark.tests.test_ouro import *  # noqa: F401,F403
from benchmark.tests.test_solar import *  # noqa: F401,F403
from benchmark.tests.test_gigachat import *  # noqa: F401,F403
from benchmark.tests.test_cmdaplus import *  # noqa: F401,F403
