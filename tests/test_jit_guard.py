"""Tier-1 static guard over jit sites — now a thin shell around the
veles-lint T-series pass (``veles_tpu/analysis/passes/purity.py``),
so there is ONE jit-site scanner: every ``jax.jit`` inside
``veles_tpu/`` must route through ``telemetry.track_jit`` (T203), the
serving entry points must register their stable names (T204), and
deliberate exceptions live in ``analysis/baseline.txt`` WITH reasons
(the old in-test allowlist).  The AST pass is strictly stronger than
the old regex: bare ``@jax.jit`` decorators (which ``jax\\.jit\\(``
never matched) are now caught too."""

from pathlib import Path

import pytest

from veles_tpu.analysis import analyze
from veles_tpu.analysis.baseline import load_baseline
from veles_tpu.analysis.passes.purity import (
    REQUIRED_REGISTRATIONS, PurityPass)

PKG = Path(__file__).resolve().parent.parent / "veles_tpu"

pytestmark = pytest.mark.analysis


def _t_findings():
    findings, fresh, stale, errors = analyze(
        [str(PKG)], root=PKG.parent, passes=(PurityPass(),))
    assert not errors, errors
    return findings, fresh, stale


def test_all_jax_jit_sites_are_tracked():
    _, fresh, _ = _t_findings()
    untracked = [str(f) for f in fresh if f.code == "T203"]
    assert not untracked, (
        "jax.jit call sites not routed through telemetry.track_jit "
        "(compiles would escape veles_jit_* metrics and cost "
        "accounting).  Wrap with track_jit(name, jax.jit(...)) or "
        "baseline with a reason in veles_tpu/analysis/baseline.txt:\n"
        + "\n".join(untracked))


def test_serving_jit_entry_points_registered():
    """T204: the stable entry-point names bench and the compile
    dashboards key on must exist — and must never be baselined
    away."""
    findings, _, _ = _t_findings()
    missing = [str(f) for f in findings if f.code == "T204"]
    assert not missing, "\n".join(missing)
    # the registry itself must still cover the serving surface
    covered = {name for _, name in REQUIRED_REGISTRATIONS}
    assert {"serving.paged_step", "serving.verify_step",
            "serving.prefill", "serving.prefill_chunk",
            "serving.kv_insert_blocks"} <= covered


def test_guard_baseline_entries_still_exist():
    """A stale baseline entry means the exception it documented is
    gone — prune it so it can't mask a future regression (the old
    allowlist-pruning rule, now over every pass's entries)."""
    findings, _, stale, _ = analyze([str(PKG)], root=PKG.parent)
    assert not stale, (
        "baseline entries matching no finding — remove them from "
        "veles_tpu/analysis/baseline.txt:\n" + "\n".join(stale))
    entries = load_baseline()
    for key, reason in entries.items():
        assert reason.strip(), "baseline entry %r has no reason" % key
