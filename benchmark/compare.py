"""The arithmetic that decides ``correct`` (yardstick)."""

import statistics


def flatten(by_layer):
    """[{leaf: x}] by layer -> {"<layer>.<leaf>": x}."""
    return {"%d.%s" % (i, name): float(x)
            for i, layer in enumerate(by_layer)
            for name, x in layer.items()}


def worst_norm_gap(program, reference, leave_out=()):
    """The worst leaf's gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Returns (gap, leaf)."""
    median = statistics.median(reference.values())
    worst, where = 0.0, None
    for leaf, ref in reference.items():
        if leaf in leave_out:
            continue
        gap = abs(program[leaf] - ref) / max(ref, median)
        if gap > worst or where is None:
            worst, where = gap, leaf
    return worst, where


def dead_leaves(reference_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding (under
    ``share`` of the median leaf's): Adam moves them by round-off alone,
    so their change is not compared."""
    median = statistics.median(reference_grad_norms.values())
    return {leaf for leaf, g in reference_grad_norms.items()
            if g < share * median}


def worst_relative(program, reference):
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def verdict(compared):
    """``compared``: [{"name", "value", "limit"}]; correct when every
    value is a number at or under its limit."""
    return bool(compared) and all(
        c["value"] == c["value"] and c["value"] <= c["limit"]
        for c in compared)
