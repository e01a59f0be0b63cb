"""Percentile and rate arithmetic of the end-to-end metrics (yardstick)."""


def percentile(values, q):
    """The q-th percentile (0..100) with linear interpolation between the
    order statistics (numpy's default), over ALL the values given."""
    data = sorted(float(v) for v in values)
    if not data:
        raise ValueError("percentile of nothing")
    pos = (len(data) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def rate(units, seconds):
    """All the work over all the time: a stall inside the window lowers
    it, where a best-of or a median of chunks would hide it."""
    if seconds <= 0:
        raise ValueError("rate over a window of %r s" % (seconds,))
    return units / seconds
