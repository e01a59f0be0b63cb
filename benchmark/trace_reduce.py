"""From a profiler trace (``.xplane.pb``) to numbers (yardstick).

``reduce_events`` is pure arithmetic on ``(name, start_ns, duration_ns)``
tuples, so the tests drive it with synthetic events; ``read_xplane``
takes those tuples out of a trace with nothing but ``jax.profiler``.

- busy: the UNION of the intervals in which an operation ran on a chip
  (overlapping and nested events count once), averaged over the chips;
- per name: SELF time -- an event's duration less the events nested in
  it, so a loop or a fused call that encloses others is not counted
  twice;
- idle gaps: the stretches between busy intervals, each labelled with the
  benchmark's host span that covers most of it, or ``inside_program``.

``python3 -m benchmark.trace_reduce FILE`` prints what a trace holds: the
way to look at one by hand before writing a reader's name pattern.
"""

import collections
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def union_intervals(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def self_times(events):
    """{name: [self_ns, count]} of one line's events."""
    out = collections.defaultdict(lambda: [0.0, 0])
    stack = []                       # [name, end, self]
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name][0] += max(own, 0.0)
            out[name][1] += 1
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def reduce_events(device_lines, host_spans, window_ns=None):
    """``device_lines``: one event list per chip; ``host_spans``: the
    benchmark's own (name, start_ns, duration_ns).  Times in ns."""
    busy, names, gaps = [], collections.defaultdict(lambda: [0.0, 0]), []
    for events in device_lines:
        merged = union_intervals((s, s + d) for _, s, d in events if d > 0)
        busy.append(sum(e - s for s, e in merged))
        for name, (own, count) in self_times(events).items():
            names[name][0] += own / len(device_lines)
            names[name][1] += count
        gaps += [(b[0] - a[1], a[1], b[0])
                 for a, b in zip(merged, merged[1:])]
    by_label = collections.defaultdict(float)
    longest = sorted(gaps, reverse=True)[:5000]
    for length, start, end in longest:
        best, cover = "inside_program", 0.0
        for name, s, d in host_spans:
            over = min(end, s + d) - max(start, s)
            if over > cover:
                best, cover = name, over
        by_label[best] += length / max(len(device_lines), 1)
    busy_ns = sum(busy) / max(len(busy), 1)
    return {
        "busy_s": busy_ns / 1e9,
        "chips": len(device_lines),
        "window_s": None if window_ns is None else window_ns / 1e9,
        "op_seconds": {n: v[0] / 1e9 for n, v in names.items()},
        "op_counts": {n: v[1] for n, v in names.items()},
        "gap_seconds": {n: v / 1e9 for n, v in by_label.items()},
        "gap_total_s": sum(g[0] for g in gaps) / 1e9
        / max(len(device_lines), 1),
    }


def span_seconds(device_lines):
    """First start to last end of the device events: to hold against the
    traced window's length, which has to cover it."""
    events = [e for line in device_lines for e in line]
    if not events:
        return 0.0
    return (max(s + d for _, s, d in events)
            - min(s for _, s, _ in events)) / 1e9


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def read_xplane(path):
    """(device_lines, host_spans) of a trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_lines, host_spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append([
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events
                               if e.name.startswith(SPAN_PREFIX)]
    return device_lines, host_spans


def breakdown(reduced, top=10):
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    gaps = sorted(reduced["gap_seconds"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n[:160], s] for n, s in ops[:top]],
            "idle_gaps": [[n[:160], s] for n, s in gaps[:top]]}


def describe(path, top=60):
    """What a trace holds, for a reader of traces."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
            if not events:
                continue
            span = (min(e[1] for e in events),
                    max(e[1] + e[2] for e in events))
            print("  LINE %r: %d events over %.3f s, from %.0f ns" % (
                line.name, len(events), (span[1] - span[0]) / 1e9,
                span[0]))
            if not (plane.name.startswith("/device")
                    or line.name.startswith("python")):
                continue
            own = self_times(events)
            for name, (ns, count) in sorted(
                    own.items(), key=lambda kv: -kv[1][0])[:top]:
                print("    %10.6f s  x%-6d %s" % (ns / 1e9, count,
                                                  name[:140]))
            first = next(iter(line.events))
            print("    first event stats:", [
                (k, str(v)[:120]) for k, v in list(first.stats)[:12]])


if __name__ == "__main__":
    describe(find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1])
             else sys.argv[1])
