"""The delta-rule state update's share of its roofline: the least time
the chip could take for the calls the trace holds over the device self
time they cost.  The calls are the trace events whose names match
``params["pattern"]`` (the Pallas call ``veles_delta_state``: the trace
names an op by its HLO text, so an op that reads the call's result
names it too and is not matched).  One call advances the live rows of
one delta-rule layer in one decode step, and a live row needs
``params["row_bytes"]`` of HBM traffic (the metric's data: its float32
state read and written once, and the small operands); its arithmetic is
far below what the bytes take, so the bytes bind.  The mean live rows a
call come from the window's counters: live rows a decode step
(``busy`` over ``steps``) times the share of the layers' states the
call advanced (``fused`` over ``rows``); the traced part of the window
is its leading part, the counters are of all of it.  No match or no
counter: nothing to read."""

import re

COUNTERS = ("steps", "busy", "rows", "fused")


def read(record, params):
    trace, counters = record.get("trace"), record.get("counters", {})
    if not trace or any(params[k] not in counters for k in COUNTERS):
        return None
    steps, busy, states, fused = (counters[params[k]] for k in COUNTERS)
    pattern = re.compile(params["pattern"])
    names = [n for n in trace["op_seconds"] if pattern.search(n)]
    seconds = sum(trace["op_seconds"][n] for n in names)
    calls = sum(trace["op_counts"][n] for n in names)
    if seconds <= 0 or calls <= 0 or steps <= 0 or states <= 0:
        return None
    rows = busy / steps * fused / states
    least = rows * params["row_bytes"] / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * least * calls / seconds
