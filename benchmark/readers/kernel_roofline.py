"""A kernel's share of its roofline: the least time the chip could take
for the calls the traced window made (operations and bytes from shapes,
``benchmark/flops.py``) over the device self time of the trace events
whose names match ``params["pattern"]``.  No match: nothing to read."""

import re

from benchmark import flops


def read(record, params):
    pattern = re.compile(params["pattern"])
    seconds = sum(s for name, s in record["trace"]["op_seconds"].items()
                  if pattern.search(name))
    if seconds <= 0:
        return None
    cost = getattr(flops, params["cost"])(
        record["shapes"], record["traffic"]["sequence"])
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"],
                                       record["peak"])[0]
                for c in cost.values())
    calls = record["sequences"] * record["shapes"]["layers"]
    return 100.0 * least * calls / seconds
