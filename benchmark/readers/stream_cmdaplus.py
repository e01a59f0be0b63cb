"""The bytes that the decode steps of the window had to read
(``cmdaplus_flops.stream_bytes``: the weights outside the held experts a
step, the held experts that the routing touched, the ring rows of the
sliding layers and the K/V rows of the full layers that decode attention
read), from the four counters that ``params`` names (``steps``,
``touched``, ``window``, ``full``).

``params["of"]``: ``"peak"``, all of them against what the chip could
stream in the WINDOW's seconds (so it cannot pass 100); ``"window"`` or
``"full"``, that cache's share of them.  A program without the counters,
or a record of another chain's shapes: nothing to read."""

from benchmark import cmdaplus_flops


def read(record, params):
    counters = record.get("counters", {})
    shapes = record.get("shapes", {})
    if "sliding_window" not in shapes or not record.get("window_s") or any(
            params[k] not in counters
            for k in ("steps", "touched", "window", "full")):
        return None
    moved = cmdaplus_flops.stream_bytes(
        shapes, *(counters[params[k]]
                  for k in ("steps", "touched", "window", "full")))
    total = sum(moved.values())
    if total <= 0:
        return None
    if params["of"] == "peak":
        return 100.0 * total / record["window_s"] \
            / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * moved[params["of"]] / total
