"""The bytes that the decode steps of the window had to move
(``solar_flops.stream_bytes``: the weights outside the held experts a
step, the held experts that the routing touched, the per-slot state of
the live rows read and written), from the three counters that
``params`` names (``steps``, ``touched``, ``rows``).

``params["of"]``: ``"peak"``, all of them against what the chip could
stream in the WINDOW's seconds (by the window and not by the host's
step seconds, which no longer cover the device's step since
launch-ahead, so it cannot pass 100); or ``"state"``, the state's part
of them.  A program without the counters, or a record of another
chain's shapes: nothing to read."""

from benchmark import solar_flops


def read(record, params):
    counters = record.get("counters", {})
    shapes = record.get("shapes", {})
    if "held" not in shapes or not record.get("window_s") or any(
            params[k] not in counters
            for k in ("steps", "touched", "rows")):
        return None
    moved = solar_flops.stream_bytes(
        shapes, counters[params["steps"]], counters[params["touched"]],
        counters[params["rows"]])
    total = sum(moved.values())
    if total <= 0:
        return None
    if params["of"] == "state":
        return 100.0 * moved["state"] / total
    return 100.0 * total / record["window_s"] \
        / record["peak"]["hbm_bytes_per_s"]
