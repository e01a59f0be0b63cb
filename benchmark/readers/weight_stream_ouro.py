"""The weight bytes that the decode steps of the window had to read (the
looped stack once a pass, and the head) against what the chip could
stream in the WINDOW's seconds (``params``: the counter of the steps).
By the window and not by the host's step seconds, so it cannot pass 100.
A record without the counter or the shapes of a looped stack: nothing to
read."""

from benchmark import ouro_flops


def read(record, params):
    steps = record.get("counters", {}).get(params["steps"], 0.0)
    shapes = record.get("shapes", {})
    if steps <= 0 or "passes" not in shapes \
            or not record.get("window_s"):
        return None
    return 100.0 * steps * ouro_flops.step_weight_bytes(shapes) \
        / record["window_s"] / record["peak"]["hbm_bytes_per_s"]
