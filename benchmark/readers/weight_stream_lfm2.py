"""The weight bytes that the decode steps of the window had to read, as
the routing fell, against what the chip could stream in the host
seconds those steps took (``params``: the counters of the steps, of
the experts touched, and of the step phase's seconds).  A program
without the counters: nothing to read."""

from benchmark import lfm2_flops


def read(record, params):
    counters = record.get("counters", {})
    seconds = counters.get(params["seconds"], 0.0)
    if seconds <= 0 or params["touched"] not in counters:
        return None
    shapes = record["shapes"]
    nbytes = lfm2_flops.step_bytes_outside_experts(shapes) \
        * counters.get(params["steps"], 0.0) \
        + lfm2_flops.expert_bytes(shapes) * counters[params["touched"]]
    return 100.0 * nbytes / seconds / record["peak"]["hbm_bytes_per_s"]
