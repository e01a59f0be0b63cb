"""Whole serving step's share of the chip's bf16 peak for a
``solar_open2`` chain of which this chip holds a share of the experts:
only what a token multiplies HERE counts
(``solar_flops.forward_flops_per_token``).  A record of another chain's
shapes: nothing to read."""

from benchmark import solar_flops


def read(record, params):
    rate = record.get("processed_tokens_per_s")
    if not rate or "held" not in record.get("shapes", {}):
        return None
    per_token = solar_flops.forward_flops_per_token(
        record["shapes"], record["mean_context"])
    return 100.0 * rate * per_token / record["peak"]["bf16_flops_per_s"]
