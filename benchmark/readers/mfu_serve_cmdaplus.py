"""Whole serving step's share of the chip's bf16 peak for a
``cohere2_moe`` chain of which this chip holds a share of the experts:
only what a token multiplies HERE counts
(``cmdaplus_flops.forward_flops_per_token``, at the window's mean
context).  A record of another chain's shapes: nothing to read."""

from benchmark import cmdaplus_flops


def read(record, params):
    rate = record.get("processed_tokens_per_s")
    if not rate or "sliding_window" not in record.get("shapes", {}):
        return None
    per_token = cmdaplus_flops.forward_flops_per_token(
        record["shapes"], record["mean_context"])
    return 100.0 * rate * per_token / record["peak"]["bf16_flops_per_s"]
