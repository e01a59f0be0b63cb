"""Whole serving step's share of the chip's bf16 peak."""

from benchmark import flops


def read(record, params):
    rate = record.get("processed_tokens_per_s")
    if not rate:
        return None
    per_token = flops.forward_flops_per_token(
        record["shapes"], record["mean_context"])["total"]
    return 100.0 * rate * per_token / record["peak"]["bf16_flops_per_s"]
