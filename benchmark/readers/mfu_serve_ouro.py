"""Whole serving step's share of the chip's bf16 peak for a looped
``ouro`` chain: every layer application a token passes counts
(``ouro_flops.forward_flops_per_token``)."""

from benchmark import ouro_flops


def read(record, params):
    rate = record.get("processed_tokens_per_s")
    if not rate:
        return None
    per_token = ouro_flops.forward_flops_per_token(
        record["shapes"], record["mean_context"])
    return 100.0 * rate * per_token / record["peak"]["bf16_flops_per_s"]
