"""Whole training step's share of the chip's bf16 peak."""

from benchmark import flops


def read(record, params):
    per_token = flops.train_flops_per_token(
        record["shapes"], record["traffic"]["sequence"])["total"]
    return (100.0 * record["tokens_per_s"] * per_token
            / record["peak"]["bf16_flops_per_s"])
