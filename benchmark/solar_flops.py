"""Operations and bytes of a ``solar_open2`` chain from its shapes
(yardstick): what ONE token's forward pass multiplies on this chip, and
what one decode step has to read and write."""

import numpy

from benchmark import solar_weights

#: leaves of a layer that are neither a held expert's nor elementwise
_ELEMENTWISE = ("input_norm", "post_norm", "embedding_norm", "o_norm",
                "conv_taps", "A_log", "dt_bias", "gate_bias",
                "expert_bias")


def _layers(shapes):
    return solar_weights.chain_layout(shapes)[1:]


def expert_params(shapes):
    """One expert: its three matrices."""
    return 3 * shapes["dim"] * shapes["expert_ffn"]


def matmul_params_outside_held_experts(shapes):
    """Parameters every token multiplies whatever its routing: the
    operators' matrices, the router, the shared expert and the held
    head.  The table is a gather, the norm vectors, taps and biases are
    elementwise: left out."""
    return sum(int(numpy.prod(s)) for layer in _layers(shapes)
               for name, s in layer.items()
               if not name.startswith("expert_w")
               and name not in _ELEMENTWISE)


def held_share(shapes):
    """The share of a token's routed experts that falls on the experts
    held here at even routing."""
    return shapes["held"][1] / shapes["experts"]


def forward_flops_per_token(shapes, context):
    """2 FLOPs a multiply-add over the parameters a token multiplies
    HERE: everything outside the held experts, plus the held share of
    its ``experts_per_token`` routed experts (one eighth of 8: one
    expert a layer on average); the recurrence of a KDA layer (per head
    the decay, S^T k, the rank-1 update and S^T q: about 6 K^2
    operations); scores and context of a GQA layer over ``context``
    keys (4 ctx H K)."""
    kda = sum(1 for kind in shapes["kinds"] if kind == "kda")
    gqa = len(shapes["kinds"]) - kda
    heads, hd = shapes["heads"], shapes["head_dim"]
    routed = len(shapes["kinds"]) * shapes["experts_per_token"] \
        * held_share(shapes) * expert_params(shapes)
    return 2.0 * (matmul_params_outside_held_experts(shapes) + routed) \
        + kda * heads * 6.0 * hd * hd \
        + gqa * 4.0 * context * heads * hd


def expert_bytes(shapes):
    """One expert as the program holds it (bfloat16)."""
    return 2 * expert_params(shapes)


def step_bytes_outside_held_experts(shapes):
    """Bytes of every weight a decode step reads whatever the routing:
    all leaves but the held experts and the table (a step gathers a row
    a sequence from it), each in the dtype the program stores."""
    total = 0
    for layer in _layers(shapes):
        for name, s in layer.items():
            if not name.startswith("expert_w"):
                total += int(numpy.prod(s)) * (
                    4 if name in solar_weights.FLOAT32 else 2)
    return total


def state_row_bytes(shapes):
    """One slot's state of ONE KDA layer: the float32 matrix a head and
    the bfloat16 conv rows.  A decode step reads it and writes it."""
    heads, hd = shapes["heads"], shapes["head_dim"]
    return 4 * heads * hd * hd \
        + 2 * (shapes["conv_kernel"] - 1) * 3 * heads * hd


def stream_bytes(shapes, steps, experts_touched, state_rows):
    """{"weights", "experts", "state"}: the bytes that ``steps`` decode
    steps had to move, as the routing fell (``experts_touched``: held
    experts with a live row, summed over layers and steps) and as the
    slots were live (``state_rows``: live rows x KDA layers, summed over
    steps; each read and written)."""
    return {"weights": steps * step_bytes_outside_held_experts(shapes),
            "experts": experts_touched * expert_bytes(shapes),
            "state": state_rows * 2 * state_row_bytes(shapes)}
