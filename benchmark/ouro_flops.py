"""Operations and bytes of a looped ``ouro`` chain from its shapes
(yardstick): what ONE token's forward pass multiplies, and what one
decode step has to read."""

import math

from benchmark import ouro_weights


def layer_matmul_params(shapes):
    """One layer's matrices: q, k, v, o and the gated FFN's three."""
    d, h = shapes["dim"], shapes["ffn"]
    return 4 * d * d + 3 * d * h


def layer_applications(shapes):
    """Layers a token passes: the stack, ``passes`` times."""
    return shapes["passes"] * shapes["layers"]


def forward_flops_per_token(shapes, context):
    """2 FLOPs a multiply-add over every layer application's matrices
    and the head (the table is a gather, the norms and the gate are
    elementwise or a vector: left out), plus the scores and the context
    of every layer application over ``context`` keys."""
    return 2.0 * layer_applications(shapes) * layer_matmul_params(shapes) \
        + 4.0 * context * shapes["dim"] * layer_applications(shapes) \
        + 2.0 * shapes["dim"] * shapes["vocab"]


def stack_bytes(shapes):
    """The stack as the program holds it: matrices bfloat16, norm
    vectors and the gate float32."""
    return sum(math.prod(s) * (4 if name in ouro_weights.FLOAT32 else 2)
               for name, s in ouro_weights.stack_layout(shapes).items())


def head_bytes(shapes):
    return 2 * shapes["dim"] * shapes["vocab"]


def step_weight_bytes(shapes):
    """Weight bytes ONE decode step reads, whatever its batch: the
    stack once a pass and the head (the table gives a row a sequence)."""
    return shapes["passes"] * stack_bytes(shapes) + head_bytes(shapes)
