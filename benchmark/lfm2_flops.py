"""Operations and bytes of an ``lfm2_moe`` chain from its shapes
(yardstick): what ONE token's forward pass multiplies, and what one
decode step has to read."""

import numpy

from benchmark import lfm2_weights


def _count(shapes, keep):
    return sum(int(numpy.prod(s))
               for layer in lfm2_weights.chain_layout(shapes)[1:]
               for name, s in layer.items() if keep(name))


def expert_params(shapes):
    """One expert: its three matrices."""
    return 3 * shapes["dim"] * shapes["expert_ffn"]


def active_params_per_token(shapes):
    """Parameters one token's forward pass multiplies: operators,
    routers (with expert_bias), the experts it is routed to, the dense
    FFN and the head.  The table is a gather and the norm vectors are
    elementwise: left out."""
    routed = sum(1 for _, ffn in shapes["kinds"] if ffn == "routed")
    rest = _count(shapes, lambda n: n in ("q_norm", "k_norm") or not (
        n.startswith("expert_w") or n.endswith("_norm")))
    return rest + routed * shapes["experts_per_token"] \
        * expert_params(shapes)


def forward_flops_per_token(shapes, context):
    """2 FLOPs a multiply-add over the active parameters, plus the
    attention layers' scores and context over ``context`` keys."""
    attention = sum(1 for op, _ in shapes["kinds"] if op == "attention")
    return 2.0 * active_params_per_token(shapes) \
        + 4.0 * context * shapes["dim"] * attention


def expert_bytes(shapes):
    """One expert as the program holds it (bfloat16)."""
    return 2 * expert_params(shapes)


def step_bytes_outside_experts(shapes):
    """Bytes of every weight a decode step reads whatever the routing:
    all leaves but the experts and the table (a step gathers a row a
    sequence from it), each in the dtype the program stores."""
    total = 0
    for layer in lfm2_weights.chain_layout(shapes)[1:]:
        for name, s in layer.items():
            if not name.startswith("expert_w"):
                total += int(numpy.prod(s)) * (
                    4 if name in lfm2_weights.FLOAT32 else 2)
    return total
