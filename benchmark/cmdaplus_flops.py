"""Operations and bytes of a ``cohere2_moe`` chain from its shapes
(yardstick): what ONE token's forward pass multiplies on this chip, and
what one decode step has to read."""

import numpy

from benchmark import cmdaplus_weights


def _layers(shapes):
    return cmdaplus_weights.chain_layout(shapes)[1:-1]


def expert_params(shapes):
    """One routed expert: its three matrices."""
    return 3 * shapes["dim"] * shapes["expert_ffn"]


def head_params(shapes):
    """The tied head multiplies the table's held rows."""
    return shapes["vocab"] * shapes["dim"]


def matmul_params_outside_held_experts(shapes):
    """Parameters every token multiplies whatever its routing: the
    attention's four matrices, the router, the shared experts and the
    tied head over the held vocabulary.  The embedding is a gather and
    the norm weights elementwise: left out."""
    return sum(int(numpy.prod(s)) for layer in _layers(shapes)
               for name, s in layer.items()
               if not name.startswith("expert_w")
               and name != "input_norm") + head_params(shapes)


def held_share(shapes):
    """The share of a token's routed experts that falls on the experts
    held here at even routing."""
    return shapes["held"][1] / shapes["experts"]


def attention_flops(shapes, context):
    """4 H D a key a layer (the scores and the context) at ``context``
    keys: every key on a full layer, at most the window on a sliding
    one."""
    per_key = 4.0 * shapes["heads"] * shapes["head_dim"]
    kinds = shapes["kinds"]
    return per_key * (kinds.count("full") * context
                      + kinds.count("sliding")
                      * min(context, shapes["sliding_window"]))


def forward_flops_per_token(shapes, context):
    """2 FLOPs a multiply-add over the parameters a token multiplies
    HERE: everything outside the held experts, plus the held share of
    its ``experts_per_token`` routed experts a layer (16 of 128: one
    expert a layer on average), plus the attention over ``context``
    keys (:func:`attention_flops`)."""
    routed = len(shapes["kinds"]) * shapes["experts_per_token"] \
        * held_share(shapes) * expert_params(shapes)
    return 2.0 * (matmul_params_outside_held_experts(shapes) + routed) \
        + attention_flops(shapes, context)


def expert_bytes(shapes):
    """One expert as the program holds it (bfloat16)."""
    return 2 * expert_params(shapes)


def step_bytes_outside_held_experts(shapes):
    """Bytes of every weight a decode step reads whatever the routing:
    all leaves of the layers but the held experts, the final norm, and
    the table, which the tied head multiplies whole (the embedding's
    gather of one row a sequence is in it), each in the dtype the
    program stores."""
    total = 0
    for layer in cmdaplus_weights.chain_layout(shapes):
        for name, s in layer.items():
            if not name.startswith("expert_w"):
                total += int(numpy.prod(s)) * (
                    4 if name in cmdaplus_weights.FLOAT32 else 2)
    return total


def kv_row_bytes(shapes):
    """One cached position of ONE layer: its K and V rows in bfloat16."""
    return 2 * 2 * shapes["kv_heads"] * shapes["head_dim"]


def stream_bytes(shapes, steps, experts_touched, window_rows, full_rows):
    """{"weights", "experts", "window", "full"}: the bytes that ``steps``
    decode steps had to read, as the routing fell (``experts_touched``:
    held experts with a live row, summed over layers and steps) and as
    long as the rows were (``window_rows``: ring rows read, min(length,
    window) a live row and sliding layer; ``full_rows``: the live rows'
    lengths x full layers; each summed over steps)."""
    return {"weights": steps * step_bytes_outside_held_experts(shapes),
            "experts": experts_touched * expert_bytes(shapes),
            "window": window_rows * kv_row_bytes(shapes),
            "full": full_rows * kv_row_bytes(shapes)}
