"""Operations and bytes from shapes (yardstick).

Training follows the PaLM/Megatron convention copied from bench.py
(``transformer_train_flops_per_sample`` plus the ``6*s*d*V`` head term):
2 FLOPs a multiply-add, backward twice the forward, causal attention
counted as full s x s matrices, recomputation not counted, elementwise
work and the embedding gather left out.  The attention KERNEL's own
count, used for its roofline, is the causal half and leaves out the
backward pass's recomputation of the scores, so it cannot overstate what
the kernel must do.
"""


def block_matmul_params(shapes):
    """Matmul weights of one block: q, k, v, o and the two FFN matrices."""
    d, h = shapes["dim"], shapes["ffn"]
    return 4 * d * d + 2 * d * h


def train_flops_per_token(shapes, seq):
    d, v, layers = shapes["dim"], shapes["vocab"], shapes["layers"]
    blocks = 6.0 * block_matmul_params(shapes) * layers
    attention = 12.0 * seq * d * layers       # 3 x (4 s^2 d) / s
    head = 6.0 * d * v
    return {"blocks": blocks, "attention": attention, "head": head,
            "total": blocks + attention + head}


def forward_flops_per_token(shapes, context):
    """One token's forward pass with ``context`` keys visible."""
    d, v, layers = shapes["dim"], shapes["vocab"], shapes["layers"]
    blocks = 2.0 * block_matmul_params(shapes) * layers
    attention = 4.0 * context * d * layers
    head = 2.0 * d * v
    return {"blocks": blocks, "attention": attention, "head": head,
            "total": blocks + attention + head}


def attention_kernel_cost(shapes, seq, itemsize=2):
    """FLOPs and HBM bytes the causal attention core of ONE sequence in
    ONE layer needs, forward and backward: forward QK^T and PV over the
    causal half (2 s^2 d), backward the four products dV, dP, dQ, dK over
    the same half (4 s^2 d); forward reads q, k, v and writes o, backward
    reads q, k, v, o, do and writes dq, dk, dv."""
    d = shapes["dim"]
    tensor = seq * d * itemsize
    return {"forward": {"flops": 2.0 * seq * seq * d, "bytes": 4.0 * tensor},
            "backward": {"flops": 4.0 * seq * seq * d,
                         "bytes": 8.0 * tensor}}


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which side binds."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def peak_for(device_kind, table):
    """The row of ``peaks.json`` for this device; an unknown kind is an
    error, never a default or a probe."""
    try:
        return table["device_kinds"][device_kind]
    except KeyError:
        raise KeyError("device kind %r is not in benchmark/peaks.json"
                       % (device_kind,))
