"""What the ``serve_closed_solar`` driver does to the program's units:
the layer spec of a ``solar_open2`` chain at a configuration's shapes
(every layer told which experts it holds), and the hand-over of the
benchmark's weights as device leaves in the dtype the program stores,
one at a time, BEFORE the units initialize (a unit that finds a
parameter there fills none: no float32 model on the host or the
device)."""

from benchmark import solar_weights


def layer_spec(shapes):
    spec = [dict(type="embedding", vocab=shapes["vocab"],
                 dim=shapes["dim"], learned_positions=False)]
    for kind in shapes["kinds"]:
        spec.append(dict(
            type="solar_block", dim=shapes["dim"], operator=kind,
            hidden=shapes["expert_ffn"], heads=shapes["heads"],
            kv_heads=shapes["kv_heads"], head_dim=shapes["head_dim"],
            conv_kernel=shapes["conv_kernel"],
            low_rank=shapes["low_rank"], n_experts=shapes["experts"],
            top_k=shapes["experts_per_token"],
            held=tuple(shapes["held"]),
            norm_topk_prob=shapes["norm_topk_prob"],
            routed_scaling_factor=shapes["routed_scaling_factor"],
            norm_eps=shapes["norm_eps"]))
    return spec + [dict(type="rms_token_logits", vocab=shapes["vocab"],
                        norm_eps=shapes["norm_eps"])]


def hand_over_weights(forwards, seed, shapes):
    """Give every parameter array of the (uninitialized) units its
    leaf; returns the bytes handed over by dtype."""
    layout = solar_weights.chain_layout(shapes)
    share = solar_weights.chosen_share(shapes)
    handed = {}
    for i, (unit, leaves) in enumerate(zip(forwards, layout)):
        names = [n for n in unit.PARAMS
                 if n != "positions" or unit.learned_positions]
        if sorted(names) != sorted(leaves):
            raise RuntimeError("%s holds %s, the benchmark makes %s" % (
                unit.name, sorted(names), sorted(leaves)))
        for name in names:
            leaf = solar_weights.program_leaf(seed, i, name, leaves[name],
                                              share)
            leaf.block_until_ready()       # one leaf's float32 at a time
            getattr(unit, name).devmem = leaf
            key = str(leaf.dtype)
            handed[key] = handed.get(key, 0) + leaf.nbytes
    return handed
