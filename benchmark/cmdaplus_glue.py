"""What the ``serve_closed_cmdaplus`` driver does to the program's units:
the layer spec of a ``cohere2_moe`` chain at a configuration's shapes
(every layer told its attention kind and which experts it holds; the
head tied to the table), and the hand-over of the benchmark's weights as
device leaves in the dtype the program stores, one at a time, BEFORE
the units initialize (a unit that finds a parameter there fills none:
no float32 model on the host or the device)."""

from benchmark import cmdaplus_weights

#: the unit types of the chain: a program without them cannot run it
TYPES = ("cohere_moe_block", "tied_token_logits")


def layer_spec(shapes):
    spec = [dict(type="embedding", vocab=shapes["vocab"],
                 dim=shapes["dim"], learned_positions=False)]
    for kind in shapes["kinds"]:
        spec.append(dict(
            type="cohere_moe_block", dim=shapes["dim"], attention=kind,
            hidden=shapes["expert_ffn"], heads=shapes["heads"],
            kv_heads=shapes["kv_heads"], head_dim=shapes["head_dim"],
            window=shapes["sliding_window"],
            rope_theta=shapes["rope_theta"], n_experts=shapes["experts"],
            top_k=shapes["experts_per_token"], held=tuple(shapes["held"]),
            n_shared=shapes["shared"], shared_hidden=shapes["shared_ffn"],
            norm_topk_prob=shapes["norm_topk_prob"],
            norm_eps=shapes["norm_eps"]))
    return spec + [dict(type="tied_token_logits", vocab=shapes["vocab"],
                        logit_scale=shapes["logit_scale"],
                        norm_eps=shapes["norm_eps"])]


def hand_over_weights(forwards, seed, shapes):
    """Give every parameter array of the (uninitialized) units its
    leaf; returns the bytes handed over by dtype.  The head's table is
    the embedding's: only its norm is handed to it."""
    layout = cmdaplus_weights.chain_layout(shapes)
    handed = {}
    for i, (unit, leaves) in enumerate(zip(forwards, layout)):
        names = [n for n in unit.PARAMS
                 if n != "positions" or unit.learned_positions]
        if sorted(names) != sorted(leaves):
            raise RuntimeError("%s holds %s, the benchmark makes %s" % (
                unit.name, sorted(names), sorted(leaves)))
        for name in names:
            leaf = cmdaplus_weights.program_leaf(seed, i, name,
                                                 leaves[name])
            leaf.block_until_ready()       # one leaf's float32 at a time
            getattr(unit, name).devmem = leaf
            key = str(leaf.dtype)
            handed[key] = handed.get(key, 0) + leaf.nbytes
    return handed
