"""What the ``serve_closed_ouro`` driver does to the program's units:
the layer spec of a looped ``ouro`` chain at a configuration's shapes,
and the hand-over of the benchmark's weights as device leaves in the
dtype the program stores, one at a time, BEFORE the units initialize (a
unit that finds a parameter there fills none: no float32 model on the
host or the device)."""

from benchmark import ouro_weights


def layer_spec(shapes):
    return [dict(type="embedding", vocab=shapes["vocab"],
                 dim=shapes["dim"], learned_positions=False),
            dict(type="ouro_stack", dim=shapes["dim"],
                 layers=shapes["layers"], passes=shapes["passes"],
                 heads=shapes["heads"], hidden=shapes["ffn"],
                 rope_theta=shapes["rope_theta"],
                 norm_eps=shapes["norm_eps"]),
            dict(type="plain_token_logits", vocab=shapes["vocab"])]


def hand_over_weights(forwards, seed, shapes):
    """Give every parameter array of the (uninitialized) units its
    leaf; returns the bytes handed over by dtype."""
    handed = {}
    for i, (unit, leaves) in enumerate(
            zip(forwards, ouro_weights.chain_layout(shapes))):
        names = [n for n in unit.PARAMS
                 if n != "positions" or unit.learned_positions]
        if sorted(names) != sorted(leaves):
            raise RuntimeError("%s holds %s, the benchmark makes %s" % (
                unit.name, sorted(names), sorted(leaves)))
        for name in names:
            leaf = ouro_weights.program_leaf(seed, i, name, leaves[name])
            leaf.block_until_ready()       # one leaf's float32 at a time
            getattr(unit, name).devmem = leaf
            key = str(leaf.dtype)
            handed[key] = handed.get(key, 0) + leaf.nbytes
    return handed
