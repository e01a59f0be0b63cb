"""What both drivers do to the program's units: the ``samples/lm.py``
layer spec at a configuration's sizes, the hand-over of the benchmark's
weights, and the freeing of device buffers before the reference runs."""


def layer_spec(shapes, remat=False):
    """embedding -> L x transformer_block -> token_logits.  The units'
    own host-side filling is set to a constant: the benchmark hands its
    own weights over, so the fill should cost a memset and no draws."""
    cheap = {"weights_filling": "constant", "weights_stddev": 0.0}
    spec = [dict(type="embedding", vocab=shapes["vocab"],
                 dim=shapes["dim"], **cheap)]
    spec += [dict(type="transformer_block", heads=shapes["heads"],
                  hidden=shapes["ffn"], causal=True, remat=bool(remat),
                  **cheap)
             for _ in range(shapes["layers"])]
    return spec + [dict(type="token_logits", vocab=shapes["vocab"],
                        **cheap)]


def hand_over_weights(forwards, chain):
    """Put the benchmark's leaves into the units' parameter arrays."""
    for unit, layer in zip(forwards, chain):
        arrays = unit.param_arrays()
        if sorted(arrays) != sorted(layer):
            raise RuntimeError("%s holds %s, the benchmark made %s" % (
                unit.name, sorted(arrays), sorted(layer)))
        for name, arr in arrays.items():
            if tuple(arr.shape) != tuple(layer[name].shape):
                raise RuntimeError("%s.%s is %s, not %s" % (
                    unit.name, name, arr.shape, layer[name].shape))
            arr.devmem = layer[name]


def free_arrays(arrays):
    """Drop and delete the device buffer of each program ``Array``."""
    for arr in arrays:
        buf = arr._devmem_
        arr.devmem = None
        if buf is not None:
            buf.delete()
