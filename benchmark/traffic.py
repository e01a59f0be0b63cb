"""The one general generator of request traffic (yardstick; numpy only,
so the load generator's process never touches JAX).

A traffic file gives, under ``requests``, the lognormal medians, sigmas
and clips of the prompt and output lengths, the size of the pool, and
whether prompts share anything.  The POOL of (prompt length, output
length) pairs and the order a run goes through it, pass after pass, are
drawn once from the file's own ``pool_seed``; the run's seed draws the
token ids.  So the seed changes what is asked and never how much work a
window holds: with the order drawn from the seed too, runs of different
seeds differed by +-2 % in tokens/s and +-25 % in the 95th percentile of
the time to first token, where two runs of one seed agreed within 1 % and
3 % (PERF.md, PR 26).
"""

import numpy


def _lengths(rng, spec, count):
    draw = rng.lognormal(numpy.log(spec["median"]), spec["sigma"], count)
    return numpy.clip(numpy.rint(draw), spec["min"],
                      spec["max"]).astype(int)


def size_pool(requests):
    rng = numpy.random.default_rng(requests["pool_seed"])
    count = requests["pool"]
    return list(zip(_lengths(rng, requests["prompt"], count).tolist(),
                    _lengths(rng, requests["output"], count).tolist()))


def request_list(requests, seed, vocab):
    """[(prompt ids, steps)] for one run: ``passes`` passes over the
    pool, each in an order of its own that, like the pool, comes from
    the file's ``pool_seed`` -- so every run meets the same sizes in the
    same order and a window holds the same work whatever the seed.  The
    seed draws the token ids: every prompt distinct (uniform ids, no
    shared prefix), and different under another seed."""
    order = numpy.random.default_rng(requests["pool_seed"] + 1)
    ids = numpy.random.default_rng(int(seed))
    pool = size_pool(requests)
    out = []
    for _ in range(requests["passes"]):
        for i in order.permutation(len(pool)):
            out.append((ids.integers(0, vocab, pool[i][0]).tolist(),
                        pool[i][1]))
    return out


def sweep_list(requests, vocab, seed, steps):
    """One short request for every distinct number of KV blocks a prompt
    of the pool fills (``block`` tokens each): the program builds one
    insert program per block count, so the warm-up has to meet each."""
    rng = numpy.random.default_rng(int(seed))
    block = requests["kv_block"]
    by_blocks = {}
    for prompt_len, _ in size_pool(requests):
        by_blocks.setdefault(-(-prompt_len // block), prompt_len)
    return [(rng.integers(0, vocab, by_blocks[n]).tolist(), steps)
            for n in sorted(by_blocks)]


def ladder_list(ladder, vocab, seed):
    """The warm-up ladder of a traffic file: for every rung (``prompt``
    length, ``steps``, ``clients``) one batch of that many simultaneous
    greedy requests with random prompts.  They join the decoding set one
    by one as their prefill ends, so a rung passes every occupancy up to
    its ``clients`` at its depth."""
    rng = numpy.random.default_rng(int(seed))
    return [[(rng.integers(0, vocab, rung["prompt"]).tolist(),
              rung["steps"]) for _ in range(rung["clients"])]
            for rung in ladder["rungs"]]
