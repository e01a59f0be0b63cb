"""The serving sampler (``engine.sample_slots``) does only the work its
rows ask for, and its tokens are those of the unconditional form it
replaced, bit for bit, for every row mix:

- all greedy (the argmax alone runs), greedy rows that carry a top-k,
  greedy beside sampled rows with top-k 0 (the draw, no sort), sampled
  rows with top-k > 0 over logits with ties at the k-th value (the
  sort and the mask), padding rows behind live ones, at B = 1, 8, 16;
  each through ``sample_first`` (the admission's first token) and
  through a jitted ``sample_slots`` (the decode step's);
- the lowered program holds its ``sort`` only inside a conditional's
  branch, never on the path every step takes.
"""

import re

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.serving import engine

pytestmark = pytest.mark.serving

VOCAB = 96


def reference_sample_slots(logits, temps, topks, keys):
    """The sampler as it was before PR 38, verbatim: the sort, the
    divide and the draw run whatever the rows ask for."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    z = logits / jnp.maximum(temps, 1e-6)[:, None]
    zs = jnp.sort(z, axis=-1)
    kth = jnp.take_along_axis(
        zs, jnp.clip(v - topks, 0, v - 1)[:, None], axis=-1)
    z = jnp.where((topks[:, None] > 0) & (z < kth), -jnp.inf, z)
    drawn = jax.vmap(jax.random.categorical)(keys, z)
    return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy)


def _mix(name, b, rng):
    """(logits, temps, topks, seeds, counts) of one row mix."""
    logits = rng.standard_normal((b, VOCAB)).astype(numpy.float32) * 3
    temps = numpy.zeros((b,), numpy.float32)
    topks = numpy.zeros((b,), numpy.int32)
    live = numpy.arange(b)
    if name == "greedy_topk":
        topks[:] = rng.integers(1, 8, b)
    elif name == "sampled_topk0":
        # row 0 samples (so B = 1 samples too), the odd rows greedy
        temps[live % 2 == 0] = rng.uniform(0.3, 1.5, (b + 1) // 2)
    elif name == "sampled_topk_ties":
        # coarse, flat logits: many tokens tie at each row's k-th
        # value, and a draw outside the top-k is likely unless masked
        logits = numpy.round(logits / 3).astype(numpy.float32)
        temps[:] = rng.uniform(1.0, 3.0, b)
        topks[:] = rng.integers(1, 12, b)
        temps[live % 4 == 3] = 0.0            # greedy rows among them
        topks[live % 3 == 2] = 0              # full-vocab rows too
    elif name == "padding":
        # the scheduler's packing: live rows first, then padding rows
        # at temperature 0, top-k 0, seed 0, count 0
        n = b // 2 + 1
        temps[:n] = rng.uniform(0.3, 1.5, n)
        topks[:n] = rng.integers(0, 6, n)
        logits[n:] = 0.0
    seeds = rng.integers(0, 2 ** 32, b, dtype=numpy.uint64).astype(
        numpy.uint32)
    counts = rng.integers(0, 500, b).astype(numpy.int32)
    if name == "padding":
        seeds[n:] = 0
        counts[n:] = 0
    return logits, temps, topks, seeds, counts


MIXES = ["greedy", "greedy_topk", "sampled_topk0", "sampled_topk_ties",
         "padding"]


@pytest.mark.parametrize("path", ["sample_first", "sample_slots"])
@pytest.mark.parametrize("b", [1, 8, 16])
@pytest.mark.parametrize("mix", MIXES)
def test_tokens_equal_the_unconditional_sampler(mix, b, path):
    rng = numpy.random.default_rng([MIXES.index(mix), b, 38])
    logits, temps, topks, seeds, counts = _mix(mix, b, rng)
    keys = engine._fold_keys(jnp.asarray(seeds), jnp.asarray(counts))
    want = numpy.asarray(jax.jit(reference_sample_slots)(
        logits, temps, topks, keys))
    if path == "sample_first":
        got = engine._sample_first_jit(logits, temps, topks, seeds,
                                       counts)
    else:
        got = jax.jit(engine.sample_slots)(logits, temps, topks, keys)
    got = numpy.asarray(got)
    assert got.dtype == numpy.int32
    numpy.testing.assert_array_equal(got, want)
    greedy = temps == 0
    numpy.testing.assert_array_equal(
        got[greedy], numpy.argmax(logits[greedy], axis=-1))
    # the mix exercises what its name says
    if mix in ("greedy", "greedy_topk"):
        assert greedy.all()
    else:
        assert (~greedy).any()


def _computations(hlo):
    """{computation: its body's lines} of an HLO module's text, and
    the entry's name."""
    bodies, entry, name = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?(\S+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            bodies[name] = []
            if head.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    return bodies, entry


def _reached(bodies, start, through_branches):
    """The computations ``start`` reaches through calls, and through a
    conditional's branches only if ``through_branches``."""
    plain = re.compile(
        r"(?:to_apply|calls|body|condition|comparator)=%?([\w.\-]+)")
    branch = re.compile(r"branch_computations=\{([^}]*)\}")
    seen, todo = set(), [start]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in bodies[name]:
            todo.extend(plain.findall(line))
            if through_branches:
                for group in branch.findall(line):
                    todo.extend(g.strip().lstrip("%")
                                for g in group.split(","))
    return seen


def _sorts(bodies, names):
    return [line for n in names for line in bodies[n]
            if re.search(r"\ssort\(", line)]


@pytest.mark.parametrize("b", [1, 8])
def test_the_sort_lies_only_inside_a_conditional_branch(b):
    keys = jax.random.split(jax.random.key(0), b)
    hlo = jax.jit(engine.sample_slots).lower(
        jnp.zeros((b, VOCAB)), jnp.zeros((b,)),
        jnp.zeros((b,), jnp.int32), keys).as_text(dialect="hlo")
    bodies, entry = _computations(hlo)
    assert entry is not None
    always = _reached(bodies, entry, through_branches=False)
    assert not _sorts(bodies, always)
    assert _sorts(bodies, _reached(bodies, entry, through_branches=True))
    # the unconditional form, read the same way, has its sort on the
    # path every step takes: the walk tells the two apart
    old = jax.jit(reference_sample_slots).lower(
        jnp.zeros((b, VOCAB)), jnp.zeros((b,)),
        jnp.zeros((b,), jnp.int32), keys).as_text(dialect="hlo")
    bodies, entry = _computations(old)
    assert _sorts(bodies, _reached(bodies, entry, through_branches=False))
