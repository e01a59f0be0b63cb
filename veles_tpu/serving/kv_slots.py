"""The serving KV cache: block-paged.

:class:`PagedKVCache` — vLLM-lineage PagedAttention layout (Kwon et
al., SOSP 2023): K/V live in per-layer POOLS of fixed-size blocks
(``[num_blocks, block_size, d]``) plus a per-slot *block table*, so a
request holds ``ceil((prompt + steps) / block_size)`` blocks instead
of a full ``window`` row.  Admission capacity becomes
memory-proportional — short requests pack many more concurrent
streams into the same HBM — and the pool size (``kv_blocks``) is a
knob independent of ``max_slots``.  Physical block 0 is the reserved
TRASH block: never allocated, it absorbs the writes of occupancy-
bucket padding rows and backs the stale tail entries of every table
(see ops/paged_attention.py for why the garbage is exactly masked).

A slot's lifecycle: **alloc** (a request leaves the queue and claims
a slot and its whole block budget, so decode can never die of
mid-flight block starvation), **insert** (the prefilled batch-1
staging row is block-scattered in), **decode** (the shared compiled
step writes position ``len-1`` and attends over ``[0, len)``),
**release** (stop-token / step-limit frees slot + blocks; no zeroing
needed — every attended row [0, len) was written by the current
occupant).

All methods must be called from ONE thread (the scheduler's decode
loop).

THE DEVICE STATE IS DONATED, ALWAYS.  Every jitted program that takes
the cache's device state (``pools``) and returns it anew — the
decode and verify steps of serving/engine.py, and the inserts and
the import below — takes it DONATED, so the scatter lands in place
instead of in a copy of the whole pool.  The invariant that makes it
safe, said once: whoever calls such a program owns the ONLY reference
to the leaves it hands over and swaps the cache's attribute for what
came back at once; nothing else keeps an old leaf (a deleted leaf
still answers ``nbytes`` / ``shape``, which is all
:meth:`PagedKVCache.state_bytes` asks; its values raise).  The programs
that only READ the pools (the warm gather, the export) donate nothing.
``note_swap`` counts the calls whose input came back alive
(``pool_copies``: the program copied, expected 0); a call that failed
AFTER it consumed its input leaves deleted leaves behind, which
``pools_lost()`` sees and ``reset_pools()`` cures by zeroing (the
scheduler fails every request that lived in them).
"""

import functools

import numpy

import jax
import jax.numpy as jnp

from veles_tpu.telemetry import trace_named, track_jit


def _block_pair(pool_k, pool_v, src_k, src_v, ids, start):
    # batched block copy, K and V in ONE dispatch: src [1, W, d]
    # staging rows [start, start + n·bs) -> the table's physical
    # blocks (W and the block count are static through the shapes;
    # one executable per bucket; start rides traced so warm-prefix
    # inserts — which skip the shared blocks — share it too)
    n = ids.shape[0]
    bs = pool_k.shape[1]
    d = src_k.shape[-1]
    sk = jax.lax.dynamic_slice(
        src_k, (jnp.int32(0), start, jnp.int32(0)),
        (1, n * bs, d))[0].reshape(n, bs, -1)
    sv = jax.lax.dynamic_slice(
        src_v, (jnp.int32(0), start, jnp.int32(0)),
        (1, n * bs, d))[0].reshape(n, bs, -1)
    return (pool_k.at[ids].set(sk.astype(pool_k.dtype)),
            pool_v.at[ids].set(sv.astype(pool_v.dtype)))


_insert_blocks = track_jit("serving.kv_insert_blocks", jax.jit(
    trace_named("serving.kv_insert_blocks", _block_pair),
    donate_argnums=(0, 1)))


def _stack_block_pair(pool_k, pool_v, src_k, src_v, ids, start):
    # _block_pair for a unit whose cache is several cache layers behind
    # one block table: src [C, 1, W, d] -> pool [C, blocks, bs, d], every
    # cache layer's blocks in ONE dispatch (not one a cache layer)
    n = ids.shape[0]
    c, _, bs, d = pool_k.shape

    def staged(src):
        return jax.lax.dynamic_slice_in_dim(
            src[:, 0], start, n * bs, axis=1).reshape(c, n, bs, d)
    return (pool_k.at[:, ids].set(staged(src_k).astype(pool_k.dtype)),
            pool_v.at[:, ids].set(staged(src_v).astype(pool_v.dtype)))


_insert_stack_blocks = track_jit("serving.kv_insert_blocks", jax.jit(
    trace_named("serving.kv_insert_blocks", _stack_block_pair),
    donate_argnums=(0, 1)))


@functools.lru_cache(maxsize=1)
def _gather_blocks_jit():
    # built lazily (no module-level executable ref): the prefix-cache
    # warm path copies a matched prefix's pool blocks into a staging
    # row so the cold-tail chunked prefill attends over them — the
    # reverse of _block_pair, K and V in ONE dispatch
    def pair(pool_k, pool_v, dst_k, dst_v, ids):
        n = ids.shape[0]
        bs = pool_k.shape[1]
        sk = pool_k[ids].reshape(1, n * bs, -1)
        sv = pool_v[ids].reshape(1, n * bs, -1)
        return (jax.lax.dynamic_update_slice(
                    dst_k, sk.astype(dst_k.dtype), (0, 0, 0)),
                jax.lax.dynamic_update_slice(
                    dst_v, sv.astype(dst_v.dtype), (0, 0, 0)))
    return track_jit("serving.kv_gather_blocks", jax.jit(pair))


def _quant_block_pair(pool_k, pool_v, scale_k, scale_v, src_k, src_v,
                      ids, start):
    # the int8 counterpart of _block_pair: quantize the staging rows
    # per row on the way in, writing the f32 scales at the SAME
    # [block, row] coordinates — scales follow blocks through every
    # later move (donate / evict / gather) because block ids index
    # both arrays
    from veles_tpu.ops.paged_attention import quantize_kv_rows
    n = ids.shape[0]
    bs = pool_k.shape[1]
    d = src_k.shape[-1]
    sk = jax.lax.dynamic_slice(
        src_k, (jnp.int32(0), start, jnp.int32(0)),
        (1, n * bs, d))[0].reshape(n, bs, -1)
    sv = jax.lax.dynamic_slice(
        src_v, (jnp.int32(0), start, jnp.int32(0)),
        (1, n * bs, d))[0].reshape(n, bs, -1)
    qk, sck = quantize_kv_rows(sk)
    qv, scv = quantize_kv_rows(sv)
    return (pool_k.at[ids].set(qk), pool_v.at[ids].set(qv),
            scale_k.at[ids].set(sck), scale_v.at[ids].set(scv))


@functools.lru_cache(maxsize=1)
def _insert_blocks_q8_jit():
    # lazy like _gather_blocks_jit — no module-level executable ref
    return track_jit("serving.kv_quant_insert_blocks",
                     jax.jit(_quant_block_pair,
                             donate_argnums=(0, 1, 2, 3)))


@functools.lru_cache(maxsize=1)
def _gather_blocks_q8_jit():
    # warm-path gather out of an INT8 pool: dequantize the resident
    # rows against their scales into the f32 staging row — the cold
    # tail then attends over exactly the K/V later decode steps read
    def pair(pool_k, pool_v, scale_k, scale_v, dst_k, dst_v, ids):
        from veles_tpu.ops.paged_attention import dequantize_kv
        n = ids.shape[0]
        bs = pool_k.shape[1]
        sk = dequantize_kv(pool_k[ids], scale_k[ids],
                           dst_k.dtype).reshape(1, n * bs, -1)
        sv = dequantize_kv(pool_v[ids], scale_v[ids],
                           dst_v.dtype).reshape(1, n * bs, -1)
        return (jax.lax.dynamic_update_slice(dst_k, sk, (0, 0, 0)),
                jax.lax.dynamic_update_slice(dst_v, sv, (0, 0, 0)))
    return track_jit("serving.kv_quant_gather_blocks", jax.jit(pair))


@functools.lru_cache(maxsize=1)
def _export_blocks_jit():
    # disaggregated prefill→decode handoff, the OUT half: gather a
    # slot's blocks RAW out of two same-indexed pool arrays (K/V
    # pair, or the scale pair — the function is dtype/shape generic,
    # so int8 pools and their f32 scales ride the same executable
    # family and the exported bytes are exactly the resident bytes,
    # no dequant round trip)
    def pair(a, b, ids):
        return a[ids], b[ids]
    return track_jit("serving.kv_export_blocks", jax.jit(pair))


@functools.lru_cache(maxsize=1)
def _import_blocks_jit():
    # the IN half: scatter previously exported raw blocks into a
    # decode replica's own table blocks — same generic pairing, so
    # int8 blocks land unrequantized (bit-identical to the exporting
    # pool) and their scales follow through the same call
    def pair(a, b, src_a, src_b, ids):
        return (a.at[ids].set(src_a.astype(a.dtype)),
                b.at[ids].set(src_b.astype(b.dtype)))
    return track_jit("serving.kv_import_blocks",
                     jax.jit(pair, donate_argnums=(0, 1)))


def _units_of_kind(forwards, kind):
    return {i: u.name for i, u in enumerate(forwards)
            if hasattr(u, "init_cache")
            and getattr(u, "cache_kind", "paged") == kind}


def slot_state_units(forwards):
    """{chain index: unit name} of the cacheable units whose cache is
    ONE fixed state per slot (``cache_kind == "slot"``: a short
    convolution's last rows) and not rows that grow with the text."""
    return _units_of_kind(forwards, "slot")


def stacked_units(forwards):
    """{chain index: unit name} of the cacheable units whose cache is
    SEVERAL cache layers of paged rows behind one block table
    (``cache_kind == "stack"``: a stack run several times a token keeps
    a K/V row pair for every layer application)."""
    return _units_of_kind(forwards, "stack")


def state_refusal(what, units):
    """The error of asking, for a chain with per-slot state, for what
    does not carry it (``units``: :func:`slot_state_units`)."""
    return ValueError(
        "%s is not carried for a chain with per-slot state (%s): "
        "blocks alone do not hold its requests"
        % (what, ", ".join(sorted(units.values()))))


def stack_refusal(what, units):
    """The error of asking, for a chain that holds a stack of cache
    layers behind one block table, for what does not carry it
    (``units``: :func:`stacked_units`)."""
    return ValueError(
        "%s is not carried for a stack of cache layers behind one block "
        "table (%s): its programs move one layer's K and V pair"
        % (what, ", ".join(sorted(units.values()))))


def blocks_only_refusal(what, state_units, stack_units):
    """The error of asking for ``what``, which moves blocks of ONE
    layer's K and V pair, on a chain that holds more than such blocks
    (:func:`slot_state_units`, :func:`stacked_units`); None when the
    chain holds nothing else."""
    if state_units:
        return state_refusal(what, state_units)
    if stack_units:
        return stack_refusal(what, stack_units)
    return None


def _state_rows(pool, src, slot):
    # a per-slot state is written WHOLE: batch-1 staging -> row slot
    return {name: jax.lax.dynamic_update_slice(
        pool[name], src[name].astype(pool[name].dtype),
        (slot,) + (jnp.int32(0),) * (pool[name].ndim - 1))
        for name in pool}


_insert_state = track_jit("serving.kv_insert_state", jax.jit(
    trace_named("serving.kv_insert_state", _state_rows),
    donate_argnums=(0,)))


def _pairs_only(state, names, what):
    """Refuse, in words, a layer whose cache is not exactly ``names``:
    the paired programs hand each array over donated, once."""
    for i, layer in state.items():
        if set(layer) != set(names):
            raise ValueError(
                "%s holds %s for chain unit %s, not %s: its insert, "
                "gather, export and import programs move K and V as "
                "one donated pair" % (what, sorted(layer), i,
                                      sorted(names)))


class PagedKVCache:
    """Block-paged K/V pools + per-slot block tables.

    ``block_size`` tokens per block; ``kv_blocks`` — the pool's
    usable capacity in blocks (default:
    ``max_slots · ceil(window / block_size)``, so a default-sized pool
    admits ``max_slots`` requests of full length).  ``window`` stays the
    per-request length bound (the positional-table limit), NOT a
    per-request memory reservation.

    ``kv_dtype`` — ``"fp32"`` (the compute-dtype pools above; parity
    baseline, byte-for-byte the PR 5 layout) or ``"int8"``: pools
    stored as int8 with per-row f32 dequant scales
    ([num_blocks, block_size], keys ``k_scale``/``v_scale``) living
    beside them in the same per-layer dict.  Scales are indexed by
    PHYSICAL block id exactly like the pools, so they follow blocks
    through every ownership move — prefix-cache donation, eviction,
    warm gather, preempt→resume — with no extra bookkeeping.
    Inserts quantize (``serving.kv_quant_insert_blocks``), the warm
    gather dequantizes (``serving.kv_quant_gather_blocks``), and the
    decode/verify steps quantize-on-scatter / dequant-on-gather in
    ``ops/paged_attention.py``.

    TWO KINDS OF STATE.  Each cacheable unit is asked what it holds
    (:func:`slot_state_units`): paged rows as above, or one fixed
    state per slot.  A state pool is ``init_cache(max_slots + 1, ...)``
    — row ``max_slots`` is the trash row that a packed step's padding
    rows read and write — indexed by SLOT, written whole by
    :meth:`insert` and by every decode step, never zeroed (an
    admission's staging starts from ``init_cache``'s zeros) and
    forgotten at release.  It costs no block: ``bytes_per_token`` and
    admission count the paged layers alone, :meth:`state_bytes`
    gives both.  A prefix of blocks says nothing about such a state,
    so block export/import, the warm gather, int8 pools and a tp mesh
    refuse a chain that has one.

    THE THIRD KIND: A STACK.  A unit that is a whole stack of layers
    run several times a token (:func:`stacked_units`) holds a K/V row
    pair for every layer application: its pool is ONE array pair
    ``[cache layers, num_blocks, block_size, d]`` behind the same block
    table, its staging ``[cache layers, 1, width, d]``.  A block id
    names that block of every cache layer, so admission, release and
    the tables are the paged kind's; ``bytes_per_token`` counts every
    cache layer, and :meth:`insert` scatters all of them in one
    dispatch.  The programs that move ONE layer's pair (export/import,
    the warm gather, int8 pools, a tp mesh) refuse it in words."""

    #: state-returning calls that came back / that copied
    pool_swaps = pool_copies = 0

    def __init__(self, forwards, max_slots, window, block_size=16,
                 kv_blocks=None, kv_dtype="fp32", tp=None):
        from veles_tpu import dtypes
        self.max_slots = int(max_slots)
        self.window = int(window)
        self.block_size = int(block_size)
        if self.max_slots < 1 or self.window < 2:
            raise ValueError("need max_slots >= 1 and window >= 2")
        if self.block_size < 1:
            raise ValueError("need block_size >= 1")
        if kv_dtype not in ("fp32", "int8"):
            raise ValueError("kv_dtype must be 'fp32' or 'int8'")
        self.kv_dtype = kv_dtype
        self.blocks_per_slot = -(-self.window // self.block_size)
        self.capacity_blocks = int(
            kv_blocks or self.max_slots * self.blocks_per_slot)
        if self.capacity_blocks < 1:
            raise ValueError("need kv_blocks >= 1")
        num = self.capacity_blocks + 1          # + the trash block 0
        self.state_units = slot_state_units(forwards)
        self.stack_units = stacked_units(forwards)
        if tp is not None:
            self._blocks_only("tp")
        if kv_dtype == "int8":
            self._blocks_only("kv_dtype='int8'")
            # int8 needs block-pool-aware units (the scale layout is
            # theirs to consume in apply_step_paged)
            missing = [type(u).__name__ for u in forwards
                       if hasattr(u, "init_cache")
                       and not hasattr(u, "init_block_pool")]
            if missing:
                raise ValueError(
                    "kv_dtype='int8' needs init_block_pool on every "
                    "cacheable block; missing on %s" % missing)
            self.pools = {
                i: u.init_block_pool(num, self.block_size,
                                     dtypes.compute_dtype(),
                                     kv_dtype="int8")
                for i, u in enumerate(forwards)
                if hasattr(u, "init_cache")}
        else:
            self.pools = {
                i: u.init_cache(
                    self.max_slots + 1 if i in self.state_units
                    else num, self.block_size, dtypes.compute_dtype())
                for i, u in enumerate(forwards)
                if hasattr(u, "init_cache")}
        if not self.pools:
            raise ValueError("chain has no cacheable blocks")
        _pairs_only(
            {i: layer for i, layer in self.pools.items()
             if i not in self.state_units},
            ("k", "v", "k_scale", "v_scale") if kv_dtype == "int8"
            else ("k", "v"), "the paged cache")
        #: tensor-parallel serving context (serving/tp.py) — pools
        #: shard HEAD-WISE over the mesh (each chip stores
        #: [num_blocks, block_size, d/tp]; scales replicate), so the
        #: per-chip HBM a kv_blocks budget costs drops by the mesh
        #: factor; the compiled steps read the ctx off the cache
        self.tp_ = tp
        if tp is not None:
            self.pools = tp.shard_pools(self.pools)
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._free_blocks = list(range(num - 1, 0, -1))
        #: host-side tables [max_slots, blocks_per_slot]; entries past
        #: a slot's live count stay 0 (the trash block)
        self.tables = numpy.zeros(
            (self.max_slots, self.blocks_per_slot), numpy.int32)
        self.n_blocks = numpy.zeros((self.max_slots,), numpy.int32)
        #: leading SHARED blocks per slot (prefix-cache residents the
        #: slot reads but does not own — release hands them back to
        #: the caller instead of the free list; decode never writes
        #: them because the cold offset starts past the shared range)
        self.n_shared = numpy.zeros((self.max_slots,), numpy.int32)
        #: what the chain's units counted in the last decode step:
        #: {kind: a small device array} (serving/engine.STEP_COUNTS)
        self.step_counts = {}
        #: where the last decode step left its tokens, if it COMMITTED
        #: them there (None before the first step, and for a step over
        #: uncommitted parameters): the step places host tokens alike,
        #: so a launch from the host and one from the previous step's
        #: device tokens are ONE call signature
        self.token_sharding = None

    # -- occupancy reads ------------------------------------------------

    @property
    def free_slots(self):
        return len(self._free_slots)

    @property
    def free_blocks(self):
        return len(self._free_blocks)

    @property
    def used_blocks(self):
        return self.capacity_blocks - len(self._free_blocks)

    def bytes_per_token(self):
        """PER-CHIP HBM bytes ONE cached token costs across every
        layer's pools — the denominator of "streams per HBM dollar"
        (int8 pays ``2·d + 8`` per layer where the compute dtype pays
        ``2·d·itemsize``; reported in ``/serving/metrics`` and
        Prometheus as ``kv_bytes_per_token``).  Under tensor-parallel
        serving the K/V contribution divides by the mesh factor —
        each chip stores ``d/tp`` of every row — while the replicated
        scales still cost every chip their full byte."""
        shards = self.tp_.size if self.tp_ is not None else 1
        total = 0
        for i, layer in self.pools.items():
            if i in self.state_units:    # costs a slot, not a token
                continue
            for name, arr in layer.items():
                if name.endswith("_scale"):   # one scale per row
                    total += arr.dtype.itemsize
                else:    # a row, of every cache layer of a stack
                    total += int(numpy.prod(arr.shape[:-3])) \
                        * arr.shape[-1] * arr.dtype.itemsize // shards
        return int(total)

    def state_bytes(self):
        """{"kv": bytes of the paged pools, "conv": bytes of the
        per-slot state pools' short-convolution rows, and a further
        kind for each other array a state unit keeps a slot (a
        delta-rule layer's matrix ``S``), by its name} resident on the
        devices.  Metadata alone (``nbytes``), so it answers from any
        thread, also on a leaf that a step in flight has consumed."""
        out = {"kv": 0, "conv": 0}
        for i, layer in self.pools.items():
            for name, a in layer.items():
                kind = name if i in self.state_units else "kv"
                out[kind] = out.get(kind, 0) + a.nbytes
        return out

    def first_leaf(self):
        """The first array of the device state: what a caller hands to
        :meth:`note_swap` once its call has come back."""
        return next(iter(next(iter(self.pools.values())).values()))

    def note_swap(self, old):
        """Account one state-returning call that came back: ``old`` is
        the first leaf that went in donated.  Still alive (a host-side
        flag, no device sync) means the program could not write in
        place and copied (``veles_serving_pool_copies_total``,
        expected 0)."""
        self.pool_swaps += 1
        if not old.is_deleted():
            self.pool_copies += 1

    def pools_lost(self):
        """True when a call that failed after it consumed its donated
        input left deleted leaves behind."""
        return any(a.is_deleted() for a in jax.tree.leaves(self.pools))

    def reset_pools(self):
        """Zero the whole device state: every request and every
        resident prefix in it is lost (the caller fails and forgets
        them).  Shape, dtype and sharding still answer on a deleted
        leaf."""
        self.pools = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype, device=a.sharding),
            self.pools)
        self.step_counts = {}

    def _blocks_only(self, what):
        refused = blocks_only_refusal(what, self.state_units,
                                      self.stack_units)
        if refused is not None:
            raise refused

    def blocks_needed(self, total_tokens):
        return -(-max(int(total_tokens), 1) // self.block_size)

    def alloc(self, total_tokens, shared=()):
        """Claim a slot and its full block budget, or None when slots
        or blocks are exhausted.  ``shared`` — block ids of an
        already-resident prompt prefix (prefix-cache hit): they head
        the table READ-ONLY and only ``need - len(shared)`` NEW
        blocks are claimed, which is how a warm prompt raises the
        concurrent-stream ceiling."""
        need = self.blocks_needed(total_tokens)
        shared = [int(b) for b in shared]
        if need > self.blocks_per_slot:
            raise ValueError(
                "request of %d tokens needs %d blocks > %d per-slot "
                "table width" % (total_tokens, need,
                                 self.blocks_per_slot))
        if len(shared) >= need:
            raise ValueError(
                "shared prefix of %d blocks must leave at least one "
                "private block of the %d-block budget"
                % (len(shared), need))
        if not self._free_slots \
                or need - len(shared) > len(self._free_blocks):
            return None
        slot = self._free_slots.pop()
        ids = shared + [self._free_blocks.pop()
                        for _ in range(need - len(shared))]
        self.tables[slot, :need] = ids
        self.tables[slot, need:] = 0
        self.n_blocks[slot] = need
        self.n_shared[slot] = len(shared)
        return slot

    def release(self, slot, donate=0):
        """Free a slot.  The leading shared blocks are handed BACK
        (never freed — the prefix cache still owns them); the next
        ``donate`` private blocks transfer ownership to the caller
        (a finishing request donating its prompt+generated prefix to
        the cache); the rest return to the free list.  Returns
        ``(shared_ids, donated_ids)``."""
        slot = int(slot)
        if slot in self._free_slots:
            raise ValueError("slot %d double-freed" % slot)
        n = int(self.n_blocks[slot])
        ns = int(self.n_shared[slot])
        donate = int(donate)
        if donate < 0 or ns + donate > n:
            raise ValueError(
                "donate=%d outside slot %d's %d private blocks"
                % (donate, slot, n - ns))
        row = [int(b) for b in self.tables[slot, :n]]
        shared, donated = row[:ns], row[ns:ns + donate]
        self._free_blocks.extend(reversed(row[ns + donate:]))
        self.tables[slot, :] = 0
        self.n_blocks[slot] = 0
        self.n_shared[slot] = 0
        self._free_slots.append(slot)
        return shared, donated

    def reclaim(self, ids):
        """Return blocks whose ownership left the slot machinery
        (prefix-cache evictions, duplicate donations) to the free
        list."""
        for b in ids:
            b = int(b)
            if b < 1 or b > self.capacity_blocks:
                raise ValueError("reclaim of invalid block %d" % b)
            if b in self._free_blocks:
                raise ValueError("block %d double-freed" % b)
            self._free_blocks.append(b)

    def take_free_blocks(self, n):
        """Claim ``n`` blocks off the free list OUTSIDE the slot
        machinery — the tiered-KV ingest path (host-tier promotion,
        peer prefix import) fills them via :meth:`import_blocks` and
        hands ownership straight to the prefix cache.  Returns the
        id list, or None when the free list is short (the ingest is
        best-effort and simply stays cold)."""
        n = int(n)
        if n < 0 or n > len(self._free_blocks):
            return None
        return [self._free_blocks.pop() for _ in range(n)]

    def check(self, resident=()):
        """Invariant sweep (tests): every block is exactly one of
        {trash, free, resident-in-the-prefix-cache,
        privately-owned-by-one-slot}, and every slot's SHARED prefix
        blocks appear in ``resident`` (they are counted once, as the
        cache's)."""
        resident = set(int(b) for b in resident)
        live = []
        for slot in range(self.max_slots):
            if slot not in self._free_slots:
                ns = int(self.n_shared[slot])
                row = [int(b) for b in
                       self.tables[slot, :self.n_blocks[slot]]]
                assert set(row[:ns]) <= resident, \
                    "slot %d shares non-resident blocks %s" \
                    % (slot, sorted(set(row[:ns]) - resident))
                live.extend(row[ns:])
        owned = live + [int(b) for b in self._free_blocks] \
            + sorted(resident)
        assert 0 not in owned, "trash block leaked into circulation"
        assert len(owned) == len(set(owned)), "block double-owned"
        assert len(owned) == self.capacity_blocks, \
            "block leaked: %d tracked of %d" % (len(owned),
                                                self.capacity_blocks)
        if self.kv_dtype == "int8":
            # scales-follow-blocks: every int8 pool must carry scale
            # arrays indexed by the same block axis (content checks
            # ride the gather/insert tests; this catches a layer
            # whose scales were dropped on a functional swap)
            for i, layer in self.pools.items():
                assert {"k", "v", "k_scale", "v_scale"} \
                    <= set(layer), \
                    "layer %s lost its scale arrays" % (i,)
                for name in ("k", "v"):
                    assert layer[name + "_scale"].shape \
                        == layer[name].shape[:2], \
                        "layer %s %s_scale shape drifted" % (i, name)

    def table_rows(self, slots, width):
        """The packed [len(slots), width] block-table batch the
        compiled paged step gathers through."""
        return self.tables[numpy.asarray(slots, numpy.intp), :width]

    def insert(self, slot, row_caches, length, from_block=0):
        """Block-scatter a prefilled batch-1 staging row (width a
        multiple of block_size, rows ≥ length zeroed) into ``slot``'s
        table blocks ``[from_block, ceil(length / block_size))``.
        ``from_block`` skips a warm shared prefix: those staging rows
        were GATHERED from the resident blocks (:meth:`load_staging`)
        and must not be written back through the shared table
        entries."""
        need = self.blocks_needed(length)
        f = int(from_block)
        if need > int(self.n_blocks[slot]):
            raise ValueError(
                "insert of %d tokens exceeds slot %d's %d-block "
                "budget" % (length, slot, int(self.n_blocks[slot])))
        if f >= need:
            raise ValueError(
                "from_block %d leaves nothing of the %d-block insert"
                % (f, need))
        # a host COPY: on the CPU backend ``jnp.asarray`` of a numpy
        # view may alias the host table, which ``release`` zeroes while
        # the asynchronous scatter has yet to read its ids
        ids = jnp.asarray(self.tables[slot, f:need].copy())
        start = jnp.int32(f * self.block_size)
        for i in self.pools:
            src = row_caches[i]
            if i in self.state_units:
                self._insert_state(i, src, slot)
                continue
            wk = next(iter(src.values())).shape[-2]
            if wk < need * self.block_size:
                raise ValueError(
                    "staging width %d < %d blocks x %d" %
                    (wk, need, self.block_size))
            layer = self.pools[i]
            old = layer["k"]
            if self.kv_dtype == "int8":
                k, v, sk, sv = _insert_blocks_q8_jit()(
                    old, layer["v"], layer["k_scale"],
                    layer["v_scale"], src["k"], src["v"], ids, start)
                self.pools[i] = {"k": k, "v": v, "k_scale": sk,
                                 "v_scale": sv}
            else:
                fn = _insert_stack_blocks if i in self.stack_units \
                    else _insert_blocks
                k, v = fn(old, layer["v"], src["k"], src["v"], ids,
                          start)
                self.pools[i] = {"k": k, "v": v}
            self.note_swap(old)

    def _insert_state(self, i, src, slot):
        state = self.pools[i]
        old = next(iter(state.values()))
        self.pools[i] = _insert_state(state, src, jnp.int32(slot))
        self.note_swap(old)

    def export_blocks(self, ids):
        """Gather blocks ``ids`` RAW out of every layer's pools for a
        disaggregated prefill→decode handoff: returns
        ``{layer: {"k", "v"[, "k_scale", "v_scale"]}}`` host numpy
        arrays, K/V shaped ``[len(ids), block_size, d]`` in the
        pool's storage dtype (int8 stays int8 — its scales travel in
        the same record, so the importing replica reproduces the
        resident bytes exactly, no dequant→requant noise)."""
        self._blocks_only("block export")
        ids = jnp.asarray(numpy.asarray(ids, numpy.int32))
        fn = _export_blocks_jit()
        out = {}
        for i, layer in self.pools.items():
            if self.kv_dtype == "int8":
                k, v = fn(layer["k"], layer["v"], ids)
                sk, sv = fn(layer["k_scale"], layer["v_scale"], ids)
                got = {"k": k, "v": v, "k_scale": sk, "v_scale": sv}
            else:
                k, v = fn(layer["k"], layer["v"], ids)
                got = {"k": k, "v": v}
            out[i] = {n: numpy.asarray(a) for n, a in got.items()}
        return out

    def import_blocks(self, ids, layers):
        """Scatter a :meth:`export_blocks` record into THIS cache's
        blocks ``ids`` (a decode-specialist adopting a prefill
        replica's finished KV): raw block contents land unconverted —
        the importing table's blocks end up byte-identical to the
        exporter's, scales included — so the decode loop attends over
        exactly the K/V the colocated path would have."""
        self._blocks_only("block import")
        ids_j = jnp.asarray(numpy.asarray(ids, numpy.int32))
        n = int(len(ids))
        fn = _import_blocks_jit()
        for i, layer in self.pools.items():
            src = layers[i]
            ref = src["k"] if "k" in src else next(iter(src.values()))
            if ref.shape[0] != n or ref.shape[1] != self.block_size:
                raise ValueError(
                    "imported layer %s blocks %s do not fit %d x "
                    "block_size %d" % (i, ref.shape[:2], n,
                                       self.block_size))
            if self.kv_dtype == "int8" and "k_scale" not in src:
                raise ValueError(
                    "int8 import needs k_scale/v_scale riding "
                    "the exported blocks")
            old = layer["k"]
            k, v = fn(old, layer["v"], jnp.asarray(src["k"]),
                      jnp.asarray(src["v"]), ids_j)
            # swapped at once: a refused scale pair must not leave
            # the consumed K and V behind as the cache's
            layer = self.pools[i] = dict(layer, k=k, v=v)
            if self.kv_dtype == "int8":
                sk, sv = fn(layer["k_scale"], layer["v_scale"],
                            jnp.asarray(src["k_scale"]),
                            jnp.asarray(src["v_scale"]), ids_j)
                self.pools[i] = dict(layer, k_scale=sk, v_scale=sv)
            self.note_swap(old)

    def load_staging(self, row_caches, ids):
        """Copy resident blocks ``ids`` (a matched prompt prefix)
        into the FRONT of a batch-1 staging row — the warm half of a
        prefix-cache admission: the cold tail's chunked prefill then
        attends over these rows exactly as if it had prefilled them
        itself (the resident K/V was produced by the identical
        computation).  Returns the updated staging dict."""
        self._blocks_only("the warm gather of a prefix")
        if not len(ids):
            return row_caches
        ids = jnp.asarray(numpy.asarray(ids, numpy.int32))
        if self.kv_dtype == "int8":
            fn = _gather_blocks_q8_jit()
            out = {}
            for i, layer in self.pools.items():
                src = row_caches[i]
                k, v = fn(layer["k"], layer["v"], layer["k_scale"],
                          layer["v_scale"], src["k"], src["v"], ids)
                out[i] = {"k": k, "v": v}
            return out
        fn = _gather_blocks_jit()
        out = {}
        for i, layer in self.pools.items():
            src = row_caches[i]
            k, v = fn(layer["k"], layer["v"], src["k"], src["v"], ids)
            out[i] = {"k": k, "v": v}
        return out
