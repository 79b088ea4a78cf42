"""Block-based paged KV cache for continuous-batching transformer serving.

Instead of one dense ``(B, max_len, Hkv, D)`` slab per batch, K/V live in
a shared pool of fixed-size blocks:

    k_pool, v_pool : (num_layers, P, Hkv, block_size, D)

where ``P = num_blocks + 1`` — the last block is a *garbage* block that
masked (inactive) rows write into, so the jit'd step never needs a
dynamic write mask.  Each decode slot owns an ordered list of pool
blocks; the ``(max_slots, blocks_per_slot)`` block table maps a slot's
logical context position ``p`` to pool coordinates
``(table[slot, p // bs], p % bs)``.  Attention reads straight through
the table (:func:`repro.kernels.decode_attention.paged_decode_attention`),
so blocks never need to be contiguous and freeing is defrag-free: a
freed block goes back on the free list and can be handed to any slot.

Blocks are **reserved** at admission but **allocated on demand**:
:meth:`allocate_slot` records the request's worst-case footprint
(``ceil(total_len / bs)`` blocks) against the pool without touching the
free list, and :meth:`ensure_capacity` pulls physical blocks as the
slot's written length actually grows.  Reservation accounting keeps the
original no-mid-flight-starvation guarantee — admission only succeeds
while ``sum(reserved) + new <= num_blocks``, so a running slot's growth
can never find the free list empty — while on-demand allocation means a
slot holds only the blocks behind its *current* length.  That is what
makes speculative-decoding rollback cheap: rejected draft positions are
undone by :meth:`truncate_slot`, which rewinds the slot's length and
returns any block that no longer backs a written position to the free
list (no copying — the table indirection already decouples logical
position from storage).

The allocator is host-side (plain Python): allocation happens at
admission/growth, outside jit, and only the table *contents* change
shape-free between steps.  Pool layout is head-major ``(..., Hkv, bs, D)``
so the Pallas kernel DMAs contiguous ``(bs, D)`` tiles per (block, head)
and the per-step write is a single advanced-index scatter.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` block ids with leak and
    double-free detection (serving runs for ever; a leaked block is a
    slow OOM, a double-freed one is silent cross-request corruption)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._allocated: set = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def allocated_count(self) -> int:
        return len(self._allocated)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: requested {n} blocks, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        self._allocated.update(out)
        return out

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if b not in self._allocated:
                raise RuntimeError(f"double-free of KV block {b}")
            self._allocated.remove(b)
            self._free.append(b)

    def check_conservation(self) -> None:
        assert len(self._free) + len(self._allocated) == self.num_blocks, (
            len(self._free), len(self._allocated), self.num_blocks)
        assert not (set(self._free) & self._allocated)


class PagedKVCache:
    """Device block pools + host block table for one model.

    ``slot`` lifecycle: :meth:`allocate_slot` at admission *reserves*
    every block the request can ever touch (``ceil(total_len / bs)``,
    which bounds in-flight speculative draft positions too — the engine
    clamps per-slot drafts to the remaining generation budget, so a
    draft row never writes past ``total_len - 1``); :meth:`ensure_capacity`
    allocates physical blocks as the written length grows;
    :meth:`truncate_slot` rewinds it (speculative rollback);
    :meth:`free_slot` at eviction returns blocks and reservation alike.
    Stale pool contents need no zeroing — attention masks by per-row
    length, and a reused position is overwritten before the slot's
    length grows past it.
    """

    num_shards = 1   # ShardedPagedKVCache overrides; schedulers branch on it
    # Per-block scale pools: None for full-precision caches; the
    # quantized variants (repro.quant.kv_cache) allocate (L, P, Hkv)
    # float32 absmax scales indexed by the same block ids as the pools.
    k_scales = None
    v_scales = None

    def __init__(self, cfg: ModelConfig, serve: ServeConfig):
        self.cfg = cfg
        self.serve = serve
        self.block_size = serve.kv_block_size
        self.num_blocks = serve.resolved_num_blocks
        self.garbage_block = self.num_blocks          # index P-1, never allocated
        self.allocator = BlockAllocator(self.num_blocks)
        self._alloc_pools(cfg, serve)
        # host-side table; unassigned entries point at the garbage block
        # (always a valid pool index, always masked by length)
        self.block_table = np.full((serve.max_slots, serve.blocks_per_slot),
                                   self.garbage_block, dtype=np.int32)
        self._slot_blocks: Dict[int, List[int]] = {}
        self._slot_reserved: Dict[int, int] = {}      # worst-case block count
        self.reserved_total = 0

    def _alloc_pools(self, cfg: ModelConfig, serve: ServeConfig) -> None:
        """Create the device pools.  The quantized variants override
        this with int8 code pools plus float32 scale pools.

        Latent attention (``cfg.mla``) keeps one latent pool of
        ``(L, P, 1, bs, kv_entry_dim)`` (the latent and the roped key,
        padded to 128 lanes) and no V pool: the values are the entries'
        leading lanes.  A config with layer groups (a leading dense
        group) holds its pool as one array per group, in group order:
        each group's layer scan carries its pool whole and each layer's
        paged op writes and reads its own layer of it in place, so the
        step neither splits, joins nor slices a pool."""
        pool_shape = (self.num_blocks + 1, cfg.num_kv_heads, self.block_size,
                      cfg.kv_entry_dim)
        dtype = cfg.activation_dtype

        def pool():
            pools = tuple(jnp.zeros((n,) + pool_shape, dtype)
                          for _, n in cfg.layer_groups)
            return pools if len(pools) > 1 else pools[0]

        self.k_pool = pool()
        self.v_pool = None if cfg.mla else pool()

    @property
    def block_bytes(self) -> int:
        """Device bytes one KV block costs across all layers (K + V, or
        the latent entry alone).  Computed from the config (not the live
        pools) so detached sub-caches report it too."""
        cfg = self.cfg
        per_entry = (cfg.num_kv_heads * self.block_size * cfg.kv_entry_dim)
        pools = 1 if cfg.mla else 2
        return pools * cfg.num_layers * per_entry * cfg.activation_dtype.itemsize

    def blocks_needed(self, total_len: int) -> int:
        return -(-total_len // self.block_size)

    @property
    def max_request_blocks(self) -> int:
        """Largest worst-case footprint any single request may reserve.
        The whole pool here; one *shard's* pool under a sharded cache
        (a request lives entirely on the shard that owns its slot)."""
        return self.num_blocks

    def can_allocate_slot_on(self, slot: int, total_len: int, prompt=None) -> bool:
        """Admission gate for a *specific* slot.  All slots draw on the
        one pool here, so the slot is irrelevant; the sharded cache
        routes to the allocator of the shard owning ``slot``."""
        return self.can_allocate_slot(total_len, prompt=prompt)

    def row_table(self, slot: int) -> np.ndarray:
        """Block-table row the jit'd step should attend through for
        ``slot`` — pool-local ids here, *shard-local* ids under a
        sharded cache (each shard_map body indexes its own pool slice)."""
        return self.block_table[slot]

    def detach_pools(self) -> None:
        """Drop this cache's device pools.  Used by the sharded cache,
        which owns one stacked global pool and keeps sub-caches for host
        accounting (tables, allocators, reservations) only."""
        self.k_pool = self.v_pool = None
        self.k_scales = self.v_scales = None

    def can_allocate_slot(self, total_len: int, prompt=None) -> bool:
        """Admission gate: does the pool have unreserved room for this
        request's worst-case footprint?  Gating on *reservations* (not
        the free list) preserves the no-starvation invariant under
        on-demand allocation: every admitted slot can always grow to its
        reserved bound.  ``prompt`` is ignored here; the prefix-caching
        subclass matches it against cached blocks and charges only the
        unshared footprint."""
        return (self.reserved_total + self.blocks_needed(total_len)
                <= self.num_blocks)

    def allocate_slot(self, slot: int, total_len: int, prompt=None) -> int:
        """Reserve ``slot``'s worst-case footprint.  Returns the number
        of prompt tokens already backed by cached KV blocks — always 0
        here; ``PrefixCachingKVCache`` binds matched blocks and returns
        how much prefill can be skipped."""
        assert slot not in self._slot_reserved, f"slot {slot} already allocated"
        need = self.blocks_needed(total_len)
        if self.reserved_total + need > self.num_blocks:
            raise RuntimeError(
                f"KV pool over-reserved: slot {slot} needs {need} blocks, "
                f"{self.num_blocks - self.reserved_total} unreserved")
        self._slot_reserved[slot] = need
        self.reserved_total += need
        self._slot_blocks[slot] = []
        self.block_table[slot, :] = self.garbage_block
        return 0

    def commit(self, slot: int, tokens) -> None:
        """Confirm the token contents behind ``slot``'s written
        positions.  A no-op without prefix caching; the prefix-caching
        subclass publishes newly full blocks into its content index."""

    def free_slot(self, slot: int) -> None:
        blocks = self._slot_blocks.pop(slot)
        if blocks:
            self.allocator.free(blocks)
        self.reserved_total -= self._slot_reserved.pop(slot)
        self.block_table[slot, :] = self.garbage_block

    def ensure_capacity(self, slot: int, length: int) -> None:
        """Allocate any missing physical blocks so positions
        ``[0, length)`` of ``slot`` are backed.  Never exceeds the
        slot's admission-time reservation (the growth that reservation
        guarantees can always be satisfied)."""
        need = self.blocks_needed(length)
        held = self._slot_blocks[slot]
        assert need <= self._slot_reserved[slot], (
            f"slot {slot}: length {length} needs {need} blocks, "
            f"reserved only {self._slot_reserved[slot]}")
        if need > len(held):
            new = self.allocator.alloc(need - len(held))
            self.block_table[slot, len(held):need] = new
            held.extend(new)

    def truncate_slot(self, slot: int, new_len: int) -> None:
        """Speculative rollback: rewind ``slot`` so only positions
        ``[0, new_len)`` are considered written.  Blocks past the new
        length (over-allocated for rejected draft positions) return to
        the free list; the reservation is untouched (the request is
        still running and may grow back).  No data moves — the next
        write at a rewound position simply overwrites stale K/V, which
        per-row lengths already mask until then."""
        keep = self.blocks_needed(new_len) if new_len > 0 else 0
        held = self._slot_blocks[slot]
        if keep < len(held):
            self.allocator.free(held[keep:])
            self.block_table[slot, keep:] = self.garbage_block
            del held[keep:]

    def write_coords(self, slot: int, position: int) -> Tuple[int, int]:
        """Pool (block, offset) for logical ``position`` of ``slot``."""
        b, o = divmod(position, self.block_size)
        return int(self.block_table[slot, b]), o

    # -- preemption swap hooks (repro.serving.slo) ---------------------------

    def warm_prefix_tokens(self, prompt) -> int:
        """Prompt tokens already backed by cached KV (the ``cache_aware``
        admission signal).  Always 0 without prefix caching."""
        return 0

    def swap_footprint(self, slot: int) -> int:
        """Host blocks a swap-out of ``slot`` would consume (owned
        blocks only; the prefix subclass excludes bound shared blocks)."""
        return len(self._slot_blocks[slot])

    def swap_out(self, slot: int, swap, *, uid: int, total_len: int,
                 context_len: int):
        """Copy ``slot``'s blocks into the host pool and release the
        slot (blocks, reservation, table row).  Returns the
        :class:`~repro.serving.slo.swap.SwapRecord` restore needs."""
        rec = swap.store(self, uid=uid, total_len=total_len,
                         context_len=context_len,
                         blocks=list(self._slot_blocks[slot]),
                         skip=0, hashes=[])
        self.free_slot(slot)
        return rec

    def can_restore(self, rec) -> bool:
        """Admission gate for a preempted request: same reservation test
        as a fresh request of the recorded worst-case footprint."""
        return self.can_allocate_slot(rec.total_len)

    def restore_slot(self, slot: int, rec, swap) -> int:
        """Rebuild ``slot`` from a swap record: re-reserve the worst-case
        footprint, allocate device blocks for the recorded context, and
        upload the host copies.  Returns the resume position (always the
        full recorded context here; the prefix subclass may return less
        when an evicted shared block forces recompute-by-prefill).  The
        caller releases ``rec``'s host blocks afterwards."""
        self.allocate_slot(slot, rec.total_len)
        self.ensure_capacity(slot, rec.context_len)
        held = self._slot_blocks[slot]
        swap.load(self, [(rec.host_of[k], held[k])
                         for k in range(rec.num_blocks)])
        return rec.context_len

    def held_blocks(self, slot: int) -> int:
        return len(self._slot_blocks.get(slot, ()))

    def check_conservation(self) -> None:
        """Allocator conservation plus reservation/table invariants:
        held <= reserved per slot, total reservation within the pool,
        and no table row dangles (entries beyond a slot's held blocks
        point at the garbage block; entries within match its blocks)."""
        self.allocator.check_conservation()
        held_total = 0
        for slot, blocks in self._slot_blocks.items():
            held_total += len(blocks)
            assert len(blocks) <= self._slot_reserved[slot], (slot, blocks)
            assert list(self.block_table[slot, :len(blocks)]) == blocks
            assert (self.block_table[slot, len(blocks):]
                    == self.garbage_block).all()
        assert held_total == self.allocator.allocated_count
        assert self.reserved_total == sum(self._slot_reserved.values())
        assert self.reserved_total <= self.num_blocks
        # every slot with no state has an all-garbage table row
        for slot in range(self.block_table.shape[0]):
            if slot not in self._slot_blocks:
                assert (self.block_table[slot] == self.garbage_block).all()

    def update_pools(self, k_pool: jax.Array, v_pool: jax.Array,
                     k_scales=None, v_scales=None) -> None:
        """Adopt the step function's donated-output pools (and scale
        pools, when quantized)."""
        self.k_pool = k_pool
        self.v_pool = v_pool
        if k_scales is not None:
            self.k_scales = k_scales
            self.v_scales = v_scales

    def occupancy(self) -> list:
        """Per-shard block occupancy for the metrics registry: one dict
        per shard with ``free``/``live``/``cached``/``reserved`` block
        counts plus ``block_bytes``, the per-block device cost (bytes
        across all layers, K + V + scales) — counts x ``block_bytes``
        is the pool's byte footprint.  ``cached`` is the refcounted
        prefix allocator's cached-LRU population (0 for the plain
        allocator)."""
        a = self.allocator
        return [{
            "free": a.free_count,
            "live": (getattr(a, "allocated_count", 0)
                     + getattr(a, "live_count", 0)),
            "cached": getattr(a, "cached_count", 0),
            "reserved": self.reserved_total,
            "block_bytes": self.block_bytes,
        }]


class ShardedPagedKVCache:
    """D per-shard caches behind the single-cache interface.

    The mesh's data axis partitions slots *contiguously* — slot ``s``
    lives on shard ``s // slots_per_shard`` — and each shard runs its own
    allocator (:class:`BlockAllocator`, or the refcounted prefix-caching
    one when ``serve.prefix_cache``) over a private pool slice with its
    own garbage block.  Block ids in tables, write coords and the step's
    row buffers are therefore **shard-local**: exactly what each
    shard_map body needs to index its ``(shard_blocks + 1, ...)`` pool
    slice, and structurally what keeps any unsharded ``(num_blocks, ...)``
    pool out of the mapped computation.

    Admission invariants hold at both levels.  Per shard, each sub-cache
    enforces its own reservation bound (``reserved <= shard_blocks``),
    so a shard's running slots can never starve on their own free list
    no matter what other shards do.  In aggregate, this class's
    :meth:`check_conservation` re-asserts the summed invariants.  The
    :class:`~repro.serving.scheduler.Scheduler` keeps the *global*
    admission view: it probes :meth:`can_allocate_slot_on` per free slot,
    so a request is admitted iff some shard with a free slot has room.

    The device pools live *here*, stacked over shards:
    ``(num_layers, D * (shard_blocks + 1), Hkv, bs, hd)``, shard ``d``
    owning rows ``[d * (shard_blocks+1), (d+1) * (shard_blocks+1))`` with
    its garbage block last in its slice.  Sub-caches run detached
    (host accounting only).

    Not supported with data sharding: KV swap-to-host preemption (the
    swap pool is single-device) — the engine rejects ``serve.slo`` with
    preemption before construction, and the hooks here raise.
    """

    def __init__(self, cfg: ModelConfig, serve: ServeConfig):
        import dataclasses

        d = serve.data_shards
        self.cfg = cfg
        self.serve = serve
        self.num_shards = d
        self.block_size = serve.kv_block_size
        self.num_blocks = serve.resolved_num_blocks
        self.slots_per_shard = serve.max_slots // d
        self.shard_blocks = self.num_blocks // d
        # shard-local garbage index: last row of each shard's pool slice
        self.garbage_block = self.shard_blocks
        sub_serve = dataclasses.replace(
            serve, mesh=None, max_slots=self.slots_per_shard,
            num_blocks=self.shard_blocks)
        quantized = getattr(serve, "kv_quant", "none") != "none"
        if serve.prefix_cache:
            if quantized:
                from repro.quant.kv_cache import QuantizedPrefixCachingKVCache
                sub_cls = QuantizedPrefixCachingKVCache
            else:
                from repro.serving.prefix_cache import PrefixCachingKVCache
                sub_cls = PrefixCachingKVCache
        elif quantized:
            from repro.quant.kv_cache import QuantizedPagedKVCache
            sub_cls = QuantizedPagedKVCache
        else:
            sub_cls = PagedKVCache
        self.shards = [sub_cls(cfg, sub_serve) for _ in range(d)]
        for s in self.shards:
            s.detach_pools()
        hd = cfg.resolved_head_dim
        rows = d * (self.shard_blocks + 1)
        pool_shape = (cfg.num_layers, rows, cfg.num_kv_heads,
                      self.block_size, hd)
        if quantized:
            self.k_pool = jnp.zeros(pool_shape, jnp.int8)
            self.v_pool = jnp.zeros(pool_shape, jnp.int8)
            self.k_scales = jnp.zeros(
                (cfg.num_layers, rows, cfg.num_kv_heads), jnp.float32)
            self.v_scales = jnp.zeros_like(self.k_scales)
        else:
            dtype = cfg.activation_dtype
            self.k_pool = jnp.zeros(pool_shape, dtype)
            self.v_pool = jnp.zeros(pool_shape, dtype)
            self.k_scales = self.v_scales = None

    def _loc(self, slot: int) -> Tuple[int, int]:
        """(shard, shard-local slot) for a global slot id."""
        return divmod(slot, self.slots_per_shard)

    def shard_of(self, slot: int) -> int:
        return slot // self.slots_per_shard

    # -- admission / accounting (scheduler-facing) ---------------------------

    @property
    def max_request_blocks(self) -> int:
        return self.shard_blocks

    @property
    def reserved_total(self) -> int:
        return sum(s.reserved_total for s in self.shards)

    def blocks_needed(self, total_len: int) -> int:
        return -(-total_len // self.block_size)

    def can_allocate_slot(self, total_len: int, prompt=None) -> bool:
        """True when *some* shard has room (slot-blind compatibility
        view; the scheduler uses :meth:`can_allocate_slot_on`)."""
        return any(s.can_allocate_slot(total_len, prompt=prompt)
                   for s in self.shards)

    def can_allocate_slot_on(self, slot: int, total_len: int, prompt=None) -> bool:
        d, _ = self._loc(slot)
        return self.shards[d].can_allocate_slot(total_len, prompt=prompt)

    def allocate_slot(self, slot: int, total_len: int, prompt=None) -> int:
        d, ls = self._loc(slot)
        return self.shards[d].allocate_slot(ls, total_len, prompt=prompt)

    def commit(self, slot: int, tokens) -> None:
        d, ls = self._loc(slot)
        self.shards[d].commit(ls, tokens)

    def committed_blocks(self, slot: int) -> int:
        d, ls = self._loc(slot)
        return self.shards[d].committed_blocks(ls)

    def free_slot(self, slot: int) -> None:
        d, ls = self._loc(slot)
        self.shards[d].free_slot(ls)

    def ensure_capacity(self, slot: int, length: int) -> None:
        d, ls = self._loc(slot)
        self.shards[d].ensure_capacity(ls, length)

    def truncate_slot(self, slot: int, new_len: int) -> None:
        d, ls = self._loc(slot)
        self.shards[d].truncate_slot(ls, new_len)

    def write_coords(self, slot: int, position: int) -> Tuple[int, int]:
        """Shard-local (block, offset): the step's scatter and attention
        run under shard_map, where each body sees only its pool slice."""
        d, ls = self._loc(slot)
        return self.shards[d].write_coords(ls, position)

    def row_table(self, slot: int) -> np.ndarray:
        d, ls = self._loc(slot)
        return self.shards[d].row_table(ls)

    def held_blocks(self, slot: int) -> int:
        d, ls = self._loc(slot)
        return self.shards[d].held_blocks(ls)

    def warm_prefix_tokens(self, prompt) -> int:
        return max(s.warm_prefix_tokens(prompt) for s in self.shards)

    @property
    def stats(self):
        """Summed prefix-cache counters across shards."""
        totals: Dict[str, int] = {}
        for s in self.shards:
            for k, v in s.stats.items():
                totals[k] = totals.get(k, 0) + v
        return totals

    def occupancy(self) -> list:
        """Per-shard occupancy — one entry per private allocator."""
        return [d for s in self.shards for d in s.occupancy()]

    # -- preemption swap hooks: unsupported under data sharding --------------

    def swap_footprint(self, slot: int) -> int:
        raise NotImplementedError(
            "KV swap-to-host preemption is not supported on a sharded cache")

    def swap_out(self, slot, swap, *, uid, total_len, context_len):
        raise NotImplementedError(
            "KV swap-to-host preemption is not supported on a sharded cache")

    def can_restore(self, rec) -> bool:
        raise NotImplementedError(
            "KV swap-to-host preemption is not supported on a sharded cache")

    def restore_slot(self, slot, rec, swap) -> int:
        raise NotImplementedError(
            "KV swap-to-host preemption is not supported on a sharded cache")

    def check_conservation(self) -> None:
        """Every shard's full invariant suite, then the aggregate view:
        summed reservations within the global pool and summed
        free/allocated conservation across per-shard allocators."""
        for s in self.shards:
            s.check_conservation()
        assert self.reserved_total <= self.num_blocks
        free = live = cached = 0
        for s in self.shards:
            a = s.allocator
            free += a.free_count
            # plain allocator: allocated; refcounted: live + cached-LRU
            live += getattr(a, "allocated_count", 0) + getattr(a, "live_count", 0)
            cached += getattr(a, "cached_count", 0)
        assert free + live + cached == self.num_blocks, (
            free, live, cached, self.num_blocks)
        if self.k_scales is not None:
            # scale-pool / code-pool bijection over the stacked rows:
            # shard_map splits both along the same row axis, so every
            # shard-local block id indexes its codes and its scale
            assert self.k_scales.shape == self.k_pool.shape[:2] + (
                self.k_pool.shape[2],), (self.k_scales.shape,
                                         self.k_pool.shape)
            assert self.v_scales.shape == self.k_scales.shape

    def update_pools(self, k_pool: jax.Array, v_pool: jax.Array,
                     k_scales=None, v_scales=None) -> None:
        self.k_pool = k_pool
        self.v_pool = v_pool
        if k_scales is not None:
            self.k_scales = k_scales
            self.v_scales = v_scales


def make_kv_cache(cfg: ModelConfig, serve: ServeConfig):
    """Select and build the cache variant ``serve`` asks for: the
    sharded composition when ``serve.mesh`` is set, prefix caching when
    ``serve.prefix_cache``, and the quantized pools when
    ``serve.kv_quant != "none"`` — all eight combinations compose.
    Lazy imports keep the plain paged cache importable on its own."""
    if serve.mesh is not None:
        return ShardedPagedKVCache(cfg, serve)
    quantized = getattr(serve, "kv_quant", "none") != "none"
    if serve.prefix_cache:
        if quantized:
            from repro.quant.kv_cache import QuantizedPrefixCachingKVCache
            return QuantizedPrefixCachingKVCache(cfg, serve)
        from repro.serving.prefix_cache import PrefixCachingKVCache
        return PrefixCachingKVCache(cfg, serve)
    if quantized:
        from repro.quant.kv_cache import QuantizedPagedKVCache
        return QuantizedPagedKVCache(cfg, serve)
    return PagedKVCache(cfg, serve)
