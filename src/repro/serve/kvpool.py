"""Paged KV-cache pool: fixed-size block allocator + device page ops.

The pool replaces the per-row contiguous ring buffer with a shared set
of fixed-size *blocks* (pages) of KV entries, vLLM-style:

  * ``KVPool``     — host-side allocator (policy layer, numpy only, no
                     jax): free list, per-client block tables,
                     alloc / append / free.  A *client* is one backbone
                     row of the serve grid — with mux N == 1 that is
                     exactly one request stream; with N > 1 it is a mux
                     group whose N streams share the row's muxed KV (see
                     DESIGN.md for why muxed KV cannot be split finer).
  * ``ShardedKVPool`` — the mesh-serving allocator (DESIGN.md §sharded
                     serving): the global block-id space is split into
                     ``n_shards`` contiguous segments, one per data
                     shard, each with its own free list and its own
                     local trash block.  Rows map to shards contiguously
                     (row j -> shard j // (n_rows // n_shards), matching
                     how ``NamedSharding`` partitions the block-table
                     rows over the 'data' axis), so a row's block table
                     only ever references pages of the device shard that
                     owns the row — the invariant behind collective-free
                     sharded decode.
  * device helpers — a pytree of ``(num_blocks, block_size, Hkv, Dh)``
                     pages per attention layer plus a per-slot absolute
                     position array, with functional scatter-write and
                     gather-view ops used by ``models.blocks`` and the
                     pure-JAX reference attention path.

Block id 0 is reserved as the *trash block*: writes for invalid
positions (padding, inactive rows) are routed there and its position
entries stay -1, so they are always masked out of attention.  Under
``ShardedKVPool`` every shard reserves its own trash (local block 0,
global id ``shard * blocks_per_shard``) so invalid writes never cross
shards; ``paged_write`` takes a per-row ``trash`` vector for this.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.core import quant as quantlib


class PoolError(RuntimeError):
    """Misuse of the pool API (double alloc / double free / unknown client)."""


class PoolExhausted(PoolError):
    """No free blocks left (or a client hit its per-sequence block cap)."""


TRASH_BLOCK = 0


def blocks_for(num_tokens: int, block_size: int) -> int:
    """Number of blocks needed to hold ``num_tokens`` entries."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    return -(-max(num_tokens, 0) // block_size)


def live_blocks(positions, block_size: int, window=None) -> np.ndarray:
    """Table blocks that a decode query at each of ``positions`` reads:
    block j holds positions [j*block_size, (j+1)*block_size)
    (``paged_write``), so the blocks from the one holding the window's
    first position to the query's own; 0 for an inactive row (position
    < 0).  The paged decode kernel walks exactly these."""
    pos = np.asarray(positions)
    lo = 0 if window is None else np.maximum(pos - window + 1, 0) // block_size
    return np.where(pos >= 0, pos // block_size - lo + 1, 0)


@dataclass
class KVPool:
    """Host-side block allocator with per-client block tables.

    num_blocks includes the reserved trash block 0; allocatable capacity
    is ``num_blocks - 1`` blocks.

    quota: optional soft cap on *live* blocks, below the hard device
    capacity.  The device pages stay sized at ``num_blocks`` (shapes
    never change, so jitted programs never re-trace); the quota only
    gates the host-side allocator.  Width-lane serving partitions one
    global block budget across per-lane pools this way — each lane keeps
    its own free list, and ``serve.router.LaneRouter`` moves *unused*
    quota between lanes as load shifts (DESIGN.md §width lanes).
    Shrinking a quota below the current usage is legal: nothing is
    reclaimed, but new allocations are refused until rows drain.
    """
    num_blocks: int
    block_size: int
    max_blocks_per_seq: int
    quota: int | None = None
    _free: list = field(init=False, repr=False)
    _tables: dict = field(default_factory=dict, init=False, repr=False)
    _lens: dict = field(default_factory=dict, init=False, repr=False)

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        if self.block_size < 1 or self.max_blocks_per_seq < 1:
            raise ValueError("block_size / max_blocks_per_seq must be >= 1")
        if self.quota is not None and self.quota < 0:
            raise ValueError(f"quota must be >= 0, got {self.quota}")
        # LIFO free list over ids 1..num_blocks-1 (0 = trash)
        self._free = list(range(self.num_blocks - 1, 0, -1))

    # -- introspection -----------------------------------------------------
    @property
    def n_free_blocks(self) -> int:
        return len(self._free)

    @property
    def n_used_blocks(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    @property
    def headroom(self) -> int:
        """Blocks still allocatable: free list, capped by the quota."""
        if self.quota is None:
            return len(self._free)
        return max(0, min(len(self._free), self.quota - self.n_used_blocks))

    @property
    def ceiling(self) -> int:
        """Device-side allocatable blocks (total minus the trash block)."""
        return self.num_blocks - 1

    def set_quota(self, quota: int | None):
        """Install a new soft cap (None = uncapped).  Takes effect on the
        next allocation; live blocks above a shrunken quota stay live."""
        if quota is not None and quota < 0:
            raise ValueError(f"quota must be >= 0, got {quota}")
        self.quota = quota

    def has(self, cid) -> bool:
        return cid in self._tables

    def num_tokens(self, cid) -> int:
        return self._lens[cid]

    def used_tokens(self) -> int:
        return sum(self._lens.values())

    def utilization(self) -> float:
        """Fraction of allocatable pool slots holding live tokens."""
        return self.used_tokens() / ((self.num_blocks - 1) * self.block_size)

    def occupancy_stats(self) -> list:
        """Per-shard occupancy snapshot — one entry for this unsharded
        pool, matching ``ShardedKVPool.occupancy_stats``: live/free/
        allocatable blocks, the quota soft cap, and the occupied
        fraction of allocatable blocks.  Telemetry publishes these as
        the ``pool_*`` gauges each engine step (DESIGN.md
        §observability)."""
        return [{"used": self.n_used_blocks, "free": self.n_free_blocks,
                 "headroom": self.headroom, "quota": self.quota,
                 "occupancy": self.n_used_blocks / (self.num_blocks - 1)}]

    # -- alloc / append / free --------------------------------------------
    def _take(self, n: int):
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free")
        if self.quota is not None and self.n_used_blocks + n > self.quota:
            raise PoolExhausted(
                f"need {n} blocks, quota {self.quota} with "
                f"{self.n_used_blocks} in use")
        return [self._free.pop() for _ in range(n)]

    def allocate(self, cid, num_tokens: int = 0):
        """Register client ``cid`` and reserve blocks for ``num_tokens``.
        Returns the allocated block ids; blocks are reused WITHOUT
        device-side clearing, so callers must reset their position
        entries (``engine.reset_blocks``) before the first write."""
        if cid in self._tables:
            raise PoolError(f"client {cid!r} already allocated")
        n = blocks_for(num_tokens, self.block_size)
        if n > self.max_blocks_per_seq:
            raise PoolExhausted(
                f"{num_tokens} tokens exceed per-seq cap "
                f"{self.max_blocks_per_seq * self.block_size}")
        blocks = self._take(n)
        self._tables[cid] = blocks
        self._lens[cid] = num_tokens
        return list(blocks)

    def append(self, cid, n: int = 1) -> list:
        """Grow client ``cid`` by ``n`` tokens, allocating blocks as
        boundaries are crossed.  Returns the newly allocated block ids
        ([] if the table did not grow) — callers must reset those
        blocks' device-side position entries (``engine.reset_blocks``)
        before writing, since freed blocks are reused without clearing."""
        if cid not in self._tables:
            raise PoolError(f"client {cid!r} not allocated")
        new_len = self._lens[cid] + n
        need = blocks_for(new_len, self.block_size)
        if need > self.max_blocks_per_seq:
            raise PoolExhausted(
                f"client {cid!r}: {new_len} tokens exceed per-seq cap "
                f"{self.max_blocks_per_seq * self.block_size}")
        fresh = []
        if need > len(self._tables[cid]):
            fresh = self._take(need - len(self._tables[cid]))
            self._tables[cid].extend(fresh)
        self._lens[cid] = new_len
        return fresh

    def free(self, cid):
        """Return all of ``cid``'s blocks to the free list."""
        if cid not in self._tables:
            raise PoolError(f"client {cid!r} not allocated (double free?)")
        self._free.extend(reversed(self._tables.pop(cid)))
        del self._lens[cid]

    # -- migration (disaggregated serving; DESIGN.md §disaggregated) -------
    def migrate_rows(self, cid, dst, dst_cid=None):
        """Move client ``cid`` out of this pool into ``dst`` (registered
        there as ``dst_cid``, default the same id): allocate the same
        block count in the destination, release the source blocks, and
        return ``(src_blocks, dst_blocks)`` — equal-length id lists the
        caller must hand to the device page copy (``engine.
        copy_cache_pages``) so the KV payload (and any quant scales)
        follows the accounting.  Ids are in each pool's own id space
        (global when a ``ShardedKVPool`` is involved on that side).

        Atomic: destination allocation goes through the normal allocator
        (quota + per-seq cap + dead-shard checks apply), and on
        ``PoolExhausted`` nothing has changed on either side — the
        stream just keeps serving from the source partition."""
        if cid not in self._tables:
            raise PoolError(f"client {cid!r} not allocated")
        if dst_cid is None:
            dst_cid = cid
        if dst is self and dst_cid == cid:
            raise PoolError(f"client {cid!r}: migration onto itself")
        dst_blocks = dst.allocate(dst_cid, self._lens[cid])
        src_blocks = list(self._tables[cid])
        assert len(dst_blocks) == len(src_blocks), \
            "source table not minimal — allocator invariant broken"
        self.free(cid)
        return src_blocks, dst_blocks

    # -- block-table views -------------------------------------------------
    def block_table(self, cid) -> np.ndarray:
        """(max_blocks_per_seq,) int32, -1-padded."""
        if cid not in self._tables:
            raise PoolError(f"client {cid!r} not allocated")
        bt = np.full((self.max_blocks_per_seq,), -1, np.int32)
        blocks = self._tables[cid]
        bt[:len(blocks)] = blocks
        return bt

    def table_array(self, clients) -> np.ndarray:
        """Stack block tables for an ordered sequence of clients; entries
        that are None or unallocated give all -1 rows.  Returns
        (len(clients), max_blocks_per_seq) int32."""
        out = np.full((len(clients), self.max_blocks_per_seq), -1, np.int32)
        for i, cid in enumerate(clients):
            if cid is not None and cid in self._tables:
                out[i] = self.block_table(cid)
        return out

    def check_invariants(self):
        """Debug/test hook: no block owned twice, free list disjoint."""
        owned = [b for blks in self._tables.values() for b in blks]
        assert len(owned) == len(set(owned)), "block owned by two clients"
        assert not (set(owned) & set(self._free)), "owned block on free list"
        assert TRASH_BLOCK not in owned and TRASH_BLOCK not in self._free
        assert len(owned) + len(self._free) == self.num_blocks - 1
        for cid, blks in self._tables.items():
            assert len(blks) >= blocks_for(self._lens[cid], self.block_size)
            assert len(blks) <= self.max_blocks_per_seq

    # -- checkpoint state (serve.recovery; DESIGN.md §fault tolerance) -----
    def dump_state(self) -> dict:
        """JSON-able allocator snapshot: free list, tables, lengths,
        quota.  Block ids are LOCAL to this pool; ``ShardedKVPool``
        nests one entry per shard.  Clients (backbone rows) are ints."""
        return {"free": [int(b) for b in self._free],
                "tables": {str(c): [int(b) for b in blks]
                           for c, blks in self._tables.items()},
                "lens": {str(c): int(n) for c, n in self._lens.items()},
                "quota": self.quota}

    def load_state(self, state: dict):
        """Restore a ``dump_state`` snapshot into this (freshly built,
        identically sized) pool."""
        self._free = [int(b) for b in state["free"]]
        self._tables = {int(c): [int(b) for b in blks]
                        for c, blks in state["tables"].items()}
        self._lens = {int(c): int(n) for c, n in state["lens"].items()}
        self.quota = state["quota"]
        self.check_invariants()


@dataclass
class ShardedKVPool:
    """Per-shard block allocator for mesh-sharded serving.

    The global id space [0, num_blocks) splits into ``n_shards``
    contiguous segments of ``num_blocks // n_shards`` blocks; segment s
    is owned by data shard s, whose local block 0 (global id
    ``s * blocks_per_shard``) is that shard's trash block.  Clients are
    backbone rows in [0, n_rows): row j lives on shard
    ``j // (n_rows // n_shards)`` and only ever receives blocks from its
    own segment, so block tables stay shard-local (the device pages are
    sharded over the blocks axis on the mesh 'data' axis with exactly
    this segmentation).  API mirrors ``KVPool``; block ids returned and
    accepted are GLOBAL.
    """
    num_blocks: int
    block_size: int
    max_blocks_per_seq: int
    n_shards: int
    n_rows: int
    _shards: list = field(init=False, repr=False)
    # shards fenced by kill_shard: quota 0, allocations refused, their
    # segment's pages dark until a (process-level) repair re-adds them
    dead_shards: set = field(default_factory=set, init=False, repr=False)

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.num_blocks % self.n_shards:
            raise ValueError(
                f"num_blocks={self.num_blocks} not divisible by "
                f"n_shards={self.n_shards}")
        if self.n_rows % self.n_shards:
            raise ValueError(
                f"n_rows={self.n_rows} not divisible by "
                f"n_shards={self.n_shards}")
        self._shards = [KVPool(num_blocks=self.blocks_per_shard,
                               block_size=self.block_size,
                               max_blocks_per_seq=self.max_blocks_per_seq)
                        for _ in range(self.n_shards)]

    # -- shard topology ----------------------------------------------------
    @property
    def blocks_per_shard(self) -> int:
        return self.num_blocks // self.n_shards

    @property
    def rows_per_shard(self) -> int:
        return self.n_rows // self.n_shards

    @property
    def alive_shards(self) -> list:
        return [s for s in range(self.n_shards) if s not in self.dead_shards]

    def shard_of(self, cid) -> int:
        j = int(cid)
        if not 0 <= j < self.n_rows:
            raise PoolError(f"row {cid!r} outside [0, {self.n_rows})")
        return j // self.rows_per_shard

    def _offset(self, s: int) -> int:
        return s * self.blocks_per_shard

    def trash_for(self, cid) -> int:
        """Global id of the trash block of ``cid``'s shard."""
        return self._offset(self.shard_of(cid))

    def trash_vector(self, clients) -> np.ndarray:
        """(len(clients),) int32 per-row trash block ids (``paged_write``'s
        ``trash`` argument)."""
        return np.asarray([self.trash_for(c) for c in clients], np.int32)

    # -- introspection (aggregate + per-shard) ----------------------------
    @property
    def n_free_blocks(self) -> int:
        return sum(p.n_free_blocks for p in self._shards)

    @property
    def n_used_blocks(self) -> int:
        return sum(p.n_used_blocks for p in self._shards)

    @property
    def headroom(self) -> int:
        """Allocatable blocks summed over shards (quota-capped per shard)."""
        return sum(p.headroom for p in self._shards)

    @property
    def quota(self) -> int | None:
        """Aggregate soft cap (sum of per-shard quotas over ALIVE shards;
        None = uncapped).  Dead shards are pinned at quota 0 and do not
        count toward — or un-None — the aggregate."""
        qs = [self._shards[s].quota for s in self.alive_shards]
        return None if any(q is None for q in qs) else sum(qs)

    @property
    def ceiling(self) -> int:
        """Device-side allocatable blocks over ALIVE shards (each shard's
        segment minus its trash block).  A killed shard's pages go dark:
        they stop counting toward capacity until the shard is repaired."""
        return sum(self._shards[s].ceiling for s in self.alive_shards)

    def set_quota(self, quota: int | None):
        """Split an aggregate soft cap across ALIVE shards, flooring each
        shard's share at its CURRENT usage: shrinking a lane's quota
        (e.g. a rebalance donation) must never drop a hot shard below
        its live blocks — only genuinely unused headroom moves.  The
        spare above the floors splits evenly (remainder to the low
        shards).  When the quota cannot even cover total usage (never
        the rebalance path, which donates free quota only) the deficit
        falls back to an even split.  Per-shard quotas keep lane
        rebalancing honest under a mesh: a lane cannot borrow headroom
        a single shard does not actually have.  Dead shards always get
        quota 0 (their segment is unreachable)."""
        alive = self.alive_shards
        for s in self.dead_shards:
            self._shards[s].set_quota(0)
        if quota is None:
            for s in alive:
                self._shards[s].set_quota(None)
            return
        used = [self._shards[s].n_used_blocks for s in alive]
        if quota >= sum(used):
            base, rem = divmod(quota - sum(used), len(alive))
            for k, s in enumerate(alive):
                self._shards[s].set_quota(used[k] + base
                                          + (1 if k < rem else 0))
        else:
            base, rem = divmod(quota, len(alive))
            for k, s in enumerate(alive):
                self._shards[s].set_quota(base + (1 if k < rem else 0))

    def kill_shard(self, s: int) -> int:
        """Fence shard ``s`` after device loss (DESIGN.md §fault
        tolerance): its segment stops serving allocations and its quota
        is reclaimed by the surviving shards (split evenly, remainder to
        the low shards).  The caller must have freed/preempted the
        shard's rows first — the dead shard's KV pages are GONE, so a
        table still referencing them would be a correctness hole, not a
        leak.  Returns the quota handed to the survivors (0 when
        uncapped)."""
        if not 0 <= s < self.n_shards:
            raise PoolError(f"shard {s} outside [0, {self.n_shards})")
        if s in self.dead_shards:
            raise PoolError(f"shard {s} already dead")
        if len(self.alive_shards) <= 1:
            raise PoolError("cannot kill the last surviving shard")
        p = self._shards[s]
        if p._tables:
            raise PoolError(
                f"shard {s} still owns rows {sorted(p._tables)} — "
                "preempt/free them before kill_shard")
        reclaimed = p.quota or 0
        p.set_quota(0)
        self.dead_shards.add(s)
        survivors = self.alive_shards
        if reclaimed:
            base, rem = divmod(reclaimed, len(survivors))
            for k, t in enumerate(survivors):
                q = self._shards[t].quota
                if q is not None:
                    self._shards[t].set_quota(q + base
                                              + (1 if k < rem else 0))
        return reclaimed

    def shard_used_blocks(self, cid) -> int:
        """Used blocks on ``cid``'s OWN shard (backpressure decisions are
        shard-local: a row can only ever wait on its own shard's drains)."""
        return self._shards[self.shard_of(cid)].n_used_blocks

    def has(self, cid) -> bool:
        return self._shards[self.shard_of(cid)].has(cid)

    def num_tokens(self, cid) -> int:
        return self._shards[self.shard_of(cid)].num_tokens(cid)

    def used_tokens(self) -> int:
        return sum(p.used_tokens() for p in self._shards)

    def utilization(self) -> float:
        return self.used_tokens() / (
            (self.num_blocks - self.n_shards) * self.block_size)

    def occupancy_stats(self) -> list:
        """Occupancy snapshot per data shard (see
        ``KVPool.occupancy_stats``) — index s describes shard s's own
        segment, so the pool gauges stay shard-keyed under a mesh."""
        return [st for p in self._shards for st in p.occupancy_stats()]

    # -- alloc / append / free (global ids) -------------------------------
    def allocate(self, cid, num_tokens: int = 0):
        s = self.shard_of(cid)
        if s in self.dead_shards:
            raise PoolError(f"shard {s} is dead (row {cid!r} cannot be "
                            "placed there until the shard is repaired)")
        try:
            local = self._shards[s].allocate(cid, num_tokens)
        except PoolExhausted as e:
            raise PoolExhausted(f"shard {s}: {e}") from e
        return [b + self._offset(s) for b in local]

    def append(self, cid, n: int = 1) -> list:
        s = self.shard_of(cid)
        try:
            local = self._shards[s].append(cid, n)
        except PoolExhausted as e:
            raise PoolExhausted(f"shard {s}: {e}") from e
        return [b + self._offset(s) for b in local]

    def free(self, cid):
        self._shards[self.shard_of(cid)].free(cid)

    # -- migration (disaggregated serving; DESIGN.md §disaggregated) -------
    def migrate_pages(self, cid, dst_cid=None, dst=None):
        """Global-id variant of ``KVPool.migrate_rows``: move row ``cid``'s
        pages into ``dst`` (another pool, or this one for a cross-shard
        move when ``dst`` is None/self) under id ``dst_cid``.  Returns
        ``(src_blocks, dst_blocks)`` with ids global in each pool's own
        space; destination placement goes through the normal allocator,
        so shard-locality, trash-reservation, quota, and dead-shard
        fencing all hold for the new blocks by construction.  Atomic on
        ``PoolExhausted`` — nothing moves."""
        if dst is None:
            dst = self
        if dst_cid is None:
            dst_cid = cid
        s = self.shard_of(cid)
        if not self._shards[s].has(cid):
            raise PoolError(f"row {cid!r} not allocated")
        if dst is self and dst_cid == cid:
            raise PoolError(f"row {cid!r}: migration onto itself")
        n_tok = self._shards[s].num_tokens(cid)
        dst_blocks = dst.allocate(dst_cid, n_tok)
        src_blocks = [b + self._offset(s)
                      for b in self._shards[s]._tables[cid]]
        assert len(dst_blocks) == len(src_blocks), \
            "source table not minimal — allocator invariant broken"
        self.free(cid)
        return src_blocks, dst_blocks

    # -- block-table views -------------------------------------------------
    def block_table(self, cid) -> np.ndarray:
        s = self.shard_of(cid)
        bt = self._shards[s].block_table(cid)
        return np.where(bt >= 0, bt + self._offset(s), bt).astype(np.int32)

    def table_array(self, clients) -> np.ndarray:
        out = np.full((len(clients), self.max_blocks_per_seq), -1, np.int32)
        for i, cid in enumerate(clients):
            if cid is not None and self.has(cid):
                out[i] = self.block_table(cid)
        return out

    def check_invariants(self):
        for s, p in enumerate(self._shards):
            p.check_invariants()
            # a dead shard's segment must be fully dark: no tables, no
            # allocatable headroom
            if s in self.dead_shards:
                assert not p._tables, "dead shard still owns rows"
                assert p.quota == 0, "dead shard has non-zero quota"
            # a shard's tables reference only its own segment, and never
            # any shard's trash block
            off = self._offset(s)
            for cid, blks in p._tables.items():
                assert self.shard_of(cid) == s, "row on the wrong shard"
                for b in blks:
                    g = b + off
                    assert off < g < off + self.blocks_per_shard, \
                        "block table crosses shard boundary"
                    assert g % self.blocks_per_shard != 0, \
                        "trash block referenced by a live table"

    # -- checkpoint state (serve.recovery; DESIGN.md §fault tolerance) -----
    def dump_state(self) -> dict:
        """JSON-able snapshot: per-shard allocator states (local block
        ids) plus the dead-shard set."""
        return {"shards": [p.dump_state() for p in self._shards],
                "dead_shards": sorted(self.dead_shards)}

    def load_state(self, state: dict):
        """Restore a ``dump_state`` snapshot into this (freshly built,
        identically shaped) pool."""
        if len(state["shards"]) != self.n_shards:
            raise PoolError(
                f"snapshot has {len(state['shards'])} shards, pool has "
                f"{self.n_shards}")
        for p, st in zip(self._shards, state["shards"]):
            p.load_state(st)
        self.dead_shards = set(int(s) for s in state["dead_shards"])
        self.check_invariants()


# ===========================================================================
# device-side page ops (functional, jit-safe)
# ===========================================================================

def init_pages(num_blocks: int, block_size: int, n_kv_heads: int,
               head_dim: int, dtype, quant: str | None = None):
    """Pages for ONE attention layer + the shared per-slot position map.

    quant: 'int8' / 'fp8' stores the pages in that dtype with per-(slot,
    kv-head) fp32 scales alongside (``ksc``/``vsc``, shape (P, BS, Hkv)).
    The presence of the ``ksc`` key is what marks a cache as quantized
    downstream (paged_write quantizes at write, the Pallas kernels fuse
    the dequant into their page loads).
    """
    if quant is None:
        return {
            "kp": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                            dtype),
            "vp": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                            dtype),
            "ppos": jnp.full((num_blocks, block_size), -1, jnp.int32),
        }
    store = quantlib.kv_store_dtype(quant)
    return {
        "kp": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim), store),
        "vp": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim), store),
        "ksc": jnp.zeros((num_blocks, block_size, n_kv_heads), jnp.float32),
        "vsc": jnp.zeros((num_blocks, block_size, n_kv_heads), jnp.float32),
        "ppos": jnp.full((num_blocks, block_size), -1, jnp.int32),
    }


def paged_write(cache, k, v, positions, block_tables=None, trash=None):
    """Scatter L new KV entries per row into their pages.

    cache: dict with kp/vp (P, BS, Hkv, Dh), ppos (P, BS) and (unless
    ``block_tables`` overrides it) bt (B, MB).  k, v: (B, L, Hkv, Dh).
    positions: (B, L) int32 absolute token positions; entries < 0 (pad
    tokens, inactive rows) are routed to the trash block and stay masked.
    trash: trash block id — scalar or a (B,) per-row vector (sharded
    pools route each row's invalid writes to its OWN shard's trash so
    they never cross shards); default block 0.
    Rows own disjoint blocks (allocator invariant), so scatters never
    collide across rows.
    """
    bt = cache["bt"] if block_tables is None else block_tables
    bs = cache["kp"].shape[1]
    blk = positions // bs                                    # (B, L)
    in_range = (positions >= 0) & (blk < bt.shape[1])
    page = jnp.take_along_axis(bt, jnp.clip(blk, 0, bt.shape[1] - 1),
                               axis=1)                       # (B, L)
    valid = in_range & (page >= 0)
    t = jnp.asarray(TRASH_BLOCK if trash is None else trash, page.dtype)
    if t.ndim:
        t = t[:, None]                                       # (B, 1)
    page = jnp.where(valid, page, t)
    slot = jnp.where(valid, positions % bs, 0)
    stored = jnp.where(valid, positions, -1)
    if "ksc" in cache:
        # Quantize-at-write: the pool only ever holds low-precision
        # payloads + per-(slot, head) scales.  Per-vector scaling keeps
        # writes append-only — no neighbour slot is requantized.
        kind = quantlib.kv_quant_kind(cache["kp"].dtype)
        kq, ks = quantlib.quantize_kv(k, kind)               # (B,L,H,D)/(B,L,H)
        vq, vs = quantlib.quantize_kv(v, kind)
        return {**cache,
                "kp": cache["kp"].at[page, slot].set(kq),
                "vp": cache["vp"].at[page, slot].set(vq),
                "ksc": cache["ksc"].at[page, slot].set(ks),
                "vsc": cache["vsc"].at[page, slot].set(vs),
                "ppos": cache["ppos"].at[page, slot].set(stored)}
    return {**cache,
            "kp": cache["kp"].at[page, slot].set(
                k.astype(cache["kp"].dtype)),
            "vp": cache["vp"].at[page, slot].set(
                v.astype(cache["vp"].dtype)),
            "ppos": cache["ppos"].at[page, slot].set(stored)}


def copy_pages(src, dst, src_ids, dst_ids):
    """Copy whole pages between two layer caches: pages ``src_ids`` of
    ``src`` land in slots ``dst_ids`` of ``dst``.  Moves the payload
    (``kp``/``vp``), the quant scales when present (``ksc``/``vsc`` —
    scales must follow their pages bit-exactly or dequant corrupts the
    migrated KV), and the per-slot position map (``ppos``, which carries
    the -1 mask for unwritten slots, so a partially filled tail page
    stays masked after migration).

    ``src`` and ``dst`` may be the same dict (cross-shard moves inside
    one pool).  Functional and eager: a host-orchestrated cache edit
    like ``engine.reset_blocks`` — never a jit input, so the
    compile-once contract is untouched.  Page dtypes must already match
    (migration never re-quantizes).
    """
    if len(src_ids) != len(dst_ids):
        raise ValueError(
            f"page copy needs equal id lists, got {len(src_ids)} -> "
            f"{len(dst_ids)}")
    if len(src_ids) == 0:
        return dst
    if src["kp"].dtype != dst["kp"].dtype or ("ksc" in src) != ("ksc" in dst):
        raise ValueError("source/destination page dtypes differ — "
                         "cannot migrate pages across kv_dtype")
    si = jnp.asarray(list(src_ids), jnp.int32)
    di = jnp.asarray(list(dst_ids), jnp.int32)
    out = dict(dst)
    for key in ("kp", "vp", "ksc", "vsc", "ppos"):
        if key in dst:
            out[key] = dst[key].at[di].set(src[key][si])
    return out


def paged_view(cache):
    """Gather each row's pages into a contiguous (B, MB*BS, Hkv, Dh) view
    plus per-row slot positions (B, MB*BS) with -1 for empty/unallocated.
    Used by the pure-JAX attention path and tests; the Pallas kernel
    reads pages in place via the block table instead."""
    bt = cache["bt"]
    b, mb = bt.shape
    btc = jnp.maximum(bt, 0)
    if "ksc" in cache:
        k = quantlib.dequantize_kv(cache["kp"][btc], cache["ksc"][btc])
        v = quantlib.dequantize_kv(cache["vp"][btc], cache["vsc"][btc])
    else:
        k = cache["kp"][btc]                                 # (B, MB, BS, H, D)
        v = cache["vp"][btc]
    pos = jnp.where(bt[..., None] >= 0, cache["ppos"][btc], -1)
    return (k.reshape(b, -1, *k.shape[3:]),
            v.reshape(b, -1, *v.shape[3:]),
            pos.reshape(b, -1))
