"""KVPool allocator: alloc/append/free lifecycle, exhaustion, block-table
consistency under churn (property-tested when hypothesis is available),
the device-side paged write/gather ops, and KV page migration between
pool partitions (DESIGN.md §disaggregated serving)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.kvpool import (KVPool, ShardedKVPool, PoolError,
                                PoolExhausted, TRASH_BLOCK, blocks_for,
                                copy_pages, init_pages, live_blocks,
                                paged_write, paged_view)

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # property tests skip, the rest still run
    from hypothesis_stub import given, settings, st


def test_blocks_for():
    assert blocks_for(0, 4) == 0
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2


def test_live_blocks():
    # first / last slot of a page, an inactive row
    assert live_blocks([0, 3, 4, 7, -1], 4).tolist() == [1, 1, 2, 2, 0]
    # a window of 6 ending at 9 covers positions 4..9 (blocks 1-2), at
    # 11 covers 6..11 (blocks 1-2), at 12 covers 7..12 (blocks 1-3)
    assert live_blocks([9, 11, 12, 2], 4, window=6).tolist() == [2, 2, 3, 1]


def test_alloc_free_roundtrip():
    p = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    assert p.n_free_blocks == 8          # block 0 reserved
    b0 = p.allocate("a", 10)             # 3 blocks
    assert len(b0) == 3 and TRASH_BLOCK not in b0
    assert p.num_tokens("a") == 10 and p.n_used_blocks == 3
    bt = p.block_table("a")
    assert bt.shape == (4,) and list(bt[:3]) == b0 and bt[3] == -1
    p.free("a")
    assert p.n_free_blocks == 8 and not p.has("a")
    p.check_invariants()


def test_append_grows_table_on_boundary():
    p = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    p.allocate("a", 3)
    assert p.append("a") == []           # 4 tokens, still 1 block
    fresh = p.append("a")                # 5 tokens -> 2 blocks
    assert len(fresh) == 1 and fresh[0] in p.block_table("a")
    assert p.num_tokens("a") == 5 and len(p.block_table("a")) == 4
    assert (p.block_table("a") >= 0).sum() == 2
    p.check_invariants()


def test_double_alloc_and_double_free_raise():
    p = KVPool(num_blocks=5, block_size=4, max_blocks_per_seq=2)
    p.allocate("a", 4)
    with pytest.raises(PoolError):
        p.allocate("a", 4)
    p.free("a")
    with pytest.raises(PoolError):
        p.free("a")
    with pytest.raises(PoolError):
        p.append("ghost")


def test_pool_exhaustion_raises():
    p = KVPool(num_blocks=4, block_size=4, max_blocks_per_seq=3)
    p.allocate("a", 8)                   # 2 of 3 blocks
    with pytest.raises(PoolExhausted):
        p.allocate("b", 8)               # needs 2, only 1 free
    # failed alloc must not leak partial state
    p.check_invariants()
    assert not p.has("b") and p.n_free_blocks == 1


def test_per_seq_cap_raises():
    p = KVPool(num_blocks=32, block_size=4, max_blocks_per_seq=2)
    with pytest.raises(PoolExhausted):
        p.allocate("a", 9)               # 3 blocks > cap 2
    p.allocate("b", 8)
    with pytest.raises(PoolExhausted):
        p.append("b")                    # 9 tokens > cap


def test_table_array_ordering_and_missing_rows():
    p = KVPool(num_blocks=9, block_size=2, max_blocks_per_seq=3)
    p.allocate(1, 2)
    arr = p.table_array([0, 1, None])
    assert arr.shape == (3, 3)
    assert (arr[0] == -1).all() and (arr[2] == -1).all()
    assert arr[1, 0] >= 1 and (arr[1, 1:] == -1).all()


def _churn(p, ops):
    """Deterministic alloc/append/free churn driven by an op list."""
    live = set()
    for kind, cid, n in ops:
        try:
            if kind == 0 and cid not in live:
                p.allocate(cid, n)
                live.add(cid)
            elif kind == 1 and cid in live:
                p.append(cid, n)
            elif kind == 2 and cid in live:
                p.free(cid)
                live.discard(cid)
        except PoolExhausted:
            pass                          # legal under churn; state intact
        p.check_invariants()
    return live


def test_churn_deterministic():
    rng = np.random.default_rng(0)
    p = KVPool(num_blocks=17, block_size=4, max_blocks_per_seq=5)
    ops = [(int(rng.integers(3)), int(rng.integers(6)),
            int(rng.integers(1, 12))) for _ in range(300)]
    live = _churn(p, ops)
    assert p.used_tokens() == sum(p.num_tokens(c) for c in live)
    assert 0.0 <= p.utilization() <= 1.0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 5),
                          st.integers(1, 12)), max_size=120))
def test_churn_property(ops):
    """No double-ownership, free-list disjointness, per-seq caps — under
    arbitrary alloc/append/free interleavings."""
    _churn(KVPool(num_blocks=11, block_size=4, max_blocks_per_seq=4), ops)


def _trash_ids(pool):
    if isinstance(pool, ShardedKVPool):
        return {pool._offset(s) for s in range(pool.n_shards)}
    return {TRASH_BLOCK}


def _live_blocks(pool, clients):
    out = set()
    for c in clients:
        if pool.has(c):
            out |= {int(b) for b in pool.block_table(c) if b >= 0}
    return out


@pytest.mark.parametrize("make", [
    lambda: KVPool(num_blocks=17, block_size=4, max_blocks_per_seq=5),
    lambda: ShardedKVPool(num_blocks=16, block_size=4,
                          max_blocks_per_seq=3, n_shards=2, n_rows=6),
])
def test_trash_never_live_under_churn(make):
    """After arbitrary alloc/append/free interleavings, no trash block
    (block 0; every shard's local block 0 in the sharded pool) is ever
    referenced by a live block table."""
    rng = np.random.default_rng(4)
    p = make()
    ops = [(int(rng.integers(3)), int(rng.integers(6)),
            int(rng.integers(1, 12))) for _ in range(300)]
    live = set()
    for kind, cid, n in ops:
        try:
            if kind == 0 and cid not in live:
                p.allocate(cid, n)
                live.add(cid)
            elif kind == 1 and cid in live:
                p.append(cid, n)
            elif kind == 2 and cid in live:
                p.free(cid)
                live.discard(cid)
        except PoolExhausted:
            pass
        p.check_invariants()
        assert not (_live_blocks(p, range(6)) & _trash_ids(p))


# -- sharded pool ------------------------------------------------------------

def test_sharded_pool_row_to_shard_mapping_and_trash():
    p = ShardedKVPool(num_blocks=12, block_size=4, max_blocks_per_seq=3,
                      n_shards=3, n_rows=6)
    assert p.blocks_per_shard == 4 and p.rows_per_shard == 2
    assert [p.shard_of(j) for j in range(6)] == [0, 0, 1, 1, 2, 2]
    assert [p.trash_for(j) for j in range(6)] == [0, 0, 4, 4, 8, 8]
    np.testing.assert_array_equal(p.trash_vector(range(6)),
                                  [0, 0, 4, 4, 8, 8])
    with pytest.raises(PoolError):
        p.shard_of(6)


def test_sharded_pool_blocks_stay_in_segment():
    p = ShardedKVPool(num_blocks=12, block_size=4, max_blocks_per_seq=3,
                      n_shards=2, n_rows=4)
    b0 = p.allocate(0, 8)                 # shard 0: global ids in (0, 6)
    b2 = p.allocate(2, 8)                 # shard 1: global ids in (6, 12)
    assert all(0 < b < 6 for b in b0)
    assert all(6 < b < 12 for b in b2)
    assert p.append(2, 4)[0] > 6
    bt = p.table_array([0, 1, 2, 3])
    assert (bt[1] == -1).all() and (bt[3] == -1).all()
    assert set(bt[0][bt[0] >= 0]) == set(b0)
    p.check_invariants()


def test_sharded_pool_exhaustion_is_per_shard():
    """Shard 0 running dry must not consume (or corrupt) shard 1's
    blocks, and vice versa; double free still raises."""
    p = ShardedKVPool(num_blocks=8, block_size=4, max_blocks_per_seq=3,
                      n_shards=2, n_rows=4)      # 3 allocatable per shard
    p.allocate(0, 12)                            # shard 0 full
    with pytest.raises(PoolExhausted, match="shard 0"):
        p.allocate(1, 4)
    b = p.allocate(2, 12)                        # shard 1 unaffected
    assert len(b) == 3 and all(4 < x < 8 for x in b)
    with pytest.raises(PoolExhausted, match="shard 1"):
        p.allocate(3, 4)
    p.free(0)
    with pytest.raises(PoolError):
        p.free(0)                                # double free
    p.allocate(1, 4)                             # freed segment reusable
    p.check_invariants()


def test_sharded_pool_validates_divisibility():
    with pytest.raises(ValueError):
        ShardedKVPool(num_blocks=9, block_size=4, max_blocks_per_seq=2,
                      n_shards=2, n_rows=4)
    with pytest.raises(ValueError):
        ShardedKVPool(num_blocks=8, block_size=4, max_blocks_per_seq=2,
                      n_shards=2, n_rows=3)


def test_paged_write_per_row_trash_routing():
    """Invalid positions route to each row's OWN trash block: no write
    ever lands outside the row's shard segment."""
    bs, hk, hd = 2, 1, 4
    p = ShardedKVPool(num_blocks=8, block_size=bs, max_blocks_per_seq=2,
                      n_shards=2, n_rows=2)
    p.allocate(0, 2)
    p.allocate(1, 2)
    cache = init_pages(8, bs, hk, hd, jnp.float32)
    cache["bt"] = jnp.asarray(p.table_array([0, 1]))
    positions = jnp.asarray([[0, 1, -1], [0, 1, -1]])   # one pad per row
    marker = jnp.concatenate(
        [jnp.ones((2, 2, hk, hd)), jnp.full((2, 1, hk, hd), 7.0)], axis=1)
    cache = paged_write(cache, marker, -marker, positions,
                        trash=jnp.asarray(p.trash_vector([0, 1])))
    # both trash blocks took a (masked) pad write; neither crossed shards
    kp = np.asarray(cache["kp"])
    assert kp[0, 0, 0, 0] == 7.0 and kp[4, 0, 0, 0] == 7.0
    assert (np.asarray(cache["ppos"])[0] == -1).all()
    assert (np.asarray(cache["ppos"])[4] == -1).all()
    # live writes landed in the right segments
    kc, _, pos = paged_view(cache)
    np.testing.assert_array_equal(np.asarray(pos[:, :2]),
                                  [[0, 1], [0, 1]])
    assert (np.asarray(kc[:, :2]) == 1.0).all()


# -- device-side page ops ---------------------------------------------------

def test_paged_write_and_view():
    bs, hk, hd = 4, 2, 8
    pool = KVPool(num_blocks=6, block_size=bs, max_blocks_per_seq=3)
    pool.allocate(0, 6)
    pool.allocate(1, 2)
    cache = init_pages(6, bs, hk, hd, jnp.float32)
    cache["bt"] = jnp.asarray(pool.table_array([0, 1]))
    k = jnp.arange(2 * 6 * hk * hd, dtype=jnp.float32).reshape(2, 6, hk, hd)
    v = -k
    positions = jnp.asarray([[0, 1, 2, 3, 4, 5],       # row 0: 6 tokens
                             [0, 1, -1, -1, -1, -1]])  # row 1: 2 + pads
    cache = paged_write(cache, k, v, positions)
    kc, vc, pos = paged_view(cache)
    assert kc.shape == (2, 3 * bs, hk, hd)
    np.testing.assert_array_equal(np.asarray(pos[0, :6]), np.arange(6))
    assert (np.asarray(pos[0, 6:]) == -1).all()
    np.testing.assert_array_equal(np.asarray(pos[1, :2]), [0, 1])
    assert (np.asarray(pos[1, 2:]) == -1).all()
    np.testing.assert_array_equal(np.asarray(kc[0, :6]), np.asarray(k[0]))
    np.testing.assert_array_equal(np.asarray(vc[1, :2]), np.asarray(v[1, :2]))
    # pad writes landed in the trash block, which stays masked
    assert (np.asarray(cache["ppos"][TRASH_BLOCK]) == -1).all()


def test_paged_write_routes_overflow_positions_to_trash():
    """Positions beyond the block table (caller kept decoding without
    growing the table) must NOT clip into the last allocated block."""
    bs, hk, hd = 2, 1, 4
    pool = KVPool(num_blocks=6, block_size=bs, max_blocks_per_seq=2)
    pool.allocate(0, 4)                  # table full: 2 blocks = 4 slots
    cache = init_pages(6, bs, hk, hd, jnp.float32)
    cache["bt"] = jnp.asarray(pool.table_array([0]))
    cache = paged_write(cache, jnp.ones((1, 4, hk, hd)),
                        jnp.ones((1, 4, hk, hd)),
                        jnp.arange(4)[None])
    before = np.asarray(paged_view(cache)[0][0, :4]).copy()
    # overflow write at position 4 (block index 2 > table width 2)
    cache = paged_write(cache, jnp.full((1, 1, hk, hd), 9.0),
                        jnp.full((1, 1, hk, hd), 9.0),
                        jnp.asarray([[4]]))
    kc, _, pos = paged_view(cache)
    np.testing.assert_array_equal(np.asarray(kc[0, :4]), before)
    np.testing.assert_array_equal(np.asarray(pos[0]), [0, 1, 2, 3])
    assert (np.asarray(cache["ppos"][TRASH_BLOCK]) == -1).all()


def test_paged_write_disjoint_rows_do_not_collide():
    bs, hk, hd = 2, 1, 4
    pool = KVPool(num_blocks=8, block_size=bs, max_blocks_per_seq=3)
    for cid in (0, 1, 2):
        pool.allocate(cid, 4)
    cache = init_pages(8, bs, hk, hd, jnp.float32)
    cache["bt"] = jnp.asarray(pool.table_array([0, 1, 2]))
    k = jnp.stack([jnp.full((4, hk, hd), float(r + 1)) for r in range(3)])
    positions = jnp.broadcast_to(jnp.arange(4)[None], (3, 4))
    cache = paged_write(cache, k, -k, positions)
    kc, _, pos = paged_view(cache)
    for r in range(3):
        np.testing.assert_array_equal(np.asarray(kc[r, :4]),
                                      np.full((4, hk, hd), float(r + 1)))


# ---------------------------------------------------------------- quotas

def test_quota_caps_allocation_below_capacity():
    """A lane quota gates the allocator below the device ceiling: blocks
    beyond the quota stay on the free list but are not handed out."""
    p = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4, quota=3)
    assert p.headroom == 3
    p.allocate("a", 12)                  # exactly the 3-block quota
    assert p.headroom == 0 and p.n_free_blocks == 5
    with pytest.raises(PoolExhausted):
        p.allocate("b", 1)               # free blocks exist, quota doesn't
    p.check_invariants()
    p.free("a")
    assert p.headroom == 3


def test_quota_shrink_below_usage_blocks_growth_only():
    """Shrinking a quota below current usage reclaims nothing: live
    blocks stay live, and new allocations wait for drains."""
    p = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    p.allocate("a", 12)                  # 3 blocks, uncapped
    p.set_quota(1)
    assert p.headroom == 0 and p.n_used_blocks == 3
    with pytest.raises(PoolExhausted):
        p.append("a", 4)                 # boundary crossing needs a block
    p.free("a")                          # drain; quota now funds 1 block
    assert p.headroom == 1
    p.allocate("b", 4)
    p.check_invariants()


def test_quota_none_uncaps():
    p = KVPool(num_blocks=5, block_size=4, max_blocks_per_seq=4, quota=0)
    with pytest.raises(PoolExhausted):
        p.allocate("a", 1)
    p.set_quota(None)
    p.allocate("a", 1)
    assert p.headroom == 3


def test_sharded_pool_quota_splits_per_shard():
    """An aggregate quota splits evenly across shards, so a lane cannot
    borrow headroom a single shard does not actually have."""
    p = ShardedKVPool(num_blocks=12, block_size=4, max_blocks_per_seq=4,
                      n_shards=2, n_rows=2)
    p.set_quota(4)
    assert p.quota == 4 and p.headroom == 4
    p.allocate(0, 8)                     # 2 blocks on shard 0 = its quota
    with pytest.raises(PoolExhausted):
        p.allocate(1, 12)                # shard 1 quota is 2, needs 3
    assert p.headroom == 2               # shard 1's remaining quota
    p.set_quota(None)
    assert p.quota is None
    p.allocate(1, 12)
    p.check_invariants()


# ------------------------------------------------------------- migration

def test_migrate_rows_frees_source_and_lands_whole():
    src = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    dst = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    src.allocate("a", 10)
    sb, db = src.migrate_rows("a", dst)
    assert len(sb) == len(db) == 3
    assert not src.has("a") and dst.has("a")
    assert dst.num_tokens("a") == 10
    assert src.n_free_blocks == 8 and dst.n_used_blocks == 3
    dst.append("a")                      # 11 tokens, still 3 blocks
    assert dst.num_tokens("a") == 11
    src.check_invariants()
    dst.check_invariants()


def test_migrate_rows_rejects_self_and_missing():
    src = KVPool(num_blocks=5, block_size=4, max_blocks_per_seq=2)
    dst = KVPool(num_blocks=5, block_size=4, max_blocks_per_seq=2)
    with pytest.raises(PoolError):
        src.migrate_rows("ghost", dst)
    src.allocate("a", 4)
    with pytest.raises(PoolError):
        src.migrate_rows("a", src)       # onto itself
    src.migrate_rows("a", src, dst_cid="b")   # same pool, new id is fine
    assert not src.has("a") and src.has("b")
    src.check_invariants()


def test_migrate_rows_atomic_on_dst_exhaustion():
    """A failed migration (destination pool full) must leave the source
    row untouched and the destination clean — no half-moved row."""
    src = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    dst = KVPool(num_blocks=3, block_size=4, max_blocks_per_seq=4)
    src.allocate("a", 12)                # 3 blocks > dst's 2 allocatable
    with pytest.raises(PoolExhausted):
        src.migrate_rows("a", dst)
    assert src.has("a") and src.num_tokens("a") == 12
    assert not dst.has("a") and dst.n_used_blocks == 0
    src.check_invariants()
    dst.check_invariants()


def test_migrate_rows_respects_dst_quota():
    """Migration allocates under the destination's quota like any other
    admission: quota exhausted -> PoolExhausted, source intact."""
    src = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4)
    dst = KVPool(num_blocks=9, block_size=4, max_blocks_per_seq=4, quota=1)
    src.allocate("a", 8)                 # 2 blocks > quota 1
    with pytest.raises(PoolExhausted):
        src.migrate_rows("a", dst)
    assert src.has("a") and not dst.has("a")
    dst.set_quota(None)
    src.migrate_rows("a", dst)
    assert dst.num_tokens("a") == 8
    dst.check_invariants()


def test_migrate_pages_sharded_crosses_partitions():
    """ShardedKVPool.migrate_pages returns GLOBAL page ids on both sides
    and lands the row on the destination row's own shard segment."""
    src = ShardedKVPool(num_blocks=12, block_size=4, max_blocks_per_seq=3,
                        n_shards=2, n_rows=4)
    dst = ShardedKVPool(num_blocks=12, block_size=4, max_blocks_per_seq=3,
                        n_shards=2, n_rows=4)
    src.allocate(2, 8)                   # shard 1: global ids in (6, 12)
    sb, db = src.migrate_pages(2, dst_cid=0, dst=dst)   # -> shard 0
    assert all(6 < b < 12 for b in sb)
    assert all(0 < b < 6 for b in db)
    assert not src.has(2) and dst.has(0)
    assert dst.num_tokens(0) == 8 and dst.shard_of(0) == 0
    with pytest.raises(PoolError):
        dst.migrate_pages(0)             # onto itself
    src.check_invariants()
    dst.check_invariants()


@pytest.mark.parametrize("quant", [None, "int8", "fp8"])
def test_copy_pages_bit_exact(quant):
    """Migrated pages are bit-exact: payload, quant scales (when
    present) and the per-slot position mask all match the source pages
    after ``copy_pages`` — migration never re-quantizes."""
    bs, hk, hd = 4, 2, 8
    src_pool = KVPool(num_blocks=6, block_size=bs, max_blocks_per_seq=3)
    dst_pool = KVPool(num_blocks=6, block_size=bs, max_blocks_per_seq=3)
    src_pool.allocate(0, 6)              # 2 blocks, tail half-filled
    dst_pool.allocate("pad", 4)          # offset dst ids away from src's
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((1, 6, hk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 6, hk, hd)), jnp.float32)
    src_cache = init_pages(6, bs, hk, hd, jnp.float32, quant=quant)
    src_cache["bt"] = jnp.asarray(src_pool.table_array([0]))
    src_cache = paged_write(src_cache, k, v, jnp.arange(6)[None])
    dst_cache = init_pages(6, bs, hk, hd, jnp.float32, quant=quant)
    sb, db = src_pool.migrate_rows(0, dst_pool)
    dst_cache = copy_pages(src_cache, dst_cache, sb, db)
    keys = ("kp", "vp", "ppos") + (("ksc", "vsc") if quant else ())
    for key in keys:
        np.testing.assert_array_equal(
            np.asarray(dst_cache[key][np.asarray(db)]),
            np.asarray(src_cache[key][np.asarray(sb)]),
            err_msg=f"{key} pages not bit-exact after migration")
    # the tail page's unwritten slots keep their -1 mask
    assert (np.asarray(dst_cache["ppos"][db[-1], 2:]) == -1).all()
    # untouched destination pages stay untouched
    others = np.asarray([i for i in range(6) if i not in db])
    assert (np.asarray(dst_cache["ppos"])[others] == -1).all()


def test_copy_pages_rejects_dtype_mismatch():
    bs, hk, hd = 4, 1, 4
    a = init_pages(4, bs, hk, hd, jnp.float32)
    q = init_pages(4, bs, hk, hd, jnp.float32, quant="int8")
    with pytest.raises(ValueError):
        copy_pages(a, q, [1], [1])
    with pytest.raises(ValueError):
        copy_pages(a, a, [1, 2], [1])
    assert copy_pages(a, q, [], []) is q   # empty move is a no-op


def _churn_migrate(pa, pb, ops, n_clients=6):
    """alloc/append/free/migrate interleavings over a pool pair; checks
    free-list conservation, migration atomicity and trash-never-live
    after every op."""
    alloc_a = pa.num_blocks - pa.n_shards if hasattr(pa, "n_shards") \
        else pa.num_blocks - 1
    live = {}
    for kind, cid, n in ops:
        try:
            if kind == 0 and cid not in live:
                pa.allocate(cid, n)
                live[cid] = pa
            elif kind == 1 and cid in live:
                live[cid].append(cid, n)
            elif kind == 2 and cid in live:
                live[cid].free(cid)
                del live[cid]
            elif kind == 3 and cid in live:
                src = live[cid]
                dst = pb if src is pa else pa
                toks = src.num_tokens(cid)
                try:
                    if hasattr(src, "migrate_pages"):
                        src.migrate_pages(cid, dst=dst)
                    else:
                        src.migrate_rows(cid, dst)
                except PoolExhausted:
                    # atomic: the source row survives a failed landing
                    assert src.has(cid)
                    assert src.num_tokens(cid) == toks
                    assert not dst.has(cid)
                else:
                    live[cid] = dst
                    assert dst.num_tokens(cid) == toks
                    assert not src.has(cid)
        except PoolExhausted:
            pass
        for p in (pa, pb):
            p.check_invariants()
            assert not (_live_blocks(p, range(n_clients)) & _trash_ids(p))
        # conservation: no block leaks or double-books across the pair
        assert pa.n_used_blocks + pa.n_free_blocks == alloc_a
        assert pb.n_used_blocks + pb.n_free_blocks == alloc_a
        assert (pa.n_used_blocks + pb.n_used_blocks
                == sum(len([b for b in live[c].block_table(c) if b >= 0])
                       for c in live))
    return live


def test_migrate_churn_deterministic():
    rng = np.random.default_rng(7)
    pa = KVPool(num_blocks=11, block_size=4, max_blocks_per_seq=4)
    pb = KVPool(num_blocks=11, block_size=4, max_blocks_per_seq=4)
    ops = [(int(rng.integers(4)), int(rng.integers(6)),
            int(rng.integers(1, 12))) for _ in range(300)]
    _churn_migrate(pa, pb, ops)


def test_migrate_churn_sharded_deterministic():
    """Same interleavings through two sharded pools: rows keep their
    shard mapping on both sides, quotas and segments hold."""
    rng = np.random.default_rng(8)
    mk = lambda: ShardedKVPool(num_blocks=16, block_size=4,
                               max_blocks_per_seq=3, n_shards=2, n_rows=6)
    pa, pb = mk(), mk()
    pb.set_quota(10)                     # migrations land under a quota
    ops = [(int(rng.integers(4)), int(rng.integers(6)),
            int(rng.integers(1, 12))) for _ in range(300)]
    _churn_migrate(pa, pb, ops)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 5),
                          st.integers(1, 12)), max_size=120))
def test_migrate_churn_property(ops):
    """Pages conserve, migrations are atomic, trash never goes live —
    under arbitrary alloc/append/free/migrate interleavings."""
    _churn_migrate(KVPool(num_blocks=11, block_size=4,
                          max_blocks_per_seq=4),
                   KVPool(num_blocks=11, block_size=4,
                          max_blocks_per_seq=4), ops)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 5),
                          st.integers(1, 12)), max_size=100))
def test_migrate_churn_sharded_property(ops):
    mk = lambda: ShardedKVPool(num_blocks=16, block_size=4,
                               max_blocks_per_seq=3, n_shards=2, n_rows=6)
    pa, pb = mk(), mk()
    pb.set_quota(10)
    _churn_migrate(pa, pb, ops)


def test_sharded_pool_quota_shrink_floors_at_shard_usage():
    """A quota shrink (rebalance donation) must never drop a hot shard
    below its live blocks: only genuinely unused headroom moves.  Here
    shard 0 holds 5 live blocks while shard 1 is idle; shrinking the
    aggregate quota from 12 to 8 must leave shard 0 able to keep (and
    grow into) its usage rather than splitting 4/4 and stranding it."""
    p = ShardedKVPool(num_blocks=16, block_size=4, max_blocks_per_seq=6,
                      n_shards=2, n_rows=2)
    p.set_quota(12)
    p.allocate(0, 20)                    # 5 live blocks, all on shard 0
    p.set_quota(8)                       # donate 4 blocks of spare quota
    assert p._shards[0].quota >= 5       # floor at live usage
    assert p.quota == 8
    assert p.append(0, 4)                # 6th block still allocatable
    p.check_invariants()
