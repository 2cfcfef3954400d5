"""Mesh-sharded serving (DESIGN.md §sharded serving): token parity with
single-device paged serving, compile-once on the mesh, shard_map kernel
parity, and shard-local backpressure / preemption.

Device-backed tests need fake host devices and skip on a plain 1-device
run; the devices=8 CI job (and local runs) opt in via

    REPRO_TEST_DEVICES=8 python -m pytest tests/test_mesh_serve.py

(tests/conftest.py translates the env var into XLA_FLAGS before jax
initializes).  The spec/validation tests at the bottom always run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import MuxSpec
from repro.configs import get_config
from repro.models import TransformerLM
from repro.runtime.sharding import cache_specs
from repro.serve import ServeConfig, Request, greedy_generate
from repro.serve.runtime import ServeRuntime
from repro.launch.mesh import make_serve_mesh
from repro.launch.serve import run_continuous

KEY = jax.random.PRNGKey(0)


def needs_devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (set REPRO_TEST_DEVICES={n})")


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-1.5b", reduced=True)
    params = TransformerLM.init(KEY, cfg, MuxSpec(n=1))
    return cfg, params


def _sc(cfg, n_shards=1, capacity=48, block_size=4, **kw):
    return ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1),
                       capacity=capacity, dtype=jnp.float32,
                       cache_layout="paged", block_size=block_size,
                       n_shards=n_shards, **kw)


def _serve(params, sc, rows, arrivals, *, mesh=None, chunk=8, **kw):
    stats = run_continuous(params, sc, rows,
                           [(t, p.copy(), m) for t, p, m in arrivals],
                           chunk=chunk, mesh=mesh, **kw)
    return {tuple(r.prompt): r.output for r in stats["completed"]}, stats


def _staggered(cfg, lens, seed=0, max_new=4, every=2):
    rng = np.random.default_rng(seed)
    return [(i * every,
             rng.integers(4, cfg.vocab_size, size=(l,)).astype(np.int32),
             max_new) for i, l in enumerate(lens)]


# ------------------------------------------------ serving on the mesh

@needs_devices(2)
def test_mesh_tokens_match_single_device(model):
    """Acceptance: data-sharded serving is token-identical to the
    single-device paged-chunked arm, with identical compile counts
    (1 decode + one program per prefill bucket)."""
    cfg, params = model
    arrivals = _staggered(cfg, (5, 9, 14, 7))
    o1, s1 = _serve(params, _sc(cfg), 2, arrivals)
    o2, s2 = _serve(params, _sc(cfg, n_shards=2), 2, arrivals,
                    mesh=make_serve_mesh(2, 1))
    assert len(o1) == 4 and o1 == o2
    assert s1["trace_counts"] == s2["trace_counts"]
    assert s2["trace_counts"]["decode"] == 1
    bucket_keys = [k for k in s2["trace_counts"] if k.startswith("prefill_")]
    assert bucket_keys and all(s2["trace_counts"][k] == 1
                               for k in bucket_keys)
    assert s2["pool"].n_used_blocks == 0
    s2["pool"].check_invariants()


@needs_devices(4)
def test_mesh_tensor_parallel_tokens_match(model):
    """(data=2, model=2): tensor parallelism on top of the row shards
    must not change any stream's tokens."""
    cfg, params = model
    arrivals = _staggered(cfg, (6, 11, 8), seed=1)
    o1, _ = _serve(params, _sc(cfg), 2, arrivals)
    o2, s2 = _serve(params, _sc(cfg, n_shards=2), 2, arrivals,
                    mesh=make_serve_mesh(2, 2))
    assert o1 == o2
    assert s2["trace_counts"]["decode"] == 1


@needs_devices(2)
def test_mesh_compile_once_across_prompt_lengths(model):
    """The PR 2 compile-once guarantee extends to the mesh path: >= 3
    distinct prompt lengths still trace 1 decode program and one program
    per used prefill bucket."""
    cfg, params = model
    arrivals = _staggered(cfg, (3, 10, 15, 6, 12), seed=2)
    _, stats = _serve(params, _sc(cfg, n_shards=2), 2, arrivals,
                      mesh=make_serve_mesh(2, 1))
    counts = stats["trace_counts"]
    assert counts["decode"] == 1
    buckets = sorted(k for k in counts if k.startswith("prefill_"))
    # lengths 3/10/15/6/12 at chunk 8 only ever use the 4- and 8-buckets
    assert buckets == ["prefill_4", "prefill_8"]
    assert all(counts[k] == 1 for k in buckets)


@needs_devices(2)
def test_mesh_solo_greedy_exact(model):
    """Every mesh-served stream reproduces its solo greedy_generate
    output token-for-token (N=1 exactness on the mesh)."""
    cfg, params = model
    sc1 = _sc(cfg)
    arrivals = _staggered(cfg, (5, 8), seed=3, max_new=5)
    o2, _ = _serve(params, _sc(cfg, n_shards=2), 2, arrivals,
                   mesh=make_serve_mesh(2, 1))
    for _, p, m in arrivals:
        want = greedy_generate(params, sc1, jnp.asarray(p)[None],
                               steps=m)[0]
        np.testing.assert_array_equal(
            np.asarray(o2[tuple(int(t) for t in p)]), np.asarray(want))


@needs_devices(2)
def test_mesh_use_kernels_matches_gather_path(model):
    """use_kernels=True routes decode + chunk attention through the
    shard_map'd Pallas kernels (shard-local pages, rebased tables); the
    tokens must match the pure-JAX gather path."""
    cfg, params = model
    arrivals = _staggered(cfg, (6, 9), seed=4, max_new=3, every=1)
    mesh = make_serve_mesh(2, 1)
    o1, _ = _serve(params, _sc(cfg, n_shards=2), 2, arrivals, mesh=mesh)
    o2, _ = _serve(params, _sc(cfg, n_shards=2), 2, arrivals, mesh=mesh,
                   use_kernels=True)
    assert o1 == o2


@needs_devices(4)
def test_mesh_use_kernels_with_tensor_parallelism(model):
    """(data=2, model=2) + use_kernels: the shard_map kernel splits the
    kv-head groups over 'model' (both head counts divide it on the
    reduced config), and the tokens still match the unsharded arm."""
    cfg, params = model
    assert cfg.n_heads % 2 == 0 and cfg.n_kv_heads % 2 == 0
    arrivals = _staggered(cfg, (6, 9), seed=7, max_new=3, every=1)
    o1, _ = _serve(params, _sc(cfg), 2, arrivals)
    o2, _ = _serve(params, _sc(cfg, n_shards=2), 2, arrivals,
                   mesh=make_serve_mesh(2, 2), use_kernels=True)
    assert o1 == o2


@needs_devices(2)
def test_mesh_mux_groups_tokens_match():
    """Mux N=2 on the mesh: each data shard serves whole mux groups; the
    tokens must match the single-device paged-chunked arm."""
    cfg = get_config("qwen2-1.5b", reduced=True)
    mux = MuxSpec(n=2)
    params = TransformerLM.init(KEY, cfg, mux)

    def sc(n_shards):
        return ServeConfig(cfg=cfg, kind="lm", mux=mux, capacity=32,
                           dtype=jnp.float32, cache_layout="paged",
                           block_size=4, n_shards=n_shards)

    arrivals = _staggered(cfg, (6, 6, 9, 9), seed=9, max_new=3)
    o1, _ = _serve(params, sc(1), 2, arrivals, chunk=4)
    o2, s2 = _serve(params, sc(2), 2, arrivals, chunk=4,
                    mesh=make_serve_mesh(2, 1))
    assert len(o1) == 4 and o1 == o2
    assert s2["trace_counts"]["decode"] == 1


# ------------------------------------- shard-local pool pressure

@needs_devices(2)
def test_mesh_backpressure_is_shard_local(model):
    """Each shard fits exactly one live row: admissions beyond that are
    rolled back (cancel_admit) and retried after the shard's own drains
    — both shards keep serving, every request stays exact."""
    cfg, params = model
    # capacity 12 = 3 blocks of 4; one shard = 4 blocks (1 trash + 3
    # allocatable) -> exactly one row at a time per shard
    sc = _sc(cfg, n_shards=2, capacity=12, num_blocks=8)
    sc1 = _sc(cfg, capacity=12)
    rng = np.random.default_rng(5)
    arrivals = [(0, rng.integers(4, cfg.vocab_size,
                                 size=(8,)).astype(np.int32), 4)
                for _ in range(4)]
    out, stats = _serve(params, sc, 4, arrivals, mesh=make_serve_mesh(2, 1))
    assert len(out) == 4
    assert stats["pool"].n_used_blocks == 0
    stats["pool"].check_invariants()
    for _, p, m in arrivals:
        want = greedy_generate(params, sc1, jnp.asarray(p)[None],
                               steps=m)[0]
        np.testing.assert_array_equal(
            np.asarray(out[tuple(int(t) for t in p)]), np.asarray(want))


@needs_devices(2)
def test_admission_retries_on_sibling_shard(model):
    """A group whose first-choice shard has no blocks must be re-planned
    onto a sibling shard with free blocks IN THE SAME STEP — not parked
    at the queue head behind the busy shard."""
    cfg, params = model
    # per shard: 3 blocks (1 trash + 2 allocatable); capacity 8 = 2-block
    # per-seq cap.  Admission order visits rows [0, 2, 1, 3].
    sc = _sc(cfg, n_shards=2, capacity=8, num_blocks=6)
    rng = np.random.default_rng(8)
    mk = lambda l: rng.integers(4, cfg.vocab_size,
                                size=(l,)).astype(np.int32)
    rt = ServeRuntime(params, sc, 4, chunk=4, mesh=make_serve_mesh(2, 1))
    from repro.serve.batcher import Request
    rt.submit(Request(uid=0, prompt=[int(t) for t in mk(5)], max_new=2))
    rt.submit(Request(uid=1, prompt=[int(t) for t in mk(3)], max_new=2))
    rt.submit(Request(uid=2, prompt=[int(t) for t in mk(3)], max_new=2))
    rt.step()
    # uid 0 -> row 0 fills shard 0 (2 blocks); uid 1 -> row 2 (shard 1);
    # uid 2's first-choice row 1 (shard 0) has no blocks — it must have
    # been re-planned onto row 3 (shard 1), not left in the queue
    # (short prompts may complete within this very step, so the prefill
    # log — one entry per chunk event — is the placement evidence)
    assert not rt.sched.queue
    placed_rows = {r for rows_, _ in rt.stats["prefill_log"]
                   for r in rows_}
    assert placed_rows == {0, 2, 3}
    while rt.has_work():
        rt.step()
    assert len(rt.stats["completed"]) == 3
    assert rt.pool.n_used_blocks == 0
    rt.pool.check_invariants()
    sc1 = _sc(cfg, capacity=8)
    by_uid = {r.uid: (r.prompt, r.output)
              for r in rt.stats["completed"]}
    for uid in range(3):
        prompt, got = by_uid[uid]
        want = greedy_generate(params, sc1,
                               jnp.asarray(prompt, jnp.int32)[None],
                               steps=2)[0]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs_devices(2)
def test_mesh_preemption_is_shard_local(model):
    """Two rows per shard whose decode growth exhausts the shard: the
    preempted rows requeue and resume on their OWN shard; outputs stay
    exact and the pool drains."""
    cfg, params = model
    # per shard: 5 blocks (1 trash + 4 allocatable); two 8-token prompts
    # (2 blocks each) fill a shard, growth at position 8 preempts
    sc = _sc(cfg, n_shards=2, capacity=12, num_blocks=10)
    sc1 = _sc(cfg, capacity=12)
    rng = np.random.default_rng(6)
    arrivals = [(0, rng.integers(4, cfg.vocab_size,
                                 size=(8,)).astype(np.int32), 4)
                for _ in range(4)]
    out, stats = _serve(params, sc, 4, arrivals, mesh=make_serve_mesh(2, 1))
    assert len(out) == 4
    assert stats["pool"].n_used_blocks == 0
    for _, p, m in arrivals:
        want = greedy_generate(params, sc1, jnp.asarray(p)[None],
                               steps=m)[0]
        np.testing.assert_array_equal(
            np.asarray(out[tuple(int(t) for t in p)]), np.asarray(want))


# ------------------------------------------- shard_map kernel parity

def _sharded_pool(lens, *, n_shards, bps, block_size, max_blocks, hkv, dh,
                  key):
    """Pool with the ShardedKVPool layout: row r lives on shard
    r // (len(lens) // n_shards); shard s owns blocks [s*bps, (s+1)*bps)
    with local block 0 as its trash."""
    num_blocks = n_shards * bps
    ks = jax.random.split(key, 2)
    kp = jax.random.normal(ks[0], (num_blocks, block_size, hkv, dh))
    vp = jax.random.normal(ks[1], (num_blocks, block_size, hkv, dh))
    bt = np.full((len(lens), max_blocks), -1, np.int32)
    ppos = np.full((num_blocks, block_size), -1, np.int32)
    free = {s: list(range(s * bps + 1, (s + 1) * bps))
            for s in range(n_shards)}
    rps = len(lens) // n_shards
    for r, n in enumerate(lens):
        if n < 0:
            continue
        nb = -(-n // block_size) if n else 0
        blocks = [free[r // rps].pop(0) for _ in range(nb)]
        bt[r, :nb] = blocks
        for t in range(n):
            ppos[blocks[t // block_size], t % block_size] = t
    return kp, vp, jnp.asarray(bt), jnp.asarray(ppos)


@needs_devices(2)
def test_sharded_paged_attention_matches_ref():
    from repro.kernels import ops, ref
    mesh = make_serve_mesh(2, 1)
    lens = [20, 9, 13, -1]                   # heterogeneous + inactive
    kp, vp, bt, ppos = _sharded_pool(lens, n_shards=2, bps=8, block_size=8,
                                     max_blocks=4, hkv=2, dh=16, key=KEY)
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 1, 8, 16))
    q_pos = jnp.asarray([19, 8, 12, -1], jnp.int32)
    got = ops.sharded_paged_attention(mesh, q, kp, vp, bt, ppos, q_pos)
    want = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos)
    np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(want)[:3],
                               atol=3e-5, rtol=1e-4)
    assert np.isfinite(np.asarray(got)).all()


@needs_devices(2)
def test_sharded_paged_prefill_attention_matches_ref():
    from repro.kernels import ops, ref
    mesh = make_serve_mesh(2, 1)
    lens = [20, 9, 13, 5]
    kp, vp, bt, ppos = _sharded_pool(lens, n_shards=2, bps=8, block_size=8,
                                     max_blocks=4, hkv=2, dh=16,
                                     key=jax.random.fold_in(KEY, 2))
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (4, 4, 8, 16))
    q_start = jnp.asarray([16, 5, 9, 1], jnp.int32)
    q_len = jnp.asarray([4, 4, 4, 3], jnp.int32)   # one bucket-padded row
    got = ops.sharded_paged_prefill_attention(mesh, q, kp, vp, bt, ppos,
                                              q_start, q_len)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, ppos, q_start,
                                           q_len)
    np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(want)[:3],
                               atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got)[3, :3],
                               np.asarray(want)[3, :3],
                               atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("rows", [[2], [1, 2, 3]])
def test_sharded_paged_kernels_replicated_rows_match_ref(rows):
    """Row subsets that do not split evenly over 'data' (the runtime's
    one-row prefill chunk): the rows replicate, every shard runs them
    against its own pages, and the psum keeps each row's owning shard.
    On four devices the mesh is (data=2, model=2), so heads split over
    'model' too; on one device the same path runs on a 1x1 mesh."""
    from repro.kernels import ops, ref
    data, model = (2, 2) if jax.device_count() >= 4 else (1, 1)
    mesh = make_serve_mesh(data, model)
    kp, vp, bt, ppos = _sharded_pool([20, 9, 13, 5], n_shards=data,
                                     bps=16 // data, block_size=8,
                                     max_blocks=4, hkv=2, dh=16,
                                     key=jax.random.fold_in(KEY, 4))
    sub = jnp.asarray(rows)
    bt = bt[sub]
    q = jax.random.normal(jax.random.fold_in(KEY, 5), (len(rows), 4, 8, 16))
    q_start = jnp.asarray([16, 5, 9, 1], jnp.int32)[sub]
    q_len = jnp.asarray([4, 4, 4, 3], jnp.int32)[sub]
    got = ops.sharded_paged_prefill_attention(
        mesh, q, kp, vp, bt, ppos, q_start, q_len, rows_sharded=False)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, ppos, q_start,
                                           q_len)
    for i, n in enumerate(np.asarray(q_len)):     # valid queries only
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   atol=3e-5, rtol=1e-4)
    q_pos = q_start + q_len - 1
    got = ops.sharded_paged_attention(mesh, q[:, :1], kp, vp, bt, ppos,
                                      q_pos, rows_sharded=False)
    want = ref.paged_attention_ref(q[:, :1], kp, vp, bt, ppos, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


# ------------------------------------- specs + validation (always run)

class FakeMesh:
    def __init__(self, **axes):
        self.shape = axes


def test_cache_specs_paged_layout():
    """Paged cache leaves: pages/ppos shard over 'data' on the blocks
    axis, block tables over 'data' on the rows axis, KV heads over
    'model' — including period-stacked leaves."""
    mesh = FakeMesh(data=2, model=2)
    cache = {
        "periods": [{"kp": jnp.zeros((3, 10, 8, 2, 16)),
                     "vp": jnp.zeros((3, 10, 8, 2, 16)),
                     "ppos": jnp.zeros((3, 10, 8)),
                     "bt": jnp.zeros((3, 4, 5))}],
        "tail": [{"kp": jnp.zeros((10, 8, 2, 16)),
                  "ppos": jnp.zeros((10, 8)),
                  "bt": jnp.zeros((4, 5))}],
    }
    specs = cache_specs(cache, mesh)
    assert specs["periods"][0]["kp"] == P(None, ("data",), None, "model",
                                          None)
    assert specs["periods"][0]["ppos"] == P(None, ("data",), None)
    assert specs["periods"][0]["bt"] == P(None, ("data",), None)
    assert specs["tail"][0]["kp"] == P(("data",), None, "model", None)
    assert specs["tail"][0]["ppos"] == P(("data",), None)
    assert specs["tail"][0]["bt"] == P(("data",), None)


def test_serve_mesh_validates_device_count():
    with pytest.raises(ValueError, match="devices"):
        make_serve_mesh(jax.device_count() + 1, 1)


def test_runtime_validates_shard_config(model):
    cfg, params = model
    # n_shards > 1 without a mesh is LOGICAL sharding (DESIGN.md §fault
    # tolerance): pool segments + shard-local scheduling on one device —
    # the substrate the kill-a-shard fuzz runs on — but rows must still
    # split evenly across shards
    rt = ServeRuntime(params, _sc(cfg, n_shards=2), 2)
    assert rt.pool.n_shards == 2 and rt.mesh is None
    with pytest.raises(ValueError, match="not divisible"):
        ServeRuntime(params, _sc(cfg, n_shards=2), 3)
    if jax.device_count() >= 2:
        # n_shards mismatch against the mesh data axis
        with pytest.raises(ValueError, match="n_shards"):
            ServeRuntime(params, _sc(cfg), 2, mesh=make_serve_mesh(2, 1))


def test_pool_blocks_divisibility_errors(model):
    cfg, _ = model
    with pytest.raises(ValueError, match="divisible"):
        _sc(cfg, n_shards=2, num_blocks=9).pool_blocks(4)
    with pytest.raises(ValueError, match="divisible"):
        _sc(cfg, n_shards=2).pool_blocks(3)
