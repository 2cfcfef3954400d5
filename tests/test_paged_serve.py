"""Paged serving end-to-end: layout equivalence (paged == ring greedy
generation), continuous-serving exactness at N=1, and the no-sibling-
re-prefill guarantee of the paged admission path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MuxSpec
from repro.configs import get_config
from repro.models import TransformerLM
from repro.serve import (ServeConfig, greedy_generate, make_pool,
                         init_cache, set_block_tables, prefill, decode_step)
from repro.launch.serve import run_continuous

KEY = jax.random.PRNGKey(0)


def make_model(mux_n=1, arch="qwen2-1.5b", capacity=48, **sc_kw):
    cfg = get_config(arch, reduced=True)
    mux = MuxSpec(n=mux_n)
    params = TransformerLM.init(KEY, cfg, mux)
    sc = ServeConfig(cfg=cfg, kind="lm", mux=mux, capacity=capacity,
                     dtype=jnp.float32, **sc_kw)
    return cfg, params, sc


@pytest.mark.parametrize("mux_n", [1, 2])
def test_paged_greedy_matches_ring(mux_n):
    cfg, params, ring = make_model(mux_n)
    paged = ServeConfig(cfg=cfg, kind="lm", mux=ring.mux, capacity=48,
                        dtype=jnp.float32, cache_layout="paged",
                        block_size=4)
    prompt = jax.random.randint(KEY, (2 * mux_n, 6), 4, cfg.vocab_size)
    g_ring = greedy_generate(params, ring, prompt, steps=4)
    g_paged = greedy_generate(params, paged, prompt, steps=4)
    np.testing.assert_array_equal(np.asarray(g_ring), np.asarray(g_paged))


def test_paged_decode_matches_full_forward_mux():
    """Prefill + paged decode (vector positions) == full forward."""
    cfg, params, sc = make_model(2, cache_layout="paged", block_size=4)
    toks = jax.random.randint(KEY, (4, 12), 4, cfg.vocab_size)
    pool = make_pool(sc, 4)
    cache = init_cache(sc, 4)
    for j in range(2):
        pool.allocate(j, 11)
    cache = set_block_tables(cache, pool.table_array(range(2)))
    lg_last, cache = prefill(params, sc, cache, toks[:, :11])
    for j in range(2):
        pool.append(j)
    cache = set_block_tables(cache, pool.table_array(range(2)))
    lg, cache = decode_step(params, sc, cache, toks[:, 11:],
                            jnp.asarray([11, 11]))
    full = TransformerLM.apply(params, cfg, toks, mux=sc.mux,
                               dtype=jnp.float32)["logits"]
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-4)
    np.testing.assert_allclose(np.asarray(lg_last),
                               np.asarray(full[:, -2]), atol=2e-4)


def test_continuous_paged_exact_at_n1():
    """With mux N=1, rows are independent: continuous paged serving with
    staggered arrivals must reproduce each request's solo greedy output
    (per-row block tables + per-row positions are exercised end to end)."""
    cfg, params, sc = make_model(1, cache_layout="paged", block_size=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, size=(l,)).astype(np.int32)
               for l in (5, 7, 6)]
    arrivals = [(0, prompts[0], 5), (2, prompts[1], 4), (4, prompts[2], 3)]
    stats = run_continuous(params, sc, 2, arrivals)
    assert len(stats["completed"]) == 3
    by_prompt = {tuple(r.prompt): r for r in stats["completed"]}
    for prompt, max_new in [(prompts[0], 5), (prompts[1], 4),
                            (prompts[2], 3)]:
        want = greedy_generate(params, sc, jnp.asarray(prompt)[None],
                               steps=max_new)[0]
        got = by_prompt[tuple(int(t) for t in prompt)].output
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_continuous_paged_never_reprefills_occupied_rows():
    """The paged admission path prefills exactly the joining row; rows
    occupied by live siblings never reappear in the prefill log, and
    prefill cost is the joining row's prompt length (not the grid)."""
    cfg, params, sc = make_model(2, cache_layout="paged", block_size=4)
    rng = np.random.default_rng(1)
    arrivals = [(i * 2, rng.integers(4, cfg.vocab_size,
                                     size=(6,)).astype(np.int32), 6)
                for i in range(5)]
    events = []

    def on_prefill(rows, backbone_tokens):
        events.append((rows, backbone_tokens))

    stats = run_continuous(params, sc, 2, arrivals, on_prefill=on_prefill)
    assert len(stats["completed"]) == 5
    # every prefill touches exactly one row and costs only that row's
    # prompt tokens — never the grid (ring admission costs rows * L_pad)
    for rows, toks in events:
        assert len(rows) == 1
        assert toks == 6              # one mux group's padded prompt length
    # 5 requests at N=2 need at least ceil(5/2) groups; each group is
    # prefilled exactly once (no re-prefill when siblings retire)
    assert 3 <= stats["prefill_events"] <= 5
    assert stats["prefill_events"] == len(events)
    assert stats["prefill_tokens"] == sum(t for _, t in events)


def test_continuous_paged_capacity_bound_heterogeneous_group():
    """Regression: a mux group with heterogeneous prompt lengths whose
    streams retire at the capacity bound (max_new effectively unbounded)
    must drain cleanly — the short-prompt stream's position is aligned to
    the padded group length at admission, so the row's physical length
    can never outgrow the pool's per-sequence block cap."""
    cfg, params, sc = make_model(2, capacity=24, cache_layout="paged",
                                 block_size=8)
    rng = np.random.default_rng(3)
    arrivals = [
        (0, rng.integers(4, cfg.vocab_size, size=(16,)).astype(np.int32),
         100),
        (0, rng.integers(4, cfg.vocab_size, size=(6,)).astype(np.int32),
         100)]
    stats = run_continuous(params, sc, 1, arrivals)   # one row: forced group
    assert len(stats["completed"]) == 2               # no PoolExhausted
    # both streams were capacity-retired from the padded length 16
    assert all(len(r.output) == 24 - 16 for r in stats["completed"])
    assert stats["pool"].n_used_blocks == 0


def test_admit_paged_aligns_stream_positions_to_group_pad():
    from repro.serve.scheduler import ContinuousScheduler
    from repro.serve.batcher import Request
    s = ContinuousScheduler(n_mux=2, backbone_batch=1, max_len=64)
    s.submit(Request(uid=0, prompt=list(range(9)), max_new=4))
    s.submit(Request(uid=1, prompt=list(range(3)), max_new=4))
    s.admit_paged()
    assert s.slots[0][0].pos == 9 and s.slots[0][1].pos == 9
    assert s.slots[0][1].prompt_len == 3              # true length kept


def test_continuous_paged_backpressure_on_undersized_pool():
    """An undersized pool must not crash the serve loop: admission that
    can't get blocks re-queues the group and retries after rows drain.
    An impossible request (can never fit even an empty pool) raises a
    clear PoolExhausted instead of spinning."""
    from repro.serve import PoolExhausted
    cfg, params, _ = make_model(1)
    # room for exactly one row at a time: 2 blocks of 8 = 16 tokens
    sc = ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1), capacity=16,
                     dtype=jnp.float32, cache_layout="paged",
                     block_size=8, num_blocks=3)
    rng = np.random.default_rng(4)
    mk = lambda l: rng.integers(4, cfg.vocab_size, size=(l,)).astype(np.int32)
    # each request needs both blocks (12 prompt + 4 generated = 16): the
    # second admission hits PoolExhausted, requeues, and is served after
    # the first drains — REUSING the first request's freed blocks, which
    # also regression-tests the stale-position reset (contaminated blocks
    # would corrupt the second request's logits)
    prompts = [mk(12), mk(12)]
    stats = run_continuous(params, sc, 2,
                           [(0, prompts[0], 4), (0, prompts[1], 4)])
    assert len(stats["completed"]) == 2          # served sequentially
    assert stats["pool"].n_used_blocks == 0
    by_prompt = {tuple(r.prompt): r for r in stats["completed"]}
    for p in prompts:
        want = greedy_generate(params, sc, jnp.asarray(p)[None], steps=4)[0]
        got = by_prompt[tuple(int(t) for t in p)].output
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(PoolExhausted):
        run_continuous(params, sc, 2, [(0, mk(17), 4)])   # > per-seq cap


def test_admit_exhaustion_triggers_cancel_admit_not_corruption():
    """Driving the runtime directly: an admission the pool cannot fund is
    rolled back via cancel_admit — the request returns to the queue, the
    row's slots and prefill bookkeeping are cleared, the pool's
    invariants hold (nothing leaked) — and the group is served correctly
    once the blocking row drains."""
    from repro.serve import Request
    from repro.serve.runtime import ServeRuntime
    cfg, params, _ = make_model(1)
    sc = ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1), capacity=16,
                     dtype=jnp.float32, cache_layout="paged",
                     block_size=8, num_blocks=3)     # one row at a time
    rt = ServeRuntime(params, sc, 2, chunk=8)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(4, cfg.vocab_size, size=(12,)).astype(np.int32)
               for _ in range(2)]
    rt.submit(Request(uid=0, prompt=[int(t) for t in prompts[0]],
                      max_new=4))
    rt.submit(Request(uid=1, prompt=[int(t) for t in prompts[1]],
                      max_new=4))
    rt.step()
    # request 0 admitted (2 blocks), request 1's admission rolled back
    assert len(rt.sched.queue) == 1
    assert rt.sched.queue[0].uid == 1
    assert rt.sched.queue[0].output == []            # untouched by rollback
    assert 0 in rt.row_len and 1 not in rt.row_len   # only row 0 funded
    assert 1 not in rt.sched.prefill_progress        # rollback cleared it
    assert not any(s.request is not None and s.request.uid == 1
                   for row in rt.sched.slots for s in row)
    rt.pool.check_invariants()
    while rt.has_work():
        rt.step()
    assert len(rt.stats["completed"]) == 2
    assert rt.pool.n_used_blocks == 0
    rt.pool.check_invariants()
    by_uid = {r.uid: r.output for r in rt.stats["completed"]}
    for i, p in enumerate(prompts):
        want = greedy_generate(params, sc, jnp.asarray(p)[None], steps=4)[0]
        np.testing.assert_array_equal(np.asarray(by_uid[i]),
                                      np.asarray(want))


def test_continuous_paged_preempts_on_append_exhaustion():
    """A row whose mid-decode block append exhausts the pool is
    preempted (blocks freed, requests requeued) and later resumed from
    prompt + generated-so-far — with N=1 the final outputs must still
    match each request's solo greedy generation exactly."""
    cfg, params, _ = make_model(1)
    # 3 allocatable blocks of 4: row A (prompt 7 -> 2 blocks) + row B
    # (prompt 4 -> 1 block) fill the pool; B's growth at token 5
    # triggers preemption
    sc = ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1), capacity=12,
                     dtype=jnp.float32, cache_layout="paged",
                     block_size=4, num_blocks=4)
    rng = np.random.default_rng(5)
    pa = rng.integers(4, cfg.vocab_size, size=(7,)).astype(np.int32)
    pb = rng.integers(4, cfg.vocab_size, size=(4,)).astype(np.int32)
    stats = run_continuous(params, sc, 2, [(0, pa, 3), (0, pb, 6)])
    assert len(stats["completed"]) == 2
    assert stats["pool"].n_used_blocks == 0
    # the preempted row really was re-prefilled (admission, admission,
    # resumption-with-generated-tokens)
    assert stats["prefill_events"] == 3
    by_prompt = {tuple(r.prompt): r for r in stats["completed"]}
    for p, max_new in [(pa, 3), (pb, 6)]:
        want = greedy_generate(params, sc, jnp.asarray(p)[None],
                               steps=max_new)[0]
        got = by_prompt[tuple(int(t) for t in p)].output
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_continuous_paged_simultaneous_preemption_recovers():
    """Two rows crossing a block boundary in the same decode step both
    preempt; neither alone outgrew the pool, so the loop must requeue
    and serve them sequentially (exactly), not raise PoolExhausted."""
    cfg, params, _ = make_model(1)
    sc = ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1), capacity=12,
                     dtype=jnp.float32, cache_layout="paged",
                     block_size=4, num_blocks=5)   # 4 allocatable blocks
    rng = np.random.default_rng(6)
    prompts = [rng.integers(4, cfg.vocab_size, size=(8,)).astype(np.int32)
               for _ in range(2)]                  # 2 blocks each: pool full
    stats = run_continuous(params, sc, 2,
                           [(0, prompts[0], 4), (0, prompts[1], 4)])
    assert len(stats["completed"]) == 2
    assert stats["pool"].n_used_blocks == 0
    by_prompt = {tuple(r.prompt): r for r in stats["completed"]}
    for p in prompts:
        want = greedy_generate(params, sc, jnp.asarray(p)[None], steps=4)[0]
        got = by_prompt[tuple(int(t) for t in p)].output
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_continuous_ring_never_wraps_physical_positions():
    """Padding gaps let the ring arm's physical write position outrun
    logical lengths; the loop must compact (grid re-prefill) before the
    ring buffer would wrap over live context."""
    cfg, params, _ = make_model(1, capacity=16)
    sc = ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1), capacity=16,
                     dtype=jnp.float32)
    rng = np.random.default_rng(7)
    arrivals = [
        (0, rng.integers(4, cfg.vocab_size, size=(4,)).astype(np.int32), 8),
        (2, rng.integers(4, cfg.vocab_size, size=(14,)).astype(np.int32), 8)]
    stats = run_continuous(params, sc, 2, arrivals)
    assert len(stats["completed"]) == 2
    assert stats.get("max_grid_pos", 0) <= sc.capacity


def test_continuous_ring_vs_paged_prefill_cost():
    """Same trace: the ring layout re-prefills the grid on admission, the
    paged layout only the joining rows — strictly fewer backbone tokens."""
    cfg, params, ring = make_model(2)
    paged = ServeConfig(cfg=cfg, kind="lm", mux=ring.mux, capacity=48,
                        dtype=jnp.float32, cache_layout="paged",
                        block_size=4)
    rng = np.random.default_rng(2)
    arrivals = [(i * 3, rng.integers(4, cfg.vocab_size,
                                     size=(5,)).astype(np.int32), 4)
                for i in range(4)]
    s_ring = run_continuous(params, ring, 2,
                            [(t, p.copy(), m) for t, p, m in arrivals])
    s_paged = run_continuous(params, paged, 2,
                             [(t, p.copy(), m) for t, p, m in arrivals])
    assert len(s_ring["completed"]) == len(s_paged["completed"]) == 4
    assert s_paged["prefill_tokens"] < s_ring["prefill_tokens"]
    # paged: blocks all returned to the pool at drain
    assert s_paged["pool"].n_used_blocks == 0


def test_set_block_tables_gives_each_layer_its_own_buffer():
    """The jitted serve steps donate the cache pytree, and one buffer
    behind two leaves cannot be donated twice: every unstacked paged
    layer must get a table buffer of its own."""
    cache = {"periods": (),
             "tail": tuple({"bt": jnp.full((2, 3), -1, jnp.int32)}
                           for _ in range(2))}
    cache = set_block_tables(cache, np.arange(6, dtype=np.int32)
                             .reshape(2, 3))
    out = jax.jit(lambda c: c, donate_argnums=0)(cache)
    for layer in out["tail"]:
        np.testing.assert_array_equal(np.asarray(layer["bt"]),
                                      np.arange(6).reshape(2, 3))
