"""Differential churn fuzz over the continuous-serving arms.

Random admit / prefill-chunk / free / preempt schedules (arrival step,
prompt length, generation budget, pool pressure) are served through:

  * ring            — grid re-prefill on every composition change;
  * paged-blocking  — whole-prompt prefill at admission;
  * paged-chunked   — fixed-size chunks interleaved with decode;
  * mesh-sharded    — paged-chunked on a ('data', 'model') device mesh
                      (degenerates to (1, 1) on a single-device run; the
                      devices=8 CI job exercises real shards via
                      REPRO_TEST_DEVICES);
  * width lanes     — SLO-routed lanes at mux widths 1/4/8
                      (``run_continuous(lanes=...)``): each lane's
                      routed sub-schedule must be token-identical to a
                      fixed-width run at that lane's N, with compile
                      counts of 1 decode + one per bucket per width;
  * telemetry       — paged-chunked with a live ``serve.telemetry``
                      session, plain and with profiler annotations:
                      token- and compile-count-identical to the
                      uninstrumented run (observability must add no
                      host syncs and no jit inputs);
  * quantized KV    — paged-chunked with int8 pages + fused-dequant
                      kernels (``ServeConfig(kv_dtype='int8')``): same
                      churn schedules as the bf16-page arm, greedy
                      agreement >= 99% of generated tokens, compile
                      counts unchanged (quantization adds no buckets);
  * disaggregated   — a prefill-only + decode-only lane pair
                      (``LaneSpec(role=...)``): finished rows migrate
                      their KV pages to the decode lane and resume from
                      the already-sampled token — token-identical to
                      the single-lane chunked arm with zero re-prefill
                      on the decode lane and per-role compile counts
                      (prefill lane: buckets only; decode lane: decode
                      only), under plain, pool-budget, decode-lane
                      shard-kill and goodput-routing schedules.

All paged arms must emit token-identical greedy streams per request, and
each stream must equal its solo ``greedy_generate`` output.  The ring
arm's padded grid rebuild position-shifts heterogeneous rows (DESIGN.md
§ring), so its exactness is asserted on *aligned* schedules (simultaneous
equal-length arrivals — the only schedules where ring is exact by
construction); on arbitrary schedules it must still complete every
request with the right stream lengths.

Property variants run under hypothesis when installed and skip cleanly
otherwise (tests/hypothesis_stub.py); the deterministic seed sweeps
below them always run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from hypothesis_stub import given, settings, st

from repro.core import MuxSpec
from repro.configs import get_config
from repro.models import TransformerLM
from repro.serve import ServeConfig, greedy_generate
from repro.serve.router import LaneSpec, SLO_CLASSES
from repro.serve.telemetry import Telemetry
from repro.launch.mesh import make_serve_mesh
from repro.launch.serve import run_continuous

KEY = jax.random.PRNGKey(0)
ROWS = 2
CAPACITY = 20          # every schedule keeps prompt + max_new <= capacity
BLOCK = 4


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-1.5b", reduced=True)
    params = TransformerLM.init(KEY, cfg, MuxSpec(n=1))
    return cfg, params


def _paged_sc(cfg, *, n_shards=1, num_blocks=None):
    return ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1),
                       capacity=CAPACITY, dtype=jnp.float32,
                       cache_layout="paged", block_size=BLOCK,
                       num_blocks=num_blocks, n_shards=n_shards)


def _ring_sc(cfg):
    return ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1),
                       capacity=CAPACITY, dtype=jnp.float32)


def _schedule(cfg, seed, *, aligned=False, n_req=None):
    """Derive a churn schedule from one integer seed: arrivals of
    (step, prompt, max_new).  aligned: simultaneous equal-length
    arrivals (the schedules where the ring arm is exact)."""
    rng = np.random.default_rng(seed)
    n = int(n_req if n_req is not None else rng.integers(2, 5))
    if aligned:
        n = min(n, ROWS)
        length = int(rng.integers(2, 13))
        steps = [0] * n
        lens = [length] * n
    else:
        steps = sorted(int(rng.integers(0, 10)) for _ in range(n))
        lens = [int(rng.integers(1, 13)) for _ in range(n)]
    return [(s, rng.integers(4, cfg.vocab_size,
                             size=(l,)).astype(np.int32),
             int(rng.integers(1, min(6, CAPACITY - l + 1))))
            for s, l in zip(steps, lens)]


def _run_arm(params, sc, arrivals, **kw):
    """Serve a copy of the schedule; returns uid -> (prompt, output)."""
    stats = run_continuous(params, sc, ROWS,
                           [(t, p.copy(), m) for t, p, m in arrivals],
                           **kw)
    out = {r.uid: (tuple(r.prompt), list(r.output))
           for r in stats["completed"]}
    assert len(out) == len(arrivals), "arm dropped requests"
    if "pool" in stats:
        assert stats["pool"].n_used_blocks == 0
        stats["pool"].check_invariants()
    return out


def _mesh_arm():
    """Largest usable (data, model) serve mesh on this run: real shards
    under REPRO_TEST_DEVICES / the devices=8 CI job, (1, 1) otherwise."""
    nd = jax.device_count()
    data = 2 if nd >= 2 and ROWS % 2 == 0 else 1
    model_ax = 2 if nd >= 2 * data else 1
    return make_serve_mesh(data, model_ax), data


def _check_paged_arms(cfg, params, arrivals):
    """paged-blocking == paged-chunked == mesh-sharded == solo greedy."""
    chunked = _run_arm(params, _paged_sc(cfg), arrivals, chunk=4)
    blocking = _run_arm(params, _paged_sc(cfg), arrivals,
                        prefill_mode="blocking")
    mesh, data = _mesh_arm()
    meshed = _run_arm(params, _paged_sc(cfg, n_shards=data), arrivals,
                      chunk=4, mesh=mesh)
    assert chunked == blocking == meshed
    sc1 = _paged_sc(cfg)
    for uid, (_, prompt, max_new) in enumerate(arrivals):
        want = greedy_generate(params, sc1, jnp.asarray(prompt)[None],
                               steps=max_new)[0]
        got = chunked[uid][1]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return chunked


def _fuzz_once(cfg, params, seed):
    arrivals = _schedule(cfg, seed)
    paged = _check_paged_arms(cfg, params, arrivals)
    for uid, (_, _, max_new) in enumerate(arrivals):
        assert len(paged[uid][1]) == max_new
    # ring liveness on arbitrary schedules: every request completes with
    # a non-empty stream (the padded grid rebuild may position-shift a
    # row into early max_len retirement, so exact lengths/tokens are
    # only asserted on aligned schedules — DESIGN.md §ring)
    ring = _run_arm(params, _ring_sc(cfg), arrivals)
    for uid, (_, _, max_new) in enumerate(arrivals):
        assert 1 <= len(ring[uid][1]) <= max_new


def _fuzz_aligned_once(cfg, params, seed):
    """Aligned schedules: ALL FOUR arms token-identical per request."""
    arrivals = _schedule(cfg, seed, aligned=True)
    paged = _check_paged_arms(cfg, params, arrivals)
    ring = _run_arm(params, _ring_sc(cfg), arrivals)
    assert ring == paged


def _fuzz_pressure_once(cfg, params, seed):
    """Undersized pool: admissions roll back (cancel_admit) and decode
    growth preempts; paged-blocking == paged-chunked == solo greedy
    through arbitrary requeue/resume interleavings."""
    arrivals = _schedule(cfg, seed, n_req=3)
    # 7 allocatable blocks < 2 rows x 5-block per-seq cap: contention,
    # while any single row (<= 5 blocks) always fits an empty pool
    sc = lambda: _paged_sc(cfg, num_blocks=8)
    chunked = _run_arm(params, sc(), arrivals, chunk=4)
    blocking = _run_arm(params, sc(), arrivals, prefill_mode="blocking")
    assert chunked == blocking
    for uid, (_, prompt, max_new) in enumerate(arrivals):
        want = greedy_generate(params, sc(), jnp.asarray(prompt)[None],
                               steps=max_new)[0]
        np.testing.assert_array_equal(np.asarray(chunked[uid][1]),
                                      np.asarray(want))


def _fuzz_telemetry_once(cfg, params, seed):
    """Telemetry-parity arm (DESIGN.md §observability): serving the same
    schedule with a live ``Telemetry`` must be token-identical AND
    compile-count-identical to the uninstrumented run — instrumentation
    adds no host syncs, no jit inputs, no recompiles.  The instrumented
    run's metrics must also agree with the runtime's own stats."""
    arrivals = _schedule(cfg, seed)

    def arm(telemetry=None):
        stats = run_continuous(params, _paged_sc(cfg), ROWS,
                               [(t, p.copy(), m) for t, p, m in arrivals],
                               chunk=4, telemetry=telemetry)
        tokens = {r.uid: (tuple(r.prompt), list(r.output))
                  for r in stats["completed"]}
        assert len(tokens) == len(arrivals)
        return tokens, dict(stats["trace_counts"]), stats

    base_tokens, base_traces, _ = arm()
    # annotated: every span and instant also enters a jax.profiler
    # TraceAnnotation, which must not perturb serving either
    ann_tokens, ann_traces, _ = arm(Telemetry(annotate=True))
    assert ann_tokens == base_tokens, "annotation changed the token streams"
    assert ann_traces == base_traces, "annotation changed the compile counts"
    tele = Telemetry(snapshot_every=2)
    tokens, traces, stats = arm(tele)
    assert tokens == base_tokens, "telemetry changed the token streams"
    assert traces == base_traces, "telemetry changed the compile counts"
    reg = tele.registry
    generated = sum(len(out) for _, out in tokens.values())
    assert reg.value("tokens_generated", lane=0) == generated
    assert reg.value("requests_completed", lane=0) == len(arrivals)
    assert (reg.hist("decode_step_s", lane=0, shard=0).count
            == stats["decode_steps"])
    assert reg.hist("ttft_s", lane=0).count == len(arrivals)
    # lifecycle stamps stay ordered through churn/preemption
    for r in stats["completed"]:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    # the periodic snapshots and exports stay schema-valid
    assert tele.snapshots and all("step" in s for s in tele.snapshots)
    phs = {e["ph"] for e in tele.tracer.chrome_trace()["traceEvents"]}
    assert phs <= {"X", "i", "M"}


def _fuzz_kill_shard_once(cfg, params, seed):
    """Kill-a-shard arm (DESIGN.md §fault tolerance): killing a data
    shard mid-run must leave every stream token-identical to the
    undisturbed 2-shard run — survivors untouched, the dead shard's
    streams replayed to completion on surviving shards from host token
    logs — with the dead shard's pool segment drained and compile
    counts unchanged (no reshape, no re-trace)."""
    arrivals = _schedule(cfg, seed)
    base = _run_arm(params, _paged_sc(cfg, n_shards=2), arrivals, chunk=4)
    stats = run_continuous(params, _paged_sc(cfg, n_shards=2), ROWS,
                           [(t, p.copy(), m) for t, p, m in arrivals],
                           chunk=4,
                           events=[{"step": 4, "op": "kill_shard",
                                    "shard": 1}])
    killed = {r.uid: (tuple(r.prompt), list(r.output))
              for r in stats["completed"]}
    assert len(killed) == len(arrivals), "kill-shard arm dropped requests"
    assert killed == base, "kill-shard arm diverged from undisturbed run"
    # solo-greedy exactness survives the kill (replay re-prefills the
    # full host token log, so each stream continues exactly)
    sc1 = _paged_sc(cfg)
    for uid, (_, prompt, max_new) in enumerate(arrivals):
        want = greedy_generate(params, sc1, jnp.asarray(prompt)[None],
                               steps=max_new)[0]
        np.testing.assert_array_equal(np.asarray(killed[uid][1]),
                                      np.asarray(want))
    pool = stats["pool"]
    assert pool.dead_shards == {1}
    assert pool.n_used_blocks == 0
    pool.check_invariants()
    rec = stats["recovery"]
    assert rec["shards_killed"] == 1
    assert (len(rec["recovery_latency_s"]) == rec["requests_replayed"])
    assert all(v == 1 for v in stats["trace_counts"].values())


def _fuzz_restart_once(cfg, params, seed, ckpt_dir):
    """Hot-restart arm (DESIGN.md §fault tolerance): snapshotting the
    full serving state mid-run, rebuilding the runtime and restoring
    must be invisible in the token streams — restored rows resume
    decode with no re-prefill (a restart costs a re-jit, nothing
    else)."""
    arrivals = _schedule(cfg, seed)
    base = _run_arm(params, _paged_sc(cfg), arrivals, chunk=4)
    stats = run_continuous(params, _paged_sc(cfg), ROWS,
                           [(t, p.copy(), m) for t, p, m in arrivals],
                           chunk=4, ckpt_dir=ckpt_dir,
                           events=[{"step": 6, "op": "restart"}])
    got = {r.uid: (tuple(r.prompt), list(r.output))
           for r in stats["completed"]}
    assert got == base, "restart arm diverged from undisturbed run"
    assert stats["recovery"]["restarts"] == 1
    assert stats["pool"].n_used_blocks == 0
    stats["pool"].check_invariants()


def _paged_sc_kv(cfg, kv_dtype):
    return ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=1),
                       capacity=CAPACITY, dtype=jnp.float32,
                       cache_layout="paged", block_size=BLOCK,
                       kv_dtype=kv_dtype)


def _fuzz_quantized_once(cfg, params, seed):
    """Quantized-KV arm (DESIGN.md §quantized pages): int8 pages with the
    fused-dequant kernels, same churn schedule as the bf16-page arm.
    Greedy agreement >= 99% of generated tokens (quantization noise may
    flip a rare near-tie, never the stream shape) and compile counts
    unchanged — the quantized pool adds no jit inputs and no buckets."""
    arrivals = _schedule(cfg, seed)

    def arm(kv_dtype):
        stats = run_continuous(params, _paged_sc_kv(cfg, kv_dtype), ROWS,
                               [(t, p.copy(), m) for t, p, m in arrivals],
                               chunk=4, use_kernels=True)
        tokens = {r.uid: (tuple(r.prompt), list(r.output))
                  for r in stats["completed"]}
        assert len(tokens) == len(arrivals), f"{kv_dtype} arm dropped"
        assert stats["pool"].n_used_blocks == 0
        return tokens, dict(stats["trace_counts"])

    base_tokens, base_traces = arm("bf16")
    q_tokens, q_traces = arm("int8")
    assert q_traces == base_traces, "quantization changed compile counts"
    total = agree = 0
    for uid, (prompt, out) in base_tokens.items():
        q_prompt, q_out = q_tokens[uid]
        assert q_prompt == prompt and len(q_out) == len(out)
        total += len(out)
        agree += sum(int(a == b) for a, b in zip(out, q_out))
    assert total and agree / total >= 0.99, (
        f"int8 greedy agreement {agree}/{total} below 99%")


def _run_disagg(cfg, params, arrivals, *, n_shards=1, pool_budget=None,
                events=None, route="load"):
    """Serve the schedule through a prefill-only + decode-only lane pair
    at width 1 (DESIGN.md §disaggregated); returns (uid -> tokens,
    stats) after asserting the disaggregation contract: the prefill
    lane never decodes, the decode lane never prefills (migrated rows
    resume from their already-sampled token — zero re-prefill), both
    lanes keep per-width compile counts, and the pools drain clean."""
    lanes = (LaneSpec(n_mux=1, rows=ROWS, chunk=4, role="prefill"),
             LaneSpec(n_mux=1, rows=ROWS, chunk=4, role="decode"))
    stats = run_continuous({1: params}, _paged_sc(cfg, n_shards=n_shards),
                           ROWS, [(t, p.copy(), m) for t, p, m in arrivals],
                           chunk=4, lanes=lanes, pool_budget=pool_budget,
                           events=events, route=route)
    out = {r.uid: (tuple(r.prompt), list(r.output))
           for r in stats["completed"]}
    assert len(out) == len(arrivals), "disagg arm dropped requests"
    for pool in stats["pools"]:
        assert pool.n_used_blocks == 0
        pool.check_invariants()
    pre, dec = stats["lanes"]
    assert pre["role"] == "prefill" and dec["role"] == "decode"
    # phase separation: the prefill lane never ran a decode step, the
    # decode lane never prefilled — every migrated row resumed decoding
    # from the token the prefill lane already sampled (zero re-prefill)
    assert pre["decode_steps"] == 0, "prefill lane ran decode"
    assert dec["prefill_events"] == 0, "decode lane re-prefilled"
    assert dec["prefill_tokens"] == 0
    # compile-once per role: prefill lane traces only prefill buckets,
    # decode lane only its decode step, each exactly once
    assert all(k.startswith("prefill_") for k in pre["trace_counts"]), (
        f"prefill lane traced {pre['trace_counts']}")
    served = bool(dec["completed"])
    assert dict(dec["trace_counts"]) == ({"decode": 1} if served else {}), (
        f"decode lane traced {dec['trace_counts']}")
    assert all(v == 1 for v in pre["trace_counts"].values())
    rec = stats["recovery"]
    assert rec["handoffs"] == pre["handoffs_out"] == dec["handoffs_in"]
    assert rec["migrated_kv_bytes"] == pre["migrated_bytes"]
    if rec["handoffs"]:
        assert rec["migrated_kv_bytes"] > 0
    return out, stats


def _fuzz_disagg_once(cfg, params, seed):
    """Disaggregated arm: prefill→migrate→decode must be token-identical
    to the single-lane chunked arm and to solo greedy, with every
    stream needing >= 2 tokens handed off exactly once (max_new == 1
    streams finish on the prefill lane and never migrate)."""
    arrivals = _schedule(cfg, seed)
    base = _run_arm(params, _paged_sc(cfg), arrivals, chunk=4)
    got, stats = _run_disagg(cfg, params, arrivals)
    assert got == base, "disagg arm diverged from single-lane chunked"
    sc1 = _paged_sc(cfg)
    for uid, (_, prompt, max_new) in enumerate(arrivals):
        want = greedy_generate(params, sc1, jnp.asarray(prompt)[None],
                               steps=max_new)[0]
        np.testing.assert_array_equal(np.asarray(got[uid][1]),
                                      np.asarray(want))
    # width-1 lanes: one stream per row, so handoffs == streams that
    # outlive their prefill-lane first token
    need_decode = sum(1 for _, _, m in arrivals if m >= 2)
    assert stats["recovery"]["handoff_streams"] == need_decode


def _fuzz_disagg_pressure_once(cfg, params, seed):
    """Disaggregated arm under a shared block budget: admission
    rollbacks on the prefill lane and handoff deferrals (decode pool
    momentarily full → the row parks and retries) must not change a
    single token."""
    arrivals = _schedule(cfg, seed, n_req=3)
    base = _run_arm(params, _paged_sc(cfg), arrivals, chunk=4)
    got, _ = _run_disagg(cfg, params, arrivals, pool_budget=20)
    assert got == base, "budget-pressure disagg arm diverged"


def _fuzz_disagg_kill_shard_once(cfg, params, seed):
    """Disaggregated arm with a decode-lane shard kill: the dead
    shard's rows bounce back through the router to the prefill lane,
    replay from host token logs, and hand off again — token-identical
    to the undisturbed run, with the decode lane still never running a
    prefill itself (replay prefills happen on the prefill lane)."""
    arrivals = _schedule(cfg, seed)
    base = _run_arm(params, _paged_sc(cfg), arrivals, chunk=4)
    got, stats = _run_disagg(cfg, params, arrivals, n_shards=2,
                             events=[{"step": 4, "op": "kill_shard",
                                      "shard": 1, "lane": 1}])
    assert got == base, "kill-shard disagg arm diverged"
    assert stats["pools"][1].dead_shards == {1}
    assert stats["recovery"]["shards_killed"] == 1


def _fuzz_disagg_goodput_once(cfg, params, seed):
    """Goodput routing must be a pure candidate re-ordering: with one
    prefill lane and one decode lane the routed sets are forced, so
    the goodput-mode run is token-identical to load-mode."""
    arrivals = _schedule(cfg, seed)
    load, _ = _run_disagg(cfg, params, arrivals, route="load")
    goodput, _ = _run_disagg(cfg, params, arrivals, route="goodput")
    assert goodput == load, "goodput routing changed the token streams"


LANE_WIDTHS = (1, 4, 8)


@pytest.fixture(scope="module")
def lane_models():
    cfg = get_config("qwen2-1.5b", reduced=True)
    params = {w: TransformerLM.init(jax.random.fold_in(KEY, w), cfg,
                                    MuxSpec(n=w)) for w in LANE_WIDTHS}
    return cfg, params


def _paged_sc_width(cfg, w):
    return ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=w),
                       capacity=CAPACITY, dtype=jnp.float32,
                       cache_layout="paged", block_size=BLOCK)


def _fuzz_lanes_once(cfg, params_by_width, seed):
    """Lane parity (DESIGN.md §width lanes): serve a random churn
    schedule with mixed SLO classes through lanes at widths 1/4/8, then
    replay each lane's routed sub-schedule through a fixed-width
    ``ServeRuntime`` at that lane's N — every request's tokens must be
    identical, and compile counts must stay 1 decode + one per used
    bucket *per width*."""
    arrivals = _schedule(cfg, seed)
    rng = np.random.default_rng(seed + 99)
    lane_arrivals = [(t, p.copy(), m, None, str(rng.choice(SLO_CLASSES)))
                     for t, p, m in arrivals]
    stats = run_continuous(params_by_width, _paged_sc(cfg), ROWS,
                           lane_arrivals, chunk=4, lanes=LANE_WIDTHS)
    assert len(stats["completed"]) == len(arrivals), "lanes dropped requests"
    for pool in stats["pools"]:
        assert pool.n_used_blocks == 0
        pool.check_invariants()
    for ls in stats["lanes"]:
        # compile-once per width: a lane that served anything traced its
        # decode step exactly once, and each bucket at most once
        # (_run_lanes also runs check_compile_once before returning)
        served = bool(ls["completed"])
        assert ls["trace_counts"].get("decode", 0) == int(served)
        assert all(v == 1 for v in ls["trace_counts"].values())
        if not served:
            continue
        routed = sorted(ls["completed"], key=lambda r: r.uid)
        assert all(r.lane == ls["lane"] for r in routed)
        sub = [(r.routed_step, np.asarray(r.prompt, np.int32), r.max_new)
               for r in routed]
        fixed = _run_arm(params_by_width[ls["n_mux"]],
                         _paged_sc_width(cfg, ls["n_mux"]), sub, chunk=4)
        for i, r in enumerate(routed):
            assert fixed[i] == (tuple(r.prompt), list(r.output)), (
                f"lane {ls['lane']} (N={ls['n_mux']}) diverged from the "
                f"fixed-width run for uid {r.uid}")


def _fuzz_lane_resize_once(cfg, params_by_width, seed):
    """Live-resize arm (DESIGN.md §fault tolerance): drain a lane
    mid-run (queued work re-routes, placed streams finish where they
    are) and add a lane at a new width under traffic — no stream
    dropped, and every lane that ever served (the retired one included)
    stays token-identical to a fixed-width replay of its routed
    sub-schedule, with compile counts of 1 decode + one per bucket per
    width."""
    arrivals = _schedule(cfg, seed)
    rng = np.random.default_rng(seed + 99)
    lane_arrivals = [(t, p.copy(), m, None, str(rng.choice(SLO_CLASSES)))
                     for t, p, m in arrivals]
    stats = run_continuous(params_by_width, _paged_sc(cfg), ROWS,
                           lane_arrivals, chunk=4, lanes=(1, 4),
                           events=[{"step": 3, "op": "drain_lane",
                                    "width": 4},
                                   {"step": 6, "op": "add_lane",
                                    "width": 8}])
    assert len(stats["completed"]) == len(arrivals), (
        "resize dropped requests")
    rec = stats["recovery"]
    assert rec["lane_drains"] == 1 and rec["lane_adds"] == 1
    assert rec["lanes_retired"] == 1
    for pool in stats["pools"]:
        assert pool.n_used_blocks == 0
        pool.check_invariants()
    for ls in stats["lanes"]:
        served = bool(ls["completed"])
        assert ls["trace_counts"].get("decode", 0) == int(served)
        assert all(v == 1 for v in ls["trace_counts"].values())
        if not served:
            continue
        routed = sorted(ls["completed"], key=lambda r: r.uid)
        assert all(r.lane == ls["lane"] for r in routed)
        sub = [(r.routed_step, np.asarray(r.prompt, np.int32), r.max_new)
               for r in routed]
        fixed = _run_arm(params_by_width[ls["n_mux"]],
                         _paged_sc_width(cfg, ls["n_mux"]), sub, chunk=4)
        for i, r in enumerate(routed):
            assert fixed[i] == (tuple(r.prompt), list(r.output)), (
                f"lane {ls['lane']} (N={ls['n_mux']}) diverged from the "
                f"fixed-width run for uid {r.uid} across the resize")


# ------------------------------------------------- deterministic sweeps

@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_churn_deterministic(model, seed):
    cfg, params = model
    _fuzz_once(cfg, params, seed)


def test_fuzz_aligned_deterministic(model):
    cfg, params = model
    _fuzz_aligned_once(cfg, params, 2)


def test_fuzz_pool_pressure_deterministic(model):
    cfg, params = model
    _fuzz_pressure_once(cfg, params, 3)


@pytest.mark.parametrize("seed", [0, 4])
def test_fuzz_telemetry_parity_deterministic(model, seed):
    cfg, params = model
    _fuzz_telemetry_once(cfg, params, seed)


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_lane_parity_deterministic(lane_models, seed):
    cfg, params_by_width = lane_models
    _fuzz_lanes_once(cfg, params_by_width, seed)


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_kill_shard_deterministic(model, seed):
    cfg, params = model
    _fuzz_kill_shard_once(cfg, params, seed)


def test_fuzz_restart_deterministic(model, tmp_path):
    cfg, params = model
    _fuzz_restart_once(cfg, params, 5, str(tmp_path / "ckpt"))


def test_fuzz_lane_resize_deterministic(lane_models):
    cfg, params_by_width = lane_models
    _fuzz_lane_resize_once(cfg, params_by_width, 0)


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_quantized_kv_deterministic(model, seed):
    cfg, params = model
    _fuzz_quantized_once(cfg, params, seed)


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_disagg_deterministic(model, seed):
    cfg, params = model
    _fuzz_disagg_once(cfg, params, seed)


def test_fuzz_disagg_pressure_deterministic(model):
    cfg, params = model
    _fuzz_disagg_pressure_once(cfg, params, 3)


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_disagg_kill_shard_deterministic(model, seed):
    cfg, params = model
    _fuzz_disagg_kill_shard_once(cfg, params, seed)


def test_fuzz_disagg_goodput_deterministic(model):
    cfg, params = model
    _fuzz_disagg_goodput_once(cfg, params, 0)


# ------------------------------------------------- hypothesis variants

@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fuzz_churn_property(model, seed):
    cfg, params = model
    _fuzz_once(cfg, params, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fuzz_pool_pressure_property(model, seed):
    cfg, params = model
    _fuzz_pressure_once(cfg, params, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fuzz_quantized_kv_property(model, seed):
    cfg, params = model
    _fuzz_quantized_once(cfg, params, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fuzz_disagg_property(model, seed):
    cfg, params = model
    _fuzz_disagg_once(cfg, params, seed)
