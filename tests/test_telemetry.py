"""Serve-stack telemetry unit tests (DESIGN.md §observability).

Streaming-histogram algebra (merge == observing the concatenated
samples; property-tested when hypothesis is available), registry
labeling + Prometheus text format, Chrome trace-event schema
round-trips, and the zero-overhead-disabled contract of
``NULL_TELEMETRY``.  The end-to-end no-host-sync invariant (telemetry
on == off, tokens and compile counts) lives in
tests/test_serve_fuzz.py::test_fuzz_telemetry_parity_deterministic.
"""
import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from hypothesis_stub import given, settings, st

from repro.serve.telemetry import (Telemetry, MetricsRegistry,
                                   StepTracer, StreamingHistogram,
                                   NULL_TELEMETRY, default_edges)


# ------------------------------------------------- streaming histograms

def test_histogram_exact_moments():
    h = StreamingHistogram()
    xs = [0.001, 0.01, 0.25, 1.5, 80.0]
    for x in xs:
        h.observe(x)
    assert h.count == len(xs)
    assert h.total == pytest.approx(sum(xs))
    assert h.vmin == min(xs) and h.vmax == max(xs)
    assert h.mean == pytest.approx(np.mean(xs))


def test_histogram_percentile_bounds_and_order():
    h = StreamingHistogram()
    rng = np.random.default_rng(0)
    xs = rng.lognormal(-3, 2, size=500)
    for x in xs:
        h.observe(float(x))
    qs = [h.percentile(q) for q in (0, 25, 50, 75, 95, 100)]
    assert qs == sorted(qs)                     # monotone in q
    for v in qs:                                # clamped to observed range
        assert h.vmin <= v <= h.vmax
    # bucketed median within one log-bucket of the exact one
    exact = float(np.percentile(xs, 50))
    edges = h.edges
    i = int(np.searchsorted(edges, exact))
    lo = edges[max(i - 2, 0)]
    hi = edges[min(i + 1, len(edges) - 1)]
    assert lo <= h.percentile(50) <= hi


def test_histogram_merge_equals_concat():
    a, b, both = (StreamingHistogram() for _ in range(3))
    rng = np.random.default_rng(1)
    for x in rng.exponential(0.05, size=64):
        a.observe(float(x)); both.observe(float(x))
    for x in rng.exponential(5.0, size=64):
        b.observe(float(x)); both.observe(float(x))
    a.merge(b)
    assert a.snapshot() == both.snapshot()


def test_histogram_merge_requires_identical_edges():
    a = StreamingHistogram()
    b = StreamingHistogram(edges=default_edges(per_decade=8))
    with pytest.raises(ValueError):
        a.merge(b)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(1e-6, 1e3), max_size=40),
       st.lists(st.floats(1e-6, 1e3), max_size=40))
def test_histogram_merge_property(xs, ys):
    a, b, both = (StreamingHistogram() for _ in range(3))
    for x in xs:
        a.observe(x); both.observe(x)
    for y in ys:
        b.observe(y); both.observe(y)
    a.merge(b)
    assert a.count == both.count == len(xs) + len(ys)
    got, want = a.snapshot(), both.snapshot()
    # merging adds the two partial sums, so the float total may round
    # differently from summing the samples in order
    for k in ("sum", "mean"):
        assert got.pop(k) == pytest.approx(want.pop(k))
    assert got == want


# ------------------------------------------------- registry + prometheus

def test_registry_labels_and_values():
    reg = MetricsRegistry()
    reg.inc("preempts", lane=0, shard=1)
    reg.inc("preempts", 2, lane=0, shard=1)
    reg.inc("preempts", lane=1, shard=0)
    reg.gauge("pool_occupancy", 0.5, lane=0, shard=0)
    assert reg.value("preempts", lane=0, shard=1) == 3
    assert reg.value("preempts", lane=1, shard=0) == 1
    assert reg.value("preempts", lane=9, shard=9) == 0     # default
    assert reg.value("pool_occupancy", lane=0, shard=0) == 0.5
    # label order never matters
    assert reg.value("preempts", shard=1, lane=0) == 3


def test_registry_snapshot_and_prometheus():
    reg = MetricsRegistry()
    reg.inc("preempts", 3, lane=0, shard=1)
    reg.observe("ttft_s", 0.25, lane=0)
    snap = reg.snapshot()
    assert {r["name"] for r in snap["counters"]} == {"preempts"}
    (h,) = snap["histograms"]
    assert h["name"] == "ttft_s" and h["labels"] == {"lane": 0}
    assert h["count"] == 1 and h["sum"] == pytest.approx(0.25)
    text = reg.to_prometheus()
    assert '# TYPE repro_preempts counter' in text
    assert 'repro_preempts{lane="0",shard="1"} 3' in text
    assert '# TYPE repro_ttft_s histogram' in text
    assert 'repro_ttft_s_count{lane="0"} 1' in text
    # cumulative buckets end at +Inf with the full count
    assert 'le="+Inf"' in text


def test_registry_merge_across_workers():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("tokens_generated", 5, lane=0)
    b.inc("tokens_generated", 7, lane=0)
    b.observe("ttft_s", 0.1, lane=0)
    a.merge(b)
    assert a.value("tokens_generated", lane=0) == 12
    assert a.hist("ttft_s", lane=0).count == 1


# ------------------------------------------------- chrome trace tracer

def test_tracer_chrome_schema_roundtrip(tmp_path):
    tr = StepTracer()
    tr.process_name(0, "lane 0 (N=2)")
    t0 = tr.now_us()
    tr.complete("decode", t0, 120.0, pid=0, tid=1, args={"rows": 2})
    tr.instant("preempt", pid=0, tid=1, args={"row": 3})
    path = tmp_path / "trace.json"
    tr.export(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "i", "M"}
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "decode" and x["dur"] == pytest.approx(120.0)
    assert x["pid"] == 0 and x["tid"] == 1 and x["args"] == {"rows": 2}
    i = next(e for e in evs if e["ph"] == "i")
    assert i["s"] == "t" and i["args"] == {"row": 3}
    assert doc["otherData"]["dropped_events"] == 0
    assert set(doc["otherData"]["clock_anchor"]) == {"perf_counter_ns",
                                                     "time_ns"}


def test_tracer_ring_buffer_drops_oldest():
    tr = StepTracer(capacity=4)
    for k in range(10):
        tr.instant(f"e{k}", pid=0, tid=0)
    evs = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "i"]
    assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6


# ------------------------------------------------- telemetry facade

def test_null_telemetry_is_inert():
    tele = NULL_TELEMETRY
    with tele.span("decode", lane=0, metric="decode_step_s"):
        pass
    tele.inc("preempts", lane=0)
    tele.observe("ttft_s", 0.1, lane=0)
    tele.gauge("pool_occupancy", 0.3, lane=0, shard=0)
    tele.instant("cancel", lane=0)
    tele.maybe_snapshot(0)
    assert tele.registry.snapshot() == {"counters": [], "gauges": [],
                                        "histograms": []}
    assert tele.snapshots == []
    assert tele.tracer.chrome_trace()["traceEvents"] == []
    # the disabled span is one shared object: no per-call allocation
    assert tele.span("a") is tele.span("b")


def test_enabled_span_records_metric_and_event():
    tele = Telemetry()
    with tele.span("decode", lane=1, shard=2, metric="decode_step_s",
                   rows=4):
        pass
    h = tele.registry.hist("decode_step_s", lane=1, shard=2)
    assert h is not None and h.count == 1
    (x,) = [e for e in tele.tracer.chrome_trace()["traceEvents"]
            if e["ph"] == "X"]
    assert (x["name"], x["pid"], x["tid"]) == ("decode", 1, 2)
    assert x["args"]["rows"] == 4


def test_snapshot_interval_and_exports(tmp_path):
    tele = Telemetry(snapshot_every=2)
    for step in range(1, 7):
        tele.inc("tokens_generated", lane=0)
        tele.maybe_snapshot(step)
    assert [s["step"] for s in tele.snapshots] == [2, 4, 6]
    counts = [s["counters"][0]["value"] for s in tele.snapshots]
    assert counts == [2, 4, 6]                  # trajectory, not deltas
    mpath = tmp_path / "metrics.json"
    prom = tele.write_metrics(mpath)
    doc = json.loads(mpath.read_text())
    assert len(doc["snapshots"]) == 3
    assert doc["final"]["counters"][0]["value"] == 6
    assert prom.suffix == ".prom" and "repro_tokens_generated" in prom.read_text()
    tpath = tmp_path / "trace.json"
    tele.write_trace(tpath)
    assert "traceEvents" in json.loads(tpath.read_text())


def test_tracer_clock_anchor_maps_spans_to_wall_time():
    import time
    before = time.time_ns()
    tr = StepTracer()
    after = time.time_ns()
    anchor = tr.chrome_trace()["otherData"]["clock_anchor"]
    assert before <= anchor["time_ns"] <= after
    # the tracer's zero is the anchor's perf_counter reading
    assert tr._t0 == pytest.approx(anchor["perf_counter_ns"] * 1e-9)
    t_ns = time.perf_counter_ns()
    wall = time.time_ns()
    ts_us = (t_ns - anchor["perf_counter_ns"]) / 1e3
    assert abs(anchor["time_ns"] + ts_us * 1e3 - wall) < 5e6     # 5 ms


def test_annotated_instant_reaches_the_profiler(monkeypatch):
    from repro.serve import telemetry as tm
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tm, "_trace_annotation", Ann)
    Telemetry(annotate=True).instant("preempt", lane=0, row=1)
    assert seen == [("enter", "preempt"), ("exit", "preempt")]
    seen.clear()
    Telemetry().instant("preempt", lane=0)
    NULL_TELEMETRY.instant("preempt", lane=0)
    assert seen == []


# ------------------------------------------------- runtime spans

ROWS, CAPACITY, BLOCK, CHUNK = 2, 24, 4, 4


@pytest.fixture(scope="module")
def mux_model():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import MuxSpec
    from repro.models import TransformerLM
    from repro.serve import ServeConfig
    cfg = get_config("qwen2-1.5b", reduced=True)
    mux = MuxSpec(n=2)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg, mux)
    sc = ServeConfig(cfg=cfg, kind="lm", mux=mux, capacity=CAPACITY,
                     dtype=jnp.float32, cache_layout="paged",
                     block_size=BLOCK)
    return cfg, params, sc


def _serve(mux_model, telemetry=None, uid0=0):
    """Serve five requests (two row groups, then one more) to the end
    through a fresh runtime; returns it."""
    from repro.serve import Request
    from repro.serve.runtime import ServeRuntime
    cfg, params, sc = mux_model
    rt = ServeRuntime(params, sc, ROWS, chunk=CHUNK, telemetry=telemetry)
    rng = np.random.default_rng(0)
    for k, (n, new) in enumerate([(6, 7), (9, 5), (3, 6), (5, 8), (7, 3)]):
        rt.submit(Request(uid=uid0 + k, max_new=new, prompt=list(
            rng.integers(1, cfg.vocab_size, size=n, dtype=np.int32))))
    while rt.has_work():
        rt.step()
    return rt


def _spans(tele):
    return [(n, ts, dur, args or {}) for ph, n, ts, dur, _p, _t, args
            in tele.tracer.events if ph == "X"]


def test_runtime_spans_nest_under_engine_step(mux_model):
    tele = Telemetry()
    rt = _serve(mux_model, tele)
    spans = _spans(tele)
    steps = {a["step"]: (ts, ts + dur) for n, ts, dur, a in spans
             if n == "engine_step"}
    assert sorted(steps) == list(range(rt.engine_steps))
    names = {n for n, *_ in spans}
    assert {"admit", "cache_edit", "step_inputs", "prefill_chunk",
            "decode"} <= names
    for n, ts, dur, a in spans:
        # every span names its engine step and lies inside it
        lo, hi = steps[a["step"]]
        assert lo <= ts and ts + dur <= hi, (n, a)
    kinds = {a["kind"] for n, *_, a in spans if n == "cache_edit"}
    assert kinds == {"reset", "tables"}      # no mesh: nothing to commit
    phases = {a["phase"] for n, *_, a in spans if n == "step_inputs"}
    assert phases == {"plan", "decode", "chunk"}
    # admit and prefill_chunk carry the row group's request ids; the
    # chunks of a row carry the ids that row was admitted with
    groups = {}
    for n, _, _, a in spans:
        if n == "admit":
            assert a["uids"] and len(a["uids"]) <= 2
            groups[a["row"]] = a["uids"]
        elif n == "prefill_chunk":
            assert a["uids"] == groups[a["row"]]
    admitted = sorted(u for n, *_, a in spans if n == "admit"
                      for u in a["uids"])
    assert admitted == list(range(5))


def test_reset_spans_count_reset_calls(mux_model, monkeypatch):
    from repro.serve import runtime
    calls = []
    real = runtime.reset_blocks

    def counted(cache, ids):
        calls.append(list(ids))
        return real(cache, ids)

    monkeypatch.setattr(runtime, "reset_blocks", counted)
    tele = Telemetry()
    _serve(mux_model, tele)
    resets = [a for n, *_, a in _spans(tele)
              if n == "cache_edit" and a["kind"] == "reset"]
    assert len(resets) == len(calls) > 0
    assert [a["blocks"] for a in resets] == [len(c) for c in calls]


def test_disabled_telemetry_reads_no_clock(mux_model, monkeypatch):
    import time
    warm = _serve(mux_model)                 # compile every program first
    assert warm.tele is NULL_TELEMETRY

    def no_clock():
        raise AssertionError("a clock was read with telemetry off")

    monkeypatch.setattr(time, "perf_counter", no_clock)
    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    rt = _serve(mux_model, uid0=10)
    assert len(rt.sched.completed) == 5
    assert dict(rt.trace_counts) == dict(warm.trace_counts)


def test_decode_pages_walked_share_per_step(mux_model, monkeypatch):
    """One observation per decode step: the live table blocks of the
    step's active rows over rows x table width."""
    from repro.serve import runtime
    seen = []
    real = runtime.live_blocks

    def spy(pos, *a, **kw):
        seen.append(np.asarray(pos).copy())
        return real(pos, *a, **kw)

    monkeypatch.setattr(runtime, "live_blocks", spy)
    tele = Telemetry()
    rt = _serve(mux_model, tele)
    h = tele.registry.hist("decode_pages_walked_share", lane=0)
    assert h.count == len(seen) == rt.stats["decode_steps"] > 0
    width = CAPACITY // BLOCK
    want = [np.where(p >= 0, p // BLOCK + 1, 0).sum() / (ROWS * width)
            for p in seen]
    assert h.total == pytest.approx(sum(want))
    assert 0 < h.vmin <= h.vmax <= 1
