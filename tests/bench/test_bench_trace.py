"""The trace reduction (``bench/trace_reduce.py``): busy time, per-op
time, and idle gaps split by the host span open during them, on events
laid out by hand (times in ns)."""
import pytest

from bench import trace_reduce

# device: ops at [100, 200) and [150, 300) overlap, then [500, 600)
DEVICE = [[(100, 200), (150, 300), (500, 600)]]
PA = ('%paged_attention.2 = bf16[4,2,6,128]{3,2,1,0} custom-call(), '
      'custom_call_target="tpu_custom_call"')
OPS = {"fusion.1": 250e-9, PA: 100e-9}
META = {"fusion.1": "", PA: "device_duration_ps=100000"}
# host: an engine step over [50, 700) holding a decode over [400, 650),
# then a sleep [700, 1000); the first span starts the window, the last
# one ends it
HOST = [(50, 700, "engine_step"), (400, 650, "decode"), (700, 1000, "sleep"),
        (0, 2000, "python frame")]


@pytest.fixture
def red(monkeypatch):
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: (DEVICE, OPS, META, HOST))
    return trace_reduce.reduce("unused")


def test_window_and_busy(red):
    assert red["window_s"] == pytest.approx(950e-9)        # 50 .. 1000
    assert red["busy_s"] == pytest.approx(300e-9)          # 200 + 100


def test_idle_split_by_innermost_host_span(red):
    idle = dict(red["idle_by_host"])
    # gaps: [50,100) engine_step; [300,500) mid 400 -> decode (innermost);
    # [600,1000) mid 800 -> sleep
    assert idle == pytest.approx({"engine_step": 50e-9, "decode": 200e-9,
                                  "sleep": 400e-9})
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_top_ops_and_kernel_time(red):
    assert [k for k, _ in red["top_ops"]] == ["fusion.1", PA]
    import importlib.util
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[2]
    spec = importlib.util.spec_from_file_location(
        "k", root / "bench" / "kernels" / "paged_attention.py")
    k = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(k)
    assert trace_reduce.kernel_seconds(red, k.in_trace) == pytest.approx(
        100e-9)
    assert trace_reduce.kernel_seconds(red, lambda n, m: False) is None


def test_no_device_ops_is_an_error(monkeypatch):
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: ([[]], {}, {}, HOST))
    with pytest.raises(ValueError):
        trace_reduce.reduce("unused")


def test_declared_metric_that_reads_nothing_fails_the_run():
    import numpy as np
    from bench import harness
    rec = harness.Record(due=np.array([0.5, 1.5]), admit=[0.75, None])
    view = harness.RunView(spec={}, config={}, peaks={}, rec=rec,
                           window=(0.0, 2.0), window_spans=[],
                           traced_spans=[], trace=None)
    got = harness.read_per_layer(["queue_wait_p95_s"], view)
    # waits 0.25 s and (2.0 - 1.5) = 0.5 s (never admitted): p95 0.4875
    assert got == {"queue_wait_p95_s": {"value": pytest.approx(0.4875),
                                        "unit": "s"}}
    with pytest.raises(harness.MetricMissing, match="idle_share"):
        harness.read_per_layer(["queue_wait_p95_s", "idle_share"], view)


def _recorded():
    """Three decode steps of ``qwen2-1.5b-mux2.chat`` recorded on one TPU
    v5e: the reduction's inputs as ``trace_reduce.load`` read them from
    the ``.xplane.pb`` (host spans of the serve path and the harness
    only; operation statistics dropped)."""
    import gzip
    import json
    import pathlib
    path = pathlib.Path(__file__).parent / "data" / "decode_trace.json.gz"
    d = json.loads(gzip.decompress(path.read_bytes()))
    return d["devices"], d["ops"], d["meta"], d["host"]


@pytest.fixture
def recorded(monkeypatch):
    loaded = _recorded()
    monkeypatch.setattr(trace_reduce, "load", lambda path: loaded)
    return loaded, trace_reduce.reduce("unused")


def test_recorded_chip_trace_busy_and_idle(recorded):
    (devices, _, _, host), red = recorded
    # the window runs from the first harness span to the last one
    assert red["window_s"] == pytest.approx(
        (max(e for _, e, _ in host) - min(s for s, _, _ in host)) * 1e-9)
    assert red["window_s"] == pytest.approx(0.151573556)
    # busy: the union of the operation intervals, by a plain sweep
    edges = sorted([(s, 1) for s, _ in devices[0]]
                   + [(e, -1) for _, e in devices[0]],
                   key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert red["busy_s"] == pytest.approx(0.139675865)
    # every idle gap lies inside a decode step: the engine step around
    # it, or the decode call that waits on the device
    idle = dict(red["idle_by_host"])
    assert set(idle) == {"engine_step", "decode"}
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_recorded_chip_trace_kernel_time(recorded):
    (_, ops, _, host), red = recorded
    import importlib.util
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[2]
    kernels = {}
    for name in ("paged_attention", "paged_prefill_attention"):
        spec = importlib.util.spec_from_file_location(
            name, root / "bench" / "kernels" / f"{name}.py")
        kernels[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(kernels[name])
    pa = [k for k in ops if kernels["paged_attention"].in_trace(k, "")]
    assert len(pa) == 1 and pa[0].startswith("%paged_attention.8 = ")
    # three decode steps of 28 layers, about 21.5 ms of kernel a step
    assert sum(1 for _, _, n in host if n == "decode") == 3
    assert trace_reduce.kernel_seconds(
        red, kernels["paged_attention"].in_trace) == pytest.approx(
            0.06459653)
    # a decode-only slice holds no prefill kernel
    assert trace_reduce.kernel_seconds(
        red, kernels["paged_prefill_attention"].in_trace) is None


def test_traced_ttft_reader():
    import numpy as np
    from bench import harness
    rec = harness.Record(due=np.array([0.5, 1.5, 1.0]),
                         stamps=[[0.75, 0.9], [], [1.5]])
    view = harness.RunView(spec={}, config={}, peaks={}, rec=rec,
                           window=(0.0, 2.0), window_spans=[],
                           traced_spans=[], trace=None)
    got = harness.read_per_layer(["ttft_p95_s.traced"], view)
    # waits 0.25 s, (2.0 - 1.5) = 0.5 s (no token by the end) and 0.5 s
    assert got["ttft_p95_s.traced"]["value"] == pytest.approx(0.5)
