"""The reduction's two added keys (``bench/trace_spans.py``): device idle
put down to the innermost program span, and device time per step
program from the module line, as ``harness.reduce_trace`` merges them
for the readers; and the five readers built on them, on events laid
out by hand (times in ns) and on a recorded chip trace."""
import json

import numpy as np
import pytest

from bench import harness, trace_reduce, trace_spans

# device: ops at [100, 200), [300, 400), [700, 800) and [960, 1000)
DEVICE = [[(100, 200), (300, 400), (700, 800), (960, 1000)]]
# host: engine step 0 over [50, 1000) holds a step_inputs [60, 120), an
# admit [200, 500) holding a cache_edit [210, 290), and a decode
# [600, 950); then a sleep [1000, 1200).  The outer frame is no
# candidate span.
HOST = [(50, 1000, "engine_step"), (60, 120, "step_inputs"),
        (200, 500, "admit"), (210, 290, "cache_edit"), (600, 950, "decode"),
        (1000, 1200, "sleep"), (0, 2000, "python frame")]
MODS = [[(20, 40, "jit__decode_impl(12)"),          # before the window
         (100, 400, "jit__decode_impl(12)"),
         (700, 800, "jit__chunk_impl(3)"),
         (710, 720, "jit_scatter(5)")]]


@pytest.fixture
def red(monkeypatch):
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: (DEVICE, {"fusion.1": 300e-9},
                                      {"fusion.1": ""}, HOST))
    monkeypatch.setattr(trace_spans, "load_modules", lambda path: MODS)
    return harness.reduce_trace("unused")


def test_idle_by_span_innermost_and_complete(red):
    # gaps: [50,100) mid 75 step_inputs; [200,300) mid 250 cache_edit
    # (inside admit); [400,700) mid 550 engine_step (admit has ended);
    # [800,960) mid 880 decode; [1000,1200) mid 1100 sleep
    assert red["idle_by_span"] == pytest.approx({
        "step_inputs": 50e-9, "cache_edit": 100e-9, "engine_step": 300e-9,
        "decode": 160e-9, "sleep": 200e-9, "admit": 0.0})
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_existing_keys_unchanged(red):
    # the added keys leave trace_reduce's own numbers as they were: the
    # finer spans' gaps stay with the enclosing admit / engine_step there
    assert red["window_s"] == pytest.approx(1150e-9)
    assert red["busy_s"] == pytest.approx(340e-9)
    assert dict(red["idle_by_host"]) == pytest.approx({
        "engine_step": 350e-9, "admit": 100e-9, "decode": 160e-9,
        "sleep": 200e-9})
    alone = trace_reduce.reduce("unused")
    assert json.dumps(alone) == json.dumps(
        {k: red[k] for k in alone})


def test_gap_with_no_span_open():
    host = [(0, 100, "engine_step"), (300, 400, "sleep")]
    got = trace_spans.idle_by_span([[(50, 350)]], host)
    # [0,50) in engine_step, [350,400) in sleep; nothing else is idle
    assert got == pytest.approx({"engine_step": 50e-9, "sleep": 50e-9})
    got = trace_spans.idle_by_span([[(0, 10)]], host)
    # [10,400): mid 205 lies between the two spans
    assert got == pytest.approx({"engine_step": 0.0, "sleep": 0.0,
                                 "none": 390e-9})


def test_modules_count_executions_in_window(red):
    assert red["modules"] == {
        "_decode_impl": {"count": 1, "device_s": pytest.approx(300e-9)},
        "_chunk_impl": {"count": 1, "device_s": pytest.approx(100e-9)}}
    two = trace_spans.modules(MODS + MODS, 50, 1200)
    assert two["_decode_impl"]["count"] == 2          # summed over planes


def _view(red, window_spans=()):
    return harness.RunView(spec={}, config={}, peaks={},
                           rec=harness.Record(due=np.array([])),
                           window=(0.0, 1.0), window_spans=list(window_spans),
                           traced_spans=[], trace=red)


NEW = ("idle_share.cache_edit", "idle_share.step_inputs",
       "cache_edits_per_step", "decode_device_ms", "prefill_chunk_device_ms")


def test_readers(red):
    spans = [("engine_step", 0.0, 1.0, {}), ("step_inputs", 0.1, 0.1, {}),
             ("cache_edit", 0.2, 0.1, {}), ("cache_edit", 0.4, 0.1, {}),
             ("engine_step", 1.0, 1.0, {}), ("decode", 1.2, 0.5, {}),
             ("cache_edit", 1.8, 0.1, {})]
    got = harness.read_per_layer(NEW, _view(red, spans))
    value = {k: v["value"] for k, v in got.items()}
    assert value == pytest.approx({
        "idle_share.cache_edit": 100 * 100 / 1150,
        "idle_share.step_inputs": 100 * 50 / 1150,
        "cache_edits_per_step": 1.5,
        "decode_device_ms": 300e-6, "prefill_chunk_device_ms": 100e-6})
    assert {k: v["unit"] for k, v in got.items()} == {
        "idle_share.cache_edit": "%", "idle_share.step_inputs": "%",
        "cache_edits_per_step": "edits/step", "decode_device_ms": "ms",
        "prefill_chunk_device_ms": "ms"}


def test_readers_read_nothing_without_the_spans():
    # a program that records neither span, and a reduction without the
    # added keys: every reader returns None and none raises
    red = {"window_s": 1.0, "busy_s": 0.5, "idle_by_span": {"decode": 0.5},
           "modules": {"_decode_impl": {"count": 0, "device_s": 0.0}}}
    spans = [("engine_step", 0.0, 1.0, {}), ("decode", 0.2, 0.5, {})]
    for trace in (red, {"window_s": 1.0, "busy_s": 0.5}, None):
        for name in NEW:
            mod = harness.load_module(harness.ROOT / "bench" / "metrics"
                                      / f"{name}.py")
            assert mod.read(_view(trace, spans)) is None, name


def _step_trace():
    """Four engine steps of ``qwen2-1.5b-mux2.chat`` recorded on one TPU
    v5e, the first of them admitting a row group: the device operation
    intervals, the program's and the harness's annotations, and the
    module executions, in ns from the slice's start."""
    import gzip
    import pathlib
    path = pathlib.Path(__file__).parent / "data" / "step_trace.json.gz"
    d = json.loads(gzip.decompress(path.read_bytes()))
    return d["devices"], [tuple(h) for h in d["host"]], d["modules"]


@pytest.fixture
def recorded(monkeypatch):
    devices, host, mods = _step_trace()
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: (devices, {}, {}, host))
    monkeypatch.setattr(trace_spans, "load_modules", lambda path: mods)
    return host, harness.reduce_trace("unused")


def test_recorded_step_trace_idle_by_span(recorded):
    host, red = recorded
    assert red["window_s"] == pytest.approx(0.388296708)
    assert red["busy_s"] == pytest.approx(0.359176488)
    idle = red["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    # the admission's block reset runs about ten eager programs, and the
    # device idles between them: trace_reduce puts that under admit
    # (and the table install under engine_step), the finer spans under
    # cache_edit
    assert idle["cache_edit"] == pytest.approx(0.006719098)
    assert idle["admit"] == 0.0
    assert dict(red["idle_by_host"])["admit"] == pytest.approx(0.00543423)
    assert idle["step_inputs"] == pytest.approx(0.003943454)
    # every gap inside an engine step sits under a named span but for the
    # ones whose middle falls between two of them
    assert idle["engine_step"] == pytest.approx(0.008752659)
    assert set(idle) == {"engine_step", "admit", "cache_edit",
                         "step_inputs", "prefill_chunk", "decode", "stamp",
                         "submit"}


def test_recorded_step_trace_modules(recorded):
    host, red = recorded
    decode = sorted((s, e) for s, e, n in host if n == "decode")
    chunks = [h for h in host if h[2] == "prefill_chunk"]
    mods = red["modules"]
    # one device execution per jitted call
    assert mods["_decode_impl"]["count"] == len(decode) == 4
    assert mods["_chunk_impl"]["count"] == len(chunks) == 5
    assert mods["_decode_impl"]["device_s"] == pytest.approx(0.186238364)
    assert mods["_chunk_impl"]["device_s"] == pytest.approx(0.172910937)
    # each decode execution ends inside its decode span, which closes
    # after the read-back of its tokens
    _, _, execs = _step_trace()
    runs = sorted((s, e) for s, e, n in execs[0]
                  if n.startswith("jit__decode_impl"))
    for (s, e), (hs, he) in zip(runs, decode):
        assert hs < e <= he


def test_declared_readers_read_the_recorded_trace(recorded):
    # the five readers that BENCHMARK.json declares for the chat cell, on
    # what run_cell hands them (harness.reduce_trace) of the recorded trace
    host, red = recorded
    declared = [m["name"] for m in json.loads(
        (harness.ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] in NEW]
    assert sorted(declared) == sorted(NEW)
    spans = [(n, s * 1e-9, (e - s) * 1e-9, {}) for s, e, n in host]
    got = harness.read_per_layer(declared, _view(red, spans))
    assert got["decode_device_ms"]["value"] == pytest.approx(
        1e3 * 0.186238364 / 4)
    assert got["prefill_chunk_device_ms"]["value"] == pytest.approx(
        1e3 * 0.172910937 / 5)
    assert got["idle_share.cache_edit"]["value"] == pytest.approx(
        100 * 0.006719098 / 0.388296708)
    assert got["idle_share.step_inputs"]["value"] == pytest.approx(
        100 * 0.003943454 / 0.388296708)
    names = [n for _, _, n in host]
    assert got["cache_edits_per_step"]["value"] == pytest.approx(
        names.count("cache_edit") / names.count("engine_step"))
