"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The device side is the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane: one event per operation that ran, kernels included.  The host
side is every trace annotation on the ``/host:CPU`` plane: the serve
path's telemetry spans (``engine_step``, ``admit``, ``prefill_chunk``,
``decode``) and the harness's own (``submit``, ``stamp``, ``sleep``).
Both sides share the profiler's clock.

``reduce`` returns:
  * ``window_s``: the traced window, from the start of the first host
    span above to the end of the last;
  * ``busy_s``: the union of device operation intervals, averaged over
    the device planes;
  * ``ops``: device seconds per operation name, summed over devices;
  * ``meta``: per operation name, the text of its first event's
    statistics (the HLO metadata, such as the ``jit(...)`` scope that
    names the kernel a custom call runs);
  * ``top_ops``: the ten largest of ``ops`` as [name, seconds];
  * ``idle_by_host``: device idle time inside the window, split by the
    innermost host span open at the middle of each gap, ten largest
    first, as [name, seconds] (``"none"`` where no span was open).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

HOST_SPANS = ("engine_step", "admit", "prefill_chunk", "decode", "submit",
              "stamp", "sleep")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_trace(path) -> str:
    """The newest ``.xplane.pb`` at or under ``path``."""
    if os.path.isfile(path):
        return str(path)
    files = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path):
    """(device intervals per plane, op seconds, op metadata, host spans),
    times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_trace(path))
    devices, ops, meta, host = [], defaultdict(float), {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            iv = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    iv.append((ev.start_ns, ev.end_ns))
                    ops[ev.name] += ev.duration_ns * 1e-9
                    if ev.name not in meta:
                        meta[ev.name] = " ".join(
                            f"{k}={v}" for k, v in ev.stats)
            devices.append(iv)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.start_ns, ev.end_ns, ev.name))
    return devices, dict(ops), meta, host


def reduce(path) -> dict:
    """The numbers above for the trace at ``path``."""
    devices, ops, meta, host = load(path)
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operations")
    spans = sorted((s, e, n) for s, e, n in host if n in HOST_SPANS)
    if spans:
        lo, hi = spans[0][0], max(e for _, e, _ in spans)
    else:
        lo = min(s for s, _, _ in host)
        hi = max(e for _, e, _ in host)
    window = (hi - lo) * 1e-9
    busy, idle = 0.0, defaultdict(float)
    for iv in devices:
        merged = _union((max(s, lo), min(e, hi)) for s, e in iv
                        if e > lo and s < hi)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_host_at(spans, (a + b) / 2)] += (b - a) * 1e-9
    n = len(devices)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window, "busy_s": busy / n, "ops": ops, "meta": meta,
            "top_ops": [[k, v] for k, v in top],
            "idle_by_host": [[k, v / n] for k, v in gaps]}


def _host_at(spans, t) -> str:
    """The innermost (shortest) host span open at time t."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "none"


def kernel_seconds(red: dict, match) -> float | None:
    """Device seconds of the operations that ``match(name, meta)``
    accepts; None when no operation matches."""
    hits = [v for k, v in red["ops"].items() if match(k, red["meta"][k])]
    return sum(hits) if hits else None
