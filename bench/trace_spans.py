"""Two keys beside ``bench/trace_reduce.py``'s reduction of a profiler
trace: device idle put down to the program's finer spans, and the device
time of each step program.

``reduce`` returns:
  * ``idle_by_span``: device idle time inside ``trace_reduce``'s window,
    split by the innermost annotation of ``SPANS`` open at the middle of
    each gap (``"none"`` where none is open), averaged over the device
    planes.  Every name of ``SPANS`` that occurs in the trace has an
    entry, 0.0 where no gap fell inside it, so an entry is missing only
    where the program records no such span.  The entries sum to
    ``window_s - busy_s``.
  * ``modules``: per step program of ``STEP_PROGRAMS`` (the jitted
    function's name), the number of its executions that start inside
    the window and their device seconds, summed over device planes, from
    the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane.

The program's spans (``serve/runtime.py``): ``engine_step`` holds
``admit`` (a row group's admission), ``cache_edit`` (an eager edit of
the cache pytree outside the jitted steps), ``step_inputs`` (host work
that plans a step or builds its inputs), ``prefill_chunk`` and
``decode`` (the jitted calls); the harness adds ``submit``, ``stamp``
and ``sleep``.
"""
from __future__ import annotations

from bench import trace_reduce

SPANS = ("engine_step", "admit", "cache_edit", "step_inputs",
         "prefill_chunk", "decode", "submit", "stamp", "sleep")
MODULES_LINE = "XLA Modules"
STEP_PROGRAMS = ("_decode_impl", "_chunk_impl")


def load_modules(path):
    """Module executions per device plane, as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace_reduce.find_trace(path))
    return [[(ev.start_ns, ev.end_ns, ev.name)
             for line in plane.lines if line.name == MODULES_LINE
             for ev in line.events]
            for plane in pd.planes
            if plane.name.startswith(trace_reduce.DEVICE_PREFIX)]


def window(host) -> tuple:
    """``trace_reduce``'s window in ns: from the first start to the last
    end of its host spans (of every annotation where there are none)."""
    spans = [(s, e) for s, e, n in host if n in trace_reduce.HOST_SPANS]
    spans = spans or [(s, e) for s, e, _ in host]
    return min(s for s, _ in spans), max(e for _, e in spans)


def idle_by_span(devices, host) -> dict:
    """Device idle seconds inside the window per innermost span."""
    lo, hi = window(host)
    spans = sorted((s, e, n) for s, e, n in host if n in SPANS)
    idle = dict.fromkeys(sorted({n for _, _, n in spans}), 0.0)
    for iv in devices:
        merged = trace_reduce._union((max(s, lo), min(e, hi)) for s, e in iv
                                     if e > lo and s < hi)
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                name = trace_reduce._host_at(spans, (a + b) / 2)
                idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    return {k: v / len(devices) for k, v in idle.items()}


def modules(mods, lo, hi) -> dict:
    """Executions of each step program that start in [lo, hi)."""
    out = {p: {"count": 0, "device_s": 0.0} for p in STEP_PROGRAMS}
    for plane in mods:
        for s, e, name in plane:
            prog = next((p for p in STEP_PROGRAMS
                         if name.startswith(f"jit_{p}")), None)
            if prog is not None and lo <= s < hi:
                out[prog]["count"] += 1
                out[prog]["device_s"] += (e - s) * 1e-9
    return out


def reduce(path) -> dict:
    """The two keys above for the trace at ``path``."""
    devices, _, _, host = trace_reduce.load(path)
    lo, hi = window(host)
    return {"idle_by_span": idle_by_span(devices, host),
            "modules": modules(load_modules(path), lo, hi)}


def idle_share(red, span: str):
    """100 x the device idle under ``span`` over the window; None where
    the reduction has no such entry."""
    idle = (red or {}).get("idle_by_span")
    if idle is None or span not in idle or red["window_s"] <= 0:
        return None
    return 100 * idle[span] / red["window_s"]


def module_ms(red, program: str):
    """Mean device milliseconds of one execution of ``program``; None
    where it did not run inside the window."""
    m = ((red or {}).get("modules") or {}).get(program)
    if not m or not m["count"]:
        return None
    return 1e3 * m["device_s"] / m["count"]
