"""One run of one benchmark cell: open-loop serving through ``ServeRuntime``.

``run_cell`` builds the cell's weights from the seed on the device,
warms every program the cell's traffic uses, drives the traffic's
warm-up segment and then its measured window through the serve path
(``ServeRuntime.submit`` + ``ServeRuntime.step``, one loop that submits
every request already due, then steps; it sleeps only when the server
has no work), and afterwards checks what the window served against the
float32 reference.  It returns the result object that ``bench/run.py``
prints as its last line.

Clocks: every stamp is ``time.perf_counter()``.  A token is stamped when
the ``step()`` that produced it returns, which is when it is on the
host.  TTFT runs from the request's due time, not its submit time, so a
late generator or a stalled loop counts against the server.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import stats, traffic as traffic_mod
from bench.model_spec import model_spec

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 3.0          # length of the profiled slice of a --trace 1 window
TRACE_START = 0.35           # it starts this far into the window
PAD_ID = 0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def say(*a):
    print(*a, flush=True)


def load_cell(workload: str) -> dict:
    """The cell ``workload`` of ``BENCHMARK.json``, with its configuration
    and traffic files read."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    per_layer = [m["name"] for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m["name"] for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return cell_from_files(workload, configs[w["config"]]["file"],
                           w["traffic"], w["chips"], per_layer, end_to_end)


def cell_from_files(name: str, config_file: str, traffic: str,
                    chips: int = 1, per_layer=(), end_to_end=None) -> dict:
    """A cell from a configuration file (path from the checkout's root)
    and a traffic mix name (``bench/traffic/<mix>.json``).
    ``end_to_end``: the metrics a ``--trace 0`` run reports (all the
    harness measures when None)."""
    conf = json.loads((ROOT / config_file).read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                     .read_text())
    return {"name": name, "chips": chips, "config": conf, "traffic": mix,
            "per_layer": list(per_layer), "end_to_end": end_to_end}


# ---------------------------------------------------------------------------
# compile counting
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts the programs this process lowers, from JAX's monitoring
    events: every new executable, a jitted step or an eager op, is lowered
    once, whether it then compiles or comes from the compile cache."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _installed = None

    def __init__(self):
        self.n = 0

    @classmethod
    def get(cls):
        if cls._installed is None:
            import jax
            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed._on_event)
        return cls._installed

    def _on_event(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

# the program's RMSNorm epsilon where its ModelConfig has no ``norm_eps``
# (``nn/layers.py`` RMSNorm.apply)
PROGRAM_NORM_EPS = 1e-6


def program_config(conf: dict, spec: dict):
    """The serve path's ModelConfig, MuxSpec and ServeConfig for a
    configuration file; refuses one that the program would not run as
    the reference computes it.

    Of ``model_spec``'s keys, passed on to the program: ``layers``,
    ``d``, ``heads``, ``kv_heads``, ``head_dim``, ``ffn``, ``vocab``,
    ``qkv_bias``, ``tied``, ``rope_theta`` and ``window`` (ModelConfig),
    ``n_mux`` and ``demux_hidden`` (MuxSpec), and ``norm_eps`` as
    ``ModelConfig.norm_eps`` where the program's ModelConfig has that
    field.  Checked instead: ``norm_eps`` where it has not, which has
    to be the program's fixed ``PROGRAM_NORM_EPS``.  The layer
    equations (activation, gated FFN, RMSNorm, RoPE, causal, no soft
    cap, no embedding scale, attention blocks only, no experts) come
    from the architecture and are checked against the reference's."""
    import dataclasses
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import MuxSpec
    from repro.serve import ServeConfig
    base = get_config(conf["arch"])
    extra = {}
    if "norm_eps" in {f.name for f in dataclasses.fields(base)}:
        extra["norm_eps"] = spec["norm_eps"]
    elif spec["norm_eps"] != PROGRAM_NORM_EPS:
        raise ValueError(
            f"{conf['arch']}: rms_norm_eps is {spec['norm_eps']!r} in the "
            f"configuration, but the program's RMSNorm epsilon is fixed at "
            f"{PROGRAM_NORM_EPS!r} (its ModelConfig has no norm_eps)")
    cfg = base.replace(
        n_layers=spec["layers"], d_model=spec["d"], n_heads=spec["heads"],
        n_kv_heads=spec["kv_heads"], head_dim=spec["head_dim"],
        d_ff=spec["ffn"], vocab_size=spec["vocab"], qkv_bias=spec["qkv_bias"],
        tie_embeddings=spec["tied"], rope_theta=spec["rope_theta"],
        window=spec["window"], **extra)
    same = (cfg.activation == conf["hidden_act"] and cfg.glu
            and cfg.norm == "rms" and cfg.positions == "rope" and cfg.causal
            and cfg.logit_softcap is None and not cfg.embedding_scale
            and tuple(cfg.block_pattern) == ("attn",) and cfg.moe is None)
    if not same:
        raise ValueError(f"{conf['arch']}: layer equations differ from the "
                         "reference (bench/reference/decoder.py)")
    mux = conf["mux"]
    mspec = MuxSpec(n=spec["n_mux"], mux_kind=mux["mux_kind"],
                    demux_kind=mux["demux_kind"],
                    demux_hidden=spec["demux_hidden"])
    srv = conf["serve"]
    sc = ServeConfig(cfg=cfg, kind="lm", mux=mspec,
                     capacity=srv["capacity"],
                     dtype=getattr(jnp, srv["dtype"]), cache_layout="paged",
                     block_size=srv["block_size"])
    return cfg, mspec, sc


def blocks_for(n: int, bs: int) -> int:
    return -(-n // bs)


def warm_eager_block_ops(rt, conf: dict, mix: dict):
    """Compile the runtime's eager block-reset program for every id-list
    length the cell can hand it (admissions: the blocks of any padded
    prompt; decode: up to one new block per row), on the still-empty
    pool: resetting free blocks that are already empty changes nothing."""
    from repro.serve.engine import reset_blocks
    bs = conf["serve"]["block_size"]
    lens = set(range(1, rt.nrows + 1))
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    lens |= {blocks_for(n, bs) for n in range(lo, hi + 1)}
    for n in sorted(lens):
        rt.cache = reset_blocks(rt.cache, range(1, n + 1))
    import jax
    jax.block_until_ready(rt.cache)
    return len(lens)


def warm_step_programs(rt, rng, vocab: int):
    """Serve one pair of requests per prefill bucket (prompt lengths that
    end in that bucket, two output tokens) so that the decode step and
    every chunk bucket compile before the traffic starts."""
    from repro.serve import Request
    lens = [b - 1 for b in rt.buckets[:-1]] + [rt.chunk]
    if len(lens) > rt.nrows:
        raise ValueError("fewer rows than prefill buckets to warm")
    uid = -1
    for n in lens:
        for _ in range(rt.n_mux):
            rt.submit(Request(uid=uid, max_new=2, prompt=list(
                rng.integers(1, vocab, size=n, dtype=np.int32))))
            uid -= 1
    while rt.has_work():
        rt.step()
    want = {"decode": 1, **{f"prefill_{b}": 1 for b in rt.buckets}}
    if dict(rt.trace_counts) != want:
        raise RuntimeError(f"warm-up traced {rt.trace_counts}, "
                           f"expected {want}")
    rt.sched.completed.clear()


# ---------------------------------------------------------------------------
# the record of one run
# ---------------------------------------------------------------------------

@dataclass
class Record:
    """Per-request stamps and placements, in harness seconds."""
    due: np.ndarray
    submit: list = field(default_factory=list)     # by schedule index
    stamps: list = field(default_factory=list)     # token stamps per request
    admit: list = field(default_factory=list)      # program's t_admit
    reqs: list = field(default_factory=list)       # the Request objects
    groups: list = field(default_factory=list)     # row groups
    decode_ctx: list = field(default_factory=list)  # traced decode steps
    steps: int = 0


class Driver:
    """The open loop: one thread submits what is due, then steps."""

    def __init__(self, rt, schedule, tele=None, clock=time.perf_counter):
        self.rt = rt
        self.s = schedule
        self.clock = clock
        self.tele = tele
        n = len(schedule)
        self.rec = Record(due=schedule.due, submit=[None] * n,
                          stamps=[[] for _ in range(n)],
                          admit=[None] * n, reqs=[None] * n)
        self.next = 0
        self.queued = {}          # index -> Request, submitted, not admitted
        self.active = {}          # index -> Request, admitted, not finished
        self.t_origin = None
        self.tracing = False
        # time.time() stamps of the program, on this harness's clock
        self.wall_to_clock = clock() - time.time()

    def _ann(self, name):
        if self.tele is None:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def _submit_due(self, now):
        from repro.serve import Request
        s, k = self.s, self.next
        while k < len(s) and s.due[k] <= now:
            r = Request(uid=k, prompt=s.prompts[k], max_new=int(s.max_new[k]))
            self.rt.submit(r)
            self.rec.submit[k] = self.clock() - self.t_origin
            self.rec.reqs[k] = r
            self.queued[k] = r
            k += 1
        self.next = k

    def _after_step(self, t):
        rec = self.rec
        admitted = [k for k, r in self.queued.items() if r.t_admit is not None]
        if admitted:
            self._place(admitted)
        done = []
        for k, r in self.active.items():
            st = rec.stamps[k]
            if len(r.output) > len(st):
                st.extend([t] * (len(r.output) - len(st)))
                if r.done:
                    done.append(k)
        for k in done:
            del self.active[k]

    def _place(self, admitted):
        """Record the row group of newly admitted requests: row, slot and
        the padded prompt length the row was prefilled with."""
        rt, rec = self.rt, self.rec
        where = {id(sl.request): (j, i) for j, row in enumerate(rt.sched.slots)
                 for i, sl in enumerate(row) if sl.request is not None}
        new = {}
        for k in admitted:
            r = self.queued.pop(k)
            self.active[k] = r
            rec.admit[k] = r.t_admit + self.wall_to_clock - self.t_origin
            j, i = where[id(r)]
            new.setdefault(j, {})[i] = k
        for j, members in new.items():
            lpad = max(len(self.s.prompts[k]) for k in members.values())
            rec.groups.append({"row": j, "l_pad": lpad, "slots": members})

    def _decoded_rows(self, before, pp_before):
        """Live KV length of every row that decoded in the last step."""
        rt = self.rt
        after, pp = rt.row_len, rt.sched.prefill_progress
        ctx = [n for j, n in after.items() if j not in pp]
        ctx += [n + 1 for j, n in before.items()
                if j not in after and j not in pp_before]
        return ctx

    def run(self, t0, t1, on_window_start, trace_from=None, trace_for=None,
            trace_dir=None):
        """Serve the warm-up segment and the window [t0, t1) (seconds
        after the traffic starts), calling ``on_window_start()`` when the
        loop first reaches t0.  With ``trace_from``, profile the device
        from the first decode step after that time for ``trace_for``
        seconds.  Returns the traced interval, or None."""
        import jax
        rt, rec = self.rt, self.rec
        self.t_origin = self.clock()
        traced, in_window = None, False
        while True:
            now = self.clock() - self.t_origin
            if now >= t1:
                break
            if not in_window and now >= t0:
                in_window = True
                on_window_start()
            with self._ann("submit"):
                self._submit_due(now)
            if rt.has_work():
                steps_before = rt.stats["decode_steps"]
                if self.tracing:
                    before = dict(rt.row_len)
                    pp_before = set(rt.sched.prefill_progress)
                rt.step()
                rec.steps += 1
                t = self.clock() - self.t_origin
                decoded = rt.stats["decode_steps"] != steps_before
                if self.tracing and decoded:
                    rec.decode_ctx.append(self._decoded_rows(before,
                                                             pp_before))
                with self._ann("stamp"):
                    self._after_step(t)
                # the trace starts and stops right after a decode step,
                # whose token read-back leaves no device work in flight
                if decoded and trace_from is not None:
                    if traced is None and t >= trace_from:
                        jax.profiler.start_trace(str(trace_dir))
                        self.tracing = True
                        traced = [self.clock() - self.t_origin, None]
                    elif self.tracing and t >= traced[0] + trace_for:
                        jax.profiler.stop_trace()
                        self.tracing = False
                        traced[1] = self.clock() - self.t_origin
            else:
                nxt = (self.s.due[self.next] if self.next < len(self.s)
                       else t1)
                with self._ann("sleep"):
                    time.sleep(max(0.0, min(nxt, t1) - now))
        if self.tracing:
            jax.block_until_ready(rt.cache)
            jax.profiler.stop_trace()
            self.tracing = False
            traced[1] = self.clock() - self.t_origin
        return traced


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def end_to_end(rec: Record, t0: float, t1: float) -> dict:
    first = [st[0] if st else None for st in rec.stamps]
    ttft = stats.censored_waits(rec.due, first, t0, t1)
    itl = stats.gaps_ending_in(rec.stamps, t0, t1)
    return {"ttft_p95_s": stats.percentile(ttft, 95),
            "itl_p95_s": stats.percentile(itl, 95),
            "out_tok_s": stats.count_in(rec.stamps, t0, t1) / (t1 - t0),
            "_n_ttft": len(ttft), "_n_itl": len(itl)}


def lateness(rec: Record, t0: float, t1: float) -> dict:
    late = [s - d for s, d in zip(rec.submit, rec.due)
            if s is not None and t0 <= d < t1]
    return {"p50": stats.percentile(late, 50),
            "p99": stats.percentile(late, 99),
            "max": max(late) if late else None, "n": len(late)}


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------

@dataclass
class RunView:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) sees."""
    spec: dict
    config: dict
    peaks: dict
    rec: Record
    window: tuple
    window_spans: list       # (name, start_s, dur_s, args) starting in window
    traced_spans: list       # the same, in the traced slice
    trace: dict | None       # bench.trace_reduce.reduce() of the slice

    @property
    def block(self) -> int:
        return self.config["serve"]["block_size"]

    def kernel(self, name: str):
        return load_module(ROOT / "bench" / "kernels" / f"{name}.py")

    def kernel_match(self, name: str):
        """The kernel's test for its operations' names in the trace."""
        return self.kernel(name).in_trace


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class MetricMissing(RuntimeError):
    """A per-layer metric that BENCHMARK.json declares for the cell found
    nothing to read."""


def read_per_layer(names, view: RunView) -> dict:
    """Every metric in ``names`` from its reader; a reader that finds
    nothing to read returns None, and a metric the cell declares must not."""
    out, missing = {}, []
    for name in names:
        m = load_module(ROOT / "bench" / "metrics" / f"{name}.py")
        v = m.read(view)
        if v is None:
            missing.append(name)
        else:
            out[name] = {"value": float(v), "unit": m.UNIT}
    if missing:
        raise MetricMissing(f"declared per-layer metrics read nothing: "
                            f"{missing} (read: {out})")
    return out


def telemetry_spans(tele, clock_offset: float, lo: float, hi: float):
    """The program's telemetry spans whose start lies in [lo, hi), in
    harness seconds."""
    out = []
    for ph, name, ts, dur, _pid, _tid, args in tele.tracer.events:
        if ph != "X":
            continue
        start = ts / 1e6 + clock_offset
        if lo <= start < hi:
            out.append((name, start, dur / 1e6, args or {}))
    return out


def reduce_trace(path) -> dict:
    """What the per-layer readers see of the profiler trace at ``path``:
    ``bench/trace_reduce.py``'s keys and ``bench/trace_spans.py``'s
    (``idle_by_span``, ``modules``)."""
    from bench import trace_reduce, trace_spans
    return {**trace_reduce.reduce(path), **trace_spans.reduce(path)}


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device {kind!r} in bench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclass
class Setup:
    """A cell ready to serve: its device, sizes, weights and runtime."""
    dev: object
    n_devices: int
    conf: dict
    mix: dict
    spec: dict
    params: object
    rt: object
    tele: object


def setup_cell(cell: dict, seed: int, *, trace: bool, require_tpu: bool,
               t_start: float) -> Setup:
    """Check the device, make the weights from the seed and warm every
    program the cell's traffic uses."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise NoChip(f"cell {cell['name']} needs {cell['chips']} TPU chip(s);"
                     f" JAX found {len(devices)} {dev.platform} device(s)")
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")

    from repro.serve.runtime import ServeRuntime
    from repro.serve.telemetry import Telemetry
    from bench.weights import make_params

    conf, mix = cell["config"], cell["traffic"]
    spec = model_spec(conf)
    _, _, sc = program_config(conf, spec)
    srv = conf["serve"]
    say(f"cell {cell['name']}: {conf['arch']} layers={spec['layers']} "
        f"d={spec['d']} heads={spec['heads']}/{spec['kv_heads']} "
        f"head_dim={spec['head_dim']} ffn={spec['ffn']} vocab={spec['vocab']} "
        f"mux N={spec['n_mux']}; {srv['rows']} rows, capacity "
        f"{srv['capacity']}, block {srv['block_size']}, chunk {srv['chunk']},"
        f" {srv['dtype']}; traffic {traffic_mod.describe(mix)}")

    params = make_params(spec, seed, getattr(jnp, srv["dtype"]),
                         tuple(srv.get("f32_leaves", ())))
    jax.block_until_ready(params)
    t_params = time.perf_counter() - t_start
    tele = Telemetry(annotate=True) if trace else None
    rt = ServeRuntime(params, sc, srv["rows"], chunk=srv["chunk"],
                      pad_id=PAD_ID, use_kernels=srv["use_kernels"],
                      telemetry=tele)
    if rt.stats["prefill_mode"] != "chunked":
        raise RuntimeError("the runtime fell back to blocking prefill")
    n_eager = warm_eager_block_ops(rt, conf, mix)
    warm_step_programs(rt, np.random.default_rng(seed), spec["vocab"])
    say(f"set-up: weights {t_params:.2f} s, programs warm at "
        f"{time.perf_counter() - t_start:.2f} s ({dict(rt.trace_counts)}; "
        f"{n_eager} block-reset lengths)")
    return Setup(dev=dev, n_devices=len(devices), conf=conf, mix=mix,
                 spec=spec, params=params, rt=rt, tele=tele)


def serve(st: Setup, schedule, seconds: float, trace_path=None):
    """Drive ``schedule`` (warm-up, then a window of ``seconds``) through
    the runtime; returns (driver, traced interval, compiles in window)."""
    t0 = schedule.window_start
    counter = CompileCounter.get()
    drv = Driver(st.rt, schedule, tele=st.tele)
    before = {}
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        traced = drv.run(
            t0, t0 + seconds,
            lambda: before.setdefault("n", counter.n),
            trace_from=(t0 + TRACE_START * seconds
                        if trace_path is not None else None),
            trace_for=min(TRACE_SECONDS, 0.5 * seconds),
            trace_dir=trace_path)
    finally:
        gc.enable()
        gc.unfreeze()
    return drv, traced, counter.n - before["n"]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, trace_dir=None,
             control: bool = False) -> dict:
    """Run one cell once; returns the result object (see module doc).
    ``t_start``: ``perf_counter()`` when the process started (set-up time
    counts from there).  ``control`` puts the output check's control (the
    float8 reference) in the program's place on the same sample, which
    has to make ``correct`` false; the program's own reading is kept
    under ``readings``."""
    st = setup_cell(cell, seed, trace=trace, require_tpu=require_tpu,
                    t_start=t_start)
    dev, conf, spec = st.dev, st.conf, st.spec
    schedule = traffic_mod.make_schedule(st.mix, seed, seconds, spec["vocab"])
    t0 = schedule.window_start
    t1 = t0 + seconds
    trace_path = None
    if trace:
        import tempfile
        trace_path = Path(trace_dir) if trace_dir else Path(
            tempfile.mkdtemp(prefix="bench_trace_"))
    drv, traced, window_compiles = serve(st, schedule, seconds, trace_path)
    setup_s = drv.t_origin + t0 - t_start
    rec = drv.rec
    e2e = end_to_end(rec, t0, t1)
    late = lateness(rec, t0, t1)
    attempted = sum(1 for d in rec.due if t0 <= d < t1)
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    say(f"window: {seconds} s, {attempted} requests due, "
        f"{e2e['_n_ttft']} TTFT samples, {e2e['_n_itl']} token gaps, "
        f"{rec.steps} engine steps in all")
    ttft = stats.censored_waits(rec.due, [s[0] if s else None
                                          for s in rec.stamps], t0, t1)
    say("TTFT percentiles (p50, p90, p95, p99) in s: "
        f"{[stats.percentile(ttft, q) for q in (50, 90, 95, 99)]}; "
        f"output tokens in the window: {stats.count_in(rec.stamps, t0, t1)}")
    say(f"generator lateness (submit - due) in the window: p50 "
        f"{late['p50']} s, p99 {late['p99']} s, max {late['max']} s")
    say(f"peak HBM: peak_bytes_in_use={peak} "
        f"bytes_limit={mem.get('bytes_limit')}")
    say(f"compiles inside the window: {window_compiles}")

    metrics = {"setup_s": {"value": setup_s, "unit": "s"},
               "ttft_p95_s": {"value": e2e["ttft_p95_s"], "unit": "s"},
               "itl_p95_s": {"value": e2e["itl_p95_s"], "unit": "s"},
               "out_tok_s": {"value": e2e["out_tok_s"], "unit": "tokens/s"}}
    if cell.get("end_to_end") is not None:
        metrics = {k: v for k, v in metrics.items()
                   if k in cell["end_to_end"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": st.n_devices, "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        tele = st.tele
        offset = (time.perf_counter() - tele.tracer.now_us() / 1e6
                  - drv.t_origin)
        red = reduce_trace(trace_path)
        view = RunView(spec=spec, config=conf,
                       peaks=peaks_for(dev.device_kind) if require_tpu else {},
                       rec=rec, window=(t0, t1),
                       window_spans=telemetry_spans(tele, offset, t0, t1),
                       traced_spans=telemetry_spans(tele, offset, *traced),
                       trace=red)
        metrics = read_per_layer(cell["per_layer"], view)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["idle_by_host"]}
        say(f"trace: busy {red['busy_s']} s of {red['window_s']} s; "
            f"{len(view.traced_spans)} program spans in the traced slice")
        if not trace_dir:
            import shutil
            shutil.rmtree(trace_path, ignore_errors=True)

    # free the program's state before the reference runs
    finished = [g for g in rec.groups
                if all(rec.reqs[k].done for k in g["slots"].values())]
    served = {k: list(rec.reqs[k].output) for g in finished
              for k in g["slots"].values()}
    params = st.params
    del st, drv
    gc.collect()

    checks = {"window_compiles": {"value": window_compiles, "limit": 0,
                                  "holds": "<="}}
    from bench.check import check_groups
    found, readings = check_groups(params, spec, conf, schedule, finished,
                                   served, seed,
                                   quant="fp8" if control else None)
    checks.update(found)
    correct = all(_holds(c) for c in checks.values())
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if control:
        out["readings"] = readings
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def _holds(c) -> bool:
    v, lim = c["value"], c["limit"]
    if v is None or (isinstance(v, float) and math.isnan(v)):
        return False
    return v <= lim if c["holds"] == "<=" else v >= lim

