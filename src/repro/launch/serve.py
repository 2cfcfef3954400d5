"""Serving launcher: batched multiplexed inference.

Two modes (DESIGN.md):

  * fill-drain (default): ``MuxBatcher`` packs requests into the
    N_mux × B grid; spare slots duplicate live requests and the averaged
    logits implement the paper's ensembling mode.
  * continuous (``--continuous``): requests join and leave the decode
    loop every step.  ``--cache ring`` re-prefills the whole grid
    whenever the composition changes (the ring layout's shared position
    vector allows nothing finer); ``--cache paged`` runs the
    ``serve.runtime.ServeRuntime`` — jitted shape-stable steps, prompts
    prefilled in fixed-size chunks interleaved with decode
    (``--prefill chunked``, the default) or whole-prompt at admission
    (``--prefill blocking``, the measured baseline).

    python -m repro.launch.serve --arch qwen2-1.5b --mux-n 2 \
        --requests 8 --new-tokens 8
    python -m repro.launch.serve --arch qwen2-1.5b --continuous \
        --cache paged --requests 8 --new-tokens 8 --temperature 0.8

  * width lanes (``--lanes 1,4,8``, DESIGN.md §width lanes): several
    paged runtimes at different mux widths served side by side; each
    request's SLO class (``--slo-mix``) picks its lane — the narrow lane
    for latency, wide lanes for throughput — with spill-over when a lane
    saturates and an optional shared block budget (``--pool-budget``)
    rebalanced across lanes:

    python -m repro.launch.serve --arch qwen2-1.5b --continuous \
        --cache paged --lanes 1,4,8 \
        --slo-mix latency=0.25,balanced=0.5,throughput=0.25 \
        --requests 12 --new-tokens 8

  * fault injection / elastic resize (DESIGN.md §fault tolerance):
    ``--shards 2 --kill-shard 4:1`` kills a data shard mid-run — its
    streams replay from host token logs onto the survivors;
    ``--drain-lane STEP:WIDTH`` / ``--add-lane STEP:WIDTH`` resize the
    lane set under traffic without dropping a stream; ``--restart-step
    STEP --ckpt-dir DIR`` snapshots the full serving state (KV pages +
    block tables + scheduler) and hot-restores a rebuilt runtime — a
    restart re-jits but never re-prefills live rows:

    python -m repro.launch.serve --arch qwen2-1.5b --continuous \
        --cache paged --shards 2 --kill-shard 6:1 --requests 8 \
        --new-tokens 8

Sampling (``serve.sampling``) is per-stream: ``--temperature``,
``--top-k`` and ``--top-p`` set every request's policy here, with the
request uid as its seed; programmatic callers attach a ``SamplingParams``
per request instead.
"""
from __future__ import annotations

import argparse
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MuxSpec
from repro.configs import get_config, model_kind
from repro.launch.compile_cache import enable_compile_cache
from repro.models import TransformerLM, VLM, EncDecLM
from repro.serve import (ServeConfig, init_cache, prefill, decode_step,
                         MuxBatcher, Request, sampling)
from repro.serve.engine import lane_config
from repro.serve.recovery import RecoverySupervisor
from repro.serve.router import LaneRouter, LaneSpec, SLO_CLASSES
from repro.serve.runtime import ServeRuntime
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.telemetry import NULL_TELEMETRY, Telemetry

# stats keys merged across a --restart-step process swap: counters sum,
# per-step traces concatenate (old process first)
_COUNTER_KEYS = ("prefill_tokens", "prefill_compute_tokens",
                 "prefill_events", "decode_steps")
_TRACE_KEYS = ("prefill_log", "slot_util", "cache_util")


def _sample_grid(sched, logits, default_sampling):
    """Sample one token per grid slot (mux-major instance order) with
    each slot's own SamplingParams."""
    plist, steps = [], []
    for i in range(sched.n_mux):
        for j in range(sched.backbone_batch):
            r = sched.slots[j][i].request
            plist.append((r.sampling or default_sampling)
                         if r is not None else None)
            steps.append(len(r.output) if r is not None else 0)
    if all(p is None or p.temperature <= 0 for p in plist):
        return np.asarray(sampling.greedy(logits))    # skip sampler machinery
    return np.asarray(sampling.sample_params(
        logits, plist, np.asarray(steps, np.int32)))


def _lane_event(ev, router, sup, params_by_width, sc, backbone_rows,
                *, step, chunk, prefill_mode, pad_id, default_sampling,
                on_prefill, mesh, use_kernels, telemetry):
    """Apply one failure/resize event to the lane set (DESIGN.md §fault
    tolerance): ``kill_shard`` fences a data shard of one lane's grid,
    ``drain_lane`` starts removing the lane at a width (streams finish
    in place, queued work re-routes), ``add_lane`` brings up a fresh
    runtime at a new width under traffic."""
    op = ev["op"]
    if op == "kill_shard":
        idx = router._index_of(ev.get("lane", 0))
        sup.kill_shard(router.runtimes[idx], ev["shard"])
    elif op == "drain_lane":
        width = ev["width"]
        lane = next((rt.lane for rt in router.runtimes
                     if rt.n_mux == width), None)
        if lane is None:
            raise ValueError(f"drain_lane: no lane at width {width}")
        sup.drain_lane(router, lane, step=step)
    elif op == "add_lane":
        width = ev["width"]
        if width not in params_by_width:
            raise ValueError(f"add_lane: no params for width {width}")
        lane_id = 1 + max(rt.lane for rt in
                          router.runtimes + router.retired)
        rt = ServeRuntime(
            params_by_width[width], lane_config(sc, width),
            ev.get("rows", backbone_rows),
            chunk=None if prefill_mode == "blocking"
            else ev.get("chunk", chunk),
            pad_id=pad_id, default_sampling=default_sampling,
            on_prefill=on_prefill, mesh=mesh, use_kernels=use_kernels,
            lane=lane_id, telemetry=telemetry)
        sup.add_lane(router, rt)
    else:
        raise ValueError(f"unknown serve event op {op!r}")


def _run_lanes(params_by_width, sc: ServeConfig, backbone_rows: int,
               arrivals, lanes, *, pad_id, on_prefill, chunk, prefill_mode,
               default_sampling, mesh, use_kernels, pool_budget,
               spill_queue, telemetry, events=None, ckpt_dir=None,
               route="load", fence_stragglers=False):
    """Width-lane serve loop (DESIGN.md §width lanes): one ``ServeRuntime``
    per lane at that lane's mux width, ``LaneRouter`` admitting each
    arrival by SLO class + live lane load, all lanes stepping in lockstep
    (narrowest lane first — latency lanes admit before throughput lanes
    contend for rebalanced pool quota).

    Every lane keeps the single-width runtime's guarantees lane-locally:
    token streams identical to a fixed-width run at the lane's N fed the
    same sub-schedule, compile counts 1 decode + one per bucket per
    width (asserted via ``check_compile_once`` before returning), and
    backpressure (rollback / preemption) confined to the lane's own pool
    partition.

    Disaggregated roles (DESIGN.md §disaggregated): lanes whose
    ``LaneSpec.role`` is ``"prefill"``/``"decode"`` split the two serve
    phases across dedicated runtimes.  After every lockstep step the
    loop runs a handoff pass: each prefill lane's finished rows migrate
    (KV pages + sampled next token, no re-prefill) onto a free row of a
    same-width decode lane picked by ``router.handoff_targets``;
    requests a decode lane bounced back (preemption, shard-loss replay)
    drain through the router to a prefill-capable lane.
    """
    specs = [s if isinstance(s, LaneSpec)
             else LaneSpec(n_mux=int(s), rows=backbone_rows, chunk=chunk)
             for s in lanes]
    runtimes = []
    for idx, spec in enumerate(specs):
        if spec.n_mux not in params_by_width:
            raise ValueError(
                f"lanes mode needs params per width: missing width "
                f"{spec.n_mux} in {sorted(params_by_width)}")
        sc_l = lane_config(sc, spec.n_mux)
        runtimes.append(ServeRuntime(
            params_by_width[spec.n_mux], sc_l, spec.rows,
            chunk=None if prefill_mode == "blocking" else spec.chunk,
            pad_id=pad_id, default_sampling=default_sampling,
            on_prefill=on_prefill, mesh=mesh, use_kernels=use_kernels,
            lane=idx, telemetry=telemetry, role=spec.role))
    disagg = any(rt.role != "both" for rt in runtimes)
    for rt in runtimes:
        # a prefill lane with nowhere to hand off would park finished
        # rows forever — fail at construction, not mid-traffic
        if rt.role == "prefill" and not any(
                d.role != "prefill" and d.n_mux == rt.n_mux
                for d in runtimes):
            raise ValueError(
                f"prefill lane at width {rt.n_mux} has no same-width "
                f"decode-capable lane to hand off to")
    router = LaneRouter(runtimes, budget=pool_budget,
                        spill_queue=spill_queue, telemetry=telemetry,
                        mode=route)
    sup = RecoverySupervisor(ckpt_dir=ckpt_dir, telemetry=telemetry)
    if fence_stragglers:
        sup.enable_straggler_fencing()
    pending = collections.deque(
        sorted(events or [], key=lambda e: e["step"]))
    arrivals = collections.deque(sorted(arrivals, key=lambda a: a[0]))
    uid, step = 0, 0
    t0 = time.time()
    while (arrivals or pending
           or any(rt.has_work() for rt in router.runtimes)):
        while pending and pending[0]["step"] <= step:
            _lane_event(pending.popleft(), router, sup, params_by_width,
                        sc, backbone_rows, step=step, chunk=chunk,
                        prefill_mode=prefill_mode, pad_id=pad_id,
                        default_sampling=default_sampling,
                        on_prefill=on_prefill, mesh=mesh,
                        use_kernels=use_kernels, telemetry=telemetry)
        if disagg:
            # requests a decode lane bounced back into its own queue
            # (preemption rollback, shard-loss replay) cannot prefill
            # there — drain them through the router to a
            # prefill-capable lane before this step's admissions
            for rt in router.runtimes:
                if rt.role != "decode":
                    continue
                while rt.sched.queue:
                    r = rt.sched.queue.popleft()
                    i = router.route(r)
                    r.routed_step = step
                    router.runtimes[i].submit(r)
        while arrivals and arrivals[0][0] <= step:
            a = arrivals.popleft()
            r = Request(uid=uid, prompt=list(a[1]), max_new=a[2],
                        sampling=a[3] if len(a) > 3 else None,
                        slo=a[4] if len(a) > 4 else None)
            uid += 1
            i = router.route(r)
            r.routed_step = step
            router.runtimes[i].submit(r)
        router.rebalance()
        # step order: narrow lanes first, so the latency lane's
        # admissions land before wider lanes draw on freshly rebalanced
        # quota (recomputed per step — resize changes the lane set)
        for rt in sorted(router.runtimes, key=lambda rt: rt.n_mux):
            t_step = time.time()
            rt.step()
            if sup.fencing_enabled and rt.sc.n_shards >= 2:
                dt = time.time() - t_step
                sup.observe_shard_times(rt, {
                    s: dt for s in range(rt.sc.n_shards)
                    if s not in rt.sched.dead_shards})
        if disagg:
            # handoff pass: stream each prefill lane's finished rows to
            # a free row of a same-width decode lane — KV pages migrate
            # across pool partitions, the row's streams keep decoding
            # from their already-sampled next token (zero re-prefill)
            for rt in router.runtimes:
                if rt.role != "prefill":
                    continue
                for j in rt.handoff_ready():
                    for i in router.handoff_targets(rt.n_mux):
                        dst = router.runtimes[i]
                        rows = dst.free_rows()
                        if not rows:
                            continue
                        before = rt.stats["migrated_bytes"]
                        plan = rt.handoff_to(dst, j, rows[0])
                        if plan is not None:
                            sup.note_handoff(
                                plan, rt.stats["migrated_bytes"] - before)
                            break
                    # no target had a free row: the row parks on the
                    # prefill lane and retries next step (backpressure,
                    # not an error)
        sup.note_step()
        sup.pop_drained(router)
        step += 1
        telemetry.maybe_snapshot(step)
    # retired (drained) lanes keep their runtimes so the compile-once
    # and stats contracts still cover every lane that ever served;
    # lane-id order == construction order when no resize happened
    all_lanes = sorted(router.runtimes + router.retired,
                       key=lambda rt: rt.lane)
    for rt in all_lanes:
        rt.check_compile_once()
    wall = time.time() - t0
    completed = [r for rt in all_lanes for r in rt.stats["completed"]]
    stats = {
        # per-lane goodput accounting (TTFT-SLO attainment × tok/s)
        "lane_stats": router.lane_stats(wall=wall),
        "lanes": [rt.stats for rt in all_lanes],
        "widths": [rt.n_mux for rt in all_lanes],
        "pools": [rt.pool for rt in all_lanes],
        "routing": router.counters,
        "completed": completed,
        "wall": wall,
        "generated_tokens": sum(len(r.output) for r in completed),
        "prefill_mode": all_lanes[0].stats["prefill_mode"],
        "recovery": sup.stats,
        # aggregates over lanes (sums for counters, concatenation for
        # per-step traces) so single-width consumers keep working
        "prefill_tokens": sum(rt.stats["prefill_tokens"]
                              for rt in all_lanes),
        "prefill_compute_tokens": sum(rt.stats["prefill_compute_tokens"]
                                      for rt in all_lanes),
        "prefill_events": sum(rt.stats["prefill_events"]
                              for rt in all_lanes),
        "decode_steps": sum(rt.stats["decode_steps"] for rt in all_lanes),
        "slot_util": [u for rt in all_lanes
                      for u in rt.stats["slot_util"]],
        "cache_util": [u for rt in all_lanes
                       for u in rt.stats["cache_util"]],
    }
    return stats


def run_continuous(params, sc: ServeConfig, backbone_rows: int, arrivals,
                   *, pad_id: int = 0, on_prefill=None, chunk: int = 32,
                   prefill_mode: str = "chunked", default_sampling=None,
                   mesh=None, use_kernels: bool = False, lanes=None,
                   pool_budget=None, spill_queue=None, telemetry=None,
                   events=None, ckpt_dir=None, route: str = "load",
                   fence_stragglers: bool = False):
    """Continuous-batching serve loop for both cache layouts.

    arrivals: iterable of (step, prompt_tokens, max_new[, SamplingParams
    [, slo_class]]), sorted by step.  Each loop iteration admits what it
    can, then runs one decode step over the grid.  Returns a stats dict.

    events: optional failure/resize schedule (DESIGN.md §fault
    tolerance) — dicts of ``{"step": K, "op": ...}`` applied before
    step K's admissions, orchestrated by a
    ``serve.recovery.RecoverySupervisor`` whose accounting lands in
    ``stats["recovery"]``.  Paged single-runtime ops: ``kill_shard``
    (``shard``; needs ``sc.n_shards >= 2`` — lost streams replay onto
    surviving shards) and ``restart`` (snapshot + rebuild + restore;
    needs ``ckpt_dir``).  Lanes-mode ops: ``kill_shard`` (``shard``,
    optional ``lane``), ``drain_lane`` (``width``) and ``add_lane``
    (``width``, optional ``rows``/``chunk`` — ``params`` must carry
    that width).  ckpt_dir: checkpoint directory for the hot KV-pool
    snapshot/restore path.

    telemetry: optional ``serve.telemetry.Telemetry`` — streaming SLO
    metrics, the step-span trace and periodic registry snapshots
    (``Telemetry(snapshot_every=K)``), threaded through every layer of
    the serve stack.  Telemetry never changes what is computed: token
    streams and compile counts are identical with it on or off
    (DESIGN.md §observability).

    mesh: optional ('data', 'model') mesh (``launch.mesh.make_serve_mesh``)
    for the paged runtime — rows/pool shards over 'data', tensor
    parallelism over 'model'; requires ``sc.n_shards`` == data-axis size.

    lanes: optional width-lane serving (DESIGN.md §width lanes): a
    sequence of mux widths (ints) or ``serve.router.LaneSpec``s.  One
    ``ServeRuntime`` is hosted per lane at that lane's width and
    ``serve.router.LaneRouter`` admits each arrival to a lane from its
    SLO class (the 5th arrival element) and live lane load.  ``params``
    must then be a mapping {width: params} (one trained model per mux
    width) and ``sc`` is the width-agnostic base config
    (``engine.lane_config`` derives each lane's).  pool_budget /
    spill_queue are forwarded to the router.

    Disaggregated serving (DESIGN.md §disaggregated): ``LaneSpec``s
    with ``role="prefill"``/``role="decode"`` dedicate lanes to one
    phase — finished prefill rows migrate their KV pages onto a
    same-width decode lane without re-prefill.  route: ``"load"``
    (default) routes on live lane load; ``"goodput"`` stable-sorts
    admission and handoff targets on each lane's published goodput
    (TTFT-SLO attainment × tok/s).  fence_stragglers: arm per-shard
    step-time ``StragglerDetector``s — a shard flagged alone is fenced
    via the shard-loss replay path before it fails outright.

    Prefill accounting (consistent across arms — DESIGN.md):
      * ``prefill_tokens``          — backbone token-positions processed
                                      (per-row tokens × rows touched);
      * ``prefill_compute_tokens``  — same, after shape-bucket padding
                                      (the compute actually dispatched);
      * ``prefill_log``             — (rows, per_row_tokens) per event;
        ``on_prefill(rows, per_row_tokens)`` mirrors the log entries.

    ring:  admission re-prefills the WHOLE grid from every row's current
           tokens (the shared slot-position vector makes positions
           uniform across rows, so one row cannot be rebuilt alone).
    paged: ``ServeRuntime`` — a joining row's prompt advances one chunk
           per engine step while live rows keep decoding
           (``prefill_mode='chunked'``), or is prefilled whole at
           admission (``'blocking'``, the pre-runtime baseline).
    """
    if sc.kind != "lm":
        raise NotImplementedError(
            "continuous serving supports decoder-only LM families")
    if mesh is not None and sc.cache_layout != "paged":
        raise ValueError("mesh serving requires the paged cache layout")
    if telemetry is None:
        telemetry = NULL_TELEMETRY
    if lanes is not None:
        if sc.cache_layout != "paged":
            raise ValueError(
                "width-lane serving requires the paged cache layout")
        return _run_lanes(params, sc, backbone_rows, arrivals, lanes,
                          pad_id=pad_id, on_prefill=on_prefill, chunk=chunk,
                          prefill_mode=prefill_mode,
                          default_sampling=default_sampling, mesh=mesh,
                          use_kernels=use_kernels, pool_budget=pool_budget,
                          spill_queue=spill_queue, telemetry=telemetry,
                          events=events, ckpt_dir=ckpt_dir, route=route,
                          fence_stragglers=fence_stragglers)
    if events and sc.cache_layout != "paged":
        raise ValueError("failure/resize events require the paged layout")
    arrivals = collections.deque(sorted(arrivals, key=lambda a: a[0]))
    uid = 0
    t0 = time.time()

    def _pop_arrivals(step, submit):
        nonlocal uid
        while arrivals and arrivals[0][0] <= step:
            a = arrivals.popleft()
            sp = a[3] if len(a) > 3 else None
            submit(Request(uid=uid, prompt=list(a[1]), max_new=a[2],
                           sampling=sp))
            uid += 1

    if sc.cache_layout == "paged":
        def make_rt():
            return ServeRuntime(
                params, sc, backbone_rows,
                chunk=None if prefill_mode == "blocking" else chunk,
                pad_id=pad_id, default_sampling=default_sampling,
                on_prefill=on_prefill, mesh=mesh,
                use_kernels=use_kernels, telemetry=telemetry)

        rt = make_rt()
        sup = RecoverySupervisor(ckpt_dir=ckpt_dir, telemetry=telemetry)
        if fence_stragglers:
            sup.enable_straggler_fencing()
        pending = collections.deque(
            sorted(events or [], key=lambda e: e["step"]))
        step = 0
        while arrivals or pending or rt.has_work():
            while pending and pending[0]["step"] <= step:
                ev = pending.popleft()
                if ev["op"] == "kill_shard":
                    sup.kill_shard(rt, ev["shard"])
                elif ev["op"] == "restart":
                    # simulated process restart: hot snapshot, fresh
                    # runtime (fresh jit caches — the restart pays a
                    # re-trace, never a re-prefill), restore, and carry
                    # the old process's delivered results + counters
                    sup.snapshot(rt, step)
                    old = rt
                    rt = make_rt()
                    sup.restore(rt)
                    rt.sched.completed[:0] = old.sched.completed
                    for k in _COUNTER_KEYS:
                        rt.stats[k] += old.stats[k]
                    for k in _TRACE_KEYS:
                        rt.stats[k][:0] = old.stats[k]
                else:
                    raise ValueError(f"unknown serve event op "
                                     f"{ev['op']!r}")
            _pop_arrivals(step, rt.submit)
            t_step = time.time()
            rt.step()
            if sup.fencing_enabled and sc.n_shards >= 2:
                dt = time.time() - t_step
                sup.observe_shard_times(rt, {
                    s: dt for s in range(sc.n_shards)
                    if s not in rt.sched.dead_shards})
            sup.note_step()
            step += 1
            telemetry.maybe_snapshot(step)
        stats = rt.stats
        stats["recovery"] = sup.stats
        stats["wall"] = time.time() - t0
        stats["generated_tokens"] = sum(
            len(r.output) for r in stats["completed"])
        return stats

    # ------------------------------------------------------------- ring
    n_mux = max(sc.mux.n, 1)
    nrows = backbone_rows
    nb_inst = n_mux * nrows
    sched = ContinuousScheduler(n_mux=n_mux, backbone_batch=nrows,
                                max_len=sc.capacity, telemetry=telemetry)
    stats = {"prefill_tokens": 0, "prefill_compute_tokens": 0,
             "prefill_events": 0, "decode_steps": 0,
             "prefill_log": [], "slot_util": [], "cache_util": [],
             "completed": sched.completed}
    next_tok = np.zeros((n_mux, nrows), np.int32)
    cache, grid_pos = None, 0

    def _clear_dead_slots():
        for i in range(n_mux):
            for j in range(nrows):
                if sched.slots[j][i].request is None:
                    next_tok[i, j] = pad_id

    step = 0
    while arrivals or sched.queue or sched.n_active:
        _pop_arrivals(step, sched.submit)

        # -- admission ---------------------------------------------------
        if sched.admit() or (sched.n_active and grid_pos >= sc.capacity):
            # ring: any composition change -> grid-wide re-prefill of
            # every row's prompt + generated tokens, padded to a common
            # length; this *is* the cost the paged layout removes.  The
            # same rebuild fires when the physical write position reaches
            # capacity: padding gaps let grid_pos outrun the logical
            # lengths, and re-prefilling compacts positions before the
            # ring would wrap over live context.  (Live lengths are
            # < capacity — record_tokens retires at max_len — so each
            # rebuild strictly lowers grid_pos: progress is guaranteed.)
            grids = [sched.row_prompts(j, pad_id) for j in range(nrows)]
            l_pad = max(g.shape[1] for g in grids)
            arr = np.full((n_mux, nrows, l_pad), pad_id, np.int32)
            for j, g in enumerate(grids):
                arr[:, j, :g.shape[1]] = g
            cache = init_cache(sc, nb_inst)
            with telemetry.span("prefill", tokens=l_pad * nrows):
                logits, cache = prefill(
                    params, sc, cache,
                    jnp.asarray(arr.reshape(nb_inst, l_pad)))
            grid_pos = l_pad
            stats["prefill_tokens"] += l_pad * nrows
            stats["prefill_compute_tokens"] += l_pad * nrows
            stats["prefill_events"] += 1
            stats["prefill_log"].append((tuple(range(nrows)), l_pad))
            if on_prefill is not None:
                on_prefill(tuple(range(nrows)), l_pad)
            toks = _sample_grid(sched, logits, default_sampling)   # (NB,)
            sched.record_tokens(toks)
            next_tok = toks.reshape(n_mux, nrows).astype(np.int32)

        # -- one decode step over the grid -------------------------------
        if sched.n_active:
            _clear_dead_slots()
            toks_in = jnp.asarray(next_tok.reshape(-1))[:, None]
            with telemetry.span("decode", metric="decode_step_s"):
                logits, cache = decode_step(params, sc, cache, toks_in,
                                            grid_pos)
                out = _sample_grid(sched, logits[:, 0], default_sampling)
            sched.record_tokens(out)
            next_tok = out.reshape(n_mux, nrows).astype(np.int32)
            stats["decode_steps"] += 1
            stats["slot_util"].append(sched.utilization())
            grid_pos += 1
            stats["max_grid_pos"] = max(
                stats.get("max_grid_pos", 0), grid_pos)
            stats["cache_util"].append(
                min(grid_pos, sc.capacity) / sc.capacity
                if sched.n_active else 0.0)
        step += 1
        telemetry.maybe_snapshot(step)
    stats["wall"] = time.time() - t0
    stats["generated_tokens"] = sum(len(r.output) for r in sched.completed)
    return stats


def _fill_drain(params, sc, cfg, kind, args, default_sampling):
    import dataclasses
    batcher = MuxBatcher(n_mux=sc.mux.n, backbone_batch=args.backbone_batch)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        r = batcher.submit(rng.integers(
            4, cfg.vocab_size, size=(args.prompt_len,)).astype(np.int32),
            max_new=args.new_tokens)
        if default_sampling is not None:
            # per-request seed: streams must not draw correlated noise
            r.sampling = dataclasses.replace(default_sampling, seed=r.uid)

    def _sample(ens, slots_unique, t):
        plist = [r.sampling or default_sampling for r in slots_unique]
        if all(p is None or p.temperature <= 0 for p in plist):
            return sampling.greedy(ens)
        return sampling.sample_params(ens, plist, t)

    served = 0
    t0 = time.time()
    while True:
        slots, owners = batcher.next_batch()
        if slots is None:
            break
        uniq = list({id(s): s for s in slots}.values())
        prompts = jnp.stack([jnp.asarray(s.prompt) for s in slots])
        cache = init_cache(sc, prompts.shape[0])
        extra = None
        if kind == "vlm":
            extra = jnp.zeros((prompts.shape[0], cfg.frontend_len, 1024),
                              jnp.float32)
        elif kind == "encdec":
            extra = jnp.zeros(
                (prompts.shape[0], cfg.encoder.frontend_len,
                 cfg.encoder.d_model), jnp.float32)
        logits, cache = prefill(params, sc, cache, prompts, extra=extra)
        n_unique = len(uniq)
        ens = MuxBatcher.combine_logits(logits, owners, n_unique)
        tok_unique = _sample(ens, uniq, 0)
        toks = tok_unique[jnp.asarray(owners)][:, None]
        outs = [tok_unique]
        for t in range(args.new_tokens - 1):
            lg, cache = decode_step(params, sc, cache, toks,
                                    args.prompt_len + t)
            ens = MuxBatcher.combine_logits(lg[:, 0], owners, n_unique)
            tok_unique = _sample(ens, uniq, t + 1)
            toks = tok_unique[jnp.asarray(owners)][:, None]
            outs.append(tok_unique)
        served += n_unique
        for j, s in enumerate(uniq):
            s.output = [int(o[j]) for o in outs]
            s.done = True
    dt = time.time() - t0
    print(f"served {served} requests x {args.new_tokens} tokens in "
          f"{dt:.1f}s  (mux N={sc.mux.n}, backbone batch "
          f"{args.backbone_batch}; throughput "
          f"{served * args.new_tokens / dt:.1f} tok/s)")


def _parse_slo_mix(ap, spec: str):
    """Parse 'latency=0.25,balanced=0.5,throughput=0.25' into normalized
    class weights."""
    mix = {}
    for part in spec.split(","):
        k, eq, v = part.partition("=")
        k = k.strip()
        if k not in SLO_CLASSES or not eq:
            ap.error(f"--slo-mix: expected CLASS=WEIGHT with CLASS in "
                     f"{SLO_CLASSES}, got {part!r}")
        try:
            mix[k] = float(v)
        except ValueError:
            ap.error(f"--slo-mix: bad weight in {part!r}")
    total = sum(mix.values())
    if total <= 0:
        ap.error("--slo-mix weights must sum to > 0")
    return {k: v / total for k, v in mix.items()}


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--mux-n", type=int, default=2)
    ap.add_argument("--backbone-batch", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (requests join/leave every "
                         "step) instead of fill-drain")
    ap.add_argument("--cache", choices=("ring", "paged"), default="ring",
                    help="KV-cache layout for --continuous")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged layout: tokens per KV block")
    ap.add_argument("--prefill", choices=("chunked", "blocking"),
                    default="chunked",
                    help="paged: interleave fixed-size prompt chunks with "
                         "decode, or prefill whole prompts at admission")
    ap.add_argument("--chunk", type=int, default=32,
                    help="paged chunked prefill: tokens per chunk")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="paged continuous serving on a (data, model) "
                         "device mesh, e.g. --mesh 2,4: rows + KV block "
                         "shards over 'data', tensor parallelism over "
                         "'model' (CPU: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--lanes", default=None, metavar="N1,N2,...",
                    help="width-lane serving (e.g. --lanes 1,4,8): one "
                         "paged runtime per mux width, requests routed "
                         "to lanes by SLO class + live load "
                         "(DESIGN.md §width lanes); requires "
                         "--continuous --cache paged")
    ap.add_argument("--lane-rows", default=None, metavar="R1,R2,...",
                    help="backbone rows per lane (default: "
                         "--backbone-batch for every lane)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving (DESIGN.md "
                         "§disaggregated): dedicated prefill and decode "
                         "lanes (--prefill-lanes/--decode-lanes); "
                         "finished prefill rows migrate their KV pages "
                         "to a same-width decode lane with no "
                         "re-prefill; requires --continuous "
                         "--cache paged")
    ap.add_argument("--prefill-lanes", default=None, metavar="N1,N2,...",
                    help="--disagg: mux widths of the prefill-only "
                         "lanes (each width needs a same-width entry "
                         "in --decode-lanes)")
    ap.add_argument("--decode-lanes", default=None, metavar="N1,N2,...",
                    help="--disagg: mux widths of the decode-only lanes")
    ap.add_argument("--route", choices=("load", "goodput"),
                    default="load",
                    help="lane routing signal: live lane load "
                         "(default) or published per-lane goodput "
                         "(TTFT-SLO attainment × tok/s) for admission "
                         "and handoff-target choice")
    ap.add_argument("--fence-stragglers", action="store_true",
                    help="paged continuous: arm per-shard step-time "
                         "straggler detectors — a shard flagged alone "
                         "is fenced via the shard-loss replay path "
                         "before it fails outright (needs >= 2 data "
                         "shards)")
    ap.add_argument("--slo-mix", default="balanced=1",
                    help="SLO-class mix of the synthetic trace, e.g. "
                         "latency=0.25,balanced=0.5,throughput=0.25")
    ap.add_argument("--pool-budget", type=int, default=None,
                    help="lanes: global KV block budget partitioned into "
                         "per-lane quotas; the router rebalances unused "
                         "quota toward queued lanes")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["fp32", "bf16", "int8", "fp8"],
                    help="paged continuous serving: KV-page storage dtype "
                         "(int8/fp8 store quantized pages with per-slot "
                         "scales; dequant fuses into the Pallas kernels "
                         "under --use-kernels). Default: serve dtype")
    ap.add_argument("--use-kernels", action="store_true",
                    help="paged continuous serving: route decode/chunk "
                         "attention through the Pallas paged kernels "
                         "(with --mesh: the shard_map'd shard-local "
                         "kernels; interpret mode on the CPU)")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="continuous: one request arrives every K steps")
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="paged continuous: partition rows + KV pool "
                         "into N logical data shards WITHOUT a device "
                         "mesh (host-side segments; the fault-injection "
                         "substrate for --kill-shard on one device). "
                         "With --mesh the data axis sets the shard "
                         "count instead")
    ap.add_argument("--kill-shard", action="append", default=None,
                    metavar="STEP:SHARD",
                    help="fault injection (repeatable): at engine step "
                         "STEP, kill data shard SHARD — its streams "
                         "replay from host token logs onto surviving "
                         "shards, its pool quota is reclaimed "
                         "(DESIGN.md §fault tolerance; requires "
                         "--shards/--mesh with >= 2 data shards)")
    ap.add_argument("--drain-lane", action="append", default=None,
                    metavar="STEP:WIDTH",
                    help="live resize (repeatable, needs --lanes): at "
                         "step STEP, start draining the lane at mux "
                         "width WIDTH — queued work re-routes, placed "
                         "streams finish, the lane retires when empty")
    ap.add_argument("--add-lane", action="append", default=None,
                    metavar="STEP:WIDTH[:ROWS]",
                    help="live resize (repeatable, needs --lanes): at "
                         "step STEP, add a lane at mux width WIDTH "
                         "(ROWS backbone rows, default "
                         "--backbone-batch) under traffic")
    ap.add_argument("--restart-step", type=int, default=None,
                    metavar="STEP",
                    help="paged continuous: at step STEP, snapshot the "
                         "full serving state (KV pages + block tables + "
                         "scheduler) via --ckpt-dir, rebuild the "
                         "runtime, and hot-restore — no re-prefill of "
                         "live rows")
    ap.add_argument("--ckpt-dir", default=None, metavar="PATH",
                    help="checkpoint directory for --restart-step's hot "
                         "KV-pool snapshot/restore")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="continuous: write telemetry metrics as JSON "
                         "(counters/gauges/histograms keyed lane+shard, "
                         "plus periodic snapshots) to PATH, and a "
                         "Prometheus text dump next to it (.prom)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="continuous: write the step-span timeline as "
                         "Chrome trace-event JSON to PATH (open at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="STEPS",
                    help="snapshot the metrics registry every K engine "
                         "steps into the --metrics-out JSON (0 = final "
                         "totals only)")
    ap.add_argument("--trace-annotate", action="store_true",
                    help="also wrap traced spans in jax.profiler trace "
                         "annotations (visible when profiling with "
                         "jax.profiler.trace)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for all requests "
                         "(0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus filter (1.0 = off)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    kind = model_kind(args.arch)
    mux = MuxSpec(n=args.mux_n)
    key = jax.random.PRNGKey(args.seed)
    cls = {"lm": TransformerLM, "vlm": VLM, "encdec": EncDecLM}[kind]

    def _ev_ints(spec, flag, want):
        try:
            vals = [int(x) for x in spec.split(":")]
        except ValueError:
            vals = []
        if len(vals) not in want:
            ap.error(f"{flag} expects "
                     f"{':'.join(['N'] * min(want))} (got {spec!r})")
        return vals

    events, add_widths = [], []
    for spec in args.kill_shard or []:
        s, sh = _ev_ints(spec, "--kill-shard", (2,))
        events.append({"step": s, "op": "kill_shard", "shard": sh})
    for spec in args.drain_lane or []:
        s, w = _ev_ints(spec, "--drain-lane", (2,))
        events.append({"step": s, "op": "drain_lane", "width": w})
    for spec in args.add_lane or []:
        v = _ev_ints(spec, "--add-lane", (2, 3))
        ev = {"step": v[0], "op": "add_lane", "width": v[1]}
        if len(v) == 3:
            ev["rows"] = v[2]
        events.append(ev)
        add_widths.append(v[1])
    if args.restart_step is not None:
        if not args.ckpt_dir:
            ap.error("--restart-step requires --ckpt-dir")
        if args.lanes is not None:
            ap.error("--restart-step supports the single-runtime "
                     "paged mode (drop --lanes)")
        events.append({"step": args.restart_step, "op": "restart"})
    if events and not (args.continuous and args.cache == "paged"):
        ap.error("failure/resize flags (--kill-shard/--drain-lane/"
                 "--add-lane/--restart-step) require --continuous "
                 "--cache paged")
    if (args.drain_lane or args.add_lane) and args.lanes is None:
        ap.error("--drain-lane/--add-lane require --lanes")

    def _widths(spec, flag):
        try:
            return [int(x) for x in spec.split(",")]
        except ValueError:
            ap.error(f"{flag} expects comma-separated widths, e.g. 1,4,8")

    if args.disagg:
        if args.lanes is not None:
            ap.error("--disagg replaces --lanes "
                     "(use --prefill-lanes/--decode-lanes)")
        if not (args.prefill_lanes and args.decode_lanes):
            ap.error("--disagg requires --prefill-lanes and "
                     "--decode-lanes")
        if args.prefill == "blocking":
            ap.error("--disagg requires chunked prefill "
                     "(drop --prefill blocking)")
    elif args.prefill_lanes or args.decode_lanes:
        ap.error("--prefill-lanes/--decode-lanes require --disagg")

    lanes = slo_mix = None
    if args.lanes is not None or args.disagg:
        if not (args.continuous and args.cache == "paged"):
            ap.error("--lanes/--disagg require --continuous --cache paged")
        if args.disagg:
            pw = _widths(args.prefill_lanes, "--prefill-lanes")
            dw = _widths(args.decode_lanes, "--decode-lanes")
            missing = sorted(set(pw) - set(dw))
            if missing:
                ap.error(f"--disagg: prefill widths {missing} have no "
                         f"same-width decode lane")
            widths = pw + dw
            roles = ["prefill"] * len(pw) + ["decode"] * len(dw)
        else:
            widths = _widths(args.lanes, "--lanes")
            roles = ["both"] * len(widths)
        lane_rows = ([int(x) for x in args.lane_rows.split(",")]
                     if args.lane_rows
                     else [args.backbone_batch] * len(widths))
        if len(lane_rows) != len(widths):
            ap.error(f"--lane-rows gives {len(lane_rows)} entries for "
                     f"{len(widths)} lanes")
        lanes = [LaneSpec(n_mux=w, rows=r, chunk=args.chunk, role=ro)
                 for w, r, ro in zip(widths, lane_rows, roles)]
        slo_mix = _parse_slo_mix(ap, args.slo_mix)
        # one trained model per mux width (MUX-PLMs are width-specific),
        # including widths that only join later via --add-lane
        params = {w: cls.init(jax.random.fold_in(key, w), cfg,
                              MuxSpec(n=w))
                  for w in set(widths) | set(add_widths)}
    else:
        params = cls.init(key, cfg, mux)
    mesh = None
    n_shards = 1
    if args.mesh is not None:
        if not (args.continuous and args.cache == "paged"):
            ap.error("--mesh requires --continuous --cache paged")
        from repro.launch.mesh import make_serve_mesh
        try:
            data, model = (int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error("--mesh expects DATA,MODEL, e.g. --mesh 2,4")
        mesh = make_serve_mesh(data, model)
        n_shards = data
    if args.shards is not None:
        if not (args.continuous and args.cache == "paged"):
            ap.error("--shards requires --continuous --cache paged")
        if mesh is not None and args.shards != n_shards:
            ap.error(f"--shards {args.shards} must match the --mesh "
                     f"data axis ({n_shards})")
        n_shards = args.shards
    if args.kill_shard and n_shards < 2:
        ap.error("--kill-shard needs >= 2 data shards "
                 "(set --shards N or --mesh DATA,MODEL)")
    if args.fence_stragglers:
        if not (args.continuous and args.cache == "paged"):
            ap.error("--fence-stragglers requires --continuous "
                     "--cache paged")
        if n_shards < 2:
            ap.error("--fence-stragglers needs >= 2 data shards "
                     "(set --shards N or --mesh DATA,MODEL)")
    if args.route == "goodput" and lanes is None:
        ap.error("--route goodput requires --lanes or --disagg")
    if args.kv_dtype and not (args.continuous and args.cache == "paged"):
        ap.error("--kv-dtype requires --continuous --cache paged")
    sc = ServeConfig(cfg=cfg, kind=kind, mux=mux,
                     capacity=args.prompt_len + args.new_tokens + 8,
                     dtype=jnp.float32,
                     cache_layout=args.cache if args.continuous else "ring",
                     block_size=args.block_size, n_shards=n_shards,
                     kv_dtype=args.kv_dtype)
    default_sampling = None
    if args.temperature > 0:
        default_sampling = sampling.SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed)

    telemetry = None
    if args.metrics_out or args.trace_out:
        if not args.continuous:
            ap.error("--metrics-out/--trace-out require --continuous")
        telemetry = Telemetry(snapshot_every=args.metrics_interval,
                              annotate=args.trace_annotate)

    if not args.continuous:
        _fill_drain(params, sc, cfg, kind, args, default_sampling)
        return 0

    rng = np.random.default_rng(args.seed)
    arrivals = []
    for i in range(args.requests):
        sp = default_sampling and sampling.SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=i)
        arr = (i * args.arrival_every,
               rng.integers(4, cfg.vocab_size,
                            size=(args.prompt_len,)).astype(np.int32),
               args.new_tokens, sp)
        if lanes is not None:
            classes = sorted(slo_mix)
            arr += (str(rng.choice(classes,
                                   p=[slo_mix[c] for c in classes])),)
        arrivals.append(arr)
    stats = run_continuous(params, sc, args.backbone_batch, arrivals,
                           chunk=args.chunk, prefill_mode=args.prefill,
                           default_sampling=default_sampling, mesh=mesh,
                           use_kernels=args.use_kernels, lanes=lanes,
                           pool_budget=args.pool_budget,
                           telemetry=telemetry, events=events or None,
                           ckpt_dir=args.ckpt_dir, route=args.route,
                           fence_stragglers=args.fence_stragglers)
    done = len(stats["completed"])
    util = float(np.mean(stats["slot_util"])) if stats["slot_util"] else 0.0
    # report the mode that actually ran (the runtime falls back to
    # blocking for recurrent blocks / contextual mux)
    mode = (f"paged/{stats['prefill_mode']}" if sc.cache_layout == "paged"
            else "ring")
    if mesh is not None:
        mode += f"/mesh{tuple(mesh.devices.shape)}"
    lanes_desc = None
    if lanes is not None:
        lanes_desc = (f"P:{args.prefill_lanes}>D:{args.decode_lanes}"
                      if args.disagg else args.lanes)
        mode += (f"/disagg[{lanes_desc}]" if args.disagg
                 else f"/lanes[{lanes_desc}]")
    width = (f"widths {lanes_desc}" if lanes is not None
             else f"mux N={mux.n}")
    print(f"continuous[{mode}] served {done} requests "
          f"({stats['generated_tokens']} tokens) in {stats['wall']:.1f}s  "
          f"({width}, rows {args.backbone_batch}; "
          f"{stats['generated_tokens'] / stats['wall']:.1f} tok/s, "
          f"prefill {stats['prefill_tokens']} backbone tokens "
          f"({stats['prefill_compute_tokens']} padded) in "
          f"{stats['prefill_events']} events, slot util {util:.2f})")
    if lanes is not None:
        for ls in stats["lanes"]:
            toks = sum(len(r.output) for r in ls["completed"])
            lu = (float(np.mean(ls["slot_util"]))
                  if ls["slot_util"] else 0.0)
            compiled = ", ".join(
                f"{k}×{v}" for k, v in sorted(ls["trace_counts"].items()))
            print(f"  lane{ls['lane']} N={ls['n_mux']} "
                  f"rows={ls['rows']}: {len(ls['completed'])} requests, "
                  f"{toks} tokens, slot util {lu:.2f}; "
                  f"compiled [{compiled}]")
        rc = stats["routing"]
        routed = ", ".join(f"{k}={v}" for k, v in rc["routed"].items())
        print(f"routing[{args.route}]: {routed}; "
              f"demotions={rc['demotions']}, "
              f"promotions={rc['promotions']}, "
              f"rebalanced={rc['rebalanced_blocks']} blocks")
        if args.disagg:
            drec = stats["recovery"]
            print(f"disagg: {drec['handoffs']} handoffs "
                  f"({drec['handoff_streams']} streams, "
                  f"{drec['migrated_kv_bytes']} KV bytes migrated, "
                  f"zero re-prefill)")
        for ls in stats["lane_stats"]:
            print(f"  lane{ls['lane']} N={ls['n_mux']}: goodput "
                  f"{ls['goodput_tok_s']:.1f} tok/s "
                  f"(TTFT-SLO attainment {ls['slo_attainment']:.2f} "
                  f"× {ls['tok_s']:.1f} tok/s)")
    if "trace_counts" in stats:
        compiled = ", ".join(f"{k}×{v}"
                             for k, v in sorted(stats["trace_counts"].items()))
        print(f"compiled programs: {compiled}")
    hists = (telemetry.registry.snapshot()["histograms"]
             if telemetry is not None else [])
    walked = [h for h in hists if h["name"] == "decode_pages_walked_share"]
    if walked:
        n = sum(h["count"] for h in walked)
        print(f"decode pages walked: "
              f"{sum(h['sum'] for h in walked) / n:.3f} of the table "
              f"(min {min(h['min'] for h in walked):.3f}, "
              f"max {max(h['max'] for h in walked):.3f}) over {n} "
              f"decode steps")
    rec = stats.get("recovery")
    if args.fence_stragglers and rec:
        print(f"stragglers: {rec['stragglers_fenced']} fenced, "
              f"{rec['global_slow_steps']} global slow steps")
    if events and rec:
        lat = rec["recovery_latency_s"]
        line = (f"recovery: {rec['shards_killed']} shard kills, "
                f"{rec['requests_replayed']} streams replayed "
                f"({rec['replay_prefill_tokens']} re-prefill tokens), "
                f"{rec['lane_drains']} drains / {rec['lane_adds']} adds "
                f"({rec['lanes_retired']} lanes retired), "
                f"{rec['restarts']} restarts")
        if lat:
            line += f"; worst recovery latency {max(lat) * 1e3:.1f}ms"
        if rec["restore_latency_s"]:
            line += (f"; restore "
                     f"{max(rec['restore_latency_s']) * 1e3:.1f}ms")
        print(line)
    if telemetry is not None:
        if args.metrics_out:
            prom = telemetry.write_metrics(args.metrics_out)
            print(f"metrics written to {args.metrics_out} (+ {prom})")
        if args.trace_out:
            telemetry.write_trace(args.trace_out)
            print(f"trace written to {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
