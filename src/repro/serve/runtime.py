"""Compile-once serve runtime: chunked prefill interleaved with decode.

``ServeRuntime`` is the mechanism half of the continuous-serving stack
(the policy half is ``serve.scheduler.ContinuousScheduler``, which emits
typed plans — admit / prefill-chunk / decode / free — that the runtime
executes against the device).  It owns the paged cache pytree, the
host-side ``KVPool`` and a small set of jitted, shape-stable step
functions, so steady-state serving compiles a fixed number of programs
up front instead of once per prompt length:

  * **decode step** — the whole N_mux × B grid advances one token:
    (NB, 1) input tokens, a (B,) per-row position vector and the
    per-stream sampling vectors go in, the (NB,) sampled tokens come
    out.  Compiles exactly once: the sampling params are traced arrays
    and the sampler's full-vocab machinery sits behind a traced
    ``lax.cond`` (``serve.sampling.sample``), so an all-greedy grid
    skips it at runtime while a request changing its sampling config
    mid-stream never triggers a new trace.  Sampling happens on device
    so logits never cross back to the host — only the token vector is
    gathered.
  * **prefill-chunk step, one per shape bucket** — a joining row's
    prompt is split into fixed-size chunks written through the paged
    path (``engine.prefill_chunk``): the chunk's KV is scattered into
    the row's blocks mid-sequence and its queries attend causally over
    previously written blocks.  Chunks are padded to power-of-two
    buckets (padded positions route to the trash block and are fully
    masked), so the step compiles once per bucket.  Row index, start
    offset and valid length are traced scalars.

A joining row advances one chunk per engine step while live rows keep
decoding — admission never stalls the grid behind a long prompt.  Cache
buffers are donated to the jitted steps (XLA updates the pool in place),
so nothing may hold a cache pytree across a step.

Pool pressure flows runtime -> scheduler: an admission that cannot get
blocks is rolled back (``cancel_admit``) and retried after rows drain; a
row whose mid-decode block append exhausts the pool is preempted
(``preempt_row`` — blocks freed, requests requeued and later resumed
from prompt + generated-so-far).  Backpressure is shard-local under a
mesh: a row only ever waits on (or is doomed by) its OWN shard's pool.
Chunked prefill requires position-wise mux (gaussian) and attention-only
block patterns — bucket padding would corrupt recurrent (RG-LRU / RWKV)
state — and falls back to blocking (whole-prompt) prefill otherwise.

Mesh-sharded serving (DESIGN.md §sharded serving): pass ``mesh`` (axes
'data', 'model' — ``launch.mesh.make_serve_mesh``) and set
``ServeConfig.n_shards`` to the 'data' axis size.  Backbone rows, their
block tables and the pool's pages partition over 'data' (each data
shard owns its own ``ShardedKVPool`` segment and trash block); params
and the KV head axes partition over 'model' via the repo's sharding
rules.  The jitted steps pin the cache's NamedShardings on both sides
(in via committed inputs, out via ``out_shardings``), so the compile
counters still read 1 decode program + one per prefill bucket on every
device, and sampling runs on the devices owning each row — only the
(NB,) token vector is gathered to host.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.serve import sampling
from repro.serve.engine import (ServeConfig, init_cache, make_pool, prefill,
                                prefill_chunk, decode_step, set_block_tables,
                                reset_blocks, copy_cache_pages)
from repro.serve.kvpool import PoolExhausted, live_blocks
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.telemetry import NULL_SPAN, NULL_TELEMETRY

MIN_BUCKET = 4


def chunk_buckets(chunk: int, min_bucket: int = MIN_BUCKET):
    """Shape buckets for chunked prefill: powers of two up to ``chunk``
    (the last chunk of a prompt is padded up to the smallest fitting
    bucket; full chunks use ``chunk`` itself)."""
    b, out = min_bucket, []
    while b < chunk:
        out.append(b)
        b *= 2
    out.append(chunk)
    return out


class ServeRuntime:
    """Plan-executing serve runtime over the paged KV pool.

    params/sc: model parameters and a ``ServeConfig`` with
    ``cache_layout='paged'``.  backbone_rows: B rows of the N_mux × B
    grid.  chunk: prefill chunk size in tokens (None = blocking prefill:
    a joining row's whole prompt is prefilled in one eager call — the
    pre-runtime behaviour, kept as the measured baseline).
    default_sampling: ``SamplingParams`` for requests that don't carry
    their own (None = greedy).  mesh: optional ('data', 'model') device
    mesh for sharded serving — requires ``sc.n_shards`` == the 'data'
    axis size and ``backbone_rows`` divisible by it.  lane: serving-lane
    id under width-lane serving (DESIGN.md §width lanes) — tags the
    scheduler's plans and this runtime's stats/load snapshots; each lane
    owns its own runtime, pool partition and jitted step set.
    telemetry: serve-wide ``serve.telemetry.Telemetry`` handle (None =
    disabled).  Instrumentation is host-side only, at the step
    boundaries that already exist — spans bracket the jitted calls the
    runtime was dispatching anyway, TTFT stamps ride the existing
    device->host token read-back — so telemetry adds no host syncs and
    no recompiles, and token streams are identical with it on or off
    (the no-host-sync invariant, DESIGN.md §observability; enforced by
    ``tests/test_serve_fuzz.py``).
    """

    def __init__(self, params, sc: ServeConfig, backbone_rows: int, *,
                 chunk: int | None = 32, pad_id: int = 0,
                 default_sampling=None, on_prefill=None,
                 use_kernels: bool = False, mesh=None, lane: int = 0,
                 telemetry=None, role: str = "both"):
        if sc.cache_layout != "paged":
            raise ValueError("ServeRuntime requires cache_layout='paged'")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got {role!r}")
        if role == "prefill" and chunk is None:
            # a prefill-only lane exists to overlap chunk cadence with a
            # sibling decode lane; blocking prefill would defeat it
            raise ValueError("a prefill-role lane requires chunked prefill")
        if sc.kind != "lm":
            raise NotImplementedError(
                "continuous serving supports decoder-only LM families")
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1 (or None for blocking "
                             f"prefill), got {chunk}")
        if mesh is not None:
            data = mesh.shape.get("data", 1)
            if sc.n_shards != data:
                raise ValueError(
                    f"ServeConfig.n_shards={sc.n_shards} must equal the "
                    f"mesh 'data' axis size {data}")
            if backbone_rows % data:
                raise ValueError(
                    f"backbone_rows={backbone_rows} not divisible by the "
                    f"mesh 'data' axis size {data}")
        elif sc.n_shards != 1:
            # logical sharding without a device mesh: rows and pool
            # blocks still segment per shard (ShardedKVPool + per-row
            # trash routing), but the device arrays stay unsharded.
            # This is the substrate for fault-injection testing
            # (kill_shard) on a single device; a real mesh only changes
            # where the pages live, never the allocator behaviour.
            if backbone_rows % sc.n_shards:
                raise ValueError(
                    f"backbone_rows={backbone_rows} not divisible by "
                    f"n_shards={sc.n_shards}")
        blocks = tuple(sc.cfg.block_pattern) + tuple(sc.cfg.tail_blocks)
        if chunk is not None and (
                any(b not in ("attn", "local") for b in blocks)
                or (sc.mux.enabled and sc.mux.mux_kind != "gaussian")):
            # bucket padding runs pad tokens through recurrent state /
            # sequence-contextual mux — not exact; use blocking prefill
            chunk = None
        self.params = params
        self.sc = sc
        self.n_mux = max(sc.mux.n, 1)
        self.nrows = backbone_rows
        self.nb = self.n_mux * backbone_rows
        self.chunk = chunk
        self.buckets = chunk_buckets(chunk) if chunk is not None else []
        self.pad_id = pad_id
        self.default_sampling = default_sampling
        self.on_prefill = on_prefill
        self.use_kernels = use_kernels
        self.mesh = mesh
        self.lane = lane
        self.role = role
        self.tele = telemetry if telemetry is not None else NULL_TELEMETRY
        if self.tele.enabled:
            tag = f" [{role}]" if role != "both" else ""
            self.tele.tracer.process_name(
                lane, f"lane {lane} (N={self.n_mux}){tag}")

        self.sched = ContinuousScheduler(n_mux=self.n_mux,
                                         backbone_batch=backbone_rows,
                                         max_len=sc.capacity,
                                         n_shards=sc.n_shards,
                                         lane=lane,
                                         telemetry=self.tele)
        self.pool = make_pool(sc, self.nb)
        self.cache = init_cache(sc, self.nb)
        # per-row trash-block routing (each shard's invalid writes stay
        # on that shard; block 0 everywhere in the unsharded case)
        self._trash = (jnp.asarray(self.pool.trash_vector(
            range(backbone_rows))) if sc.n_shards > 1 else None)
        self._cache_sh = None
        if mesh is not None:
            # pin NamedShardings on params and cache: rows/block tables/
            # pages over 'data', heads and MLP width over 'model'.  The
            # cache shardings are re-asserted after every host-side table
            # edit and via out_shardings on the jitted steps, so input
            # shardings never drift and nothing ever re-traces.
            from repro.runtime import sharding as shard
            self.params = params = jax.device_put(
                params, shard.named(shard.param_specs(params, mesh), mesh))
            self._cache_sh = shard.named(
                shard.cache_specs(self.cache, mesh), mesh)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        self.row_len: dict[int, int] = {}      # rows holding blocks
        self.row_tokens: dict[int, np.ndarray] = {}
        self.next_tok = np.full((self.n_mux, backbone_rows), pad_id,
                                np.int32)
        self.engine_steps = 0
        self.trace_counts: dict[str, int] = {}
        # prefill_mode reflects what actually runs — "blocking" when the
        # recurrent/contextual-mux fallback above overrode chunk
        self.stats = {"prefill_tokens": 0, "prefill_events": 0,
                      "prefill_compute_tokens": 0, "decode_steps": 0,
                      "prefill_log": [], "slot_util": [], "cache_util": [],
                      "completed": self.sched.completed, "pool": self.pool,
                      "trace_counts": self.trace_counts,
                      "n_mux": self.n_mux, "rows": backbone_rows,
                      "lane": lane, "role": role,
                      "handoffs_out": 0, "handoffs_in": 0,
                      "migrated_bytes": 0,
                      "prefill_mode": ("chunked" if chunk is not None
                                       else "blocking")}
        # donation: the cache pytree (arg 1) is consumed and returned by
        # every step, so XLA updates the pool in place.  Every backend
        # honours it, so a stale read of a pre-step cache fails on the
        # CPU exactly as it would on the chip.
        jit_kw = {"donate_argnums": (1,)}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # tokens come back replicated (the one host gather per step);
            # the cache keeps its pinned shardings so the committed-input
            # signature of the next step is identical
            jit_kw["out_shardings"] = (NamedSharding(mesh, P()),
                                       self._cache_sh)
        self._decode_jit = jax.jit(self._decode_impl, **jit_kw)
        self._chunk_jit = jax.jit(self._chunk_impl, **jit_kw)

    # -- jitted step bodies (traced once per shape signature) --------------
    def _traced(self, key: str):
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1
        # runs at TRACE time (host-side, once per program), so a compile
        # event in the timeline marks exactly where a step first traced —
        # and a second 'compile' instant for the same program is a
        # compile-once violation visible in the trace itself
        if self.tele.enabled:
            self.tele.inc("compiles", lane=self.lane, program=key)
            self.tele.instant("compile", lane=self.lane, program=key)

    def _step_ctx(self, trash):
        """Layer-context extras shared by the jitted steps: the mesh (for
        sharding constraints / the shard_map kernel path) and the trash
        routing vector."""
        ctx = {}
        if self.mesh is not None:
            ctx["mesh"] = self.mesh
        if trash is not None:
            ctx["trash"] = trash
        return ctx

    def _decode_impl(self, params, cache, tokens, pos, temps, top_k,
                     top_p, seeds, steps):
        # ONE decode program for greedy and sampled workloads alike: the
        # sampling params are traced arrays, and sampling.sample gates
        # the full-vocab machinery behind a lax.cond — a request whose
        # sampling config changes mid-stream can never re-trace this
        self._traced("decode")
        logits, cache = decode_step(params, self.sc, cache, tokens, pos,
                                    extra_ctx=self._step_ctx(self._trash),
                                    use_kernels=self.use_kernels)
        toks = sampling.sample(logits[:, 0], temps, top_k, top_p, seeds,
                               steps)
        return toks, cache

    def _chunk_impl(self, params, cache, tokens, row, start, length,
                    temps, top_k, top_p, seeds, steps):
        self._traced(f"prefill_{tokens.shape[1]}")
        trash = (self._trash[row[None]] if self._trash is not None
                 else None)
        logits, cache = prefill_chunk(params, self.sc, cache, tokens,
                                      rows=row[None], start=start,
                                      length=length,
                                      use_kernels=self.use_kernels,
                                      extra_ctx=self._step_ctx(trash))
        toks = sampling.sample(logits, temps, top_k, top_p, seeds, steps)
        return toks, cache

    # -- per-stream sampling vectors --------------------------------------
    def _sampling_row(self, j: int):
        reqs = [self.sched.slots[j][i].request for i in range(self.n_mux)]
        arr = sampling.params_arrays(
            [(r.sampling or self.default_sampling) if r is not None
             else None for r in reqs])
        steps = np.asarray([len(r.output) if r is not None else 0
                            for r in reqs], np.int32)
        return arr, steps

    def _sampling_grid(self):
        temps = np.zeros((self.nb,), np.float32)
        top_k = np.zeros((self.nb,), np.int32)
        top_p = np.ones((self.nb,), np.float32)
        seeds = np.zeros((self.nb,), np.int32)
        steps = np.zeros((self.nb,), np.int32)
        for i in range(self.n_mux):
            for j in range(self.nrows):
                r = self.sched.slots[j][i].request
                if r is None:
                    continue
                sp = r.sampling or self.default_sampling
                idx = i * self.nrows + j
                if sp is not None:
                    temps[idx] = sp.temperature
                    top_k[idx] = sp.top_k
                    top_p[idx] = sp.top_p
                    seeds[idx] = sp.seed
                steps[idx] = len(r.output)
        return temps, top_k, top_p, seeds, steps

    # -- plan execution ----------------------------------------------------
    def submit(self, request):
        self.sched.submit(request)

    def has_work(self) -> bool:
        return bool(self.sched.queue) or self.sched.n_active > 0

    def load(self):
        """Live-load snapshot for SLO-aware lane routing
        (``serve.router.LaneRouter``; DESIGN.md §width lanes): slot
        utilization, admission-queue depth and quota-capped pool
        headroom, tagged with this runtime's lane id and width."""
        from repro.serve.router import LaneLoad
        pool = self.pool
        headroom = (pool.headroom if hasattr(pool, "headroom")
                    else pool.n_free_blocks)
        return LaneLoad(lane=self.lane, n_mux=self.n_mux,
                        slots=self.n_mux * self.nrows,
                        active=self.sched.n_active,
                        queue_depth=self.sched.queue_depth,
                        headroom_blocks=headroom,
                        mid_prefill=len(self.sched.prefill_progress))

    def check_compile_once(self):
        """Assert the compile-once contract (DESIGN.md §step runtime):
        exactly one decode program and at most one program per declared
        prefill bucket have been traced since construction.  Width-lane
        serving calls this per lane — the contract holds *per width*,
        each lane owning its own step set."""
        counts = dict(self.trace_counts)
        if counts.pop("decode", 0) > 1:
            raise AssertionError(
                f"decode step re-traced: {self.trace_counts}")
        legal = {f"prefill_{b}" for b in self.buckets}
        for k, v in counts.items():
            if k not in legal:
                raise AssertionError(
                    f"unexpected traced program {k!r} "
                    f"(declared buckets {sorted(self.buckets)})")
            if v > 1:
                raise AssertionError(
                    f"prefill bucket {k} re-traced: {self.trace_counts}")

    def kill_shard(self, shard: int):
        """Fence a lost data shard and replay its streams (DESIGN.md
        §fault tolerance; the Petals recovery model, arXiv:2312.08361).

        The dead shard's KV pages are gone, but every stream's full
        token log — prompt + generated-so-far — lives on the host in its
        ``Request``, so nothing is actually lost: each of the shard's
        rows is preempted (``preempt_row`` requeues its live requests at
        the head of the queue) and re-admitted onto surviving shards,
        where chunked prefill of ``row_prompts`` rebuilds exactly the KV
        that died.  Greedy replay is exact (the pressure fuzz arm proves
        the preempt→replay path token-identical), and sampled streams
        resume their per-step sample sequence because the sampler folds
        the request seed with ``len(output)``.

        Surviving rows are never touched — their slots, blocks and
        positions are unchanged, so their streams stay token-identical
        to an undisturbed run.  The pool fences the shard
        (``ShardedKVPool.kill_shard``): its quota moves to the
        survivors and the scheduler's persistent ``dead_shards`` set
        keeps admission off its rows.

        Returns the replayed requests in requeue order (queue head
        first).  Raises if the shard is already dead or is the last one
        alive (nothing could replay the streams)."""
        if self.sc.n_shards < 2:
            raise ValueError("kill_shard requires n_shards >= 2")
        if shard in self.sched.dead_shards:
            raise ValueError(f"shard {shard} is already dead")
        if len(self.sched.dead_shards) + 2 > self.sc.n_shards:
            raise ValueError("cannot kill the last surviving shard")
        rps = self.nrows // self.sc.n_shards
        rows = range(shard * rps, (shard + 1) * rps)
        replayed = [s.request for j in rows for s in self.sched.slots[j]
                    if s.request is not None]
        # reversed: preempt_row appendlefts, so ascending-row order at
        # the queue head (matching ``replayed``) needs the last row first
        for j in reversed(rows):
            self.sched.preempt_row(j)
            if j in self.row_len:
                self.pool.free(j)
                del self.row_len[j]
                del self.row_tokens[j]
            self.next_tok[:, j] = self.pad_id
        self.sched.dead_shards.add(shard)
        reclaimed = self.pool.kill_shard(shard)
        # the dead rows' tables drop to all -1 on device: they stop
        # addressing the dead segment's pages (shapes unchanged — the
        # jitted steps never re-trace across a kill)
        self._install_tables()
        if self.tele.enabled:
            self.tele.inc("shards_lost", lane=self.lane, shard=shard)
            self.tele.inc("requests_replayed", len(replayed),
                          lane=self.lane)
            self.tele.instant("shard_lost", lane=self.lane, shard=shard,
                              rows=rps, requests=len(replayed),
                              reclaimed_quota=reclaimed)
        return replayed

    # -- disaggregated handoff (DESIGN.md §disaggregated) ------------------
    def handoff_ready(self):
        """Rows whose prompt is fully prefilled and whose streams are
        still live — the set a prefill-role lane offers for handoff.
        Their first generated tokens are already recorded (``_exec_chunk``
        on the last chunk), so a decode lane can continue them with zero
        re-prefill."""
        return [j for j in sorted(self.row_len)
                if j not in self.sched.prefill_progress
                and self.sched.row_active(j)]

    def free_rows(self):
        """Rows that can receive a handoff: empty, holding no blocks,
        and on an alive shard."""
        return [j for j in range(self.nrows)
                if not self.sched.row_active(j)
                and j not in self.row_len
                and j not in self.sched.prefill_progress
                and self.sched.shard_of(j) not in self.sched.dead_shards]

    def handoff_to(self, dst, j: int, dst_row: int):
        """Migrate row ``j``'s finished-prefill mux group into runtime
        ``dst`` at ``dst_row``: pool pages move via the migration
        primitive (quant scales included), the device payload follows
        via ``copy_cache_pages``, block tables are rebased to the
        destination pool's ids, and the streams' slots / host token
        state transfer — no re-prefill anywhere.  Returns the executed
        ``HandoffPlan`` (None when the destination pool cannot take the
        row right now — nothing has changed, retry later).

        The group moves whole (same mux width — muxed KV is inseparable
        from its stream composition) and the caches must share page
        geometry and ``kv_dtype`` (migration never re-quantizes)."""
        if dst is self:
            raise ValueError("handoff requires a distinct destination lane")
        if dst.n_mux != self.n_mux:
            raise ValueError(
                f"handoff across widths (N={self.n_mux} -> {dst.n_mux}): "
                "a muxed row cannot change composition")
        if (dst.sc.block_size != self.sc.block_size
                or dst.sc.kv_dtype != self.sc.kv_dtype
                or dst.sc.capacity != self.sc.capacity):
            raise ValueError("handoff lanes must share page geometry "
                             "(block_size / capacity / kv_dtype)")
        plan = self.sched.plan_handoff(j, dst.lane, dst_row,
                                       self.pool.num_tokens(j))
        try:
            if hasattr(self.pool, "migrate_pages"):
                src_blocks, dst_blocks = self.pool.migrate_pages(
                    j, dst_row, dst=dst.pool)
            else:
                src_blocks, dst_blocks = self.pool.migrate_rows(
                    j, dst.pool, dst_row)
        except PoolExhausted:
            if self.tele.enabled:
                self.tele.inc("handoff_deferrals", lane=self.lane,
                              dst_lane=dst.lane)
            return None
        nbytes = (len(src_blocks) * self.sc.block_size
                  * self.sc.kv_bytes_per_token())
        with self.tele.span("handoff", lane=self.lane, dst_lane=dst.lane,
                            metric="handoff_s", step=self.engine_steps,
                            row=j, dst_row=dst_row, tokens=plan.tokens,
                            blocks=len(src_blocks), bytes=nbytes):
            dst.cache = copy_cache_pages(self.cache, dst.cache,
                                         src_blocks, dst_blocks)
            self._install_tables()
            dst._install_tables()
            slots = self.sched.retire_handoff(plan)
            dst.sched.admit_handoff(plan, slots)
            dst.row_len[dst_row] = self.row_len.pop(j)
            dst.row_tokens[dst_row] = self.row_tokens.pop(j)
            dst.next_tok[:, dst_row] = self.next_tok[:, j]
            self.next_tok[:, j] = self.pad_id
        self.stats["handoffs_out"] += 1
        self.stats["migrated_bytes"] += nbytes
        dst.stats["handoffs_in"] += 1
        if self.tele.enabled:
            self.tele.inc("handoffs", lane=self.lane, dst_lane=dst.lane)
            self.tele.inc("migration_bytes", nbytes, lane=self.lane,
                          dst_lane=dst.lane)
            self.tele.instant("handoff", lane=self.lane, dst_lane=dst.lane,
                              row=j, dst_row=dst_row, tokens=plan.tokens,
                              streams=len(plan.uids))
        return plan

    def step(self):
        """One engine step: execute this step's batch of scheduler plans.

        The plan/execute contract (DESIGN.md §step runtime; the plan
        types are documented in ``serve.scheduler``):

        1. **Admissions** — for each ``AdmitPlan``, allocate the group's
           blocks from the plan's pool shard and register the row; a
           failed allocation is rolled back lane-/shard-locally
           (``cancel_admit``) and re-planned onto sibling shards.
        2. **Prefill chunks** — one ``PrefillChunkPlan`` per mid-prefill
           row: advance that row's prompt by one shape-bucketed chunk
           through the jitted chunk step (or the whole prompt eagerly
           under blocking prefill).
        3. **Decode** — the ``DecodePlan``'s rows advance one token in
           ONE jitted decode call over the grid; rows whose block append
           exhausts the pool are preempted first (``preempt_row``).
        4. **Frees** — drained rows (``FreePlan``) return their blocks.

        Every plan executed here carries this runtime's ``lane`` id and
        a ``shard`` scope where relevant; the runtime never executes a
        plan from another lane's scheduler (lane isolation is
        structural — one scheduler, pool and step set per lane).

        Disaggregated roles (DESIGN.md §disaggregated) gate the legs: a
        ``prefill`` lane runs admissions/chunks/frees only — its
        finished rows park (first tokens already recorded) until the
        orchestrator hands them to a decode lane; a ``decode`` lane runs
        decode/frees only — its rows arrive via ``admit_handoff``, so it
        never admits from its own queue (streams preempted there are
        re-routed by the orchestrator, since re-prefill is prefill-lane
        work)."""
        with (self.tele.span("engine_step", lane=self.lane,
                             metric="step_latency_s", step=self.engine_steps)
              if self.tele.enabled else NULL_SPAN):
            if self.role != "decode":
                self._exec_admissions()
                with self._inputs_span("plan"):
                    chunks = self.sched.plan_chunks(self.chunk)
                for plan in chunks:
                    self._exec_chunk(plan)
                self._exec_frees()         # e.g. max_new=1 done at prefill
            if self.role != "prefill":
                with self._inputs_span("plan"):
                    dp = self.sched.plan_decode()
                    rows = [j for j in dp.rows if j in self.row_len]
                if rows:
                    self._exec_decode(rows)
                    self._exec_frees()
        self.engine_steps += 1
        if self.tele.enabled:
            self._record_pool_gauges()

    def _record_pool_gauges(self):
        """Publish the pool occupancy / quota-headroom gauges, keyed
        (lane, shard).  Host-side allocator state only — never touches
        device arrays."""
        for s, st in enumerate(self.pool.occupancy_stats()):
            self.tele.gauge("pool_occupancy", st["occupancy"],
                            lane=self.lane, shard=s)
            self.tele.gauge("pool_headroom_blocks", st["headroom"],
                            lane=self.lane, shard=s)
            if st["quota"] is not None:
                self.tele.gauge("pool_quota_blocks", st["quota"],
                                lane=self.lane, shard=s)

    # -- spans around the host work of a step -----------------------------
    # Every span the runtime records carries ``step``, the index of the
    # engine step it belongs to.  A span with arguments is only built
    # when telemetry is on: off, each hook costs one ``enabled`` check.
    def _edit_span(self, kind: str, blocks: int):
        """``cache_edit``: an eager edit of the cache pytree outside the
        jitted steps (``reset`` / ``tables`` / ``commit``) naming
        ``blocks`` pool blocks or block-table entries."""
        if not self.tele.enabled:
            return NULL_SPAN
        return self.tele.span("cache_edit", lane=self.lane,
                              step=self.engine_steps, kind=kind,
                              blocks=blocks)

    def _inputs_span(self, phase: str):
        """``step_inputs``: host work that plans a step (``plan``) or
        builds the inputs of a decode call (``decode``) or of a prefill
        chunk (``chunk``)."""
        if not self.tele.enabled:
            return NULL_SPAN
        return self.tele.span("step_inputs", lane=self.lane,
                              step=self.engine_steps, phase=phase)

    def _row_span(self, name: str, j: int, metric=None, **args):
        """``admit`` / ``prefill_chunk`` over row ``j``, with the request
        ids of its row group (only called with telemetry on)."""
        uids = [s.request.uid for s in self.sched.slots[j]
                if s.request is not None]
        return self.tele.span(name, lane=self.lane,
                              shard=self.sched.shard_of(j), metric=metric,
                              step=self.engine_steps, row=j, uids=uids,
                              **args)

    def _reset_blocks(self, blocks):
        with self._edit_span("reset", len(blocks)):
            self.cache = reset_blocks(self.cache, blocks)

    def _install_tables(self):
        """Install every row's block table from the pool and re-commit
        the cache."""
        with self._edit_span("tables", self.nrows
                             * self.pool.max_blocks_per_seq):
            self.cache = set_block_tables(
                self.cache, self.pool.table_array(range(self.nrows)))
        self._commit_cache()

    def _commit_cache(self):
        """Re-assert the pinned NamedShardings after a host-side cache
        edit (set_block_tables / reset_blocks build fresh arrays whose
        sharding would otherwise drift and force a silent re-trace of
        the jitted steps on their next call)."""
        if self._cache_sh is not None:
            with self._edit_span("commit", self.pool.num_blocks):
                self.cache = jax.device_put(self.cache, self._cache_sh)

    def _shard_used_blocks(self, row: int) -> int:
        """Used blocks on ``row``'s shard (the whole pool when unsharded)
        — backpressure verdicts are shard-local."""
        if hasattr(self.pool, "shard_used_blocks"):
            return self.pool.shard_used_blocks(row)
        return self.pool.n_used_blocks

    def _exec_admissions(self):
        """Execute this step's admission plans.  A plan whose shard has
        no blocks is rolled back (``cancel_admit``) and — under a mesh —
        immediately re-planned with that shard excluded, so a group
        waiting on one busy shard lands on a sibling shard with free
        blocks instead of head-of-line blocking the queue."""
        failed: set = set()
        admitted = False
        with self._inputs_span("plan"):
            plans = self.sched.plan_admissions(self.pad_id)
        while plans:
            retry = False
            for plan in plans:
                with (self._row_span("admit", plan.row, tokens=plan.total)
                      if self.tele.enabled else NULL_SPAN):
                    ok = self._exec_admit(plan)
                if ok:
                    admitted = True
                else:
                    failed.add(plan.shard)
                    retry = True
            alive = self.sc.n_shards - len(self.sched.dead_shards)
            if not retry or len(failed) >= alive or not self.sched.queue:
                break
            # every iteration adds at least one newly failed shard, so
            # this terminates after <= n_shards rounds
            with self._inputs_span("plan"):
                plans = self.sched.plan_admissions(self.pad_id,
                                                   skip_shards=failed)
        if admitted:
            # one combined table install + sharding re-commit for ALL of
            # this step's admissions (per-plan block resets already
            # happened; rebuilding the (nrows, MB) table array and
            # re-committing the cache pytree per plan would be redundant)
            self._install_tables()

    def _exec_admit(self, plan) -> bool:
        with self._inputs_span("plan"):
            try:
                blocks = self.pool.allocate(plan.row, plan.total)
            except PoolExhausted:
                # backpressure: roll the group back and retry once blocks
                # free up; later groups still get their shot.  The verdict
                # is shard-local: only the plan's own shard can ever free
                # the blocks this group is waiting for.
                self.sched.cancel_admit(plan)
                if self.tele.enabled:
                    self.tele.inc("admit_rollbacks", lane=self.lane,
                                  shard=plan.shard)
                    self.tele.instant("cancel", lane=self.lane,
                                      shard=plan.shard, row=plan.row,
                                      tokens=plan.total)
                if self._shard_used_blocks(plan.row) == 0:
                    raise PoolExhausted(
                        f"request group of {plan.total} tokens cannot fit "
                        f"an empty pool shard (num_blocks="
                        f"{self.pool.num_blocks}, block_size="
                        f"{self.pool.block_size}, shards "
                        f"{self.sc.n_shards}, per-seq cap "
                        f"{self.pool.max_blocks_per_seq})")
                return False
            self.row_len[plan.row] = plan.total
            self.row_tokens[plan.row] = np.asarray(plan.tokens, np.int32)
        self._reset_blocks(blocks)
        return True

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _exec_chunk(self, plan):
        with (self._row_span("prefill_chunk", plan.row,
                             metric="prefill_chunk_s", start=plan.start,
                             length=plan.length, last=plan.last)
              if self.tele.enabled else NULL_SPAN):
            self._exec_chunk_inner(plan)

    def _chunk_buffer(self, plan):
        """The chunk's tokens, padded to its shape bucket."""
        buf = np.full((self.n_mux, self._bucket(plan.length)), self.pad_id,
                      np.int32)
        buf[:, :plan.length] = self.row_tokens[plan.row][
            :, plan.start:plan.start + plan.length]
        return buf

    def _exec_chunk_inner(self, plan):
        j = plan.row
        with self._inputs_span("chunk"):
            arr, steps = self._sampling_row(j)
            buf = self._chunk_buffer(plan) if self.chunk is not None else None
        if buf is None:
            # blocking prefill: whole prompt, eager, fresh-KV attention
            compute = plan.length
            trash = (self._trash[jnp.asarray([j])]
                     if self._trash is not None else None)
            logits, self.cache = prefill(
                self.params, self.sc, self.cache,
                jnp.asarray(self.row_tokens[j]), rows=[j],
                extra_ctx=self._step_ctx(trash))
            self._commit_cache()
            out = sampling.sample(logits, arr["temperature"], arr["top_k"],
                                  arr["top_p"], arr["seed"], steps)
        else:
            compute = buf.shape[1]
            out, self.cache = self._chunk_jit(
                self.params, self.cache, buf, np.int32(j),
                np.int32(plan.start), np.int32(plan.length),
                arr["temperature"], arr["top_k"], arr["top_p"],
                arr["seed"], steps)
        self.stats["prefill_tokens"] += plan.length
        self.stats["prefill_compute_tokens"] += compute
        self.stats["prefill_events"] += 1
        self.stats["prefill_log"].append(((j,), plan.length))
        if self.on_prefill is not None:
            self.on_prefill((j,), plan.length)
        done = self.sched.chunk_done(j, plan.length)
        if plan.last:
            assert done
            # the existing device->host read-back of the row's first
            # generated tokens; the timestamp taken right after it is
            # the uniform TTFT stamp for the whole group (no NEW sync)
            first = np.asarray(out)
            self.sched.record_row_tokens(j, first, now=time.time())
            self.next_tok[:, j] = first

    def _clear_dead_slots(self):
        for j in range(self.nrows):
            if j in self.sched.prefill_progress:
                self.next_tok[:, j] = self.pad_id
                continue
            for i in range(self.n_mux):
                if self.sched.slots[j][i].request is None:
                    self.next_tok[i, j] = self.pad_id

    def _shard_mates(self, j: int) -> int:
        """Live rows sharing ``j``'s shard (j included) — the set whose
        drains could ever unblock j's shard."""
        if hasattr(self.pool, "shard_of"):
            s = self.pool.shard_of(j)
            return sum(1 for r in self.row_len
                       if self.pool.shard_of(r) == s)
        return len(self.row_len)

    def _exec_decode(self, rows):
        with self._inputs_span("decode"):
            pos_vec, fresh, preempt = self._append_slots(rows)
        if fresh:
            self._reset_blocks(fresh)
        if fresh or preempt:
            self._install_tables()
        rows = [j for j in rows if j not in preempt]
        if not rows:
            return
        with self._inputs_span("decode"):
            self._clear_dead_slots()
            toks_in = self.next_tok.reshape(-1)[:, None]
            temps, top_k, top_p, seeds, steps = self._sampling_grid()
        with (self.tele.span("decode", lane=self.lane,
                             metric="decode_step_s", step=self.engine_steps,
                             rows=len(rows))
              if self.tele.enabled else NULL_SPAN):
            out, self.cache = self._decode_jit(
                self.params, self.cache, toks_in, pos_vec, temps, top_k,
                top_p, seeds, steps)
            # the one existing device->host gather per decode step; the
            # span closes after it, so decode_step_s covers dispatch +
            # this read-back (no NEW sync), and the timestamp below is
            # the step's uniform token-arrival stamp for every stream
            grid = np.asarray(out).reshape(self.n_mux, self.nrows)
        now = time.time()
        with self._inputs_span("decode"):
            for j in rows:
                self.sched.record_row_tokens(j, grid[:, j], now=now)
                self.row_len[j] += 1
            self.next_tok = grid.copy()
            self.stats["decode_steps"] += 1
            self.stats["slot_util"].append(self.sched.utilization())
            self.stats["cache_util"].append(self.pool.utilization())
            if self.tele.enabled:
                # share of the table blocks the decode kernel walks
                mb = self.pool.max_blocks_per_seq
                walked = live_blocks(pos_vec, self.pool.block_size,
                                     self.sc.cfg.window).sum()
                self.tele.observe("decode_pages_walked_share",
                                  walked / (self.nrows * mb), lane=self.lane)

    def _append_slots(self, rows):
        """Reserve each decoding row's next slot; rows whose shard is
        full are preempted.  Returns (positions, fresh blocks, preempted
        rows)."""
        pos_vec = np.full((self.nrows,), -1, np.int32)
        fresh, preempt = [], []
        for j in rows:
            try:
                fresh += self.pool.append(j)    # reserve the new slot
            except PoolExhausted:
                preempt.append(j)
                continue
            pos_vec[j] = self.row_len[j]
        # a row that outgrows its shard's pool while it is the shard's
        # SOLE user can never be served (requeueing would thrash
        # forever); with shard-mates, preempted rows retry after drains
        for j in preempt:
            if self._shard_mates(j) == 1:
                raise PoolExhausted(
                    "a single row outgrew its whole pool shard "
                    f"(num_blocks={self.pool.num_blocks}, block_size="
                    f"{self.pool.block_size}, shards {self.sc.n_shards})"
                    " — it can never be served")
        for j in preempt:
            self.sched.preempt_row(j)
            self.pool.free(j)
            del self.row_len[j]
            del self.row_tokens[j]
            if self.tele.enabled:
                shard = (self.pool.shard_of(j)
                         if hasattr(self.pool, "shard_of") else 0)
                self.tele.inc("preempts", lane=self.lane, shard=shard)
                self.tele.instant("preempt", lane=self.lane, shard=shard,
                                  row=j)
        return pos_vec, fresh, preempt

    def _exec_frees(self):
        with self._inputs_span("plan"):
            for plan in self.sched.plan_frees():
                if plan.row in self.row_len:
                    self.pool.free(plan.row)
                    del self.row_len[plan.row]
                    del self.row_tokens[plan.row]
                    if self.tele.enabled:
                        self.tele.instant(
                            "free", lane=self.lane,
                            shard=self.sched.shard_of(plan.row), row=plan.row)
