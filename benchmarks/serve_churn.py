"""Continuous serving under a churning request trace: ring vs paged,
blocking vs chunked prefill, fixed mux widths vs SLO-routed width lanes.

Beyond-paper benchmark for the serve stack (DESIGN.md): a stream of
requests with heterogeneous prompt lengths and output budgets arrives
over time; the grid admits and retires streams continuously.  Arms over
the identical trace:

  * ``ring``           — grid-wide re-prefill on every composition
                         change (the layout allows nothing finer);
  * ``paged-blocking`` — block-pool cache, whole prompts prefilled at
                         admission (the decode grid stalls behind every
                         joining prompt);
  * ``paged-chunked``  — the ``ServeRuntime``: shape-bucketed prompt
                         chunks interleaved with decode, jitted steps
                         that compile once per bucket;
  * ``fixed-N<w>``     — paged-chunked pinned at mux width w, one arm
                         per lane width: the paper's Table-1-style
                         throughput-vs-width curve measured at serve
                         time rather than in fill-drain batches;
  * ``paged-chunked-kernels`` / ``paged-chunked-<kv>`` /
    ``paged-chunked-<kv>-cap`` — the quantized-page dimension
    (``--kv-dtype``; DESIGN.md §quantized pages): the Pallas-kernel
    fp32 baseline, the same grid on quantized pages with fused-dequant
    kernels (the bytes/token and TPOT delta), and the byte-parity
    capacity arm — the pool budget of the fp32 arm re-spent on
    quantized pages, serving MORE concurrent rows under the same
    device bytes (the capacity headline);
  * ``recovery-kill``  — paged-chunked over two logical shard segments
                         with shard 1 killed mid-trace (DESIGN.md
                         §fault tolerance): same CSV columns (the
                         prefill delta over ``paged-chunked`` is the
                         replay re-prefill tax) plus JSON keys
                         ``requests_replayed`` /
                         ``replay_prefill_tokens`` /
                         ``recovery_latency_s``;
  * ``lanes``          — width-lane serving (DESIGN.md §width lanes):
                         one runtime per width in ``--lanes``, requests
                         routed by SLO class + live lane load;
  * ``disagg``         — disaggregated prefill/decode lanes (DESIGN.md
                         §disaggregated serving): a prefill-only lane
                         hands each finished row's KV pages to a
                         same-width decode-only lane (bit-exact
                         migration, zero re-prefill), handoff placement
                         goodput-ordered; read against
                         ``paged-chunked``, the interleaved grid on the
                         same trace.  JSON adds ``handoffs`` /
                         ``handoff_streams`` / ``migrated_kv_bytes``
                         plus one ``disagg/<role>`` row per lane.

Reported per arm (CSV: ``serve_churn,<arm>,...``; the ``lanes`` arm adds
one ``serve_churn,lanes/N<w>,...`` row per lane):
  * mux_n            — the arm's active mux width (the lanes arm
                       reports aggregate widths plus per-lane rows, so
                       trajectories stay comparable across lane configs)
  * tok_s            — generated tokens / wall second
  * prefill_backbone — backbone token-positions spent in prefill
                       (per-row tokens × rows touched; the re-prefill
                       tax is the ring-vs-paged headline)
  * prefill_compute  — the same after shape-bucket padding (what the
                       device actually executes; chunked > blocking by
                       the bucket-padding overhead)
  * ttft_p50/p95     — request time-to-first-token percentiles (s)
  * tpot_p50/p95     — per-request time-per-output-token percentiles
                       (s/token); the blocking-vs-chunked p95 gap is
                       the no-stall claim, measured
  * slot_util        — mean occupied fraction of the N_mux × B grid
  * cache_util       — mean occupancy of the reserved cache memory
  * slo_attainment   — fraction of requests whose TTFT met their SLO
                       class's target (``router.DEFAULT_TTFT_SLO``;
                       classless fixed-arm requests count as balanced)
  * goodput_tok_s    — SLO attainment × tok_s: the goodput signal the
                       lane router publishes per lane (the lanes arm's
                       per-lane rows report each lane's own goodput)
  * bytes_tok        — KV-pool bytes one token occupies across all
                       attention layers (payload + quant scales + slot
                       position; ``ServeConfig.kv_bytes_per_token``)
  * pool_bytes       — total reserved cache bytes for the arm's grid
                       (the quantized arms' budget-parity axis)

``--json PATH`` additionally dumps every row (including the per-lane
breakdown and routing counters) as JSON for trajectory tooling;
``--metrics-out`` / ``--trace-out`` attach a ``serve.telemetry``
session to the lanes arm and persist its metrics snapshot (+ ``.prom``
sibling) and Perfetto-loadable step-span trace;
``--disagg-trace-out`` does the same for the disagg arm, whose
timeline carries the KV-page handoff spans and instants.

Runnable in reduced mode on CPU:

    PYTHONPATH=src python -m benchmarks.serve_churn --smoke
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MuxSpec
from repro.configs import get_config
from repro.models import TransformerLM
from repro.serve import ServeConfig
from repro.serve.router import LaneSpec, SLO_CLASSES, ttft_attainment
from repro.serve.telemetry import Telemetry
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import run_continuous


def make_trace(rng, n_requests: int, *, arrival_every: float,
               prompt_lo: int, prompt_hi: int, new_lo: int, new_hi: int,
               vocab: int):
    """Poisson-ish arrivals with heterogeneous prompt/output lengths."""
    t, out = 0.0, []
    for _ in range(n_requests):
        t += rng.exponential(arrival_every)
        out.append((int(t),
                    rng.integers(4, vocab,
                                 size=(int(rng.integers(prompt_lo,
                                                        prompt_hi + 1)),)
                                 ).astype(np.int32),
                    int(rng.integers(new_lo, new_hi + 1))))
    return out


def with_slo(trace, seed: int):
    """Tag a trace with uniformly mixed SLO classes (lanes arm only;
    the base trace stays byte-identical across arms)."""
    rng = np.random.default_rng(seed + 17)
    return [(t, p, m, None, str(rng.choice(SLO_CLASSES)))
            for t, p, m in trace]


def _pct(xs, q):
    return float(np.percentile(xs, q)) if xs else 0.0


def latency_stats(completed):
    """TTFT / TPOT percentiles from the requests' wall-clock stamps."""
    ttft = [r.t_first - r.t_submit for r in completed
            if r.t_first is not None and r.t_submit is not None]
    tpot = [(r.t_done - r.t_first) / max(len(r.output) - 1, 1)
            for r in completed
            if r.t_done is not None and r.t_first is not None]
    return {"ttft_p50": _pct(ttft, 50), "ttft_p95": _pct(ttft, 95),
            "tpot_p50": _pct(tpot, 50), "tpot_p95": _pct(tpot, 95)}


CSV_HEADER = ("serve_churn,arm,mux_n,tok_s,prefill_backbone,"
              "prefill_compute,prefill_events,ttft_p50,ttft_p95,"
              "tpot_p50,tpot_p95,slot_util,cache_util,requests,"
              "slo_attainment,goodput_tok_s,bytes_tok,pool_bytes")


def _csv(row):
    print(f"serve_churn,{row['arm']},{row['mux_n']},{row['tok_s']:.2f},"
          f"{row['prefill_backbone']},{row['prefill_compute']},"
          f"{row['prefill_events']},"
          f"{row['ttft_p50']:.4f},{row['ttft_p95']:.4f},"
          f"{row['tpot_p50']:.4f},{row['tpot_p95']:.4f},"
          f"{row['slot_util']:.3f},{row['cache_util']:.3f},"
          f"{row['requests']},"
          f"{row['slo_attainment']:.3f},{row['goodput_tok_s']:.2f},"
          f"{row.get('bytes_tok', 0)},{row.get('pool_bytes', 0)}")


def _mean(xs):
    return float(np.mean(xs)) if len(xs) else 0.0


def _row(arm, mux_n, stats, completed, wall=None, sc=None, rows=None):
    wall = stats["wall"] if wall is None else wall
    row = {
        "arm": arm,
        "mux_n": mux_n,
        "tok_s": (sum(len(r.output) for r in completed)
                  / max(wall, 1e-9)),
        "prefill_backbone": stats["prefill_tokens"],
        "prefill_compute": stats["prefill_compute_tokens"],
        "prefill_events": stats["prefill_events"],
        "slot_util": _mean(stats["slot_util"]),
        "cache_util": _mean(stats["cache_util"]),
        "requests": len(completed),
    }
    if sc is not None:
        # the memory axis of the kv-dtype dimension: bytes one token
        # occupies in the pool and the arm's total cache reservation
        bt = sc.kv_bytes_per_token()
        row["bytes_tok"] = bt
        row["kv_dtype"] = sc.kv_dtype or "serve-dtype"
        if rows is not None:
            row["rows"] = rows
        pools = stats.get("pools") or (
            [stats["pool"]] if stats.get("pool") is not None else None)
        if pools is not None:
            row["pool_bytes"] = (sum(p.num_blocks for p in pools)
                                 * sc.block_size * bt)
        elif rows is not None and sc.cache_layout == "ring":
            row["pool_bytes"] = rows * sc.capacity * bt   # contiguous rows
    row.update(latency_stats(completed))
    # goodput = TTFT-SLO attainment × tok_s (DESIGN.md §observability);
    # classless requests (the fixed arms) count against the balanced
    # target, the lanes arm carries each request's own class
    attain, measured = ttft_attainment(completed)
    row["slo_attainment"] = attain
    row["ttft_measured"] = measured
    row["goodput_tok_s"] = attain * row["tok_s"]
    return row


def run(budget=None, *, arch="qwen2-1.5b", mux_n=2, rows=2,
        n_requests=10, arrival_every=2.0, seed=0, block_size=8,
        chunk=8, prompt=(6, 16), new=(3, 10), lanes=(1, 2, 4),
        kv_dtype="int8", json_path=None, metrics_out=None,
        trace_out=None, disagg_trace_out=None):
    cfg = get_config(arch, reduced=True)
    widths = sorted(set((mux_n,) + tuple(lanes)))
    # one trained model per mux width (MUX-PLMs are width-specific)
    params = {w: TransformerLM.init(
        jax.random.fold_in(jax.random.PRNGKey(seed), w), cfg, MuxSpec(n=w))
        for w in widths}
    capacity = prompt[1] + new[1] + block_size
    results = []
    print(CSV_HEADER)

    def sc_for(width, layout, kv=None, num_blocks=None):
        return ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=width),
                           capacity=capacity, dtype=jnp.float32,
                           cache_layout=layout, block_size=block_size,
                           kv_dtype=kv, num_blocks=num_blocks)

    def trace_for():
        rng = np.random.default_rng(seed)        # identical trace per arm
        return make_trace(rng, n_requests, arrival_every=arrival_every,
                          prompt_lo=prompt[0], prompt_hi=prompt[1],
                          new_lo=new[0], new_hi=new[1],
                          vocab=cfg.vocab_size)

    fixed_arms = [("ring", "ring", None, mux_n),
                  ("paged-blocking", "paged", "blocking", mux_n),
                  ("paged-chunked", "paged", "chunked", mux_n)]
    # the serve-time Table-1-style width curve: chunked paged runtime
    # pinned at each lane width over the identical trace
    fixed_arms += [(f"fixed-N{w}", "paged", "chunked", w)
                   for w in lanes]

    for arm, layout, mode, width in fixed_arms:
        sc = sc_for(width, layout)
        stats = run_continuous(params[width], sc, rows,
                               trace_for(), chunk=chunk,
                               prefill_mode=mode or "chunked")
        assert len(stats["completed"]) == n_requests
        # the arm label must describe what actually ran (the runtime
        # falls back to blocking for recurrent / contextual-mux configs)
        assert layout == "ring" or stats["prefill_mode"] == mode
        row = _row(arm, width, stats, stats["completed"], sc=sc, rows=rows)
        results.append(row)
        _csv(row)

    # --kv-dtype dimension (DESIGN.md §quantized pages): the Pallas
    # fp32 baseline, the same grid on quantized pages (bytes/token +
    # TPOT delta), and the byte-parity capacity arm — the fp32 arm's
    # pool budget respent on quantized pages buys MORE concurrent rows
    if kv_dtype:
        sc_base = sc_for(mux_n, "paged")
        sc_q = sc_for(mux_n, "paged", kv=kv_dtype)
        kv_arms = [("paged-chunked-kernels", sc_base, rows),
                   (f"paged-chunked-{kv_dtype}", sc_q, rows)]
        pool_budget = sc_base.pool_bytes(mux_n * rows)
        bt_q = sc_q.kv_bytes_per_token()
        mbs = sc_q.max_blocks_per_seq
        # largest row count whose worst-case pool fits the fp32 budget
        rows_cap = (pool_budget // (block_size * bt_q) - 1) // mbs
        if rows_cap > rows:
            blocks_cap = int(rows_cap) * mbs + 1
            kv_arms.append((f"paged-chunked-{kv_dtype}-cap",
                            sc_for(mux_n, "paged", kv=kv_dtype,
                                   num_blocks=blocks_cap),
                            int(rows_cap)))
        for arm, sc, arm_rows in kv_arms:
            stats = run_continuous(params[mux_n], sc, arm_rows,
                                   trace_for(), chunk=chunk,
                                   use_kernels=True)
            assert len(stats["completed"]) == n_requests
            row = _row(arm, mux_n, stats, stats["completed"], sc=sc,
                       rows=arm_rows)
            results.append(row)
            _csv(row)

    # recovery arm (DESIGN.md §fault tolerance): paged-chunked over two
    # logical shard segments with shard 1 killed mid-trace — the extra
    # prefill_backbone over paged-chunked is the replay re-prefill tax,
    # and the JSON row carries the supervisor's recovery accounting
    sc_kill = ServeConfig(cfg=cfg, kind="lm", mux=MuxSpec(n=mux_n),
                          capacity=capacity, dtype=jnp.float32,
                          cache_layout="paged", block_size=block_size,
                          n_shards=2)
    stats = run_continuous(params[mux_n], sc_kill, rows, trace_for(),
                           chunk=chunk,
                           events=[{"step": 10, "op": "kill_shard",
                                    "shard": 1}])
    assert len(stats["completed"]) == n_requests
    rec = stats["recovery"]
    row = _row("recovery-kill", mux_n, stats, stats["completed"],
               sc=sc_kill, rows=rows)
    row["shards_killed"] = rec["shards_killed"]
    row["requests_replayed"] = rec["requests_replayed"]
    row["replay_prefill_tokens"] = rec["replay_prefill_tokens"]
    row["recovery_latency_s"] = rec["recovery_latency_s"]
    row["recovery_latency_max_s"] = (max(rec["recovery_latency_s"])
                                     if rec["recovery_latency_s"] else 0.0)
    results.append(row)
    _csv(row)

    if lanes:
        # telemetry rides the lanes arm only: the fixed arms above stay
        # the uninstrumented baseline the fuzz suite compares against
        telemetry = (Telemetry() if metrics_out or trace_out else None)
        stats = run_continuous(params, sc_for(mux_n, "paged"), rows,
                               with_slo(trace_for(), seed), chunk=chunk,
                               lanes=tuple(lanes), telemetry=telemetry)
        assert len(stats["completed"]) == n_requests
        agg = _row("lanes", "+".join(str(w) for w in lanes), stats,
                   stats["completed"], sc=sc_for(mux_n, "paged"),
                   rows=rows)
        agg["widths"] = list(lanes)
        agg["routing"] = stats["routing"]
        agg["lane_goodput"] = stats["lane_stats"]
        agg["lanes"] = []
        by_lane = {ls["lane"]: ls for ls in stats["lane_stats"]}
        for ls in stats["lanes"]:
            lane_row = _row(f"lanes/N{ls['n_mux']}", ls["n_mux"], ls,
                            ls["completed"], wall=stats["wall"],
                            sc=sc_for(ls["n_mux"], "paged"))
            lane_row["lane"] = ls["lane"]
            lane_row["rows"] = ls["rows"]
            # the router's own goodput accounting for this lane (same
            # numbers the lane_goodput_tok_s gauge publishes) overrides
            # the generic classless recomputation from _row
            g = by_lane.get(ls["lane"])
            if g is not None:
                lane_row["slo_attainment"] = g["slo_attainment"]
                lane_row["ttft_measured"] = g["ttft_measured"]
                if g["goodput_tok_s"] is not None:
                    lane_row["goodput_tok_s"] = g["goodput_tok_s"]
            agg["lanes"].append(lane_row)
        results.append(agg)
        _csv(agg)
        for lane_row in agg["lanes"]:
            _csv(lane_row)
        if telemetry is not None:
            if metrics_out:
                prom = telemetry.write_metrics(metrics_out)
                print(f"serve_churn wrote {metrics_out} (+ {prom})")
            if trace_out:
                telemetry.write_trace(trace_out)
                print(f"serve_churn wrote {trace_out}")

    # disaggregated arm (DESIGN.md §disaggregated serving): a prefill
    # lane streams each finished row's KV pages to a same-width decode
    # lane — zero re-prefill, goodput-ordered handoff placement.  The
    # paged-chunked arm above is the interleaved baseline on this trace.
    disagg = (LaneSpec(n_mux=mux_n, rows=rows, chunk=chunk,
                       role="prefill"),
              LaneSpec(n_mux=mux_n, rows=rows, chunk=chunk,
                       role="decode"))
    disagg_tel = Telemetry() if disagg_trace_out else None
    stats = run_continuous(params, sc_for(mux_n, "paged"), rows,
                           trace_for(), chunk=chunk, lanes=disagg,
                           route="goodput", telemetry=disagg_tel)
    assert len(stats["completed"]) == n_requests
    # zero re-prefill, measured: decode lanes never run a prefill step
    assert all(ls["prefill_events"] == 0 for ls in stats["lanes"]
               if ls["role"] == "decode")
    rec = stats["recovery"]
    row = _row("disagg", mux_n, stats, stats["completed"],
               sc=sc_for(mux_n, "paged"), rows=rows)
    row["route"] = "goodput"
    row["handoffs"] = rec["handoffs"]
    row["handoff_streams"] = rec["handoff_streams"]
    row["migrated_kv_bytes"] = rec["migrated_kv_bytes"]
    row["lanes"] = []
    for ls in stats["lanes"]:
        lane_row = _row(f"disagg/{ls['role']}", ls["n_mux"], ls,
                        ls["completed"], wall=stats["wall"],
                        sc=sc_for(ls["n_mux"], "paged"), rows=ls["rows"])
        lane_row["lane"] = ls["lane"]
        lane_row["role"] = ls["role"]
        lane_row["handoffs_out"] = ls["handoffs_out"]
        lane_row["handoffs_in"] = ls["handoffs_in"]
        lane_row["migrated_bytes"] = ls["migrated_bytes"]
        row["lanes"].append(lane_row)
    results.append(row)
    _csv(row)
    for lane_row in row["lanes"]:
        _csv(lane_row)
    if disagg_tel is not None:
        # the disagg arm's step-span trace: handoff spans + instants on
        # the lane timelines (CI uploads it next to the lanes trace)
        disagg_tel.write_trace(disagg_trace_out)
        print(f"serve_churn wrote {disagg_trace_out}")

    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"serve_churn wrote {json_path}")
    return results


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace (CI / laptop CPU)")
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--mux-n", type=int, default=2)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lanes", default="1,2,4", metavar="N1,N2,...",
                    help="width-lane arm + one fixed-N arm per width "
                         "('' disables the lane arms)")
    ap.add_argument("--kv-dtype", default="int8",
                    choices=["", "bf16", "int8", "fp8"],
                    help="page storage for the quantized-KV arms: adds "
                         "a kernels baseline, a quantized arm, and the "
                         "byte-parity capacity arm ('' disables)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump all rows (incl. per-lane breakdown and "
                         "routing counters) as JSON")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the lanes arm's telemetry metrics "
                         "snapshot as JSON (+ Prometheus .prom sibling)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the lanes arm's step-span trace as "
                         "Chrome trace-event JSON (ui.perfetto.dev)")
    ap.add_argument("--disagg-trace-out", default=None, metavar="PATH",
                    help="write the disagg arm's step-span trace — "
                         "handoff spans/instants on the lane timelines")
    args = ap.parse_args()
    lanes = (tuple(int(x) for x in args.lanes.split(","))
             if args.lanes else ())
    n = 6 if args.smoke else args.requests
    t0 = time.time()
    run(arch=args.arch, mux_n=args.mux_n, rows=args.rows, n_requests=n,
        chunk=args.chunk, seed=args.seed, lanes=lanes,
        kv_dtype=args.kv_dtype, json_path=args.json,
        metrics_out=args.metrics_out, trace_out=args.trace_out,
        disagg_trace_out=args.disagg_trace_out)
    print(f"serve_churn done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
