"""Find a cell's knee: the highest offered rate it sustains without a
growing backlog.

    python3 bench/sweep.py --workload <name> --rates 4,6,8 [--seconds 20]

One process sets the cell up once, then serves the cell's traffic at
each rate in turn (its warm-up, then a window), and drains what is left
before the next rate.  Rate i's traffic comes from seed ``--seed`` + i;
in a mix with ``order_seed`` that seed draws only the token ids, so the
sweep serves one fixed order at every rate, laid over the number of
requests the rate makes.  Per rate it prints one JSON line: the offered
and admitted rates, the backlog (requests due and not yet admitted) at the
window's start and end, completed tokens per second, and TTFT and
inter-token-gap percentiles.  A backlog that grows across the window,
or an admitted rate below the offered, marks a rate above the knee:
a rate is sustained when the window admits at least 97% of the offered
rate and the backlog grows by at most max(3, 5% of the requests due).
After the first rate that is not, the sweep tries the midpoint between
it and the last sustained one, then prints the knee (the highest
sustained rate) and 0.8 x the knee.  The chosen rate goes into the
traffic file as a number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def backlog(rec, t: float) -> int:
    """Requests due by t and not admitted by t."""
    return sum(1 for d, a in zip(rec.due, rec.admit)
               if d <= t and (a is None or a > t))


def sustained(row: dict) -> bool:
    grew = row["backlog_end"] - row["backlog_start"]
    return (row["admitted_rps"] >= 0.97 * row["rate"]
            and grew <= max(3, 0.05 * row["due"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s, ascending")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain-seconds", type=float, default=90.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, stats, traffic
    from bench.run import enable_compile_cache

    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    st = harness.setup_cell(cell, args.seed, trace=False, require_tpu=True,
                            t_start=T_START)
    rt = st.rt
    rates = [float(r) for r in args.rates.split(",")]
    ok, refined, i = [], False, 0
    while rates:
        rate = rates.pop(0)
        sched = traffic.make_schedule(st.mix, args.seed + i, args.seconds,
                                      st.spec["vocab"], rate=rate)
        i += 1
        drv, _, compiles = harness.serve(st, sched, args.seconds)
        rec, t0 = drv.rec, sched.window_start
        t1 = t0 + args.seconds
        e2e = harness.end_to_end(rec, t0, t1)
        admitted = sum(1 for a in rec.admit if a is not None and t0 <= a < t1)
        first = [s[0] if s else None for s in rec.stamps]
        ttft = stats.censored_waits(rec.due, first, t0, t1)
        row = {"workload": args.workload, "rate": rate,
               "due": sum(1 for d in rec.due if t0 <= d < t1),
               "admitted_rps": admitted / args.seconds,
               "backlog_start": backlog(rec, t0),
               "backlog_end": backlog(rec, t1),
               "out_tok_s": e2e["out_tok_s"],
               "ttft_p50_s": stats.percentile(ttft, 50),
               "ttft_p95_s": e2e["ttft_p95_s"],
               "itl_p50_s": stats.percentile(
                   stats.gaps_ending_in(rec.stamps, t0, t1), 50),
               "itl_p95_s": e2e["itl_p95_s"],
               "window_compiles": compiles}
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            ok.append(rate)
        t = time.perf_counter()
        while rt.has_work() and time.perf_counter() - t < args.drain_seconds:
            rt.step()
        rt.sched.completed.clear()
        if rt.has_work():
            print(f"sweep: backlog not drained in {args.drain_seconds} s "
                  f"after {rate} req/s; stopping", flush=True)
            break
        if not row["sustained"]:
            if refined or not ok:
                break
            refined = True                # one midpoint, then stop
            rates = [round((max(ok) + rate) / 2, 2)]
    knee = max(ok) if ok else None
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "rate_0.8": None if knee is None
                      else round(0.8 * knee, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
