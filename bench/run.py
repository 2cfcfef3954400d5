"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, the device
busy time and a breakdown from a profiler trace of part of the window.
It needs the serving program (``src/repro``) beside it and a TPU with
as many chips as the cell asks for; otherwise it exits non-zero and
prints no result.  The numbers that decide ``correct`` are printed last
on standard error, each beside its limit, and again under ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def enable_compile_cache():
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` inside the checkout (a fixed path, so a
    second run finds every program); every program is kept, however
    quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of deleting it")
    ap.add_argument("--control", action="store_true",
                    help="compare the float8 control in the program's place "
                         "(correct must come out false)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: the serving program (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.check import print_checks

    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  trace_dir=args.trace_dir,
                                  control=args.control)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print_checks(result["checks"])
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
