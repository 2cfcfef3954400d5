"""Chip smoke test: serve full-width qwen2-1.5b on a TPU.

Drives ``repro.launch.serve.run_continuous`` (the loop behind
``python -m repro.launch.serve --continuous --cache paged``) with the
published qwen2-1.5b config (28 layers, d_model 1536, vocab 151936),
random weights from ``--seed``, mux N=2, chunked prefill and greedy
sampling, on a handful of requests of a few dozen tokens.

    python chip_smoke.py              # one chip: gather arm + kernel arm
    python chip_smoke.py --mesh 2,2   # four chips: kernel arm on a
                                      # (data=2, model=2) mesh + the same
                                      # requests on one chip

Each arm prints what it served, its compile counts, device memory and a
wall time (a smoke timing, not a metric).  A probe then takes one
mid-flight cache of the kernel arm's runtime and checks that its
compiled decode step holds Mosaic kernels (``tpu_custom_call``) and that
one decode step's logits from the kernel path and the gather path agree
within ``LOGIT_TOL``.  Any failed check raises, so the exit code is not
0.  The last line of a passing run is one JSON object naming the device.
It refuses to run anywhere but a TPU, and only from a checkout of the
repository (it imports ``src/repro``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ARCH = "qwen2-1.5b"
MUX_N = 2
ROWS = 2                 # backbone rows: 2 rows x N=2 = 4 streams in flight
REQUESTS = 6
PROMPT_LEN = 40          # chunks of 32 + 8: two prefill buckets
NEW_TOKENS = 16
CHUNK = 32
BLOCK_SIZE = 16
ARRIVAL_EVERY = 2
# Kernel vs gather logits, both traced under matmul precision "highest":
# a wrong page, slot or mask moves the logits by O(1) of their scale,
# while fp32 rounding, or bf16 passes in a kernel's dots, stay far below
# 2% of it.
LOGIT_TOL = 2e-2


def _check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _memory(devices):
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(f"dev{d.id} peak_bytes_in_use={st.get('peak_bytes_in_use')}"
                   f" bytes_in_use={st.get('bytes_in_use')}")
    return "; ".join(out)


def _expected_programs():
    """Programs a compile-once run of PROMPT_LEN-token prompts traces:
    one decode step and one chunk step per prefill bucket it uses."""
    from repro.serve.runtime import chunk_buckets
    buckets = chunk_buckets(CHUNK)
    lens = [CHUNK] * (PROMPT_LEN // CHUNK)
    if PROMPT_LEN % CHUNK:
        lens.append(PROMPT_LEN % CHUNK)
    used = {next(b for b in buckets if b >= n) for n in lens}
    return {"decode": 1, **{f"prefill_{b}": 1 for b in used}}


def _arrivals(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [(i * ARRIVAL_EVERY,
             rng.integers(4, vocab, size=(PROMPT_LEN,)).astype(np.int32),
             NEW_TOKENS)
            for i in range(REQUESTS)]


def _serve_arm(name, params, sc, arrivals, *, use_kernels, mesh, devices):
    import jax
    from repro.launch.serve import run_continuous
    t0 = time.time()
    stats = run_continuous(params, sc, ROWS, arrivals, chunk=CHUNK,
                           prefill_mode="chunked", mesh=mesh,
                           use_kernels=use_kernels)
    wall = time.time() - t0
    done = stats["completed"]
    counts = dict(stats["trace_counts"])
    print(f"[{name}] served {len(done)} requests, "
          f"{stats['generated_tokens']} tokens generated; "
          f"wall {wall:.2f} s (smoke timing incl. compiles, not a metric)")
    print(f"[{name}] trace_counts {counts}; prefill mode "
          f"{stats['prefill_mode']}")
    print(f"[{name}] memory: {_memory(devices)}")
    _check(len(done) == REQUESTS, f"{name}: served {len(done)} of "
           f"{REQUESTS} requests")
    _check(all(len(r.output) == NEW_TOKENS for r in done),
           f"{name}: a request stopped short of {NEW_TOKENS} tokens")
    _check(counts == _expected_programs(),
           f"{name}: trace_counts {counts} != {_expected_programs()}")
    # the runtime drops to blocking prefill for recurrent blocks or
    # contextual mux; this config must keep the chunked path
    _check(stats["prefill_mode"] == "chunked",
           f"{name}: runtime fell back to {stats['prefill_mode']} prefill")
    del stats
    gc.collect()
    return {r.uid: list(r.output) for r in done}


def _agreement(a, b):
    same = total = 0
    for uid in a:
        same += sum(x == y for x, y in zip(a[uid], b[uid]))
        total += len(a[uid])
    return same, total


def _probe(params, sc, arrivals, mesh):
    """Kernel arm's runtime, stepped until every row has a fully
    prefilled group, has decoded, and has its next slot inside an
    allocated block.  Returns (custom-call count in its compiled decode
    step, max |logit| of the gather path, max |Δlogits| kernel-gather)."""
    import jax
    from repro.serve import Request
    from repro.serve.engine import decode_step
    from repro.serve.runtime import ServeRuntime
    rt = ServeRuntime(params, sc, ROWS, chunk=CHUNK, mesh=mesh,
                      use_kernels=True)
    for uid, (_, prompt, max_new) in enumerate(arrivals[:rt.nb]):
        rt.submit(Request(uid=uid, prompt=list(prompt), max_new=max_new))

    def ready():
        return (len(rt.row_len) == rt.nrows and not rt.sched.prefill_progress
                and rt.stats["decode_steps"] > 0
                and all(n % BLOCK_SIZE for n in rt.row_len.values()))

    for _ in range(BLOCK_SIZE + 4):
        if ready():
            break
        rt.step()
    _check(ready(), "probe: rows never reached a mid-block decode state")
    pos = np.full((rt.nrows,), -1, np.int32)
    for j, n in rt.row_len.items():
        pos[j] = n
    rt._clear_dead_slots()
    toks = rt.next_tok.reshape(-1)[:, None]
    hlo = rt._decode_jit.lower(rt.params, rt.cache, toks, pos,
                               *rt._sampling_grid()).compile().as_text()
    ctx = rt._step_ctx(rt._trash)
    logits = {}
    with jax.default_matmul_precision("highest"):
        for uk in (False, True):
            f = jax.jit(lambda p, c, t, q, uk=uk: decode_step(
                p, sc, c, t, q, extra_ctx=ctx, use_kernels=uk)[0])
            logits[uk] = np.asarray(f(rt.params, rt.cache, toks, pos))
    for lg in logits.values():
        _check(lg.shape == (rt.nb, 1, sc.cfg.vocab_size)
               and np.isfinite(lg).all(), "probe: logits not finite or "
               "of the wrong shape")
    scale = float(np.abs(logits[False]).max())
    gap = float(np.abs(logits[True] - logits[False]).max())
    return hlo.count("tpu_custom_call"), scale, gap


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="run the kernel arm on a (data, model) mesh and "
                         "compare it with the same requests on one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: no src/repro next to this script — run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 3

    from repro.configs import get_config
    from repro.core import MuxSpec
    from repro.models import TransformerLM
    from repro.serve import ServeConfig

    cfg = get_config(ARCH, reduced=False)
    mux = MuxSpec(n=MUX_N)
    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}")
    print(f"model: {ARCH} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size}; mux N={MUX_N}, {ROWS} rows, fp32")
    # the blocks.py kernel branches skip Pallas when logit_softcap is set
    _check(cfg.logit_softcap is None, "logit_softcap set: the paged "
           "kernels would be skipped")
    print("fallbacks: logit_softcap=None (no Pallas skip); chunked prefill "
          "checked per arm")

    t0 = time.time()
    params = jax.jit(TransformerLM.init, static_argnums=(1, 2))(
        jax.random.PRNGKey(args.seed), cfg, mux)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params} random fp32 weights from seed {args.seed} "
          f"in {time.time() - t0:.1f} s")
    arrivals = _arrivals(cfg.vocab_size, args.seed)

    def serve_config(n_shards):
        return ServeConfig(cfg=cfg, kind="lm", mux=mux,
                           capacity=PROMPT_LEN + NEW_TOKENS + 8,
                           dtype=jnp.float32, cache_layout="paged",
                           block_size=BLOCK_SIZE, n_shards=n_shards)

    if args.mesh is None:
        sc = serve_config(1)
        ref = _serve_arm("gather", params, sc, arrivals, use_kernels=False,
                         mesh=None, devices=[dev])
        got = _serve_arm("kernels", params, sc, arrivals, use_kernels=True,
                         mesh=None, devices=[dev])
        probe_sc, probe_mesh, names = sc, None, ("kernels", "gather")
    else:
        from repro.launch.mesh import make_serve_mesh
        data, model = (int(x) for x in args.mesh.split(","))
        mesh = make_serve_mesh(data, model)
        print("mesh: paged attention kernels run under shard_map; the mux "
              "entry/exit run as XLA ops (GSPMD cannot partition a Mosaic "
              "kernel)")
        got = _serve_arm(f"kernels mesh{(data, model)}", params,
                         serve_config(data), arrivals, use_kernels=True,
                         mesh=mesh, devices=list(mesh.devices.flat))
        ref = _serve_arm("kernels one-chip", params, serve_config(1),
                         arrivals, use_kernels=True, mesh=None,
                         devices=[dev])
        probe_sc, probe_mesh = serve_config(data), mesh
        names = (f"kernels mesh{(data, model)}", "kernels one-chip")

    same, total = _agreement(got, ref)
    print(f"greedy-token agreement {names[0]} vs {names[1]}: {same}/{total} "
          f"= {same / total:.4f} (reported, not asserted)")

    n_cc, scale, gap = _probe(params, probe_sc, arrivals, probe_mesh)
    print(f"[probe] kernel-arm decode step: tpu_custom_call x{n_cc}")
    print(f"[probe] one decode step on one cache: max|logit| {scale:.6g}, "
          f"max|Δlogits| kernel vs gather {gap:.6g} "
          f"(tolerance {LOGIT_TOL} x {scale:.6g} = {LOGIT_TOL * scale:.6g})")
    _check(n_cc > 0, "kernel-arm decode step holds no tpu_custom_call")
    _check(gap <= LOGIT_TOL * scale, "kernel/gather logit gap above "
           "tolerance")
    print(f"memory after probe: "
          f"{_memory(devices if args.mesh else [dev])}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
