import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) cell against the production meshes, print memory/cost analysis, and
record roofline terms.

The lines above MUST stay the first statements in this module — jax
locks the device count at first backend init, and only the dry-run may
see 512 placeholder host devices.  They are host (CPU) devices, so the
platform is pinned too: on a machine with an accelerator JAX would
otherwise take it and fail to build the (16, 16) mesh.

Usage:
    python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
    python -m repro.launch.dryrun --arch rwkv6-7b --shape long_500k \
        --mux-n 4      # the paper's technique on the serving path
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.core import MuxSpec
from repro.configs import ARCHS, SHAPES, get_config, model_kind, cell_status
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh, HW
from repro.launch.hlo_analysis import analyze, op_census, roofline_terms
from repro.models.config import param_count, active_param_count
from repro.runtime import sharding as shard
from jax.sharding import NamedSharding, PartitionSpec as P


def _cost_dict(compiled):
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return dict(c) if c else {}
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def _memory_dict(compiled):
    try:
        m = compiled.memory_analysis()
        if m is None:
            return {}
        return {
            "argument_bytes": getattr(m, "argument_size_in_bytes", None),
            "output_bytes": getattr(m, "output_size_in_bytes", None),
            "temp_bytes": getattr(m, "temp_size_in_bytes", None),
            "peak_bytes": getattr(m, "peak_memory_in_bytes", None),
            "generated_code_bytes": getattr(
                m, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def lower_cell(arch: str, shape_name: str, mesh, *, mux_n: int = 1,
               vocab_chunk: int = 0, donate: bool = True):
    """Build + lower one cell.  Returns (lowered, aux_info)."""
    sh = SHAPES[shape_name]
    mux = MuxSpec(n=mux_n)
    params_struct = S.abstract_params(arch, mux)
    pshard = shard.named(shard.param_specs(params_struct, mesh), mesh)
    batch = S.input_specs(arch, shape_name, mux_n=mux_n)
    bshard = S.batch_shardings_for(batch, mesh)

    if sh.kind == "train":
        opt = S.make_optimizer()
        opt_struct = S.abstract_opt_state(params_struct, opt)
        oshard = shard.named(
            shard.opt_state_specs(params_struct, mesh), mesh)
        step = S.build_train_step(arch, mux=mux, optimizer=opt,
                                  vocab_chunk=vocab_chunk, mesh=mesh)
        jitted = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1) if donate else ())
        with mesh:
            lowered = jitted.lower(params_struct, opt_struct, batch)
        return lowered

    cache_struct = S.abstract_cache(arch, shape_name, mux)
    cshard = shard.named(shard.cache_specs(cache_struct, mesh), mesh)
    if sh.kind == "prefill":
        fn = S.build_prefill(arch, mux=mux, mesh=mesh)
    else:
        fn = S.build_decode_step(arch, mux=mux, seq_len=sh.seq_len,
                                 mesh=mesh)
    jitted = jax.jit(
        fn,
        in_shardings=(pshard, cshard, bshard),
        out_shardings=(None, cshard),
        donate_argnums=(1,) if donate else ())
    with mesh:
        lowered = jitted.lower(params_struct, cache_struct, batch)
    return lowered


def run_cell(arch: str, shape_name: str, mesh_kind: str = "single", *,
             mux_n: int = 1, vocab_chunk: int = 0,
             keep_text: bool = False) -> dict:
    status = cell_status(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mux_n": mux_n, "status": status}
    if status != "ok":
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    cfg = get_config(arch)
    t0 = time.time()
    try:
        lowered = lower_cell(arch, shape_name, mesh, mux_n=mux_n,
                             vocab_chunk=vocab_chunk)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = _cost_dict(compiled)
        memory = _memory_dict(compiled)
        text = compiled.as_text()
        analysis = analyze(text)          # trip-count-aware (per device)
        census = op_census(text)
        rl = roofline_terms(analysis, HW)
        n = param_count(cfg)
        na = active_param_count(cfg)
        sh = SHAPES[shape_name]
        tokens = sh.global_batch * (sh.seq_len if sh.kind in
                                    ("train", "prefill") else 1)
        mult = 6 if sh.kind == "train" else 2
        model_flops = mult * na * tokens          # global useful FLOPs
        hlo_flops_global = rl["flops"] * n_chips  # per-device -> global
        rec.update({
            "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "chips": n_chips,
            "params": n, "active_params": na,
            "cost": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "optimal_seconds")
                     if k in cost},
            "memory": memory,
            "collectives": analysis["collectives"],
            "op_census": census,
            "roofline": rl,
            "model_flops": model_flops,
            "useful_flops_ratio": (model_flops / hlo_flops_global
                                   if hlo_flops_global else None),
        })
        if keep_text:
            rec["hlo_text"] = text
    except Exception as e:
        rec["status"] = f"error: {type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def fmt_row(r: dict) -> str:
    if not r["status"].startswith("ok"):
        return (f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} "
                f"{r['status'][:80]}")
    rl = r["roofline"]
    mem = r["memory"].get("peak_bytes") or 0
    return (f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} "
            f"N={r['mux_n']:<2d} "
            f"compute={rl['compute_s']*1e3:9.2f}ms "
            f"memory={rl['memory_s']*1e3:9.2f}ms "
            f"coll={rl['collective_s']*1e3:9.2f}ms "
            f"bound={rl['bottleneck']:10s} "
            f"peak={mem/1e9:6.2f}GB "
            f"useful={100*(r['useful_flops_ratio'] or 0):5.1f}% "
            f"[lower {r['t_lower_s']}s compile {r['t_compile_s']}s]")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--mux-n", type=int, default=1)
    ap.add_argument("--vocab-chunk", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="jsonl output path")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="config override applied to every arch in this "
                         "run, e.g. --set attn_seq_shard=true "
                         "--set moe_impl=local_group --set rwkv_chunk=16")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        else:
            try:
                v = int(v)
            except ValueError:
                pass
        overrides[k] = v

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if overrides:
        from repro.configs.registry import set_overrides
        for arch in archs:
            set_overrides(arch, **overrides)

    recs = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                r = run_cell(arch, shape, mk, mux_n=args.mux_n,
                             vocab_chunk=args.vocab_chunk)
                recs.append(r)
                print(fmt_row(r), flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(
                            {k: v for k, v in r.items()
                             if k != "hlo_text"}) + "\n")
    bad = [r for r in recs if r["status"].startswith("error")]
    print(f"\n{len(recs) - len(bad)}/{len(recs)} cells passed "
          f"({sum(1 for r in recs if r['status'].startswith('skip'))} "
          f"skipped by design)")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
