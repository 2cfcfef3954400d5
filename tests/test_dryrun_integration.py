"""Integration: the real dry-run path (specs -> shardings -> lower ->
compile -> roofline analysis) on a fake 8-device mesh with REDUCED
configs — the CI-scale version of the 512-chip production dry-run."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CODE = """
import os
assert os.environ["XLA_FLAGS"]
import jax
import numpy as np
from jax.sharding import Mesh
from repro.configs.registry import set_reduced_mode
set_reduced_mode(True)
from repro.launch import specs as S
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import HW
from repro.launch import hlo_analysis as H
from repro.runtime import sharding as shard
from repro.core import MuxSpec
from repro.configs import SHAPES

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))

# shrink the shape grid to CI scale
SHAPES["train_4k"] = SHAPES["train_4k"].__class__("train_4k", 32, 8, "train")
SHAPES["decode_32k"] = SHAPES["decode_32k"].__class__(
    "decode_32k", 64, 8, "decode")

for arch, shape, mux_n in [
    ("gemma-2b", "train_4k", 1),
    ("granite-moe-3b-a800m", "train_4k", 2),
    ("rwkv6-7b", "decode_32k", 2),
    ("whisper-small", "train_4k", 1),
]:
    mux = MuxSpec(n=mux_n)
    params = S.abstract_params(arch, mux)
    psh = shard.named(shard.param_specs(params, mesh), mesh)
    batch = S.input_specs(arch, shape, mux_n=mux_n)
    bsh = S.batch_shardings_for(batch, mesh)
    sh = SHAPES[shape]
    if sh.kind == "train":
        opt = S.make_optimizer()
        osh = shard.named(shard.opt_state_specs(params, mesh), mesh)
        fn = S.build_train_step(arch, mux=mux, optimizer=opt, mesh=mesh)
        jitted = jax.jit(fn, in_shardings=(
            psh, osh, bsh), out_shardings=(psh, osh, None))
        with mesh:
            compiled = jitted.lower(
                params, S.abstract_opt_state(params, opt), batch).compile()
    else:
        cache = S.abstract_cache(arch, shape, mux)
        csh = shard.named(shard.cache_specs(cache, mesh), mesh)
        fn = S.build_decode_step(arch, mux=mux, seq_len=sh.seq_len,
                                 mesh=mesh)
        jitted = jax.jit(fn, in_shardings=(psh, csh, bsh),
                         out_shardings=(None, csh))
        with mesh:
            compiled = jitted.lower(params, cache, batch).compile()
    a = analyze(compiled.as_text())
    assert a["flops"] > 0, arch
    rl = H.roofline_terms(a, HW)
    print(f"CELL-OK {arch} {shape} N={mux_n} bound={rl['bottleneck']}")
print("ALL-OK")
"""


def test_dryrun_reduced_grid():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"          # the fake devices are host devices
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ALL-OK" in r.stdout
    assert r.stdout.count("CELL-OK") == 4
