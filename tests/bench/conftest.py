"""Shared set-up of the benchmark's CPU tests: the repository root on
``sys.path`` (for ``bench``) and tiny variants of the benchmark's cells.

A tiny cell keeps a cell's configuration and traffic files but shrinks
every size so that a whole run, kernels in interpret mode included,
takes seconds on the CPU.  The cells are every workload of
``BENCHMARK.json``, each configuration found through its ``file`` there,
and the test-only ``VARIANTS``."""
import copy
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG_FILES = {c["name"]: c["file"] for c in BENCH["configs"]}
# test-only variants of a configuration: (configuration, traffic mix,
# changed keys).  "windowed-untied" carries the layer features of the
# reference beside qwen2's (a sliding window, an untied head, no q/k/v
# bias), as the program's h2o-danube-1.8b has them
VARIANTS = {
    "windowed-untied": ("qwen2-1.5b-mux2", "chat", {
        "arch": "h2o-danube-1.8b", "qkv_bias": False,
        "tie_word_embeddings": False, "use_sliding_window": True,
        "sliding_window": 4096, "rope_theta": 10000.0}),
}
# (configuration, traffic mix) of the tiny cells
CELLS = tuple((w["config"], w["traffic"]) for w in BENCH["workloads"]) + \
    tuple((name, mix) for name, (_, mix, _) in VARIANTS.items())


def cell(config: str, mix: str) -> dict:
    from bench import harness
    base, _, changes = VARIANTS.get(config, (config, mix, {}))
    c = harness.cell_from_files(f"{config}.{mix}", CONFIG_FILES[base], mix)
    c["config"].update(changes)
    return c


def tiny(cell: dict) -> dict:
    cell = copy.deepcopy(cell)
    conf = cell["config"]
    conf.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=128, vocab_size=512)
    if conf.get("sliding_window"):
        conf["sliding_window"] = 24          # rows of up to 64 cross it
    conf["serve"].update(rows=4, capacity=64, block_size=4, chunk=16)
    conf["check"].update(tokens=200, max_groups=4, min_tokens=20)
    cell["traffic"].update(
        rate_rps=3.0, warmup_s=1.0,
        prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.6,
                    "min": 4, "max": 40},
        output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                    "min": 2, "max": 16})
    return cell


@pytest.fixture(params=CELLS, ids=lambda c: c[0])
def tiny_cell(request):
    return tiny(cell(*request.param))


def serve_requests(cell: dict, dtype: str, seed: int, lengths):
    """Serve ``lengths`` ((prompt, output) pairs) to completion through
    ``ServeRuntime`` in the cell's configuration at ``dtype``; returns
    (params, spec, prompts, finished groups, served tokens) in the form
    ``bench.check`` takes."""
    import numpy as np
    import jax.numpy as jnp
    from bench import harness
    from bench.model_spec import model_spec
    from bench.weights import make_params
    from repro.serve import Request
    from repro.serve.runtime import ServeRuntime

    conf = copy.deepcopy(cell["config"])
    conf["serve"]["dtype"] = dtype
    spec = model_spec(conf)
    _, _, sc = harness.program_config(conf, spec)
    srv = conf["serve"]
    params = make_params(spec, seed, getattr(jnp, dtype),
                         tuple(srv["f32_leaves"]))
    rt = ServeRuntime(params, sc, srv["rows"], chunk=srv["chunk"],
                      use_kernels=srv["use_kernels"])
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, spec["vocab"], size=n, dtype=np.int32)
               for n, _ in lengths]
    reqs = [Request(uid=k, prompt=p, max_new=m)
            for k, (p, (_, m)) in enumerate(zip(prompts, lengths))]
    for r in reqs:
        rt.submit(r)
    groups = []
    while rt.has_work():
        rt.step()
        for j, row in enumerate(rt.sched.slots):
            members = {i: s.request.uid for i, s in enumerate(row)
                       if s.request is not None}
            known = {k for g in groups for k in g["slots"].values()}
            if members and not set(members.values()) & known:
                groups.append({"row": j, "l_pad": max(
                    len(prompts[k]) for k in members.values()),
                    "slots": members})
    served = {r.uid: list(r.output) for r in reqs}
    return params, spec, conf, prompts, groups, served
