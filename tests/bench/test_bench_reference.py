"""The plain float32 reference (``bench/reference/decoder.py``) against
the serving program, at tiny sizes of every configuration of
``BENCHMARK.json`` and of a variant with a sliding window, an untied
head and no q/k/v bias.

Tolerances:
  * the weight tree is the program's, leaf for leaf (exact);
  * the program's float32 forward and the reference differ only in the
    order of float32 operations, so their logits agree to 1e-4 of the
    logits' scale (a wrong norm, key, bias, window or head is O(1) of
    it);
  * a configuration the program cannot run as stated (an RMSNorm
    epsilon other than its fixed one) is refused at set-up, or, once
    the program takes it, matches the reference as above;
  * served through ``ServeRuntime`` in float32, with chunked prefill
    across chunk and block boundaries, the Pallas kernels in interpret
    mode and then decode through the paged pool, every served greedy
    token is the reference's best to 1e-4 (a near-tie can flip the
    argmax, but only by a gap of that order).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, weights
from bench.model_spec import model_spec
from bench.reference import decoder as ref
from conftest import (BENCH, CELLS, CONFIG_FILES, ROOT, VARIANTS, cell,
                      serve_requests, tiny)

# prompts longer than a chunk (16) and not a multiple of a block (4);
# a row pads its two prompts to the longer
LENGTHS = [(37, 9), (21, 12), (5, 3), (29, 7)]


def _program_tree_matches(conf):
    from repro.models import TransformerLM
    spec = model_spec(conf)
    cfg, mux, _ = harness.program_config(conf, spec)
    prog = jax.eval_shape(lambda k: TransformerLM.init(k, cfg, mux),
                          jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda: weights.make_params(spec, 0))
    assert jax.tree.structure(prog) == jax.tree.structure(ours)
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape,
                                        prog, ours)) == [True] * len(
        jax.tree.leaves(prog))


@pytest.mark.parametrize("name", [*CONFIG_FILES, *VARIANTS])
def test_weight_tree_is_the_programs(name):
    mix = dict(CELLS)[name]
    _program_tree_matches(cell(name, mix)["config"])


def _forward_gap(conf, eps=None):
    """(widest gap between the program's float32 logits and the
    reference's, the reference logits' scale) on one row of 48
    positions; ``eps`` runs the reference at that RMSNorm epsilon."""
    from repro.models import TransformerLM
    spec = model_spec(conf)
    cfg, mux, _ = harness.program_config(conf, spec)
    params = weights.make_params(spec, 11, jnp.float32)
    tokens = np.random.default_rng(0).integers(
        1, spec["vocab"], size=(spec["n_mux"], 48), dtype=np.int32)
    out = TransformerLM.apply(params, cfg, jnp.asarray(tokens), mux=mux,
                              dtype=jnp.float32)["logits"]
    if eps is not None:
        spec = {**spec, "norm_eps": eps}
    h = ref.demuxed_hidden(params, spec, tokens)
    head = params["embed"]["table"] if spec["tied"] else \
        params["lm_head"]["w"].T
    want = jnp.einsum("ntd,vd->ntv", h, head,
                      precision=jax.lax.Precision.HIGHEST)
    return float(jnp.abs(out - want).max()), float(jnp.abs(want).max())


def test_program_forward_matches_reference(tiny_cell):
    gap, scale = _forward_gap(tiny_cell["config"])
    assert gap <= 1e-4 * scale


def test_norm_eps_is_run_or_refused():
    # danube publishes rms_norm_eps 1e-5: the harness hands it to the
    # program, or refuses the configuration at set-up, naming the key
    conf = tiny(cell("windowed-untied", "chat"))["config"]
    conf["rms_norm_eps"] = 1e-5
    from repro.models.config import ModelConfig
    takes_eps = "norm_eps" in {f.name for f in dataclasses.fields(ModelConfig)}
    try:
        gap, scale = _forward_gap(conf)
    except ValueError as e:
        assert not takes_eps
        assert "rms_norm_eps" in str(e)
        assert "1e-05" in str(e) and "1e-06" in str(e)
    else:
        assert takes_eps
        assert gap <= 1e-4 * scale


def test_norm_eps_shows_in_the_forward():
    # why a departure is refused and not run: the program at its 1e-6
    # against the reference at 1e-5 is far outside the tolerance above
    conf = tiny(cell("windowed-untied", "chat"))["config"]
    gap, scale = _forward_gap(conf, eps=1e-5)
    assert gap > 1e-3 * scale


def test_demux_hidden_reaches_the_program():
    # a demux MLP of 3 x d, not the program's default 2 x d
    conf = tiny(cell("qwen2-1.5b-mux2", "chat"))["config"]
    conf["mux"]["demux_hidden"] = 3 * conf["hidden_size"]
    assert model_spec(conf)["demux_hidden"] == 192
    _program_tree_matches(conf)
    gap, scale = _forward_gap(conf)
    assert gap <= 1e-4 * scale


def test_every_workload_is_a_tiny_cell():
    for w in BENCH["workloads"]:
        assert (w["config"], w["traffic"]) in CELLS
        c = cell(w["config"], w["traffic"])
        conf = next(x for x in BENCH["configs"] if x["name"] == w["config"])
        assert c["config"] == json.loads((ROOT / conf["file"]).read_text())


def test_served_tokens_are_reference_best(tiny_cell):
    params, spec, conf, prompts, groups, served = serve_requests(
        tiny_cell, "float32", 5, LENGTHS)
    assert sorted(k for g in groups for k in g["slots"].values()) == [
        0, 1, 2, 3]
    length = check.reference_length(conf["serve"]["capacity"])
    n = 0
    for g in groups:
        arrays = check.group_arrays(g, prompts, served, spec["n_mux"], length)
        r = check.gaps(params, spec, *arrays)
        assert float(r["gap"].max()) <= 1e-4
        n += len(r["gap"])
    assert n == sum(m for _, m in LENGTHS)
