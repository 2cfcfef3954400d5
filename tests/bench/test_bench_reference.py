"""The plain float32 reference (``bench/reference/decoder.py``) against
the serving program, at tiny sizes of the configuration and of a
variant with a sliding window, an untied head and no q/k/v bias.

Tolerances:
  * the weight tree is the program's, leaf for leaf (exact);
  * the program's float32 forward and the reference differ only in the
    order of float32 operations, so their logits agree to 1e-4 of the
    logits' scale (a wrong norm, key, bias, window or head is O(1) of
    it);
  * served through ``ServeRuntime`` in float32, with chunked prefill
    across chunk and block boundaries, the Pallas kernels in interpret
    mode and then decode through the paged pool, every served greedy
    token is the reference's best to 1e-4 (a near-tie can flip the
    argmax, but only by a gap of that order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, weights
from bench.model_spec import model_spec
from bench.reference import decoder as ref
from conftest import cell, serve_requests

# prompts longer than a chunk (16) and not a multiple of a block (4);
# a row pads its two prompts to the longer
LENGTHS = [(37, 9), (21, 12), (5, 3), (29, 7)]


@pytest.mark.parametrize("name", ["qwen2-1.5b-mux2", "windowed-untied"])
def test_weight_tree_is_the_programs(name):
    from repro.models import TransformerLM
    conf = cell(name, "chat")["config"]
    spec = model_spec(conf)
    cfg, mux, _ = harness.program_config(conf, spec)
    prog = jax.eval_shape(lambda k: TransformerLM.init(k, cfg, mux),
                          jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda: weights.make_params(spec, 0))
    assert jax.tree.structure(prog) == jax.tree.structure(ours)
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape,
                                        prog, ours)) == [True] * len(
        jax.tree.leaves(prog))


def test_program_forward_matches_reference(tiny_cell):
    from repro.models import TransformerLM
    conf = tiny_cell["config"]
    spec = model_spec(conf)
    cfg, mux, _ = harness.program_config(conf, spec)
    params = weights.make_params(spec, 11, jnp.float32)
    tokens = np.random.default_rng(0).integers(
        1, spec["vocab"], size=(spec["n_mux"], 48), dtype=np.int32)
    out = TransformerLM.apply(params, cfg, jnp.asarray(tokens), mux=mux,
                              dtype=jnp.float32)["logits"]
    h = ref.demuxed_hidden(params, spec, tokens)
    head = params["embed"]["table"] if spec["tied"] else \
        params["lm_head"]["w"].T
    want = jnp.einsum("ntd,vd->ntv", h, head,
                      precision=jax.lax.Precision.HIGHEST)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(out - want).max()) <= 1e-4 * scale


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
