"""Seeded random weights for the served decoder, made by the benchmark.

The weights are the benchmark's, not the program's: one jitted call
draws every leaf on the device from ``--seed`` in the dtype they are
served in, laid out as the serve path's parameter tree expects
(``repro.models.TransformerLM``: layers stacked on a leading axis, a
mux engine with a Gaussian mux and an RSA demux).  The reference reads
the same tree, so both sides start from identical numbers.

Distributions: matrices and embeddings N(0, 0.02) as in the program's
own init; biases N(0, 0.02); the RMSNorm scales (stored as ``1 + s``)
N(0, 0.1) and the demux LayerNorm scale 1 + N(0, 0.1), so that a norm
applied without its scale shows; mux and demux keys N(0, 1).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_STD = {"w": 0.02, "table": 0.02, "b": 0.02, "bias": 0.02, "scale": 0.1,
        "v": 1.0, "k": 1.0}


def prng_key(seed: int):
    """A threefry key from any non-negative seed (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def param_shapes(s: dict) -> dict:
    """The serve path's parameter tree for spec ``s``, shapes as leaves."""
    d, nl, h, hk = s["d"], s["layers"], s["heads"], s["kv_heads"]
    hd, f = s["head_dim"], s["ffn"]

    def proj(out):
        p = {"w": (nl, d, *out)}
        if s["qkv_bias"]:
            p["b"] = (nl, *out)
        return p

    layer = {"ln1": {"scale": (nl, d)},
             "wq": proj((h, hd)), "wk": proj((hk, hd)), "wv": proj((hk, hd)),
             "wo": {"w": (nl, h * hd, d)},
             "ln2": {"scale": (nl, d)},
             "ffn": {"up": {"w": (nl, d, f)}, "down": {"w": (nl, f, d)},
                     "gate": {"w": (nl, d, f)}}}
    tree = {"embed": {"table": (s["vocab"], d)}, "periods": (layer,),
            "tail": (), "final_norm": {"scale": (d,)}}
    if not s["tied"]:
        tree["lm_head"] = {"w": (d, s["vocab"])}
    if s["n_mux"] > 1:
        n, dh = s["n_mux"], s["demux_hidden"]
        tree["mux_engine"] = {
            "mux": {"v": (n, d)},
            "demux": {"k": (n, d), "w1h": {"w": (d, dh), "b": (dh,)},
                      "w1k": {"w": (d, dh)}, "w2": {"w": (dh, d), "b": (d,)},
                      "ln": {"scale": (d,), "bias": (d,)}}}
    return tree


def _is_shape(x):
    return (isinstance(x, tuple) and len(x) > 0
            and all(isinstance(i, int) for i in x))


def make_params(s: dict, seed: int, dtype=jnp.bfloat16, f32_leaves=()):
    """Every weight of spec ``s`` from ``seed``, in one jitted call, as
    values of ``dtype``; the leaves named in ``f32_leaves`` (dotted paths)
    keep those values in a float32 container."""
    shapes = param_shapes(s)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def draw(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * _STD[name]
            if name == "scale" and path[-2].key == "ln":
                x = x + 1.0                 # LayerNorm scale is multiplied
            x = x.astype(dtype)
            dotted = ".".join(str(getattr(k, "key", k)) for k in path)
            out.append(x.astype(jnp.float32) if dotted in f32_leaves else x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(prng_key(seed))
