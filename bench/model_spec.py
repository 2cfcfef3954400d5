"""The sizes of one benchmark configuration, read from its JSON file.

A configuration file (``bench/configs/<name>.json``) keeps the published
``config.json`` keys of the model under their own names, plus the keys
the benchmark adds: ``arch`` (the model's name in ``repro.configs``),
``qkv_bias``, ``mux`` and ``serve``.  ``model_spec`` turns it into the
flat dict of sizes that the weight maker, the reference and the FLOP and
byte counts share, so all three read one set of numbers.
"""
from __future__ import annotations


def model_spec(config: dict) -> dict:
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    mux = config.get("mux", {})
    window = config.get("sliding_window")
    if not config.get("use_sliding_window", True):
        window = None
    return {
        "d": d,
        "layers": config["num_hidden_layers"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or d // heads,
        "ffn": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "qkv_bias": bool(config.get("qkv_bias", False)),
        "tied": bool(config["tie_word_embeddings"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "window": window,
        "n_mux": int(mux.get("n", 1)),
        "demux_hidden": int(mux.get("demux_hidden") or 2 * d),
    }
