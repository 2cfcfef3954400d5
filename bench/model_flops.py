"""Model FLOPs of the serve path's step programs, from shapes.

Counted is the work the model needs, two operations per multiply-add:
the backbone's projections and FFN for every backbone position, the
attention each query needs (its causal, windowed keys), the Gaussian mux
(N products and a sum per position), and where a token is produced the
RSA demux (the shared projection once per position, the output
projection once per stream) and the LM head once per stream.  A decode
step produces a token at every live row; a prefill chunk only at its
last position.  Work on inactive rows or bucket padding is not counted.
"""
from __future__ import annotations


def _backbone(s: dict) -> int:
    d, hd, f = s["d"], s["head_dim"], s["ffn"]
    qkv = d * (s["heads"] + 2 * s["kv_heads"]) * hd
    return 2 * s["layers"] * (qkv + s["heads"] * hd * d + 3 * d * f)


def _attn(s: dict, keys: int) -> int:
    return 4 * s["layers"] * s["heads"] * s["head_dim"] * keys


def _token_out(s: dict) -> int:
    """Demux and head at one position, for all N streams."""
    n, d = s["n_mux"], s["d"]
    demux = 2 * d * s["demux_hidden"] * (1 + n) if n > 1 else 0
    return demux + n * 2 * d * s["vocab"]


def _mux(s: dict) -> int:
    return 2 * s["n_mux"] * s["d"] if s["n_mux"] > 1 else 0


def decode_flops(s: dict, ctx_lens) -> int:
    """One decode step over live rows whose KV lengths (the new token
    included) are ``ctx_lens``."""
    w = s["window"]
    per_row = _backbone(s) + _mux(s) + _token_out(s)
    return sum(per_row + _attn(s, c if w is None else min(c, w))
               for c in ctx_lens)


def prefill_flops(s: dict, start: int, length: int) -> int:
    """One prefill chunk of one row: ``length`` positions from ``start``."""
    w = s["window"]
    keys = sum(q + 1 if w is None else min(q + 1, w)
               for q in range(start, start + length))
    return (length * (_backbone(s) + _mux(s)) + _attn(s, keys)
            + _token_out(s))
