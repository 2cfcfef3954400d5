"""Plain float32 reference of the served decoder, in ``jax.numpy``.

One backbone row carries N streams: a Gaussian mux sums the N token
embeddings, each times its fixed key v_i, over N (MUX-PLMs, Eq. 1-2);
the backbone is a pre-norm decoder (RMSNorm, grouped-query attention
with optional q/k/v bias and an optional sliding window, half-rotation
RoPE, SwiGLU); after the final norm an RSA demux recovers each stream,
h_i = LN(W2 gelu(W1h h + W1k k_i + b1) + b2) (Eq. 6), and the logits
come from the tied embedding or the LM head.

It imports nothing of the serving program: it reads the weight tree of
``bench/weights.py`` and computes in float32 under "highest" matmul
precision, one layer at a time (``lax.scan``) and attention in blocks of
queries, so that a whole row of several thousand positions fits beside
the weights.

Departures from the published models, each kept because the served
model has it too: RMSNorm multiplies by ``1 + scale`` (the weights store
scale - 1); the demux MLP uses the tanh form of GELU; the demux
LayerNorm's epsilon is 1e-6.  The sliding window keeps keys with
q - k < window, as Hugging Face transformers does for Mistral.

``quant="fp8"`` rounds every matmul operand to float8 e4m3 (weights per
output channel, activations per token, K and V per token and head), the
lower-precision control that the output check has to fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                     # largest finite float8_e4m3fn
Q_BLOCK = 256                      # attention query block


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., K) @ w (K, ...) in float32 at highest precision."""
    w = w.astype(jnp.float32).reshape(w.shape[0], -1)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _layer_norm(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (T, H, hd); half-rotation convention."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """Causal grouped-query attention. q (T, H, hd); k, v (T, Hkv, hd)."""
    t, h, hd = q.shape
    hk = k.shape[1]
    qb = min(Q_BLOCK, t)
    kpos = jnp.arange(t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)     # (qb, H, hd)
        qi = qi.reshape(qb, hk, h // hk, hd) * hd ** -0.5
        s = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HIGHEST)
        qpos = i * qb + jnp.arange(qb)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
        return o.reshape(qb, h, hd)

    out = jax.lax.map(block, jnp.arange(t // qb))
    return out.reshape(t, h, hd)


def _decoder_layer(x, lw, s, pos, quant):
    t = x.shape[0]
    h, hk, hd = s["heads"], s["kv_heads"], s["head_dim"]
    a = _rms(x, lw["ln1"]["scale"], s["norm_eps"])

    def proj(p, heads):
        y = _mm(a, p["w"], quant).reshape(t, heads, hd)
        if "b" in p:
            y = y + p["b"].astype(jnp.float32)
        return y

    q = _rope(proj(lw["wq"], h), pos, s["rope_theta"])
    k = _rope(proj(lw["wk"], hk), pos, s["rope_theta"])
    v = proj(lw["wv"], hk)
    if quant == "fp8":
        k, v = _q8(k, -1), _q8(v, -1)
    o = _attention(q, k, v, s["window"])
    x = x + _mm(o.reshape(t, h * hd), lw["wo"]["w"], quant)
    b = _rms(x, lw["ln2"]["scale"], s["norm_eps"])
    ffn = lw["ffn"]
    u = jax.nn.silu(_mm(b, ffn["gate"]["w"], quant)) * _mm(b, ffn["up"]["w"],
                                                         quant)
    return x + _mm(u, ffn["down"]["w"], quant)


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _hidden(w, tokens, spec_items, quant):
    s = dict(spec_items)
    n, t = tokens.shape
    table = w["embed"]["table"]
    e = table[tokens].astype(jnp.float32)                      # (N, T, D)
    if s["n_mux"] > 1:
        vkeys = w["mux_engine"]["mux"]["v"].astype(jnp.float32)
        x = jnp.einsum("ntd,nd->td", e, vkeys, precision=HIGHEST) / n
    else:
        x = e[0]
    pos = jnp.arange(t)

    def body(x, lw):
        return _decoder_layer(x, lw, s, pos, quant), None

    x, _ = jax.lax.scan(body, x, w["periods"][0])
    x = _rms(x, w["final_norm"]["scale"], s["norm_eps"])
    if s["n_mux"] == 1:
        return x[None]
    dm = w["mux_engine"]["demux"]
    shared = _mm(x, dm["w1h"]["w"], quant) + dm["w1h"]["b"].astype(jnp.float32)
    kb = _mm(dm["k"].astype(jnp.float32), dm["w1k"]["w"], quant)  # (N, Dh)
    z = jax.nn.gelu(shared[None] + kb[:, None, :], approximate=True)
    out = _mm(z, dm["w2"]["w"], quant) + dm["w2"]["b"].astype(jnp.float32)
    return _layer_norm(out, dm["ln"]["scale"], dm["ln"]["bias"])


def demuxed_hidden(w, spec: dict, tokens, quant: str | None = None):
    """(N, T) token ids of one row -> (N, T, D) float32 stream states."""
    return _hidden(w, jnp.asarray(tokens, jnp.int32),
                   tuple(sorted(spec.items())), quant)


@functools.partial(jax.jit, static_argnames=("tied", "quant"))
def _stats(w, hidden, stream, pos, target, tied, quant):
    head = (w["embed"]["table"].T if tied else w["lm_head"]["w"])
    y = hidden[stream, pos]                                    # (M, D)
    logits = _mm(y, head, quant)
    at = jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]
    return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def position_stats(w, spec: dict, hidden, stream, pos, target,
                   quant: str | None = None, block: int = 128):
    """For each (stream, position): the best logit, the logit of
    ``target`` and the argmax token, in blocks of ``block`` positions.
    Inputs are numpy arrays of one length M; returns three numpy arrays."""
    import numpy as np
    m = len(stream)
    pad = -m % block
    cols = [np.pad(np.asarray(a, np.int32), (0, pad))
            for a in (stream, pos, target)]
    out = [[], [], []]
    for i in range(0, m + pad, block):
        r = _stats(w, hidden, *(jnp.asarray(c[i:i + block]) for c in cols),
                   tied=spec["tied"], quant=quant)
        for acc, x in zip(out, r):
            acc.append(np.asarray(x))
    return tuple(np.concatenate(a)[:m] for a in out)
