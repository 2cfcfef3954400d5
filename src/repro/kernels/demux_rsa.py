"""Pallas TPU kernel: fused RSA demultiplexer MLP.

    out[n] = gelu(h @ W1h + k[n] @ W1k + b1) @ W2 + b2      (Eq. 6, split)

The naive path materializes the (N, T, F) GELU intermediate in HBM
(F = 2D typically) — at N=10 that is the demux's dominant memory traffic.
This kernel keeps the (bt, bf) intermediate in VMEM and accumulates the
second matmul over F tiles, so HBM sees only h (once per N — streamed),
the weights, and the (N, T, D) output.  The per-instance term k[n] @ W1k
is a (N, F) matrix precomputed outside (negligible).

Grid: (N, T/bt, F/bf); F is the innermost (sequential on TPU) axis so the
output tile accumulates in place across F steps.  MXU-aligned tiles
(bt, bf multiples of 128).  The default tiles keep the double-buffered
h, W1h, W2 and output tiles of an fp32 D=1536 model (qwen2-1.5b) inside
the 16 MiB of VMEM a v5e kernel may use by default; the per-instance
bias carries a unit axis so its (1, bf) block is a whole minor tile.

Epilogue fusion (the serve decode exit path): ``entry_kind`` absorbs the
backbone's final norm (RMS or LN) into the kernel's read of h, and
``exit_ln`` applies the demux's own LayerNorm to the accumulated output
tile at the last F step — so final_norm -> demux-MLP -> LN is ONE kernel
launch and the un-normed backbone hidden state is the only input crossing
HBM.  Both norms are row-wise over the full D axis, which each grid tile
holds in VMEM ((bt, D) in, (bt, D) out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _entry_norm(h, kind, scale_ref, bias_ref):
    """Backbone final norm on an fp32 (bt, D) tile — same math as
    nn.layers.RMSNorm/LayerNorm at fp32 (eps 1e-6)."""
    if kind is None:
        return h
    if kind == "rms":
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        return h * jax.lax.rsqrt(var + 1e-6) \
            * (1.0 + scale_ref[0].astype(jnp.float32))
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    y = (h - mu) * jax.lax.rsqrt(var + 1e-6)
    return y * scale_ref[0].astype(jnp.float32) \
        + bias_ref[0].astype(jnp.float32)


def _kernel_full(h_ref, w1h_ref, kb_ref, w2_ref, b2_ref, *rest,
                 f_last: int, entry_kind, exit_ln: bool):
    # h_ref: (bt, D); w1h_ref: (D, bf); kb_ref: (1, 1, bf) [b1 folded in];
    # w2_ref: (bf, D); b2_ref: (1, D); o_ref: (1, bt, D) accumulated
    # across the (sequential, innermost) F grid axis.  Optional norm
    # params ride between b2 and the output ref.
    it = iter(rest)
    en_s = next(it) if entry_kind is not None else None
    en_b = next(it) if entry_kind == "ln" else None
    ex_s = next(it) if exit_ln else None
    ex_b = next(it) if exit_ln else None
    o_ref = next(it)
    f = pl.program_id(2)
    h = _entry_norm(h_ref[...].astype(jnp.float32), entry_kind, en_s, en_b)
    z = jnp.dot(h, w1h_ref[...].astype(jnp.float32))
    z = jax.nn.gelu(z + kb_ref[0].astype(jnp.float32))
    part = jnp.dot(z, w2_ref[...].astype(jnp.float32))

    @pl.when(f == 0)
    def _init():
        o_ref[0] = (part + b2_ref[0].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(f > 0)
    def _acc():
        o_ref[0] = (o_ref[0].astype(jnp.float32) + part).astype(o_ref.dtype)

    if exit_ln:
        @pl.when(f == f_last)
        def _exit():
            y = o_ref[0].astype(jnp.float32)
            mu = jnp.mean(y, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
            y = (y - mu) * jax.lax.rsqrt(var + 1e-6)
            y = y * ex_s[0].astype(jnp.float32) \
                + ex_b[0].astype(jnp.float32)
            o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("entry_kind", "block_t",
                                             "block_f", "interpret"))
def demux_rsa(h, k, w1h, w1k, b1, w2, b2, *, entry_kind=None,
              entry_scale=None, entry_bias=None, exit_scale=None,
              exit_bias=None, block_t: int = 128, block_f: int = 256,
              interpret: bool = False):
    """h: (T, D); k: (N, D); w1h: (D, F); w1k: (D, F); b1: (F,);
    w2: (F, D); b2: (D,) -> (N, T, D).

    entry_kind='rms'/'ln' + entry_scale/entry_bias: apply the backbone's
    final norm to h inside the kernel.  exit_scale/exit_bias: apply the
    demux LayerNorm to the output tile at the last F step (fused decode
    exit — see module docstring).
    """
    t, d = h.shape
    n = k.shape[0]
    f = w1h.shape[1]
    bt = min(block_t, t)
    bf = min(block_f, f)
    exit_ln = exit_scale is not None
    kb = (k @ w1k + b1[None]).astype(h.dtype)            # (N, F) tiny
    # zero-pad the F axis so partial tiles contribute exactly zero
    # (padded W2 rows are zero; padded kb/W1h columns only feed those rows)
    f_p = pl.cdiv(f, bf) * bf
    if f_p != f:
        w1h = jnp.pad(w1h, ((0, 0), (0, f_p - f)))
        w2 = jnp.pad(w2, ((0, f_p - f), (0, 0)))
        kb = jnp.pad(kb, ((0, 0), (0, f_p - f)))
    nf = pl.cdiv(f_p, bf)
    grid = (n, pl.cdiv(t, bt), nf)
    in_specs = [
        pl.BlockSpec((bt, d), lambda i, j, l: (j, 0)),     # h rows
        pl.BlockSpec((d, bf), lambda i, j, l: (0, l)),     # W1h F-tile
        pl.BlockSpec((1, 1, bf), lambda i, j, l: (i, 0, l)),  # k@W1k+b1
        pl.BlockSpec((bf, d), lambda i, j, l: (l, 0)),     # W2 F-tile
        pl.BlockSpec((1, d), lambda i, j, l: (0, 0)),      # b2
    ]
    args = [h, w1h, kb[:, None], w2, b2[None]]
    row_spec = pl.BlockSpec((1, d), lambda i, j, l: (0, 0))
    if entry_kind is not None:
        in_specs.append(row_spec)
        args.append(entry_scale[None])
    if entry_kind == "ln":
        in_specs.append(row_spec)
        args.append(entry_bias[None])
    if exit_ln:
        in_specs += [row_spec, row_spec]
        args += [exit_scale[None], exit_bias[None]]
    return pl.pallas_call(
        functools.partial(_kernel_full, f_last=nf - 1,
                          entry_kind=entry_kind, exit_ln=exit_ln),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bt, d), lambda i, j, l: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, t, d), h.dtype),
        interpret=interpret,
    )(*args)
