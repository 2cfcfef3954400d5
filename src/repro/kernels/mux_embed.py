"""Pallas TPU kernel: fused embed + Gaussian mux-combine entry.

    out[t] = (scale / N) * sum_i  emb[tokens[i, t]] ⊙ v[i]

The unfused decode prologue is three HBM-traffic ops — an (N*T, D)
embedding gather, the embedding-scale multiply, and the mux-combine
Hadamard/mean (``kernels/mux_combine.py``) — each materializing an
(N, T, D) intermediate.  This kernel is the whole prologue in ONE launch:
the token ids are scalar-prefetched, so the embedding-row DMA for grid
step (t, j, i) is issued directly against row ``tokens[i, t]`` (the same
prefetched-index-map trick as the paged-attention kernels) and the N-term
sum accumulates in VMEM; nothing instance-shaped ever reaches HBM.

Grid: (T, D/bd, i) with the instance axis innermost (sequential on TPU)
so the accumulator carries across instances of one (t, d-tile).
``scale`` folds the backbone's static embedding scale (sqrt(D)) into the
epilogue for free.

Mosaic tiles the last two dims of every block by (8, 128) unless a dim
is whole, so a single-row block is not expressible: each step DMAs the
8-row group holding the token's row (``ROWS``) and picks the row in
VMEM, the keys come in whole and are picked the same way, and the output
carries a unit axis so its (1, bd) block is a whole minor tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = 8      # embedding rows per DMA: one sublane tile


def _kernel(tok_ref, e_ref, v_ref, o_ref, acc_ref, *, n: int, scale: float):
    ti = pl.program_id(0)
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = e_ref[pl.ds(tok_ref[ni, ti] % ROWS, 1), :]       # (1, bd)
    acc_ref[...] += (e.astype(jnp.float32)
                     * v_ref[pl.ds(ni, 1), :].astype(jnp.float32))

    @pl.when(ni == n - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] * (scale / n)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_d", "out_dtype",
                                             "interpret"))
def mux_embed_combine(tokens, emb, v, *, scale: float = 1.0,
                      block_d: int = 512, out_dtype=jnp.float32,
                      interpret: bool = False):
    """tokens: (N, T) int32; emb: (V, D) raw embedding table; v: (N, D)
    mux keys -> (T, D) = (scale/N) * sum_i emb[tokens[i]] * v[i].
    Token ids must be in-range (the serve path clamps inactive rows'
    ids to 0 before calling)."""
    n, t = tokens.shape
    d = emb.shape[1]
    bd = min(block_d, d)
    tokens = jnp.asarray(tokens, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # tokens
        grid=(t, pl.cdiv(d, bd), n),
        in_specs=[
            pl.BlockSpec((ROWS, bd),
                         lambda t_, j, i, tok: (tok[i, t_] // ROWS, j)),
            pl.BlockSpec((n, bd), lambda t_, j, i, tok: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bd), lambda t_, j, i, tok: (t_, 0, j)),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, d), out_dtype),
        interpret=interpret,
    )(tokens, emb, v)
    return out.reshape(t, d)
