"""GPipe-style pipeline parallelism as an explicit ``shard_map``.

Stage weights are stacked on a leading axis sharded over the 'pipe' mesh
axis; activations flow stage-to-stage via ``lax.ppermute`` while a
``lax.scan`` ticks the fill-drain schedule (bubble = (S-1)/(M+S-1)).
Microbatch m enters stage 0 at tick m; stage s processes microbatch
m = t - s at tick t; the last stage's outputs are collected and made
replicated with a masked psum.

The compute of tick t overlaps with the collective_permute of tick t-1's
activations (XLA's async scheduler) — the standard PP compute/comm
overlap.  Used when layers don't fit the TP×DP mesh; demonstrated on a
fake 8-device mesh in tests (the production dry-run mandates the 2D/3D
mesh, where GSPMD handles distribution).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stacked_params, x, *, mesh: Mesh,
                   axis_name: str = "pipe"):
    """stage_fn(stage_params, x_mb) -> y_mb (same shape class as x_mb).

    stacked_params: pytree, every leaf (n_stages, ...), sharded on 'pipe'.
    x: (n_micro, mb, ...) microbatched input (replicated).
    Returns (n_micro, mb, ...) = stage_{S-1}(...stage_0(x)).
    """
    n_stages = mesh.shape[axis_name]

    def body(params, xs):
        params = jax.tree.map(lambda p: p[0], params)     # this stage's slice
        stage = jax.lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        state = jnp.zeros_like(xs[0])
        collected = jnp.zeros_like(xs)

        def tick(carry, t):
            state_in, outs = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(stage == 0, xs[mb_idx], state_in)
            out = stage_fn(params, inp)
            nxt = jax.lax.ppermute(
                out, axis_name,
                [(i, i + 1) for i in range(n_stages - 1)])
            done = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (done >= 0)
            idx = jnp.clip(done, 0, n_micro - 1)
            outs = jnp.where(write, outs.at[idx].set(out), outs)
            return (nxt, outs), None

        (_, collected), _ = jax.lax.scan(
            tick, (state, collected),
            jnp.arange(n_micro + n_stages - 1))
        # only the last stage holds real outputs; make them replicated
        return jax.lax.psum(
            jnp.where(stage == n_stages - 1, collected, 0.0), axis_name)

    pspec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    f = jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                      out_specs=P(), check_vma=False)
    return f(stacked_params, x)


def stack_stages(per_stage_params: list):
    """[stage0_params, stage1_params, ...] -> stacked pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
