"""Explicit shard_map data-parallel step with int8 error-feedback
gradient compression on the DP all-reduce.

The pjit/GSPMD path reduces gradients implicitly (fp32 on the wire); this
variant makes the reduction explicit so the payload can be quantized —
a 4x cut of the DP collective bytes, which §Roofline shows is the
dominant term for small models on big meshes.  Error feedback keeps the
quantization *unbiased over time*; convergence equivalence is tested in
test_runtime.py.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.compression import compress_tree_psum, init_error_state


def make_compressed_dp_step(loss_fn: Callable, optimizer, *, mesh: Mesh,
                            axis_name: str = "data",
                            compress: bool = True):
    """loss_fn(params, batch, rng) -> (loss, metrics).

    Returns step(state, batch, rng) with
    state = {params, opt, err}; batch sharded on `axis_name`; params and
    optimizer state replicated (each replica applies the same update —
    ZeRO-0; combine with param sharding for bigger models).
    """

    def local_step(state, batch, rng):
        params, opt_state, err = state["params"], state["opt"], state["err"]
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, rng)
        if compress:
            grads, err = compress_tree_psum(grads, err, axis_name)
        else:
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, axis_name), grads)
        updates, opt_state, om = optimizer.update(grads, opt_state, params)
        params = optimizer.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, axis_name)
        return ({"params": params, "opt": opt_state, "err": err},
                {**metrics, **om, "loss": loss})

    rep = P()
    f = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, P(axis_name), rep),
        out_specs=(rep, rep),
        check_vma=False)
    return jax.jit(f)


def init_dp_state(params, optimizer):
    return {"params": params, "opt": optimizer.init(params),
            "err": init_error_state(params)}
