"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — jax locks the device count on
first backend init, and only dryrun.py is allowed to request the 512
placeholder host devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
    an outer data-parallel dimension crossing the DCN/ICI boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: the model code places
    activations with ``with_sharding_constraint`` and leaves the rest to
    GSPMD, which Explicit axes (``make_mesh``'s default) refuse."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_serve_mesh(data: int = 1, model: int = 1):
    """Serve mesh (DESIGN.md §sharded serving): backbone rows, their KV
    block tables and the paged pool's pages partition over 'data' (one
    ``ShardedKVPool`` segment per data shard); attention heads / MLP
    width partition over 'model' via the repo's sharding rules.  Uses
    the first data*model local devices, so it works on any subset of an
    8-host-device CPU run (``XLA_FLAGS=--xla_force_host_platform_
    device_count=8``) as well as on a real slice."""
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    need = data * model
    if need > len(devs):
        raise ValueError(
            f"serve mesh ({data}, {model}) needs {need} devices, have "
            f"{len(devs)} (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")
    return Mesh(np.asarray(devs[:need]).reshape(data, model),
                ("data", "model"))


HW = {
    # TPU v5e per-chip constants used by §Roofline
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link (~per-device effective)
    "hbm_bytes": 16e9,
}
