"""Production meshes.

Single pod: 16x16 = 256 chips (TPU v5e pod slice), axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) — 'pod' is the
cross-pod (DCN) data-parallel axis; FSDP stays within a pod on 'data'.

Defined as functions so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings are propagated
    by the compiler, as the sharding rules in ``launch.sharding`` expect
    (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Degenerate mesh over the locally-available devices (CPU smoke tests)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_auto_mesh((data, model_axis), ("data", "model"))


def make_embed_mesh(num_shards: int = 0):
    """1-D ``('shard',)`` mesh for the sharded embedding store
    (``runtime.sharded_engine``). Takes the first ``num_shards`` local
    devices (0 = all); on CPU, ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` makes N host devices available before jax initializes.

    Built from an explicit device array (not ``jax.make_mesh``) so callers
    can span a strict prefix of the devices — a ClusterSim host that *is* a
    mesh slice uses fewer shards than the process exposes.
    """
    import numpy as np

    devs = jax.devices()
    n = num_shards or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} shards, only {len(devs)} devices")
    return jax.sharding.Mesh(np.array(devs[:n]), ("shard",))


# Hardware constants for the roofline (TPU v5e-class chip).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per chip usable)
DCN_BW = 25e9                 # bytes/s per chip across pods (scaled)
HBM_PER_CHIP = 16 * 1024**3   # 16 GiB
