"""Production mesh construction.

Importing this module never touches jax device state; all meshes are built
inside functions (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: one pod = (16, 16) chips over
    (data, model); two pods = (2, 16, 16) over (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def device_slices(n_slices: int, devices=None) -> list[list]:
    """Split the device list into ``n_slices`` contiguous near-equal
    slices (sizes differ by at most one) — the replica pool's stage-shard
    mode gives each pipeline replica one slice and stage-pipelines across
    it. With more slices than devices, slices wrap round-robin so every
    replica still owns a device (they then share, which is exactly the
    forced-host-device CPU case)."""
    if n_slices < 1:
        raise ValueError(f"n_slices={n_slices} < 1")
    devs = list(jax.devices() if devices is None else devices)
    if not devs:
        raise ValueError("no devices to slice")
    if n_slices >= len(devs):
        return [[devs[i % len(devs)]] for i in range(n_slices)]
    base, extra = divmod(len(devs), n_slices)
    out, i = [], 0
    for s in range(n_slices):
        k = base + (1 if s < extra else 0)
        out.append(devs[i:i + k])
        i += k
    return out


def make_debug_mesh(n_data: int = 2, n_model: int = 2, n_pod: int = 1):
    """Small host-device mesh for tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=n_data*n_model*n_pod)."""
    if n_pod > 1:
        return _auto_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode: the sharding rules
    (``repro.runtime.sharding``) leave propagation to the compiler."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
