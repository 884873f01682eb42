"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(*, data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh over forced host devices for integration tests."""
    if pods:
        return _mk((pods, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def make_fleet_mesh(n_shards: int):
    """1-D mesh over the ``fl`` axis for the sharded fleet engine: device
    rows (theta, ELL neighbor lists, trigger state) partition across it,
    one shard per mesh device (DESIGN.md "Sharded fleet engine").  On CPU
    CI the devices are forced host devices
    (XLA_FLAGS=--xla_force_host_platform_device_count=N, set before any jax
    import); on TPU the same mesh spans real chips."""
    n = jax.device_count()
    if n_shards > n:
        raise ValueError(
            f"fleet mesh needs {n_shards} devices but jax sees {n}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before importing jax (CPU), or run on a platform "
            "with enough devices")
    return _mk((n_shards,), ("fl",))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (ici_bw: per link, one
# of four).
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def device_peaks(device_kind: str) -> dict[str, float]:
    """The peaks of ``device_kind``; a device that is not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
