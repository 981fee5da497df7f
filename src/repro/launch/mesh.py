"""Production mesh construction (multi-pod dry-run deliverable).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state."""
from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)
