"""Production mesh construction (multi-pod dry-run spec).

A function, not a module-level constant — importing this module never
touches jax device state. Single pod: 16x16 = 256 chips ('data' x 'model');
multi-pod: 2x16x16 = 512 chips ('pod' x 'data' x 'model'), the 'pod' axis
carrying only data parallelism + gradient reduction (DCN-friendly).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (tests/examples)."""
    n = len(jax.devices())
    return jax.make_mesh(
        (n, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_data_mesh(n: int = 0):
    """1-D ``('data',)`` mesh over ``n`` local devices (0 = all).

    The serving-side mesh: `serve.runners.snn.SNNRunner` splits its slot
    batch over this axis when it is installed as the ambient compute mesh
    (``dist.context.compute_mesh``). On CPU, force the device count with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    n = n or len(jax.devices())
    return jax.make_mesh(
        (n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
