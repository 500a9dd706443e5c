"""Production meshes (TPU v5e pods): 16x16 = 256 chips/pod, 2 pods = 512.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: sharding propagates as in GSPMD jit (jax.make_mesh
    # defaults to Explicit axes, which every consumer here would have to
    # annotate)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever this host actually has (tests / examples / smoke runs)."""
    n = len(jax.devices())
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def make_forced_host_mesh(shape, axes=("data", "model")):
    """Mesh over the first prod(shape) host devices — may use a subset.

    For SPMD tests and scale-out sweeps on the CPU container under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``: unlike
    ``jax.make_mesh`` this does not insist on covering every device, so
    one 8-device process can sweep 1/2/4/8-way meshes.
    """
    import numpy as np
    from jax.sharding import Mesh
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"host has {len(devices)}")
    return Mesh(np.array(devices[:n]).reshape(shape), axes)


# TPU v5e hardware constants (per chip) — roofline denominators.
PEAK_BF16_FLOPS = 197e12          # 197 TFLOP/s
HBM_BW = 819e9                    # 819 GB/s
ICI_BW = 50e9                     # ~50 GB/s per link
