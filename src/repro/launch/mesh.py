"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.

Target hardware (roofline constants live in benchmarks/roofline.py):
  TPU v5e pod: 16x16 = 256 chips, (data=16, model=16)
  2 pods     : (pod=2, data=16, model=16) = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """Mesh over the visible devices with every axis in Auto mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, data_axis=None):
    """(data=16, model=16) per pod; 512 chips with multi_pod.

    ``data_axis`` reshapes the LOGICAL (data, model) factorization of the
    same 256 chips/pod (perf-iteration knob; the default is the baseline).
    """
    chips = 256
    data = data_axis or 16
    assert chips % data == 0, data
    model = chips // data
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1, data_axis: int = 1,
                   multi_pod: bool = False):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data_axis = min(data_axis, n // model_axis) or 1
    if multi_pod:
        return auto_mesh((1, data_axis, model_axis), ("pod", "data", "model"))
    return auto_mesh((data_axis, model_axis), ("data", "model"))


def client_axes(mesh) -> tuple:
    """Mesh axes along which FL clients are laid out."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_clients(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n
