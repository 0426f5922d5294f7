"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Every mesh here has Auto axes: the sharding
rules (``repro.sharding``) pin layouts with ``with_sharding_constraint``,
which accepts only Auto axes, while ``jax.make_mesh`` defaults to
Explicit ones.

The production target is a TPU v5e pod of 16x16 = 256 chips; the
multi-pod mesh stacks 2 pods on a leading 'pod' axis (512 chips)
connected by slower inter-pod links.  ``make_host_mesh`` lays the same
('data', 'model') axes over whatever devices this process sees, such as
the four chips of one v5e host.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType

from repro.config import MeshConfig


def _auto_mesh(shape, axis_names, devices=None):
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(devices=None, model: int = 1):
    """('data', 'model') mesh over ``devices`` (default: all devices of
    the process), ``model`` of them along the model axis."""
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) % model:
        raise ValueError(f"{len(devices)} devices do not split into "
                         f"model={model}")
    return _auto_mesh((len(devices) // model, model), ("data", "model"),
                      devices=devices)


def make_mesh(cfg: MeshConfig):
    return _auto_mesh(cfg.shape, cfg.axis_names)


def make_test_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pods:
        return _auto_mesh((pods, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_abstract_mesh(shape, axis_names):
    """``AbstractMesh`` with Auto axes, for sharding-rule logic that needs
    only ``.shape``/``.axis_names``."""
    return AbstractMesh(tuple(shape), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))
