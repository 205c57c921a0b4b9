"""Mesh construction: production shapes, host meshes, and device-subset
(mesh-slice) meshes for the concurrent cluster executor.

All constructors validate the requested shape against the devices actually
present and fail with an actionable message (available vs requested, plus the
``XLA_FLAGS`` incantation to force host devices) instead of surfacing a raw
XLA assertion.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def _require_devices(n_req: int, shape, axes) -> None:
    avail = jax.device_count()
    if avail < n_req:
        raise RuntimeError(
            f"mesh {dict(zip(axes, shape))} needs {n_req} devices but this "
            f"host has only {avail}. On CPU, force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_req} "
            f"(set before the first jax import), or request a smaller mesh."
        )


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules here are
    constraints the partitioner propagates, not explicit-sharding types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _require_devices(int(np.prod(shape)), shape, axes)
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly forced) host devices exist —
    used by tests that exercise sharding logic without 512 fake devices."""
    _require_devices(data * model, (data, model), ("data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def slice_mesh(src, g: Optional[int] = None, *, data: int = 1,
               model: Optional[int] = None):
    """Mesh over an explicit *subset* of devices — a cluster mesh slice.

    ``src`` may be a ``repro.cluster.DevicePool`` / ``MeshSlice`` (anything
    with a ``.devices`` attribute) or a plain device sequence; ``g`` takes
    the first ``g`` of them (default: all). The slice mesh has shape
    ``(data, model)`` with ``data * model == g`` (default ``(1, g)``:
    tensor-parallel within the slice, matching the cost model's TP
    assumption). Unlike ``jax.make_mesh`` this never touches devices outside
    the subset, so disjoint slices can host concurrently running jobs.
    """
    devices = list(getattr(src, "devices", src))
    if g is None:
        g = len(devices)
    if g > len(devices):
        raise RuntimeError(
            f"slice of width {g} requested but the source holds only "
            f"{len(devices)} devices"
        )
    devices = devices[:g]
    if model is None:
        if g % data:
            raise ValueError(f"slice width {g} not divisible by data={data}")
        model = g // data
    if data * model != g:
        raise ValueError(
            f"slice mesh ({data}, {model}) does not cover width {g}"
        )
    return jax.sharding.Mesh(
        np.asarray(devices, dtype=object).reshape(data, model),
        ("data", "model"),
    )


def mesh_axes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
