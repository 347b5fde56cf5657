"""Production mesh factory (spec: MULTI-POD DRY-RUN step 1).

Functions, not module-level constants, so importing this module never
touches jax device state.
"""

from __future__ import annotations

from typing import Mapping

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_serving_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagates the
    shardings the planner pins)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serving_mesh(mesh_axes: Mapping[str, int]):
    """Build the ``(data, model)`` mesh :func:`repro.serving.plan_serving`
    suggests — the simulator picks the split, this materializes it, which
    closes the paper's §V-B loop for serving:

        mesh_axes, report = plan_serving("yi-6b", hardware="tpu_v5e_2x2")
        mesh = make_serving_mesh(mesh_axes)      # {"data": dp, "model": tp}
        step = make_serve_step(arch, cfg, mesh)

    The runtime must expose ``data * model`` devices (a pod slice, or
    ``--xla_force_host_platform_device_count`` for CPU dry-runs).
    """
    shape = (int(mesh_axes["data"]), int(mesh_axes["model"]))
    return make_mesh(shape, ("data", "model"))
