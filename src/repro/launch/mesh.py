"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (TPU v5e pod),
axes (data, model).  Multi-pod: 2 pods x 256 = 512 chips, axes
(pod, data, model); the `pod` axis is the rotor-scheduled inter-pod
dimension (DESIGN.md §3.1).

`auto_mesh` is the repo's one mesh constructor: every axis is Auto, so
GSPMD propagates shardings and `shard_map` regions bind axes explicitly
(``jax.make_mesh`` defaults to Explicit axes).
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many (fake or real) local devices exist —
    used by tests and the CPU examples, never by the dry-run."""
    n = len(jax.devices())
    data = n // model
    return auto_mesh((data, model), ("data", "model"))


def pctx_for_mesh(mesh, **kw):
    from repro.models.parallel import ParallelContext

    axes = mesh.axis_names
    dp = ("pod", "data") if "pod" in axes else ("data",)
    if kw.get("layout") == "dp_only":
        dp = dp + ("model",)
    if "pod" in axes:
        return ParallelContext(
            mesh=mesh, dp_axes=dp, tp_axis="model", pod_axis="pod", **kw
        )
    return ParallelContext(mesh=mesh, dp_axes=dp, tp_axis="model", **kw)
