"""Production meshes.

``make_production_mesh`` is the deliverable-prescribed mesh: one pod is a
16x16 grid (data, model); the multi-pod deployment stacks pods on a leading
axis. ``make_shift_mesh`` re-factorizes the *same devices* into
(data, sp, tp) for Shift Parallelism: the model axis splits into sp*tp = 16
with tp innermost (fastest-varying), so the model group stays within the
16-device ICI ring and physical placement is identical to the production
mesh. Data/pod axes scale the deployment out: nothing in the model group
ever spans the (slower) pod interconnect, which is what makes the design
valid at 1000+ nodes."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

AXIS_POD, AXIS_DATA, AXIS_SP, AXIS_TP = "pod", "data", "sp", "tp"


def _make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_shift_mesh(sp: int = 8, tp: int = 2, *, multi_pod: bool = False):
    """Same 256/512 devices as the production mesh, model axis factorized
    into (sp, tp). sp*tp must equal the model-axis extent (16)."""
    assert sp * tp == 16, (sp, tp)
    shape = (2, 16, sp, tp) if multi_pod else (16, sp, tp)
    axes = (("pod", "data", "sp", "tp") if multi_pod
            else ("data", "sp", "tp"))
    return _make_mesh(shape, axes)


def make_test_mesh(data=1, sp=2, tp=2):
    """Small mesh for CPU multi-device tests (8 virtual devices)."""
    return _make_mesh((data, sp, tp), ("data", "sp", "tp"))


def layout_axes(multi_pod: bool = False):
    """(dp_axes, sp_axes, tp_axes) for the shift mesh."""
    dp = ("pod", "data") if multi_pod else ("data",)
    return dp, ("sp",), ("tp",)
