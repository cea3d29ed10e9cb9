"""Production mesh construction (spec'd API — a FUNCTION, so importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A device mesh whose axes GSPMD partitions automatically."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods -> 512 chips.

    Axes: data (DP), model (TP/EP/SP); the pod axis is pure DP across pods
    (gradient all-reduce crosses the inter-pod links only once per step).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh made by make_production_mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for in-process multi-device tests (host platform devices)."""
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(dp: int, tp: int):
    """(dp, tp) -> Mesh("data", "model") for the serving engine; validates
    the device count up front so --dp/--tp failures are actionable."""
    need = dp * tp
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"mesh dp={dp} x tp={tp} needs {need} devices but only {have} "
            "are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return make_mesh((dp, tp), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """Device-free mesh stand-in (``.shape``/``.axis_names`` only) for
    spec-level planning, e.g. per-device serving budgets on a login host."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))
