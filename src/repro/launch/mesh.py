"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's forced 512-device
host platform to initialize first.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """`jax.make_mesh` with Auto axis types: the sharding rules here are
    GSPMD hints (with_sharding_constraint), which explicit axes reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: "data" = FSDP + DP within a pod; "model" = tensor/expert parallel;
    "pod" = pure DP across pods (slow inter-pod links: ZeRO-1 + optional int8
    compressed gradient all-reduce live on this axis).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int | None = None) -> Mesh:
    """Tiny mesh over whatever devices exist (tests / CPU)."""
    n = n_devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))


def elastic_mesh_shape(n_devices: int, prefer_model: int = 16) -> tuple:
    """Elastic re-mesh planning: pick (data, model) for a changed device count
    (node failure / scale-up). Keeps the model axis as close to `prefer_model`
    as divisibility allows, shrinking data-parallel width first — params stay
    shardable, only the batch layout changes."""
    for model in range(min(prefer_model, n_devices), 0, -1):
        if n_devices % model == 0:
            return (n_devices // model, model)
    return (n_devices, 1)
