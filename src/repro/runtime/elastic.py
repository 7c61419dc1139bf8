"""Elastic scaling: recompute the mesh for a changed device count and
re-place a checkpointed state onto it.

On a real fleet this runs in the coordinator after a slice change; here the
planner + resharding restore are exercised by tests with a forced multi-device
host platform.
"""
from __future__ import annotations

from typing import Any

from jax.sharding import Mesh

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig
from repro.launch.mesh import elastic_mesh_shape, make_mesh
from repro.sharding import state_specs, to_named


def plan_mesh(n_devices: int, prefer_model: int = 16) -> Mesh:
    shape = elastic_mesh_shape(n_devices, prefer_model)
    return make_mesh(shape, ("data", "model"))


def restore_on_mesh(
    ckpt: CheckpointManager, step: int, abstract_state: Any,
    cfg: ArchConfig, mesh: Mesh,
) -> Any:
    """Re-shard a checkpoint onto a (possibly different) mesh."""
    specs = state_specs(cfg, abstract_state, mesh)
    shardings = to_named(mesh, specs)
    return ckpt.restore(step, abstract_state, shardings=shardings)
