"""Elastic scaling: rebuild the mesh from the live device set and re-shard
the training state.

JIF checkpoints record *logical* axes, not device placements, so a restore
can materialize the same state under ANY mesh: scale-down after failures
and scale-up after recovery are both "restore under the new rules" — the
serverless cold-start machinery doing cluster-management work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.sharding.partition import axis_rules, shardings_from_specs


@dataclasses.dataclass
class MeshPlan:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]


def plan_mesh(n_devices: int, model_parallel: int = 16, pods: int = 1) -> MeshPlan:
    """Largest (pod, data, model) grid that fits the live device count,
    holding TP fixed (weights layouts survive) and shrinking DP."""
    mp = model_parallel
    while mp > 1 and n_devices % mp:
        mp //= 2
    data = max(n_devices // (mp * pods), 1)
    if pods > 1:
        return MeshPlan((pods, data, mp), ("pod", "data", "model"))
    return MeshPlan((data, mp), ("data", "model"))


def make_mesh_from_plan(plan: MeshPlan, devices: Optional[List] = None):
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(plan.shape))
    dev = np.asarray(devices[:n]).reshape(plan.shape)
    return jax.sharding.Mesh(
        dev, plan.axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(plan.axes),
    )


def reshard_state(state_np, specs_tree, mesh, rules: Dict):
    """Place a host-resident (restored) state onto a new mesh."""
    with axis_rules(mesh, rules):
        sh = shardings_from_specs(specs_tree)

    def put(arr, s):
        if s is None:
            return jax.device_put(arr)
        return jax.device_put(arr, s)

    return jax.tree.map(put, state_np, sh)
