"""Elastic scaling: replan the mesh when hosts join/leave, re-shard state.

Checkpoints store full (unsharded) arrays (train/checkpoint.py), so
re-sharding after a topology change is: plan a new mesh from the surviving
chip count, rebuild NamedShardings with the same rules engine, and
device_put the restored pytree -- no format migration.  `plan_mesh` keeps
the model axis fixed (TP degree is a property of the model, not the fleet)
and gives the remainder to data/pod axes, dropping stragglers to the
largest usable power-of-two-friendly shape.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.launch.mesh import auto_axes


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: tuple[int, ...]
    axis_names: tuple[str, ...]
    used_chips: int
    idle_chips: int


def plan_mesh(available_chips: int, model_parallel: int = 16,
              chips_per_pod: int = 256) -> MeshPlan:
    """Largest usable mesh with a fixed model axis."""
    if available_chips < model_parallel:
        raise ValueError(
            f"need >= {model_parallel} chips for TP={model_parallel}")
    if available_chips >= 2 * chips_per_pod:
        pods = available_chips // chips_per_pod
        data = chips_per_pod // model_parallel
        shape = (pods, data, model_parallel)
        names = ("pod", "data", "model")
    else:
        data = available_chips // model_parallel
        shape = (data, model_parallel)
        names = ("data", "model")
    used = int(np.prod(shape))
    return MeshPlan(shape, names, used, available_chips - used)


def make_mesh_from_plan(plan: MeshPlan, devices=None):
    devices = devices if devices is not None else jax.devices()
    return jax.make_mesh(plan.shape, plan.axis_names,
                         axis_types=auto_axes(len(plan.axis_names)),
                         devices=devices[:plan.used_chips])


def reshard(tree, shardings):
    """Place a (host or differently-sharded) pytree onto new shardings."""
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), s), tree, shardings)


def rebatch_plan(global_batch: int, old_dp: int, new_dp: int) -> dict:
    """Keep the global batch (approximately) constant across elastic events
    by adjusting the per-replica microbatch, adding gradient accumulation
    when the new replica count would otherwise need a bigger-than-before
    microbatch (memory-safe).  The effective batch rounds UP to the nearest
    achievable size; it never shrinks."""
    old_per = max(1, global_batch // max(old_dp, 1))
    accum = 1
    while True:
        per = -(-global_batch // (new_dp * accum))   # ceil
        if per <= old_per or accum >= global_batch:
            break
        accum += 1
    return {"per_replica_batch": per, "grad_accum": accum,
            "effective_batch": per * new_dp * accum}
