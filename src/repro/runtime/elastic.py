"""Elastic scaling: rebuild the mesh at a new size and reshard state.

The mechanism is deliberately thin because the substrate makes it cheap:
  * checkpoints are mesh-agnostic (host numpy),
  * shardings are derived from (config, mesh) — not stored,
  * the data pipeline is deterministic in (seed, step, shard),
so scaling from N to M pods is: build new mesh -> derive shardings ->
restore latest checkpoint with them -> continue at the saved step.
"""
from __future__ import annotations

import jax

from repro.checkpoint import ckpt
from repro.distributed import sharding as shd


def reshard_restore(ckpt_dir: str, target_tree, mesh, *, fsdp: bool,
                    step=None):
    """Restore a params/opt pytree onto ``mesh`` (any size)."""
    with shd.use_mesh(mesh):
        shardings = shd.param_shardings(
            jax.eval_shape(lambda: target_tree), fsdp)
        return ckpt.restore(ckpt_dir, target_tree, step=step,
                            shardings=shardings)


def remesh(n_devices: int, *, multi_pod: bool = False):
    """Build the largest (data, model) mesh for the available devices,
    holding the model axis fixed and scaling the data axis — the policy a
    resize controller would use when pods join/leave."""
    from repro.launch.mesh import make_mesh, make_production_mesh  # lazy
    try:
        return make_production_mesh(multi_pod=multi_pod)
    except Exception:
        devs = jax.devices()[:n_devices]
        model = min(16, len(devs))
        data = len(devs) // model
        return make_mesh((data, model), ("data", "model"),
                         devices=devs[: data * model])


def remesh_lanes(n_lanes: int, n_workers: int) -> list[range]:
    """Partition ``n_lanes`` device lanes over ``n_workers`` processes.

    The lane-sharding analogue of :func:`remesh`, used by the process
    coordinator (``runtime/coordinator.py``) to (re)assign lane
    ownership when workers join or leave: contiguous slices, sizes
    differing by at most one, earlier workers taking the remainder.
    With more workers than lanes, the surplus workers share lane 0
    (every worker must own at least one lane to be schedulable — a
    lane-less worker could never run a flush).  Deterministic in
    (n_lanes, n_workers), so every process computes the same partition
    without coordination."""
    if n_workers < 1:
        raise ValueError(f"n_workers must be >= 1, got {n_workers}")
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    if n_workers > n_lanes:
        # surplus workers share lane 0 rather than idling
        return [range(0, 1) if i >= n_lanes else range(i, i + 1)
                for i in range(n_workers)]
    base, rem = divmod(n_lanes, n_workers)
    out, lo = [], 0
    for i in range(n_workers):
        hi = lo + base + (1 if i < rem else 0)
        out.append(range(lo, hi))
        lo = hi
    return out
