"""Production meshes.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — the dry-run must set XLA_FLAGS
before the first jax initialization.  Every mesh uses Auto axis types:
the model code leaves sharding propagation to the compiler (explicit
axes would reject its gathers over sharded operands)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_lane_mesh(n: int | None = None):
    """1-D ("lanes",) mesh over the visible devices — the axis the
    lane-sharded batched SpGEMM path (distributed/spgemm_shard.py) runs
    its shard_map over. ``n`` caps the device count (default: all)."""
    devs = jax.devices()
    n = n or len(devs)
    return make_mesh((n,), ("lanes",), devices=devs[:n])


def make_host_mesh(model_axis: int | None = None):
    """Largest (data, model) mesh on the visible devices (tests, examples)."""
    n = len(jax.devices())
    model = model_axis or (4 if n % 4 == 0 and n >= 4 else 1)
    data = n // model
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])
