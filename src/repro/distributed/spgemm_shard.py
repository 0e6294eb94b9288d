"""Lane-sharded batched SpGEMM: balanced lane->device assignment + shard_map.

SpArch's observation is that merge-tree throughput multiplies across
independent partitions, and the RISC-V SpGEMM study shows that *load
balance*, not raw FLOPs, decides vectorized SpGEMM throughput.  A
``BatchedCSR`` request batch is embarrassingly parallel across lanes, so
this module scales ``spgemm_batched`` by (1) assigning lanes to devices
with an LPT (longest-processing-time-first) greedy pass over per-lane
work — one heavy matrix must not serialize a device — and (2) running
each device's lane group in parallel:

  * **esc** (the jittable engine): one ``shard_map`` over a 1-D
    ``("lanes",)`` mesh (``launch/mesh.py::make_lane_mesh``, the same
    idiom as ``models/moe.py``), every device vmapping the ESC core
    over its local lane shard under one compilation;
  * **spz family** (host-orchestrated pipelines): the same balanced
    assignment executed group-at-a-time through the batched drivers,
    device d's lane group placed on and run by mesh device d —
    per-stream payloads are independent of which streams share a kernel
    issue (see ``core/spgemm.py``), so splitting the batch cannot change
    results.

Both paths produce output ``BatchedCSR``s bit-identical to the
single-device ``spgemm_batched``: planning is shared (same
``ExecutionPlan``, same static capacities), only the placement differs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch as dp
from repro.core import spgemm_engines as sg
from repro.core import trace
from repro.core.formats import EMPTY, BatchedCSR, csr_from_coo
from repro.launch.mesh import make_lane_mesh
from repro.runtime import faultinject as fi


class WorkerLost(RuntimeError):
    """A shard worker (one device's lane group) died mid-flush.

    Raised by the ``shard.worker`` fault site in chaos tests, and the
    exception a real multi-host transport would surface on a lost peer.
    The executors below treat it as recoverable: the dead worker's lanes
    are re-run on a surviving device (see :func:`_execute_groups`)."""

    def __init__(self, device: int, message: str = ""):
        self.device = device
        super().__init__(message or f"shard worker {device} lost")


def kill_worker_spec(device: int, *, rate: float = 1.0,
                     max_fires: Optional[int] = 1) -> fi.FaultSpec:
    """A :class:`~repro.runtime.faultinject.FaultSpec` that kills shard
    worker ``device`` (default: once) — the chaos-test building block."""
    return fi.FaultSpec(
        site="shard.worker", kind="raise", rate=rate, max_fires=max_fires,
        match={"device": device},
        exc_factory=lambda site, ctx: WorkerLost(
            ctx.get("device", device), "injected worker kill"))


# ---------------------------------------------------------------------------
# work-balanced lane assignment
# ---------------------------------------------------------------------------

def lane_works(A: BatchedCSR, B: BatchedCSR) -> np.ndarray:
    """Per-lane multiply work (sum of row_work); 0 for invalid lanes."""
    w = np.zeros(A.batch, np.int64)
    for i, a in A.lanes():
        if bool(np.asarray(B.valid)[i]):
            w[i] = int(sg.row_work(a, B[i]).sum())
    return w


def assign_lanes(works: np.ndarray, n_dev: int,
                 lanes_per_dev: Optional[int] = None) -> np.ndarray:
    """LPT greedy lane->device assignment.

    Heaviest lane first onto the least-loaded device that still has a
    free slot (shard_map needs equal lane counts per device, so each
    device takes at most ``lanes_per_dev`` = ceil(n/n_dev) lanes).
    Returns the device id per lane."""
    n = len(works)
    cap = lanes_per_dev or -(-n // max(1, n_dev))
    dev = np.zeros(n, np.int64)
    load = np.zeros(n_dev, np.int64)
    counts = np.zeros(n_dev, np.int64)
    for i in np.argsort(-np.asarray(works, np.int64), kind="stable"):
        order = np.argsort(load, kind="stable")
        d = next(int(d) for d in order if counts[d] < cap)
        dev[i] = d
        load[d] += works[i]
        counts[d] += 1
    return dev


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """A batched ExecutionPlan plus its lane->device placement.

    ``slot_of_lane[i]`` is lane i's position in the device-major slot
    layout (device d owns slots [d*lanes_per_dev, (d+1)*lanes_per_dev));
    unfilled slots hold padding (empty, invalid) lanes."""

    base: dp.ExecutionPlan
    mesh: jax.sharding.Mesh
    n_dev: int
    lanes_per_dev: int
    slot_of_lane: tuple
    works: tuple

    @property
    def n_slots(self) -> int:
        return self.n_dev * self.lanes_per_dev

    def device_loads(self) -> list:
        """Planned per-device total work (for inspection/benchmarks)."""
        loads = [0] * self.n_dev
        for i, s in enumerate(self.slot_of_lane):
            loads[s // self.lanes_per_dev] += self.works[i]
        return loads


def plan_sharded(A: BatchedCSR, B: BatchedCSR, engine: str = "auto", *,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 cache: Optional[dp.AutotuneCache] = None,
                 rules=dp.DEFAULT_HEURISTICS, **kw) -> ShardPlan:
    """Plan a batched multiply and its work-balanced lane placement."""
    works = lane_works(A, B)
    base = dp.plan_batched(A, B, engine, cache=cache, rules=rules,
                           lane_work_hint=works, **kw)
    if mesh is None:
        mesh = make_lane_mesh()
    if "lanes" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'lanes' axis: {mesh.axis_names}")
    n_dev = mesh.shape["lanes"]
    lanes_per_dev = -(-A.batch // n_dev)
    dev = assign_lanes(works, n_dev, lanes_per_dev)
    next_slot = [d * lanes_per_dev for d in range(n_dev)]
    slot_of_lane = []
    for i in range(A.batch):
        slot_of_lane.append(next_slot[dev[i]])
        next_slot[dev[i]] += 1
    return ShardPlan(base=base, mesh=mesh, n_dev=n_dev,
                     lanes_per_dev=lanes_per_dev,
                     slot_of_lane=tuple(slot_of_lane),
                     works=tuple(int(w) for w in works))


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def _permute_to_slots(A: BatchedCSR, sp: ShardPlan) -> BatchedCSR:
    """Re-lay a BatchedCSR into the plan's device-major slot order,
    padding unfilled slots with empty invalid lanes."""
    n_rows = A.shape[0]
    indptr = np.zeros((sp.n_slots, n_rows + 1), np.int32)
    indices = np.full((sp.n_slots, A.nnz_cap), EMPTY, np.int32)
    data = np.zeros((sp.n_slots, A.nnz_cap), np.float32)
    valid = np.zeros(sp.n_slots, bool)
    slots = np.asarray(sp.slot_of_lane, np.int64)
    indptr[slots] = np.asarray(A.indptr)
    indices[slots] = np.asarray(A.indices)
    data[slots] = np.asarray(A.data)
    valid[slots] = np.asarray(A.valid)
    return BatchedCSR(jnp.asarray(indptr), jnp.asarray(indices),
                      jnp.asarray(data), jnp.asarray(valid), A.shape)


@functools.lru_cache(maxsize=64)
def _sharded_esc_fn(mesh, cap_products: int, n_rows: int, n_cols: int):
    """One jitted shard_map per (mesh, static capacities): each device
    vmaps the ESC core over its local lane shard."""
    def local(ip, ix, d, bip, bix, bd):
        return jax.vmap(sg.esc_core_impl,
                        in_axes=(0, 0, 0, 0, 0, 0, None, None, None))(
            ip, ix, d, bip, bix, bd, cap_products, n_rows, n_cols)

    spec = P("lanes")
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 6,
                                 out_specs=(spec,) * 5))


def _execute_esc_sharded(sp: ShardPlan, A: BatchedCSR, B: BatchedCSR) -> list:
    kw = sp.base.kwargs_dict
    unknown = set(kw) - {"cap_products"}
    if unknown:  # parity with the strict-kwargs single-device driver
        raise TypeError(f"esc sharded path got unexpected kwargs {unknown}")
    Ap, Bp = _permute_to_slots(A, sp), _permute_to_slots(B, sp)
    # the shard_map launch is the batched kernel for this flush: same
    # fault site as the per-group drivers in _execute_groups
    fi.fire("kernel.batched", engine="esc", lanes=A.batch)
    cap = kw["cap_products"]
    fn = _sharded_esc_fn(sp.mesh, cap, A.n_rows, B.n_cols)
    r, c, v, valid, _ = fn(Ap.indptr, Ap.indices, Ap.data,
                           Bp.indptr, Bp.indices, Bp.data)
    r, c, v, valid = map(np.asarray, (r, c, v, valid))
    lane_ok = np.asarray(A.valid) & np.asarray(B.valid)
    outs = []
    for i in range(A.batch):
        s = sp.slot_of_lane[i]
        outs.append(csr_from_coo(r[s][valid[s]], c[s][valid[s]],
                                 v[s][valid[s]], (A.n_rows, B.n_cols))
                    if lane_ok[i] else None)
    return outs


def _lane_select(A: BatchedCSR, idx: np.ndarray, device) -> BatchedCSR:
    """Lanes ``idx`` of a batch, committed to ``device``."""
    return BatchedCSR(*jax.device_put(
        (A.indptr[idx], A.indices[idx], A.data[idx], A.valid[idx]), device),
        A.shape)


def _execute_groups(sp: ShardPlan, A: BatchedCSR, B: BatchedCSR, *,
                    dead: Optional[set] = None,
                    max_worker_restarts: int = 3) -> list:
    """Host-orchestrated engines: run one device group at a time through
    the batched driver (same plan kwargs, so same static shapes), group
    d's operands committed to mesh device d and its kernels issued there.

    Worker supervision (the serving-flush generalization of
    ``runtime/fault.py::run_resilient``'s restart loop): a device group
    whose worker dies (:class:`WorkerLost` — injected via the
    ``shard.worker`` fault site, or a real transport error) marks that
    device dead and collects its lanes; after the first pass, lost lanes
    are re-run on a surviving device, with bounded restarts.  Because
    per-stream payloads are independent of which streams share a kernel
    issue, re-running a lane group elsewhere is bit-identical to the
    uninterrupted flush."""
    driver = dp.get_batch_driver(sp.base.engine)
    kw = sp.base.kwargs_dict
    slots = np.asarray(sp.slot_of_lane)
    outs: list = [None] * A.batch
    lane_ok = np.asarray(A.valid) & np.asarray(B.valid)
    dead = set() if dead is None else set(dead)
    devices = sp.mesh.devices.reshape(-1)

    def run(lanes: list, device: int) -> None:
        fi.fire("shard.worker", device=device, engine=sp.base.engine)
        idx = np.asarray(lanes)
        dev = devices[device]
        with jax.default_device(dev), trace.span(
                trace.ENGINE, engine=sp.base.engine,
                backend=str(sp.base.backend), lanes=len(lanes)):
            sub = driver(_lane_select(A, idx, dev), _lane_select(B, idx, dev),
                         **kw)
        for j, i in enumerate(lanes):
            outs[i] = sub[j]

    lost: list = []
    for d in range(sp.n_dev):
        lo, hi = d * sp.lanes_per_dev, (d + 1) * sp.lanes_per_dev
        lanes = [i for i in range(A.batch)
                 if lo <= slots[i] < hi and lane_ok[i]]
        if not lanes:
            continue
        if d in dead:
            lost.extend(lanes)
            continue
        try:
            run(lanes, d)
        except WorkerLost:
            dead.add(d)
            lost.extend(lanes)
    restarts = 0
    while lost:
        alive = [d for d in range(sp.n_dev) if d not in dead]
        if not alive or restarts >= max_worker_restarts:
            raise WorkerLost(
                -1, f"{len(lost)} lanes unrecovered after {restarts} "
                    f"restarts ({sp.n_dev - len(alive)}/{sp.n_dev} "
                    f"workers dead)")
        restarts += 1
        try:
            run(lost, alive[0])
            lost = []
        except WorkerLost:
            dead.add(alive[0])
    return outs


def execute_sharded(sp: ShardPlan, A: BatchedCSR,
                    B: BatchedCSR) -> BatchedCSR:
    """Run a ShardPlan; bit-identical to ``execute_batched`` on the same
    base plan, with lanes placed per the balanced assignment."""
    dp.check_batch(A, B)
    if A.shape != sp.base.a_shape or B.shape != sp.base.b_shape \
            or A.batch != sp.base.batch:
        raise ValueError(
            f"shard plan/operand mismatch: planned {sp.base.batch}x"
            f"{sp.base.a_shape} @ {sp.base.b_shape}, got "
            f"{A.batch}x{A.shape} @ {B.shape}")
    if sp.base.engine == "esc":
        try:
            # the shard_map launch spans every device: fire the worker
            # site per participant so a kill spec matched on any device
            # id takes the whole launch down (one computation)
            for d in range(sp.n_dev):
                fi.fire("shard.worker", device=d, engine="esc")
            outs = _execute_esc_sharded(sp, A, B)
        except WorkerLost as e:
            # recover by re-running lane groups per device through the
            # batched driver, skipping the dead worker
            outs = _execute_groups(sp, A, B, dead={e.device})
    else:
        outs = _execute_groups(sp, A, B)
    return dp.assemble_batched(outs, A, B)


def spgemm_batched_sharded(A: BatchedCSR, B: BatchedCSR,
                           engine: str = "auto", *,
                           mesh: Optional[jax.sharding.Mesh] = None,
                           cache: Optional[dp.AutotuneCache] = None,
                           rules=dp.DEFAULT_HEURISTICS, **kw) -> BatchedCSR:
    """``spgemm_batched`` with lanes sharded over the device mesh.

    Exactly ``execute_sharded(plan_sharded(A, B, ...), A, B)``."""
    sp = plan_sharded(A, B, engine, mesh=mesh, cache=cache, rules=rules,
                      **kw)
    return execute_sharded(sp, A, B)
