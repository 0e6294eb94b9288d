"""Cross-process serving: coordinator-backed worker pools, process-kill
chaos, quarantine propagation through the shared cache file, and the
pool-lost fallback to the in-process ladder.

These tests spawn REAL worker processes (multiprocessing spawn context)
and kill them with REAL SIGKILLs — no simulation.  The CI
``chaos-multiproc`` lane re-runs them with 8 forced host devices so the
per-worker lane meshes actually span devices."""
import numpy as np
import pytest

from repro.core import dispatch as dp
from repro.core.formats import random_sparse
from repro.runtime import coordinator as coord
from repro.runtime import faultinject as fi
from repro.serving import spgemm_service as svc

N_REQ = 12

CLASSES = [(32, 0.02, "uniform"), (48, 0.05, "uniform"),
           (48, 0.008, "powerlaw"), (64, 0.03, "banded")]


def _mat(n=48, density=0.02, seed=0, pattern="uniform"):
    return random_sparse(n, n, density, seed=seed, pattern=pattern)


def _dense(csr):
    return np.asarray(csr.to_dense(), np.float64)


def _stream(n_req=N_REQ):
    mats = [_mat(n=c[0], density=c[1], pattern=c[2], seed=i)
            for i, c in enumerate(CLASSES)]
    rng = np.random.default_rng(3)
    return [mats[int(rng.integers(len(mats)))] for _ in range(n_req)]


def _run_traffic(cache, coordinator=None, n_req=N_REQ):
    """Drive the fixed request stream through a service (in-process when
    ``coordinator`` is None, pool-dispatched otherwise)."""
    service = svc.SpGemmService(
        cache=cache, max_batch=4, flush_timeout=1e9,
        coordinator=coordinator,
        policy=dp.RetryPolicy(max_attempts=5, backoff_base_s=0.0))
    for m in _stream(n_req):
        service.submit(m, m)
    service.drain()
    return service


@pytest.fixture(scope="module")
def ref_run(tmp_path_factory):
    """The fault-free single-process reference: the bit-exactness oracle
    for every multi-process run of the same stream."""
    cache = dp.AutotuneCache(
        str(tmp_path_factory.mktemp("ref") / "autotune.json"))
    service = _run_traffic(cache)
    assert len(service.completed) == N_REQ and not service.dead_letters
    return {r.id: _dense(r.result) for r in service.completed}


def _wait_task(pool, task_id, timeout=180.0):
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for tid, res in pool.poll(timeout=1.0):
            if tid == task_id:
                return res
    raise TimeoutError(f"task {task_id} never completed")


# ---------------------------------------------------------------------------
# payload plumbing
# ---------------------------------------------------------------------------

def test_pack_unpack_csr_round_trip():
    m = _mat(seed=5)
    back = coord.unpack_csr(coord.pack_csr(m))
    assert back.shape == m.shape
    np.testing.assert_array_equal(_dense(back), _dense(m))


def test_remote_flush_payload_carries_policy():
    class _R:
        def __init__(self, m):
            self.A = self.B = m
    p = coord.make_flush_payload(
        [_R(_mat(seed=6))], bucket=("b",), engine="auto", max_batch=4,
        policy=dp.RetryPolicy(max_attempts=7, backoff_base_s=0.125))
    assert p["policy"]["max_attempts"] == 7
    assert p["policy"]["backoff_base_s"] == 0.125
    assert len(p["pairs"]) == 1 and p["max_batch"] == 4


# ---------------------------------------------------------------------------
# the pool, healthy
# ---------------------------------------------------------------------------

def test_multiproc_serving_matches_single_process(tmp_path, ref_run):
    """2-worker pool, no faults: every request completes, results are
    bit-exact vs the in-process run, flush provenance comes from the
    workers."""
    with coord.ProcessCoordinator(
            2, cache_path=str(tmp_path / "mp.json")) as pool:
        service = _run_traffic(
            dp.AutotuneCache(str(tmp_path / "mp.json")), coordinator=pool)
        assert pool.alive_count == 2
    assert len(service.completed) == N_REQ and not service.dead_letters
    for r in service.completed:
        assert r.tier == "planned"
        assert np.array_equal(_dense(r.result), ref_run[r.id]), r.id
    assert service.flush_log and all(f.engine not in ("?", None)
                                     for f in service.flush_log)
    # the pool actually partitioned the lane space at startup
    spawns = [e for e in pool.events if e["event"] == "spawn"]
    assert len(spawns) == 2 and all(e["n_lanes"] >= 1 for e in spawns)


# ---------------------------------------------------------------------------
# the acceptance gate: SIGKILL a worker process mid-flush
# ---------------------------------------------------------------------------

def test_chaos_process_kill_mid_flush(tmp_path, ref_run):
    """THE multi-process chaos acceptance: worker process 0 is SIGKILLed
    mid-flush (a real ``kill_process`` fault inside the spawned process)
    while batched kernel launches fail at a 10% injected rate in every
    worker.  Every submitted id must resolve, availability must be 1.0,
    and planned-tier outputs must be bit-exact vs the fault-free
    single-process run."""
    kernel_chaos = fi.FaultSpec(site="kernel.batched", kind="raise",
                                rate=0.10)
    specs = {
        0: [fi.FaultSpec(site="service.flush", kind="kill_process",
                         max_fires=1), kernel_chaos],
        1: [kernel_chaos],
    }
    with coord.ProcessCoordinator(
            2, cache_path=str(tmp_path / "chaos.json"),
            fault_specs=specs, fault_seed=11,
            max_worker_restarts=1) as pool:
        service = _run_traffic(
            dp.AutotuneCache(str(tmp_path / "chaos.json")),
            coordinator=pool)
        events = [e["event"] for e in pool.events]

    # nothing silently dropped: every submitted id resolves exactly once
    for rid in range(N_REQ):
        r = service.lookup(rid)
        assert r.done, f"request {rid} neither completed nor dead-lettered"
        assert (r.result is not None) != (r.error is not None)
    stats = service.stats()
    assert stats["availability"] == 1.0, stats

    # planned-tier outputs are bit-exact vs the fault-free run — a kill
    # moves *where* a bucket ran, never *what* it computed
    for r in service.completed:
        if r.tier == "planned":
            assert np.array_equal(_dense(r.result), ref_run[r.id]), r.id
        else:
            np.testing.assert_allclose(_dense(r.result), ref_run[r.id],
                                       rtol=1e-4, atol=1e-4)

    # the chaos was real: a worker died and the pool re-partitioned
    assert "worker_lost" in events, events
    assert "remesh" in events, events


def test_hung_worker_is_killed_and_task_requeued(tmp_path):
    """A worker that hangs mid-task (injected ``hang``) is declared lost
    at task_timeout_s, SIGKILLed, and its bucket re-runs on a
    survivor."""
    specs = {0: [fi.FaultSpec(site="service.flush", kind="hang",
                              delay_s=120.0, max_fires=1)]}
    m = _mat(n=32, density=0.02, seed=0)
    with coord.ProcessCoordinator(
            2, cache_path=str(tmp_path / "hang.json"),
            fault_specs=specs, max_worker_restarts=0,
            task_timeout_s=6.0) as pool:
        payload = {"pairs": [(coord.pack_csr(m), coord.pack_csr(m))],
                   "engine": "auto", "max_batch": 4,
                   "policy": {"max_attempts": 2, "backoff_base_s": 0.0}}
        tid = pool.submit(payload, prefer=0)
        res = _wait_task(pool, tid)
        events = [e for e in pool.events if e["event"] == "worker_lost"]
    assert res.get("outcomes") and all(o["ok"] for o in res["outcomes"])
    assert events and "timeout" in events[0]["why"], pool.events


# ---------------------------------------------------------------------------
# quarantine propagation across processes
# ---------------------------------------------------------------------------

def test_quarantine_propagates_across_worker_processes(tmp_path):
    """A combo crashing in worker process A is routed around by worker
    process B without B ever executing it: A's local ladder quarantines
    and pushes to the shared cache file; B's plan miss pulls the poison
    and selects a healthy engine on the first attempt."""
    cache_path = str(tmp_path / "shared.json")
    m = _mat(n=48, density=0.05, seed=1)
    payload = {"pairs": [(coord.pack_csr(m), coord.pack_csr(m))] * 2,
               "engine": "auto", "max_batch": 4,
               "policy": {"max_attempts": 2, "backoff_base_s": 0.0}}
    # worker 0: every *batched* kernel launch dies (planned tier and
    # the whole ladder — isolation is single-pair and survives);
    # worker 1: healthy
    specs = {0: [fi.FaultSpec(site="kernel.batched", kind="raise")]}
    with coord.ProcessCoordinator(
            2, cache_path=cache_path, fault_specs=specs) as pool:
        t1 = pool.submit(dict(payload), prefer=0)
        res1 = _wait_task(pool, t1)
        # A survived on per-request isolation (its batched path is dead)
        # and — the point — pushed the quarantine to the shared file
        assert all(o["ok"] for o in res1["outcomes"])
        assert res1["flush"]["tier"] == "isolated", res1["flush"]

        shared = dp.AutotuneCache(cache_path)
        key = dp.cache_key(m, m)
        poisoned = {e for e, _ in shared.quarantined(key)}
        assert poisoned, "worker A never pushed its quarantine"

        t2 = pool.submit(dict(payload), prefer=1)
        res2 = _wait_task(pool, t2)
    # B planned around the poison: healthy engine, first attempt, no
    # errors — it never executed the quarantined combo
    assert all(o["ok"] for o in res2["outcomes"])
    f2 = res2["flush"]
    assert f2["tier"] == "planned", f2
    assert f2["attempts"] == 1 and not f2["errors"], f2
    assert f2["engine"] not in poisoned, (f2, poisoned)


# ---------------------------------------------------------------------------
# total pool loss: the in-process ladder is the floor
# ---------------------------------------------------------------------------

def test_pool_lost_falls_back_to_local_ladder(tmp_path):
    """1-worker pool with zero restart budget and a kill-on-flush fault:
    the pool dies, and the service serves every request through its own
    in-process ladder anyway."""
    specs = [fi.FaultSpec(site="service.flush", kind="kill_process",
                          max_fires=1)]
    with coord.ProcessCoordinator(
            1, cache_path=str(tmp_path / "lost.json"),
            fault_specs=specs, max_worker_restarts=0) as pool:
        service = _run_traffic(
            dp.AutotuneCache(str(tmp_path / "lost.json")),
            coordinator=pool, n_req=8)
        assert pool.alive_count == 0  # the pool really is gone
    assert len(service.completed) == 8 and not service.dead_letters
    assert service.stats()["availability"] == 1.0


# ---------------------------------------------------------------------------
# one process per chip: the pool refuses to spawn where workers would
# contend for a TPU, instead of hanging
# ---------------------------------------------------------------------------

class _FakeTpu:
    platform = "tpu"


def test_coordinator_refuses_when_parent_holds_tpu(monkeypatch):
    from jax._src import xla_bridge
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    with pytest.raises(coord.ChipContention, match="holds its chips"):
        coord.ProcessCoordinator(1)


@pytest.mark.parametrize("n_workers", [1, 4])
def test_coordinator_refuses_on_tpu_host(monkeypatch, n_workers):
    monkeypatch.setattr(coord, "_host_tpu_chips", lambda: 4)
    with pytest.raises(coord.ChipContention,
                       match=f"{n_workers} worker process"):
        coord.ProcessCoordinator(n_workers)

