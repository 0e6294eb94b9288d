"""Chip smoke test: A @ A through the normal entry points on a TPU, with the
Pallas kernels compiled for the chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the lane-sharded service flush, 4 chips

One chip: the device check, then two of the paper's matrices at their
published sizes (``benchmarks/datasets.py::PUBLISHED``, built from
``--seed``): ``plan``/``execute`` for ``spz`` pinned to the Pallas
backend and for ``esc``, then an in-process ``SpGemmService`` answering a
few requests per matrix with ``engine="auto"``.  Every result is checked
against ``scipy.sparse`` on the host.  Any fallback that would hide the
device path counts as a failure here: a degraded tier, a dead letter or
a quarantined kernel.

Four chips: only the service flush over a 4-device ``("lanes",)`` mesh,
and the one-device ``execute_batched`` it must match bit for bit, on
four requests of m133-b3 (its one bucket shape keeps the compiles, which
every device makes for itself, to one per device).

The last line of standard output is a JSON object with ``"ok": true`` and
the device, printed only when every phase passed.  Without a TPU, or
without the repository next to it, the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
REQUESTS_PER_MATRIX = 4


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def reference(A):
    """scipy A @ A in float64, and |A| @ |A| (a bound on the float32
    rounding of each output entry) on the same pattern."""
    import numpy as np
    import scipy.sparse as sps
    n = int(np.asarray(A.indptr)[-1])
    a = sps.csr_matrix((np.asarray(A.data)[:n].astype(np.float64),
                        np.asarray(A.indices)[:n], np.asarray(A.indptr)),
                       shape=A.shape)
    want = (a @ a).tocsr()
    want.sort_indices()
    bound = (abs(a) @ abs(a)).tocsr()
    bound.sort_indices()
    want_keys = _keys(want.indptr, want.indices, want.shape[1])
    at = np.searchsorted(_keys(bound.indptr, bound.indices, want.shape[1]),
                         want_keys)
    return want_keys, want.data, bound.data[at]


def _keys(indptr, indices, n_cols):
    import numpy as np
    rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                     np.diff(indptr))
    return rows * n_cols + np.asarray(indices, np.int64)


def check(label: str, got, ref) -> None:
    """Same pattern as the reference, values within rtol 1e-4 plus the
    float32 accumulation bound 1e-5 * (|A| @ |A|)."""
    import numpy as np
    want_keys, want, bound = ref
    n = int(np.asarray(got.indptr)[-1])
    keys = _keys(np.asarray(got.indptr), np.asarray(got.indices)[:n],
                 got.shape[1])
    order = np.argsort(keys, kind="stable")
    if not np.array_equal(keys[order], want_keys):
        fail(f"{label}: output pattern differs from scipy "
             f"({n} vs {len(want_keys)} nonzeros)")
    vals = np.asarray(got.data)[:n][order].astype(np.float64)
    err = np.abs(vals - want)
    tol = 1e-4 * np.abs(want) + 1e-5 * bound
    if not (err <= tol).all():
        i = int(np.argmax(err - tol))
        fail(f"{label}: value {vals[i]} vs scipy {want[i]} at entry {i}")
    log(f"check {label}: ok ({n} nonzeros match scipy)")


def block(csr):
    import jax
    jax.block_until_ready((csr.indptr, csr.indices, csr.data))
    return csr


def direct_engines(mats, refs, cache) -> None:
    """plan/execute for spz on the Pallas backend and for esc."""
    from repro.core import dispatch as dp
    for name, A in mats.items():
        for engine, backend in (("spz", "pallas"), ("esc", "auto")):
            p = dp.plan(A, A, engine=engine, backend=backend, cache=cache)
            if engine == "spz" and p.backend != "pallas":
                fail(f"spz planned on backend {p.backend!r}, not pallas")
            t0 = time.perf_counter()
            block(dp.execute(p, A, A))
            t1 = time.perf_counter()
            out = block(dp.execute(p, A, A))
            t2 = time.perf_counter()
            log(f"phase=direct matrix={name} engine={p.engine} "
                f"backend={p.backend} first_call_s={t1 - t0:.3f} "
                f"(compile + run)")
            log(f"phase=direct matrix={name} engine={p.engine} "
                f"backend={p.backend} steady_run_s={t2 - t1:.3f}")
            check(f"direct {name} {p.engine}/{p.backend}", out, refs[name])


def served(svc) -> None:
    """Fail unless every request was answered by its planned tier, with
    no dead letter and no quarantined kernel."""
    st = svc.stats()
    if svc.pending or svc.dead_letters:
        fail(f"{svc.pending} pending, {len(svc.dead_letters)} dead "
             f"letters: {[str(r.error) for r in svc.dead_letters]}")
    tiers = {r.tier for r in svc.completed}
    if tiers != {"planned"} or st["n_degraded"]:
        fail(f"requests served by tiers {tiers}, not only the planned one")
    quarantined = {k: svc.cache.quarantined(k)
                   for k in svc.cache.entries() if svc.cache.quarantined(k)}
    if quarantined:
        fail(f"quarantined kernels: {quarantined}")
    for f in svc.flush_log:
        log(f"phase=service flush bucket_rows={f.bucket[0][0]} "
            f"lanes={f.n_requests} engine={f.engine} source={f.source} "
            f"tier={f.tier} attempts={f.attempts} wall_s={f.wall_s:.3f}")
    log(f"phase=service requests={st['n_requests']} flushes="
        f"{st['n_flushes']} degraded={st['n_degraded']} dead_letters="
        f"{st['n_dead_letters']} quarantined=0")


def one_chip(mats, refs, cache) -> None:
    from repro.core import dispatch as dp
    from repro.kernels import backend as kb
    from repro.serving.spgemm_service import SpGemmService
    direct_engines(mats, refs, cache)
    log(f"phase=service backend_auto={kb.resolve_backend('auto').name}")
    svc = SpGemmService(max_batch=2, flush_timeout=1e9, engine="auto",
                        cache=cache, policy=dp.RetryPolicy())
    reqs = [(name, svc.submit(A, A)) for name, A in mats.items()
            for _ in range(REQUESTS_PER_MATRIX)]
    svc.drain()
    served(svc)
    for name, r in reqs:
        check(f"service {name} request {r.id} ({r.engine})", r.result,
              refs[name])


def four_chips(mats, refs, cache, name="m133-b3") -> None:
    import jax
    import numpy as np
    from repro.core import dispatch as dp
    from repro.core.formats import batch_csr
    from repro.launch.mesh import make_lane_mesh
    from repro.runtime import faultinject as fi
    from repro.serving.spgemm_service import SpGemmService
    mesh = make_lane_mesh(4)
    seen: list[str] = []
    # every batched kernel launch notes the device it was issued on
    spy = fi.FaultSpec(site="kernel.batched", kind="call", rate=1.0,
                       max_fires=None, action=lambda **ctx: seen.append(
                           str(jax.config.jax_default_device)))
    svc = SpGemmService(max_batch=4, flush_timeout=1e9, engine="auto",
                        mesh=mesh, cache=cache, policy=dp.RetryPolicy())
    A = mats[name]
    with fi.injected(spy):
        reqs = [svc.submit(A, A) for _ in range(4)]
        svc.drain()
    served(svc)
    log(f"phase=sharded lane groups issued on: "
        f"{sorted(set(seen))} ({len(seen)} launches)")
    if len(set(seen)) != 4:
        fail(f"lanes ran on {sorted(set(seen))}, not on 4 devices")
    Ab = batch_csr([A] * 4)
    p = dp.plan_batched(Ab, Ab, engine=reqs[0].engine, backend="pallas",
                        cache=cache)
    t0 = time.perf_counter()
    ref = dp.execute_batched(p, Ab, Ab)
    log(f"phase=one_device matrix={name} engine={p.engine} "
        f"backend={p.backend} run_s={time.perf_counter() - t0:.3f}")
    for j, r in enumerate(reqs):
        for f in ("indptr", "indices", "data"):
            if not np.array_equal(np.asarray(getattr(r.result, f)),
                                  np.asarray(getattr(ref[j], f))):
                fail(f"sharded {name} request {r.id}: {f} differs from "
                     f"the one-device execute_batched")
        check(f"sharded {name} request {r.id}", r.result, refs[name])
    log(f"phase=sharded matrix={name}: 4 lanes bit-exact vs one device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        import jax
        from benchmarks import datasets
        from repro.core import dispatch as dp
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"cannot import the repository next to this script: {e}")
    log(f"compile cache: {enable_compile_cache()}")
    devs = jax.devices()
    d0 = devs[0]
    log(f"jax {jax.__version__} platform={d0.platform} "
        f"kind={d0.device_kind} count={len(devs)}")
    if d0.platform != "tpu":
        fail(f"no TPU found (JAX sees {d0.platform} devices)")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devs)}")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    cache = dp.AutotuneCache(os.path.join(OUT, "autotune.json"))
    mats, refs = {}, {}
    for name in datasets.PUBLISHED if args.chips == 1 else ["m133-b3"]:
        t0 = time.perf_counter()
        mats[name] = A = datasets.build_published(name, seed=args.seed)
        refs[name] = reference(A)
        log(f"input {name}: {A.shape[0]} rows, "
            f"{int(A.indptr[-1])} nonzeros, A@A {len(refs[name][0])} "
            f"nonzeros (built + scipy reference in "
            f"{time.perf_counter() - t0:.1f}s)")
    (four_chips if args.chips == 4 else one_chip)(mats, refs, cache)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
