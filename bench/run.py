"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload m133.a2 --seed 7 --seconds 10 --trace 0

Everything per cell is found by name from ``BENCHMARK.json``: the
configuration in ``bench/configs/<config>.json`` (its matrices from the
generator it names, ``bench/gen/<name>.py``), the traffic mix in
``bench/traffic/<traffic>.json`` (read by ``drive.py``, the one
generator) and each metric's reader in ``bench/metrics/<name>.py``, or,
for a quantity split by cell (``<quantity>.<part>``), in
``bench/metrics/<quantity>.py``.

A run builds its operands from ``--seed``, warms every shape its traffic
uses (set-up), measures a window of ``--seconds``, then compares every
answer of the window with the plain reference (``reference.py``).
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records the window with the profiler and reports its per-layer metrics,
read from the run: ``run.trace`` (``trace_reduce.py``: device busy time,
the benchmark's spans), ``run.program`` (``program_trace.py``: the
program's spans and device scopes) and ``run.operands`` (each completed
operation's operand and reference size, for counting least work).
The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.

``--rehearse`` runs the same path on the CPU at a tiny size, with the
products' Pallas kernels in interpret mode: its result line says
``"rehearsal": true`` and carries no metric values.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import psutil

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
REHEARSAL_ROWS = 1024
TOP = 10


class NoResult(Exception):
    """The run cannot produce a result; it exits non-zero."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(name: str) -> dict:
    """The cell, its configuration, its traffic and its metrics."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NoResult(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell,
            "config": load_json(ROOT, configs[cell["config"]]["file"]),
            "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read``; where that file is not
    there, the reader of the quantity the metric splits by cell:
    ``device.idle_share.py`` for ``device.idle_share.serve``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(BENCH, "metrics",
                            metric.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """What JAX traced, lowered, compiled and found in its persistent
    cache, per phase of the run: the window should see none of it."""

    KINDS = {"jaxpr_trace_duration": "traced",
             "jaxpr_to_mlir_module_duration": "lowered",
             "backend_compile_duration": "compiled",
             "cache_retrieval_time_sec": "cache_hit"}

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._event)
        jax.monitoring.register_event_listener(self._miss)

    def _add(self, kind: str, seconds: float) -> None:
        n, s = self.counts.get((self.phase, kind), (0, 0.0))
        self.counts[(self.phase, kind)] = (n + 1, s + seconds)

    def _event(self, event: str, duration: float, **kw) -> None:
        kind = self.KINDS.get(event.rsplit("/", 1)[-1])
        if kind:
            self._add(kind, duration)

    def _miss(self, event: str, **kw) -> None:
        if event.endswith("cache_misses"):
            self._add("cache_miss", 0.0)

    def count(self, phase: str, kind: str) -> int:
        return self.counts.get((phase, kind), (0, 0.0))[0]

    def summary(self, phase: str) -> str:
        return ", ".join(
            f"{kind} {n} ({s:.3f} s)" for (p, kind), (n, s)
            in sorted(self.counts.items()) if p == phase) or "nothing"


def device_check(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if rehearse:
        return d0, devs, None
    if d0.platform != "tpu":
        raise NoResult(f"no TPU found (JAX sees {d0.platform} devices)")
    if len(devs) < chips:
        raise NoResult(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    import work
    try:
        return d0, devs, work.peaks(d0.device_kind)
    except KeyError as e:
        raise NoResult(str(e)) from None


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory of the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says; every program
    is cached, however short its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def measure(args, spec, t_start: float):
    """Set-up, then the window; returns the run's record, its pool and
    the answers on the host, and the device's memory peak."""
    import jax
    import drive
    from trace_reduce import find_xplane

    d0, devs, peak = device_check(spec["cell"]["chips"], args.rehearse)
    drive.log(f"bench: workload={args.workload} seed={args.seed} "
              f"platform={d0.platform} kind={d0.device_kind} "
              f"count={len(devs)}"
              + (" REHEARSAL on the CPU, Pallas in interpret mode"
                 if args.rehearse else ""))
    if not args.rehearse:
        enable_compile_cache()
    compiles = CompileLog()
    traffic, config = spec["traffic"], spec["config"]
    rows = REHEARSAL_ROWS if args.rehearse else config["rows"]
    run = drive.Run(peak=peak)
    t = time.time()
    drive.log(f"bench: set-up: JAX and the devices ready at "
              f"{t - t_start:.3f} s")
    pool = drive.Pool(config, traffic, args.seed, rows)
    drive.log(f"bench: set-up: operand pool of {len(pool)} built in "
              f"{time.time() - t:.3f} s")
    t = time.time()
    entry = drive.ENTRIES[traffic["entry"]](
        traffic, pool, run, "pallas" if args.rehearse else "auto")
    for ks in pool.warm_rounds(traffic["callers"]):
        for op in entry.round(ks):   # warms every shape of the traffic
            if op.error:
                drive.warn(f"bench: warm-up operation failed: {op.error}")
    run.flushes = []
    drive.log(f"bench: set-up: warm-up in {time.time() - t:.3f} s")
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
    run.setup_s = time.time() - t_start
    compiles.phase = "window"
    run.ops, run.window_s, rounds = drive.window(
        entry, pool, traffic["callers"], args.seconds)
    compiles.phase = "after"
    if args.trace:
        jax.profiler.stop_trace()
    # the fullest device's peak
    memory_peak = max((p for p in ((d.memory_stats() or {}).get(
        "peak_bytes_in_use") for d in devs) if p is not None), default=None)
    entry.close()
    drive.log(f"bench: {len(run.done)} of {len(run.ops)} operations "
              f"completed in {len(rounds)} rounds; window {run.window_s!r} "
              f"s; set-up {run.setup_s!r} s")
    drive.log(f"bench: round seconds {[round(t, 3) for t in rounds]}")
    if run.flushes:
        drive.log("bench: flushes (requests, reason): "
                  f"{[(f.n_requests, f.reason) for f in run.flushes]}")
    for engine, backend, source in sorted(run.plans, key=str):
        drive.log(f"bench: plan engine={engine} backend={backend} "
                  f"source={source}")
    drive.log(f"bench: set-up: {compiles.summary('setup')}")
    drive.log(f"bench: programs traced in the window: "
              f"{compiles.count('window', 'traced')}, compiled: "
              f"{compiles.count('window', 'compiled')}")
    for op in run.ops:
        if op.error:
            drive.warn(f"bench: operation on pool entry {op.pool_index} "
                       f"failed: {op.error}")
    # the answers to the host, the program's device state freed
    outputs = [(op.pool_index, jax.device_get(
        (op.output.indptr, op.output.indices, op.output.data)))
        for op in run.done]
    for op in run.ops:
        op.output = None
    pool.release()
    if args.trace:
        read_trace(run, find_xplane(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
    return run, pool, outputs, (d0, devs, memory_peak)


def read_trace(run, path: str) -> None:
    """The window's trace reduced twice, for the readers: the device's
    busy time and the benchmark's spans (``run.trace``), and the
    program's spans and device scopes (``run.program``)."""
    from jax.profiler import ProfileData
    import drive
    import program_trace
    import trace_reduce
    t = time.time()
    with open(path, "rb") as f:
        space = f.read()
    pd = ProfileData.from_serialized_xspace(space)
    run.trace = trace_reduce.reduce_profile(pd)
    run.program = program_trace.reduce_profile(pd,
                                               program_trace.trace_hlo(space))
    engines = program_trace.named(run.program, "repro.engine")
    drive.log(f"bench: trace read in {time.time() - t:.3f} s; device busy "
              f"seconds {run.program.device_busy_s}; {len(engines)} engine "
              f"calls of {sorted({e.stats.get('lanes') for e in engines})} "
              "lanes")


def check(run, pool, outputs) -> dict:
    """Compare every answer with the reference; leave each completed
    operation's operand and reference size on ``run.operands``, and the
    window's least work on ``run``."""
    import reference as refm
    import window_work
    rows = pool.shape[0]
    refs, readings = {}, []
    for k, (indptr, indices, data) in outputs:
        if k not in refs:
            refs[k] = refm.reference(*pool.host[k], pool.shape)
        readings.append(refm.compare(indptr, indices, data, rows, refs[k]))
    run.operands = [(*pool.host[op.pool_index][:2], refs[op.pool_index].nnz)
                    for op in run.done]
    run.least_bytes, run.least_flops = window_work.least(run)
    return refm.worst(readings)


def result_line(args, spec, run, outputs, numbers, device) -> dict:
    import drive
    import reference as refm
    d0, devs, memory_peak = device
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(outputs) and len(run.done) == len(run.ops)
              and refm.verdict(numbers),
              "attempted": len(run.ops),
              "failed": len(run.ops) - len(run.done),
              "metrics": metrics, "device": dev}
    if args.trace and run.trace.n_devices:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        mods = sorted(run.trace.modules.items(), key=lambda m: -m[1][1])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, (_, s) in mods[:TOP]],
            "idle_gaps": [[n, s] for n, s in run.program.gaps[:TOP]]}
    if args.rehearse:   # no CPU number under a metric's name
        result = {"rehearsal": True, **result, "metrics": {},
                  "metrics_read": sorted(metrics)}
    result["checks"] = {k: {"value": numbers[k], "limit": lim}
                        for k, lim in refm.LIMITS.items()}
    for k, c in result["checks"].items():
        drive.warn(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    t_start = psutil.Process().create_time()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        spec = cell_spec(args.workload)
        sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), BENCH)
                        if p not in sys.path]
        try:
            import jax  # noqa: F401
            import repro.core  # noqa: F401
        except ImportError as e:
            raise NoResult(f"cannot import the program: {e}") from None
        with tempfile.TemporaryDirectory(prefix="bench-autotune-") as d:
            # a fresh autotune cache, so no selection leaks between runs
            os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(d, "a.json")
            run, pool, outputs, device = measure(args, spec, t_start)
            numbers = check(run, pool, outputs)
            result = result_line(args, spec, run, outputs, numbers, device)
    except (NoResult, FileNotFoundError) as e:
        print(f"bench: no result: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
