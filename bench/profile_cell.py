"""Profile one cell's window and keep the trace, with the program's spans
and device scopes read out.

    python3 bench/profile_cell.py --workload m133.a2 --seed 7 \\
        --seconds 51 OUT_DIR

Set-up and window as ``run.py`` makes them (same configuration, traffic,
seed, warm-up and profiler options as its ``--trace 1``).  On a TPU the
trace carries each module's HLO even with ``enable_hlo_proto`` off, so
``program_trace.py`` maps device operations to their
``jax.named_scope``.  Writes
``OUT_DIR/<workload>.xplane.pb`` and prints one JSON object: the
window's operations, the end-to-end metric as measured under the
profiler, the per-layer metrics of ``BENCHMARK.json`` read as ``run.py
--trace 1`` reads them, and ``program_trace.summary``.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import jax  # noqa: E402

import drive  # noqa: E402
import program_trace  # noqa: E402
import run as bench_run  # noqa: E402
from trace_reduce import find_xplane  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("out_dir")
    args = ap.parse_args(argv)
    spec = bench_run.cell_spec(args.workload)
    d0, devs, peak = bench_run.device_check(spec["cell"]["chips"], False)
    bench_run.enable_compile_cache()
    traffic, config = spec["traffic"], spec["config"]
    with tempfile.TemporaryDirectory(prefix="bench-autotune-") as d:
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(d, "a.json")
        run = drive.Run(peak=peak)
        pool = drive.Pool(config, traffic, args.seed, config["rows"])
        entry = drive.ENTRIES[traffic["entry"]](traffic, pool, run, "auto")
        for ks in pool.warm_rounds(traffic["callers"]):
            entry.round(ks)
        run.flushes = []
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        run.ops, run.window_s, rounds = drive.window(
            entry, pool, traffic["callers"], args.seconds)
        jax.profiler.stop_trace()
        entry.close()
    os.makedirs(args.out_dir, exist_ok=True)
    path = os.path.join(args.out_dir, args.workload + ".xplane.pb")
    shutil.copy(find_xplane(tmp), path)
    shutil.rmtree(tmp, ignore_errors=True)
    bench_run.read_trace(run, path)
    done = len(run.done)
    per_layer = {}
    for m in spec["per_layer"]:
        value = bench_run.reader(m["name"])(run)
        if value is not None:
            per_layer[m["name"]] = value
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": d0.device_kind, "attempted": len(run.ops), "done": done,
        "window_s": run.window_s, "rounds_s": rounds,
        "traced": {"product_s": run.window_s / done if done else None,
                   "req_per_s": done / run.window_s},
        "per_layer": per_layer,
        "program": program_trace.summary(run.program),
    }, default=str), flush=True)
    return 0 if done == len(run.ops) else 1


if __name__ == "__main__":
    sys.exit(main())
