"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py OUT_DIR

On a TPU: a warm product, then a window of two products of an
m133-b3-class matrix of 4096 rows through ``plan``/``execute``, inside
the benchmark's spans, with the profiler on.  Writes
``OUT_DIR/product.xplane.pb`` and prints the trace's planes and lines
and what ``trace_reduce`` reads from it.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import jax  # noqa: E402

import drive  # noqa: E402
from trace_reduce import find_xplane, reduce_file  # noqa: E402

CONFIG = {"rows": 4096, "nnz": 16384, "structure_seed": 0,
          "generator": {"name": "regular", "params": {"per_row": 4}}}
TRAFFIC = {"entry": "product", "callers": 1, "patterns": 1, "value_sets": 2}


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    run = drive.Run()
    pool = drive.Pool(CONFIG, TRAFFIC, 5, CONFIG["rows"])
    entry = drive.ProductEntry(TRAFFIC, pool, run, "auto")
    entry.round([0])
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with drive.span("bench.window"):
        for k in (0, 1):
            entry.round([k])
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "product.xplane.pb")
    shutil.copy(find_xplane(tmp), path)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    r = reduce_file(path)
    print("reduction", r.window_s, r.busy_s, r.n_devices)
    print("modules", r.modules)
    print("gaps", r.gaps[:10])
    print("spans", r.spans)
    print("last_device_end", r.last_device_end)
    print("plans", run.plans)


if __name__ == "__main__":
    main(sys.argv[1])
