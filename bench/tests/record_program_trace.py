"""Record the small chip trace that ``test_program_trace.py`` reads.

    python3 bench/tests/record_program_trace.py OUT_DIR

On a TPU, after a warm round of each: inside one ``bench.window``, two
products of an m133-b3-class matrix of 4096 rows through
``plan``/``execute``, then one round of two requests through an
``SpGemmService`` of two lanes (one flush), with the profiler on and
the modules' HLO protos recorded (``enable_hlo_proto``), so that each
device operation can be mapped to its ``jax.named_scope``.  Writes
``OUT_DIR/program.xplane.pb`` and prints the trace's planes and lines
and what ``program_trace`` reads from it.
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
import program_trace  # noqa: E402
from trace_reduce import find_xplane  # noqa: E402

CONFIG = {"rows": 4096, "nnz": 16384, "structure_seed": 0,
          "generator": {"name": "regular", "params": {"per_row": 4}}}
PRODUCT = {"entry": "product", "callers": 1, "patterns": 1, "value_sets": 2}
SERVICE = {"entry": "service", "callers": 2, "patterns": 2, "value_sets": 1,
           "max_batch": 2, "flush_timeout": 0.02}


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_program_trace: no TPU")
    run = drive.Run()
    pools = {name: drive.Pool(CONFIG, traffic, 5, CONFIG["rows"])
             for name, traffic in (("product", PRODUCT),
                                   ("service", SERVICE))}
    product = drive.ProductEntry(PRODUCT, pools["product"], run, "auto")
    service = drive.ServiceEntry(SERVICE, pools["service"], run, "auto")
    product.round([0])
    service.round([0, 1])
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with drive.span("bench.window"):
        for k in (0, 1):
            product.round([k])
        ops = service.round([0, 1])
    jax.profiler.stop_trace()
    service.close()
    assert all(op.error is None for op in ops), [op.error for op in ops]
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "program.xplane.pb")
    shutil.copy(find_xplane(tmp), path)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs),
                  [(e.name[:80], dict(e.stats)) for e in evs[:2]])
    r = program_trace.reduce_file(path)
    print("program spans", len(r.program_spans),
          sorted({s.name for s in r.program_spans}))
    print("scopes", r.scope_s)
    print("gaps", r.gaps[:10])
    print("metrics", program_trace.metrics(r, "product"),
          program_trace.metrics(r, "flush"))
    print("plans", run.plans)


if __name__ == "__main__":
    main(sys.argv[1])
