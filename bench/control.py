"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload m133.a2 --seeds 11 12 13

The plain reference put in the program's place one precision below the
configuration's float32: every value of A rounded to bfloat16, products
and sums in float32 (what a float32 product computed at the chip's
default matrix precision gives).  For each seed it builds the cell's
operand pool as a run does, computes every pool entry's product this
way, compares each with the float64 reference exactly as a run compares
the program's answers, and prints the worst readings beside the limits.
The control has to come out as not correct.  The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np
import scipy.sparse as sps

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import reference as refm  # noqa: E402
import drive  # noqa: E402


def bf16_product(indptr, indices, data, shape):
    """``A @ A`` with A's values rounded to bfloat16, in float32; CSR
    arrays."""
    n = int(indptr[-1])
    v = np.asarray(data[:n]).astype(ml_dtypes.bfloat16).astype(np.float32)
    a = sps.csr_matrix((v, np.asarray(indices[:n]), np.asarray(indptr)),
                       shape=shape)
    c = (a @ a).tocsr()
    c.sort_indices()
    return c.indptr, c.indices, c.data


def readings(config, traffic, seed: int, rows: int) -> dict:
    shape = (rows, rows)
    return refm.worst(
        refm.compare(*bf16_product(*arrays, shape), rows,
                     refm.reference(*arrays, shape))
        for arrays in drive.host_pool(config, traffic, seed, rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    spec = run.cell_spec(args.workload)
    failed_all = True
    for seed in args.seeds:
        numbers = readings(spec["config"], spec["traffic"], seed,
                           spec["config"]["rows"])
        ok = refm.verdict(numbers)
        failed_all &= not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok,
                          "checks": {k: {"value": numbers[k], "limit": lim}
                                     for k, lim in refm.LIMITS.items()}}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
