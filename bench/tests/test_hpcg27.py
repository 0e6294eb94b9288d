"""HPCG's 27-point operator (``gen/hpcg27.py``) and its cell
``hpcg27.a2``: the generator against HPCG's closed forms, and the cell
through a rehearsal run, sound and with faults planted."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sps

import window_work
from gen import hpcg27, matrices

from conftest import BENCH, ROOT

GRIDS = [(5, 5, 5), (8, 6, 5), (16, 8, 8), (32, 32, 32)]


def _grid(nx, ny, nz):
    return hpcg27.generate(nx * ny * nz, None, nx, ny, nz)


@pytest.mark.parametrize("nx,ny,nz", GRIDS)
def test_sizes_are_hpcgs(nx, ny, nz):
    indptr, indices, data = _grid(nx, ny, nz)
    assert indptr[-1] == len(indices) == (3 * nx - 2) * (3 * ny - 2) * (
        3 * nz - 2)
    products = window_work.row_products(indptr, indices)
    assert products.sum() == (9 * nx - 10) * (9 * ny - 10) * (9 * nz - 10)
    assert products.max() == 729


@pytest.mark.parametrize("nx,ny,nz", GRIDS[:3])
def test_values_pattern_and_order_are_generate_problems(nx, ny, nz):
    indptr, indices, data = _grid(nx, ny, nz)
    n = nx * ny * nz
    a = sps.csr_matrix((data, indices, indptr), shape=(n, n))
    assert a.has_sorted_indices
    assert (a.diagonal() == 26.0).all()
    off = a - sps.diags(a.diagonal())
    off.eliminate_zeros()
    assert (off.data == -1.0).all() and off.nnz == a.nnz - n
    assert (a != a.T).nnz == 0
    # the row of (ix, iy, iz) holds each in-grid neighbour, here an
    # interior point and a corner
    for ix, iy, iz in [(1, 1, 1), (0, 0, 0)]:
        row = iz * nx * ny + iy * nx + ix
        want = sorted((iz + sz) * nx * ny + (iy + sy) * nx + ix + sx
                      for sz in (-1, 0, 1) for sy in (-1, 0, 1)
                      for sx in (-1, 0, 1)
                      if 0 <= ix + sx < nx and 0 <= iy + sy < ny
                      and 0 <= iz + sz < nz)
        assert indices[indptr[row]:indptr[row + 1]].tolist() == want


def test_rehearsal_grid():
    assert hpcg27.rehearsal_grid(1024) == (16, 8, 8)
    assert hpcg27.rehearsal_grid(32768) == (32, 32, 32)
    indptr, _, _ = hpcg27.generate(1024, None, 32, 32, 32)
    assert len(indptr) == 1025
    for rows in (1000, 256, 0):
        with pytest.raises(ValueError):
            hpcg27.rehearsal_grid(rows)


def test_the_configuration_names_this_generator():
    with open(os.path.join(BENCH, "configs", "hpcg27-32.json")) as f:
        cfg = json.load(f)
    indptr, indices, _ = matrices.structure(cfg, cfg["rows"], 0)
    assert len(indptr) - 1 == cfg["rows"] == cfg["cols"]
    assert len(indices) == cfg["nnz"]


def _rehearse(fault=None, trace=0):
    code = ("import sys; sys.path[:0] = {paths!r}\n"
            "import faults\n"
            "{install}\n"
            "import run\n"
            "sys.exit(run.main({argv!r}))").format(
        paths=[os.path.join(ROOT, "src"), BENCH,
               os.path.join(BENCH, "tests")],
        install=f"faults.install({fault!r})" if fault else "",
        argv=["--workload", "hpcg27.a2", "--seed", "2147520301",
              "--seconds", "1.0", "--trace", str(trace), "--rehearse"])
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


def test_rehearsal_runs_esc_and_reads_its_spans():
    result, p = _rehearse(trace=1)
    assert result["correct"] is True, p.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"] == {}
    assert "bench: plan engine=esc" in p.stdout
    assert "programs traced in the window: 0, compiled: 0" in p.stdout
    assert {"esc.fetch_ms", "esc.fetch_mb", "esc.assemble_ms",
            "plan.ms"} <= set(result["metrics_read"])


@pytest.mark.parametrize("fault", ["altered", "half", "control"])
def test_a_planted_fault_makes_the_run_incorrect(fault):
    result, p = _rehearse(fault=fault)
    assert result["correct"] is False, p.stderr[-3000:]
