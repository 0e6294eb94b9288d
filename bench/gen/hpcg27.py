"""HPCG's 27-point operator, as its reference code generates it.

``src/GenerateProblem_ref.cpp`` of the HPCG benchmark
(hpcg-benchmark.org): on an ``nx x ny x nz`` grid, the row of point
``(ix, iy, iz)`` is ``iz*nx*ny + iy*nx + ix``, and it holds one entry
for each of the 27 points ``(ix+sx, iy+sy, iz+sz)``, ``sx, sy, sz`` in
``-1, 0, 1``, that lies inside the grid: 26.0 on the diagonal, -1.0
elsewhere.  So an interior row has 27 entries, and its row of ``A @ A``
27 * 27 = 729 partial products.

It imports nothing of the program, so no change to the program can
change the benchmark's data.  The pattern is deterministic: ``seed`` is
unused, and a run's permutation and values come from
``matrices.build`` as for every configuration.
"""
from __future__ import annotations

import numpy as np

MIN_SIDE = 5   # a side of 5 holds rows with all 27 neighbours' 27


def rehearsal_grid(rows: int) -> tuple[int, int, int]:
    """``(nx, ny, nz)`` of ``rows`` points as near a cube as powers of
    two allow, the longest side first (1,024 -> 16 x 8 x 8), every side
    at least :data:`MIN_SIDE`."""
    k = rows.bit_length() - 1
    if rows < 1 or rows != 1 << k:
        raise ValueError(f"{rows} rows make no grid of powers of two")
    nz, ny = k // 3, (k + 1) // 3
    nx = k - ny - nz
    sides = (1 << nx, 1 << ny, 1 << nz)
    if min(sides) < MIN_SIDE:
        raise ValueError(f"{rows} rows give the grid {sides}, a side under "
                         f"{MIN_SIDE}")
    return sides


def generate(rows: int, seed, nx: int, ny: int, nz: int):
    """The operator on the ``nx x ny x nz`` grid, or, where ``rows`` is
    not ``nx * ny * nz`` (a rehearsal), on :func:`rehearsal_grid`'s:
    CSR ``(indptr, indices, data)``, columns sorted within rows."""
    if rows != nx * ny * nz:
        nx, ny, nz = rehearsal_grid(rows)
    iz, iy, ix = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    iz, iy, ix = (a.reshape(-1, 1) for a in (iz, iy, ix))
    # the 27 offsets in GenerateProblem_ref's loop order (sz, sy, sx),
    # which is also the order of their columns within a row
    sz, sy, sx = (a.reshape(1, -1) for a in np.meshgrid(
        (-1, 0, 1), (-1, 0, 1), (-1, 0, 1), indexing="ij"))
    inside = ((0 <= iz + sz) & (iz + sz < nz) & (0 <= iy + sy)
              & (iy + sy < ny) & (0 <= ix + sx) & (ix + sx < nx))
    row = iz * nx * ny + iy * nx + ix
    cols = row + sz * nx * ny + sy * nx + sx
    indptr = np.zeros(rows + 1, np.int32)
    indptr[1:] = np.cumsum(inside.sum(axis=1))
    indices = cols[inside].astype(np.int32)
    data = np.where(cols == row, 26.0, -1.0)[inside].astype(np.float32)
    return indptr, indices, data
