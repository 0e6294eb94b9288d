"""The least work of a product and the chip's peaks.

The yardstick for the kernels' roofline share reads the same work
whatever implements the product: the bytes the product itself requires,
taken from the operands and the reference output, never from a
kernel's padded shapes.  So an engine switch, a kernel rewrite or a
fused driver is judged against one unchanged count.
"""
from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def csr_bytes(rows: int, nnz: int) -> int:
    """int32 row pointers, int32 columns and float32 values."""
    return 4 * (rows + 1) + 8 * nnz


def partial_products(a_indptr, a_indices, b_indptr) -> int:
    """Products A[i, k] * B[k, j] a Gustavson product forms."""
    n = int(a_indptr[-1])
    return int(np.diff(np.asarray(b_indptr, np.int64))[
        np.asarray(a_indices[:n])].sum())


def least_bytes(a_rows, a_nnz, b_rows, b_nnz, c_rows, c_nnz,
                products) -> int:
    """Read A and B once, write C once, and write and read each partial
    product once as an int32 key and a float32 value."""
    return (csr_bytes(a_rows, a_nnz) + csr_bytes(b_rows, b_nnz)
            + csr_bytes(c_rows, c_nnz) + 2 * 8 * products)


def flops(products) -> int:
    """A multiply per partial product and at most one add."""
    return 2 * products


def peaks(device_kind: str) -> dict:
    """The published peaks of a chip; a kind not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(nbytes: int, nflops: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = nflops / peak["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
