"""The work of the window's operations, counted from their operands.

``run.py``'s ``check`` leaves on the run, for each completed operation,
the operand's host ``(indptr, indices)`` and the reference product's
``nnz`` (``Run.operands``).  What is counted here comes from those alone,
never from the program's counters or its padded shapes, so a reader of a
mechanism's roofline share counts that mechanism's least work from the
data, as ``work.py`` counts the product's.
"""
from __future__ import annotations

import numpy as np

import work


def row_products(indptr, indices) -> np.ndarray:
    """Partial products of each row of ``A @ A`` (int64): what a row's
    sort and merge have to handle."""
    indptr = np.asarray(indptr, np.int64)
    lens = np.diff(indptr)
    per_entry = np.concatenate(
        [[0], np.cumsum(lens[np.asarray(indices[:indptr[-1]])])])
    return per_entry[indptr[1:]] - per_entry[indptr[:-1]]


def least(run) -> tuple:
    """The mean least bytes and operations of the window's ``A @ A``
    products (``work.least_bytes``, ``work.flops``), or ``(None,
    None)`` for a window with none."""
    nbytes, nflops, products = [], [], {}
    for indptr, indices, c_nnz in run.operands:
        if id(indices) not in products:
            products[id(indices)] = work.partial_products(indptr, indices,
                                                          indptr)
        p = products[id(indices)]
        rows, nnz = len(indptr) - 1, len(indices)
        nbytes.append(work.least_bytes(rows, nnz, rows, nnz, rows, c_nnz, p))
        nflops.append(work.flops(p))
    if not nbytes:
        return None, None
    return sum(nbytes) / len(nbytes), sum(nflops) / len(nflops)
