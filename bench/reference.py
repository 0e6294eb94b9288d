"""The plain reference and the comparison that decides ``correct``.

The reference is ``scipy.sparse`` in float64 on the host: ``A @ A`` and
``|A| @ |A|``, the second being the structural pattern of the product
and, at each of its entries, the bound on the rounding of a float32
computation (the error of a sum of products is at most a small multiple
of the unit roundoff times the sum of their magnitudes).  It imports
nothing of the program.

Two numbers are compared over the answers a run checks:

- ``extra_entries``: entries an answer holds outside the structural
  pattern of ``A @ A``, or holds twice; an exact comparison, limit 0.
- ``value_err``: the largest ``|C - C_ref| / (|A| @ |A|)`` over the
  structural pattern, an entry absent from either side counting as 0.
  A product whose terms cancel to exactly 0 in float32 is dropped by
  the program, as scipy drops its own zeros: that is within rounding,
  and reads as a tiny error here rather than a pattern fault.  The limit
  lies between what sound float32 runs of the program read and what the
  bfloat16 control reads (``PERF.md``).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sps

LIMITS = {"extra_entries": 0, "value_err": 1e-4}


@dataclasses.dataclass
class Reference:
    keys: np.ndarray    # sorted int64 row * n_cols + col of |A| @ |A|
    want: np.ndarray    # float64 A @ A at keys, 0 where it cancels
    bound: np.ndarray   # float64 |A| @ |A| at keys
    nnz: int            # nonzeros of A @ A


def keys_of(indptr, indices, n_cols):
    rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                     np.diff(np.asarray(indptr, np.int64)))
    return rows * n_cols + np.asarray(indices, np.int64)


def _csr64(indptr, indices, data, shape):
    n = int(indptr[-1])
    return sps.csr_matrix((np.asarray(data[:n], np.float64),
                           np.asarray(indices[:n]), np.asarray(indptr)),
                          shape=shape)


def reference(indptr, indices, data, shape) -> Reference:
    """``A @ A`` and its rounding bound, for A given as CSR arrays."""
    a = _csr64(indptr, indices, data, shape)
    c = (a @ a).tocsr()
    c.sort_indices()
    bound = (abs(a) @ abs(a)).tocsr()
    bound.sort_indices()
    keys = keys_of(bound.indptr, bound.indices, shape[1])
    want = np.zeros(len(keys))
    want[np.searchsorted(keys, keys_of(c.indptr, c.indices, shape[1]))] = \
        c.data
    return Reference(keys, want, bound.data, c.nnz)


def compare(indptr, indices, data, n_cols, ref: Reference) -> dict:
    """``extra_entries`` and ``value_err`` of one answer (CSR arrays, any
    padding past ``indptr[-1]`` ignored) against its reference."""
    indptr = np.asarray(indptr)
    n = int(indptr[-1])
    keys = keys_of(indptr, np.asarray(indices)[:n], n_cols)
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    vals = np.asarray(data)[:n][order].astype(np.float64)
    first = np.ones(len(keys), bool)
    first[1:] = keys[1:] != keys[:-1]
    pos = np.searchsorted(ref.keys, keys)
    inside = pos < len(ref.keys)
    inside[inside] = ref.keys[pos[inside]] == keys[inside]
    got = np.zeros(len(ref.keys))
    take = inside & first
    got[pos[take]] = vals[take]
    err = np.abs(got - ref.want) / ref.bound
    return {"extra_entries": int(len(keys) - take.sum()),
            "value_err": float(err.max()) if err.size else 0.0}


def worst(readings) -> dict:
    """The largest reading of each number over several outputs."""
    out = {k: 0 for k in LIMITS}
    for r in readings:
        out["extra_entries"] += r["extra_entries"]
        out["value_err"] = max(out["value_err"], r["value_err"])
    return out


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
