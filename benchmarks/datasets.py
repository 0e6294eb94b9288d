"""Synthetic stand-ins for the paper's SuiteSparse matrices (Table III).

SuiteSparse is not available offline, so each evaluated matrix is replaced
by a generator matched on the structural features that drive SparseZipper's
behaviour: density, average per-row work, and per-16-row work variance
(Table III columns). Names keep the paper's labels with a ``syn-`` prefix.
Sizes are scaled (~2-6K rows) so the full benchmark suite runs in minutes
on one CPU core; the structural ratios, not absolute sizes, are what the
algorithms respond to.
"""
from __future__ import annotations

import numpy as np

from repro.core.formats import CSR, csr_from_coo, random_sparse

# (paper name, pattern, n_rows, density, skew) — ordered like Table III
# (descending per-16-row work variance).
SPECS = [
    ("p2p",      "powerlaw", 1024, 3.0e-3, 2.2),   # tiny work, high var
    ("wiki",     "powerlaw",  768, 1.6e-2, 1.7),   # heavy rows, high var
    ("soc",      "powerlaw", 1024, 1.0e-2, 1.8),
    ("ca-cm",    "powerlaw", 1024, 7.0e-3, 1.5),
    ("ndwww",    "powerlaw", 1536, 2.5e-3, 1.6),
    ("patents",  "uniform",  1536, 1.5e-3, 0.0),
    ("email",    "powerlaw", 1024, 6.0e-3, 1.3),
    ("scircuit", "banded",   1024, 4.0e-3, 0.0),
    ("bcsstk17", "blocked",   768, 2.5e-2, 0.0),   # dup-heavy compression
    ("usroads",  "banded",   1536, 1.5e-3, 0.0),   # work < chunk width
    ("p3d",      "banded",    768, 2.5e-2, 0.0),
    ("cage11",   "uniform",  1024, 4.0e-3, 0.0),
    ("m133-b3",  "uniform",  1536, 2.6e-3, 0.0),   # exactly-regular rows
]


# two of the paper's matrices at their published SuiteSparse sizes
# (rows, nonzeros): m133-b3 is generated exactly (4 nnz per row); soc is
# a power-law stand-in of soc-Epinions1's size with SPECS' soc skew
PUBLISHED = {"m133-b3": (200_200, 800_800), "soc": (75_879, 508_837)}


def _regular4(rows: int, seed: int) -> CSR:
    """Exactly 4 nnz per row, random columns — m133-b3's structure."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(rows), 4)
    c = rng.integers(0, rows, rows * 4)
    v = rng.standard_normal(rows * 4).astype(np.float32)
    return csr_from_coo(r, c, v, (rows, rows))


def build(name: str) -> CSR:
    for n, pattern, rows, dens, skew in SPECS:
        if n == name:
            if n == "m133-b3":
                # the paper's m133-b3 has exactly 4 nnz/row, zero variance
                return _regular4(rows, seed=7)
            return random_sparse(rows, rows, dens, seed=abs(hash(n)) % 2**31,
                                 pattern=pattern, skew=skew or 1.5)
    raise KeyError(name)


def build_published(name: str, seed: int = 0) -> CSR:
    """``name`` at its published size (see ``PUBLISHED``), from ``seed``."""
    rows, nnz = PUBLISHED[name]
    if name == "m133-b3":
        return _regular4(rows, seed)
    skew = next(s[4] for s in SPECS if s[0] == name)
    return random_sparse(rows, rows, nnz / rows ** 2, seed=seed,
                         pattern="powerlaw", skew=skew)


def names(limit=None):
    ns = [s[0] for s in SPECS]
    return ns[:limit] if limit else ns
