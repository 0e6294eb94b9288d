"""The benchmark's own matrix generators, on the host in NumPy.

Copies of the repository's generator for the paper's Table III
matrix m133-b3 at its published size (``datasets._regular4`` and
``formats.csr_from_coo``),
kept here so that no change to the program can change the benchmark's
data.  They return plain ``(indptr, indices, data)`` arrays; the program
receives them only through its public ``CSR`` constructor.

A cell's matrices are the same for every run seed up to the order of
rows and columns: each pattern is generated once from the configuration's
``structure_seed``, and a run's seed draws a symmetric permutation of it
(``P A P^T``, whose square is ``P A^2 P^T``) and the values.  So every
seed gives the program the same sizes, the same work per row and the
same compiled shapes, in another order and with other numbers.

A configuration names its generator (``generator.name``): ``regular``
lives here; any other name is the file ``bench/gen/<name>.py``, whose
``generate(rows, seed, **params)`` returns ``(indptr, indices, data)``
as ``regular`` does.  So a new class of matrix comes with a file of its
own and no edit here.
"""
from __future__ import annotations

import importlib.util
import os
import re

import numpy as np

GEN_DIR = os.path.dirname(os.path.abspath(__file__))


def coo_to_csr(rows, cols, vals, shape):
    """COO to CSR arrays, duplicates summed, columns sorted within rows."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    key = rows * shape[1] + cols
    order = np.argsort(key, kind="stable")
    key, vals = key[order], vals[order]
    uniq, inv = np.unique(key, return_inverse=True)
    acc = np.zeros(len(uniq), vals.dtype)
    np.add.at(acc, inv, vals)
    rows = (uniq // shape[1]).astype(np.int32)
    indptr = np.zeros(shape[0] + 1, np.int32)
    np.add.at(indptr[1:], rows, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return (indptr, (uniq % shape[1]).astype(np.int32),
            acc.astype(np.float32))


def regular(rows: int, per_row: int, seed):
    """``per_row`` nonzeros in every row at random columns (m133-b3's
    structure; a repeated column within a row is summed)."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(rows), per_row)
    c = rng.integers(0, rows, rows * per_row)
    v = rng.standard_normal(rows * per_row).astype(np.float32)
    return coo_to_csr(r, c, v, (rows, rows))


GENERATORS = {"regular": regular}


def generator(name: str):
    """The generator a configuration names: one of :data:`GENERATORS`,
    else ``generate`` of ``GEN_DIR/<name>.py``."""
    if name in GENERATORS:
        return GENERATORS[name]
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"generator name {name!r} is not a module name")
    path = os.path.join(GEN_DIR, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no generator {name!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location("bench_gen_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def structure(config: dict, rows: int, pattern: int):
    """Pattern ``pattern`` of ``config``'s class with ``rows`` rows (its
    published row count, or fewer for a rehearsal)."""
    g = config["generator"]
    return generator(g["name"])(
        rows, seed=[config["structure_seed"], pattern], **g["params"])


def permuted(indptr, indices, perm):
    """The pattern of ``P A P^T``: row ``i`` is A's row ``perm[i]``, and
    a column ``j`` of A becomes the column where ``perm`` holds ``j``.
    Returns the new ``(indptr, indices)`` and, for each new entry, the
    index of the entry of A it came from."""
    rows = len(indptr) - 1
    inv = np.empty(rows, np.int64)
    inv[perm] = np.arange(rows)
    src_row = np.repeat(np.arange(rows), np.diff(indptr))
    key = inv[src_row] * rows + inv[indices]
    order = np.argsort(key, kind="stable")
    new_rows = (key[order] // rows).astype(np.int32)
    new_indptr = np.zeros(rows + 1, np.int32)
    np.add.at(new_indptr[1:], new_rows, 1)
    return (np.cumsum(new_indptr).astype(np.int32),
            (key[order] % rows).astype(np.int32), order)


def build(config: dict, rows: int, seed, pattern: int, value_sets: int):
    """Pattern ``pattern`` of a run with ``seed``, permuted by the seed,
    with ``value_sets`` float32 value arrays drawn from the seed:
    ``(indptr, indices, [data, ...])``."""
    indptr, indices, _ = structure(config, rows, pattern)
    perm = np.random.default_rng([seed, pattern]).permutation(rows)
    indptr, indices, _ = permuted(indptr, indices, perm)
    return indptr, indices, [
        np.random.default_rng([seed, pattern, v]).standard_normal(
            len(indices)).astype(np.float32) for v in range(value_sets)]
