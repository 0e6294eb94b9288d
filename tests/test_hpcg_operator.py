"""HPCG's 27-point operator squared through ``plan``/``execute``.

HPCG (``GenerateProblem_ref``) couples each point of an ``n^3`` grid with
its 27 neighbours inside the grid, so an interior row of ``A @ A`` has
27 * 27 = 729 partial products, folded about 5.9 into each output entry.
At ``n >= 5`` those rows take 64 chunks of R = 16 (six merge rounds of
the zip-merge tree), which is where the spz engine's merge tree does the
work; the ``dense`` rule of the engine selection sends the operator to
ESC.  Every engine is held to scipy's float64 product: the same pattern,
and each value within float32 rounding of ``|A| @ |A|``.
"""
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sps

from repro.core import dispatch as dp
from repro.core import spgemm_engines as sg
from repro.core.formats import CSR

N = 6   # a 6^3 grid: 64 interior rows of 729 products
TOL = 1e-5


def hpcg27(n: int, seed: int = 0) -> sps.csr_matrix:
    """The 27-point pattern on an ``n^3`` grid, row ``iz*n*n + iy*n + ix``,
    with float32 standard normal values (HPCG's are 26 and -1)."""
    rows, cols = [], []
    for iz in range(n):
        for iy in range(n):
            for ix in range(n):
                for sz in (-1, 0, 1):
                    for sy in (-1, 0, 1):
                        for sx in (-1, 0, 1):
                            z, y, x = iz + sz, iy + sy, ix + sx
                            if 0 <= z < n and 0 <= y < n and 0 <= x < n:
                                rows.append((iz * n + iy) * n + ix)
                                cols.append((z * n + y) * n + x)
    vals = np.random.default_rng(seed).standard_normal(len(rows))
    a = sps.csr_matrix((vals.astype(np.float32), (rows, cols)),
                       shape=(n ** 3, n ** 3))
    a.sort_indices()
    return a


@pytest.fixture(scope="module")
def operator():
    a = hpcg27(N)
    A = CSR(jnp.asarray(a.indptr, jnp.int32), jnp.asarray(a.indices, jnp.int32),
            jnp.asarray(a.data), a.shape)
    return a, A


def _check(C: CSR, a: sps.csr_matrix) -> None:
    a64 = a.astype(np.float64)
    want = (a64 @ a64).tocsr()
    bound = (abs(a64) @ abs(a64)).tocsr()
    want.sort_indices()
    bound.sort_indices()
    indptr = np.asarray(C.indptr)
    n = int(indptr[-1])
    got = sps.csr_matrix((np.asarray(C.data)[:n].astype(np.float64),
                          np.asarray(C.indices)[:n], indptr), shape=a.shape)
    got.sort_indices()
    np.testing.assert_array_equal(got.indptr, bound.indptr)
    np.testing.assert_array_equal(got.indices, bound.indices)
    np.testing.assert_array_equal(want.indptr, bound.indptr)
    np.testing.assert_array_equal(want.indices, bound.indices)
    err = np.abs(got.data - want.data) / bound.data
    assert err.max() <= TOL, err.max()


def test_operator_has_hpcg_shape(operator):
    a, A = operator
    assert a.nnz == (3 * N - 2) ** 3
    work = sg.row_work(A, A)
    assert int(work.sum()) == (9 * N - 10) ** 3
    assert int(work.max()) == 729
    assert sg._pow2_chunks(int(work.max()), 16) == 64   # 6 merge rounds


def test_auto_plan_names_esc_by_the_dense_rule(operator, tmp_path):
    _, A = operator
    p = dp.plan(A, A, cache=dp.AutotuneCache(str(tmp_path / "a.json")),
                model=False)
    assert (p.engine, p.source, p.rule) == ("esc", "heuristic", "dense")


@pytest.mark.parametrize("engine,backend", [
    ("auto", "auto"), ("esc", "auto"), ("spz", "xla"), ("spz", "pallas")])
def test_square_matches_scipy(operator, tmp_path, engine, backend):
    a, A = operator
    p = dp.plan(A, A, engine=engine, backend=backend,
                cache=dp.AutotuneCache(str(tmp_path / "a.json")), model=False)
    if engine == "spz":
        assert p.backend == backend
    _check(dp.execute(p, A, A), a)
