"""Device-resident (fused) spz driver: equality, stats, and primitives.

The fused driver must be BIT-identical to the host lock-step driver —
same engine semantics, different execution — and structure-identical to
the scl-array oracle (oracle values differ only by its float64
accumulation).  Hypothesis property tests are skipped on a bare checkout
(same guard as the rest of the suite).
"""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import dispatch as dp
from repro.core import spgemm_engines as sg
from repro.core import stream as kvstream
from repro.core.formats import (EMPTY, batch_csr, csr_from_coo,
                                random_sparse)
from repro.kernels import merge_tree, ref


def _dense(m):
    return np.asarray(m.to_dense(), np.float64)


def _csr_arrays(m):
    nnz = int(np.asarray(m.indptr)[-1])
    return (np.asarray(m.indptr), np.asarray(m.indices)[:nnz],
            np.asarray(m.data)[:nnz])


def _assert_drivers_identical(A, B, **kw):
    out_h, st_h = sg.spgemm_spz(A, B, driver="host", backend="xla", **kw)
    out_f, st_f = sg.spgemm_spz(A, B, driver="fused", backend="xla", **kw)
    for h, f in zip(_csr_arrays(out_h), _csr_arrays(out_f)):
        np.testing.assert_array_equal(h, f)
    assert (st_h.n_mssort, st_h.sort_elems, st_h.n_mszip, st_h.zip_elems) \
        == (st_f.n_mssort, st_f.sort_elems, st_f.n_mszip, st_f.zip_elems)
    return out_f, st_f


# ---------------------------------------------------------------------------
# fused driver vs host driver / oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", ["uniform", "powerlaw", "banded"])
def test_fused_bit_identical_to_host(pattern):
    A = random_sparse(96, 96, 0.03, seed=11, pattern=pattern)
    out_f, _ = _assert_drivers_identical(A, A, R=16)
    want = _dense(sg.spgemm_scl_array(A, A))
    np.testing.assert_allclose(_dense(out_f), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("R", [8, 16, 128])
def test_fused_chunk_widths(R):
    A = random_sparse(64, 64, 0.05, seed=5, pattern="powerlaw")
    out_f, st_f = _assert_drivers_identical(A, A, R=R)
    assert st_f.n_mssort > 0


def test_fused_rectangular_and_rsort():
    A = random_sparse(40, 70, 0.06, seed=1)
    B = random_sparse(70, 50, 0.06, seed=2)
    _assert_drivers_identical(A, B, R=16)
    Ask = random_sparse(128, 128, 0.04, seed=9, pattern="powerlaw")
    _assert_drivers_identical(Ask, Ask, R=16, S=16, rsort=True)


def test_fused_structure_identical_to_oracle():
    A = random_sparse(80, 80, 0.05, seed=3, pattern="powerlaw")
    oracle = sg.spgemm_scl_array(A, A)
    out, _ = sg.spgemm_spz(A, A, R=16, backend="xla", driver="fused")
    o_indptr, o_idx, _ = _csr_arrays(oracle)
    f_indptr, f_idx, _ = _csr_arrays(out)
    np.testing.assert_array_equal(o_indptr, f_indptr)
    np.testing.assert_array_equal(o_idx, f_idx)


def test_empty_inputs_both_drivers():
    """n_rows == 0 must not crash (np.concatenate([]) regression)."""
    E = csr_from_coo([], [], [], (0, 7))
    B = random_sparse(7, 5, 0.2, seed=0)
    for driver in ("host", "fused"):
        out, stats = sg.spgemm_spz(E, B, driver=driver)
        assert out.shape == (0, 5)
        assert int(np.asarray(out.indptr)[-1]) == 0
        assert stats.n_mssort == 0 and stats.n_mszip == 0


def test_zero_nnz_and_empty_rows():
    Z = csr_from_coo([], [], [], (8, 8))
    for driver in ("host", "fused"):
        out, _ = sg.spgemm_spz(Z, Z, driver=driver)
        assert int(np.asarray(out.indptr)[-1]) == 0
    # some empty rows, some populated
    A = csr_from_coo([1, 1, 5], [0, 3, 2], [1.0, 2.0, 3.0], (8, 8))
    _assert_drivers_identical(A, A, R=8)


def test_unknown_driver_raises():
    A = random_sparse(8, 8, 0.1, seed=0)
    with pytest.raises(ValueError, match="unknown spz driver"):
        sg.spgemm_spz(A, A, driver="nope")


# ---------------------------------------------------------------------------
# engine registry / dispatch integration
# ---------------------------------------------------------------------------

def test_registry_has_fused_engines():
    names = set(dp.available_engines())
    assert {"spz-fused", "spz-host"} <= names
    assert dp.get_engine("spz-fused").batchable
    assert not dp.get_engine("spz-host").measure


def test_dispatch_spz_fused_engine():
    A = random_sparse(48, 48, 0.04, seed=2)
    out, stats = dp.spgemm(A, A, engine="spz-fused", R=16, backend="xla",
                           return_stats=True)
    np.testing.assert_allclose(_dense(out), _dense(sg.spgemm_scl_array(A, A)),
                               rtol=1e-4, atol=1e-4)
    assert stats is not None and stats.n_mssort > 0


@pytest.mark.parametrize("rsort", [False, True])
def test_batched_fused_matches_host_batched(rsort):
    mats = [random_sparse(32, 32, d, seed=i)
            for i, d in enumerate((0.01, 0.06, 0.02))]
    A = batch_csr(mats, batch_cap=len(mats) + 1)
    kw = dict(R=8, S=32, rsort=rsort)
    out_f = dp.spgemm_batched(A, A, engine="spz-fused", **kw)
    out_h = dp.spgemm_batched(A, A, engine="spz-host", **kw)
    for i in range(len(mats)):
        for h, f in zip(_csr_arrays(out_h[i]), _csr_arrays(out_f[i])):
            np.testing.assert_array_equal(h, f)


# ---------------------------------------------------------------------------
# output assembly from merged stream runs
# ---------------------------------------------------------------------------

def _cancelling():
    """Row 1 of A @ A cancels to exactly 0.0 in column 0; rows 2-3 empty."""
    return csr_from_coo([0, 0, 1, 1, 2], [0, 1, 0, 1, 3],
                        [1.0, 1.0, 1.0, -1.0, 2.0], (4, 4))


_RUN_CASES = {
    "uniform": lambda: (random_sparse(96, 96, 0.03, seed=11), {}),
    # small groups of skewed rows: several buckets per group
    "powerlaw": lambda: (random_sparse(128, 128, 0.04, seed=9,
                                       pattern="powerlaw"), dict(S=32)),
    "rsort": lambda: (random_sparse(128, 128, 0.04, seed=9,
                                    pattern="powerlaw"),
                      dict(S=16, rsort=True)),
    "empty_rows": lambda: (csr_from_coo([1, 1, 5], [0, 3, 2],
                                        [1.0, 2.0, 3.0], (8, 8)), {}),
    "all_zero": lambda: (csr_from_coo([], [], [], (8, 8)), {}),
    "cancel": lambda: (_cancelling(), {}),
    "batched": lambda: (batch_csr([random_sparse(32, 32, d, seed=i)
                                   for i, d in enumerate((0.01, 0.06, 0.0,
                                                          0.02))],
                                  batch_cap=6), dict(S=32)),
}


@pytest.mark.parametrize("case", sorted(_RUN_CASES))
def test_runs_to_csrs_bit_identical_to_csr_from_coo(monkeypatch, case):
    """The sort-free build gives, lane by lane, exactly the CSR that
    ``csr_from_coo`` builds from the same fused parts: indptr, padded
    indices and data, dtypes and capacity."""
    calls = []
    real = sg._runs_to_csrs

    def spy(runs, shape, n_lanes=1):
        out = real(runs, shape, n_lanes)
        calls.append((runs, shape, n_lanes, out))
        return out

    monkeypatch.setattr(sg, "_runs_to_csrs", spy)
    A, kw = _RUN_CASES[case]()
    if case == "batched":
        got = dp._spz_batched(A, A, R=8, backend="xla", **kw)
        lane_ok = np.asarray(A.valid)
        assert [g is None for g in got] == [not v for v in lane_ok]
    else:
        got = [sg.spgemm_spz(A, A, R=8, backend="xla", driver="fused",
                             **kw)[0]]
    (runs, shape, n_lanes, out), = calls
    lanes, rows, lens, keys, vals = (
        np.concatenate([p[k] for p in runs]) if runs else np.zeros(0, dt)
        for k, dt in enumerate((np.int32, np.int32, np.int32, np.int32,
                                np.float32)))
    # the build's premise: each (lane, row) is one sorted, unique run
    assert len(set(zip(lanes.tolist(), rows.tolist()))) == len(rows)
    for k in np.split(keys, np.cumsum(lens)[:-1]):
        assert (np.diff(k) > 0).all()
    if case == "cancel":
        assert (vals == 0.0).any()
    if case == "all_zero":
        assert len(keys) == 0
    r, ln = np.repeat(rows, lens), np.repeat(lanes, lens)
    assert len(out) == n_lanes
    for i, c in enumerate(out):
        m = (ln == i) & (vals != 0.0)
        want = csr_from_coo(r[m], keys[m], vals[m], shape)
        for w, g in zip((want.indptr, want.indices, want.data),
                        (c.indptr, c.indices, c.data)):
            assert np.asarray(w).dtype == np.asarray(g).dtype
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    for g, c in zip(got, out):
        if g is not None:
            assert g is c


# ---------------------------------------------------------------------------
# device-resident primitives
# ---------------------------------------------------------------------------

def _sorted_unique_partition(rng, N, L, key_hi):
    lens = rng.integers(0, L + 1, N).astype(np.int32)
    keys = np.full((N, L), EMPTY, np.int32)
    vals = np.zeros((N, L), np.float32)
    for s in range(N):
        u = np.sort(rng.choice(key_hi, size=lens[s], replace=False))
        keys[s, :lens[s]] = u
        vals[s, :lens[s]] = rng.standard_normal(lens[s])
    return keys, vals, lens


def test_merge_partitions_equals_host_merge_round():
    """The while-loop primitive must reproduce the host merge_round
    byte-for-byte, including the mszip issue count."""
    rng = np.random.default_rng(7)
    N, L, R = 6, 32, 8
    ka, va, la = _sorted_unique_partition(rng, N, L, 3 * L)
    kb, vb, lb = _sorted_unique_partition(rng, N, L, 3 * L)
    stats = sg.SpzStats()
    hk, hv, hl = sg.merge_round((ka, va, la.astype(np.int64)),
                                 (kb, vb, lb.astype(np.int64)),
                                 R, "xla", stats)
    fk, fv, fl, cnt = kvstream.merge_partitions(ka, va, la, kb, vb, lb, R=R)
    fk, fv, fl = np.asarray(fk), np.asarray(fv), np.asarray(fl)
    np.testing.assert_array_equal(hl, fl)
    for s in range(N):
        np.testing.assert_array_equal(hk[s, :hl[s]], fk[s, :fl[s]])
        np.testing.assert_array_equal(hv[s, :hl[s]], fv[s, :fl[s]])
    assert int(cnt.n_mszip) == stats.n_mszip
    assert int(cnt.zip_elems) == stats.zip_elems


def test_merge_partitions_empty_side():
    rng = np.random.default_rng(3)
    N, L, R = 4, 16, 8
    ka, va, la = _sorted_unique_partition(rng, N, L, 2 * L)
    kb = np.full((N, L), EMPTY, np.int32)
    vb = np.zeros((N, L), np.float32)
    lb = np.zeros(N, np.int32)
    fk, fv, fl, cnt = kvstream.merge_partitions(ka, va, la, kb, vb, lb, R=R)
    np.testing.assert_array_equal(np.asarray(fl), la)
    for s in range(N):
        np.testing.assert_array_equal(np.asarray(fk)[s, :la[s]],
                                      ka[s, :la[s]])
    assert int(cnt.n_mszip) == 0 and int(cnt.zip_elems) == 0


def test_sort_chunks_linear_byte_identical_to_ref():
    rng = np.random.default_rng(0)
    for key_hi in (3, 9, 1000):  # duplicate-heavy through nearly-unique
        for _ in range(10):
            N, R = 5, 16
            lens = rng.integers(0, R + 1, N).astype(np.int32)
            keys = rng.integers(0, key_hi, (N, R)).astype(np.int32)
            vals = rng.standard_normal((N, R)).astype(np.float32)
            args = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(lens))
            for r, f in zip(ref.stream_sort_ref(*args),
                            merge_tree.sort_chunks_linear(*args)):
                np.testing.assert_array_equal(np.asarray(r), np.asarray(f))


def test_fused_sort_merge_counters_layout():
    """Stream-level fused entry returns the 6 SpzStats counters."""
    rng = np.random.default_rng(1)
    S, L, R = 4, 32, 8
    plens = rng.integers(0, L + 1, S).astype(np.int32)
    keys = np.where(np.arange(L)[None, :] < plens[:, None],
                    rng.integers(0, 50, (S, L)), EMPTY).astype(np.int32)
    vals = np.where(np.arange(L)[None, :] < plens[:, None],
                    rng.standard_normal((S, L)), 0).astype(np.float32)
    mk, mv, ml, counters = kvstream.fused_sort_merge(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(plens), R=R)
    counters = np.asarray(counters)
    assert counters.shape == (6,)
    assert counters[0] == -(-int(plens.max()) // R)  # n_mssort
    assert counters[1] == int(plens.sum())           # sort_elems
    # every stream's output is sorted unique
    mk, ml = np.asarray(mk), np.asarray(ml)
    for s in range(S):
        assert (np.diff(mk[s, :ml[s]]) > 0).all()


# ---------------------------------------------------------------------------
# dispatch feature cache
# ---------------------------------------------------------------------------

def test_feature_cache_hits_and_invalidations(monkeypatch):
    dp.clear_feature_cache()
    A = random_sparse(32, 32, 0.05, seed=4)
    calls = {"n": 0}
    real = sg.work_stats

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(sg, "work_stats", counting)
    f1 = dp.extract_features(A, A)
    f2 = dp.extract_features(A, A)
    assert calls["n"] == 1 and f1 == f2
    # a different matrix object misses
    B = random_sparse(32, 32, 0.05, seed=5)
    dp.extract_features(B, B)
    assert calls["n"] == 2
    # mutating the returned dict must not poison the cache
    f1["density"] = -1.0
    assert dp.extract_features(A, A)["density"] != -1.0
    assert calls["n"] == 2
    dp.clear_feature_cache()
    dp.extract_features(A, A)
    assert calls["n"] == 3


def test_feature_cache_bounded():
    cache = dp._OperandMemo(maxsize=4)
    for i in range(8):
        A = random_sparse(8, 8, 0.1, seed=i)
        cache.put(A, A, 16, {"i": i})
    assert len(cache._entries) == 4


# ---------------------------------------------------------------------------
# hypothesis property tests
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @st.composite
    def fused_matrix(draw):
        """Random densities, skewed rows, empty rows, duplicate-heavy
        streams — the regimes the fused driver must cover."""
        n = draw(st.integers(8, 48))
        density = draw(st.floats(0.01, 0.2))
        seed = draw(st.integers(0, 10_000))
        pattern = draw(st.sampled_from(["uniform", "powerlaw", "banded",
                                        "blocked"]))
        return random_sparse(n, n, density, seed=seed, pattern=pattern)

    @settings(max_examples=20, deadline=None)
    @given(fused_matrix())
    def test_prop_fused_equals_oracle(A):
        want = _dense(sg.spgemm_scl_array(A, A))
        got = _dense(sg.spgemm_spz(A, A, R=8, backend="xla",
                                   driver="fused")[0])
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    @settings(max_examples=10, deadline=None)
    @given(fused_matrix(), st.sampled_from([8, 16]),
           st.sampled_from([16, 64]))
    def test_prop_fused_stats_match_host(A, R, S):
        """n_mszip / zip_elems (and the whole output) must match the host
        driver on the same input and lock-step parameters."""
        _assert_drivers_identical(A, A, R=R, S=S)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 8), st.integers(0, 10_000))
    def test_prop_merge_partitions_union(N, seed):
        """Merged output == sorted union with cross-side accumulation."""
        rng = np.random.default_rng(seed)
        L, R = 16, 8
        ka, va, la = _sorted_unique_partition(rng, N, L, 24)
        kb, vb, lb = _sorted_unique_partition(rng, N, L, 24)
        fk, fv, fl, _ = kvstream.merge_partitions(ka, va, la, kb, vb, lb,
                                                  R=R)
        fk, fv, fl = np.asarray(fk), np.asarray(fv), np.asarray(fl)
        for s in range(N):
            want = {}
            for k, v in list(zip(ka[s, :la[s]], va[s, :la[s]])) + \
                    list(zip(kb[s, :lb[s]], vb[s, :lb[s]])):
                want[int(k)] = want.get(int(k), np.float32(0)) + v
            keys = sorted(want)
            assert fl[s] == len(keys)
            np.testing.assert_array_equal(fk[s, :fl[s]], keys)
            np.testing.assert_allclose(fv[s, :fl[s]],
                                       [want[k] for k in keys], rtol=1e-6,
                                       atol=1e-6)
