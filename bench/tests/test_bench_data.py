"""The benchmark's own data, its reference and its yardstick."""
import hashlib
import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import reference as refm
import window_work
import work
from gen import matrices

from conftest import BENCH, ROOT


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 20260917])
def test_generators_match_the_programs_at_the_time_of_copying(seed):
    from benchmarks import datasets
    want = datasets.build_published("m133-b3", seed=seed)
    cfg = _config("m133-b3")
    got = matrices.GENERATORS[cfg["generator"]["name"]](
        cfg["rows"], seed=seed, **cfg["generator"]["params"])
    n = int(np.asarray(want.indptr)[-1])
    assert np.array_equal(got[0], np.asarray(want.indptr))
    assert np.array_equal(got[1], np.asarray(want.indices)[:n])
    assert np.array_equal(got[2], np.asarray(want.data)[:n])


def test_every_seed_gives_the_same_sizes_in_another_order():
    import scipy.sparse as sps
    cfg = _config("m133-b3")
    s_indptr, s_indices, _ = matrices.structure(cfg, 3000, 0)
    works = []
    for seed in (1, 2**31 + 5):
        indptr, indices, (data,) = matrices.build(cfg, 3000, seed, 0, 1)
        assert len(indices) == len(s_indices)
        works.append(np.sort(np.diff(indptr)[indices].astype(np.int64)))
        # the pattern is P S P^T for the permutation the seed draws
        perm = np.random.default_rng([seed, 0]).permutation(3000)
        s = sps.csr_matrix((np.ones(len(s_indices)), s_indices, s_indptr),
                           shape=(3000, 3000))
        a = sps.csr_matrix((np.ones(len(indices)), indices, indptr),
                           shape=(3000, 3000))
        assert (s[perm][:, perm] != a).nnz == 0
    assert np.array_equal(works[0], works[1])


def _digest(*arrays):
    d = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        d.update(str(a.dtype).encode())
        d.update(a.tobytes())
    return d.hexdigest()


def test_m133_arrays_are_bit_identical_to_those_of_the_first_benchmark():
    cfg = _config("m133-b3")
    assert _digest(*matrices.structure(cfg, cfg["rows"], 0)) == \
        "818a33c8b51a4f172ef1e8ced35c8bd64b2925df2efd9e93e9d199b0440b9573"
    indptr, indices, values = matrices.build(cfg, cfg["rows"], 2**31 + 5, 3,
                                             2)
    assert _digest(indptr, indices, *values) == \
        "2ce70685be309302a014d974a7b32e715b31e6354b11ebdbef511567b9303929"


BAND = '''
import numpy as np


def generate(rows, seed, width):
    """A band of ``2 * width + 1`` diagonals, values from the seed."""
    r = np.repeat(np.arange(rows), 2 * width + 1)
    c = r + np.tile(np.arange(-width, width + 1), rows)
    keep = (c >= 0) & (c < rows)
    r, c = r[keep], c[keep]
    indptr = np.searchsorted(r, np.arange(rows + 1)).astype(np.int32)
    v = np.random.default_rng(seed).standard_normal(len(c))
    return indptr, c.astype(np.int32), v.astype(np.float32)
'''


def test_a_generator_file_is_found_and_built_through_the_pool(
        tmp_path, monkeypatch):
    import drive
    (tmp_path / "gen").mkdir()
    (tmp_path / "gen" / "band.py").write_text(BAND)
    monkeypatch.setattr(matrices, "GEN_DIR", str(tmp_path / "gen"))
    cfg = {"rows": 40, "structure_seed": 5,
           "generator": {"name": "band", "params": {"width": 2}}}
    traffic = {"patterns": 2, "value_sets": 2}
    pool = drive.Pool(cfg, traffic, 2**31 + 9, 40)
    assert len(pool) == 4 and pool.shape == (40, 40)
    for k in range(4):
        indptr, indices, data = pool.host[k]
        # a band permuted symmetrically: 5 a row but at the two ends
        assert sorted(np.diff(indptr)) == [3, 3, 4, 4] + [5] * 36
        assert len(data) == len(indices) == 194
        a = pool.csr(k)
        assert np.array_equal(np.asarray(a.indices), indices)
    # the value sets of one pattern share it; the patterns differ
    assert pool.host[0][1] is pool.host[2][1]
    assert not np.array_equal(pool.host[0][1], pool.host[1][1])
    monkeypatch.setattr(matrices, "GEN_DIR", str(tmp_path))
    with pytest.raises(FileNotFoundError, match="no generator 'band'"):
        matrices.structure(cfg, 40, 0)
    with pytest.raises(ValueError, match="not a module name"):
        matrices.generator("../gen/band")


def test_least_bytes_of_a_hand_counted_product():
    # A = [[1 . . 2]    A @ A: row 0 = 1*A0 + 2*A3 -> 2 + 1 = 3 partial
    #      [. . 3 .]    products, two of them in column 0; row 1 = 3*A2
    #      [. . . 4]    -> 1; row 2 = 4*A3 -> 1; row 3 = 5*A0 -> 2: 7
    #      [5 . . .]]   partial products, and C has 2 + 1 + 1 + 2 = 6
    indptr = np.array([0, 2, 3, 4, 5])
    indices = np.array([0, 3, 2, 3, 0])
    data = np.array([1, 2, 3, 4, 5], np.float32)
    products = work.partial_products(indptr, indices, indptr)
    assert products == 7
    ref = refm.reference(indptr, indices, data, (4, 4))
    assert ref.nnz == 6
    # A and B: 5 row pointers and 5 entries each; C: 5 and 6; the partial
    # products written and read once as 4 + 4 bytes
    assert work.least_bytes(4, 5, 4, 5, 4, 6, products) == \
        (20 + 40) * 2 + (20 + 48) + 2 * 8 * 7
    assert work.flops(products) == 14
    # per row: 3, 1, 1, 2; and the window's mean over its operations
    assert window_work.row_products(indptr, indices).tolist() == [3, 1, 1, 2]
    run = NS(operands=[(indptr, indices, 6), (indptr, indices, 4)])
    assert window_work.least(run) == (
        (2 * work.least_bytes(4, 5, 4, 5, 4, 6, 7) - 2 * 8) / 2, 14)
    assert window_work.least(NS(operands=[])) == (None, None)


def test_peaks_refuse_an_unknown_chip():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_least_seconds_names_the_bound():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_seconds(819e9, 1, peak)
    assert (t, bound) == (1.0, "hbm")
    t, bound = work.least_seconds(1, 197e12, peak)
    assert (t, bound) == (1.0, "flops")


def _csr(entries, n=4):
    """CSR arrays of {(row, col): value}, in order, repeats kept."""
    entries = sorted(entries, key=lambda e: e[0])
    indptr = np.searchsorted([r for r, _, _ in entries], np.arange(n + 1))
    return (indptr, np.array([c for _, c, _ in entries]),
            np.array([v for _, _, v in entries], np.float32))


def test_compare_reads_zeros_as_values_and_counts_extra_entries():
    # A = [[1 . . 1]   A @ A: row 0 = 1*A0 + 1*A3 = [1 . . 1] + [-1 . . .]
    #      [. . 3 .]   cancels at (0, 0), which scipy drops; (0, 3) = 1;
    #      [. . . 4]   row 1 = 3*A2: (1, 3) = 12; row 2 = 4*A3: (2, 0) = -4;
    #      [-1 . . .]] row 3 = -1*A0: (3, 0) = -1, (3, 3) = -1
    a = _csr([(0, 0, 1), (0, 3, 1), (1, 2, 3), (2, 3, 4), (3, 0, -1)])
    ref = refm.reference(*a, (4, 4))
    assert ref.nnz == 5 and len(ref.keys) == 6
    exact = [(0, 3, 1), (1, 3, 12), (2, 0, -4), (3, 0, -1), (3, 3, -1)]
    assert refm.compare(*_csr(exact), 4, ref) == {"extra_entries": 0,
                                                 "value_err": 0.0}
    # the cancelled entry held as an explicit 0, or as round-off: fine
    for v in (0.0, 1e-9):
        got = refm.compare(*_csr(exact + [(0, 0, v)]), 4, ref)
        assert got["extra_entries"] == 0 and got["value_err"] < 1e-8
    # an entry outside the pattern of A @ A, and an entry held twice
    assert refm.compare(*_csr(exact + [(1, 0, 1)]), 4, ref)[
        "extra_entries"] == 1
    assert refm.compare(*_csr(exact + [(1, 3, 12)]), 4, ref)[
        "extra_entries"] == 1
    # an entry left out reads as its whole value; one off by 1e-3 as 1e-3
    assert refm.compare(*_csr(exact[1:]), 4, ref)["value_err"] == 1.0
    off = [(0, 3, 1), (1, 3, 12 * (1 + 1e-3))] + exact[2:]
    assert refm.compare(*_csr(off), 4, ref)["value_err"] == \
        pytest.approx(1e-3, rel=1e-4)


def test_control_fails_the_comparison():
    import control
    for traffic in ("product_loop", "serve_closed"):
        with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
            numbers = control.readings(_config("m133-b3"), json.load(f), 3,
                                       2048)
        assert not refm.verdict(numbers)
        assert numbers["value_err"] > 1e-3


def test_benchmark_json_names_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
    cells = {w["name"] for w in bench["workloads"]}
    import run
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells


def test_each_pair_of_config_and_traffic_is_one_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)


def test_the_four_chip_mix_is_the_one_chip_mix():
    # m133.serve-b8x4 and m133.serve-b8 differ only by the mesh
    mixes = [json.load(open(os.path.join(BENCH, "traffic", t + ".json")))
             for t in ("serve_closed", "serve_closed_4chips")]
    assert mixes[0] == mixes[1]
