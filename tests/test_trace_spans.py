"""Program spans (``core/trace.py``) as a profiler trace records them:
the spz driver's nesting and counts, the service's request ids, and
outputs unchanged by tracing."""
import glob
import math
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import dispatch as dp
from repro.core import spgemm_engines as sg
from repro.core import trace
from repro.core.formats import random_sparse
from repro.serving import spgemm_service as svc

R, S = 16, 128


def _record(tmp_path, fn):
    """Run ``fn`` under the profiler; return its result and the repro
    spans as dicts (name, start, end, thread, stats), by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append({"name": ev.name, "start": ev.start_ns,
                                  "end": ev.start_ns + ev.duration_ns,
                                  "thread": line.name,
                                  "stats": dict(ev.stats)})
    return out, sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(child, parent):
    return (child["thread"] == parent["thread"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


def _within(spans, name, parent):
    return [s for s in _named(spans, name) if _inside(s, parent)]


def _product(A):
    p = dp.plan(A, A, engine="spz", backend="xla", R=R, S=S)
    return dp.execute(p, A, A)


def _arrays(m):
    return tuple(np.asarray(x) for x in (m.indptr, m.indices, m.data))


@pytest.fixture(scope="module")
def powerlaw():
    return random_sparse(300, 300, 0.02, seed=3, pattern="powerlaw")


def _expected_buckets(A):
    """(streams, used, L) of every bucket the fused driver runs, per
    group of S rows, computed from the row work."""
    work = sg.row_work(A, A)
    want = []
    for g0 in range(0, A.n_rows, S):
        counts: dict = {}
        for pl in work[g0:g0 + S]:
            if pl:
                c = sg._pow2_chunks(int(pl), R)
                counts[c] = counts.get(c, 0) + 1
        for c, n in sorted(counts.items()):
            nb = max(sg.MIN_BUCKET_STREAMS, 1 << max(0, n - 1).bit_length())
            want.append((nb, n, c * R))
    return sorted(want)


def test_fused_product_spans_nest_and_count(tmp_path, powerlaw):
    A = powerlaw
    _product(A)   # compile outside the trace
    _, spans = _record(tmp_path, lambda: _product(A))
    engine, = _named(spans, trace.ENGINE)
    assert engine["stats"] == {"engine": "spz", "backend": "xla",
                               "lanes": 1}
    prep, = _within(spans, trace.SPZ_PREP, engine)
    work = sg.row_work(A, A)
    assert prep["stats"] == {"rows": A.n_rows, "products": int(work.sum())}
    groups, = _within(spans, trace.SPZ_GROUPS, engine)
    n_groups = math.ceil(A.n_rows / S)
    assert groups["stats"] == {"groups": n_groups}
    assert prep["end"] <= groups["start"]
    group_spans = _named(spans, trace.SPZ_GROUP)
    assert len(group_spans) == n_groups
    assert all(_inside(g, groups) for g in group_spans)
    assert [g["stats"]["items"] for g in group_spans] == \
        [min(S, A.n_rows - g0) for g0 in range(0, A.n_rows, S)]
    assert sum(g["stats"]["products"] for g in group_spans) == \
        int(work.sum())
    want = _expected_buckets(A)
    assert sum(g["stats"]["buckets"] for g in group_spans) == len(want)
    for name in (trace.SPZ_LAUNCH, trace.SPZ_FETCH, trace.SPZ_UNPACK):
        got = _named(spans, name)
        assert all(any(_inside(b, g) for g in group_spans) for b in got)
        assert sorted((b["stats"]["streams"], b["stats"]["used"],
                       b["stats"]["L"]) for b in got) == want
    # each bucket launches, then fetches, then unpacks
    order = [s["name"] for s in spans if s["name"] in (
        trace.SPZ_LAUNCH, trace.SPZ_FETCH, trace.SPZ_UNPACK)]
    assert order == [trace.SPZ_LAUNCH, trace.SPZ_FETCH,
                     trace.SPZ_UNPACK] * len(want)
    assemble, = _within(spans, trace.SPZ_ASSEMBLE, engine)
    assert groups["end"] <= assemble["start"]
    out = _product(A)
    assert assemble["stats"] == {"nnz_out": int(np.asarray(out.indptr)[-1])}


def test_product_bitwise_identical_traced_and_not(tmp_path, powerlaw):
    untraced = _arrays(_product(powerlaw))
    traced, _ = _record(tmp_path, lambda: _arrays(_product(powerlaw)))
    for a, b in zip(untraced, traced):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_service_round_spans_carry_request_ids(tmp_path):
    cache = dp.AutotuneCache(str(tmp_path / "autotune.json"))
    service = svc.SpGemmService(max_batch=4, flush_timeout=1e9,
                                engine="spz", cache=cache)
    mats = [random_sparse(48, 48, 0.05, seed=s) for s in range(4)]
    warm = [service.submit(A, A) for A in mats]   # compile outside the trace
    reqs, spans = _record(tmp_path / "trace",
                          lambda: [service.submit(A, A) for A in mats])
    assert all(r.done and not r.failed for r in warm + reqs)
    submits = _named(spans, trace.SERVE_SUBMIT)
    assert [s["stats"] for s in submits] == \
        [{"request": r.id} for r in reqs]
    flush, = _named(spans, trace.SERVE_FLUSH)
    assert flush["stats"]["requests"] == trace.ids(r.id for r in reqs)
    assert flush["stats"]["requests"].split() == [str(r.id) for r in reqs]
    assert flush["stats"]["reason"] == "full"
    assert flush["stats"]["bucket"] == trace.bucket(reqs[0].bucket)
    # the fourth submit fills the bucket and runs the flush inline
    assert _inside(flush, submits[-1])
    for name in (trace.SERVE_BATCH, trace.SERVE_PLAN, trace.ENGINE,
                 trace.SHARD_ASSEMBLE, trace.SERVE_CHECK):
        inner = _within(spans, name, flush)
        assert inner and all(s["stats"]["lanes"] >= 1 for s in inner), name
    engine, = _within(spans, trace.ENGINE, flush)
    assert engine["stats"]["engine"] == "spz"
    assert engine["stats"]["lanes"] == len(reqs)
    for r in reqs:
        want = sg.spgemm_scl_array(r.A, r.B)
        np.testing.assert_allclose(np.asarray(r.result.to_dense()),
                                   np.asarray(want.to_dense()),
                                   rtol=1e-5, atol=1e-5)


def test_stat_helpers_avoid_the_profilers_separators():
    assert trace.ids([3, 4, 10]) == "3 4 10"
    key = ((48, 48), (48, 48), 128, 256)
    assert trace.bucket(key) == "48x48@48x48/128/256"
    for text in (trace.ids([1, 2]), trace.bucket(key)):
        assert "," not in text and "#" not in text and "=" not in text


def test_esc_product_spans_once_per_product_and_not_for_spz(tmp_path,
                                                            powerlaw):
    A = powerlaw
    esc = dp.plan(A, A, engine="esc")
    dp.execute(esc, A, A)   # compile outside the trace
    _product(A)
    outs, spans = _record(tmp_path, lambda: [
        dp.execute(esc, A, A), _product(A), dp.execute(esc, A, A)])
    products = int(sg.row_work(A, A).sum())
    engines = _named(spans, trace.ENGINE)
    assert [e["stats"]["engine"] for e in engines] == ["esc", "spz", "esc"]
    for engine, out in zip(engines, outs):
        inner = [_within(spans, name, engine) for name in (
            trace.ESC_DEVICE, trace.ESC_FETCH, trace.ESC_ASSEMBLE)]
        if engine["stats"]["engine"] == "spz":
            assert inner == [[], [], []]
            continue
        (device,), (fetch,), (assemble,) = inner
        assert device["end"] <= fetch["start"]
        assert fetch["end"] <= assemble["start"]
        assert device["stats"] == {"products": products, "rows": A.n_rows}
        # rows, columns and values (4 bytes each), the valid mask (1) and
        # the int32 count, each of the program's static length
        assert fetch["stats"] == {"bytes": 13 * products + 4,
                                  "entries": int(np.asarray(out.indptr)[-1])}
    for name in (trace.ESC_DEVICE, trace.ESC_FETCH, trace.ESC_ASSEMBLE):
        assert len(_named(spans, name)) == 2


def test_esc_program_names_its_phases():
    shapes = [(9,), (32,), (32,), (9,), (32,), (32,)]
    args = [jax.ShapeDtypeStruct(s, np.float32 if i % 3 == 2 else np.int32)
            for i, s in enumerate(shapes)]
    text = sg._esc_core.lower(*args, cap_products=128, n_rows=8,
                              n_cols=8).compile().as_text()
    for scope in (trace.ESC_EXPAND, trace.ESC_SORT, trace.ESC_COMPRESS):
        assert f'op_name="jit(esc_core_impl)/{scope}/' in text, scope
