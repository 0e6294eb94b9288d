"""program_trace on a trace recorded on a TPU v5e
(``record_program_trace.py``: two 4096-row m133-b3-class products and
one two-lane service flush, with the program's spans and the modules'
HLO), on a made-up trace whose every number is known, and the existing
readers on the first committed trace, whose values must not move."""
import json
import os
from types import SimpleNamespace as NS

import pytest

import drive
import program_trace as pt
import run as bench_run
import trace_reduce
import work

from conftest import ROOT

DATA = os.path.join(os.path.dirname(__file__), "data")
PROGRAM = os.path.join(DATA, "program.xplane.pb")
PRODUCT = os.path.join(DATA, "product.xplane.pb")
SPANS = {"repro.engine", "repro.spz.prep", "repro.spz.groups",
         "repro.spz.group", "repro.spz.launch", "repro.spz.fetch",
         "repro.spz.unpack", "repro.spz.assemble", "repro.serve.submit",
         "repro.serve.flush", "repro.serve.batch", "repro.serve.plan",
         "repro.serve.check", "repro.shard.assemble"}
# the readers of the program's spans and scopes (run.program)
PROGRAM_READERS = {"driver.prep_ms", "driver.idle_ms", "output.assemble_ms",
               "device.expand_ms", "device.sort_merge_ms", "serve.queue_ms"}


@pytest.fixture(scope="module")
def chip():
    return pt.reduce_file(PROGRAM)


def _inside(child, parent):
    return (child.thread == parent.thread and parent.start <= child.start
            and child.end <= parent.end)


def test_chip_program_spans_and_their_stats(chip):
    assert {s.name for s in chip.program_spans} == SPANS
    engines = pt.named(chip, "repro.engine")
    assert [e.stats["lanes"] for e in engines] == [1, 1, 2]
    assert {e.stats["engine"] for e in engines} == {"spz"}
    # 4096 rows a lane in groups of 512 rows
    preps = pt.named(chip, "repro.spz.prep")
    assert [p.stats["rows"] for p in preps] == [4096, 4096, 8192]
    groups = pt.named(chip, "repro.spz.groups")
    assert [g.stats["groups"] for g in groups] == [8, 8, 16]
    for e, p, g in zip(engines, preps, groups):
        assert _inside(p, e) and _inside(g, e) and p.end <= g.start
    group = pt.named(chip, "repro.spz.group")
    assert len(group) == 32
    assert all(any(_inside(x, g) for g in groups) for x in group)
    for name in ("repro.spz.launch", "repro.spz.fetch", "repro.spz.unpack"):
        buckets = pt.named(chip, name)
        assert len(buckets) == sum(x.stats["buckets"] for x in group)
        assert all(any(_inside(b, x) for x in group) for b in buckets)
        assert all(b.stats["used"] <= b.stats["streams"] for b in buckets)
    assert sum(x.stats["products"] for x in group) == \
        sum(p.stats["products"] for p in preps)
    submits = pt.named(chip, "repro.serve.submit")
    flush, = pt.named(chip, "repro.serve.flush")
    assert flush.stats["requests"].split() == \
        [str(s.stats["request"]) for s in submits]
    assert flush.stats["reason"] == "full"
    assert _inside(flush, submits[-1])


def test_chip_spans_cover_each_product_and_flush(chip):
    execs = [s for s in chip.bench_spans if s.name == "bench.execute"]
    assert len(execs) == 2
    assert min(pt.coverage(chip, execs, pt.PRODUCT_PHASES)) >= 0.95
    flushes = pt.named(chip, "repro.serve.flush")
    assert min(pt.coverage(chip, flushes, pt.FLUSH_PHASES)) >= 0.95


def test_chip_gap_labels(chip):
    old = trace_reduce.reduce_file(PROGRAM)
    # the same gaps as trace_reduce finds, labelled more finely
    assert [s for _, s in chip.gaps] == [s for _, s in old.gaps]
    for (label, _), (old_label, _) in zip(chip.gaps, old.gaps):
        assert label.split("/")[0] == old_label.split("/")[0]
    assert chip.gaps[0][0] == "bench.submit/repro.spz.assemble"
    assert chip.gaps[1][0] == "bench.execute/repro.spz.assemble"
    # inside the program every gap names the program's span
    assert all("/repro." in label for label, sec in chip.gaps
               if sec >= 1e-3)
    assert "bench.submit/repro.spz.fetch/np.asarray(jax.Array)" in \
        {label for label, _ in chip.gaps}


def test_chip_scope_times(chip):
    assert chip.has_hlo
    scoped = chip.scope_s["spz.expand"] + chip.scope_s["spz.sort_merge"]
    bucket = chip.module_s[pt.BUCKET_MODULE]
    assert scoped >= 0.95 * bucket
    # the pieces are disjoint: no more than the device's busy time
    assert sum(chip.scope_s.values()) == pytest.approx(
        trace_reduce.reduce_file(PROGRAM).busy_s)
    assert chip.scope_s["spz.expand"] > 10 * chip.scope_s["spz.sort_merge"]


def test_chip_metrics_per_product_and_per_flush(chip):
    product = pt.metrics(chip, "product")
    flush = pt.metrics(chip, "flush")
    assert product["units"] == 2 and flush["units"] == 1
    for m in (product, flush):
        for key in ("driver.prep_ms", "driver.idle_ms", "output.assemble_ms",
                    "device.expand_ms", "device.sort_merge_ms"):
            assert m[key] > 0, key
    assert "serve.queue_ms" not in product
    assert flush["serve.queue_ms"] >= 0
    # the flush ran two lanes' work, a product one
    assert flush["device.expand_ms"] > product["device.expand_ms"]
    assert pt.metrics(chip) == product
    total = 1e3 * chip.scope_s["spz.expand"]
    assert 2 * product["device.expand_ms"] + flush["device.expand_ms"] == \
        pytest.approx(total, rel=0.01)


def _readers(path):
    """Every per-layer reader of BENCHMARK.json that reads ``run.trace``
    (the first benchmark's), for a run of two operations with fixed host
    numbers."""
    run = drive.Run(peak=work.peaks("TPU v5 lite"))
    run.trace = trace_reduce.reduce_file(path)
    run.ops = [drive.Op(0, plan_s=0.001), drive.Op(1, plan_s=0.002)]
    run.window_s, run.least_bytes, run.least_flops = 1.0, 1e6, 2e6
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]
                 if m["name"] not in PROGRAM_READERS
                 and m["name"].rsplit(".", 1)[0] not in PROGRAM_READERS]
    return {n: bench_run.reader(n)(run) for n in names}


def test_existing_readers_unchanged_on_the_first_trace():
    assert _readers(PRODUCT) == {
        "plan.ms": pytest.approx(1.5),
        "driver.launches": 13.5,
        "output.tail_ms": pytest.approx(12.7381595),
        "spgemm_roofline": pytest.approx(0.012591589630926246),
        "device.idle_share.product": pytest.approx(82.1177388961471),
        "serve.flush_s": None,
        "device.idle_share.serve": pytest.approx(82.1177388961471)}


def test_existing_readers_read_a_trace_with_program_spans():
    got = _readers(PROGRAM)
    assert got["driver.launches"] == 106.0
    assert got["output.tail_ms"] == pytest.approx(16.0179795)
    assert got["device.idle_share.product"] == pytest.approx(
        89.67993934997212)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_run_program_from_the_committed_trace():
    run = drive.Run()
    bench_run.read_trace(run, PROGRAM)
    assert run.trace.gaps == trace_reduce.reduce_file(PROGRAM).gaps
    want = pt.metrics(pt.reduce_file(PROGRAM))
    # the trace holds products, so each reader reads per product
    assert want["unit"] == "product"
    got = {}
    for m in _bench()["per_layer"]:
        quantity = m["name"].rsplit(".", 1)[0]
        if quantity in PROGRAM_READERS or m["name"] in PROGRAM_READERS:
            got[m["name"]] = bench_run.reader(m["name"])(run)
    assert len(got) == 11
    for name, value in got.items():
        quantity = name if name in PROGRAM_READERS else name.rsplit(".", 1)[0]
        assert value == want.get(quantity), name
    assert got["device.expand_ms.product"] > 0
    assert got["serve.queue_ms"] is None     # no flush is the unit


def test_new_readers_read_nothing_without_a_trace():
    run = drive.Run()
    run.ops = [drive.Op(0, plan_s=0.001)]
    for m in _bench()["per_layer"]:
        if m["name"] in PROGRAM_READERS or \
                m["name"].rsplit(".", 1)[0] in PROGRAM_READERS:
            assert bench_run.reader(m["name"])(run) is None, m["name"]


def test_traced_result_line_of_the_serve_cell():
    """``result_line`` under ``--trace 1`` on the committed trace: the
    new per-layer metrics, and idle gaps labelled by the program."""
    run = drive.Run(peak=work.peaks("TPU v5 lite"))
    bench_run.read_trace(run, PROGRAM)
    run.ops = [drive.Op(0), drive.Op(1)]
    run.flushes = [NS(wall_s=1.0)]
    chip0 = NS(platform="tpu", device_kind="TPU v5 lite")
    spec = bench_run.cell_spec("m133.serve-b8x4")
    args = NS(trace=1, rehearse=False)
    line = bench_run.result_line(args, spec, run, [None], {
        "extra_entries": 0, "value_err": 0.0}, (chip0, [chip0] * 4, 7))
    assert line["correct"] is True and line["device"]["count"] == 4
    for name in ("driver.prep_ms.serve", "driver.idle_ms.serve",
                 "output.assemble_ms.serve", "device.expand_ms.serve",
                 "device.sort_merge_ms.serve"):
        assert line["metrics"][name]["value"] > 0, name
        assert line["metrics"][name]["unit"] == "ms"
    assert line["breakdown"]["idle_gaps"] == [
        [label, sec] for label, sec in run.program.gaps[:bench_run.TOP]]
    assert line["breakdown"]["idle_gaps"][0][0] == \
        "bench.submit/repro.spz.assemble"


def test_first_trace_has_no_program_spans():
    r = pt.reduce_file(PRODUCT)
    # a chip trace carries the modules' HLO; before the named scopes no
    # operation has a scope
    assert r.program_spans == [] and r.has_hlo and set(r.scope_s) == {""}
    assert r.gaps == trace_reduce.reduce_file(PRODUCT).gaps
    assert pt.metrics(r) == {"unit": "product", "units": 2}


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=stats)


def _fake():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 1000),
        _ev("bench.submit", 10, 980),
        _ev("repro.serve.submit", 20, 10, request=0),
        _ev("repro.serve.submit", 40, 940, request=1),
        _ev("repro.serve.flush", 50, 920, requests="0 1"),
        _ev("repro.engine", 100, 850, engine="spz", backend="pallas",
            lanes=2),
        _ev("repro.spz.prep", 110, 90, rows=8, products=32),
        _ev("repro.spz.groups", 200, 400, groups=1),
        _ev("np.asarray(jax.Array)", 500, 60),
        _ev("repro.spz.assemble", 600, 300, nnz_out=20),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit__fused_bucket_impl(7)",
                                           250, 200)]),
        NS(name="XLA Ops", events=[
            _ev("%while.1 = s32[] while(...)", 250, 100),
            _ev("%fusion.2 = s32[8] fusion(...)", 260, 30),
            _ev("%copy.3 = s32[8] copy(...)", 360, 10),
            _ev("%custom-call.4 = s32[8] custom-call(...)", 400, 50)]),
    ])
    return NS(planes=[host, dev])


HLO = {7: {"while.1": "spz.expand", "fusion.2": "spz.expand",
           "copy.3": "", "custom-call.4": "spz.sort_merge"}}


def test_made_up_trace():
    r = pt.reduce_profile(_fake(), HLO)
    # busy 250-350, 360-370, 400-450; gaps 0-250, 350-360, 370-400,
    # 450-1000, labelled by what was open at their middles
    assert r.gaps == [
        ("bench.submit/repro.spz.assemble", pytest.approx(550e-9)),
        ("bench.submit/repro.spz.prep", pytest.approx(250e-9)),
        ("bench.submit/repro.spz.groups", pytest.approx(30e-9)),
        ("bench.submit/repro.spz.groups", pytest.approx(10e-9))]
    assert r.scope_s == {"spz.expand": pytest.approx(100e-9),
                         "": pytest.approx(10e-9),
                         "spz.sort_merge": pytest.approx(50e-9)}
    m = pt.metrics(r)
    assert m["unit"] == "flush" and m["units"] == 1
    assert m["driver.prep_ms"] == pytest.approx(90e-6)
    assert m["output.assemble_ms"] == pytest.approx(300e-6)
    # idle inside 200-600: 200-250, 350-360, 370-400, 450-600
    assert m["driver.idle_ms"] == pytest.approx(240e-6)
    # request 0 waited 50 - 20, request 1 50 - 40
    assert m["serve.queue_ms"] == pytest.approx(20e-6)
    assert m["device.expand_ms"] == pytest.approx(100e-6)
    assert m["device.sort_merge_ms"] == pytest.approx(50e-6)


def test_two_devices_add_their_scope_times():
    """A second device running the same operations 20 ns later: each
    scope's time doubles, the device is idle where neither is busy, and
    each device's busy time is its own."""
    fake = _fake()
    second = NS(name="/device:TPU:1", lines=[
        NS(name=line.name, events=[
            _ev(e.name, e.start_ns + 20, e.duration_ns)
            for e in line.events]) for line in fake.planes[1].lines])
    fake.planes.append(second)
    r = pt.reduce_profile(fake, HLO)
    one = pt.reduce_profile(_fake(), HLO)
    assert r.scope_s == {k: pytest.approx(2 * v)
                         for k, v in one.scope_s.items()}
    assert r.device_busy_s == {"/device:TPU:0": pytest.approx(160e-9),
                               "/device:TPU:1": pytest.approx(160e-9)}
    # busy 250-370, 380-390, 400-470
    assert sorted(s for _, s in r.gaps) == pytest.approx(
        [10e-9, 10e-9, 250e-9, 530e-9])
    m = pt.metrics(r)
    assert m["device.expand_ms"] == pytest.approx(200e-6)
    assert m["device.sort_merge_ms"] == pytest.approx(100e-6)
    # idle inside 200-600: 200-250, 370-380, 390-400, 470-600
    assert m["driver.idle_ms"] == pytest.approx(200e-6)


def test_runtime_event_joins_the_label():
    fake = _fake()
    fake.planes[1].lines[1].events.append(
        _ev("%fusion.9 = s32[8] fusion(...)", 580, 10))
    r = pt.reduce_profile(fake, HLO)
    # the gap 450-580 has its middle in np.asarray (500-560)
    assert r.gaps[0] == (
        "bench.submit/repro.spz.assemble", pytest.approx(410e-9))
    assert ("bench.submit/repro.spz.groups/np.asarray(jax.Array)",
            pytest.approx(130e-9)) in r.gaps


def test_scopes_from_a_compiled_module():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("spz.expand"):
            y = jnp.sort(x * 2.0)
        with jax.named_scope("spz.sort_merge"):
            z = jnp.cumsum(y) + 1.0
        return z

    exe = jax.jit(f).lower(jnp.arange(1000.0)).compile()
    proto = exe.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    scopes = pt.hlo_scopes(proto)
    found = set(scopes.values())
    assert {"spz.expand", "spz.sort_merge"} <= found
    assert any(v == "spz.expand" and "sort" in k for k, v in scopes.items())
    # the compiler's cumsum windows carry no op_name of their own: they
    # take the scope of the operations that use them
    windows = {v for k, v in scopes.items()
               if k.startswith("wrapped_reduce-window")}
    assert windows == {"spz.sort_merge"}


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """A protobuf message of ``(number, value)`` fields: an int, bytes,
    a str, or a list of ints (packed)."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
            continue
        if isinstance(value, str):
            value = value.encode()
        elif isinstance(value, list):
            value = b"".join(_varint(v) for v in value)
        out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _instr(name, opcode, ident, op_name="", operands=(), calls=()):
    return _msg((1, name), (2, opcode), (7, _msg((2, op_name))),
                (35, ident), (36, list(operands)), (38, list(calls)))


def test_scopes_of_a_loop_the_compiler_made():
    # main: a loop with no op_name whose result an expand op uses; its
    # body: a constant the compiler shared with the sort phase, and a
    # slice fusion with no op_name
    main = _msg((1, "main"), (5, 1),
                (2, _instr("while.1", "while", 10, calls=[2])),
                (2, _instr("fusion.9", "fusion", 11,
                           "jit(f)/spz.expand/gather", operands=[10])))
    body = _msg((1, "body"), (5, 2),
                (2, _instr("constant.3", "constant", 20,
                           "jit(f)/spz.sort_merge/x")),
                (2, _instr("slice_fusion.2", "fusion", 21, operands=[20])),
                (2, _instr("tuple.1", "tuple", 22, operands=[21])))
    scopes = pt.hlo_scopes(_msg((1, "m"), (3, main), (3, body)))
    assert scopes == {"while.1": "spz.expand", "fusion.9": "spz.expand",
                      "constant.3": "spz.expand",
                      "slice_fusion.2": "spz.expand",
                      "tuple.1": "spz.expand"}


def test_scope_of_reads_the_name_stack():
    assert pt.scope_of("jit(f)/spz.expand/jit(searchsorted)/while") == \
        "spz.expand"
    assert pt.scope_of("jit(f)/spz.sort_merge/jit(fused_bucket_pallas)") == \
        "spz.sort_merge"
    assert pt.scope_of("jit(f)/add") == ""
    assert pt._instruction("%fusion.39 = s32[8] fusion(%a)") == "fusion.39"
    assert pt._program_id("jit__fused_bucket_impl(2170572824493102886)") == \
        2170572824493102886
