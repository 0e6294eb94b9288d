"""trace_reduce on a trace recorded on a TPU v5e (``record_trace.py``:
a window of two 4096-row m133-b3-class products), and on a made-up
trace whose every number is known."""
import os
from types import SimpleNamespace as NS

import pytest

from trace_reduce import module_name, reduce_file, reduce_profile

DATA = os.path.join(os.path.dirname(__file__), "data", "product.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    return reduce_file(DATA)


def test_chip_trace_busy_and_idle(chip):
    assert chip.n_devices == 1
    assert chip.window_s == pytest.approx(0.108421893)
    assert chip.busy_s == pytest.approx(0.019388286)
    # the gaps and the busy time tile the window
    assert sum(s for _, s in chip.gaps) + chip.busy_s == \
        pytest.approx(chip.window_s)


def test_chip_trace_modules(chip):
    # 4096 rows are 8 groups of 512 rows in one bucket: 8 bucket
    # programs per product
    assert set(chip.modules) == {"jit__fused_bucket_impl",
                                 "jit_broadcast_in_dim"}
    n, sec = chip.modules["jit__fused_bucket_impl"]
    assert n == 16
    assert sec == pytest.approx(0.019384944)
    assert chip.modules["jit_broadcast_in_dim"][0] == 11


def test_chip_trace_gap_labels(chip):
    labels = {label for label, _ in chip.gaps}
    assert all(label.startswith("bench.") for label in labels)
    assert "bench.execute/np.asarray(jax.Array)" in labels
    assert chip.gaps[0][0] == "bench.execute"
    assert chip.gaps[0][1] == pytest.approx(0.013209841)
    assert sum(chip.idle_by_label.values()) == \
        pytest.approx(chip.window_s - chip.busy_s)


def test_chip_trace_products(chip):
    products = [i for i, s in enumerate(chip.spans)
                if s[0] == "bench.product"]
    assert len(products) == 2
    assert sorted(chip.last_device_end) == products
    for i in products:
        _, start, end = chip.spans[i]
        assert start < chip.last_device_end[i] < end


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _fake():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 1000),
        _ev("bench.product", 50, 800),
        _ev("bench.execute", 150, 650),
        _ev("np.asarray(jax.Array)", 550, 150),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_f(123)", 200, 100),
                                       _ev("jit_f(123)", 400, 150),
                                       _ev("jit_g(9)", 700, 50)]),
        NS(name="XLA Ops", events=[_ev("a", 200, 60), _ev("b", 250, 50),
                                   _ev("c", 400, 150), _ev("d", 700, 50)]),
    ])
    other = NS(name="/device:TPU:0 SparseCore", lines=[
        NS(name="XLA Ops", events=[_ev("x", 0, 1000)])])
    return NS(planes=[host, dev, other])


def test_made_up_trace():
    r = reduce_profile(_fake())
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(300e-9)       # 200-300, 400-550, 700-750
    assert r.modules == {"jit_f": [2, pytest.approx(250e-9)],
                         "jit_g": [1, pytest.approx(50e-9)]}
    # gaps 0-200, 300-400, 550-700 and 750-1000, each labelled by what
    # was open at its middle; longest first
    assert r.gaps == [
        ("bench.window", pytest.approx(250e-9)),
        ("bench.product", pytest.approx(200e-9)),
        ("bench.execute/np.asarray(jax.Array)", pytest.approx(150e-9)),
        ("bench.execute", pytest.approx(100e-9))]
    # the product's last device op ends at 750; its span ends at 850
    assert r.last_device_end == {0: 750.0}


def test_module_names_drop_program_ids():
    assert module_name("jit__fused_bucket_impl(16271057464435565492)") == \
        "jit__fused_bucket_impl"
    assert module_name("jit_f") == "jit_f"
