"""Each cell through a rehearsal run, and the faults that a run has to
catch: each planted under the timed path has to make ``correct`` false.

A rehearsal runs the whole of a run but the look for a chip, at a small
size on the CPU with the Pallas kernels in interpret mode.  Each run is
its own process, as the benchmark's runs are.  A cell on four chips
rehearses on four virtual CPU devices."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELLS = ["m133.a2", "m133.serve-b8", "m133.serve-b8x4"]
CHIPS = {"m133.serve-b8x4": 4}
# what a CPU trace can give of the program's per-layer metrics: its host
# spans, but no device plane, so no device time or idle
PROGRAM_METRICS = {
    "m133.a2": ["driver.prep_ms.product", "output.assemble_ms.product"],
    "m133.serve-b8": ["driver.prep_ms.serve", "output.assemble_ms.serve",
                      "serve.queue_ms"]}
PROGRAM_METRICS["m133.serve-b8x4"] = PROGRAM_METRICS["m133.serve-b8"]
LANES = {"m133.a2": 1, "m133.serve-b8": 8, "m133.serve-b8x4": 2}


def run(workload, *, fault=None, seconds=0.5, trace=0):
    code = ("import sys; sys.path[:0] = {paths!r}\n"
            "import faults\n"
            "{install}\n"
            "import run\n"
            "sys.exit(run.main({argv!r}))").format(
        paths=[os.path.join(ROOT, "src"), BENCH,
               os.path.join(BENCH, "tests")],
        install=f"faults.install({fault!r})" if fault else "",
        argv=["--workload", workload, "--seed", "2026101712",
              "--seconds", str(seconds), "--trace", str(trace),
              "--rehearse"])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if workload in CHIPS:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={CHIPS[workload]}")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_runs_each_cell(workload):
    result, p = run(workload, trace=1)
    assert result["rehearsal"] is True
    assert result["correct"] is True, p.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"] == {}   # no CPU number under a metric name
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == CHIPS.get(workload, 1)
    assert list(result)[-1] == "checks"
    assert "programs traced in the window: 0, compiled: 0" in p.stdout
    assert set(PROGRAM_METRICS[workload]) <= set(result["metrics_read"])
    # a product is one lane; a flush's 8 lanes go to each chip in turn
    assert f"engine calls of [{LANES[workload]}] lanes" in p.stdout


@pytest.mark.parametrize("fault", ["altered", "unchanged", "half",
                                   "control"])
@pytest.mark.parametrize("workload", CELLS)
def test_a_planted_fault_makes_the_run_incorrect(workload, fault):
    result, p = run(workload, fault=fault, seconds=1.0)
    assert result["correct"] is False, p.stderr[-3000:]
    assert "check value_err" in p.stderr


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "m133.a2", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "m133.a2", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
