"""End-to-end behaviour: real training runs, resume-equivalence, serving."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import base as cb
from repro.launch.train import train
from repro.optim import adamw
from repro.runtime.fault import FaultConfig, Preempted

# real multi-step training runs + serving loops: seconds to tens of seconds
# each — CI runs these in the non-blocking slow lane, not the tier-1 gate
pytestmark = pytest.mark.slow


def _run(arch, tmp_path, steps=12, preempt_hook=None, ckpt_every=4,
         lr=1e-3):
    cfg = cb.get_smoke_config(arch)
    opt_cfg = adamw.AdamWConfig(lr=lr, warmup_steps=2, decay_steps=steps)
    fcfg = FaultConfig(ckpt_dir=str(tmp_path), ckpt_every=ckpt_every,
                       async_save=False)
    # seed pinned explicitly: the loss-decrease assertions below are
    # margin tests, and the slow lane must be deterministic
    return train(cfg, opt_cfg, fcfg, num_steps=steps, global_batch=4,
                 seq_len=32, preempt_hook=preempt_hook, log_every=1000,
                 seed=0)


def test_train_loss_decreases(tmp_path):
    # 25 steps at lr=1e-3 was borderline on CPU (drop ~= the 0.1 margin);
    # 40 steps at lr=3e-3 drops ~0.32 on the pinned seed — 3x the margin
    _, hist = _run("tinyllama_1_1b", tmp_path, steps=40, lr=3e-3)
    losses = [h["loss"] for h in hist["steps"]]
    assert losses[-1] < losses[0] - 0.1, (losses[0], losses[-1])


def test_train_moe_loss_decreases(tmp_path):
    cfg = cb.get_smoke_config("arctic_480b")
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=3, decay_steps=40)
    fcfg = FaultConfig(ckpt_dir=str(tmp_path), ckpt_every=40,
                       async_save=False)
    _, hist = train(cfg, opt_cfg, fcfg, num_steps=40, global_batch=4,
                    seq_len=32, log_every=1000)
    losses = [h["loss"] for h in hist["steps"]]
    assert losses[-1] < losses[0] - 0.02, (losses[0], losses[-1])


def test_preemption_mid_run_resumes_and_finishes(tmp_path):
    fired = {"done": False}

    def preempt(step):
        if step == 9 and not fired["done"]:
            fired["done"] = True
            raise Preempted("sim")

    state, hist = _run("qwen1_5_0_5b", tmp_path, steps=12,
                       preempt_hook=preempt)
    assert hist["restarts"] == 1
    assert int(state["opt"]["step"]) == 12


def test_resume_bitwise_equivalence(tmp_path):
    """Train 8; vs train 4 -> kill -> resume to 8: identical params.

    Holds because the data pipeline is deterministic in (seed, step) and the
    checkpoint captures the full optimizer state."""
    a, _ = _run("granite_3_2b", tmp_path / "a", steps=8, ckpt_every=8)

    fired = {"done": False}

    def preempt(step):
        if step == 6 and not fired["done"]:
            fired["done"] = True
            raise Preempted("sim")

    b, _ = _run("granite_3_2b", tmp_path / "b", steps=8, ckpt_every=4,
                preempt_hook=preempt)
    fa = jax.tree_util.tree_leaves(a["params"])
    fb = jax.tree_util.tree_leaves(b["params"])
    for la, lb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_elastic_reshard_subprocess(tmp_path):
    """Save on a 1-device mesh, restore on an 8-device (2,4) mesh."""
    from repro.checkpoint import ckpt
    from repro.models import model as M
    cfg = cb.get_smoke_config("tinyllama_1_1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path), 1, params)
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import sys
sys.path.insert(0, {repr(os.path.join(os.path.dirname(__file__), '..', 'src'))})
from repro.checkpoint import ckpt
from repro.configs import base as cb
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import model as M
import functools
cfg = cb.get_smoke_config("tinyllama_1_1b")
mesh = make_mesh((2, 4), ("data", "model"))
with shd.use_mesh(mesh):
    shapes = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    sh = shd.param_shardings(shapes, False)
    params = ckpt.restore({repr(str(tmp_path))}, shapes, shardings=sh)
    lg, _, _ = jax.jit(lambda p, t: M.forward(p, cfg, t))(params,
        jax.numpy.zeros((2, 16), jax.numpy.int32))
    assert np.isfinite(np.asarray(lg, np.float32)).all()
    srt = params["embed"]["w"].sharding
    assert len(srt.device_set) == 8, srt
print("RESHARD_OK")
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300)
    assert "RESHARD_OK" in r.stdout, r.stdout + r.stderr


def test_serving_engine_greedy_deterministic():
    from repro.serving.engine import Engine, Request
    from repro.models import model as M
    cfg = cb.get_smoke_config("qwen1_5_0_5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, max_seq=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
               for _ in range(2)]
    r1 = eng.generate([Request(p.copy(), 8) for p in prompts])
    r2 = eng.generate([Request(p.copy(), 8) for p in prompts])
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.out, b.out)


def test_zipper_topk_matches_numpy():
    from repro.serving.sampler import zipper_topk
    rng = np.random.default_rng(1)
    shards = [rng.standard_normal(64).astype(np.float32) for _ in range(4)]
    vals, ids = zipper_topk(shards, k=8)
    full = np.concatenate(shards)
    want = np.sort(full)[::-1][:8]
    np.testing.assert_allclose(np.sort(vals)[::-1], want, rtol=1e-5)
