"""Multi-device semantics (8 host devices via subprocess): shard_map MoE
vs einsum reference, sharded train step, sharding rules."""
import os
import subprocess
import sys

import pytest

# every test spawns an 8-device subprocess with its own jax init (~10 s
# each) — slow lane only
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run8(body: str) -> str:
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {repr(SRC)})
import jax, numpy as np, jax.numpy as jnp, dataclasses
from repro.configs import base as cb
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
{body}
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_zipper_moe_matches_einsum_on_mesh():
    out = _run8("""
from repro.models import moe as moe_mod
cfg = dataclasses.replace(cb.get_smoke_config("arctic_480b"),
                          moe_dispatch="zipper", num_experts=8,
                          capacity_factor=8.0)
key = jax.random.PRNGKey(0)
mesh = make_mesh((2, 4), ("data", "model"))
x = jax.random.normal(key, (4, 16, cfg.d_model), jnp.float32)
p = moe_mod.moe_init(key, cfg, jnp.float32)
y_ref, _ = moe_mod.moe_block(p, x, cfg, dispatch="einsum")
with shd.use_mesh(mesh):
    y_sm, _ = jax.jit(lambda p, x: moe_mod.moe_block(p, x, cfg,
                                                     dispatch="zipper"))(p, x)
err = float(jnp.abs(y_ref - y_sm).max())
assert err < 1e-4, err
g = None
with shd.use_mesh(mesh):
    g = jax.jit(jax.grad(lambda p, x: moe_mod.moe_block(
        p, x, cfg, dispatch="zipper")[0].sum()))(p, x)
g_ref = jax.grad(lambda p, x: moe_mod.moe_block(
    p, x, cfg, dispatch="einsum")[0].sum())(p, x)
ge = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
    lambda a, b: float(jnp.abs(a - b).max()), g, g_ref)))
assert ge < 1e-3, ge
print("MOE_MESH_OK")
""")
    assert "MOE_MESH_OK" in out


def test_sharded_train_step_matches_single_device():
    out = _run8("""
from repro.launch import steps as st
from repro.optim import adamw
cfg = cb.get_smoke_config("tinyllama_1_1b")
opt_cfg = adamw.AdamWConfig(lr=1e-3)
key = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
batch["labels"] = batch["tokens"]
# single device
state0 = st.init_train_state(cfg, opt_cfg, key)
_, m0 = jax.jit(st.make_train_step(cfg, opt_cfg))(state0, batch)
# 2x4 mesh
mesh = make_mesh((2, 4), ("data", "model"))
with shd.use_mesh(mesh):
    shapes = st.train_state_shapes(cfg, opt_cfg)
    sh = st.state_shardings(cfg, shapes)
    state1 = jax.jit(lambda k: st.init_train_state(cfg, opt_cfg, k),
                     out_shardings=sh)(key)
    _, m1 = jax.jit(st.make_train_step(cfg, opt_cfg),
                    in_shardings=(sh, None))(state1, batch)
d = abs(float(m0["loss"]) - float(m1["loss"]))
assert d < 5e-2, (float(m0["loss"]), float(m1["loss"]))
print("TRAIN_MESH_OK", float(m0["loss"]), float(m1["loss"]))
""")
    assert "TRAIN_MESH_OK" in out


def test_param_sharding_rules():
    out = _run8("""
import functools
from repro.models import model as M
cfg = cb.get_smoke_config("deepseek_v2_236b")
mesh = make_mesh((2, 4), ("data", "model"))
with shd.use_mesh(mesh):
    shapes = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    sh = shd.param_shardings(shapes, fsdp=False)
    # embed vocab -> model
    assert "model" in str(sh["embed"]["w"].spec), sh["embed"]["w"].spec
    # stacked group params lead with None
    spec = sh["g0"]["s0"]["ffn"]["experts"]["w1"].spec
    assert spec[0] is None and "model" in str(spec), spec
print("RULES_OK")
""")
    assert "RULES_OK" in out


def test_decode_seq_sharded_cache():
    """Decode with the KV-cache sequence dim sharded over the model axis
    (flash-decode partial softmax via GSPMD) matches single-device."""
    out = _run8("""
from repro.models import model as M
from repro.launch import steps as st
cfg = cb.get_smoke_config("granite_3_2b")
key = jax.random.PRNGKey(0)
params = M.init_params(cfg, key)
toks = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
cache = M.init_cache(cfg, 4, 32)
lg0, c0 = M.prefill(params, cfg, toks, cache)
d0, _ = M.decode_step(params, cfg, toks[:, :1], c0, jnp.int32(16))
mesh = make_mesh((2, 4), ("data", "model"))
with shd.use_mesh(mesh):
    cache = M.init_cache(cfg, 4, 32)
    c_sh = st.cache_shardings(jax.eval_shape(lambda: cache))
    cache = jax.device_put(cache, c_sh)
    lg1, c1 = jax.jit(lambda p, t, c: M.prefill(p, cfg, t, c))(params, toks, cache)
    d1, _ = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c, jnp.int32(16)))(params, toks[:, :1], c1)
err = float(jnp.abs(jnp.asarray(d0, jnp.float32) - jnp.asarray(d1, jnp.float32)).max())
assert err < 0.1, err
print("DECODE_MESH_OK", err)
""")
    assert "DECODE_MESH_OK" in out
