"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.formats import EMPTY
from repro.kernels import ops, ref
from repro.kernels.chunk_sort import chunk_sort_pallas
from repro.kernels.stream_merge import stream_merge_pallas

RNG = np.random.default_rng(42)


def _rand_chunks(S, R, key_hi, vdtype):
    lens = RNG.integers(0, R + 1, S).astype(np.int32)
    keys = RNG.integers(0, key_hi, (S, R)).astype(np.int32)
    vals = RNG.standard_normal((S, R)).astype(vdtype)
    return keys, vals, lens


def _sorted_chunks(S, R, key_hi, vdtype):
    lens = RNG.integers(0, R + 1, S).astype(np.int32)
    keys = np.full((S, R), EMPTY, np.int32)
    vals = np.zeros((S, R), vdtype)
    for s in range(S):
        u = np.sort(RNG.choice(key_hi, size=lens[s], replace=False))
        keys[s, :lens[s]] = u
        vals[s, :lens[s]] = RNG.standard_normal(lens[s]).astype(vdtype)
    return keys, vals, lens


# R >= 128 in interpret mode costs ~3 s per case — slow lane only
@pytest.mark.parametrize("R", [8, 16, 32, 64,
                               pytest.param(128, marks=pytest.mark.slow),
                               pytest.param(256, marks=pytest.mark.slow)])
@pytest.mark.parametrize("S", [1, 3, 16])
@pytest.mark.parametrize("vdtype", [np.float32, "bfloat16"])
def test_stream_sort_matches_ref(R, S, vdtype):
    vdtype = jnp.dtype(vdtype)
    keys, vals, lens = _rand_chunks(S, R, max(2, R // 2), np.float32)
    vals = vals.astype(vdtype)
    args = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(lens))
    rk, rv, rl = ref.stream_sort_ref(*args)
    pk, pv, plen = chunk_sort_pallas(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(rk))
    np.testing.assert_allclose(np.asarray(pv, np.float32),
                               np.asarray(rv, np.float32),
                               rtol=2e-2 if vdtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if vdtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_array_equal(np.asarray(plen), np.asarray(rl))


@pytest.mark.parametrize("R", [8, 16, 64,
                               pytest.param(128, marks=pytest.mark.slow)])
@pytest.mark.parametrize("S", [1, 5, 16])
def test_stream_merge_matches_ref(R, S):
    ka, va, la = _sorted_chunks(S, R, 4 * R, np.float32)
    kb, vb, lb = _sorted_chunks(S, R, 4 * R, np.float32)
    args = tuple(jnp.asarray(x) for x in (ka, va, la, kb, vb, lb))
    rres = ref.stream_merge_ref(*args)
    pres = stream_merge_pallas(*args, interpret=True)
    for i, (r, p) in enumerate(zip(rres, pres)):
        r, p = np.asarray(r), np.asarray(p)
        if r.dtype.kind == "f":
            np.testing.assert_allclose(p, r, rtol=1e-5, atol=1e-5,
                                       err_msg=f"output {i}")
        else:
            np.testing.assert_array_equal(p, r, err_msg=f"output {i}")


def test_stream_sort_empty_streams():
    keys = np.full((4, 16), EMPTY, np.int32)
    vals = np.zeros((4, 16), np.float32)
    lens = np.zeros(4, np.int32)
    k, v, l = ops.stream_sort(jnp.asarray(keys), jnp.asarray(vals),
                              jnp.asarray(lens), backend="pallas")
    assert int(np.asarray(l).sum()) == 0
    assert (np.asarray(k) == EMPTY).all()


def test_stream_merge_one_side_empty():
    ka, va, la = _sorted_chunks(3, 16, 64, np.float32)
    kb = np.full((3, 16), EMPTY, np.int32)
    vb = np.zeros((3, 16), np.float32)
    lb = np.zeros(3, np.int32)
    res = ops.stream_merge(*(jnp.asarray(x)
                             for x in (ka, va, la, kb, vb, lb)),
                           backend="pallas")
    _, _, _, _, ca, cb, ol = res
    # unmergeable: nothing advances, nothing is emitted
    assert int(np.asarray(ca).sum()) == 0
    assert int(np.asarray(cb).sum()) == 0
    assert int(np.asarray(ol).sum()) == 0


def test_merge_conservation_and_counts():
    """Value mass of consumed tuples == value mass of emitted tuples."""
    ka, va, la = _sorted_chunks(8, 32, 100, np.float32)
    kb, vb, lb = _sorted_chunks(8, 32, 100, np.float32)
    klo, vlo, khi, vhi, ca, cb, ol = (
        np.asarray(t) for t in ops.stream_merge(
            *(jnp.asarray(x) for x in (ka, va, la, kb, vb, lb)),
            backend="pallas"))
    for s in range(8):
        emitted = np.concatenate([vlo[s], vhi[s]])[:ol[s]].sum()
        # consumed = keys <= cutoff on each side
        consumed = va[s, :ca[s]].sum() + vb[s, :cb[s]].sum()
        np.testing.assert_allclose(emitted, consumed, rtol=1e-4, atol=1e-4)


def test_sort_tokens_by_key_matches_argsort():
    keys = jnp.asarray(RNG.integers(0, 7, 128).astype(np.int32))
    sk, perm = ops.sort_tokens_by_key(keys, backend="pallas")
    assert (np.diff(np.asarray(sk)) >= 0).all()
    np.testing.assert_array_equal(np.asarray(keys)[np.asarray(perm)],
                                  np.asarray(sk))
    # stability: equal keys keep slot order
    p = np.asarray(perm)
    k = np.asarray(keys)
    for e in range(7):
        np.testing.assert_array_equal(np.sort(p[k[p] == e]), p[k[p] == e])


def test_flash_attention_ref_consistency():
    """mha_ref (oracle) vs blocked_attention on random GQA shapes."""
    import jax
    from repro.kernels.ref import mha_ref
    from repro.models.attention import blocked_attention
    key = jax.random.PRNGKey(3)
    for (B, Sq, H, KVH, hd, win) in [(2, 64, 4, 2, 16, 0), (1, 128, 8, 1, 8, 32),
                                     (2, 96, 4, 4, 32, 0)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, Sq, KVH, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, Sq, KVH, hd), jnp.float32)
        ref_o = mha_ref(q, k, v, causal=True, window=win)
        for skip in (False, True):
            out = blocked_attention(q, k, v, causal=True, window=win,
                                    q_block=32, kv_block=16, block_skip=skip)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                                       rtol=2e-4, atol=2e-4)
