"""Compile-only checks of the SpGEMM Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported primitives, unaligned slices, VMEM overruns.  These tests
compile each kernel of the spz path with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology — no chip attached — at the
chunk width every caller uses (R=16) and at the largest bucket that
``chip_smoke.py``'s matrices produce (the soc-Epinions1-sized power-law
matrix has rows of ~86k products: L = 8192 chunks * 16 = 131072).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (the kernel modules import repro.core)
from repro.kernels.chunk_sort import chunk_sort_pallas
from repro.kernels.fused_bucket import fused_bucket_pallas
from repro.kernels.merge_partitions import merge_partitions_pallas

LARGEST_L = 131072


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _streams(N, L):
    return [((N, L), jnp.int32), ((N, L), jnp.float32), ((N,), jnp.int32)]


@pytest.mark.parametrize("N,R", [(512, 16), (64, 128)])
def test_chunk_sort_compiles_for_v5e(one_chip, N, R):
    _compile(lambda k, v, n: chunk_sort_pallas(k, v, n), one_chip,
             *_streams(N, R))


@pytest.mark.parametrize("N,L", [(512, 16), (2, LARGEST_L // 2)])
def test_merge_partitions_compiles_for_v5e(one_chip, N, L):
    _compile(lambda *a: merge_partitions_pallas(*a, R=16), one_chip,
             *_streams(N, L), *_streams(N, L))


@pytest.mark.parametrize("N,L", [(512, 16), (16, LARGEST_L)])
def test_fused_bucket_compiles_for_v5e(one_chip, N, L):
    _compile(lambda k, v, n: fused_bucket_pallas(k, v, n, R=16,
                                                 detailed=True),
             one_chip, *_streams(N, L))


def test_bucket_program_names_its_phases_and_kernel(one_chip):
    """The whole bucket program of an m133-class 4096-row product: its
    HLO carries the two device phase scopes of ``core/trace.py`` in
    every operation's ``op_name``, and the Pallas call is named after
    its kernel function, which is how a chip trace is split by phase."""
    from repro.core import spgemm_engines as sg
    from repro.core import trace
    n, nnz, Nb = 4096, 16384, 8
    shapes = [((Nb,), jnp.int32), ((Nb,), jnp.int32),
              ((1, n + 1), jnp.int32), ((1, nnz), jnp.int32),
              ((1, nnz), jnp.float32), ((1, n + 1), jnp.int32),
              ((1, nnz), jnp.int32), ((1, nnz), jnp.float32),
              ((1, nnz + 1), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = sg._fused_bucket.lower(*args, R=16, L=16,
                                  backend="pallas").compile().as_text()
    for scope in (trace.EXPAND, trace.SORT_MERGE):
        assert f'op_name="jit(_fused_bucket_impl)/{scope}/' in text, scope
    assert "_fused_bucket_kernel" in text
