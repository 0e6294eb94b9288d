"""Pallas TPU kernel: the fused pipeline's chunk-sort stage.

Sorts ALL (N, R) chunks of a work bucket in one ``pallas_call`` issue —
the sort stage ``chunk_sort_partitions`` feeds into the device-resident
zip-merge tree.  The kernel is **bit-identical** to the XLA oracle
(``ref.stream_sort_ref`` / ``merge_tree.sort_chunks_linear``):

  * the sort is a bitonic network over each R-wide chunk made *stable* by
    comparing (key, source-lane) pairs lexicographically
    (``_network.sort_segments``), so ties keep product order exactly like
    a stable argsort;
  * duplicate values accumulate in a left-to-right linear association
    (an R-step sequential run prefix, the same adds in the same order as
    ``segment_sum``'s index-order accumulation) — a tree reduction would
    round differently;
  * the compress pass (``_network.compact``) moves each surviving tuple,
    it never recombines one.

Invariants: R must be a power of two (bitonic network width); input keys
beyond ``lens`` may be garbage (the wrapper masks them to EMPTY first);
valid keys are < 2**31 - 1 so EMPTY is a strict upper bound.

Chunks are laid out in ``_network``'s (T, 128) tiles — 128 / R chunks
per row for R < 128 — and the grid walks blocks of them, so a whole
bucket's S*C chunks are one kernel issue.  The tile body is exposed as
:func:`sort_tile` so the single-kernel fused bucket pipeline
(``kernels/fused_bucket.py``) runs the identical sort stage inside its
own ``pallas_call``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import EMPTY
from repro.kernels import _network as net


def sort_tile(k, v, R):
    """Sort/combine/compress every R-wide chunk of a (T, 128) tile — pure
    jnp, usable inside any Pallas kernel body.

    k: int32 keys, EMPTY past each chunk's valid count; v: f32 values,
    0 past it.  Returns (keys, vals) with each chunk's unique sorted keys
    compressed to its front (EMPTY/0 behind), duplicate values
    accumulated left-to-right — bit-identical to ``ref.stream_sort_ref``."""
    lg = R.bit_length() - 1
    k, v = net.sort_segments(k, v, lg)
    # linear run accumulation: acc[i] = left-to-right prefix of i's run;
    # adding the predecessor's finished prefix keeps the float association
    # linear, bit-identical to segment_sum's index-order adds
    r = net.seg_pos(k.shape, lg)
    s = jnp.where(k != net.seg_shift(k, 1, lg, EMPTY), r, 0)
    # Hillis-Steele max-scan: start index of each run
    s = jax.lax.fori_loop(
        0, lg, lambda e, s: jnp.maximum(
            s, net.seg_shift(s, jnp.left_shift(1, e), lg, 0)), s)
    run_pos = r - s
    acc = jax.lax.fori_loop(
        1, R, lambda d, acc: jnp.where(
            run_pos == d, net.seg_shift(acc, 1, lg, 0.0) + v, acc), v)
    # keep the run total (last element of each run), then compress
    last = (k != net.seg_shift(k, -1, lg, EMPTY)) & (k != EMPTY)
    return net.compact(jnp.where(last, k, EMPTY), jnp.where(last, acc, 0.0),
                       lg)


def _chunk_sort_kernel(keys_ref, vals_ref, ok_ref, ov_ref, *, R):
    ok_ref[...], ov_ref[...] = sort_tile(keys_ref[...], vals_ref[...], R)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_sort_pallas(keys, vals, lens, *, interpret: bool = False):
    """Sort/combine/compress all N key-value chunks in one kernel issue.

    keys: (N, R) int32; vals: (N, R) float; lens: (N,) int32.  R must be
    a power of two.  Returns (out_keys, out_vals, out_lens), bit-identical
    to ``ref.stream_sort_ref`` on the same inputs."""
    N, R = keys.shape
    assert R & (R - 1) == 0, "R must be a power of two"
    if N == 0:  # zero chunks: same empty outputs as the xla oracle
        return keys, vals, lens.astype(jnp.int32)
    k, v = net.mask_to_lens(keys, vals, lens)
    ok, ov = net.stream_call(
        functools.partial(_chunk_sort_kernel, R=R),
        [(k, EMPTY), (v, 0.0)], [jnp.int32, jnp.float32],
        width=R, interpret=interpret)
    return ok, ov.astype(vals.dtype), jnp.sum(ok != EMPTY, axis=1,
                                              dtype=jnp.int32)
