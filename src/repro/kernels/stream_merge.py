"""Pallas TPU kernel: mszipk.tt + mszipv.tt (fused).

Two-way merge of two sorted duplicate-free key-value chunks per stream,
with the paper's data-dependent advancement semantics:

  * a key is mergeable only if the other side holds a key >= it (the
    paper's merge bit); unmergeable keys are withheld for the next step;
  * per-side consumed counts are returned (IC0/IC1 counter registers);
  * duplicates across sides are accumulated (C-state PEs);
  * the merged output is compressed and split into a low and a high
    R-chunk (east/south output sides) with its valid length (OC0/OC1).

Because both inputs are sorted, the merge needs only the log(2R)-stage
bitonic *merge* network — the same asymptotic win the systolic zip pass
gets over a full sort.  Each stream is a 2R-wide segment of
``_network``'s tile layout: side A in the lower half, side B in the
upper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import EMPTY
from repro.kernels import _network as net


def _stream_merge_kernel(k_ref, v_ref, ok_ref, ov_ref, took_ref, *, lg):
    k, v = k_ref[...], v_ref[...]
    is_a = net.seg_pos(k.shape, lg) == net.seg_pos(k.shape, lg - 1)
    # merge-bit cutoff: max valid key per side (-1 when empty)
    max_a = net.seg_allreduce(jnp.where(is_a & (k != EMPTY), k, -1), lg,
                              jnp.maximum)
    max_b = net.seg_allreduce(jnp.where(~is_a & (k != EMPTY), k, -1), lg,
                              jnp.maximum)
    m = (k != EMPTY) & (k <= jnp.minimum(max_a, max_b))
    # consumed counts, side A in the low 16 bits, side B above
    took_ref[...] = net.seg_allreduce(
        jnp.where(m, jnp.where(is_a, 1, 1 << 16), 0), lg, jnp.add)
    k, v = net.merge_segments(jnp.where(m, k, EMPTY), jnp.where(m, v, 0.0),
                              lg)
    k, v = net.combine_pairs(k, v, lg)
    ok_ref[...], ov_ref[...] = net.compact(k, v, lg)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stream_merge_pallas(ka, va, la, kb, vb, lb, *, interpret: bool = False):
    """All chunk args (S, R); lens (S,). Returns
    (k_lo, v_lo, k_hi, v_hi, consumed_a, consumed_b, out_lens)."""
    S, R = ka.shape
    assert R & (R - 1) == 0, "R must be a power of two"
    ka, fa = net.mask_to_lens(ka, va, la.astype(jnp.int32))
    kb, fb = net.mask_to_lens(kb, vb, lb.astype(jnp.int32))
    ok, ov, took = net.stream_call(
        functools.partial(_stream_merge_kernel, lg=R.bit_length()),
        [(jnp.concatenate([ka, kb], axis=1), EMPTY),
         (jnp.concatenate([fa, fb], axis=1), 0.0)],
        [jnp.int32, jnp.float32, jnp.int32], width=2 * R,
        interpret=interpret)
    ov = ov.astype(va.dtype)
    return (ok[:, :R], ov[:, :R], ok[:, R:], ov[:, R:],
            took[:, 0] & 0xFFFF, took[:, 0] >> 16,
            jnp.sum(ok != EMPTY, axis=1, dtype=jnp.int32))
