"""Pallas TPU kernel: full partition merge — the zip-merge tree's primitive.

This fills the seam PR 5 left on the ``pallas`` backend: under
``backend="pallas"`` the zip-merge tree previously still ran as the XLA
rank-based union merge (``merge_tree.merge_partitions``), bouncing
partition buffers through HBM between rounds.  Here the whole merge of
two padded sorted-unique partitions is one ``pallas_call``:

payload
    Both inputs are already sorted, so the merge needs only the *cheap*
    half of the sorting machinery: with the A side in the lower half of a
    stream's segment and the B side in the upper half,
    ``_network.merge_segments`` merges them in log(W) compare-exchange
    stages on (key, source-lane) pairs.  A-side lanes are numbered below
    B-side lanes, so cross-side duplicate keys land A-before-B
    deterministically.  Duplicates then accumulate with
    ``combine_pairs`` and ``compact`` packs the unique survivors to the
    front.

    Bit-identity with the XLA union merge: the inputs are sorted and
    duplicate-free per side, so a duplicate run has at most 2 elements
    and the accumulated value is the single IEEE add va + vb — the same
    add the union merge performs; all other values move through
    where-selections and the compaction network, untouched.

counters
    The SparseZipper chunk-advancement state machine (merge-bit cutoff =
    min of the two R-wide front maxima; consume every key <= cutoff)
    runs per stream inside the kernel as a vectorized
    ``jax.lax.while_loop`` over read pointers — gather-free: the cutoff
    is a segment min over the two fronts' last keys, the consumed counts
    a segment sum, not dynamic slices.  Per-stream step counts are
    returned and combined into per-*pair* issue counts
    outside (a pair's issue count is the max over its streams, zip_elems
    a plain sum, tails the max over streams of per-side ceil(rem/R)) —
    exactly ``merge_tree._advance_counters``'s accounting, which is
    separable per stream because a pair is active precisely while any of
    its streams is, and inactive streams present empty fronts that
    advance nothing and count zero.

Invariants: each side's keys are ascending and duplicate-free within a
row, EMPTY-padded past its ``lens`` (entries beyond lens are re-masked
here, matching the oracle's lens-trust, so a side's valid count is its
count of non-EMPTY keys); the concatenated network width is a power of
two (each side is padded to a shared pow2 width first).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import EMPTY
from repro.kernels import _network as net
from repro.kernels.merge_tree import MergeCounters


def merge_tile(k, v, lg):
    """Merge the two sorted-unique halves of every 2**lg-wide segment
    of a (T, 128) tile — pure jnp, usable inside any Pallas kernel body.

    Each half holds its valid keys ascending at its front, EMPTY/0
    behind.  Returns (keys, vals) with the merged uniques compressed to
    the segment's front, cross-side duplicates accumulated —
    bit-identical to ``merge_tree._union_merge``."""
    k, v = net.merge_segments(k, v, lg)
    # per-side-unique inputs => a key occurs at most twice, so the
    # accumulation is the single add va + vb
    k, v = net.combine_pairs(k, v, lg)
    return net.compact(k, v, lg)


def advance_tile(k, lg, R: int):
    """Per-stream chunk-advancement state machine — pure jnp while_loop,
    usable inside any Pallas kernel body.

    k: (T, 128) int32 keys laid out as in :func:`merge_tile` (A in the
    lower half of each 2**lg-wide segment, B in the upper, each
    ascending and compacted); R: modelled mszip chunk width.  Returns
    int32 (steps, zip_elems, tail_a, tail_b), each broadcast over its
    segment: lock-step advancement steps while both sides are live,
    tuples presented through the fronts, and leftover copy-through chunk
    counts per side."""
    pos = net.seg_pos(k.shape, lg)
    idx = net.seg_pos(k.shape, lg - 1)  # position within its side
    is_a = pos == idx
    valid = (k != EMPTY).astype(jnp.int32)
    la = net.seg_allreduce(jnp.where(is_a, valid, 0), lg, jnp.add)
    lb = net.seg_allreduce(jnp.where(is_a, 0, valid), lg, jnp.add)
    z = jnp.zeros(k.shape, jnp.int32)

    def cond(state):
        pa, pb, _, _ = state
        return jnp.max(((pa < la) & (pb < lb)).astype(jnp.int32)) > 0

    def body(state):
        pa, pb, steps, zips = state
        both = (pa < la) & (pb < lb)
        # fronts: [p, min(p + R, len)) per side, empty unless both live
        p = jnp.where(is_a, pa, pb)
        e = jnp.where(both, jnp.minimum(p + R, jnp.where(is_a, la, lb)), 0)
        front = (idx >= p) & (idx < e)
        # merge-bit cutoff: the smaller of the two fronts' last keys
        # (fronts are sorted, so a front's max is its last key)
        cutoff = net.seg_allreduce(
            jnp.where(front & (idx == e - 1), k, EMPTY), lg, jnp.minimum)
        took = jnp.where(front & (k <= cutoff),
                         jnp.where(is_a, 1, 1 << 16), 0)
        took = net.seg_allreduce(took, lg, jnp.add)
        fa = jnp.where(both, jnp.minimum(la - pa, R), 0)
        fb = jnp.where(both, jnp.minimum(lb - pb, R), 0)
        return (pa + (took & 0xFFFF), pb + (took >> 16),
                steps + both.astype(jnp.int32), zips + fa + fb)

    pa, pb, steps, zips = jax.lax.while_loop(cond, body, (z, z, z, z))
    return (steps, zips, _ceil_div(jnp.maximum(la - pa, 0), R),
            _ceil_div(jnp.maximum(lb - pb, 0), R))


def _ceil_div(x, R):
    return (x + (R - 1)) >> (R.bit_length() - 1)


def _merge_partitions_kernel(k_ref, v_ref, ok_ref, ov_ref, st_ref, zp_ref,
                             ta_ref, tb_ref, *, R: int, lg: int):
    k = k_ref[...]
    ok_ref[...], ov_ref[...] = merge_tile(k, v_ref[...], lg)
    for ref, c in zip((st_ref, zp_ref, ta_ref, tb_ref),
                      advance_tile(k, lg, R)):
        ref[...] = c


def _merge_payload_kernel(k_ref, v_ref, ok_ref, ov_ref, *, lg: int):
    ok_ref[...], ov_ref[...] = merge_tile(k_ref[...], v_ref[...], lg)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("R", "pair_streams",
                                             "with_counters", "interpret"))
def merge_partitions_pallas(ka, va, la, kb, vb, lb, *, R: int,
                            pair_streams: int | None = None,
                            with_counters: bool = True,
                            interpret: bool = False):
    """Fully merge two padded sorted-unique partitions per stream in one
    ``pallas_call`` — same contract as ``merge_tree.merge_partitions``.

    ka/kb: (N, La)/(N, Lb) int32 keys (EMPTY padded); va/vb: values;
    la/lb: (N,) valid lengths.  R: chunk width of the modelled mszip
    issue (a power of two); ``pair_streams``: lock-step group size S for
    the instruction accounting (rows [p*S, (p+1)*S) form pair p; default:
    one pair).

    Returns (keys (N, La+Lb), vals, lens, MergeCounters), bit-identical
    to the XLA backend including the exact counter values.
    """
    N, La = ka.shape
    Lb = kb.shape[1]
    Lo = La + Lb
    S = pair_streams or N
    zero = jnp.zeros((), jnp.int32)
    if N == 0 or Lo == 0:
        return (jnp.full((N, Lo), EMPTY, jnp.int32),
                jnp.zeros((N, Lo), va.dtype), jnp.zeros((N,), jnp.int32),
                MergeCounters(zero, zero, zero, zero))
    assert N % S == 0, f"pair_streams {S} must divide stream count {N}"
    assert R & (R - 1) == 0, "R must be a power of two"
    # pad each side to a shared pow2 width Wm: A fills the lower half of
    # a 2*Wm-wide segment, B the upper
    Wm = _next_pow2(max(La, Lb, 1))
    dtype = va.dtype
    ka, va = net.mask_to_lens(ka, va, la.astype(jnp.int32))
    kb, vb = net.mask_to_lens(kb, vb, lb.astype(jnp.int32))
    k = jnp.concatenate([
        jnp.pad(ka, ((0, 0), (0, Wm - La)), constant_values=EMPTY),
        jnp.pad(kb, ((0, 0), (0, Wm - Lb)), constant_values=EMPTY)], axis=1)
    v = jnp.concatenate([jnp.pad(va, ((0, 0), (0, Wm - La))),
                         jnp.pad(vb, ((0, 0), (0, Wm - Lb)))], axis=1)
    seg = 2 * Wm
    if with_counters:
        kernel = functools.partial(_merge_partitions_kernel, R=R,
                                   lg=seg.bit_length() - 1)
        outs = [jnp.int32, jnp.float32] + [jnp.int32] * 4
    else:
        kernel = functools.partial(_merge_payload_kernel,
                                   lg=seg.bit_length() - 1)
        outs = [jnp.int32, jnp.float32]
    res = net.stream_call(kernel, [(k, EMPTY), (v, 0.0)], outs, width=seg,
                          interpret=interpret)
    ko, vo = res[0][:, :Lo], res[1][:, :Lo].astype(dtype)
    lo = jnp.sum(ko != EMPTY, axis=1, dtype=jnp.int32)
    if not with_counters:
        return ko, vo, lo, MergeCounters(zero, zero, zero, zero)
    st, zp, ta, tb = (c[:, 0] for c in res[2:])
    P = N // S
    steps_p = jnp.max(st.reshape(P, S), axis=1)
    n_zip = jnp.sum(steps_p, dtype=jnp.int32)
    tails = (jnp.max(ta.reshape(P, S), axis=1)
             + jnp.max(tb.reshape(P, S), axis=1))
    cnt = MergeCounters(n_zip, jnp.sum(zp, dtype=jnp.int32), 2 * n_zip,
                        n_zip + jnp.sum(tails, dtype=jnp.int32))
    return ko, vo, lo, cnt
