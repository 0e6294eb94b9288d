"""Pallas TPU kernel: sort + the whole zip-merge tree in ONE pallas_call.

The fused spz driver processes one (S, L, R) work bucket as "chunk-sort
everything, then log2(C) merge rounds".  With the stage kernels issued
separately, every round's partition buffers round-trip through HBM — the
exact spill SparseZipper (and SpArch's hierarchical merge tree) exist to
avoid.  This kernel runs the entire bucket pipeline inside a single
``pallas_call``: one program holds a block of whole streams in VMEM
(``_network``'s (T, 128) tile layout), chunk-sorts all their R-chunks
(``chunk_sort.sort_tile`` — the same tile the standalone chunk-sort
kernel runs), then folds the C sorted partitions through log2(C) rounds
of ``merge_partitions.merge_tile`` — a ``fori_loop`` over the round, the
pair width doubling each time — without the intermediate partitions ever
leaving VMEM.

Counters: the lock-step instruction accounting must match the host
driver per *group*, but one program only sees its own streams — so each
round also runs the per-stream ``advance_tile`` state machine and the
kernel emits per-(stream, round-pair) step/zip/tail counts.  The wrapper
reduces them across the full stream axis exactly the way
``merge_tree.zip_merge_tree(detailed=True)`` reports rounds (a pair's
issue count is the max over its streams, zip_elems a sum, tails the max
of per-side ceil(rem/R)), so ``spgemm.fused_process_group`` consumes the
result unchanged and rebuilds group-exact ``n_mssort``/``n_mszip``.

Invariants: R is a power of two (bitonic sort width) and C = L/R is a
power of two (balanced merge tree); keys beyond ``plens`` may be garbage
(the wrapper masks them to EMPTY); valid keys < 2**31-1.  Counter layout
in the kernel outputs: four (S, L) planes (steps, zips, tail_a, tail_b);
round r writes pair j of a stream at stream position j * 2**(r+1) * R
+ r, which no other round's pair uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import EMPTY
from repro.kernels import _network as net
from repro.kernels.chunk_sort import sort_tile
from repro.kernels.merge_partitions import advance_tile, merge_tile


def _fused_bucket_kernel(k_ref, v_ref, ok_ref, ov_ref, *cnt_refs,
                         R: int, C: int):
    lg_r = R.bit_length() - 1
    k, v = sort_tile(k_ref[...], v_ref[...], R)
    planes = tuple(jnp.zeros(k.shape, jnp.int32) for _ in cnt_refs)

    def merge_round(r, carry):
        k, v, planes = carry
        lg = lg_r + 1 + r  # pair width 2**(r+1) * R
        if planes:
            at = net.seg_pos(k.shape, lg) == r
            planes = tuple(jnp.where(at, c, p) for c, p in
                           zip(advance_tile(k, lg, R), planes))
        k, v = merge_tile(k, v, lg)
        return k, v, planes

    k, v, planes = jax.lax.fori_loop(0, C.bit_length() - 1, merge_round,
                                     (k, v, planes))
    ok_ref[...] = k
    ov_ref[...] = v
    for ref, p in zip(cnt_refs, planes):
        ref[...] = p


@functools.partial(jax.jit, static_argnames=("R", "with_counters",
                                             "detailed", "interpret"))
def fused_bucket_pallas(keys, vals, plens, *, R: int,
                        with_counters: bool = True, detailed: bool = False,
                        interpret: bool = False):
    """Sort + full zip-merge tree over one (S, L, R) work bucket in one
    kernel issue — same contract as ``core/stream.fused_sort_merge``.

    keys/vals: (S, L) unsorted padded product streams, L = C*R with both
    R and C powers of two; plens: (S,) valid lengths.  Returns
    (keys (S, L), vals, lens (S,), counters (6,)) with the host driver's
    [n_mssort, sort_elems, n_mszip, zip_elems, chunk_loads, chunk_stores]
    accounting, or — with ``detailed=True`` — the per-(round, pair)
    counter tuples in ``merge_tree.zip_merge_tree(detailed=True)`` form.
    Bit-identical to the XLA sort + merge-tree composition.
    """
    S, L = keys.shape
    C = L // R
    assert C * R == L, f"partition width {L} must be a multiple of R={R}"
    assert R & (R - 1) == 0, "R must be a power of two"
    assert C & (C - 1) == 0, f"partition count {C} must be a power of two"
    plens = plens.astype(jnp.int32)
    n_mssort = (-(-jnp.max(plens) // R)).astype(jnp.int32)
    sort_elems = jnp.sum(plens, dtype=jnp.int32)
    counted = (with_counters or detailed) and C > 1
    k, v = net.mask_to_lens(keys, vals, plens)
    res = net.stream_call(
        functools.partial(_fused_bucket_kernel, R=R, C=C),
        [(k, EMPTY), (v, 0.0)],
        [jnp.int32, jnp.float32] + [jnp.int32] * (4 * counted),
        width=L, interpret=interpret)
    mk, mv = res[0], res[1].astype(vals.dtype)
    ml = jnp.sum(mk != EMPTY, axis=1, dtype=jnp.int32)
    # reduce per-(stream, pair) planes exactly like zip_merge_tree reports
    # rounds: a pair's issue count is the max over its streams, zip_elems
    # a sum, tails the max per side
    rounds = []
    for r in range(C.bit_length() - 1 if counted else 0):
        st, zp, ta, tb = (p.reshape(S, C >> (r + 1), -1)[:, :, r]
                          for p in res[2:])
        rounds.append((jnp.max(st, axis=0), jnp.sum(zp, dtype=jnp.int32),
                       jnp.stack([jnp.max(ta, axis=0), jnp.max(tb, axis=0)],
                                 axis=1)))
    if detailed:
        if not counted:  # no merge rounds, or counters skipped
            rounds = [(jnp.zeros((C >> (r + 1),), jnp.int32),
                       jnp.zeros((), jnp.int32),
                       jnp.zeros((C >> (r + 1), 2), jnp.int32))
                      for r in range(C.bit_length() - 1)]
        return mk, mv, ml, tuple(rounds)
    n_zip = sum((jnp.sum(r[0], dtype=jnp.int32) for r in rounds),
                jnp.zeros((), jnp.int32))
    zip_elems = sum((r[1] for r in rounds), jnp.zeros((), jnp.int32))
    tail_sum = sum((jnp.sum(r[2], dtype=jnp.int32) for r in rounds),
                   jnp.zeros((), jnp.int32))
    counters = jnp.stack([n_mssort, sort_elems, n_zip, zip_elems,
                          n_mssort + 2 * n_zip,
                          n_mssort + n_zip + tail_sum])
    return mk, mv, ml, counters
