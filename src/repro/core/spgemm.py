"""Row-wise (Gustavson) SpGEMM engines — the paper's §V-B implementations.

Five implementations, mirroring the paper's evaluation:

  scl-array  — scalar row loop with a dense accumulator row (Gilbert et al.)
  scl-hash   — scalar row loop with a hash-style unique/accumulate
  esc        — vectorized Expand-Sort-Compress (the vec-radix analogue);
               fully jittable with static capacities (XLA sort plays the
               radix sort's role)
  spz        — merge-based SpGEMM on the SparseZipper primitives: chunked
               stream sort + zip-merge tree with data-dependent advancement,
               lock-step groups of S streams.  Two drivers: the default
               device-resident "fused" pipeline (expand + sort + full merge
               tree under one jit, chunk pointers as jax.lax.while_loop
               state) and the original "host" lock-step Python driver (one
               kernel issue per chunk — the stats-faithful Fig. 9-11 path)
  spz-rsort  — spz with row indices pre-sorted by per-row work to reduce
               lock-step imbalance (paper §V-B / Fig. 9)

All produce identical CSR outputs (property-tested against scl-array).
``spz`` returns dynamic-instruction statistics (mssort/mszip counts) used by
the Fig. 10/11 benchmark analogues.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.formats import CSR, EMPTY, csr_from_coo, csr_to_numpy, row_ids_from_indptr
from repro.core import stream as kvstream
from repro.core import trace
from repro.kernels import backend as kb


# ---------------------------------------------------------------------------
# work statistics (Table III)
# ---------------------------------------------------------------------------

def row_work(A: CSR, B: CSR) -> np.ndarray:
    """#multiplications to compute each output row (Table III 'Work')."""
    a_indptr, a_idx, _ = csr_to_numpy(A)
    b_indptr = np.asarray(B.indptr)
    blen = (b_indptr[1:] - b_indptr[:-1]).astype(np.int64)
    w = np.zeros(A.n_rows, np.int64)
    contrib = blen[a_idx]
    rows = np.repeat(np.arange(A.n_rows), a_indptr[1:] - a_indptr[:-1])
    np.add.at(w, rows, contrib)
    return w


def work_stats(A: CSR, B: CSR, group: int = 16) -> dict:
    """Per-row and per-group work stats (Table III reproduction)."""
    w = row_work(A, B)
    n = len(w)
    pad = (-n) % group
    wg = np.pad(w, (0, pad)).reshape(-1, group).sum(1)
    return {
        "nnz": int(np.asarray(A.indptr)[-1]),
        "density": float(np.asarray(A.indptr)[-1]) / (A.n_rows * A.n_cols),
        "avg_work_per_row": float(w.mean()),
        "avg_work_per_group": float(wg.mean()),
        "work_var_per_group": float(wg.std() / max(wg.mean(), 1e-12)),
        "total_work": int(w.sum()),
    }


# ---------------------------------------------------------------------------
# scalar baselines (numpy, row-at-a-time — the paper's scl-*)
# ---------------------------------------------------------------------------

def spgemm_scl_array(A: CSR, B: CSR) -> CSR:
    """Dense-accumulator-row scalar SpGEMM (oracle for everything else)."""
    a_indptr, a_idx, a_val = csr_to_numpy(A)
    b_indptr, b_idx, b_val = csr_to_numpy(B)
    acc = np.zeros(B.n_cols, np.float64)
    out_r, out_c, out_v = [], [], []
    for i in range(A.n_rows):
        touched = []
        for t in range(a_indptr[i], a_indptr[i + 1]):
            j, av = a_idx[t], a_val[t]
            s, e = b_indptr[j], b_indptr[j + 1]
            cols = b_idx[s:e]
            acc[cols] += av * b_val[s:e]
            touched.append(cols)
        if touched:
            cols = np.unique(np.concatenate(touched))
            vals = acc[cols]
            acc[cols] = 0.0
            nz = vals != 0.0
            out_r.append(np.full(nz.sum(), i, np.int64))
            out_c.append(cols[nz])
            out_v.append(vals[nz])
    if not out_r:
        return csr_from_coo([], [], [], (A.n_rows, B.n_cols))
    return csr_from_coo(np.concatenate(out_r), np.concatenate(out_c),
                        np.concatenate(out_v), (A.n_rows, B.n_cols))


def spgemm_scl_hash(A: CSR, B: CSR) -> CSR:
    """Hash-accumulate scalar SpGEMM (paper's scl-hash; here the per-row
    hash table is modelled by sort-unique accumulation over the expanded
    products of one row at a time, then a final sort — same asymptotics,
    no O(n_cols) state)."""
    a_indptr, a_idx, a_val = csr_to_numpy(A)
    b_indptr, b_idx, b_val = csr_to_numpy(B)
    out_r, out_c, out_v = [], [], []
    for i in range(A.n_rows):
        ks, vs = [], []
        for t in range(a_indptr[i], a_indptr[i + 1]):
            j, av = a_idx[t], a_val[t]
            s, e = b_indptr[j], b_indptr[j + 1]
            ks.append(b_idx[s:e])
            vs.append(av * b_val[s:e])
        if not ks:
            continue
        k = np.concatenate(ks)
        v = np.concatenate(vs)
        uk, inv = np.unique(k, return_inverse=True)
        uv = np.zeros(len(uk), np.float64)
        np.add.at(uv, inv, v)
        nz = uv != 0.0
        out_r.append(np.full(nz.sum(), i, np.int64))
        out_c.append(uk[nz])
        out_v.append(uv[nz])
    if not out_r:
        return csr_from_coo([], [], [], (A.n_rows, B.n_cols))
    return csr_from_coo(np.concatenate(out_r), np.concatenate(out_c),
                        np.concatenate(out_v), (A.n_rows, B.n_cols))


# ---------------------------------------------------------------------------
# ESC (vec-radix analogue) — fully jittable with static capacities
# ---------------------------------------------------------------------------

def esc_core_impl(a_indptr, a_idx, a_val, b_indptr, b_idx, b_val,
                   cap_products: int, n_rows: int, n_cols: int):
    nnz_a_cap = a_idx.shape[0]
    with jax.named_scope(trace.ESC_EXPAND):
        # product p belongs to A-entry t = searchsorted(Wcum, p)
        a_rows = row_ids_from_indptr(a_indptr, nnz_a_cap)
        blen = b_indptr[1:] - b_indptr[:-1]
        nnz_a = a_indptr[-1]
        t_valid = jnp.arange(nnz_a_cap) < nnz_a
        j_of_t = jnp.where(t_valid, a_idx, 0)
        w_t = jnp.where(t_valid, blen[j_of_t], 0)
        wcum = jnp.cumsum(w_t)
        total_work = wcum[-1]
        p = jnp.arange(cap_products, dtype=jnp.int32)
        t_of_p = jnp.searchsorted(wcum, p, side="right").astype(jnp.int32)
        t_of_p = jnp.clip(t_of_p, 0, nnz_a_cap - 1)
        p_valid = p < total_work
        base = jnp.where(t_of_p > 0, wcum[t_of_p - 1], 0)
        s_of_p = b_indptr[j_of_t[t_of_p]] + (p - base)
        s_of_p = jnp.clip(s_of_p, 0, b_idx.shape[0] - 1)
        prod_row = jnp.where(p_valid, a_rows[t_of_p], n_rows)
        prod_col = jnp.where(p_valid, b_idx[s_of_p], n_cols)
        prod_val = jnp.where(p_valid, a_val[t_of_p] * b_val[s_of_p], 0.0)
    with jax.named_scope(trace.ESC_SORT):
        # by (row, col): two stable passes (the radix-sort analogue)
        o1 = jnp.argsort(prod_col, stable=True)
        r1, c1, v1 = prod_row[o1], prod_col[o1], prod_val[o1]
        o2 = jnp.argsort(r1, stable=True)
        r2, c2, v2 = r1[o2], c1[o2], v1[o2]
    with jax.named_scope(trace.ESC_COMPRESS):
        # accumulate duplicate (row, col)
        first = (r2 != jnp.roll(r2, 1)) | (c2 != jnp.roll(c2, 1))
        first = first.at[0].set(True)
        seg = jnp.cumsum(first.astype(jnp.int32)) - 1
        out_v = jax.ops.segment_sum(v2, seg, num_segments=cap_products)
        pos = seg
        out_r = jnp.full(cap_products, n_rows, jnp.int32).at[pos].set(r2.astype(jnp.int32))
        out_c = jnp.full(cap_products, n_cols, jnp.int32).at[pos].set(c2.astype(jnp.int32))
        valid_out = (out_r < n_rows) & (out_v != 0.0)
        n_out = jnp.sum(valid_out, dtype=jnp.int32)
    return out_r, out_c, out_v, valid_out, n_out


# jitted single-matrix entry; the unjitted esc_core_impl is vmapped by the
# batched dispatch path (core/dispatch.py) so a whole batch shares one jit
_esc_core = functools.partial(
    jax.jit, static_argnames=("cap_products", "n_rows", "n_cols"))(esc_core_impl)


def spgemm_esc(A: CSR, B: CSR, cap_products: int | None = None) -> CSR:
    """Vectorized Expand-Sort-Compress SpGEMM (the vec-radix analogue)."""
    if cap_products is None:
        cap_products = int(max(16, row_work(A, B).sum()))
    with trace.span(trace.ESC_DEVICE, products=cap_products, rows=A.n_rows):
        out = jax.block_until_ready(_esc_core(
            A.indptr, A.indices, A.data, B.indptr, B.indices, B.data,
            cap_products, A.n_rows, B.n_cols))
    with trace.span(trace.ESC_FETCH) as s:
        r, c, v, valid, n_out = map(np.asarray, out)
        s.set_metadata(bytes=sum(x.nbytes for x in (r, c, v, valid, n_out)),
                       entries=int(n_out))
    with trace.span(trace.ESC_ASSEMBLE):
        return csr_from_coo(r[valid], c[valid], v[valid], (A.n_rows, B.n_cols))


# ---------------------------------------------------------------------------
# SparseZipper merge-based SpGEMM (spz / spz-rsort)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpzStats:
    """Dynamic instruction counts (Fig. 11), traffic (Fig. 10) and the
    execution-time breakdown (Fig. 9).

    The four ``t_*`` fields are the host driver's wall-clock breakdown,
    read by ``benchmarks/run.py::fig9``.  The fused driver fills only
    ``t_preprocess`` and ``t_output``; its group loop is timed by the
    ``repro.spz.*`` profiler spans (``core/trace.py``)."""
    n_mssort: int = 0        # sort-instruction issues (S-stream lock-step)
    n_mszip: int = 0         # zip-instruction issues
    sort_elems: int = 0      # key-value tuples moved through sort
    zip_elems: int = 0       # key-value tuples moved through merge
    chunk_loads: int = 0     # mlxe.t analogue (chunk fronts built)
    chunk_stores: int = 0    # msxe.t analogue
    t_preprocess: float = 0.0  # row-work calc (+ rsort row ordering)
    t_expand: float = 0.0      # stream expansion (multiplications)
    t_sort: float = 0.0        # stream sorting + merging
    t_output: float = 0.0      # output generation / row reordering


def expand_group(rows, a_indptr, a_idx, a_val, b_indptr, b_idx, b_val):
    """Vectorized expansion (RVV phase in the paper) for a group of rows.
    Returns per-row (cols, vals) numpy arrays of partial products."""
    out = []
    for i in rows:
        s, e = a_indptr[i], a_indptr[i + 1]
        js = a_idx[s:e]
        avs = a_val[s:e]
        if len(js) == 0:
            out.append((np.empty(0, np.int32), np.empty(0, np.float32)))
            continue
        starts = b_indptr[js]
        lens = (b_indptr[js + 1] - starts).astype(np.int64)
        total = int(lens.sum())
        if total == 0:
            out.append((np.empty(0, np.int32), np.empty(0, np.float32)))
            continue
        pos = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens) \
            + np.repeat(starts, lens)
        cols = b_idx[pos].astype(np.int32)
        vals = (np.repeat(avs, lens) * b_val[pos]).astype(np.float32)
        out.append((cols, vals))
    return out


def sort_phase(products, R, S, backend, stats: SpzStats, cap_s=None):
    """Chunk-sort every stream's products into sorted unique partitions.

    Returns a list of partitions; partition p = (keys (S, R), vals (S, R),
    lens (S,)) — the sorted-unique output of chunk p across all lock-step
    streams (lens[s] == 0 where stream s has no p-th chunk)."""
    plens = np.array([len(k) for k, _ in products], np.int64)
    max_len = int(plens.max()) if S else 0
    n_chunks = max(1, -(-max_len // R)) if max_len else 0
    # pad the ragged product lists into one (S, n_chunks*R) buffer
    K = np.full((S, n_chunks * R), EMPTY, np.int32)
    V = np.zeros((S, n_chunks * R), np.float32)
    for s, (k, v) in enumerate(products):
        K[s, :len(k)] = k
        V[s, :len(k)] = v
    parts = []
    for c in range(n_chunks):
        lens = np.clip(plens - c * R, 0, R).astype(np.int32)
        if not lens.any():
            break
        keys = K[:, c * R:(c + 1) * R]
        vals = V[:, c * R:(c + 1) * R]
        ok, ov, ol = kvstream.sort_chunks(keys, vals, lens, backend=backend,
                                          cap_s=cap_s)
        stats.n_mssort += 1
        stats.sort_elems += int(lens.sum())
        stats.chunk_loads += 1
        stats.chunk_stores += 1
        parts.append((np.asarray(ok), np.asarray(ov),
                      np.asarray(ol).astype(np.int64)))
    return parts


def _take_chunk(K, V, lens, ptr, R):
    """Vectorized chunk front: rows [ptr, min(ptr+R, lens)) of each stream.
    K/V: (S, L) padded; returns (keys (S,R), vals (S,R), n (S,))."""
    S, L = K.shape
    idx = ptr[:, None] + np.arange(R)[None, :]
    ok = idx < lens[:, None]
    idx_c = np.minimum(idx, max(L - 1, 0))
    keys = np.where(ok, np.take_along_axis(K, idx_c, 1), EMPTY).astype(np.int32)
    vals = np.where(ok, np.take_along_axis(V, idx_c, 1), 0.0).astype(np.float32)
    return keys, vals, ok.sum(1).astype(np.int32)


def _put_rows(K, V, optr, src_k, src_v, n):
    """Vectorized append: write src[s, :n[s]] at K[s, optr[s]:...].
    Masked fancy indexing — invalid lanes are simply not written (a clamp
    here would let a masked write collide with the last valid slot)."""
    W = src_k.shape[1]
    idx = optr[:, None] + np.arange(W)[None, :]
    ok = np.arange(W)[None, :] < n[:, None]
    rows, _ = np.nonzero(ok)
    K[rows, idx[ok]] = src_k[ok]
    V[rows, idx[ok]] = src_v[ok]


def merge_round(A, B, R, backend, stats: SpzStats, cap_s=None):
    """Merge partition pair lock-step across streams, chunk by chunk.
    A, B: (keys (S, La), vals, lens (S,)) padded partitions.
    Returns merged (keys (S, La+Lb), vals, lens)."""
    (Ka, Va, lensA), (Kb, Vb, lensB) = A, B
    S = Ka.shape[0]
    Lo = Ka.shape[1] + Kb.shape[1]
    Ko = np.full((S, Lo), EMPTY, np.int32)
    Vo = np.zeros((S, Lo), np.float32)
    pa = np.zeros(S, np.int64)
    pb = np.zeros(S, np.int64)
    optr = np.zeros(S, np.int64)
    while True:
        # only streams with BOTH sides unexhausted participate (the driver
        # copy-through below handles the rest)
        both = (pa < lensA) & (pb < lensB)
        if not both.any():
            break
        ka, va, la = _take_chunk(Ka, Va, np.where(both, lensA, 0), pa, R)
        kb_, vb, lb = _take_chunk(Kb, Vb, np.where(both, lensB, 0), pb, R)
        res = kvstream.merge_chunks(ka, va, la, kb_, vb, lb, backend=backend,
                                    cap_s=cap_s)
        klo, vlo, khi, vhi, ca, cb, ol = map(np.asarray, res)
        stats.n_mszip += 1
        stats.zip_elems += int(la.sum() + lb.sum())
        stats.chunk_loads += 2
        stats.chunk_stores += 1
        merged_k = np.concatenate([klo, khi], 1)
        merged_v = np.concatenate([vlo, vhi], 1)
        _put_rows(Ko, Vo, optr, merged_k, merged_v, ol.astype(np.int64))
        optr += ol
        pa += ca
        pb += cb
    # copy-through tails (one side exhausted)
    for (K, V, lens, ptr) in ((Ka, Va, lensA, pa), (Kb, Vb, lensB, pb)):
        rem = (lens - ptr).clip(0)
        W = int(rem.max()) if len(rem) else 0
        if W > 0:
            idx = np.minimum(ptr[:, None] + np.arange(W)[None, :],
                             K.shape[1] - 1)
            ok = np.arange(W)[None, :] < rem[:, None]
            src_k = np.where(ok, np.take_along_axis(K, idx, 1), EMPTY)
            src_v = np.where(ok, np.take_along_axis(V, idx, 1), 0.0)
            _put_rows(Ko, Vo, optr, src_k.astype(np.int32),
                      src_v.astype(np.float32), rem)
            optr += rem
            stats.chunk_stores += int((-(-rem // R)).max())
    return Ko, Vo, optr.astype(np.int64)


def merge_tree_host(parts, R, backend, stats: SpzStats, cap_s=None):
    """Zip-merge tree: halve partition count per round, lock-step.
    Returns the single surviving partition (keys, vals, lens) or None."""
    while len(parts) > 1:
        nxt = []
        for j in range(0, len(parts) - 1, 2):
            nxt.append(merge_round(parts[j], parts[j + 1], R, backend,
                                    stats, cap_s=cap_s))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0] if parts else None


# ---------------------------------------------------------------------------
# device-resident (fused) spz pipeline
# ---------------------------------------------------------------------------

def fused_operands(a_indptr, a_idx, a_val, b_indptr, b_idx, b_val):
    """The fused pipeline's per-product operands: the six (batch, ...)
    stacked CSR arrays plus ``wcum`` (batch, nnz_cap + 1) int32, the
    exclusive prefix of products expanded per A entry of each lane.

    ``wcum`` depends only on the operands, so it is computed once per
    product on the host rather than inside every bucket's program (where
    a device cumsum over nnz elements also costs tens of seconds of TPU
    compile per bucket shape)."""
    ip, ix, bp = (np.asarray(x) for x in (a_indptr, a_idx, b_indptr))
    blen = np.diff(bp, axis=1)
    t_ok = np.arange(ix.shape[1])[None, :] < ip[:, -1:]
    w = np.where(t_ok, np.take_along_axis(blen, np.where(t_ok, ix, 0),
                                          axis=1), 0)
    wcum = np.concatenate([np.zeros((ix.shape[0], 1), np.int64),
                           np.cumsum(w, axis=1)], axis=1)
    return (a_indptr, a_idx, a_val, b_indptr, b_idx, b_val,
            jnp.asarray(wcum.astype(np.int32)))


def _fused_expand(row_ids, lane_ids, a_indptr, a_idx, a_val,
                  b_indptr, b_idx, b_val, wcum0, L: int):
    """Device-side expansion: per-stream padded partial products.

    row_ids/lane_ids: (S,) int32 — stream s expands output row
    ``row_ids[s]`` of batch lane ``lane_ids[s]`` (row_ids < 0 marks
    padding streams).  Matrix arrays are (batch, ...) stacked, with the
    per-lane work prefix ``wcum0`` of :func:`fused_operands`.  Returns
    (keys (S, L), vals (S, L), plens (S,)) with EMPTY/0 padding — the
    device replacement for the host ``expand_group`` + chunk-buffer
    marshaling.
    """
    Bn, n_rows1 = a_indptr.shape
    nnz_cap = a_idx.shape[1]
    bcap = b_idx.shape[1]
    valid_s = row_ids >= 0
    lane = jnp.clip(lane_ids.astype(jnp.int32), 0, Bn - 1)
    row = jnp.clip(row_ids.astype(jnp.int32), 0, n_rows1 - 2)
    # flatten lanes onto one monotone axis so one searchsorted serves the
    # whole batch: lane l lives at offset l * (max total work + 1)
    M = jnp.max(wcum0[:, -1]) + 1
    offs = jnp.arange(Bn, dtype=jnp.int32) * M
    wflat = (wcum0 + offs[:, None]).reshape(-1)
    t0 = a_indptr[lane, row]
    t1 = a_indptr[lane, row + 1]
    ws = wcum0[lane, t0]
    we = jnp.where(valid_s, wcum0[lane, t1], ws)
    plens = (we - ws).astype(jnp.int32)
    p = jnp.arange(L, dtype=jnp.int32)
    pvalid = p[None, :] < plens[:, None]
    g = jnp.where(pvalid, ws[:, None] + p[None, :], ws[:, None])
    q = (g + offs[lane][:, None]).reshape(-1)
    # product g belongs to the last A-entry whose cumulated work <= g
    tg = jnp.searchsorted(wflat, q, side="right").reshape(g.shape) - 1
    t = jnp.clip(tg - (lane * (nnz_cap + 1))[:, None], 0, nnz_cap - 1)
    base = wflat[tg] - offs[lane][:, None]
    j = a_idx[lane[:, None], t]
    pos = jnp.clip(b_indptr[lane[:, None], j] + (g - base), 0, bcap - 1)
    keys = jnp.where(pvalid, b_idx[lane[:, None], pos], EMPTY)
    vals = jnp.where(pvalid,
                     a_val[lane[:, None], t] * b_val[lane[:, None], pos], 0.0)
    return keys, vals.astype(jnp.float32), plens


def _fused_bucket_impl(row_ids, lane_ids, a_indptr, a_idx, a_val,
                       b_indptr, b_idx, b_val, wcum0, R: int, L: int,
                       backend: str):
    """One work bucket of a lock-step group, fully device-resident:
    expansion, chunk sort, and the whole zip-merge tree chained under a
    single trace.  Returns (keys (N, L), vals, lens (N,), rounds) where
    rounds carries the per-(round, pair) merge counters (see
    kernels/merge_tree.py zip_merge_tree detailed mode)."""
    with jax.named_scope(trace.EXPAND):
        keys, vals, plens = _fused_expand(row_ids, lane_ids, a_indptr, a_idx,
                                          a_val, b_indptr, b_idx, b_val,
                                          wcum0, L)
    with jax.named_scope(trace.SORT_MERGE):
        return kvstream.fused_sort_merge(keys, vals, plens, R=R,
                                         backend=backend, detailed=True)


# smallest stream count a bucket is padded to
MIN_BUCKET_STREAMS = 8

# one compiled pipeline per static (N, L, R) bucket + matrix capacity
_fused_bucket = functools.partial(
    jax.jit, static_argnames=("R", "L", "backend"))(_fused_bucket_impl)


def _pow2_chunks(max_plen: int, R: int) -> int:
    """Partition count for the merge tree: next pow2 >= ceil(max_plen/R)."""
    q = -(-int(max_plen) // R)
    return 1 << max(0, q - 1).bit_length()


def fused_process_group(items, plens, mats, R, backend, stats: SpzStats,
                         runs: list) -> None:
    """Run one lock-step group of work items through the fused pipeline.

    items: [(lane, row)] output rows of the group; plens: per-item
    product counts; mats: :func:`fused_operands`; each bucket appends
    its merged streams to ``runs`` as one (lanes, rows, lens, keys, vals)
    part, read by :func:`_runs_to_csrs` (no per-row slicing).

    Streams are bucketed by their own pow2 chunk count so a skewed group
    does not pad every stream to the group-max width (the fused analogue
    of the lock-step imbalance rsort targets).  The payload per stream is
    independent of which streams share a kernel, so bucketing cannot
    change results; the lock-step *instruction counts* are group-wide, so
    they are rebuilt exactly from the per-(round, pair) bucket counters —
    a pair's issue count is the max per-stream step count (elementwise
    max over buckets), zip_elems a plain sum.  Sort-phase counters depend
    only on plens and are computed here directly.  chunk_stores is
    approximate for this driver: the host tree passes odd partitions
    through for free, while the pow2 tree copies them through an empty
    merge."""
    with trace.span(trace.SPZ_GROUP, items=len(items),
                    products=int(plens.sum())) as group_span:
        buckets: dict[int, list[int]] = {}
        for ix, pl in enumerate(plens):
            if pl > 0:
                buckets.setdefault(_pow2_chunks(int(pl), R), []).append(ix)
        group_span.set_metadata(buckets=len(buckets))
        if not buckets:
            return
        max_plen = int(plens.max())
        n_used = -(-max_plen // R)
        stats.n_mssort += n_used
        stats.sort_elems += int(plens.sum())
        stats.chunk_loads += n_used
        stats.chunk_stores += n_used
        n_rounds = max(buckets).bit_length() - 1
        steps_acc = [np.zeros(max(buckets) >> (k + 1), np.int64)
                     for k in range(n_rounds)]
        tails_acc = [np.zeros((max(buckets) >> (k + 1), 2), np.int64)
                     for k in range(n_rounds)]
        zip_elems = 0
        for C_b in sorted(buckets):
            idxs = buckets[C_b]
            # pow2 stream counts, at least MIN_BUCKET_STREAMS, bound the
            # number of compiled bucket shapes; padding streams do no work
            Nb = max(MIN_BUCKET_STREAMS, 1 << max(0, len(idxs) - 1).bit_length())
            shape = {"streams": Nb, "used": len(idxs), "L": C_b * R}
            with trace.span(trace.SPZ_LAUNCH, **shape):
                row_ids = np.full(Nb, -1, np.int32)
                lane_ids = np.zeros(Nb, np.int32)
                for t, ix in enumerate(idxs):
                    lane_ids[t], row_ids[t] = items[ix]
                mk, mv, ml, rounds = _fused_bucket(
                    jnp.asarray(row_ids), jnp.asarray(lane_ids), *mats,
                    R=R, L=C_b * R, backend=kb.resolve_backend(backend).name)
            with trace.span(trace.SPZ_FETCH, **shape):
                mk, mv, ml = np.asarray(mk), np.asarray(mv), np.asarray(ml)
                rounds = [(np.asarray(st), int(np.asarray(ze)), np.asarray(tl))
                          for st, ze, tl in rounds]
            with trace.span(trace.SPZ_UNPACK, **shape):
                for k, (st, ze, tl) in enumerate(rounds):
                    np.maximum(steps_acc[k][:len(st)], st,
                               out=steps_acc[k][:len(st)])
                    np.maximum(tails_acc[k][:len(tl)], tl,
                               out=tails_acc[k][:len(tl)])
                    zip_elems += ze
                n = len(idxs)
                ml = ml[:n]
                valid = np.arange(mk.shape[1])[None, :] < ml[:, None]
                runs.append((lane_ids[:n], row_ids[:n], ml, mk[:n][valid],
                             mv[:n][valid]))
        n_zip = sum(int(s.sum()) for s in steps_acc)
        stats.n_mszip += n_zip
        stats.zip_elems += zip_elems
        stats.chunk_loads += 2 * n_zip
        stats.chunk_stores += n_zip + sum(int(t.sum()) for t in tails_acc)


def _group_cap(Sg: int, S: int) -> int:
    """Pad kernel issues to the next pow2 >= Sg (capped at S): bounds the
    number of distinct compiled shapes without inflating a small matrix's
    groups all the way to S streams."""
    return min(S, 1 << max(0, Sg - 1).bit_length())


def _spz_host_driver(A, B, R, S, order, backend, stats):
    """The paper-faithful lock-step Python driver: one kernel issue per
    chunk, numpy marshaling between issues (stats carry the per-phase
    wall-clock breakdown used by the Fig. 9 benchmark)."""
    a_indptr, a_idx, a_val = csr_to_numpy(A)
    b_indptr, b_idx, b_val = csr_to_numpy(B)
    out_rows_k = [None] * A.n_rows
    out_rows_v = [None] * A.n_rows
    for g0 in range(0, A.n_rows, S):
        rows = order[g0:g0 + S]
        cap_g = _group_cap(len(rows), S)
        t1 = time.perf_counter()
        products = expand_group(rows, a_indptr, a_idx, a_val,
                                 b_indptr, b_idx, b_val)
        t2 = time.perf_counter()
        stats.t_expand += t2 - t1
        parts = sort_phase(products, R, len(rows), backend, stats,
                           cap_s=cap_g)
        final = merge_tree_host(parts, R, backend, stats, cap_s=cap_g)
        stats.t_sort += time.perf_counter() - t2
        if final is not None:
            Kf, Vf, lf = final
            for s, i in enumerate(rows):
                out_rows_k[i] = Kf[s, :lf[s]]
                out_rows_v[i] = Vf[s, :lf[s]]
        else:
            for i in rows:
                out_rows_k[i] = np.empty(0, np.int32)
                out_rows_v[i] = np.empty(0, np.float32)
    return out_rows_k, out_rows_v


def _spz_fused_driver(A, R, S, order, work, mats, backend, stats):
    """Device-resident driver: per lock-step group, the work-bucketed
    expand/sort/merge-tree pipelines run as jitted computations keyed on
    static (N, L, R) buckets.  All chunk pointers live on the device;
    SpzStats counts come back as device counters."""
    runs: list = []
    with trace.span(trace.SPZ_GROUPS, groups=-(-A.n_rows // S)):
        for g0 in range(0, A.n_rows, S):
            rows = order[g0:g0 + S]
            items = [(0, int(i)) for i in rows]
            fused_process_group(items, work[rows], mats, R, backend, stats,
                                runs)
    return runs


def _runs_to_csrs(runs, shape, n_lanes: int = 1) -> list[CSR]:
    """Assemble the fused driver's merged streams into one output CSR per
    lane, dropping exact zeros like the scalar engines.

    runs: :func:`fused_process_group`'s parts; stream s of a part is row
    ``rows[s]`` of lane ``lanes[s]``, its ``lens[s]`` entries next in
    ``keys``/``vals``.  A merged stream's keys are sorted and unique, and
    each (lane, row) is one stream, so the CSR follows from the run
    lengths alone: ``indptr`` is a prefix sum of the rows' counts and
    each run is copied to its row's offset, in whatever order the rows
    came (rsort, buckets).  No sort, no per-row loop; the result equals
    ``csr_from_coo`` of the same entries."""
    n_rows = shape[0]
    with trace.span(trace.SPZ_ASSEMBLE) as span:
        lanes, rows, lens, keys, vals = (
            np.concatenate([p[k] for p in runs]) if runs
            else np.zeros(0, dt) for k, dt in enumerate(
                (np.int32, np.int32, np.int32, np.int32, np.float32)))
        # exact zeros are rare: count them per run and drop them
        zeros = np.flatnonzero(vals == 0.0)
        kept = lens - np.bincount(
            np.searchsorted(np.cumsum(lens), zeros, side="right"),
            minlength=len(lens))
        if len(zeros):
            keys, vals = np.delete(keys, zeros), np.delete(vals, zeros)
        slot = lanes.astype(np.int64) * n_rows + rows
        counts = np.bincount(slot, weights=kept, minlength=n_lanes * n_rows)
        indptr = np.zeros((n_lanes, n_rows + 1), np.int64)
        np.cumsum(counts.reshape(n_lanes, n_rows).astype(np.int64), axis=1,
                  out=indptr[:, 1:])
        # the runs in output order, from a table over every (lane, row):
        # one run a slot, so no sort
        live = np.flatnonzero(kept)
        run_at = np.full(n_lanes * n_rows, -1, np.int64)
        run_at[slot[live]] = live
        order = run_at[run_at >= 0]
        # output entry j is kept entry src[j]: src steps by 1 inside a run
        # and jumps from one run's last entry to the next run's first
        k_o = kept[order]
        b_o = (np.cumsum(kept) - kept)[order]
        src = np.ones(len(keys), np.int64)
        src[np.cumsum(k_o) - k_o] = b_o - np.concatenate(
            [[0], (b_o + k_o - 1)[:-1]])
        np.cumsum(src, out=src)
        cols, data = keys[src], vals[src]
        nnz = indptr[:, -1]
        span.set_metadata(nnz_out=len(cols))
        out = []
        for ln, o in enumerate(np.cumsum(nnz) - nnz):
            n = int(nnz[ln])
            ix, dv = ((cols[o:o + n], data[o:o + n]) if n else
                      (np.full(1, EMPTY, np.int32), np.zeros(1, np.float32)))
            out.append(CSR(jnp.asarray(indptr[ln].astype(np.int32)),
                           jnp.asarray(ix), jnp.asarray(dv), shape))
        return out


def _rows_to_csr(out_rows_k, out_rows_v, shape) -> CSR:
    """Assemble per-row key/value slices into the output CSR (empty-safe)."""
    with trace.span(trace.SPZ_ASSEMBLE) as span:
        rr, cc, vv = [], [], []
        for i, (k, v) in enumerate(zip(out_rows_k, out_rows_v)):
            nz = v != 0.0
            rr.append(np.full(int(nz.sum()), i, np.int64))
            cc.append(k[nz])
            vv.append(v[nz])
        if not rr:
            span.set_metadata(nnz_out=0)
            return csr_from_coo([], [], [], shape)
        cols = np.concatenate(cc)
        span.set_metadata(nnz_out=len(cols))
        return csr_from_coo(np.concatenate(rr), cols, np.concatenate(vv),
                            shape)


def spgemm_spz(A: CSR, B: CSR, *, R: int = 16, S: int | None = None,
               rsort: bool = False, backend="auto",
               driver: str = "fused"):
    """Merge-based SpGEMM using the SparseZipper primitives.

    R: chunk width (paper: 16; TPU-native: 128).
    S: lock-step stream count per kernel issue (>= R groups batched into one
       dispatch is allowed — stream semantics are independent — and models a
       multi-issue matrix unit; default 32*R).
    rsort: pre-sort row indices by per-row work (spz-rsort).
    backend: kernel backend for the stream primitives — a registered name
       ("xla", "pallas", "ref"), "auto" (pallas on TPU, xla elsewhere),
       or a resolved ``KernelBackend``; unknown names raise ``ValueError``
       listing the registered backends.  All registered backends are
       bit-compatible, so this is purely a performance knob (the dispatch
       layer resolves it once at plan time).
    driver: "fused" (default) — device-resident pipeline: expansion, chunk
       sort, and the whole zip-merge tree run as ONE jitted computation
       per (S, L, R) bucket, with the data-dependent chunk advancement
       under ``jax.lax.while_loop``; "host" — the original lock-step
       Python driver (one kernel issue per chunk), kept for the
       stats-faithful Fig. 9-11 wall-clock breakdown.  Both produce
       identical outputs and identical mssort/mszip instruction counts.
    Returns (CSR, SpzStats)."""
    S = S or 32 * R
    stats = SpzStats()
    if driver not in ("fused", "host"):
        raise ValueError(f"unknown spz driver {driver!r}; use 'fused'|'host'")
    bk = kb.resolve_backend(backend)  # unknown names raise, listing all
    if A.n_rows == 0:
        # zero output rows: concatenating per-row results would raise
        return csr_from_coo([], [], [], (A.n_rows, B.n_cols)), stats
    t0 = time.perf_counter()
    with trace.span(trace.SPZ_PREP, rows=A.n_rows) as span:
        work = row_work(A, B) if (rsort or driver == "fused") else None
        order = (np.argsort(work, kind="stable") if rsort
                 else np.arange(A.n_rows))
        if driver == "fused":
            mats = fused_operands(A.indptr[None], A.indices[None],
                                  A.data[None], B.indptr[None],
                                  B.indices[None], B.data[None])
        if work is not None:
            span.set_metadata(products=int(work.sum()))
    stats.t_preprocess = time.perf_counter() - t0
    if driver == "host":
        out_rows_k, out_rows_v = _spz_host_driver(A, B, R, S, order, bk,
                                                  stats)
        t3 = time.perf_counter()
        out = _rows_to_csr(out_rows_k, out_rows_v, (A.n_rows, B.n_cols))
    else:
        runs = _spz_fused_driver(A, R, S, order, work, mats, bk, stats)
        t3 = time.perf_counter()
        out, = _runs_to_csrs(runs, (A.n_rows, B.n_cols))
    stats.t_output = time.perf_counter() - t3
    return out, stats


def spgemm(A: CSR, B: CSR, method: str = "spz", **kw):
    """Deprecated front-end: use ``repro.core.spgemm(A, B, engine=...)``
    (the canonical dispatch entry re-exported by ``repro.core``).

    ``method`` names map 1:1 onto registered dispatch engines, so this
    thin alias delegates straight to the registry and will be removed
    once nothing imports it."""
    import warnings

    from repro.core import dispatch
    warnings.warn(
        "repro.core.spgemm.spgemm(method=...) is deprecated; call the "
        "canonical repro.core spgemm (core.dispatch.spgemm) with "
        "engine=... instead", DeprecationWarning, stacklevel=2)
    return dispatch.spgemm(A, B, engine=method, **kw)
