"""TPU-native building blocks shared by the zipper kernels.

The paper routes keys through a 16x16 systolic array of compare-and-route
PEs in two passes (sort/merge, then compress).  On TPU the equivalent
data-parallel structures are compare-exchange networks whose partner
lanes come from vector rotations (``pltpu.roll``) plus a select — no
gather, no lane reversal — log-step scans for duplicate accumulation and
prefix counts, and a log-step shift network for the compress pass.

Tile layout.  Every kernel sees a 2-D ``(T, 128)`` tile: a block of
independent streams laid out row-major, each stream a *segment* of
``2**lg`` consecutive flat positions ``q = row * 128 + lane`` (a segment
narrower than 128 lanes shares a row with its neighbours, a wider one
spans whole rows; T is a power of two).  ``lg`` may be a Python int or
a traced scalar: the network stages run as ``fori_loop``s over the
stride exponent with dynamic rotations, so a kernel's code size does not
grow with the segment width, and the merge tree's rounds can loop too.  The wrapper
``stream_call`` flattens (N, width) operands into that layout and picks
the block, so the tiled grid runs the same way in interpret mode on the
CPU and compiled on the chip.

Invariants the kernels built from these blocks rely on:

  * network widths are powers of two, and a network stage at stride j
    only pairs positions inside one aligned group of 2j, so a segment's
    data never leaves its segment;
  * EMPTY (INT32_MAX) compares greater than every valid key, so
    EMPTY-padded segments sort/merge with the padding parked at the end
    and a key is valid iff it is not EMPTY;
  * the sort and merge networks compare (key, source-lane) pairs
    lexicographically.  Source lanes are unique per segment, so the order
    is total: every correct network yields the same permutation (a
    *stable* sort/merge), which is what makes duplicate-value
    accumulation order deterministic and bit-identical to the XLA
    oracles;
  * ``compact`` moves values, it never recombines them: each valid lane
    moves left by the number of invalid lanes before it, one binary digit
    of that distance per step (lowest first), and no two lanes ever land
    on the same position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import EMPTY

LANES = 128
LOG_LANES = 7
SUBLANES = 8
# elements per block when streams are narrow enough to share one; a
# stream wider than this is a block of its own
BLOCK_ELEMS = 16384


def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _min(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _max(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _pow2(e):
    return 1 << e if _static(e) else jnp.left_shift(jnp.int32(1), e)


def _loop(lo, hi, body, carry):
    if _static(lo, hi) and hi <= lo:
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def seg_pos(shape, lg):
    """Position of each flat tile element inside its 2**lg-wide segment."""
    return (_iota(shape, 0) * LANES + _iota(shape, 1)) & (_pow2(lg) - 1)


def _rot(x, s, axis):
    """Cyclic rotation along one axis (jnp.roll semantics); the axis
    length is a power of two, so any integer shift is reduced with a
    mask."""
    s = s & (x.shape[axis] - 1)
    if _static(s) and s == 0:
        return x
    return pltpu.roll(x, s, axis)


def _roll_flat(x, d):
    """Cyclic roll of the flat row-major tile: out[q] = x[q - d]."""
    d = d & (x.size - 1)
    a, b = d >> LOG_LANES, d & (LANES - 1)
    if _static(b) and b == 0:
        return _rot(x, a, 0)
    y = _rot(x, b, 1)
    return jnp.where(_iota(x.shape, 1) >= b, _rot(y, a, 0), _rot(y, a + 1, 0))


def seg_shift(x, d, lg, fill):
    """Shift within segments: out[q] = x[q - d] (d < 0 shifts left),
    ``fill`` where the source falls outside the segment."""
    src = seg_pos(x.shape, lg) - d
    narrow = _static(lg) and lg <= LOG_LANES
    y = _rot(x, d, 1) if narrow else _roll_flat(x, d)
    return jnp.where((src >= 0) & (src < _pow2(lg)), y, fill)


def _xor_lanes(x, j):
    sel = (_iota(x.shape, 1) & j) == 0
    return jnp.where(sel, _rot(x, -j, 1), _rot(x, j, 1))


def _xor_rows(x, j):
    r = j >> LOG_LANES
    sel = (_iota(x.shape, 0) & r) == 0
    return jnp.where(sel, _rot(x, -r, 0), _rot(x, r, 0))


def strides(lg, fn, carry, reverse=False):
    """carry = fn(shuffle, j, carry) for the strides j = 2**e, e in
    [0, lg) (descending with ``reverse``), where shuffle(x)[q] =
    x[q ^ j] — lane rotations below 128, row rotations above."""
    parts = [(0, _min(lg, LOG_LANES), _xor_lanes),
             (LOG_LANES, _max(lg, LOG_LANES), _xor_rows)]
    for lo, hi, xor in (parts[::-1] if reverse else parts):
        def body(t, c, lo=lo, hi=hi, xor=xor):
            j = _pow2(lo + hi - 1 - t if reverse else t)
            return fn(lambda x: xor(x, j), j, c)
        carry = _loop(lo, hi, body, carry)
    return carry


def seg_allreduce(x, lg, op):
    """Every element gets ``op`` (jnp.maximum / minimum / add) reduced
    over its segment — a butterfly over the xor partners."""
    return strides(lg, lambda sh, j, y: op(y, sh(y)), x)


def seg_cumsum(x, lg):
    """Inclusive prefix sum within segments (Hillis-Steele)."""
    return _loop(0, lg, lambda e, y: y + seg_shift(y, _pow2(e), lg, 0), x)


def _exchange(k, i, v, pk, pi, pv, keep_small):
    """Keep the smaller (key, idx) pair where ``keep_small``, the larger
    elsewhere.  (key, idx) pairs are unique per segment, so self and
    partner never tie."""
    gt = (k > pk) | ((k == pk) & (i > pi))
    take = gt == keep_small
    return jnp.where(take, pk, k), jnp.where(take, pi, i), jnp.where(take, pv, v)


def _half_cleaners(kiv, lg, pos, asc):
    """Compare-exchange at strides 2**(lg-1) .. 1, ascending where
    ``asc``: sorts every bitonic 2**lg-wide run."""
    def fn(sh, j, c):
        k, i, v = c
        return _exchange(k, i, v, sh(k), sh(i), sh(v),
                         ((pos & j) == 0) == asc)
    return strides(lg, fn, kiv, reverse=True)


def sort_segments(k, v, lg):
    """Stable ascending bitonic sort of every segment by (key, position)."""
    pos = seg_pos(k.shape, lg)

    def stage(b, kiv):
        asc = ((pos & _pow2(b)) == 0) | (b == lg)
        return _half_cleaners(kiv, b, pos, asc)
    k, _, v = _loop(1, lg + 1, stage, (k, pos, v))
    return k, v


def merge_segments(k, v, lg):
    """Stable merge of the two ascending halves of every segment (the
    lower half's lanes rank first on equal keys).  The first stage pairs
    each lane with its mirror in the segment, so both halves are then
    bitonic and the usual half-cleaners finish the merge."""
    pos = seg_pos(k.shape, lg)
    mk, mi, mv = strides(lg, lambda sh, j, c: tuple(sh(a) for a in c),
                         (k, pos, v))
    kiv = _exchange(k, pos, v, mk, mi, mv, pos < _pow2(lg) // 2)
    k, _, v = _half_cleaners(kiv, lg - 1, pos, True)
    return k, v


def compact(k, v, lg):
    """Move the valid (non-EMPTY) lanes of every segment to its front, in
    order; EMPTY/0 behind them."""
    valid = (k != EMPTY).astype(jnp.int32)
    dist = (seg_pos(k.shape, lg) + 1 - seg_cumsum(valid, lg)) * valid

    def step(e, c):
        k, v, dist, valid = c
        d = _pow2(e)
        stay = (valid == 1) & ((dist & d) == 0)
        come = seg_shift(valid * (dist & d), -d, lg, 0) != 0
        return (jnp.where(come, seg_shift(k, -d, lg, EMPTY),
                          jnp.where(stay, k, EMPTY)),
                jnp.where(come, seg_shift(v, -d, lg, 0.0),
                          jnp.where(stay, v, 0.0)),
                jnp.where(come, seg_shift(dist, -d, lg, 0),
                          jnp.where(stay, dist, 0)),
                (come | stay).astype(jnp.int32))
    k, v, _, _ = _loop(0, lg, step, (k, v, dist, valid))
    return k, v


def combine_pairs(k, v, lg):
    """After a merge of two duplicate-free runs: a key occurs at most
    twice, so its value lands on the second copy as the single add of the
    two, and the first copy becomes EMPTY/0."""
    prev = seg_shift(k, 1, lg, EMPTY)
    nxt = seg_shift(k, -1, lg, EMPTY)
    v = jnp.where((k == prev) & (k != EMPTY),
                  v + seg_shift(v, 1, lg, 0.0), v)
    last = (k != nxt) & (k != EMPTY)
    return jnp.where(last, k, EMPTY), jnp.where(last, v, 0.0)


def mask_to_lens(keys, vals, lens):
    """(N, W) operands with EMPTY/0 past each row's valid length and the
    values as f32 — what the kernels take, so a key is valid iff it is
    not EMPTY."""
    ok = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :] < lens[:, None]
    return (jnp.where(ok, keys, EMPTY),
            jnp.where(ok, vals.astype(jnp.float32), 0.0))


def stream_call(kernel, ins, outs, *, width, interpret):
    """Run ``kernel`` over (N, width) stream operands in the tile layout.

    ins: [(array (N, width), pad value)]; outs: output dtypes, each an
    (N, width) array.  Streams are flattened row-major into (rows, 128)
    tiles; a block holds a power-of-two count of whole streams, at least
    one (8, 128) tile and at most about ``BLOCK_ELEMS`` elements unless
    one stream is wider; N is padded to whole blocks with the given pad
    values.  ``kernel(*in_refs, *out_refs)`` sees (T, 128) blocks; the
    call is named after the kernel function (through a
    ``functools.partial``).  Returns the outputs as (N, width) arrays."""
    N = ins[0][0].shape[0]
    assert width & (width - 1) == 0, f"stream width {width} must be a power of two"
    per_tile = max(1, SUBLANES * LANES // width)
    want = min(max(1, BLOCK_ELEMS // (per_tile * width)), -(-N // per_tile))
    spb = per_tile * (1 << (want.bit_length() - 1))
    Np = -(-max(N, 1) // spb) * spb
    rows = spb * width // LANES
    flat = [jnp.pad(x, ((0, Np - N), (0, 0)), constant_values=fill)
            .reshape(Np * width // LANES, LANES) for x, fill in ins]
    spec = pl.BlockSpec((rows, LANES), lambda b: (b, 0))
    res = pl.pallas_call(
        kernel,
        name=getattr(kernel, "func", kernel).__name__,
        grid=(Np // spb,),
        in_specs=[spec] * len(flat),
        out_specs=[spec] * len(outs),
        out_shape=[jax.ShapeDtypeStruct((Np * width // LANES, LANES), dt)
                   for dt in outs],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(rows)),
        interpret=interpret,
    )(*flat)
    return [r.reshape(Np, width)[:N] for r in res]


def _vmem_limit(rows):
    """Scoped VMEM for one block: the networks keep a few dozen
    block-sized temporaries live."""
    return min(max(32 << 20, 64 * rows * LANES * 4), 100 << 20)
