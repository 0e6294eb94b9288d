"""Kernel-backend registry: one interface over the XLA / Pallas / reference
implementations of the zipper stream primitives.

SparseZipper's pitch is that one micro-architectural substrate (the
systolic array) serves both dense GEMM and the stream sort/merge
primitives.  This reproduction's analogue of "substrate" is the kernel
implementation tier, and this module makes it a first-class, planned
dimension instead of an ``impl=`` string threaded through every call
site: a :class:`KernelBackend` bundles the four stream primitives —

  ``chunk_sort``        (N, R) chunk sort/combine/compress, traceable
                        inside the fused pipeline's jitted buckets
  ``stream_sort``       host-tier mssortk+mssortv kernel issue
  ``stream_merge``      host-tier mszipk+mszipv kernel issue
  ``merge_partitions``  device-resident full partition merge (the
                        zip-merge tree's primitive)

— plus the optional whole-pipeline slot

  ``fused_bucket``      sort + the entire zip-merge tree for one
                        (S, L, R) work bucket as ONE kernel issue
                        (``None``: the driver composes chunk_sort +
                        the XLA merge tree instead)

— plus declared capabilities, and the registry resolves a backend ONCE
(at plan time, in ``core/dispatch.py``) rather than per kernel issue.
Registered instances:

  ``xla``     pure-jnp oracles jitted as XLA computations (the driver
              workhorse off-TPU)
  ``pallas``  ``pl.pallas_call`` kernels, compiled by Mosaic on a TPU
              and run in interpret mode everywhere else (the one place
              that choice is made is :func:`_interpret` below): the
              native chunk-sort, the native
              ``merge_partitions`` bitonic-merge kernel, and the
              single-kernel fused bucket pipeline (chunks stay in VMEM
              across merge rounds) — all bit-identical to ``xla``
  ``ref``     the unjitted pure-jnp oracles (eager; debugging)

Every backend here is bit-compatible: same keys, values, lengths, and
instruction counters on the same inputs, so engine selection is purely a
performance decision.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

import jax

from repro.kernels import merge_tree, ref
from repro.kernels.chunk_sort import chunk_sort_pallas
from repro.kernels.fused_bucket import fused_bucket_pallas
from repro.kernels.merge_partitions import merge_partitions_pallas
from repro.kernels.stream_merge import stream_merge_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """A registered kernel implementation tier and its capabilities.

    ``on_device``: kernels lower into jitted device computations (False
    for the eager reference oracles).  ``counters_exact``: instruction
    counters derived from this backend's kernels match the host driver's
    per-issue accounting exactly (a future approximate TPU merge kernel
    would declare False and be skipped where exact Fig. 10/11 stats are
    required).  ``measure``: candidate for autotune measurement.
    ``needs_tpu_for_perf``: off-TPU this backend runs in a degraded mode
    (Pallas interpret) where timing it is meaningless — autotune sweeps
    include it on real TPU hardware only, and a cached plan recorded on
    a TPU host falls back to "auto" when replayed elsewhere."""

    name: str
    chunk_sort: Callable
    stream_sort: Callable
    stream_merge: Callable
    merge_partitions: Callable
    fused_bucket: Optional[Callable] = None
    on_device: bool = True
    counters_exact: bool = True
    measure: bool = True
    needs_tpu_for_perf: bool = False
    description: str = ""


_BACKENDS: dict[str, KernelBackend] = {}


def register_backend(**fields) -> KernelBackend:
    """Register (or replace) a backend; see :class:`KernelBackend`."""
    bk = KernelBackend(**fields)
    _BACKENDS[bk.name] = bk
    return bk


def get_backend(name: str) -> KernelBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel backend {name!r}; registered: "
            f"{sorted(_BACKENDS)} (or 'auto')") from None


def resolve_backend(backend: Union[str, KernelBackend] = "auto",
                    ) -> KernelBackend:
    """Resolve a backend request — a registered name, "auto" (pallas on
    TPU, xla elsewhere), or an already-resolved instance — to the
    :class:`KernelBackend`.  Unknown names raise ``ValueError`` listing
    the registered backends."""
    if isinstance(backend, KernelBackend):
        return backend
    if backend == "auto":
        return _BACKENDS["pallas" if on_tpu() else "xla"]
    return get_backend(backend)


def available_backends() -> dict[str, KernelBackend]:
    """Snapshot of the registry (name -> backend)."""
    return dict(_BACKENDS)


def measurable_backends() -> list[KernelBackend]:
    """Backends worth timing on THIS host — the autotune sweep space.
    Filters ``measure=False`` tiers and, off-TPU, tiers that would be
    measured in a degraded mode (``needs_tpu_for_perf``)."""
    return [bk for bk in _BACKENDS.values()
            if bk.measure and (on_tpu() or not bk.needs_tpu_for_perf)]


# jitted oracles: the xla tier is the driver workhorse off-TPU (SpGEMM
# chunk loops), where eager dispatch of the vmap/segment_sum graph would
# dominate
_sort_ref = jax.jit(ref.stream_sort_ref)
_merge_ref = jax.jit(ref.stream_merge_ref)


def _interpret() -> bool:
    """The Pallas kernels compile for the chip on a TPU host; anywhere
    else (the CPU test host) they run in interpret mode.  The kernel
    wrappers default to compiled, so this is the only place interpret
    mode is chosen."""
    return not on_tpu()


def _pallas_chunk_sort(keys, vals, lens):
    return chunk_sort_pallas(keys, vals, lens, interpret=_interpret())


def _pallas_stream_merge(ka, va, la, kb, vb, lb):
    return stream_merge_pallas(ka, va, la, kb, vb, lb,
                               interpret=_interpret())


def _pallas_merge_partitions(ka, va, la, kb, vb, lb, *, R,
                             pair_streams=None, with_counters=True):
    return merge_partitions_pallas(ka, va, la, kb, vb, lb, R=R,
                                   pair_streams=pair_streams,
                                   with_counters=with_counters,
                                   interpret=_interpret())


def _pallas_fused_bucket(keys, vals, plens, *, R, with_counters=True,
                         detailed=False):
    return fused_bucket_pallas(keys, vals, plens, R=R,
                               with_counters=with_counters,
                               detailed=detailed, interpret=_interpret())


register_backend(
    name="xla",
    chunk_sort=merge_tree.sort_chunks_linear,
    stream_sort=_sort_ref,
    stream_merge=_merge_ref,
    merge_partitions=merge_tree.merge_partitions,
    description="pure-jnp oracles jitted as XLA computations; the "
                "scatter-free sort_chunks_linear is the fused sort stage")
register_backend(
    name="pallas",
    chunk_sort=_pallas_chunk_sort,
    # one mssort issue over S streams is one chunk-sort issue over S chunks
    stream_sort=_pallas_chunk_sort,
    stream_merge=_pallas_stream_merge,
    merge_partitions=_pallas_merge_partitions,
    fused_bucket=_pallas_fused_bucket,
    needs_tpu_for_perf=True,
    description="pl.pallas_call kernels (Mosaic on TPU, interpret mode "
                "elsewhere); the "
                "native chunk-sort, bitonic merge_partitions, and the "
                "single-kernel fused bucket pipeline (VMEM-resident "
                "merge tree)")
register_backend(
    name="ref",
    chunk_sort=ref.stream_sort_ref,
    stream_sort=ref.stream_sort_ref,
    stream_merge=ref.stream_merge_ref,
    merge_partitions=merge_tree.merge_partitions,
    on_device=False,
    measure=False,
    description="unjitted pure-jnp oracles (eager; debugging tier)")
