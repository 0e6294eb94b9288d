"""Program spans and device scopes: the names the profiler sees.

A span is a ``jax.profiler.TraceAnnotation``: it records only while a
profiler trace is active (``jax.profiler.start_trace`` ... ``stop_trace``),
on the calling thread and on the profiler's one clock with the device's
operations, and its keyword arguments become the event's stats in the
``.xplane.pb``.  With no trace active a span costs about 1.4 us on a
host CPU, so spans open at most once per group and per bucket: never
per row, nor per request inside a loop.  Stat values are ints or
strings without commas or ``#`` (the profiler's encoding splits stats on
them), so lists are space-joined.

Spans, where they open, their stats, and what reads them
(``bench/program_trace.py``):

``repro.engine``
    ``dispatch.execute``, ``dispatch.execute_batched``'s driver call,
    and each device group's batched driver call in
    ``spgemm_shard._execute_groups``.  ``engine``, ``backend``,
    ``lanes``.
``repro.spz.prep``
    the spz drivers' set-up: ``row_work``, the row order and
    ``fused_operands`` (``spgemm_spz``); the per-lane ``row_work``, the
    ``(lane, row)`` items and ``fused_operands``, or the host driver's
    per-lane copies and output dicts (``dispatch._spz_batched``).
    ``rows``, ``products``.  Read as ``driver.prep_ms``.
``repro.spz.groups``
    the whole lock-step group loop.  ``groups``.  Device-idle time
    inside it is ``driver.idle_ms``.
``repro.spz.group``
    one ``fused_process_group`` call.  ``items``, ``products``,
    ``buckets``.
``repro.spz.launch`` / ``repro.spz.fetch`` / ``repro.spz.unpack``
    in each bucket of a group: the ``_fused_bucket`` call; the copies
    of its keys, values, lengths and round counters to the host; the
    counters' reduction and the bucket's merged runs added to the
    group's parts.
    ``streams`` (the padded stream count), ``used`` (real streams),
    ``L`` (stream width).
``repro.spz.assemble``
    the output CSR of every lane.  The fused drivers (single and
    batched) build it in ``_runs_to_csrs`` without a sort: ``indptr`` a
    prefix sum of the merged runs' lengths, each run copied to its row's
    offset.  The host drivers keep ``_rows_to_csr`` and
    ``_spz_batched``'s per-row loop with ``csr_from_coo``.  ``nnz_out``
    (the output's nonzeros, over all lanes).  Read as
    ``output.assemble_ms``.
``repro.esc.device``
    ``spgemm_esc``: the jitted expand-sort-compress program's dispatch,
    waited on until it completes, so the next span times the copy alone.
    ``products`` (``cap_products``, the program's static length),
    ``rows``.
``repro.esc.fetch``
    the copy of its outputs to the host: rows, columns, values and the
    valid mask, each ``cap_products`` long, and the valid count.  ``bytes`` (what is copied),
    ``entries`` (the valid output count).  Read as ``esc.fetch_ms`` and,
    from ``bytes``, ``esc.fetch_mb``.
``repro.esc.assemble``
    the host COO->CSR of the valid entries (``csr_from_coo``) and its
    upload.  Read as ``esc.assemble_ms``.
``repro.serve.submit``
    ``SpGemmService.submit`` (a flush it triggers runs inside it).
    ``request`` (the id).
``repro.serve.flush``
    one bucket's supervised ladder (``_run_ladder``), inline or on a
    flush thread.  ``requests`` (space-joined ids), ``reason``,
    ``bucket``.  Its start minus each id's ``repro.serve.submit`` start
    is ``serve.queue_ms``.
``repro.serve.batch`` / ``repro.serve.plan`` / ``repro.serve.check``
    one ladder attempt's ``batch_csr`` of both operands; its
    ``plan_sharded`` and sticky-cap pinning; the screening of every
    lane's output.  ``lanes``.
``repro.shard.assemble``
    ``dispatch.assemble_batched``: the lanes' CSRs stacked into the
    output ``BatchedCSR``.  ``lanes``.

Device scopes (``jax.named_scope``, in each operation's HLO
``op_name``) of the bucket program ``_fused_bucket_impl``:
``spz.expand`` (``_fused_expand``) and ``spz.sort_merge``
(``stream.fused_sort_merge``), read as ``device.expand_ms`` and
``device.sort_merge_ms``.  Each Pallas kernel of the spz path is named
after its kernel function (``kernels/_network.stream_call``).  The ESC
program ``esc_core_impl`` has ``esc.expand`` (the partial products by a
search of A's work prefix), ``esc.sort`` (the two stable argsorts) and
``esc.compress`` (the segment sum and scatters); ``bench/run.py`` does
not split by them yet.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

ENGINE = "repro.engine"
SPZ_PREP = "repro.spz.prep"
SPZ_GROUPS = "repro.spz.groups"
SPZ_GROUP = "repro.spz.group"
SPZ_LAUNCH = "repro.spz.launch"
SPZ_FETCH = "repro.spz.fetch"
SPZ_UNPACK = "repro.spz.unpack"
SPZ_ASSEMBLE = "repro.spz.assemble"
SERVE_SUBMIT = "repro.serve.submit"
SERVE_FLUSH = "repro.serve.flush"
SERVE_BATCH = "repro.serve.batch"
SERVE_PLAN = "repro.serve.plan"
SERVE_CHECK = "repro.serve.check"
SHARD_ASSEMBLE = "repro.shard.assemble"
ESC_DEVICE = "repro.esc.device"
ESC_FETCH = "repro.esc.fetch"
ESC_ASSEMBLE = "repro.esc.assemble"

EXPAND = "spz.expand"
SORT_MERGE = "spz.sort_merge"
ESC_EXPAND = "esc.expand"
ESC_SORT = "esc.sort"
ESC_COMPRESS = "esc.compress"


# ``with span(NAME, **stats) as s:``; stats known only inside the span
# are added with ``s.set_metadata(**stats)``
span = TraceAnnotation


def ids(values) -> str:
    """A list stat: the values space-joined."""
    return " ".join(str(v) for v in values)


def bucket(key: tuple) -> str:
    """A service pad bucket ``(A.shape, B.shape, cap_a, cap_b)`` as a
    stat: ``"MxK@KxN/cap_a/cap_b"``."""
    (m, k), (k2, n), cap_a, cap_b = key
    return f"{m}x{k}@{k2}x{n}/{cap_a}/{cap_b}"
