"""Per-product sums over one of the program's spans, for the readers
of metrics that ``program_trace.metrics`` does not compute.

A span counts toward a product where it lies inside that product's
``bench.product`` span, on the same thread.  Where the trace holds no
such span (a program without it, or another engine's products) the sum
is ``None``, so the metric is left out of the result line.
"""
from __future__ import annotations

import program_trace


def per_product(run, name: str, value) -> float | None:
    """The sum of ``value(span)`` over the ``name`` spans inside the
    window's products, over the number of products."""
    if run.program is None:
        return None
    _, products = program_trace.units(run.program, "product")
    spans = [s for s in program_trace.named(run.program, name)
             if any(p.thread == s.thread and p.start <= s.start
                    and s.end <= p.end for p in products)]
    return sum(value(s) for s in spans) / len(products) if spans else None
