"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

From the device planes (``/device:TPU:<n>``): the busy time (the union
of the intervals in which an operation ran), the device time and the
execution count of each XLA module, and the idle gaps.  From the host
plane: the benchmark's own spans (``jax.profiler.TraceAnnotation``
names starting with ``bench.``), which give the measured window, and
the runtime's own events on the same thread (``np.asarray(jax.Array)``,
``PjitFunction(...)``, ...).  Each gap is labelled with the innermost
benchmark span open at its middle and, where one is open there, the
innermost runtime event: ``bench.execute`` alone means the host was in
the program's Python code.  Timestamps of both kinds of plane are on
the profiler's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float            # mean over the device planes used
    modules: dict            # module -> [executions, device seconds]
    gaps: list               # [(label, seconds)], longest first
    idle_by_label: dict      # label -> idle seconds in all its gaps
    spans: list              # [(name, start_ns, end_ns)] inside the window
    last_device_end: dict    # span index -> end of its last device op (ns)
    n_devices: int


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``: drop the program id the profiler
    appends, so one module's executions add up under one name."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_profile(pd, span_names=("bench.product", "bench.round")
                   ) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``.  ``span_names`` are the
    spans of one operation each, for which the end of the last device
    op inside them is recorded."""
    spans, calls = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
            if any(e[0].startswith(SPAN_PREFIX) for e in evs):
                spans += [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                calls += [e for e in evs if not e[0].startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    _, w0, w1 = windows[0]
    spans = sorted((s for s in spans if s[0] != WINDOW
                    and s[1] >= w0 and s[2] <= w1), key=lambda s: s[1])
    modules: dict = {}
    busy_by_plane = []
    all_busy = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = [(module_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                for e in lines.get(MODULE_LINE, [])]
        ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for e in lines.get(OPS_LINE, [])] or [m[1:] for m in mods]
        busy = _union((max(s, w0), min(e, w1)) for s, e in ops
                      if e > w0 and s < w1)
        if not busy:
            continue
        busy_by_plane.append(sum(e - s for s, e in busy))
        all_busy.extend(busy)
        for name, s, e in mods:
            if e > w0 and s < w1:
                rec = modules.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += (min(e, w1) - max(s, w0)) * 1e-9
    busy = _union(all_busy)
    gaps, t = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    calls.sort(key=lambda c: c[1])
    starts = [c[1] for c in calls]
    labelled = sorted(((_label(spans, calls, starts, (a + b) / 2),
                        (b - a) * 1e-9) for a, b in gaps),
                      key=lambda g: -g[1])
    idle_by_label: dict = {}
    for label, sec in labelled:
        idle_by_label[label] = idle_by_label.get(label, 0.0) + sec
    ends = sorted(e for _, e in busy)
    last = {}
    for i, (name, s, e) in enumerate(spans):
        if name in span_names:
            j = _last_at_or_before(ends, e)
            if j is not None and ends[j] > s:
                last[i] = ends[j]
    n_dev = len(busy_by_plane)
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=(sum(busy_by_plane) / n_dev * 1e-9) if n_dev else 0.0,
        modules=modules, gaps=labelled, idle_by_label=idle_by_label,
        spans=spans,
        last_device_end=last, n_devices=n_dev)


def _label(spans, calls, starts, t, lookback=64) -> str:
    inner = [s for s in spans if s[1] <= t <= s[2]]
    label = min(inner, key=lambda s: s[2] - s[1])[0] if inner else WINDOW
    # the innermost runtime event open at t: the latest-starting one
    # that has not ended; runtime events are short, so look back a few
    j = bisect.bisect_right(starts, t) - 1
    for c in calls[max(0, j - lookback):j + 1][::-1]:
        if c[2] >= t:
            return f"{label}/{c[0]}"
    return label


def _last_at_or_before(sorted_vals, t):
    j = bisect.bisect_right(sorted_vals, t) - 1
    return j if j >= 0 else None


def reduce_file(path: str, **kw) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), **kw)
