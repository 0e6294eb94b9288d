"""Read the program's own spans and device scopes from a profiler trace.

    python3 bench/program_trace.py TRACE.xplane.pb

``trace_reduce.py`` reads the benchmark's spans (``bench.*``) and the
device's busy time.  This module reads what the program writes into the
same trace (``src/repro/core/trace.py``): its host spans (``repro.*``,
with their stats), and the device time of the operations under each
``jax.named_scope`` of its bucket programs.  A device operation's scope
is in its HLO instruction's ``metadata.op_name``; the trace's
``/host:metadata`` plane carries each module's ``HloProto`` (a TPU trace
does so with ``ProfileOptions.enable_hlo_proto`` on or off), read here
from the protobuf wire format (no generated stubs).

Each idle gap of the device is labelled
``<innermost bench span>/<innermost repro span>[/<runtime event>]``.
The per-layer numbers (``metrics``) are per product (``bench.product``
spans) where the trace has products, else per flush
(``repro.serve.flush`` spans):

- ``driver.prep_ms``: host time in ``repro.spz.prep``;
- ``driver.idle_ms``: device-idle time inside ``repro.spz.groups`` (no
  device busy; left out of a trace with no device plane);
- ``output.assemble_ms``: host time in ``repro.spz.assemble``;
- ``serve.queue_ms``: mean over requests of the start of the flush whose
  ``requests`` holds the id less the start of its ``repro.serve.submit``;
- ``device.expand_ms``, ``device.sort_merge_ms``: device time of the
  operations under ``spz.expand`` and ``spz.sort_merge``, summed over
  the devices.

On several devices a scope's time is taken on each device's own
timeline and summed; the device is idle where none of them is busy.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import sys

from trace_reduce import (DEVICE_PLANE, MODULE_LINE, OPS_LINE, SPAN_PREFIX,
                          WINDOW, _union, module_name)

PREFIX = "repro."
SCOPES = ("spz.expand", "spz.sort_merge")
BUCKET_MODULE = "jit__fused_bucket_impl"
METADATA_PLANE = "/host:metadata"
# the phases of one product (inside ``bench.execute``) and of one flush
PRODUCT_PHASES = ("repro.spz.prep", "repro.spz.groups", "repro.spz.assemble")
# the spans ``metrics`` reads inside a product or a flush
UNIT_PHASES = ("repro.spz.prep", "repro.spz.groups", "repro.spz.assemble")
FLUSH_PHASES = ("repro.serve.batch", "repro.serve.plan", "repro.engine",
                "repro.shard.assemble", "repro.serve.check")


@dataclasses.dataclass
class Span:
    name: str
    start: float     # ns, the profiler's clock
    end: float
    thread: str
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class ProgramReduction:
    window: tuple            # (start, end) ns
    program_spans: list      # [Span] of repro.*, inside the window
    bench_spans: list        # [Span] of bench.*, inside the window
    idle: list               # [(start, end)] ns: no device busy
    gaps: list               # [(label, seconds)], longest first
    scope_iv: dict           # scope ("" for none) -> {device plane:
    #                          [(start, end)] ns, sorted and disjoint}
    module_s: dict           # module -> device seconds
    unscoped_s: dict         # instruction -> device seconds, no scope
    has_hlo: bool            # the trace carried the modules' HLO
    device_busy_s: dict      # device plane -> busy seconds in the window

    @property
    def scope_s(self) -> dict:
        """Scope -> device seconds in the window, over all devices."""
        return {k: sum(e - s for iv in v.values() for s, e in iv) * 1e-9
                for k, v in self.scope_iv.items()}


# -- the protobuf wire format -------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def fields(buf):
    """``(field number, value)`` of each field of a protobuf message:
    ints for varints, bytes for length-delimited and fixed fields."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} is not supported")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _planes(space: bytes):
    """XSpace.planes (1): name (2), event_metadata (4: map of id to
    XEventMetadata), stat_metadata (5: map of id to XStatMetadata)."""
    for num, plane in fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for k, v in fields(plane):
            if k == 2:
                name = _text(v)
            elif k == 4:
                events.append(dict(fields(v)).get(2, b""))
            elif k == 5:
                entry = dict(fields(v))
                meta = dict(fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        yield name, events, stat_names


def _event_metadata(event: bytes, stat_names: dict):
    """XEventMetadata: id (1), name (2), stats (5: XStat, metadata_id
    (1) and one value field)."""
    ident, name, stats = 0, "", {}
    for k, v in fields(event):
        if k == 1:
            ident = v
        elif k == 2:
            name = _text(v)
        elif k == 5:
            stat = dict(fields(v))
            key = stat_names.get(stat.pop(1, 0), "?")
            stats[key] = next(iter(stat.values()), None)
    return ident, name, stats


# -- HLO: each instruction's scope ----------------------------------------

def _instructions(module: bytes):
    """HloModuleProto.computations (3): id (5), instructions (2: name
    (1), opcode (2), metadata (7: op_name (2)), id (35), operand_ids
    (36), called_computation_ids (38))."""
    for num, comp in fields(module):
        if num != 3:
            continue
        comp_id, instrs = None, []
        for k, v in fields(comp):
            if k == 5:
                comp_id = v
            elif k == 2:
                ins = {"name": "", "opcode": "", "op_name": "", "id": None,
                       "operands": [], "calls": []}
                for k2, v2 in fields(v):
                    if k2 == 1:
                        ins["name"] = _text(v2)
                    elif k2 == 2:
                        ins["opcode"] = _text(v2)
                    elif k2 == 7:
                        ins["op_name"] = _text(dict(fields(v2)).get(2, b""))
                    elif k2 == 35:
                        ins["id"] = v2
                    elif k2 == 36:
                        ins["operands"].extend(_packed(v2))
                    elif k2 == 38:
                        ins["calls"].extend(_packed(v2))
                instrs.append(ins)
        yield comp_id, instrs


def _packed(v):
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def scope_of(op_name: str, scopes=SCOPES) -> str:
    """The first of ``scopes`` on an ``op_name``'s name stack, or ""."""
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return ""


# instructions whose op_name says nothing of the phase they serve: the
# compiler shares constants between phases and moves them into loops
NO_VOTE = {"constant", "parameter", "get-tuple-element", "tuple"}


def hlo_scopes(module: bytes, scopes=SCOPES) -> dict:
    """Instruction name -> scope for one serialized ``HloModuleProto``.
    An instruction takes the scope its own ``op_name`` names, else the
    one the instructions of the computations it calls agree on (a
    fusion, a loop).  One still without a scope (a copy or a loop the
    compiler made) takes the scope its users agree on, else the one
    the callers of its computation agree on, until nothing changes."""
    comps = dict(_instructions(module))

    def own(ins):
        return ("" if ins["opcode"] in NO_VOTE
                else scope_of(ins["op_name"], scopes))

    inner = {cid: {own(i) for i in instrs} - {""}
             for cid, instrs in comps.items()}

    def called(cid, seen):
        found = set(inner.get(cid, ()))
        for ins in comps.get(cid, ()):
            for c in ins["calls"]:
                if c not in seen:
                    seen.add(c)
                    found |= called(c, seen)
        return found

    scope, users, callers, home = {}, {}, {}, {}
    for cid, instrs in comps.items():
        for ins in instrs:
            found = {own(ins)} - {""}
            if not found:
                for c in ins["calls"]:
                    found |= called(c, {c})
            scope[ins["id"]] = found.pop() if len(found) == 1 else ""
            home[ins["id"]] = cid
            for o in ins["operands"]:
                users.setdefault(o, []).append(ins["id"])
            for c in ins["calls"]:
                callers.setdefault(c, []).append(ins["id"])
    changed = True
    while changed:
        changed = False
        for i, sc in scope.items():
            if sc:
                continue
            for near in (users.get(i, ()), callers.get(home[i], ())):
                found = {scope[u] for u in near} - {""}
                if len(found) == 1:
                    scope[i] = found.pop()
                    changed = True
                    break
    return {ins["name"]: scope[ins["id"]]
            for instrs in comps.values() for ins in instrs}


def _module_proto(hlo_proto: bytes) -> bytes:
    """The ``HloModuleProto`` (field 1) of an ``HloProto``."""
    return dict(fields(hlo_proto)).get(1, b"")


def trace_hlo(space: bytes, scopes=SCOPES) -> dict:
    """Program id -> {instruction name: scope}, from the metadata
    plane's ``hlo_proto`` stats."""
    out = {}
    for name, events, stat_names in _planes(space):
        if name != METADATA_PLANE:
            continue
        for ev in events:
            ident, _, stats = _event_metadata(ev, stat_names)
            proto = stats.get("Hlo Proto", stats.get("hlo_proto"))
            if isinstance(proto, memoryview):
                out[ident] = hlo_scopes(_module_proto(proto), scopes)
    return out


def _program_id(module_event: str):
    """``jit_f(1234)`` -> 1234."""
    head, _, tail = module_event.rpartition("(")
    return int(tail[:-1]) if head and tail[:-1].isdigit() else None


def _instruction(op_event: str) -> str:
    """``%fusion.39 = s32[...] fusion(...)`` -> ``fusion.39``."""
    return op_event.split(" ", 1)[0].lstrip("%")


# -- the reduction --------------------------------------------------------

def reduce_profile(pd, hlo: dict | None = None) -> ProgramReduction:
    """Reduce a ``jax.profiler.ProfileData``; ``hlo`` is
    :func:`trace_hlo` of the same trace."""
    hlo = hlo or {}
    bench, program, runtime = [], [], {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                span = Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            line.name, {})
                if ev.name.startswith(PREFIX):
                    span.stats = dict(ev.stats)
                    program.append(span)
                elif ev.name.startswith(SPAN_PREFIX):
                    bench.append(span)
                else:
                    runtime.setdefault(line.name, []).append(span)
    windows = [s for s in bench if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0].start, windows[0].end

    def inside(spans):
        return sorted((s for s in spans if s.start >= w0 and s.end <= w1),
                      key=lambda s: (s.start, -s.end))
    bench = [s for s in inside(bench) if s.name != WINDOW]
    program = inside(program)
    busy, scope_iv, module_s, unscoped, device_busy = [], {}, {}, {}, {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        scoped, plane_busy = [], []
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines.get(MODULE_LINE, []))
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            if e > w0 and s < w1:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0.0) + \
                    (min(e, w1) - max(s, w0)) * 1e-9
        for ev in lines.get(OPS_LINE, []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if not (e > w0 and s < w1):
                continue
            s, e = max(s, w0), min(e, w1)
            plane_busy.append((s, e))
            j = bisect.bisect_right(starts, ev.start_ns) - 1
            scopes = hlo.get(_program_id(mods[j][2])) if j >= 0 else None
            scope = (scopes or {}).get(_instruction(ev.name), "")
            scoped.append((s, e, scope))
            if not scope:
                op = _instruction(ev.name)
                unscoped[op] = unscoped.get(op, 0.0) + (e - s) * 1e-9
        if not plane_busy:
            continue
        busy += plane_busy
        device_busy[plane.name] = 1e-9 * sum(
            e - s for s, e in _union(plane_busy))
        for s, e, scope in _exclusive(scoped):
            scope_iv.setdefault(scope, {}).setdefault(
                plane.name, []).append((s, e))
    busy = _union(busy)
    idle, t = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    labels = _labels(bench, program, runtime, [(a + b) / 2 for a, b in idle])
    gaps = sorted(((label, (b - a) * 1e-9) for label, (a, b)
                   in zip(labels, idle)), key=lambda g: -g[1])
    return ProgramReduction(window=(w0, w1), program_spans=program,
                            bench_spans=bench, idle=idle, gaps=gaps,
                            scope_iv={k: {d: [tuple(iv) for iv in _union(v)]
                                          for d, v in by_dev.items()}
                                      for k, by_dev in scope_iv.items()},
                            module_s=module_s, unscoped_s=unscoped,
                            has_hlo=bool(hlo), device_busy_s=device_busy)


def _exclusive(ops):
    """Split nested ``(start, end, scope)`` operations (a loop holds its
    body's operations) into disjoint ``(start, end, scope)`` pieces, each
    instant going to the innermost operation running then."""
    out, stack, t = [], [], None
    for s, e, scope in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, sc = stack.pop()
            if end > t:
                out.append((t, end, sc))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s if t is None else max(t, s)
        stack.append((min(e, stack[-1][0]) if stack else e, scope))
    while stack:
        end, sc = stack.pop()
        if end > t:
            out.append((t, end, sc))
            t = end
    return out


def _labels(bench, program, runtime, times) -> list:
    """``bench/repro[/runtime]`` at each of the sorted ``times``: the
    innermost span of each kind open there.  Spans on one thread nest,
    so one sweep with a stack of open spans per thread answers all."""
    by_thread: dict = {}
    for s in bench + program:
        by_thread.setdefault(s.thread, []).append(s)
    for thread, spans in runtime.items():
        if thread in by_thread:
            by_thread[thread] = by_thread[thread] + spans
    open_at = [dict() for _ in times]
    for thread, spans in by_thread.items():
        spans.sort(key=lambda s: (s.start, -s.end))
        stack, i = [], 0
        for q, t in enumerate(times):
            while i < len(spans) and spans[i].start <= t:
                while stack and stack[-1].end < spans[i].start:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1].end < t:
                stack.pop()
            for s in reversed(stack):
                if s.end < t:
                    continue
                kind = (SPAN_PREFIX if s.name.startswith(SPAN_PREFIX) else
                        PREFIX if s.name.startswith(PREFIX) else "")
                open_at[q].setdefault(kind, s)
    out = []
    for found in open_at:
        label = found[SPAN_PREFIX].name if SPAN_PREFIX in found else WINDOW
        for kind in (PREFIX, ""):
            if kind in found:
                label += "/" + found[kind].name
        out.append(label)
    return out


def reduce_file(path: str) -> ProgramReduction:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        space = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(space),
                          trace_hlo(space))


# -- the per-layer numbers ------------------------------------------------

def named(r: ProgramReduction, name: str) -> list:
    return [s for s in r.program_spans if s.name == name]


def units(r: ProgramReduction, unit: str | None = None) -> tuple:
    """``(unit, spans)``: the products (``bench.product`` spans) where
    the trace has them, else the flushes (``repro.serve.flush`` spans),
    unless ``unit`` says which."""
    products = [s for s in r.bench_spans if s.name == "bench.product"]
    if unit == "product" or (unit is None and products):
        return "product", products
    return "flush", named(r, "repro.serve.flush")


def _within(spans, parents) -> list:
    return [s for s in spans if any(
        p.thread == s.thread and p.start <= s.start and s.end <= p.end
        for p in parents)]


def _overlap(intervals, spans) -> float:
    """Seconds of ``intervals`` (sorted, disjoint) inside the union of
    ``spans``."""
    total, ends = 0.0, [e for _, e in intervals]
    for a, b in _union((s.start, s.end) for s in spans):
        for s, e in intervals[bisect.bisect_left(ends, a):]:
            if s >= b:
                break
            total += max(0.0, min(e, b) - max(s, a))
    return total * 1e-9


def coverage(r: ProgramReduction, parents: list, phases) -> list:
    """Share of each parent span covered by the union of the ``phases``
    spans on its thread."""
    out = []
    for p in parents:
        inner = [s for s in r.program_spans if s.name in phases
                 and s.thread == p.thread and s.start >= p.start
                 and s.end <= p.end]
        covered = sum(e - s for s, e in _union((s.start, s.end)
                                               for s in inner))
        out.append(covered / (p.end - p.start) if p.end > p.start else 1.0)
    return out


def queue_ms(r: ProgramReduction):
    """Mean over the traced requests of their flush's start less their
    submit's start, in ms."""
    submits = {s.stats.get("request"): s.start
               for s in named(r, "repro.serve.submit")}
    waits = [f.start - submits[int(rid)]
             for f in named(r, "repro.serve.flush")
             for rid in str(f.stats.get("requests", "")).split()
             if int(rid) in submits]
    return 1e-6 * sum(waits) / len(waits) if waits else None


def metrics(r: ProgramReduction, unit: str | None = None) -> dict:
    """The per-layer numbers of the module docstring, each summed over
    the unit spans (:func:`units`) and divided by their count; a number
    the trace cannot give is left out."""
    unit, parents = units(r, unit)
    n = len(parents)
    out = {"unit": unit, "units": n}
    if not n:
        return out
    mine = _within([s for s in r.program_spans if s.name in UNIT_PHASES],
                   parents)

    def spans(name):
        return [s for s in mine if s.name == name]

    def host_ms(name):
        return (1e3 * sum(s.seconds for s in spans(name)) / n
                if spans(name) else None)

    values = {
        "driver.prep_ms": host_ms("repro.spz.prep"),
        "driver.idle_ms": (1e3 * _overlap(r.idle, spans("repro.spz.groups"))
                           / n if spans("repro.spz.groups")
                           and r.device_busy_s else None),
        "output.assemble_ms": host_ms("repro.spz.assemble"),
        "serve.queue_ms": queue_ms(r) if unit == "flush" else None,
    }
    if any(r.scope_iv.get(scope) for scope in SCOPES):
        for scope in SCOPES:
            values[f"device.{scope.split('.', 1)[1]}_ms"] = 1e3 * sum(
                _overlap(iv, parents)
                for iv in r.scope_iv.get(scope, {}).values()) / n
    out.update({k: v for k, v in values.items() if v is not None})
    return out


def summary(r: ProgramReduction) -> dict:
    """Everything a reader of a new trace wants first."""
    totals: dict = {}
    for s in r.program_spans:
        n, sec = totals.get(s.name, (0, 0.0))
        totals[s.name] = (n + 1, sec + s.seconds)
    execs = [s for s in r.bench_spans if s.name == "bench.execute"]
    flushes = named(r, "repro.serve.flush")
    return {
        "window_s": (r.window[1] - r.window[0]) * 1e-9,
        "metrics": metrics(r),
        "span_count_seconds": totals,
        "scope_s": r.scope_s,
        "module_s": r.module_s,
        "unscoped_s": sorted(r.unscoped_s.items(), key=lambda u: -u[1])[:10],
        "coverage_execute": coverage(r, execs, PRODUCT_PHASES),
        "coverage_flush": coverage(r, flushes, FLUSH_PHASES),
        "idle_gaps": r.gaps[:20],
    }


if __name__ == "__main__":
    print(json.dumps(summary(reduce_file(sys.argv[1])), indent=1,
                     default=str))
