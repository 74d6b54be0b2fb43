"""The program's own spans on the device's clock.

While a profiler session is live, ``distkeras_tpu.telemetry.span()`` writes
each span into the session as a host event named ``dk:<name>``: it lands
on a thread's line of the ``/host:CPU`` plane of the run's ``.xplane.pb``,
on the clock the device plane uses. This module reads them back (through
``trace_reduce.read_planes``, the only place that touches the profiler's
format) and sets them against the device's own edges: the starts of the
program a cell's files name, and the gaps between operations.

A program that writes no such spans (the parent of the PR that added them)
gives ``None`` everywhere, and a reader then reports nothing.

Spans that wrap an ``await`` interleave with other tasks' spans on one
thread, and ``dk:barrier`` encloses ``dk:harvest`` and ``dk:stream``: all
arithmetic here is on unions of intervals, never on stacks.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import sys

from . import cell as cell_mod, trace_reduce

# distkeras_tpu.telemetry.spans.PREFIX, spelled out: the benchmark also runs
# on a program from before that constant (its parent), and then finds nothing.
PREFIX = "dk:"


def trace_file() -> str | None:
    """The ``.xplane.pb`` of the run whose readers are running: the
    harness keeps it under its work directory until they are done."""
    from . import main

    files = sorted(glob.glob(os.path.join(
        main.work_dir(cell_mod.ROOT), "trace", "plugins", "profile", "*",
        "*.xplane.pb")))
    return files[-1] if files else None


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime_ns: int, size: int) -> dict:
    planes = trace_reduce.read_planes(path)
    spans = sorted((name[len(PREFIX):], s, e)
                   for events in planes["host"].values()
                   for name, s, e in events if name.startswith(PREFIX))
    ops, programs = [], {}
    if planes["devices"]:
        lines = planes["devices"][sorted(planes["devices"])[0]]
        ops = sorted((s, e) for _, s, e in lines.get(trace_reduce.OP_LINE, []))
        for name, _, _ in lines.get(trace_reduce.MODULE_LINE, []):
            programs[name] = programs.get(name, 0) + 1
    print(f"chipbench: trace file {size} bytes, {len(spans)} program spans "
          f"({PREFIX}*), {len(ops)} device operations; programs run: "
          f"{programs}", file=sys.stderr)
    return {"spans": spans, "ops": ops}


def load(path: str | None = None) -> dict | None:
    """``{"spans": [(name, start, end)], "ops": [(start, end)]}`` of a
    trace: the program's spans (prefix taken off, all host threads, by
    start) and the first device's operations. Parsed once for the readers
    of one run. None where there is no trace or it holds no such span."""
    path = path or trace_file()
    if path is None:
        return None
    stat = os.stat(path)
    parsed = _parse(path, stat.st_mtime_ns, stat.st_size)
    return parsed if parsed["spans"] else None


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if s < hi and e > lo]


def uncovered(intervals, cover) -> float:
    """Length of the union of ``intervals`` that no interval of ``cover``
    overlaps."""
    return trace_reduce.union_length(list(intervals) + list(cover)) \
        - trace_reduce.union_length(cover)


def per_period(edges, work, wait=()) -> list:
    """For each period between consecutive ``edges``: the length of the
    union of the ``work`` intervals inside it, less what ``wait`` covers."""
    return [uncovered(clip(work, lo, hi), clip(wait, lo, hi))
            for lo, hi in zip(edges, edges[1:])]


def named(spans, include=None, exclude=()) -> list:
    """``(start, end)`` of the spans whose name is in ``include`` (all
    when None) and not in ``exclude``."""
    return [(s, e) for name, s, e in spans
            if (include is None or name in include) and name not in exclude]


def _innermost_segments(spans) -> list:
    """Disjoint ``(start, end, name)`` covering the union of ``spans``,
    each stretch named after the span that started last among those over
    it (the innermost, where they nest)."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    out, active, nxt = [], [], 0
    for lo, hi in zip(points, points[1:]):
        while nxt < len(by_start) and by_start[nxt][1] <= lo:
            active.append(by_start[nxt])
            nxt += 1
        active = [a for a in active if a[2] > lo]
        if active:
            out.append((lo, hi, max(active, key=lambda a: a[1])[0]))
    return out


def idle_by_span(parsed: dict) -> tuple[float, dict] | None:
    """All the time the first device ran no operation between its first
    and its last, and how much of it fell inside a program span, by the
    innermost span's name: ``(idle_s, {name: s})``. None with no gaps."""
    ops = parsed["ops"]
    if len(ops) < 2:
        return None
    gaps = trace_reduce.gaps_of(ops, ops[0][0], max(e for _, e in ops))
    segments = _innermost_segments(parsed["spans"])
    starts = [s for s, _, _ in segments]
    inside: dict = {}
    for lo, hi in gaps:
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(segments) and segments[i][0] < hi:
            s, e, name = segments[i]
            overlap = min(e, hi) - max(s, lo)
            if overlap > 0:
                inside[name] = inside.get(name, 0.0) + overlap
            i += 1
    idle = sum(hi - lo for lo, hi in gaps)
    return (idle, inside) if idle > 0 else None
