"""From a profiler trace (``.xplane.pb``) to numbers: which planes are
devices, the union of the intervals in which an operation ran on each, the
device time of each program and operation, collectives not hidden behind
compute, and the longest idle gaps labelled by what the host was doing.

Reads the file with nothing but JAX (``jax.profiler.ProfileData``). Times
are in seconds. The layout this relies on, as read by hand from a v5e trace
(PERF.md section 3): one plane per chip named ``/device:TPU:<n>``; on it a
line ``XLA Modules`` with one event per execution of a compiled program
(``jit_step(1234)``: the jitted function's name and the compilation's id)
and a line ``XLA Ops`` with one event per operation, named by its HLO
text; host threads are lines of the plane ``/host:CPU``. The serving
engine jits ``functools.partial`` objects, which JAX names
``jit__unknown``: its prefill and decode programs differ only in the id,
so a program is found by a pattern on its name AND, where the cell's files
give one, a pattern that one of the operations inside it has to match.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)
# The per-compilation id a program's name ends in: "jit_step(123)"
_ID = re.compile(r"\(\d+\)$")


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, hi = 0.0, None
    for start, end in sorted(intervals):
        if hi is None or start > hi:
            total += end - start
            hi = end
        elif end > hi:
            total += end - hi
            hi = end
    return total


def gaps_of(intervals, lo: float, hi: float) -> list:
    """The uncovered stretches of ``[lo, hi]``, longest first."""
    out, at = [], lo
    for start, end in sorted(intervals):
        if start > at:
            out.append((at, min(start, hi)))
        at = max(at, end)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def _subtract(intervals, cover) -> float:
    """Length of ``intervals`` not covered by ``cover``."""
    return union_length(intervals) + union_length(cover) - union_length(
        list(intervals) + list(cover))


def _events(line) -> list:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def read_planes(path: str) -> dict:
    """``{"devices": {plane: {line: [(name, start, end)]}}, "host": {thread:
    [...]}}`` — the only place that touches the profiler's format."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: _events(line) for line in plane.lines
                if line.name in (MODULE_LINE, OP_LINE)}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(_events(line))
    return {"devices": devices, "host": host}


def _label_gap(host: dict, start: float, end: float) -> str:
    """What the host was doing in ``[start, end]``: the host event that
    overlaps the gap longest, else ``host:unattributed``."""
    best, best_overlap = "host:unattributed", 0.0
    for thread, events in host.items():
        for name, s, e in events:
            overlap = min(e, end) - max(s, start)
            if overlap > best_overlap and not name.startswith("$"):
                best, best_overlap = f"host:{name}", overlap
    return best[:80]


def reduce(path: str, traced_s: float | None, chips: int) -> dict | None:
    """The summary the per-layer readers and the result line use, or None
    when the trace holds no device plane (a CPU rehearsal)."""
    planes = read_planes(path)
    if not planes["devices"]:
        return None
    per_device = []
    for name in sorted(planes["devices"])[:chips]:
        lines = planes["devices"][name]
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        modules = lines.get(MODULE_LINE, [])
        if not ops:
            continue
        lo = min(s for _, s, _ in ops)
        hi = max(e for _, _, e in ops)
        busy = [(s, e) for _, s, e in ops]
        # One entry per compiled program, by its full name, id and all.
        programs: dict = {}
        modules = sorted(modules, key=lambda m: m[1])
        for prog, s, e in modules:
            p = programs.setdefault(prog, {"count": 0, "total_s": 0.0,
                                           "starts_s": [], "ops": set()})
            p["count"] += 1
            p["total_s"] += e - s
            p["starts_s"].append(s)
        starts = [m[1] for m in modules]
        op_time: dict = {}
        for op, s, e in ops:
            op_time[op] = op_time.get(op, 0.0) + (e - s)
            # the operation belongs to the execution it started inside
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < modules[i][2]:
                programs[modules[i][0]]["ops"].add(op)
        collectives = [(s, e) for op, s, e in ops if COLLECTIVE.search(op)]
        compute = [(s, e) for op, s, e in ops if not COLLECTIVE.search(op)]
        per_device.append({
            "name": name, "span_s": hi - lo, "first_s": lo, "last_s": hi,
            "busy_s": union_length(busy),
            "programs": programs, "ops": op_time,
            "collective_s": union_length(collectives),
            "collective_exposed_s": _subtract(collectives, compute),
            "gaps": gaps_of(busy, lo, hi)[:10],
        })
    if not per_device:
        return None
    # The traced window: the host's clock around start/stop where the
    # caller measured it, else the span of device activity.
    window = traced_s or max(d["span_s"] for d in per_device)
    first = per_device[0]
    top_ops = sorted(first["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = [[_label_gap(planes["host"], s, e), e - s]
            for s, e in first["gaps"]]
    return {
        "window_s": window,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "devices": per_device,
        "breakdown": {"device_ops": [[k[:80], v] for k, v in top_ops],
                      "idle_gaps": gaps},
    }


def find_program(summary: dict, spec: dict | None) -> dict | None:
    """The executions, on the first device, of the program that ``spec``
    names, from the cell's files: ``{"name": <regular expression on the
    program's name without its id>, "op": <one that an operation inside it
    has to match, optional>}``.
    Compiled programs that match are taken together as one. None where the
    files give no ``spec`` or nothing matches: a reader then reports
    nothing rather than another program's time."""
    if not spec or not spec.get("name"):
        return None
    name = re.compile(spec["name"])
    op = re.compile(spec["op"]) if spec.get("op") else None
    found = {k: p for k, p in summary["devices"][0]["programs"].items()
             if name.search(_ID.sub("", k))
             and (op is None or any(op.search(o) for o in p["ops"]))}
    if not found:
        return None
    return {"names": sorted(found),
            "count": sum(p["count"] for p in found.values()),
            "total_s": sum(p["total_s"] for p in found.values()),
            "starts_s": sorted(t for p in found.values() for t in p["starts_s"])}


def idle_share(summary: dict | None) -> float | None:
    """1 - busy / traced window, in per cent, on the chip that idled most."""
    if summary is None:
        return None
    return 100.0 * max(1.0 - d["busy_s"] / summary["window_s"]
                       for d in summary["devices"])
