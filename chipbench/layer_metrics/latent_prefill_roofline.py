"""Kernels, serving: the prefill chunks' latent read's share of its
roofline. For each chunk the program's ``prefill_tick`` span names (its
first position ``offset`` and its positions ``tokens``, attributes of the
span in the profiler trace), the least time the read could take:
``max(operations / the chip's peak, bytes / its HBM bandwidth)``, with
operations ``model_math.attention_flops_per_key`` x the chunk's query-key
pairs ``sum_{p=offset}^{offset+tokens-1} (p + 1)`` and bytes the chunk's
keys ``offset + tokens`` x ``model_math.kv_bytes_per_token``; over the
device time of the operations the configuration's ``latent_prefill_ops``
names (``pattern``) inside the executions of its prefill program
(``program``). The same whatever implements the read, absorbed or
expanded.

The loop enqueues one chunk an iteration, each inside its span, so the
k-th execution of the program in the trace is the k-th span's chunk, in
order, however late the device starts it. Only the unmatched ends are
left out: executions that started before the first span did (their spans
began before the trace), and spans past the last execution (their chunks
ran after it stopped). Nothing to read where the configuration names no
such operations, the program's spans carry no ``tokens`` (a program from
before they did), or the trace holds no match."""
import bisect
import re
import sys

SOURCE = "device_trace"
UNIT = "%"
SPAN = "dk:prefill_tick"


def chunks(path, program: str, pattern: str) -> list:
    """``[((offset, tokens), latent_s)]`` of the trace at ``path``: each
    chunk whose span and execution the trace holds, with the device time
    of its execution's operations that match ``pattern``."""
    from harness import trace_reduce
    from jax.profiler import ProfileData

    spans, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SPAN:
                        stats = dict(e.stats)
                        if "offset" in stats and "tokens" in stats:
                            spans.append((e.start_ns * 1e-9, int(stats["offset"]),
                                          int(stats["tokens"])))
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events]
                for line in plane.lines}
    if not spans or not devices:
        return []
    lines = devices[sorted(devices)[0]]
    name, match = re.compile(program), re.compile(pattern)
    runs = sorted((s, e) for n, s, e in lines.get(trace_reduce.MODULE_LINE, [])
                  if name.search(trace_reduce._ID.sub("", n)))
    ops = sorted((s, e) for n, s, e in lines.get(trace_reduce.OP_LINE, [])
                 if match.search(n))
    spans.sort()
    runs = [run for run in runs if run[0] >= spans[0][0]]
    paired = []
    for (_, offset, tokens), (lo, hi) in zip(spans, runs):
        at = bisect.bisect_left(ops, (lo, lo))
        busy = 0.0
        while at < len(ops) and ops[at][0] < hi:
            busy += ops[at][1] - ops[at][0]
            at += 1
        paired.append(((offset, tokens), busy))
    print(f"latent_prefill_roofline: {len(spans)} chunk spans, {len(runs)} "
          f"executions from the first span on, {len(paired)} paired",
          file=sys.stderr, flush=True)
    return paired


def read(ctx):
    from harness import host_spans

    config, math = ctx["config"], ctx["math"]
    named = config.get("latent_prefill_ops") or {}
    per_key = getattr(math, "attention_flops_per_key", None)
    row = getattr(math, "kv_bytes_per_token", None)
    path = host_spans.trace_file()
    if (ctx["trace"] is None or path is None or per_key is None
            or row is None or not named.get("program")
            or not named.get("pattern")):
        return None
    found = chunks(path, named["program"], named["pattern"])
    busy = sum(b for _, b in found)
    if not busy:
        return None
    sizes = config["model"]["config"]
    peak = ctx["peaks"]["flops_per_s"][config["dtype"]]
    least = 0.0
    for (offset, tokens), _ in found:
        pairs = tokens * offset + tokens * (tokens + 1) // 2
        least += max(per_key(sizes) * pairs / peak,
                     row(sizes, config["kv_dtype_bytes"]) * (offset + tokens)
                     / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / busy
