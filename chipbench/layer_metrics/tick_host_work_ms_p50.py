"""Engine step loop: what the host does per decode tick, on the device's
clock. Per period from one start of the decode program (the one the
configuration names under ``decode_program``) to the next: the union of
all the program's spans (``dk:*`` in the profiler trace) other than
``harvest`` and ``loop_idle``, less what those two cover (``barrier``
encloses the harvest it waits on) — the two are waits, for the device and
for a request. Median over the traced ticks: the tick a free device would
leave."""
import statistics

SOURCE = "program_span"
UNIT = "ms"
WAITS = ("harvest", "loop_idle")


def read(ctx):
    from harness import host_spans, trace_reduce

    if ctx["trace"] is None:
        return None
    decode = trace_reduce.find_program(
        ctx["trace"], ctx["config"].get("decode_program"))
    parsed = host_spans.load()
    if decode is None or decode["count"] < 3 or parsed is None:
        return None
    work = host_spans.named(parsed["spans"], exclude=WAITS)
    wait = host_spans.named(parsed["spans"], include=WAITS)
    return 1e3 * statistics.median(
        host_spans.per_period(decode["starts_s"], work, wait))
