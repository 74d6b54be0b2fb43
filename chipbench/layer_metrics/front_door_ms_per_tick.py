"""Front door: what the server's socket work takes per decode tick, on
the device's clock. Per period from one start of the decode program (the
one the configuration names under ``decode_program``) to the next: the
union of the program's ``front_read`` spans (a request's parse and
submit) and ``front_write`` spans (an encode and write to a socket),
``dk:*`` in the profiler trace. Median over the traced ticks. The split
by span (each alone, per period, mean) goes to standard error. A program
with no such span gives nothing."""
import statistics
import sys

SOURCE = "program_span"
UNIT = "ms"
FRONT = ("front_read", "front_write")


def read(ctx):
    from harness import host_spans, trace_reduce

    if ctx["trace"] is None:
        return None
    decode = trace_reduce.find_program(
        ctx["trace"], ctx["config"].get("decode_program"))
    parsed = host_spans.load()
    if decode is None or decode["count"] < 3 or parsed is None:
        return None
    front = host_spans.named(parsed["spans"], include=FRONT)
    if not front:
        return None
    edges = decode["starts_s"]
    split = []
    for name in FRONT:
        one = host_spans.named(parsed["spans"], include=(name,))
        per = host_spans.per_period(edges, one)
        split.append(f"{name} {1e3 * statistics.fmean(per):.3f} ms "
                     f"({len(one)} spans)")
    print(f"chipbench: front door per decode tick, mean by span: "
          f"{', '.join(split)}", file=sys.stderr)
    return 1e3 * statistics.median(host_spans.per_period(edges, front))
