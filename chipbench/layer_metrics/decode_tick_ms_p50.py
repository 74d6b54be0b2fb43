"""Engine step loop: median interval from the start of one execution of
the decode program to the start of the next, on the device's clock. The
program is the one the configuration names (``decode_program``); where it
names none, or the trace holds no match, there is nothing to read."""
import statistics

SOURCE = "device_trace"
UNIT = "ms"


def read(ctx):
    from harness import trace_reduce

    if ctx["trace"] is None:
        return None
    decode = trace_reduce.find_program(
        ctx["trace"], ctx["config"].get("decode_program"))
    if decode is None or decode["count"] < 3:
        return None
    starts = sorted(decode["starts_s"])
    return 1e3 * statistics.median(b - a for a, b in zip(starts, starts[1:]))
