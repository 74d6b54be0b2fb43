"""Trainer loop: the time per step the trainer's own thread spends
fetching the next batch and putting it on the device (the feed runs inline
there), on the device's clock. Per period from one start of the step
program (the one the traffic file names under ``step_program``) to the
next: the union of the spans ``data_next`` and ``h2d_put`` (``dk:*`` in
the profiler trace). Median over the traced steps."""
import statistics

SOURCE = "program_span"
UNIT = "ms"
FEED = ("data_next", "h2d_put")


def read(ctx):
    from harness import host_spans, trace_reduce

    if ctx["trace"] is None:
        return None
    step = trace_reduce.find_program(
        ctx["trace"], ctx["traffic"].get("step_program"))
    parsed = host_spans.load()
    if step is None or step["count"] < 3 or parsed is None:
        return None
    feed = host_spans.named(parsed["spans"], include=FEED)
    if not feed:
        return None
    return 1e3 * statistics.median(
        host_spans.per_period(step["starts_s"], feed))
