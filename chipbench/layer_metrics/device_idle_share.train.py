"""Device: 1 - (union of the intervals in which an operation ran) / traced
window, on the chip that idled most."""
SOURCE = "device_trace"
UNIT = "%"


def read(ctx):
    from harness import trace_reduce

    return trace_reduce.idle_share(ctx["trace"])
