"""Scheduler: the engine's own per-request queue_wait_s (wide-event rows
of the window, through the queryz verb; the percentile is the program's
histogram estimate, not an exact rank)."""
SOURCE = "program_span"
UNIT = "ms"


def read(ctx):
    wait = ctx["counters"].get("queryz", {}).get("p95:queue_wait_s")
    return None if wait is None else 1e3 * wait
