"""Scheduler: rows decoding per decode loop iteration over the slots the
server was started with: ``serving_slot_ticks_total`` over
``serving_decode_iterations_total`` x ``--slots`` (metricsz, after less
before)."""
SOURCE = "program_counter"
UNIT = "%"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    ticks = delta.get("serving_decode_iterations_total")
    rows = delta.get("serving_slot_ticks_total")
    flags = ctx["config"].get("serve_flags", [])
    if not ticks or rows is None or "--slots" not in flags:
        return None
    slots = int(flags[flags.index("--slots") + 1])
    return 100.0 * rows / (ticks * slots)
