"""Collectives: device time of collective operations during which no
compute operation ran on that chip, over the traced window; the worst
chip. Nothing to read on one chip."""
SOURCE = "device_trace"
UNIT = "%"


def read(ctx):
    if ctx["trace"] is None or ctx["cell"]["chips"] < 2:
        return None
    if not any(d["collective_s"] for d in ctx["trace"]["devices"]):
        return None
    return 100.0 * max(d["collective_exposed_s"]
                       for d in ctx["trace"]["devices"]) / ctx["trace"]["window_s"]
