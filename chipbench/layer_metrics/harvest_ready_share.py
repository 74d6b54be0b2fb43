"""Engine step loop: of the tick harvests in the window, the share whose
tick the device had already finished when the harvest began:
``serving_harvests_total{ready=1}`` over all of
``serving_harvests_total`` (metricsz, after less before). At 100 % every
tick waited for the host, and the host paces the loop; near 0 the device
does. A program without the counter gives nothing."""
SOURCE = "program_counter"
UNIT = "%"
TOTAL = "serving_harvests_total"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    by_ready = {k[len(TOTAL):].strip("{}"): v for k, v in delta.items()
                if k.startswith(TOTAL)}
    total = sum(by_ready.values())
    if not total:
        return None
    return 100.0 * by_ready.get("ready=1", 0.0) / total
