"""Load generator (chipbench): how late requests were sent, sent - due,
95th percentile. A starved generator must not read as a fast server."""
SOURCE = "host_clock"
UNIT = "ms"


def read(ctx):
    from harness.traffic import percentile

    lag = ctx["spans"].get("loadgen_lag_s")
    return 1e3 * percentile(lag, 95) if lag else None
