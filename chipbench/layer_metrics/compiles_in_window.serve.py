"""Engine step loop: compilations the server's RecompileAuditor counted
between the window's start and its end (metricsz). Should read 0."""
SOURCE = "program_counter"
UNIT = "compiles"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta")
    if not delta:
        return None
    found = [v for k, v in delta.items() if "compiles_total" in k]
    return sum(found) if found else None
