"""Load generator (chipbench): the longest this process's own event loop
overslept a 20 ms timer inside the window. Where it reads near a gap
between tokens, the gap was the generator's and not the server's."""
SOURCE = "host_clock"
UNIT = "ms"


def read(ctx):
    stall = ctx["spans"].get("loadgen_stall_s")
    return None if stall is None else 1e3 * stall
