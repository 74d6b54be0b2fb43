"""Expert layer: of the (token, expert) pairs the router chose in the
window's decode ticks, the share whose expert is held on this chip:
``moe_pairs_held_total`` over ``moe_pairs_total`` (metricsz, after less
before; live rows, all layers). Held experts over all experts where the
routing is even."""
SOURCE = "program_counter"
UNIT = "%"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    pairs, held = delta.get("moe_pairs_total"), delta.get("moe_pairs_held_total")
    if not pairs or held is None:
        return None
    return 100.0 * held / pairs
