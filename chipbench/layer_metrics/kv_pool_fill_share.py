"""KV cache: blocks of the paged pool in use, as the mean over the
window's decode loop iterations, over the pool's capacity:
``kv_pool_block_ticks_total`` over ``serving_decode_iterations_total`` x
the capacity the server's summary reports (metricsz, after less
before)."""
SOURCE = "program_counter"
UNIT = "%"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    ticks = delta.get("serving_decode_iterations_total")
    blocks = delta.get("kv_pool_block_ticks_total")
    capacity = (ctx["counters"].get("kv_pool") or {}).get("capacity_blocks")
    if not ticks or blocks is None or not capacity:
        return None
    return 100.0 * blocks / (ticks * capacity)
