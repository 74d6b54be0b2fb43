"""Kernels, serving: the least bytes the decode steps of the traced
window had to move — each step the weights once, in the type the products
are made in, and each generated token the keys and values its query saw —
over the chip's HBM bandwidth, over the device time of the decode
program's executions in the trace (the program the configuration names
under ``decode_program``, and no other). The same whatever implements the
step."""
SOURCE = "device_trace"
UNIT = "%"


def read(ctx):
    from harness import trace_reduce

    trace, t_a = ctx["trace"], ctx["spans"].get("trace_t0")
    if trace is None or t_a is None:
        return None
    decode = trace_reduce.find_program(
        trace, ctx["config"].get("decode_program"))
    if decode is None or not decode["total_s"]:
        return None
    sizes, math = ctx["config"]["model"]["config"], ctx["math"]
    keys = sum(c for t, c in ctx["spans"]["token_events"]
               if t_a <= t < t_a + trace["window_s"])
    if not keys:
        return None
    wbytes = {"bfloat16": 2, "float32": 4}[ctx["config"]["dtype"]]
    least = (decode["count"] * math.decode_weight_bytes(sizes, wbytes)
             + keys * math.kv_bytes_per_token(sizes, ctx["config"]["kv_dtype_bytes"]))
    return 100.0 * least / ctx["peaks"]["hbm_bytes_per_s"] / decode["total_s"]
