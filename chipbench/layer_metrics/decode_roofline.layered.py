"""Kernels, serving: the least bytes the decode steps of the traced window
had to move in a model whose layers differ — each step the weights outside
the routed experts once, one expert's matrices for each held expert that
some row chose (the program's ``moe_experts_touched_total`` over
``serving_counted_ticks_total``, the mean a step of the window, times the
traced steps), and each generated token the keys and values its query saw,
a window layer's capped at the window (``model_math.decode_min_bytes``) —
over the chip's HBM bandwidth, over the device time of the decode
program's executions in the trace (the program the configuration names
under ``decode_program``, and no other). The same whatever implements the
step. Nothing to read where the program has no such counters."""
SOURCE = "device_trace"
UNIT = "%"


def read(ctx):
    from harness import trace_reduce

    trace, t_a = ctx["trace"], ctx["spans"].get("trace_t0")
    delta = ctx["counters"].get("metricsz_delta") or {}
    touched = delta.get("moe_experts_touched_total")
    ticks = delta.get("serving_counted_ticks_total")
    least = getattr(ctx["math"], "decode_min_bytes", None)
    if trace is None or t_a is None or touched is None or not ticks \
            or least is None:
        return None
    decode = trace_reduce.find_program(
        trace, ctx["config"].get("decode_program"))
    if decode is None or not decode["total_s"]:
        return None
    contexts = [c for t, c in ctx["spans"]["token_events"]
                if t_a <= t < t_a + trace["window_s"]]
    if not contexts:
        return None
    config = ctx["config"]
    wbytes = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    needed = least(config["model"]["config"], wbytes, config["kv_dtype_bytes"],
                   decode["count"], decode["count"] * touched / ticks, contexts)
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / decode["total_s"]
