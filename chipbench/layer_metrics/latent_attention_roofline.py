"""Kernels, serving: the latent read's share of its roofline. The least
bytes the decode steps of the traced window had to read of the latent pool
(each generated token's latent rows, one a position it saw a layer:
``model_math.latent_read_bytes``) over the chip's HBM bandwidth, over the
device time of the operations of the decode program (the configuration's
``decode_program``) that the configuration's ``latent_read_ops`` names (a
regular expression on operation names, ``pattern``). The same whatever
implements the read. Nothing to read where the configuration names no such
operations, its model has no such count, or the trace holds no match."""
import re

SOURCE = "device_trace"
UNIT = "%"


def read(ctx):
    from harness import trace_reduce

    trace, t_a = ctx["trace"], ctx["spans"].get("trace_t0")
    pattern = (ctx["config"].get("latent_read_ops") or {}).get("pattern")
    least = getattr(ctx["math"], "latent_read_bytes", None)
    if trace is None or t_a is None or not pattern or least is None:
        return None
    decode = trace_reduce.find_program(
        trace, ctx["config"].get("decode_program"))
    if decode is None:
        return None
    device, match = trace["devices"][0], re.compile(pattern)
    ops = {op for name in decode["names"]
           for op in device["programs"][name]["ops"] if match.search(op)}
    busy = sum(device["ops"][op] for op in ops)
    contexts = [c for t, c in ctx["spans"]["token_events"]
                if t_a <= t < t_a + trace["window_s"]]
    if not busy or not contexts:
        return None
    config = ctx["config"]
    needed = least(config["model"]["config"], config["kv_dtype_bytes"], contexts)
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / busy
