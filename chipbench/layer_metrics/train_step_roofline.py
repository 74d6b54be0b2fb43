"""Kernels, training: the least time one chip could take for its share of
a step — the larger of operations over peak FLOP/s and least bytes over
peak bytes/s — over the device time of one execution of the step program
in the trace. On the plain path the step is one XLA program and has no
kernel of its own, so the program is the kernel: the one the traffic file
names under ``step_program``."""
SOURCE = "device_trace"
UNIT = "%"


def read(ctx):
    from harness import trace_reduce

    if ctx["trace"] is None:
        return None
    step = trace_reduce.find_program(
        ctx["trace"], ctx["traffic"].get("step_program"))
    if step is None or not step["count"]:
        return None
    model = ctx["config"]["model"]
    chips = ctx["cell"]["chips"]
    tokens = ctx["counters"]["tokens_per_step"] / chips
    math = ctx["math"]
    flops_s = (math.train_flops_per_token(model["config"], model["seq_len"])
               * tokens / ctx["peaks"]["flops_per_s"][ctx["config"]["dtype"]])
    bytes_s = (math.train_step_min_bytes(model["config"], tokens)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * max(flops_s, bytes_s) / (step["total_s"] / step["count"])
