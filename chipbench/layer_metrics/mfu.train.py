"""Train step: forward + backward operations per token (chipbench's own
count, nothing recomputed) x tokens per second of the whole window, over
chips x the chip's bf16 peak."""
SOURCE = "host_clock"
UNIT = "%"


def read(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s")
    if not rate:
        return None
    model = ctx["config"]["model"]
    flops = ctx["math"].train_flops_per_token(model["config"], model["seq_len"])
    peak = ctx["peaks"]["flops_per_s"][ctx["config"]["dtype"]]
    return 100.0 * flops * rate / (ctx["cell"]["chips"] * peak)
