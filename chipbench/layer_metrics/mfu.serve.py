"""Model step, serving: operations the window's tokens needed (chipbench's
count: every prompt token of a request whose first token came in the
window, every output token with the keys it saw) over the window, over the
chip's peak. Reads nothing where prompts were served from cache: the count
of uncached prompt tokens per request is not known to the client."""
SOURCE = "host_clock"
UNIT = "%"


def read(ctx):
    if (ctx["counters"].get("queryz", {}).get("sum:prefix_hit_tokens") or 0) > 0:
        return None
    events = ctx["spans"].get("token_events")
    if not events:
        return None
    sizes, math = ctx["config"]["model"]["config"], ctx["math"]
    flops = sum(math.decode_flops(sizes, c) for _, c in events)
    flops += sum(math.prefill_flops(sizes, 0, n)
                 for n in ctx["counters"].get("first_token_prompts", []))
    peak = ctx["peaks"]["flops_per_s"][ctx["config"]["dtype"]]
    return 100.0 * flops / ctx["window_s"] / peak
