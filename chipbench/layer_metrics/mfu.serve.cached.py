"""Model step, serving, where prompts are served from cache in part:
operations the window's work needed over the window, over the chip's peak.
Counted are every output token with the keys it saw (the clients' token
events), every prompt position a prefill program computed with the keys it
attended to, cached ones included (the engine's counters
``serving_prefill_tokens_total`` and ``serving_prefill_key_positions_total``,
metricsz after less before: no position served from cache), and the head
once for each request whose first token came in the window. Nothing to
read where the program has no such counters, or its model no such count."""
SOURCE = "host_clock"
UNIT = "%"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    positions = delta.get("serving_prefill_tokens_total")
    keys = delta.get("serving_prefill_key_positions_total")
    math, events = ctx["math"], ctx["spans"].get("token_events")
    counted = getattr(math, "counted_prefill_flops", None)
    if positions is None or keys is None or counted is None or not events:
        return None
    sizes = ctx["config"]["model"]["config"]
    flops = (sum(math.decode_flops(sizes, c) for _, c in events)
             + counted(sizes, positions, keys)
             + len(ctx["counters"].get("first_token_prompts", []))
             * math.head_flops_per_token(sizes))
    peak = ctx["peaks"]["flops_per_s"][ctx["config"]["dtype"]]
    return 100.0 * flops / ctx["window_s"] / peak
