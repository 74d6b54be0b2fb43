"""KV cache: prompt tokens served from cached blocks over all prompt
tokens, of the requests that finished in the window (the engine's
wide-event counts, through queryz)."""
SOURCE = "program_counter"
UNIT = "%"


def read(ctx):
    q = ctx["counters"].get("queryz", {})
    prompt = q.get("sum:prompt_tokens")
    if not prompt:
        return None
    return 100.0 * (q.get("sum:prefix_hit_tokens") or 0.0) / prompt
