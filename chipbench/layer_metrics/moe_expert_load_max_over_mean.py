"""Expert layer: how uneven the held experts' loads are — the fullest held
expert's tokens (``moe_expert_tokens_max_total``, summed over layers and
decode ticks) over the mean of the held (``moe_pairs_held_total`` over the
experts the configuration holds, summed likewise). 1 where every held
expert gets the same; the loop of grouped products runs as long as the
fullest asks."""
SOURCE = "program_counter"
UNIT = "ratio"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    fullest, held = (delta.get("moe_expert_tokens_max_total"),
                     delta.get("moe_pairs_held_total"))
    experts = ctx["config"]["model"]["config"].get("num_experts")
    if fullest is None or not held or not experts:
        return None
    return fullest / (held / experts)
