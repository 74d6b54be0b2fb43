"""Operations and bytes a step NEEDS on this chip's share of the
``cohere2_moe`` block, from shapes alone: the yardstick's arithmetic, the
same whatever implements the step. Imports no JAX.

A multiply-add is two operations. Recomputed work is never counted, and
neither is anything an implementation does that the algorithm does not ask
for (padding to a bucket, a gather of blocks no query can see, an expert
nobody chose). The routed experts are counted at their EXPECTATION: a
token's ``num_experts_per_tok`` choices fall on the ``num_experts`` held
here out of ``num_router_outputs`` with even routing, which is one expert
a token a layer at 8 x 16 / 128. A window layer's query sees at most
``sliding_window`` keys."""

from __future__ import annotations


def _layer_counts(m: dict) -> tuple[int, int]:
    """(window layers, full layers)."""
    sliding = sum(t == "sliding_attention" for t in m["layer_types"])
    return sliding, len(m["layer_types"]) - sliding


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _attention_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    return h * d * (2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])


def routed_params(m: dict) -> int:
    """Parameters of the routed experts held here, all layers."""
    return len(m["layer_types"]) * m["num_experts"] * _expert_params(m)


def param_count(m: dict) -> int:
    """Parameters held here, as the reference defines them."""
    h = m["hidden_size"]
    layer = (_attention_params(m) + h * m["num_router_outputs"] + h
             + m["num_shared_experts"] * _expert_params(m))
    return (m["vocab_size"] * h + h + len(m["layer_types"]) * layer
            + routed_params(m))


def routed_experts_per_token(m: dict) -> float:
    """Expected routed experts held here that a token chooses, a layer."""
    return m["num_experts_per_tok"] * m["num_experts"] / m["num_router_outputs"]


def layer_matmul_flops_per_token(m: dict) -> float:
    """Forward projections of one token through every layer: q, k, v, out,
    the router, the shared experts and the expected routed experts."""
    experts = m["num_shared_experts"] + routed_experts_per_token(m)
    return len(m["layer_types"]) * 2.0 * (
        _attention_params(m) + m["hidden_size"] * m["num_router_outputs"]
        + experts * _expert_params(m))


def _keys(m: dict, context: int) -> int:
    """Keys ONE query at context ``context`` sees, summed over layers."""
    sliding, full = _layer_counts(m)
    return full * context + sliding * min(context, m["sliding_window"])


def attention_flops(m: dict, context: int) -> int:
    """Forward QK^T and AV of ONE query over ``context`` keys, all layers."""
    return 2 * 2 * m["num_attention_heads"] * m["head_dim"] * _keys(m, context)


def head_flops_per_token(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def _keys_sum(start: int, end: int, cap: int | None) -> int:
    """Sum over positions p in [start, end) of min(p + 1, cap)."""
    def upto(n):  # sum_{p < n} min(p + 1, cap)
        if cap is None or n <= cap:
            return n * (n + 1) // 2
        return cap * (cap + 1) // 2 + (n - cap) * cap
    return upto(end) - upto(start)


def prefill_flops(m: dict, start: int, end: int) -> float:
    """Forward of prompt positions [start, end), plus the head ONCE: only
    the last position's logits are needed."""
    sliding, full = _layer_counts(m)
    keys = (full * _keys_sum(start, end, None)
            + sliding * _keys_sum(start, end, m["sliding_window"]))
    return ((end - start) * layer_matmul_flops_per_token(m)
            + 2 * 2 * m["num_attention_heads"] * m["head_dim"] * keys
            + head_flops_per_token(m))


def decode_flops(m: dict, context: int) -> float:
    """One output token whose query sees ``context`` positions."""
    return (layer_matmul_flops_per_token(m) + attention_flops(m, context)
            + head_flops_per_token(m))


def kv_bytes_per_token(m: dict, dtype_bytes: int) -> int:
    return (len(m["layer_types"]) * 2 * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes)


def decode_weight_bytes(m: dict, dtype_bytes: int) -> int:
    """Every parameter held here, in the served type: what a decode step
    reads when every held expert is chosen by some row."""
    return param_count(m) * dtype_bytes


def decode_min_bytes(m: dict, dtype_bytes: int, kv_dtype_bytes: int,
                     steps: int, experts_touched: float, contexts) -> float:
    """Least HBM traffic of ``steps`` decode steps: each step the weights
    outside the routed experts once; one expert's matrices for each held
    expert that some row chose (``experts_touched``, summed over layers
    and steps, from the program's counter); and for each output token (its
    context in ``contexts``) the keys and values its query saw, by layer
    type."""
    fixed = (param_count(m) - routed_params(m)) * dtype_bytes
    per_key = 2 * m["num_key_value_heads"] * m["head_dim"] * kv_dtype_bytes
    return (steps * fixed
            + experts_touched * _expert_params(m) * dtype_bytes
            + per_key * sum(_keys(m, c) for c in contexts))
