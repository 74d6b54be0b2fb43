"""Operations and bytes a step NEEDS on this chip's share of the
``pangu_ultra_moe`` block, from shapes alone: the yardstick's arithmetic,
the same whatever implements the step. Imports no JAX.

A multiply-add is two operations. Recomputed work is never counted, and
neither is anything an implementation does that the algorithm does not ask
for (padding to a bucket, a read of blocks no query can see, an expert
nobody chose, a key or value rebuilt from a cached latent row). Norms and
the residual's additions are elementwise and not counted. Attention is
counted as the model defines it: a query over one key is ``qk_nope +
qk_rope`` multiply-adds a head for the score and ``v_head_dim`` for the
value; each token's keys and values are built once, by the projections.
The routed experts are counted at their expectation: a token's
``num_experts_per_tok`` choices over the ``num_experts`` held here out of
``num_router_outputs``. A cached position's latent row is ``kv_lora_rank +
qk_rope_head_dim`` values a layer, read once a step for every query that
sees it."""

from __future__ import annotations


def _layers(m: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    dense = m["first_k_dense_replace"]
    return dense, m["num_hidden_layers"] - dense


def _attention_matrices(m: dict) -> int:
    C, H, R, qr = (m["hidden_size"], m["num_attention_heads"],
                   m["kv_lora_rank"], m["q_lora_rank"])
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return (C * qr + qr * H * (nope + rope) + C * (R + rope)
            + R * H * (nope + dv) + H * dv * C)


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def routed_params(m: dict) -> int:
    """Parameters of the routed experts held here, all layers."""
    return _layers(m)[1] * m["num_experts"] * _expert_params(m)


def param_count(m: dict) -> int:
    """Parameters held here, as the reference defines them: per layer the
    attention's five matrices and two norms, the four sandwich norms, the
    dense MLP or the router, shared and held experts; the vocabulary
    slice's embedding and head, and the final norm."""
    C, E = m["hidden_size"], m["num_router_outputs"]
    dense, experts = _layers(m)
    layer = (_attention_matrices(m) + m["q_lora_rank"] + m["kv_lora_rank"]
             + 4 * C)
    return (2 * m["vocab_size"] * C + C + m["num_hidden_layers"] * layer
            + dense * 3 * C * m["intermediate_size"]
            + experts * (C * E + m["n_shared_experts"] * _expert_params(m))
            + routed_params(m))


def routed_experts_per_token(m: dict) -> float:
    """Expected routed experts held here that a token chooses, a layer."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_router_outputs"])


def layer_matmul_flops_per_token(m: dict) -> float:
    """Forward projections of one token through every layer: attention's
    five, the dense MLP, the router, the shared experts and the expected
    routed experts."""
    C = m["hidden_size"]
    dense, experts = _layers(m)
    ffn = (dense * 3 * C * m["intermediate_size"]
           + experts * (C * m["num_router_outputs"]
                        + (m["n_shared_experts"] + routed_experts_per_token(m))
                        * _expert_params(m)))
    return 2.0 * (m["num_hidden_layers"] * _attention_matrices(m) + ffn)


def attention_flops_per_key(m: dict) -> int:
    """A query over one key, all layers."""
    return (2 * m["num_attention_heads"] * m["num_hidden_layers"]
            * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]))


def head_flops_per_token(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def prefill_flops(m: dict, start: int, end: int) -> float:
    """Forward of prompt positions [start, end) (position p sees p + 1
    keys), plus the head ONCE: only the last position's logits are
    needed."""
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return counted_prefill_flops(m, end - start, keys) + head_flops_per_token(m)


def counted_prefill_flops(m: dict, positions: float, keys: float) -> float:
    """Forward of ``positions`` prompt positions that attend to ``keys``
    keys in all (the engine's prefill counters), without the head."""
    return (positions * layer_matmul_flops_per_token(m)
            + keys * attention_flops_per_key(m))


def decode_flops(m: dict, context: int) -> float:
    """One output token whose query sees ``context`` positions."""
    return (layer_matmul_flops_per_token(m)
            + attention_flops_per_key(m) * context + head_flops_per_token(m))


def kv_bytes_per_token(m: dict, dtype_bytes: int) -> int:
    """One position's latent rows, all layers."""
    return (m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * dtype_bytes)


def decode_weight_bytes(m: dict, dtype_bytes: int) -> int:
    """Every parameter held here, in the served type."""
    return param_count(m) * dtype_bytes


def latent_read_bytes(m: dict, kv_dtype_bytes: int, contexts) -> float:
    """Least bytes of the latent pool that output tokens with query
    contexts ``contexts`` read: each seen position's row once a layer."""
    return kv_bytes_per_token(m, kv_dtype_bytes) * sum(contexts)


def decode_min_bytes(m: dict, dtype_bytes: int, kv_dtype_bytes: int,
                     steps: int, experts_touched: float, contexts) -> float:
    """Least HBM traffic of ``steps`` decode steps: each step the weights
    outside the routed experts once; one expert's matrices for each held
    expert that some row chose (``experts_touched``, summed over layers
    and steps, from the program's counter); and each output token's latent
    rows (:func:`latent_read_bytes`)."""
    fixed = (param_count(m) - routed_params(m)) * dtype_bytes
    return (steps * fixed + experts_touched * _expert_params(m) * dtype_bytes
            + latent_read_bytes(m, kv_dtype_bytes, contexts))
