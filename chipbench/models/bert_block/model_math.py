"""Operations and bytes a step NEEDS, from shapes alone: the yardstick's
arithmetic, the same whatever implements the step. Imports no JAX.

A multiply-add is two operations. Recomputed work is never counted, and
neither is anything an implementation does that the algorithm does not ask
for (padding to a bucket, a gather of blocks no row reads)."""

from __future__ import annotations


def param_count(m: dict) -> int:
    """Parameters of the block as the reference defines it."""
    h, mlp = m["hidden_size"], m["mlp_dim"]
    layer = 4 * (h * h + h) + (h * mlp + mlp) + (mlp * h + h) + 4 * h
    return (m["vocab_size"] * h + m["max_seq_len"] * h + 2 * h
            + m["vocab_size"] + m["num_layers"] * layer)


def layer_matmul_flops_per_token(m: dict) -> int:
    """Forward projections of one token through every layer: q, k, v, out
    (4 h^2) and the two MLP products (2 h mlp)."""
    h = m["hidden_size"]
    return m["num_layers"] * 2 * (4 * h * h + 2 * h * m["mlp_dim"])


def attention_flops(m: dict, context: int) -> int:
    """Forward QK^T and AV of ONE query over ``context`` keys, all layers."""
    return m["num_layers"] * 2 * 2 * context * m["hidden_size"]


def head_flops_per_token(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward (2x forward) of one token of a ``seq``-long row;
    a causal row's query sees (seq + 1) / 2 keys on average, a
    bidirectional one all ``seq``."""
    context = (seq + 1) / 2 if m["causal"] else seq
    forward = (layer_matmul_flops_per_token(m) + attention_flops(m, 1) * context
               + head_flops_per_token(m))
    return 3.0 * forward


def prefill_flops(m: dict, start: int, end: int) -> float:
    """Forward of prompt positions [start, end) (0-based; positions before
    ``start`` came from the prefix cache), plus the head ONCE: only the
    last position's logits are needed."""
    n = end - start
    keys = sum(range(start + 1, end + 1))  # position p sees p + 1 keys
    return (n * layer_matmul_flops_per_token(m) + attention_flops(m, 1) * keys
            + head_flops_per_token(m))


def decode_flops(m: dict, context: int) -> float:
    """One output token whose query sees ``context`` keys."""
    return (layer_matmul_flops_per_token(m) + attention_flops(m, context)
            + head_flops_per_token(m))


def kv_bytes_per_token(m: dict, dtype_bytes: int) -> int:
    return m["num_layers"] * 2 * m["hidden_size"] * dtype_bytes


def decode_weight_bytes(m: dict, dtype_bytes: int) -> int:
    """What one decode step must read whatever its batch: every layer, the
    final LayerNorm and the (tied) output embedding, in the served type."""
    return (param_count(m) - m["max_seq_len"] * m["hidden_size"]) * dtype_bytes


def train_step_min_bytes(m: dict, tokens: int) -> int:
    """Least HBM traffic of one Adam step: float32 parameters and both
    moments read and written once, the gradient written and read once, the
    int32 ids and labels read. Activations are not counted: an
    implementation may keep them on chip or recompute them."""
    return param_count(m) * 4 * (3 + 3 + 2) + tokens * 4 * 2
