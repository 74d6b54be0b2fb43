"""Mixture-of-Experts with expert parallelism (``ep`` mesh axis).

GShard-style routed MoE MLP: tokens are dispatched to experts through a
capacity-bounded one-hot dispatch tensor, each expert runs a dense MLP
over its ``[capacity, d_model]`` slab (one big batched matmul on the MXU),
and outputs are combined with the router gate weights. Expert weight
tensors carry the ``"expert"`` logical axis, which the sharding rules map
to the mesh's ``ep`` axis — under jit, XLA inserts the token all-to-all
between data and expert layouts from the sharding constraints alone.

``router_top_k`` selects Switch-style top-1 (default) or GShard top-2
routing. Top-2: each token goes to its two highest-gate experts with the
two gate values renormalized to sum to 1; second choices queue *behind*
all first choices in each expert's capacity buffer, so under congestion
second choices are dropped first (capacity-aware combine). Dropped
assignments contribute nothing — a token dropped by both experts passes
through the residual unchanged, as in GShard/Switch. The reference
framework has nothing comparable (SURVEY §2: EP absent); this closes the
``ep`` axis of the mesh design.

Beside it, for serving, a DROPLESS expert layer that is told which
experts it holds (:func:`sigmoid_topk_route`, :func:`dropless_experts`):
the router scores every expert of the model, this device computes the
part of the result that its own experts give, and a pair whose expert
lives elsewhere costs nothing and adds nothing — one chip's share of an
expert-parallel stage, run without its exchange.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["MoEMLP", "dropless_experts", "sigmoid_topk_route"]

# Rows of one grouped product: one pass of a 128-row matrix unit. An
# expert with fewer rows pays for a whole step, one with more takes
# several.
_ROWS_PER_STEP = 128


@jax.named_scope("router")
def sigmoid_topk_route(logits, k: int):
    """Sigmoid routing: ``logits`` ``[N, E]`` -> ``(ids [N, k] int32,
    weights [N, k] float32)``, the ``k`` largest of ``sigmoid(logits)``
    per token with their scores normalised to sum to 1 over the ``k``
    chosen (whichever device holds them). Scores and choice in float32."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    top, ids = lax.top_k(scores, k)
    return ids.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)


@jax.named_scope("moe")
def dropless_experts(x, w_gate, w_up, w_down, ids, weights,
                     held: tuple[int, int], live=None):
    """This device's part of a routed gated-SiLU expert layer, without
    a capacity: ``sum_k weights[n, k] * f_{ids[n, k]}(x[n])`` over the
    pairs whose expert is held here, ``f_e(x) = (silu(x Wg_e) * (x
    Wu_e)) Wd_e``.

    ``x``: ``[N, H]``. ``w_gate``/``w_up``: ``[count, H, F]``,
    ``w_down``: ``[count, F, H]`` — the experts ``first .. first +
    count - 1`` of the model, ``held = (first, count)``. ``ids``/
    ``weights``: ``[N, K]`` from the router, over ALL the model's
    experts. ``live``: bool ``[N]``, tokens that count (a free decode
    slot's row is dropped like a pair held elsewhere).

    The ``N*K`` pairs are sorted by expert, held ones first; then one
    loop runs over steps of ``_ROWS_PER_STEP`` sorted rows of ONE expert
    each (``ceil(rows_e / step)`` steps for expert ``e``, none for an
    expert nobody chose, whose weights are then never read), each three
    products against that expert's matrices, indexed in place. The trip
    count is data, every shape is static: one compiled program whatever
    the routing, and no pair is ever dropped — if every token picks the
    same held expert the loop just runs longer.

    Returns ``(y [N, H] float32, stats)`` with ``stats`` int32 scalars
    ``pairs`` (live tokens x K), ``pairs_held``, ``experts_touched``
    (held experts with at least one row) and ``expert_rows_max`` (the
    fullest held expert's rows)."""
    N, K = ids.shape
    first, count = held
    H, C, R = x.shape[-1], _ROWS_PER_STEP, N * K
    e = ids.reshape(R) - first
    mine = (e >= 0) & (e < count)
    if live is not None:
        mine = mine & jnp.repeat(live, K)
    key = jnp.where(mine, e, count)  # pairs held elsewhere sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                    axis=0).astype(jnp.int32)  # rows of each held expert
    offsets = jnp.cumsum(sizes) - sizes
    steps = (sizes + C - 1) // C
    step_end = jnp.cumsum(steps)
    xs = jnp.pad(x[order // K], ((0, C), (0, 0)))  # a step may overhang

    def one_step(i, ys):
        ex = jnp.sum(step_end <= i).astype(jnp.int32)  # whose step i is
        w = i - (step_end[ex] - steps[ex])
        start = offsets[ex] + w * C
        rows = lax.dynamic_slice(xs, (start, 0), (C, H))
        gate = jnp.dot(rows, lax.dynamic_index_in_dim(w_gate, ex, 0, False),
                       preferred_element_type=jnp.float32)
        up = jnp.dot(rows, lax.dynamic_index_in_dim(w_up, ex, 0, False),
                     preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        out = jnp.dot(hidden, lax.dynamic_index_in_dim(w_down, ex, 0, False),
                      preferred_element_type=jnp.float32)
        # rows past this expert's own belong to the next expert's step
        old = lax.dynamic_slice(ys, (start, 0), (C, H))
        own = (jnp.arange(C) < sizes[ex] - w * C)[:, None]
        return lax.dynamic_update_slice(ys, jnp.where(own, out, old),
                                        (start, 0))

    ys = lax.fori_loop(0, step_end[-1], one_step,
                       jnp.zeros((R + C, H), jnp.float32))
    # back to token order; a pair held elsewhere reads a row of zeros
    by_pair = ys[jnp.argsort(order)].reshape(N, K, H)
    y = jnp.einsum("nkh,nk->nh", by_pair, weights.astype(jnp.float32))
    pairs = R if live is None else K * jnp.sum(live)
    stats = {"pairs": jnp.asarray(pairs, jnp.int32),
             "pairs_held": jnp.sum(sizes),
             "experts_touched": jnp.sum(sizes > 0).astype(jnp.int32),
             "expert_rows_max": jnp.max(sizes)}
    return y, stats


class MoEMLP(nn.Module):
    """Top-1 routed expert MLP block: ``x -> x + MoE(LN(x))`` shape-preserving.

    Args:
      num_experts: E.
      mlp_dim: hidden width per expert.
      capacity_factor: per-expert slots = ceil(T/E * factor).
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    # Include the residual add (x + moe(x)). Set False when the caller owns
    # the residual stream (e.g. a transformer block adding around LayerNorm).
    residual: bool = True
    # 1 = Switch-style single expert per token; 2 = GShard top-2 with
    # renormalized gates and second choices dropped first under congestion.
    router_top_k: int = 1
    # Manual expert parallelism for use under shard_map (where GSPMD's
    # sharding-constraint-driven all-to-all is unavailable — the pipelined
    # trunk): each mesh member along ``ep_axis`` holds E/ep_size experts
    # (w_in/w_out leading dim is LOCAL), computes its experts' outputs for
    # the full (replicated-over-ep) token set, and a psum over ``ep_axis``
    # combines. Routing/dispatch stays global; the router is replicated.
    # Leave ep_axis=None for the GSPMD path (full E, logical-axis rules).
    ep_axis: str | None = None
    ep_size: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, S, D = x.shape
        E = self.num_experts
        T = B * S
        if self.router_top_k not in (1, 2):
            raise ValueError(f"router_top_k must be 1 or 2, got {self.router_top_k}")
        # Top-2 sends up to 2T assignments into the buffers; scale capacity
        # so the same capacity_factor keeps the same drop behavior.
        capacity = max(
            1, int(T / E * self.capacity_factor * self.router_top_k)
        )

        tokens = x.reshape(T, D)
        router_kernel = self.param(
            "router",
            nn.with_logical_partitioning(nn.initializers.lecun_normal(), ("embed", "expert")),
            (D, E),
            jnp.float32,
        )
        gates = jax.nn.softmax(
            tokens.astype(jnp.float32) @ router_kernel, axis=-1
        )  # [T, E]
        expert_idx = jnp.argmax(gates, axis=-1)  # [T] first choice
        gate_val = jnp.take_along_axis(gates, expert_idx[:, None], axis=-1)[:, 0]

        # Switch-style load-balancing auxiliary loss: E * Σ_e f_e · P_e,
        # where f_e is the fraction of (first-choice) tokens routed to
        # expert e and P_e the mean router probability. Minimized (=1) at
        # uniform routing. Sown into the "aux_loss" collection; the step
        # engines add it to the task loss when present.
        frac = jnp.mean(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=0)
        prob = jnp.mean(gates, axis=0)
        self.sow("aux_loss", "load_balance", E * jnp.sum(frac * prob))

        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, E]

        def _dispatch_for(onehot_k, base_count):
            """Queue positions for one choice rank; ``base_count`` [E] seats
            already taken by higher-priority ranks."""
            pos = (jnp.cumsum(onehot_k, axis=0) - 1.0) * onehot_k
            pos = pos + base_count[None, :] * onehot_k
            keep = (pos < capacity) * onehot_k  # [T, E] within capacity
            pos_clamped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
            pos_onehot = jax.nn.one_hot(
                (pos_clamped * onehot_k.astype(jnp.int32)).sum(-1),
                capacity,
                dtype=jnp.float32,
            )  # [T, C]
            return keep[:, :, None] * pos_onehot[:, None, :]  # [T, E, C]

        if self.router_top_k == 1:
            dispatch = _dispatch_for(onehot, jnp.zeros((E,), jnp.float32))
            combine = dispatch * gate_val[:, None, None]
        else:
            # Second choice: argmax with the first choice masked out.
            gates2 = gates * (1.0 - onehot)
            expert_idx2 = jnp.argmax(gates2, axis=-1)  # [T]
            gate_val2 = jnp.take_along_axis(gates, expert_idx2[:, None], axis=-1)[:, 0]
            onehot2 = jax.nn.one_hot(expert_idx2, E, dtype=jnp.float32)
            # Renormalize the two winning gates to sum to 1 (GShard).
            denom = gate_val + gate_val2 + 1e-9
            g1 = gate_val / denom
            g2 = gate_val2 / denom
            # All first choices seat before any second choice per expert.
            count1 = jnp.sum(onehot, axis=0)  # [E]
            d1 = _dispatch_for(onehot, jnp.zeros((E,), jnp.float32))
            d2 = _dispatch_for(onehot2, count1)
            dispatch = d1 + d2
            combine = d1 * g1[:, None, None] + d2 * g2[:, None, None]

        if self.ep_axis is not None and E % self.ep_size:
            raise ValueError(
                f"num_experts {E} not divisible by ep_size {self.ep_size}"
            )
        # Leading dim of the expert weights: local shard in manual-ep mode
        # (params arrive pre-sliced by shard_map), full E otherwise.
        E_w = E // self.ep_size if self.ep_axis is not None else E
        w_in = self.param(
            "w_in",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")
            ),
            (E_w, D, self.mlp_dim),
            jnp.float32,
        )
        w_out = self.param(
            "w_out",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")
            ),
            (E_w, self.mlp_dim, D),
            jnp.float32,
        )

        if self.ep_axis is not None:
            # Manual EP: this member computes only its E/ep_size experts
            # (slice the global dispatch/combine down to the local range),
            # then a psum over ep combines the disjoint contributions —
            # tokens are replicated over ep, so no all-to-all is needed.
            ep_idx = jax.lax.axis_index(self.ep_axis)
            lo = ep_idx * E_w
            dispatch = jax.lax.dynamic_slice_in_dim(dispatch, lo, E_w, axis=1)
            combine = jax.lax.dynamic_slice_in_dim(combine, lo, E_w, axis=1)

        # dispatch: token layout -> expert layout (all-to-all under GSPMD ep)
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype), tokens.astype(self.dtype)
        )  # [E_w, C, D]
        h = jnp.einsum("ecd,edm->ecm", expert_in, w_in.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecm,emd->ecd", h, w_out.astype(self.dtype))
        # combine: expert layout -> token layout
        y = jnp.einsum(
            "tec,ecd->td", combine.astype(self.dtype), expert_out
        )
        if self.ep_axis is not None:
            y = jax.lax.psum(y, self.ep_axis)
        y = y.astype(x.dtype).reshape(B, S, D)
        return x + y if self.residual else y

    @staticmethod
    def reference_forward(variables, x, top_k: int = 1):
        """Per-token gather reference (no dispatch tensors, no capacity
        drops) for testing."""
        p = variables["params"]
        B, S, D = x.shape
        tokens = x.reshape(-1, D).astype(jnp.float32)
        gates = jax.nn.softmax(tokens @ p["router"], axis=-1)

        def expert_out(idx):
            w_in = p["w_in"][idx]  # [T, D, M]
            w_out = p["w_out"][idx]  # [T, M, D]
            h = nn.gelu(jnp.einsum("td,tdm->tm", tokens, w_in))
            return jnp.einsum("tm,tmd->td", h, w_out)

        idx1 = jnp.argmax(gates, axis=-1)
        g1 = jnp.take_along_axis(gates, idx1[:, None], axis=-1)[:, 0]
        if top_k == 1:
            y = expert_out(idx1) * g1[:, None]
        else:
            masked = gates * (1.0 - jax.nn.one_hot(idx1, gates.shape[-1]))
            idx2 = jnp.argmax(masked, axis=-1)
            g2 = jnp.take_along_axis(gates, idx2[:, None], axis=-1)[:, 0]
            denom = g1 + g2 + 1e-9
            y = (
                expert_out(idx1) * (g1 / denom)[:, None]
                + expert_out(idx2) * (g2 / denom)[:, None]
            )
        return x + y.reshape(B, S, D).astype(x.dtype)
