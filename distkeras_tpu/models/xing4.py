"""A decoder with multi-head latent attention and sigmoid routing over
experts, in two residual forms: hyper-connected residual streams with a
bias-corrected choice (``model_type`` ``xing4_0``, as the first stage of a
pipeline of whole layers), or one plain residual stream with sandwich norms
(``pangu_ultra_moe``, as one chip's share of an expert-parallel stage).

Per token, hidden size ``C`` and ``n = hc_mult`` residual streams ``X``
(``n x C``, float32):

- *Ends*: ``X_0 = [e, ..., e]`` (the embedding copied into every stream);
  after the last layer ``x = sum_i X[i]``, the final RMSNorm, the untied
  head.
- *Hyper-connection* around each sublayer ``F`` (its own ``phi_pre``,
  ``phi_post`` ``[nC, n]``, ``phi_res`` ``[nC, n*n]``, ``b_pre``, ``b_post``
  ``[n]``, ``b_res`` ``[n, n]`` and ``alpha = (a_pre, a_post, a_res)``):
  ``x^ = vec(X) / rms(vec(X))``; ``h_pre = sigmoid(a_pre x^ phi_pre +
  b_pre)``; ``h_post = 2 sigmoid(a_post x^ phi_post + b_post)``; ``H~ =
  clamp(a_res mat(x^ phi_res) + b_res)``; ``H_res`` = ``exp(H~)`` after
  ``hc_sinkhorn_iters`` steps of dividing each column, then each row, by its
  sum + ``hc_eps``; ``u = sum_i h_pre[i] X[i]``, ``f = F(RMSNorm_F(u))``,
  ``X[i] <- sum_j H_res[i, j] X[j] + h_post[i] f``.
- *Attention* (MLA): ``c_q = RMSNorm(u W_DQ)``, ``[q_nope | q_rope]_h = c_q
  W_UQ``; ``[c_kv | k_rope] = u W_DKV``, ``c_kv <- RMSNorm(c_kv)``; YaRN
  rotary on ``q_rope`` and on the one shared ``k_rope``; ``[k_nope | v]_h =
  c_kv W_UKV``; causal softmax at ``mscale^2 / sqrt(nope + rope)``; ``y =
  concat_h(o_h) W_O``.
- *FFN*: the first ``first_dense_layers`` a gated SiLU MLP of width
  ``dense_dim``; the others ``s = sigmoid(u W_r)`` (float32), the
  ``experts_per_token`` largest of ``s + b_corr`` chosen, weighted ``s_k /
  sum s_k * routed_scaling_factor``, ``sum_k w_k E_k(u) + sum E_shared(u)``,
  every expert a gated SiLU of width ``expert_dim``.

With ``sandwich_norm`` the residual is one stream ``x`` (float32), each
sublayer between two RMSNorms of its own: ``x <- x + RMSNorm_post_attn(
MLA(RMSNorm_in(x)))``, ``x <- x + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(
x)))``; after the last layer the final RMSNorm and the untied head.
Without ``router_bias`` the choice is the ``experts_per_token`` largest of
``s`` itself.

Parameters are held in ``dtype`` (bfloat16); the streams, the coefficient
path, the norms and the router are float32.

Decoding goes through the paged pool only, one LATENT leaf a layer,
``pool_latent`` ``[C, bt, kv_lora_rank + qk_rope_head_dim]`` (its lanes
padded to the 128-lane tile, ``pool_lanes``): the ``c_kv`` and turned
``k_rope`` of each token, shared by every head.
Decode ticks and prefill chunks read it absorbed
(:func:`distkeras_tpu.ops.attention.paged_latent_attention`); the full
forward without a pool is the expanded form
(:func:`distkeras_tpu.ops.attention.latent_attention`).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu.models.core import Model
from distkeras_tpu.ops.attention import (
    latent_attention,
    paged_kv_update,
    paged_latent_attention,
)
from distkeras_tpu.ops.moe import dropless_experts, sigmoid_topk_route

__all__ = ["Xing4Config", "Xing4", "xing4_tiny", "xing4_pp_stage",
           "openpangu_tiny", "openpangu_ultra_moe_ep16_stage"]

# What a decode tick counts, in the order the module sows it (the engine
# publishes ``<name>_total`` counters; serving/engine.py, tick counters).
TICK_COUNTERS = ("moe_pairs", "moe_pairs_held", "moe_experts_touched",
                 "moe_expert_tokens_max", "kv_positions_resident",
                 "latent_blocks_read")

@dataclasses.dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_hidden_layers: int = 5
    first_dense_layers: int = 1
    dense_dim: int = 9216            # the leading dense layers' MLP width
    expert_dim: int = 1024           # one expert's width, routed or shared
    num_experts: int = 64            # the router's outputs: ALL the model's
    experts_per_token: int = 4
    held_experts: tuple = (0, 64)    # (first, count) of the routed experts here
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    router_bias: bool = True         # a correction bias on the choice (noaux_tc)
    # The residual: hc_mult hyper-connected streams (False), or one plain
    # stream with an RMSNorm before and after each sublayer (True)
    sandwich_norm: bool = False
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq_len: int = 36864
    # positions of one step of the latent pool's read
    latent_key_block: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    # The decode surface (docs/serving.md): the same knobs, with the same
    # meaning, as BertConfig's.
    decode: bool = False
    decode_slots: bool = False
    decode_cache_len: int = 0
    paged_blocks: int = 0
    page_tokens: int = 16
    page_table_blocks: int = 0
    tp_mesh: object = None

    def __post_init__(self):
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.num_experts):
            raise ValueError(f"held_experts {self.held_experts} outside the "
                             f"model's {self.num_experts} experts")
        if not 0 <= self.first_dense_layers <= self.num_hidden_layers:
            raise ValueError(f"first_dense_layers {self.first_dense_layers} "
                             f"outside the {self.num_hidden_layers} layers")

    causal = True  # a decoder: what generation requires of a model

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def latent_width(self) -> int:
        """Lanes of one token's latent row: ``c_kv`` then ``k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_lanes(self) -> int:
        """Lanes of a latent row as the pool stores it: ``latent_width``
        rounded up to the 128-lane tile (576 -> 640). A leaf whose minor
        dimension fills whole tiles is laid out the way the scatter and
        the read want it; one that does not is stored with its block
        dimension minor-most, and every program converts the whole leaf
        there and back (ops/attention.py:paged_kv_update). The padding
        lanes are zeros and add nothing to any product."""
        return -(-self.latent_width // 128) * 128

    @property
    def kv_token_bytes(self) -> int:
        """One token's latent row through every layer, as the pool holds it."""
        return (self.num_layers * self.pool_lanes
                * jnp.dtype(self.dtype).itemsize)

    @property
    def tp_split_sizes(self) -> dict:
        """What a tensor-parallel degree has to divide (the pool's latent
        leaf has no heads: ``kv_pytree_shardings`` refuses to split it)."""
        return {"num_heads": self.num_heads, "vocab_size": self.vocab_size}

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    def decode_module(self, slots: bool = False, **overrides):
        """The decode-mode twin ``(module, config)``; ``overrides`` are the
        paged geometry (``paged_blocks``, ``page_tokens``,
        ``page_table_blocks``), ``decode_cache_len`` and ``tp_mesh``."""
        cfg = dataclasses.replace(self, decode=True, decode_slots=slots,
                                  **overrides)
        return Xing4(cfg), cfg


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequencies for pairs ``i < dim / 2``: the original
    ``base ** (-2i / dim)`` for pairs that turn more than ``beta_fast``
    times over ``original`` positions, divided by ``factor`` for those
    that turn fewer than ``beta_slow`` times, a linear ramp between."""
    def turns_to_pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_to_pair(beta_fast)), 0)
    high = min(math.ceil(turns_to_pair(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def _rotary(x, positions, cfg: Xing4Config):
    """YaRN rotary of ``x`` ``[B, S, H, rope]`` at ``positions`` ``[B, S]``:
    pairs ``(x[2i], x[2i+1])`` turned by ``positions * inv_freq[i]``, the
    turn scaled by ``mscale / mscale_all_dim`` (1 as published). Float32."""
    inv = yarn_inv_freq(x.shape[-1], cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_original_max_positions, cfg.rope_beta_fast,
                        cfg.rope_beta_slow)
    amp = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
           / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    angle = positions[..., None].astype(jnp.float32) * inv  # [B, S, rope/2]
    cos, sin = amp * jnp.cos(angle)[:, :, None], amp * jnp.sin(angle)[:, :, None]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _rms_norm(x, scale, eps: float):
    """RMSNorm in float32; ``scale`` None is the norm without a learned
    scale."""
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if scale is None else y * scale.astype(jnp.float32)


def _matrix(module, name, shape):
    """A matrix (or a stack of them) of the model's dtype, variance 1/fan_in."""
    return module.param(
        name, nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
            batch_axis=tuple(range(len(shape) - 2))),
        shape, module.cfg.dtype)


class _Kernel(nn.Module):
    """One bias-free weight, ``<name>/kernel``."""

    cfg: Xing4Config
    shape: tuple

    @nn.compact
    def __call__(self):
        return _matrix(self, "kernel", self.shape)


class _Experts(nn.Module):
    """``count`` gated-SiLU MLPs of width ``width`` (the experts' where
    None), stacked: ``gate``/``up`` ``[count, C, F]``, ``down`` ``[count,
    F, C]``."""

    cfg: Xing4Config
    count: int
    width: int | None = None

    @nn.compact
    def __call__(self):
        c, f = self.cfg.hidden_size, self.width or self.cfg.expert_dim
        return (_matrix(self, "gate", (self.count, c, f)),
                _matrix(self, "up", (self.count, c, f)),
                _matrix(self, "down", (self.count, f, c)))


def sinkhorn(m, iters: int, eps: float):
    """``iters`` steps of dividing each column of ``m`` ``[..., n, n]`` by
    its sum + ``eps``, then each row by its sum + ``eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """The coefficients around one sublayer, from the streams ``X``
    ``[B, S, n, C]`` (float32): returns ``(u [B, S, C], H_res [B, S, n, n],
    h_post [B, S, n])``, all float32."""

    cfg: Xing4Config

    @nn.compact
    def __call__(self, X):
        cfg = self.cfg
        B, S, n, C = X.shape
        phi = [_matrix(self, "phi_pre", (n * C, n)),
               _matrix(self, "phi_post", (n * C, n)),
               _matrix(self, "phi_res", (n * C, n * n))]
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), cfg.dtype)
        b_post = self.param("b_post", nn.initializers.zeros, (n,), cfg.dtype)
        b_res = self.param("b_res", nn.initializers.zeros, (n, n), cfg.dtype)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           cfg.dtype).astype(jnp.float32)
        with jax.named_scope("hyper_connection"):
            flat = _rms_norm(X.reshape(B, S, n * C), None, cfg.rms_norm_eps)
            proj = jnp.dot(flat, jnp.concatenate(phi, axis=-1).astype(
                jnp.float32), precision=lax.Precision.HIGHEST)
            h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n]
                                   + b_pre.astype(jnp.float32))
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                          + b_post.astype(jnp.float32))
            logits = jnp.clip(alpha[2] * proj[..., 2 * n:].reshape(B, S, n, n)
                              + b_res.astype(jnp.float32), *cfg.hc_clamp)
            h_res = sinkhorn(jnp.exp(logits), cfg.hc_sinkhorn_iters, cfg.hc_eps)
            u = jnp.sum(h_pre[..., None] * X, axis=-2)
        return u, h_res, h_post


def _merge(X, h_res, h_post, f):
    """``X[i] <- sum_j H_res[i, j] X[j] + h_post[i] f``, float32."""
    with jax.named_scope("hyper_connection"):
        mixed = jnp.sum(h_res[..., None] * X[:, :, None, :, :], axis=-2)
        return mixed + h_post[..., None] * f.astype(jnp.float32)[:, :, None, :]


class LatentAttention(nn.Module):
    cfg: Xing4Config

    @nn.compact
    def __call__(self, n, positions=None, block_tables=None, live=None):
        cfg = self.cfg
        B, S, C = n.shape
        H, nope, rope, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        R = cfg.kv_lora_rank
        q_down = _matrix(self, "q_down", (C, cfg.q_lora_rank))
        q_norm = self.param("q_norm_scale", nn.initializers.ones,
                            (cfg.q_lora_rank,), cfg.dtype)
        q_up = _matrix(self, "q_up", (cfg.q_lora_rank, H * (nope + rope)))
        kv_down = _matrix(self, "kv_down", (C, R + rope))
        kv_norm = self.param("kv_norm_scale", nn.initializers.ones, (R,),
                             cfg.dtype)
        kv_up = _matrix(self, "kv_up", (R, H * (nope + dv)))
        w_out = _matrix(self, "out", (H * dv, C))
        with jax.named_scope("latent_attention"):
            c_q = _rms_norm(jnp.dot(n, q_down, preferred_element_type=jnp.float32),
                            q_norm, cfg.rms_norm_eps).astype(cfg.dtype)
            q = jnp.dot(c_q, q_up).reshape(B, S, H, nope + rope)
            kv = jnp.dot(n, kv_down, preferred_element_type=jnp.float32)
            c_kv = _rms_norm(kv[..., :R], kv_norm, cfg.rms_norm_eps)
            paged = cfg.decode and not self.is_initializing()
            starts = positions if paged else jnp.zeros((B,), jnp.int32)
            pos = starts[:, None] + jnp.arange(S)[None, :]
            q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], pos, cfg)
            k_rope = _rotary(kv[..., None, R:], pos, cfg)[:, :, 0]
            latent = jnp.concatenate([c_kv, k_rope], axis=-1).astype(cfg.dtype)
            w_ukv = kv_up.reshape(R, H, nope + dv)
        counts = None
        if cfg.decode:
            pool = self.variable("cache", "pool_latent", jnp.zeros,
                                 (cfg.paged_blocks, cfg.page_tokens,
                                  cfg.pool_lanes), cfg.dtype)
        if paged:
            if positions is None or block_tables is None:
                raise ValueError(
                    "paged decode needs positions [B] and block_tables "
                    "[B, T] passed to every apply")
            row = jnp.pad(latent, ((0, 0), (0, 0),
                                   (0, cfg.pool_lanes - cfg.latent_width)))
            pool.value = paged_kv_update(pool.value, row, block_tables,
                                         positions, cfg.page_tokens)
            out = paged_latent_attention(
                q_nope, q_rope, pool.value, block_tables, positions, w_ukv,
                cfg.softmax_scale, key_block=cfg.latent_key_block)
            if live is not None:
                # the pool blocks the decode read copied: those that
                # cover positions 0..positions[b] (ops/pallas/paged_latent.py)
                blocks = positions // cfg.page_tokens + 1
                counts = {
                    "kv_positions_resident": jnp.sum(
                        jnp.where(live, positions + S, 0)),
                    "latent_blocks_read": jnp.sum(jnp.where(live, blocks, 0))}
        else:
            out = latent_attention(q_nope, q_rope, latent, w_ukv,
                                   cfg.softmax_scale)
        y = jnp.dot(out.reshape(B, S, H * dv), w_out,
                    preferred_element_type=jnp.float32)
        return y, counts


class DecoderLayer(nn.Module):
    cfg: Xing4Config
    dense: bool

    @nn.compact
    def __call__(self, X, positions=None, block_tables=None, live=None):
        cfg = self.cfg
        C = cfg.hidden_size

        def norm(name, x):
            scale = self.param(name, nn.initializers.ones, (C,), cfg.dtype)
            return _rms_norm(x, scale, cfg.rms_norm_eps)

        def attention(a):
            return LatentAttention(cfg, name="attention")(
                a, positions=positions, block_tables=block_tables, live=live)

        if cfg.sandwich_norm:
            with jax.named_scope("sandwich_norm"):
                a = norm("attention_norm_scale", X).astype(cfg.dtype)
            f, counts = attention(a)
            with jax.named_scope("sandwich_norm"):
                X = X + norm("post_attention_norm_scale", f)
                n32 = norm("ffn_norm_scale", X)
            f, counts = self._ffn(n32, live, counts)
            with jax.named_scope("sandwich_norm"):
                return X + norm("post_ffn_norm_scale", f), counts

        u, h_res, h_post = HyperConnection(cfg, name="attention_hc")(X)
        a = norm("attention_norm_scale", u).astype(cfg.dtype)
        f, counts = attention(a)
        X = _merge(X, h_res, h_post, f)

        u, h_res, h_post = HyperConnection(cfg, name="ffn_hc")(X)
        f, counts = self._ffn(norm("ffn_norm_scale", u), live, counts)
        return _merge(X, h_res, h_post, f), counts

    def _ffn(self, n32, live, counts):
        """The FFN of the normed ``n32`` ``[B, S, C]`` (float32): returns
        ``f`` ``[B, S, C]`` float32 and the counts with the expert layer's
        added."""
        cfg = self.cfg
        B, S, C = n32.shape
        tokens = n32.astype(cfg.dtype).reshape(B * S, C)
        if self.dense:
            gate, up, down = (w[0] for w in _Experts(
                cfg, 1, cfg.dense_dim, name="mlp")())
            hidden = (jax.nn.silu(jnp.dot(tokens, gate,
                                          preferred_element_type=jnp.float32))
                      * jnp.dot(tokens, up, preferred_element_type=jnp.float32))
            f = jnp.dot(hidden.astype(cfg.dtype), down,
                        preferred_element_type=jnp.float32)
        else:
            w_router = _Kernel(cfg, (C, cfg.num_experts), name="router")()
            bias = (self.param("router_bias", nn.initializers.zeros,
                               (cfg.num_experts,), cfg.dtype)
                    if cfg.router_bias else None)
            logits = jnp.dot(n32.reshape(B * S, C), w_router.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
            ids, weights = sigmoid_topk_route(
                logits, cfg.experts_per_token, bias=bias,
                scale=cfg.routed_scaling_factor)
            gate, up, down = _Experts(cfg, cfg.held_experts[1], name="experts")()
            f, stats = dropless_experts(
                tokens, gate, up, down, ids, weights, cfg.held_experts,
                live=None if live is None else jnp.repeat(live, S))
            with jax.named_scope("shared_experts"):
                gate, up, down = _Experts(cfg, cfg.num_shared_experts,
                                          name="shared_experts")()
                hidden = (jax.nn.silu(jnp.einsum(
                    "nc,jcf->jnf", tokens, gate,
                    preferred_element_type=jnp.float32))
                    * jnp.einsum("nc,jcf->jnf", tokens, up,
                                 preferred_element_type=jnp.float32))
                f = f + jnp.einsum("jnf,jfc->nc", hidden.astype(cfg.dtype),
                                   down, preferred_element_type=jnp.float32)
            if live is not None:
                counts = {**(counts or {}),
                          "moe_pairs": stats["pairs"],
                          "moe_pairs_held": stats["pairs_held"],
                          "moe_experts_touched": stats["experts_touched"],
                          "moe_expert_tokens_max": stats["expert_rows_max"]}
        return f.reshape(B, S, C), counts


class Xing4(nn.Module):
    """Input: int32 token ids ``[B, S]``. Output: logits ``[B, S, V]``
    (float32)."""

    cfg: Xing4Config

    # the decode tick's counters this module sows into "counters"
    tick_counters = TICK_COUNTERS

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 block_tables=None, stage=None, return_hidden: bool = False):
        cfg = self.cfg
        if stage is not None:
            raise NotImplementedError(
                "Xing4 has no pipeline-stage slices (serve it on a mesh "
                "without a pp axis)")
        if cfg.decode and cfg.paged_blocks <= 0:
            raise ValueError(
                "Xing4 decodes through the paged latent pool only (the "
                "serving engine's): it has no dense cache, so no "
                "generate() and no drafting")
        B, S = token_ids.shape
        embed = self.param("token_embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), cfg.dtype)
        e = embed[token_ids.astype(jnp.int32)].astype(jnp.float32)
        X = e if cfg.sandwich_norm else jnp.broadcast_to(
            e[:, :, None, :], (B, S, cfg.hc_mult, cfg.hidden_size))
        # A decode tick's rows that hold a request (a free slot's table row
        # is all sentinel, ids >= paged_blocks): only those are counted, and
        # only those reach the routed experts.
        live = None
        if (cfg.decode and block_tables is not None
                and not self.is_initializing() and S == 1):
            live = block_tables[:, 0] < cfg.paged_blocks
        totals = {}
        for i in range(cfg.num_layers):
            X, counts = DecoderLayer(
                cfg, dense=i < cfg.first_dense_layers, name=f"layer_{i}")(
                X, positions=positions, block_tables=block_tables, live=live)
            for k, v in (counts or {}).items():
                totals[k] = totals.get(k, 0) + v
        if totals:
            self.sow("counters", "tick", jnp.stack(
                [jnp.asarray(totals[k], jnp.int32) for k in TICK_COUNTERS]))
        x = X if cfg.sandwich_norm else jnp.sum(X, axis=-2)
        if return_hidden:
            return x
        with jax.named_scope("head"):
            scale = self.param("final_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), cfg.dtype)
            w_head = _Kernel(cfg, (cfg.hidden_size, cfg.vocab_size),
                             name="head")()
            h = _rms_norm(x, scale, cfg.rms_norm_eps).astype(cfg.dtype)
            return jnp.dot(h, w_head, preferred_element_type=jnp.float32)


def _flops(cfg: Xing4Config, seq_len: int) -> float:
    """Forward operations of one ``seq_len`` row: every projection, the
    hyper-connections' coefficients (where the model has them), the shared
    experts and the chosen ones held here (at their expectation), and
    causal attention at ``2 H (nope + rope + v)`` a key."""
    C, H, R = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    n = cfg.hc_mult
    attn = (C * cfg.q_lora_rank + cfg.q_lora_rank * H * (nope + rope)
            + C * (R + rope) + R * H * (nope + dv) + H * dv * C)
    hc = 0 if cfg.sandwich_norm else 2 * n * C * (2 * n + n * n)
    expert = 3 * C * cfg.expert_dim
    dense = 3 * C * cfg.dense_dim
    held = cfg.experts_per_token * cfg.held_experts[1] / cfg.num_experts
    moe = (held + cfg.num_shared_experts) * expert + C * cfg.num_experts
    k_dense = cfg.first_dense_layers
    per_token = 2 * (cfg.num_layers * (attn + hc) + k_dense * dense
                     + (cfg.num_layers - k_dense) * moe + C * cfg.vocab_size)
    keys = seq_len * (seq_len + 1) / 2
    return float(seq_len * per_token
                 + cfg.num_layers * 2 * H * (nope + rope + dv) * keys)


def _make(cfg: Xing4Config, seq_len: int, name: str) -> Model:
    module = Xing4(cfg)

    def init_fn(rng):
        return dict(module.init({"params": rng}, jnp.zeros((1, 8), jnp.int32)))

    def apply_fn(variables, x, train=False, rngs=None):
        return module.apply(variables, x), {}

    m = Model(init_fn, apply_fn, name=name, input_shape=(seq_len,),
              output_dim=cfg.vocab_size, flops_per_example=_flops(cfg, seq_len))
    m.config = cfg
    m.flax_module = module
    m.boxed_init = init_fn
    return m


def xing4_tiny(seq_len: int = 64, vocab_size: int = 256,
               held_experts=(0, 8), dtype="bfloat16") -> Model:
    """The block at test size: hidden 64, 4 heads (nope 16, rope 8, v 16),
    ranks 48/32, 4 streams, one dense layer of 96 then two expert layers
    of 8 experts (top-2, width 32) and one shared; YaRN x4 over 16 original
    positions; the latent read in steps of 8 positions. ``dtype="float32"`` is for comparisons to rounding."""
    cfg = Xing4Config(
        vocab_size=vocab_size, hidden_size=64, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_hidden_layers=3, first_dense_layers=1,
        dense_dim=96, expert_dim=32, num_experts=8, experts_per_token=2,
        held_experts=tuple(held_experts), num_shared_experts=1,
        rope_factor=4.0, rope_original_max_positions=16,
        max_seq_len=max(seq_len, 64), latent_key_block=8,
        dtype=jnp.dtype(dtype))
    return _make(cfg, seq_len, "xing4_tiny")


def xing4_pp_stage(seq_len: int = 36864, vocab_size: int = 131072) -> Model:
    """The first stage of ``Xing4.0-29B-A4B`` served as a pipeline of whole
    layers, at the published widths: the embedding, one leading dense
    layer and four expert layers (64 routed experts, top-4, width 1024, one
    shared), 32 latent heads over ranks 768/512, 4 residual streams, and
    the untied head over the whole vocabulary. ~4.05 G parameters, 8.1 GB
    in bfloat16."""
    cfg = Xing4Config(vocab_size=vocab_size, max_seq_len=seq_len)
    return _make(cfg, seq_len, "xing4_pp_stage")


def openpangu_tiny(seq_len: int = 64, vocab_size: int = 256,
                   held_experts=(0, 4), dtype="bfloat16") -> Model:
    """The sandwich-normed block at test size: hidden 64, 4 heads (nope 16,
    rope 8, v 16), ranks 48/32, one dense layer of 96 then two expert
    layers of 8 experts (top-2, width 32, no correction bias, scaled 2.5)
    and one shared, plain RoPE; the routed experts held in two shares of 4
    (``held_experts``); the latent read in steps of 8 positions.
    ``dtype="float32"`` is for comparisons to rounding."""
    cfg = Xing4Config(
        vocab_size=vocab_size, hidden_size=64, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_hidden_layers=3, first_dense_layers=1,
        dense_dim=96, expert_dim=32, num_experts=8, experts_per_token=2,
        held_experts=tuple(held_experts), num_shared_experts=1,
        routed_scaling_factor=2.5, router_bias=False, sandwich_norm=True,
        rms_norm_eps=1e-5, rope_theta=25600000.0, rope_factor=1.0,
        max_seq_len=max(seq_len, 64), latent_key_block=8,
        dtype=jnp.dtype(dtype))
    return _make(cfg, seq_len, "openpangu_tiny")


def openpangu_ultra_moe_ep16_stage(seq_len: int = 36864,
                                   vocab_size: int = 19200) -> Model:
    """One chip's share of the first stage of ``openPangu-Ultra-MoE-718B``
    served with data-parallel attention and 16-way expert parallelism, at
    the published widths: one leading dense layer (18,432) and four expert
    layers (routed experts 0-15 of 256, top-8, width 2,048, one shared,
    no correction bias, scaled 2.5), 128 latent heads over ranks
    1536/512, sandwich norms, plain RoPE at theta 25.6 M, and vocabulary
    rows 0-19,199 (an eighth) for the embedding and the untied head.
    ~4.92 G parameters, 9.84 GB in bfloat16."""
    cfg = Xing4Config(
        vocab_size=vocab_size, hidden_size=7680, num_heads=128,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=5,
        first_dense_layers=1, dense_dim=18432, expert_dim=2048,
        num_experts=256, experts_per_token=8, held_experts=(0, 16),
        num_shared_experts=1, routed_scaling_factor=2.5, router_bias=False,
        sandwich_norm=True, rms_norm_eps=1e-5, rope_theta=25600000.0,
        rope_factor=1.0, max_seq_len=seq_len)
    return _make(cfg, seq_len, "openpangu_ultra_moe_ep16_stage")
