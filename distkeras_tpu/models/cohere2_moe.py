"""A parallel-block decoder with routed and shared experts and window and
full attention layers in one stack (``model_type`` ``cohere2_moe``), as
one device's share of a tensor- and expert-parallel stage.

Per layer, one bias-free LayerNorm feeds attention and the expert layer
side by side: ``n = LN(x)``; ``x <- x + Attn(n) + FFN(n)``.

- *Attention*: ``num_heads`` query heads on ``num_kv_heads`` K/V heads
  (query head ``h`` reads K/V head ``h // (num_heads // num_kv_heads)``),
  no bias. ``layer_types[l] == "sliding_attention"``: interleaved-pair
  rotary over the whole head and a window (query ``i`` sees key ``j`` iff
  ``0 <= i - j < sliding_window``); ``"full_attention"``: no positional
  signal at all and every ``j <= i``.
- *FFN*: sigmoid router over ALL ``num_experts`` of the model, the
  ``experts_per_token`` largest normalised to sum to 1; this device adds
  the terms of the experts it holds (``held_experts = (first, count)``,
  :func:`distkeras_tpu.ops.moe.dropless_experts`) and the mean of the
  ``num_shared_experts`` shared experts; every expert is a gated SiLU MLP.
- *Ends*: tied embedding, final LayerNorm, ``logit_scale``.

The share: ``num_heads``/``num_kv_heads`` are the heads held here with
their rows of the output projection, ``vocab_size`` the rows of the
embedding held here; what the absent devices would add is left out and
nothing stands in for them or for an exchange. Parameters are held in
``dtype`` (bfloat16); LayerNorm, softmax, router scores and the choice of
experts are float32.

Decoding goes through the paged KV pool only (the ``positions`` /
``block_tables`` interface of :class:`distkeras_tpu.models.bert.Bert`):
one block table a row, the same blocks in every layer; a window layer
reads the blocks that cover its window, a full layer the row's whole
virtual context.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.core import Model
from distkeras_tpu.ops.attention import (
    dot_product_attention,
    paged_attention,
    paged_kv_update,
    window_table_entries,
)
from distkeras_tpu.ops.moe import dropless_experts, sigmoid_topk_route

__all__ = ["Cohere2MoeConfig", "Cohere2Moe", "command_a_plus_tiny",
           "command_a_plus_tp8ep8"]

LAYER_TYPES = ("sliding_attention", "full_attention")

# What a decode tick counts, in the order the module sows it (the engine
# publishes ``<name>_total`` counters; serving/engine.py, tick counters).
TICK_COUNTERS = ("moe_pairs", "moe_pairs_held", "moe_experts_touched",
                 "moe_expert_tokens_max", "kv_window_positions_read",
                 "kv_positions_resident")


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 32768          # embedding rows held here
    hidden_size: int = 4096
    num_heads: int = 16              # query heads held here
    num_kv_heads: int = 1            # K/V heads held here
    head_dim: int = 128
    expert_dim: int = 4096           # width of one expert, routed or shared
    num_experts: int = 128           # the router's outputs: ALL the model's
    experts_per_token: int = 8
    held_experts: tuple = (0, 16)    # (first, count) of the routed experts here
    num_shared_experts: int = 4
    layer_types: tuple = ("sliding_attention",) * 3 + ("full_attention",)
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 16384
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
        odd = [t for t in self.layer_types if t not in LAYER_TYPES]
        if odd:
            raise ValueError(f"unknown layer type(s) {odd}; known: {LAYER_TYPES}")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.num_experts):
            raise ValueError(f"held_experts {self.held_experts} outside the "
                             f"model's {self.num_experts} experts")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} K/V heads")

    causal = True  # a decoder: what generation requires of a model

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_token_bytes(self) -> int:
        """K and V of one token through every layer, as the pool holds them."""
        return (self.num_layers * 2 * self.num_kv_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)

    @property
    def tp_split_sizes(self) -> dict:
        """What a tensor-parallel degree has to divide."""
        return {"num_kv_heads": self.num_kv_heads,
                "held_experts": self.held_experts[1],
                "vocab_size": self.vocab_size}

    def decode_module(self, slots: bool = False, **overrides):
        """The decode-mode twin ``(module, config)``; ``overrides`` are the
        paged geometry (``paged_blocks``, ``page_tokens``,
        ``page_table_blocks``), ``decode_cache_len`` and ``tp_mesh``."""
        cfg = dataclasses.replace(self, decode=True, decode_slots=slots,
                                  **overrides)
        return Cohere2Moe(cfg), cfg


def _layer_norm(x, scale, eps: float):
    """Bias-free LayerNorm in float32."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rotary_interleaved(x, positions, theta: float):
    """Rotary embedding over the whole head, pairs ``(x[2i], x[2i+1])``
    (``rope_gptj``). ``x``: ``[B, S, H, D]``, ``positions``: ``[B, S]``.
    Angles and rotation in float32."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions[..., None].astype(jnp.float32) * inv  # [B, S, D/2]
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], D // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _kernel(module, name, shape):
    """A matrix (or a stack of them) of the model's dtype, variance 1/fan_in."""
    return module.param(
        name, nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
            batch_axis=tuple(range(len(shape) - 2))),
        shape, module.cfg.dtype)


class _Kernel(nn.Module):
    """One bias-free weight, ``<name>/kernel``, held in the model's dtype."""

    cfg: Cohere2MoeConfig
    shape: tuple

    @nn.compact
    def __call__(self):
        return _kernel(self, "kernel", self.shape)


class _Experts(nn.Module):
    """``count`` gated-SiLU experts, stacked: ``gate``/``up`` ``[count, H,
    F]``, ``down`` ``[count, F, H]``."""

    cfg: Cohere2MoeConfig
    count: int

    @nn.compact
    def __call__(self):
        h, f = self.cfg.hidden_size, self.cfg.expert_dim
        return (_kernel(self, "gate", (self.count, h, f)),
                _kernel(self, "up", (self.count, h, f)),
                _kernel(self, "down", (self.count, f, h)))


class Attention(nn.Module):
    cfg: Cohere2MoeConfig
    layer_type: str

    @nn.compact
    def __call__(self, n, positions=None, block_tables=None, live=None):
        cfg = self.cfg
        B, S, _ = n.shape
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        sliding = self.layer_type == "sliding_attention"
        window = cfg.sliding_window if sliding else None

        def project(name, heads):
            w = _Kernel(cfg, (cfg.hidden_size, heads * D), name=name)()
            return jnp.dot(n, w).reshape(B, S, heads, D)

        q, k, v = project("query", H), project("key", Hkv), project("value", Hkv)
        paged = cfg.decode and not self.is_initializing()
        starts = positions if paged else jnp.zeros((B,), jnp.int32)
        if sliding:
            pos = starts[:, None] + jnp.arange(S)[None, :]
            q = rotary_interleaved(q, pos, cfg.rope_theta)
            k = rotary_interleaved(k, pos, cfg.rope_theta)
        counts = None
        if cfg.decode:
            C, bt = cfg.paged_blocks, cfg.page_tokens
            pk = self.variable("cache", "pool_key", jnp.zeros,
                               (C, bt, Hkv * D), cfg.dtype)
            pv = self.variable("cache", "pool_value", jnp.zeros,
                               (C, bt, Hkv * D), cfg.dtype)
        if paged:
            if positions is None or block_tables is None:
                raise ValueError(
                    "paged decode needs positions [B] and block_tables "
                    "[B, T] passed to every apply")
            pk.value = paged_kv_update(pk.value, k, block_tables, positions, bt)
            pv.value = paged_kv_update(pv.value, v, block_tables, positions, bt)
            out = paged_attention(q, pk.value, pv.value, block_tables,
                                  positions, kv_heads=Hkv, window=window)
            if sliding and live is not None:
                read = bt * min(block_tables.shape[1],
                                window_table_entries(window, S, bt))
                counts = {
                    "kv_window_positions_read": read * jnp.sum(live),
                    "kv_positions_resident": jnp.sum(
                        jnp.where(live, positions + S, 0))}
        else:
            mask = None
            if sliding:
                i = jnp.arange(S)
                mask = (i[:, None] - i[None, :] < window)[None, None]
            rep = H // Hkv
            out = dot_product_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                mask=mask, causal=True)
        w_out = _Kernel(cfg, (H * D, cfg.hidden_size), name="out")()
        y = jnp.dot(out.reshape(B, S, H * D), w_out,
                    preferred_element_type=jnp.float32)
        return y, counts


class DecoderLayer(nn.Module):
    cfg: Cohere2MoeConfig
    layer_type: str

    @nn.compact
    def __call__(self, x, positions=None, block_tables=None, live=None):
        cfg = self.cfg
        B, S, H = x.shape
        scale = self.param("ln_scale", nn.initializers.ones, (H,), cfg.dtype)
        n32 = _layer_norm(x, scale, cfg.layer_norm_eps)
        n = n32.astype(cfg.dtype)
        attn, counts = Attention(cfg, self.layer_type, name="attention")(
            n, positions=positions, block_tables=block_tables, live=live)
        # Router: scores and choice in float32, over all the model's experts.
        w_router = _Kernel(cfg, (H, cfg.num_experts), name="router")()
        logits = jnp.dot(n32.reshape(B * S, H), w_router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        ids, weights = sigmoid_topk_route(logits, cfg.experts_per_token)
        gate, up, down = _Experts(cfg, cfg.held_experts[1], name="experts")()
        tokens = n.reshape(B * S, H)
        routed, stats = dropless_experts(
            tokens, gate, up, down, ids, weights, cfg.held_experts,
            live=None if live is None else jnp.repeat(live, S))
        with jax.named_scope("shared_experts"):
            gate, up, down = _Experts(cfg, cfg.num_shared_experts,
                                      name="shared_experts")()
            hidden = (jax.nn.silu(jnp.einsum(
                "nh,jhf->jnf", tokens, gate,
                preferred_element_type=jnp.float32))
                * jnp.einsum("nh,jhf->jnf", tokens, up,
                             preferred_element_type=jnp.float32))
            shared = jnp.einsum("jnf,jfh->nh", hidden.astype(cfg.dtype), down,
                                preferred_element_type=jnp.float32)
            shared = shared / cfg.num_shared_experts
        if live is not None:
            counts = {**(counts or {}),
                      "moe_pairs": stats["pairs"],
                      "moe_pairs_held": stats["pairs_held"],
                      "moe_experts_touched": stats["experts_touched"],
                      "moe_expert_tokens_max": stats["expert_rows_max"]}
        y = attn + (routed + shared).reshape(B, S, H)
        return x + y.astype(x.dtype), counts


class Cohere2Moe(nn.Module):
    """Input: int32 token ids ``[B, S]``. Output: logits ``[B, S, V]`` over
    the embedding rows held here (float32)."""

    cfg: Cohere2MoeConfig

    # the decode tick's counters this module sows into "counters"
    tick_counters = TICK_COUNTERS

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 block_tables=None, stage=None, return_hidden: bool = False):
        cfg = self.cfg
        if stage is not None:
            raise NotImplementedError(
                "Cohere2Moe has no pipeline-stage slices yet (serve it on a "
                "mesh without a pp axis)")
        if cfg.decode and cfg.paged_blocks <= 0:
            raise ValueError(
                "Cohere2Moe decodes through the paged KV pool only (the "
                "serving engine's): it has no dense cache, so no "
                "generate() and no drafting")
        embed = self.param("token_embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), cfg.dtype)
        x = embed[token_ids.astype(jnp.int32)]
        # A decode tick's rows that hold a request (a free slot's table row
        # is all sentinel, ids >= paged_blocks): only those are counted, and
        # only those reach the routed experts.
        live = None
        if (cfg.decode and block_tables is not None
                and not self.is_initializing() and token_ids.shape[1] == 1):
            live = block_tables[:, 0] < cfg.paged_blocks
        totals = {}
        for i, layer_type in enumerate(cfg.layer_types):
            x, counts = DecoderLayer(cfg, layer_type, name=f"layer_{i}")(
                x, positions=positions, block_tables=block_tables, live=live)
            for k, v in (counts or {}).items():
                totals[k] = totals.get(k, 0) + v
        if totals:
            self.sow("counters", "tick", jnp.stack(
                [jnp.asarray(totals[k], jnp.int32) for k in TICK_COUNTERS]))
        if return_hidden:
            return x
        with jax.named_scope("head"):
            scale = self.param("ln_final_scale", nn.initializers.ones,
                               (cfg.hidden_size,), cfg.dtype)
            x = _layer_norm(x, scale, cfg.layer_norm_eps).astype(cfg.dtype)
            logits = jnp.einsum("bsh,vh->bsv", x, embed,
                                preferred_element_type=jnp.float32)
            return logits * cfg.logit_scale


def _flops(cfg: Cohere2MoeConfig, seq_len: int) -> float:
    """Forward operations of one ``seq_len`` row on this share, with the
    expected routed experts a token (experts_per_token x held / all)."""
    h, d, f = cfg.hidden_size, cfg.head_dim, cfg.expert_dim
    proj = 2 * h * d * (2 * cfg.num_heads + 2 * cfg.num_kv_heads)
    routed = cfg.experts_per_token * cfg.held_experts[1] / cfg.num_experts
    ffn = 2 * 3 * h * f * (cfg.num_shared_experts + routed) + 2 * h * cfg.num_experts
    attn = 2 * 2 * cfg.num_heads * d * (seq_len + 1) / 2
    return float(seq_len * (cfg.num_layers * (proj + ffn + attn)
                            + 2 * h * cfg.vocab_size))


def _make(cfg: Cohere2MoeConfig, seq_len: int, name: str) -> Model:
    module = Cohere2Moe(cfg)

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


def command_a_plus_tiny(seq_len: int = 64, vocab_size: int = 256,
                        held_experts=(0, 8), dtype="bfloat16") -> Model:
    """The block at test size: hidden 64, 8 query heads on 2 K/V heads of
    16, 8 experts (top-2) with 2 shared, window 8, four layers
    sliding-sliding-sliding-full. ``held_experts=(first, count)`` cuts the
    routed experts to a share; ``dtype="float32"`` is for comparisons to
    rounding."""
    cfg = Cohere2MoeConfig(
        vocab_size=vocab_size, hidden_size=64, num_heads=8, num_kv_heads=2,
        head_dim=16, expert_dim=96, num_experts=8, experts_per_token=2,
        held_experts=tuple(held_experts), num_shared_experts=2,
        sliding_window=8, max_seq_len=max(seq_len, 64),
        dtype=jnp.dtype(dtype))
    return _make(cfg, seq_len, "command_a_plus_tiny")


def command_a_plus_tp8ep8(seq_len: int = 16384,
                          vocab_size: int = 32768) -> Model:
    """One chip's share of ``command-a-plus-05-2026`` served as an 8-way
    tensor- and expert-parallel stage, at the published widths: 16 of 128
    query heads on 1 of 8 K/V heads (head 128), the 4 shared experts
    whole, the router's 128 outputs and 8 a token with 16 of the 128
    routed experts (width 4096), an eighth of the vocabulary, one period
    of the layer pattern (three window layers of 4096, one full). ~4.23 G
    parameters, 8.5 GB in bfloat16."""
    cfg = Cohere2MoeConfig(vocab_size=vocab_size, max_seq_len=seq_len)
    return _make(cfg, seq_len, "command_a_plus_tp8ep8")
