"""BERT encoder family for masked-LM — BASELINE config #5
("BERT-base MLM via DynSGD with GSPMD data+model sharding").

TPU-first design:

- Every weight matrix is annotated with **logical axes**
  (``nn.with_logical_partitioning``) so one model definition serves 1-chip,
  data-parallel, tensor-parallel, and sequence-parallel meshes purely by
  changing the logical→mesh axis rules
  (:func:`distkeras_tpu.parallel.sharding.logical_axis_rules`) — the GSPMD
  way, not hand-written per-layout model variants.
- Attention/MLP matmuls in bfloat16 on the MXU; softmax and layernorm in
  float32.
- Long sequences: the attention layer delegates to
  :mod:`distkeras_tpu.ops.attention`, which provides a blocked/ring-capable
  implementation for sequence/context parallelism.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from distkeras_tpu.models.core import Model
from distkeras_tpu.ops.attention import dot_product_attention

__all__ = ["BertConfig", "Bert", "bert_base_mlm", "bert_tiny_mlm"]


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_seq_len: int = 512
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    # Use the Pallas flash-attention kernel (ops/pallas/flash_attention.py)
    # instead of dense attention. Unmasked attention only.
    use_flash_attention: bool = False
    # > 0 replaces each dense MLP block with a routed MoE of this many
    # experts (ops/moe.py; expert weights shard over the ep mesh axis).
    moe_experts: int = 0
    # Experts per token: 1 = Switch-style, 2 = GShard top-2 routing.
    moe_top_k: int = 1
    # Causal (decoder/GPT-style) attention masking.
    causal: bool = False
    # Sequence-parallel attention: a jax.sharding.Mesh (hashable, so valid
    # as static config) + axis name routes attention through a
    # sequence-parallel kernel — the sequence dimension never gathers.
    ring_mesh: object = None
    ring_axis: str = "sp"
    # Which sequence-parallel strategy when ring_mesh is set: "ring"
    # (ppermute K/V stream, ops/ring_flash.py), "ring_stripe" (same ring
    # in the striped token layout — balanced causal work per hop, ~2x
    # ring utilization; causal only; the model stripes after embedding
    # and unstripes before the head, so the external [B, S, V] contract
    # is unchanged), or "ulysses" (all-to-all head re-sharding,
    # ops/ulysses.py; needs num_heads % sp == 0).
    sp_impl: str = "ring"
    # Incremental decoding: attention layers keep K/V caches of length
    # max_seq_len in a mutable "cache" collection, and positions advance a
    # cache index — the autoregressive-generation config
    # (inference/generate.py). Params are layout-identical to the
    # decode=False model, so trained weights drop in.
    decode: bool = False
    # Continuous-batching decode (serving/engine.py): the cache and
    # positional indices become per-batch-row VECTORS ``[B]`` instead of
    # one shared scalar, so each batch slot can sit at a different
    # sequence position — the property that lets a serving engine admit a
    # new request into a free slot while other slots are mid-decode,
    # inside one compiled step. The same index leaves make prefill
    # restartable at any offset (pre-set them to ``n`` and an apply
    # continues the sequence at position ``n`` — see _decode_attention's
    # non-zero-offset contract), which is what the speculative draft's
    # chunked prefill and per-row rewind build on. Requires ``decode=True``;
    # params are still layout-identical to the training model.
    decode_slots: bool = False
    # > 0: dense decode caches hold this many positions per slot instead
    # of max_seq_len — the serving engine sizes its speculative draft's
    # cache with it (the request context plus the verify window's
    # overhang). Params (pos_embed in particular) are untouched; only
    # the cache variables change size.
    decode_cache_len: int = 0
    # > 0 selects PAGED decode (decode_slots only): K/V lives in a
    # shared block pool of this many fixed-size blocks per layer
    # ([paged_blocks, page_tokens, H*D] cache variables: heads folded
    # into the minor dimension, so a block's (page_tokens, H*D) minor
    # pair fills whole TPU tiles at a head size of 64 as at 128 and the
    # compiled scatter and gather run on the leaf as stored; see
    # ops/attention.paged_kv_update) instead of a dense
    # [B, L, H, D] cache, addressed through per-row block tables.
    # The module becomes position-stateless: the caller passes
    # ``positions`` [B] (each row's write offset) and ``block_tables``
    # [B, page_table_blocks] to every apply — traced arrays, so one
    # compiled step serves every table layout (ops/attention.py
    # paged_kv_update / paged_attention). Ids >= paged_blocks mark
    # unallocated table entries; writes there are dropped.
    paged_blocks: int = 0
    page_tokens: int = 16
    # Block-table length per row: virtual context = page_table_blocks *
    # page_tokens. Required (> 0) when paged_blocks > 0.
    page_table_blocks: int = 0
    # Tensor-parallel serving: a jax.sharding.Mesh (hashable — the same
    # static-config stance as ring_mesh) whose "tp" axis the serving
    # engine shards params and KV over. Decode attention then pins its
    # cache/pool updates and attention outputs to the head-sharded
    # layout (ops/attention.constrain_heads) so the SPMD partitioner
    # can never resolve the mixed sharded-KV/replicated-index evidence
    # by moving KV bytes. Params stay layout-identical; None (the
    # default) changes nothing.
    tp_mesh: object = None

    # -- the decode surface (inference/generate._decode_module) ----------

    @property
    def kv_token_bytes(self) -> int:
        """K and V of one token through every layer, as a cache holds them."""
        return (self.num_layers * 2 * self.hidden_size
                * jnp.dtype(self.dtype).itemsize)

    @property
    def tp_split_sizes(self) -> dict:
        """What a tensor-parallel degree has to divide."""
        return {"num_heads": self.num_heads, "mlp_dim": self.mlp_dim,
                "vocab_size": self.vocab_size}

    def decode_module(self, slots: bool = False, **overrides):
        """The decode-mode twin ``(module, config)``: KV-cache attention,
        no dropout, no sequence parallelism, no flash kernel."""
        cfg = dataclasses.replace(
            self, decode=True, decode_slots=slots, dropout_rate=0.0,
            ring_mesh=None, use_flash_attention=False, **overrides)
        return Bert(cfg), cfg


def _pos_window(pos_embed, starts, S: int, max_seq_len: int):
    """Per-row positional-embedding window ``[B, S, H]``: row ``b`` gets
    the embeddings for positions ``starts[b] .. starts[b] + S - 1``,
    each position clipped to the table INDEPENDENTLY. A windowed
    ``dynamic_slice`` would instead clamp the whole window's start
    backward near the table end, assigning position ``starts[b]`` — a
    position whose output IS committed — a wrong embedding. With
    per-position clipping only the overhanging tail positions (past the
    trained context) read a clamped row, and those are exactly the
    speculative-verify overshoot positions whose output is rejected or
    rolled back, never committed."""
    pos_ids = starts[:, None] + jnp.arange(S, dtype=starts.dtype)[None, :]
    return pos_embed[0][jnp.clip(pos_ids, 0, max_seq_len - 1)]


def _layer_boundary(cfg, x, *, at_boundary: bool):
    """Pin the residual stream at a decode-mode inter-layer boundary with
    an ``optimization_barrier`` so XLA cannot fuse across it. Without
    this, a pipeline-stage slice of the trunk (``stage=``) rounds
    differently from the monolithic apply — the stage jit MUST
    materialize the boundary activation while the whole-model jit is
    free to fuse through it, and the divergent bf16 rounding flips
    near-tie greedy argmaxes. With every boundary barriered, each
    layer is an identical fusion island in both compilations and the
    pp engine stays token-identical to the unsharded one. Decode-mode
    only: training keeps full cross-layer fusion freedom (no parity
    contract spans a training jit boundary)."""
    if at_boundary and cfg.decode:
        from jax import lax

        x = lax.optimization_barrier(x)
    return x


def _dense(features, logical_axes, name=None, dtype=jnp.bfloat16, use_bias=True):
    return nn.Dense(
        features,
        dtype=dtype,
        use_bias=use_bias,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), logical_axes
        ),
        name=name,
    )


class _F32AccumDense(nn.Module):
    """``nn.Dense`` twin whose matmul keeps float32 partial sums until
    after any cross-device reduction — the projection used at the two
    tensor-parallel **psum sites** of the decode path (attention ``out``
    and ``mlp_out``, whose contraction dimension is the one GSPMD splits
    over ``tp``).

    Why it exists: a bfloat16 ``Dense`` rounds its output to bf16, so
    under tensor parallelism each device would round its *partial* sum
    to bf16 before the all-reduce adds them — ~several bf16 ULPs of
    layout-dependent noise per layer, enough to flip greedy argmax on a
    near-tie and break the sharded-vs-unsharded token-identity the
    serving engine promises. Asking the dot for a float32 result
    (``preferred_element_type``) moves the psum BEFORE the one rounding:
    the partials cross the interconnect in f32, and the only remaining
    divergence is f32 reduction-order noise (~1e-7 relative), far below
    the bf16 resolution :func:`...generate.greedy_ids` quantizes to.
    Unsharded this lowering is bit-identical to ``nn.Dense`` — bf16
    matmuls accumulate in f32 on CPU, GPU, and the TPU MXU alike, so the
    explicit form only writes down what the backends already do (the
    sharded parity suite asserts it). Param names/shapes/init match
    ``nn.Dense`` exactly: trained weights drop in either way."""

    features: int
    logical_axes: tuple
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), self.logical_axes),
            (x.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        import jax.lax as lax

        y = lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return y.astype(self.dtype) + bias.astype(self.dtype)


def _reduce_dense(cfg, features, logical_axes, name):
    """The projection for a contraction GSPMD may split: the f32-accum
    twin in decode mode (where sharded/unsharded token identity is a
    contract), plain ``nn.Dense`` otherwise (training's numerics and
    HLO stay exactly as they were)."""
    if cfg.decode:
        return _F32AccumDense(features, logical_axes, cfg.dtype, name=name)
    return _dense(features, logical_axes, name, cfg.dtype)


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, train: bool = False,
                 positions=None, block_tables=None):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads
        qkv_axes = ("embed", "heads")
        q = _dense(cfg.hidden_size, qkv_axes, "query", cfg.dtype)(x)
        k = _dense(cfg.hidden_size, qkv_axes, "key", cfg.dtype)(x)
        v = _dense(cfg.hidden_size, qkv_axes, "value", cfg.dtype)(x)
        B, S = x.shape[0], x.shape[1]
        shape = (B, S, cfg.num_heads, head_dim)
        if cfg.decode:
            out = self._decode_attention(
                q.reshape(shape), k.reshape(shape), v.reshape(shape),
                positions=positions, block_tables=block_tables,
            )
        elif cfg.ring_mesh is not None and mask is None:
            if cfg.sp_impl == "ulysses":
                import functools

                from distkeras_tpu.ops.ulysses import ulysses_self_attention

                if cfg.use_flash_attention:
                    # Compose the strategies: all-to-all to head sharding,
                    # then the Pallas flash kernel over the full local
                    # sequence — no O(S^2) score materialization where the
                    # default dense local attention would build one.
                    from distkeras_tpu.ops.pallas.flash_attention import (
                        flash_attention,
                    )

                    sp_fn = functools.partial(
                        ulysses_self_attention, attn_fn=flash_attention
                    )
                else:
                    sp_fn = ulysses_self_attention
            elif cfg.sp_impl in ("ring", "ring_stripe"):
                import functools

                from distkeras_tpu.ops.ring_flash import ring_flash_attention

                stripe = cfg.sp_impl == "ring_stripe"
                if stripe and not cfg.causal:
                    raise ValueError(
                        "sp_impl='ring_stripe' is causal-only (striping "
                        "balances the causal triangle; non-causal rings "
                        "are already balanced — use sp_impl='ring')"
                    )
                # CONTRACT: with stripe, x must already be in the striped
                # token layout. Bert.__call__ stripes once after embedding;
                # direct EncoderLayer consumers must not set ring_stripe
                # (PipelineTrainer rejects ring_mesh configs outright).
                sp_fn = functools.partial(ring_flash_attention, stripe=stripe)
            else:
                raise ValueError(
                    f"unknown sp_impl {cfg.sp_impl!r}: expected 'ring', "
                    "'ring_stripe', or 'ulysses'"
                )
            out = sp_fn(
                q.reshape(shape), k.reshape(shape), v.reshape(shape),
                cfg.ring_mesh, seq_axis=cfg.ring_axis, causal=cfg.causal,
            )
        elif cfg.use_flash_attention and mask is None:
            from distkeras_tpu.ops.pallas.flash_attention import flash_attention

            out = flash_attention(
                q.reshape(shape), k.reshape(shape), v.reshape(shape),
                causal=cfg.causal,
            )
        else:
            out = dot_product_attention(
                q.reshape(shape), k.reshape(shape), v.reshape(shape),
                mask=mask, causal=cfg.causal,
            )
        out = out.reshape(B, S, cfg.hidden_size)
        return _reduce_dense(cfg, cfg.hidden_size, ("heads", "embed"),
                             "out")(out)

    def _decode_attention(self, q, k, v, positions=None, block_tables=None):
        """KV-cache attention for incremental decoding. One generic path
        serves prefill (S = prompt length, cache index 0) and per-token
        decode (S = 1): new K/V write at the cache index, the query attends
        to the full fixed-length cache under a global-position mask, and the
        index advances by S — every shape static for XLA.

        Non-zero-offset contract (what chunked prefill and the serving
        prefix cache rely on): the write position, the query positions,
        and the positional-embedding slice all derive from the cache/pos
        index leaves, never from an implicit "start at 0" — so an apply
        whose index leaves were pre-set to ``n`` (``inference.generate.
        cache_with_index``) continues a sequence at position ``n``
        exactly as if positions ``[0, n)`` had been run through this same
        module, provided the cache rows ``[0, n)`` hold that prefix's
        K/V (the speculative draft's chunked prefill does this).
        Garbage rows at ``>= n`` stay invisible: ``k_pos <= q_pos`` masks
        every position not yet written by a real token.

        Paged mode (``cfg.paged_blocks > 0``): the cache variables are
        the shared block pools ``[C, page_tokens, H*D]`` and the module
        is position-stateless — ``positions``/``block_tables`` come from
        the caller as traced arrays, the write is a dropped-OOB scatter,
        and the read is a gather over the row's block table
        (ops/attention.py). The ``k_pos <= q_pos`` mask is unchanged, so
        paged greedy output is token-identical to the dense path over
        the same resident K/V."""
        import jax
        import jax.lax as lax

        cfg = self.cfg
        B, S, H, D = q.shape
        if cfg.paged_blocks > 0:
            from distkeras_tpu.ops.attention import (
                constrain_heads,
                paged_attention,
                paged_kv_update,
            )

            C, bt = cfg.paged_blocks, cfg.page_tokens
            pk = self.variable("cache", "pool_key", jnp.zeros,
                               (C, bt, H * D), cfg.dtype)
            pv = self.variable("cache", "pool_value", jnp.zeros,
                               (C, bt, H * D), cfg.dtype)
            if self.is_initializing():
                return dot_product_attention(q, k, v, causal=True)
            if positions is None or block_tables is None:
                raise ValueError(
                    "paged decode needs positions [B] and block_tables "
                    "[B, T] passed to every apply")
            # Tensor-parallel serving: pin the pools (and the per-head
            # attention output below) to the head-sharded layout at the
            # scatter/gather sites, so the replicated table/position
            # indices can never argue the partitioner into moving KV
            # bytes. A pool's heads live in its last dimension (a
            # contiguous split of H*D over tp is the same partition of
            # heads). No-ops when tp_mesh is None.
            pk.value = constrain_heads(
                paged_kv_update(pk.value, k, block_tables, positions, bt),
                cfg.tp_mesh, dim=-1)
            pv.value = constrain_heads(
                paged_kv_update(pv.value, v, block_tables, positions, bt),
                cfg.tp_mesh, dim=-1)
            tp = cfg.tp_mesh.shape.get("tp", 1) if cfg.tp_mesh else 1
            return constrain_heads(
                paged_attention(q, pk.value, pv.value, block_tables,
                                positions,
                                head_groups=tp if H % tp == 0 else 1),
                cfg.tp_mesh)
        L = cfg.decode_cache_len or cfg.max_seq_len
        ck = self.variable("cache", "cached_key", jnp.zeros, (B, L, H, D), cfg.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros, (B, L, H, D), cfg.dtype)
        idx_shape = (B,) if cfg.decode_slots else ()
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros(idx_shape, jnp.int32))
        if self.is_initializing():
            return dot_product_attention(q, k, v, causal=True)
        idx = ci.value
        if cfg.decode_slots:
            from distkeras_tpu.ops.attention import constrain_heads

            # Per-slot positions: each row writes its K/V at its OWN cache
            # index and masks against its own position — slots at different
            # sequence depths coexist in one compiled step. A freed slot's
            # index keeps advancing on garbage tokens, hence the clamp (the
            # OOB write lands at L-S and is overwritten on re-admission).
            write = jax.vmap(
                lambda c, u, i: lax.dynamic_update_slice(c, u, (i, 0, 0))
            )
            ck.value = constrain_heads(
                write(ck.value, k.astype(ck.value.dtype), idx), cfg.tp_mesh)
            cv.value = constrain_heads(
                write(cv.value, v.astype(cv.value.dtype), idx), cfg.tp_mesh)
            ci.value = jnp.minimum(idx + S, L)
            q_pos = idx[:, None] + jnp.arange(S)[None, :]  # [B, S]
            k_pos = jnp.arange(L)
            # [B,1,S,L]: row b's queries see cache positions <= their own.
            mask = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
        else:
            ck.value = lax.dynamic_update_slice(
                ck.value, k.astype(ck.value.dtype), (0, idx, 0, 0)
            )
            cv.value = lax.dynamic_update_slice(
                cv.value, v.astype(cv.value.dtype), (0, idx, 0, 0)
            )
            ci.value = idx + S
            q_pos = idx + jnp.arange(S)  # global positions of these queries
            k_pos = jnp.arange(L)
            mask = (k_pos[None, :] <= q_pos[:, None])[None, None]  # [1,1,S,L]
        return dot_product_attention(q, ck.value, cv.value, mask=mask)


class EncoderLayer(nn.Module):
    cfg: BertConfig
    # Manual expert parallelism for shard_map contexts (the pipelined
    # trunk): forwarded to MoEMLP. None keeps the GSPMD path.
    ep_axis: str | None = None
    ep_size: int = 1

    @nn.compact
    def __call__(self, x, mask=None, train: bool = False,
                 positions=None, block_tables=None):
        cfg = self.cfg
        y = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x)
        y = SelfAttention(cfg, name="attention")(
            y, mask=mask, train=train,
            positions=positions, block_tables=block_tables)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        x = x + y
        y = nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x)
        if cfg.moe_experts > 0:
            from distkeras_tpu.ops.moe import MoEMLP

            y = MoEMLP(
                num_experts=cfg.moe_experts,
                mlp_dim=cfg.mlp_dim,
                dtype=cfg.dtype,
                residual=False,
                router_top_k=cfg.moe_top_k,
                ep_axis=self.ep_axis,
                ep_size=self.ep_size,
                name="moe_mlp",
            )(y, train=train)
        else:
            # (The attention block is a module named "attention", which
            # flax scopes by itself; the dense MLP is three calls.)
            with jax.named_scope("mlp"):
                y = _dense(cfg.mlp_dim, ("embed", "mlp"), "mlp_in",
                           cfg.dtype)(y)
                y = nn.gelu(y)
                y = _reduce_dense(cfg, cfg.hidden_size, ("mlp", "embed"),
                                  "mlp_out")(y)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        # Keep the residual stream in the compute dtype: the MoE block takes
        # the float32 LayerNorm output and would otherwise promote the whole
        # downstream stack to f32 (off the bf16 MXU path).
        return x + y.astype(x.dtype)


class Bert(nn.Module):
    """BERT encoder with a tied-embedding MLM head.

    Input: int32 token ids ``[B, S]``. Output: vocab logits ``[B, S, V]``.
    """

    cfg: BertConfig

    @nn.compact
    def __call__(self, token_ids, train: bool = False,
                 positions=None, block_tables=None, stage=None,
                 return_hidden: bool = False):
        """Full apply, or — with ``stage=(lo, hi, first, last)`` — the
        contiguous layer slice ``[lo, hi)`` of a pipeline stage.

        ``first`` stages take token ids and run the embedding;
        non-first stages take the previous stage's activation
        ``[B, S, H]`` as the first argument instead. ``last`` stages run
        the final LayerNorm + tied head and return logits; non-last
        stages return the raw activation. Stage boundaries are a
        serving-time construct: the module is always *initialized* whole
        (``stage=None``) and the param/cache trees split afterwards
        (``parallel/pp.py``), so stage applies see exactly their own
        subtree.

        ``return_hidden=True`` returns the raw trunk activation
        ``[B, S, H]`` instead of logits (the embedding verb's pooled-
        output source). No params are skipped or added —
        initialization always runs with ``return_hidden=False``, so one
        weight tree serves both shapes."""
        cfg = self.cfg
        lo, hi, first, last = (
            (0, cfg.num_layers, True, True) if stage is None else stage)
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="token_embed",
        )
        if not first:
            # Stage input is the previous stage's activation, already
            # embedded — passed through uncast (the stage boundary must
            # not re-round the stream the monolithic trunk carries).
            x = token_ids
            for i in range(lo, hi):
                x = _layer_boundary(cfg, x, at_boundary=i > lo)
                x = EncoderLayer(cfg, name=f"layer_{i}")(
                    x, train=train,
                    positions=positions, block_tables=block_tables)
            if not last or return_hidden:
                return x
            return self._head(embed, x)
        token_ids = token_ids.astype(jnp.int32)
        pos_embed = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, "seq", "embed")
            ),
            (1, cfg.max_seq_len, cfg.hidden_size),
            jnp.float32,
        )
        S = token_ids.shape[1]
        if cfg.decode and cfg.paged_blocks > 0:
            # Paged decode is position-stateless: the engine passes each
            # row's write offset explicitly, so the positional slice
            # comes from ``positions`` and no index variable exists —
            # admission/preemption never have to splice counters, only
            # hand in different (traced) values.
            if self.is_initializing():
                pos = pos_embed[:, :S]
            else:
                if positions is None:
                    raise ValueError("paged decode needs positions [B]")
                pos = _pos_window(pos_embed, positions, S,
                                  cfg.max_seq_len)  # [B, S, H]
            x = embed(token_ids) + pos.astype(cfg.dtype)
        elif cfg.decode:
            # Positions advance with the KV caches: a cache-collection
            # counter offsets the positional slice per apply (a vector of
            # per-slot counters under decode_slots — each batch row slices
            # the positional table at its own depth).
            B = token_ids.shape[0]
            pi_shape = (B,) if cfg.decode_slots else ()
            pi = self.variable(
                "cache", "pos_index", lambda: jnp.zeros(pi_shape, jnp.int32)
            )
            if self.is_initializing():
                pos = pos_embed[:, :S]
            else:
                import jax.lax as lax

                if cfg.decode_slots:
                    pos = _pos_window(pos_embed, pi.value, S,
                                      cfg.max_seq_len)  # [B, S, H]
                    pi.value = jnp.minimum(pi.value + S, cfg.max_seq_len)
                else:
                    pos = lax.dynamic_slice(
                        pos_embed, (0, pi.value, 0),
                        (1, S, cfg.hidden_size),
                    )
                    pi.value = pi.value + S
            x = embed(token_ids) + pos.astype(cfg.dtype)
        else:
            x = embed(token_ids) + pos_embed[:, :S].astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate, deterministic=not train)(x)
        # Striped sequence parallelism: permute tokens ONCE after the
        # (natural-order) positional embedding and run the whole trunk in
        # the striped layout — attention is the only position-sensitive
        # op, and it gets the striped masks from sp_impl. Un-permuted
        # before the head, so logits stay [B, S, V] in natural order.
        striped = (
            cfg.ring_mesh is not None
            and cfg.sp_impl == "ring_stripe"
            and not cfg.decode
            and stage is None
        )
        if striped:
            from distkeras_tpu.ops.ring_flash import stripe_shard

            sp = dict(cfg.ring_mesh.shape)[cfg.ring_axis]
            x = stripe_shard(x, sp)
        for i in range(lo, hi):
            x = _layer_boundary(cfg, x, at_boundary=i > lo)
            x = EncoderLayer(cfg, name=f"layer_{i}")(
                x, train=train,
                positions=positions, block_tables=block_tables)
        if striped:
            from distkeras_tpu.ops.ring_flash import stripe_unshard

            x = stripe_unshard(x, sp)
        if not last or return_hidden:
            return x
        return self._head(embed, x)

    @jax.named_scope("head")
    def _head(self, embed, x):
        """Final LayerNorm + tied MLM head (the last pipeline stage's
        tail — and the whole model's, when unstaged)."""
        cfg = self.cfg
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        # Tied MLM head: project back through the embedding matrix.
        logits = embed.attend(x.astype(jnp.float32))
        bias = self.param(
            "mlm_bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("vocab",)),
            (cfg.vocab_size,),
            jnp.float32,
        )
        return logits + bias


def _bert_flops(cfg: BertConfig, seq_len: int) -> float:
    # per-token fwd FLOPs ≈ 2 * (4*h^2 + 2*h*mlp) per layer + attention term
    per_token = cfg.num_layers * 2 * (4 * cfg.hidden_size**2 + 2 * cfg.hidden_size * cfg.mlp_dim)
    attn = cfg.num_layers * 2 * 2 * seq_len * cfg.hidden_size  # qk^T + av per token
    head = 2 * cfg.hidden_size * cfg.vocab_size
    return float(seq_len * (per_token + attn + head))


def _make(cfg: BertConfig, seq_len: int, name: str) -> Model:
    module = Bert(cfg)

    def init_fn(rng):
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        variables = module.init({"params": rng, "dropout": rng}, dummy, train=False)
        # Strip Partitioned boxes for the plain (non-GSPMD) paths; the
        # sharded path re-derives specs via eval_shape on boxed_init.
        out = dict(nn.meta.unbox(variables))
        out.pop("aux_loss", None)  # sown per step, not persistent state
        return out

    def boxed_init(rng):
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        out = dict(module.init({"params": rng, "dropout": rng}, dummy, train=False))
        out.pop("aux_loss", None)
        return out

    def apply_fn(variables, x, train=False, rngs=None):
        if train and cfg.moe_experts > 0:
            out, state = module.apply(
                variables, x, train=train, rngs=rngs, mutable=["aux_loss"]
            )
            return out, dict(state)
        return module.apply(variables, x, train=train, rngs=rngs), {}

    m = Model(
        init_fn,
        apply_fn,
        name=name,
        input_shape=(seq_len,),
        output_dim=cfg.vocab_size,
        flops_per_example=_bert_flops(cfg, seq_len),
    )
    m.config = cfg
    m.flax_module = module
    m.boxed_init = boxed_init
    return m


def bert_base_mlm(seq_len: int = 128, vocab_size: int = 30522) -> Model:
    return _make(BertConfig(vocab_size=vocab_size), seq_len, "bert_base_mlm")


def bert_tiny_mlm(seq_len: int = 64, vocab_size: int = 1024,
                  dropout_rate: float = 0.1) -> Model:
    """``dropout_rate=0.0`` gives a fully deterministic forward — what
    cross-layout parity checks need: dropout masks are the one train-time
    computation whose random bits legitimately differ between sharded and
    unsharded lowerings under the legacy (non-partitionable) threefry."""
    cfg = BertConfig(
        vocab_size=vocab_size, hidden_size=128, num_layers=2, num_heads=4,
        mlp_dim=512, max_seq_len=max(seq_len, 64),
        dropout_rate=dropout_rate,
    )
    return _make(cfg, seq_len, "bert_tiny_mlm")


def gpt_tiny(seq_len: int = 64, vocab_size: int = 1024) -> Model:
    """Decoder-only causal LM (GPT-style): same encoder stack with causal
    masking and the tied LM head — next-token training via shifted labels."""
    cfg = BertConfig(
        vocab_size=vocab_size, hidden_size=128, num_layers=2, num_heads=4,
        mlp_dim=512, max_seq_len=max(seq_len, 64), causal=True,
    )
    return _make(cfg, seq_len, "gpt_tiny")


def gpt_small(seq_len: int = 512, vocab_size: int = 50257) -> Model:
    """GPT-2-small-shaped causal LM (124M params)."""
    cfg = BertConfig(
        vocab_size=vocab_size, hidden_size=768, num_layers=12, num_heads=12,
        mlp_dim=3072, max_seq_len=max(seq_len, 512), causal=True,
    )
    return _make(cfg, seq_len, "gpt_small")


def bert_tiny_moe_mlm(
    seq_len: int = 64,
    vocab_size: int = 1024,
    num_experts: int = 4,
    top_k: int = 1,
) -> Model:
    """MoE variant: each MLP block is a routed expert mixture
    (ep-shardable); ``top_k=2`` selects GShard top-2 routing."""
    cfg = BertConfig(
        vocab_size=vocab_size, hidden_size=128, num_layers=2, num_heads=4,
        mlp_dim=512, max_seq_len=max(seq_len, 64), moe_experts=num_experts,
        moe_top_k=top_k,
    )
    return _make(cfg, seq_len, f"bert_tiny_moe{'_top2' if top_k == 2 else ''}_mlm")
