"""Ulysses sequence parallelism: all-to-all head/sequence re-sharding.

The second of the framework's two sequence/context-parallel attention
strategies (the first is :mod:`distkeras_tpu.ops.ring_flash`). Absent from
the reference (SURVEY §2 parallelism table — the 2016-era framework predates
attention); first-class here because long-context is a stated design goal.

Mechanics (DeepSpeed-Ulysses, Jacobs et al. 2023): activations arrive
sequence-sharded ``[B, S/p, H, D]``. One ``lax.all_to_all`` per tensor
re-shards heads instead of sequence — ``[B, S, H/p, D]`` — so every device
holds the FULL sequence for a 1/p slice of the heads. Attention then runs
entirely locally (dense or flash, causal or not, any mask), and a second
all-to-all restores sequence sharding on the output.

Trade-off vs ring attention (why both exist):

- **Ulysses**: 4 all-to-alls per attention call (q, k, v, out), each moving
  ``B·S·H·D/p`` elements — bandwidth-optimal on an ICI torus, and the local
  attention is a single big MXU-friendly block (no per-hop launch overhead,
  exact causal masking for free). Requires ``num_heads % p == 0`` and
  ``S × S/p`` score memory (or flash locally to avoid it).
- **Ring**: K/V stream hop-by-hop (p ppermutes) with online softmax — no
  head-count constraint, O(S/p) score memory, overlaps compute with
  neighbor traffic; more launches, approximate-free but blockwise.

Short sequences / many heads → Ulysses; extreme context / few heads → ring.
"""

from __future__ import annotations

import functools

import jax
from jax import lax

from distkeras_tpu.ops.attention import dot_product_attention

__all__ = ["ulysses_attention", "ulysses_self_attention"]


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      attn_fn=None):
    """All-to-all sequence-parallel attention (call **inside** shard_map).

    ``q/k/v``: per-device sequence shards ``[B, S/p, H, D]`` where
    ``axis_name`` is the mesh axis carrying the sequence dimension and
    ``H`` is divisible by its size ``p``. Returns ``[B, S/p, H, D]``.

    ``attn_fn(q, k, v, causal=...)`` computes full-sequence attention on the
    local head group ``[B, S, H/p, D]``; defaults to
    :func:`dot_product_attention`.
    """
    p = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % p != 0:
        raise ValueError(
            f"ulysses_attention needs num_heads % axis_size == 0; got "
            f"{H} heads over {p} devices — use ring attention for "
            f"head counts that don't divide"
        )
    if attn_fn is None:
        attn_fn = dot_product_attention

    # seq-sharded [B, S/p, H, D] -> head-sharded [B, S, H/p, D]
    to_heads = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    out = attn_fn(to_heads(q), to_heads(k), to_heads(v), causal=causal)
    # head-sharded [B, S, H/p, D] -> seq-sharded [B, S/p, H, D]
    return lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_self_attention(q, k, v, mesh, seq_axis: str = "sp",
                           causal: bool = False, attn_fn=None):
    """Convenience wrapper: run :func:`ulysses_attention` under ``shard_map``
    on ``mesh``, sharding the sequence dimension of ``[B, S, H, D]`` inputs
    over ``seq_axis`` and the batch over ``dp`` if present.

    Mirrors :func:`distkeras_tpu.ops.attention.ring_self_attention` so the
    two strategies are drop-in interchangeable at the model layer.
    """
    from jax import shard_map

    from distkeras_tpu.ops.attention import sp_batch_spec

    B, S, H, _ = q.shape
    p = mesh.shape[seq_axis]
    if S % p:
        raise ValueError(f"seq_len {S} not divisible by {seq_axis}={p}")
    if H % p:
        raise ValueError(
            f"ulysses_attention needs num_heads % {seq_axis} == 0; got "
            f"{H} heads over {p} devices — use ring attention for "
            f"head counts that don't divide"
        )
    spec = sp_batch_spec(mesh, seq_axis, B)
    # check_vma off: a Pallas attn_fn's pallas_call out_shapes carry no vma
    # annotations (same reason as ring_flash_attention's shard_map).
    fn = shard_map(
        functools.partial(
            ulysses_attention, axis_name=seq_axis, causal=causal,
            attn_fn=attn_fn,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
