"""Attention kernels: blocked softmax attention + ring attention for
sequence/context parallelism.

The reference framework predates attention entirely (2016-era MLPs/CNNs —
SURVEY §5 "long-context: absent"), but long-context is first-class here:
:func:`ring_attention` shards the sequence axis across a mesh axis and
streams K/V blocks around the ring with ``lax.ppermute``, overlapping each
hop with the local block's FLOPs — attention over sequences far larger than
one chip's HBM, with online (flash-style) softmax so nothing materializes an
``S×S`` matrix.

All matmuls run in the input dtype (bfloat16 on TPU); softmax statistics are
kept in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "constrain_heads",
    "dot_product_attention",
    "paged_attention",
    "paged_kv_update",
    "ring_attention",
    "ring_self_attention",
    "sp_batch_spec",
    "window_table_entries",
]


def constrain_heads(x, mesh, axis: str = "tp", dim: int = -2):
    """Pin ``x``'s heads dimension to the mesh's tensor-parallel axis
    with ``with_sharding_constraint`` (no-op outside a sharded context).

    ``dim`` is where the heads live: ``-2`` for ``[B, S, H, D]``
    activations and dense caches, ``-1`` for a paged block pool
    ``[C, bt, H*D]``, whose heads are folded into the minor dimension (a
    contiguous split of ``H*D`` over the axis is the same partition of
    heads). The serving engine's paged decode threads both through
    gather/scatter ops whose index operands (block tables, positions)
    are replicated; left to propagation alone, the SPMD partitioner may
    resolve that mixed evidence by resharding — or worse, all-gathering
    — the multi-MB pool around every scatter. Constraining the heads
    dim at the update/read sites makes the head-parallel layout an
    explicit fact of the program: K/V bytes never move between devices,
    only the (tiny, replicated) indices do. A dimension the axis does
    not divide passes through unconstrained (replicated layouts stay
    legal; the engine refuses a head count its ``tp`` does not divide
    before any pool exists)."""
    if mesh is None or axis not in mesh.axis_names:
        return x
    n = mesh.shape[axis]
    if n <= 1 or x.shape[dim] % n != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * x.ndim
    spec[dim] = axis
    return lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def sp_batch_spec(mesh, seq_axis: str, batch_size: int):
    """The shared ``[B, S, H, D]`` PartitionSpec for every sequence-parallel
    wrapper (ring, ring-flash, Ulysses): sequence over ``seq_axis``, batch
    over ``dp`` — but only when the batch divides it (model init traces with
    a dummy batch of 1; a replicated tiny batch is fine there)."""
    from jax.sharding import PartitionSpec as P

    batch_axis = (
        "dp"
        if "dp" in mesh.axis_names and batch_size % mesh.shape["dp"] == 0
        else None
    )
    return P(batch_axis, seq_axis, None, None)


@jax.named_scope("attention")
def dot_product_attention(q, k, v, mask=None, causal: bool = False):
    """Standard attention. ``q/k/v: [B, S, H, D]`` -> ``[B, S, H, D]``.

    Softmax in float32; einsums stay in the input dtype for the MXU.
    """
    dtype = q.dtype
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        S_q, S_k = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q)
        scores = jnp.where(causal_mask, scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def paged_kv_update(pool, new, tables, positions, page_tokens: int):
    """Scatter per-row K (or V) vectors into a paged block pool.

    ``pool``: ``[C, page_tokens, H*D]`` — the shared block pool (row ``c``
    is one ``page_tokens``-token block; a token's heads lie side by side
    in the minor dimension, the row-major bytes of ``[H, D]``). ``new``:
    ``[B, S, H, D]`` — each batch row's ``S`` new K/V vectors, written
    as rows of ``H*D``. ``tables``: int32 ``[B, T]`` —
    row ``b``'s block table: entry ``t`` is the pool row holding its
    virtual positions ``[t*page_tokens, (t+1)*page_tokens)``; any id
    ``>= C`` marks an unallocated table entry. ``positions``: int32
    ``[B]`` — the virtual position row ``b``'s first new vector writes at.

    All indices are traced, so ONE compiled program serves every table
    layout and every offset — the property that keeps the serving
    engine's decode step at one executable while blocks chain and move.

    Writes that land outside a row's allocated blocks (right-padded
    prefill garbage past the prompt's last block, or a freed slot whose
    table is all-sentinel) are DROPPED wholesale (``mode="drop"``), so a
    row can never scribble on a block it does not own — the paged
    equivalent of the dense cache's "garbage stays in your own row"
    discipline.

    Why the pool is not ``[C, page_tokens, H, D]``: on a TPU an array's
    two minor dimensions are laid out in (8, 128) tiles — (16, 128) for
    bfloat16 — and ``(H, D) = (12, 64)`` would pad to 2.67 x its bytes,
    so the compiler instead stores such a leaf with ``C`` minor-most and
    converts the WHOLE leaf to a scatterable layout and back around
    every update (three leaf-sized copies per leaf per program; the
    decode tick then follows the pool's capacity, not its rows).
    ``(page_tokens, H*D)`` fills whole tiles wherever ``H*D`` is a
    multiple of 128, so the donated leaf is scattered in place as
    stored. ``tests/test_tpu_compile.py`` holds that in the compiled
    programs.

    Multi-token windows (``S > 1``) serve chunked prefill AND the
    speculative verify step: a ``K``-token draft window scatters all
    its K/V in one call, rejected-draft positions are "rolled back" by
    the host simply not advancing ``positions`` past the accepted
    prefix (the next write overwrites them), and draft positions
    overhanging the row's allocated blocks drop — which is why the
    engine clamps the per-row commit length to the allocated span
    rather than requiring lookahead blocks to exist.
    """
    C = pool.shape[0]
    T = tables.shape[1]
    B, S = new.shape[:2]
    pos = positions[:, None] + jnp.arange(S)[None, :]  # [B, S]
    blk = pos // page_tokens
    rows = jnp.take_along_axis(tables, jnp.minimum(blk, T - 1), axis=1)
    # Past the table's reach: force an out-of-range pool row so the
    # scatter drops the write instead of clamping into a real block.
    rows = jnp.where(blk < T, rows, C)
    offs = pos % page_tokens
    new = new.reshape(B, S, -1).astype(pool.dtype)  # rows of H*D
    return pool.at[rows, offs].set(new, mode="drop")


# A query window of at most this many rows (S x heads of a group) goes
# through the folded-heads read: one pass of a 128-row matrix unit
# either way, so the block-diagonal zeros cost nothing.
_FOLDED_QUERY_ROWS = 128


def _folded_heads_attention(q, k, v, mask, head_groups: int):
    """Masked softmax attention of ``q`` ``[B, S, H, D]`` over K/V that
    keep their heads folded, ``[B, L, H*D]``, without ever splitting
    the K/V arrays' minor dimension.

    Each group of ``G = H / head_groups`` neighbouring heads is one
    matrix product: the group's queries are laid out block-diagonally,
    ``[S*G, G*D]`` with head ``g``'s ``D`` values in lanes
    ``[g*D, (g+1)*D)`` and exact zeros elsewhere, against the group's
    K as stored, ``[L, G*D]``. The zeros add nothing to any float32
    accumulation, so scores and outputs are the per-head products'
    own; the values come back ``[S*G, G*D]`` and each head keeps its
    diagonal block. ``G`` times the multiply-adds of the per-head form,
    on a unit that a short window leaves idle; in exchange a ``D`` below
    the 128-lane tile never becomes a minor dimension (for
    ``[B, L, 12, 64]`` bfloat16 that is a padded copy 2.67 x the
    gathered bytes, per K and V, per layer)."""
    B, S, H, D = q.shape
    L = k.shape[1]
    n, G = head_groups, H // head_groups
    k = k.reshape(B, L, n, G * D)
    v = v.reshape(B, L, n, G * D)
    own = jnp.eye(G, dtype=bool)[:, :, None]  # [G, G, 1]
    qbd = jnp.where(own, q.reshape(B, S, n, G, 1, D), 0)  # [B,S,n,G,G,D]
    qbd = qbd.reshape(B, S, n, G, G * D)
    scores = jnp.einsum("bqngj,bknj->bngqk", qbd, k).astype(jnp.float32)
    scores = scores * D ** -0.5
    scores = jnp.where(mask[:, :, None], scores, -1e30)  # mask [B,1,S,L]
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqk,bknj->bqngj", weights, v)
    out = jnp.einsum("bqnggd->bqngd", out.reshape(B, S, n, G, G, D))
    return out.reshape(B, S, H, D)


def _grouped_query_attention(q, k, v, mask):
    """Masked softmax attention of ``q`` ``[B, S, H, D]`` over K/V with
    fewer heads, ``[B, L, Hkv, D]``: query head ``h`` reads K/V head
    ``h // (H // Hkv)``. The K/V arrays are used as they are, never
    repeated: a K/V head's ``H // Hkv`` query heads are rows of one
    product against it. ``mask``: ``[B, 1, S, L]``."""
    B, S, H, D = q.shape
    n = k.shape[2]
    q = q.reshape(B, S, n, H // n, D)
    scores = jnp.einsum("bsngd,blnd->bngsl", q, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, :, None], scores * D ** -0.5, -1e30)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bngsl,blnd->bsngd", weights, v).reshape(B, S, H, D)


def window_table_entries(window: int, S: int, page_tokens: int) -> int:
    """Block-table entries that cover every position a window of ``S``
    queries can see through a sliding window of ``window`` positions:
    ``window + S - 1`` positions in a row touch at most this many blocks
    (``window / page_tokens + 1`` for one query)."""
    return (window + S - 2) // page_tokens + 2


@jax.named_scope("attention")
def paged_attention(q, pool_k, pool_v, tables, positions,
                    head_groups: int = 1, kv_heads: int | None = None,
                    window: int | None = None):
    """Attention over paged (block-pooled) K/V: the serving engine's
    decode-slot read path when KV lives in a shared block pool instead of
    a dense per-slot ``[B, L, H, D]`` cache.

    ``q``: ``[B, S, H, D]`` queries whose first token sits at virtual
    position ``positions[b]`` (int32 ``[B]``). ``pool_k``/``pool_v``:
    ``[C, bt, H*D]`` block pools. ``tables``: int32 ``[B, T]`` per-row
    block tables (ids ``>= C`` = unallocated; the gather clamp reads an
    arbitrary real block there, and the position mask hides it).
    ``head_groups``: into how many groups of neighbouring heads the
    heads may be kept apart — the tensor-parallel degree, so that no
    product mixes lanes of two shards; 1 on one device.

    Each row's virtual K/V is gathered in table order as ``[T*bt, H*D]``
    — position order, exactly the dense cache's layout once the heads
    are split — straight from the pool as stored, and masked with
    the same ``k_pos <= q_pos`` rule the dense decode path uses, so for
    any masked-out tail the softmax contributions are exactly zero and
    the output is that of dense attention over the same resident K/V.
    One compiled program for every table layout.

    The heads are split on the GATHERED array and never on the pool. A
    short window (decode, speculative verify: ``S`` x a group's heads
    within ``_FOLDED_QUERY_ROWS``) does not split them at all
    (:func:`_folded_heads_attention`); a long one (a prefill chunk, one
    row) reshapes its own small gather to ``[B, T*bt, H, D]``.

    ``kv_heads`` (fewer K/V heads than query heads: the pool's rows
    are ``kv_heads * D`` lanes wide) and ``window`` (query ``i`` sees key
    ``j`` iff ``0 <= i - j < window``) select the grouped-query read.
    With a window, a row gathers only the table entries that cover the
    positions its queries can see (:func:`window_table_entries` of them,
    starting at the block that holds ``positions[b] - window + 1``), not
    its whole virtual context: the bytes a window layer reads follow the
    window, whatever the row holds. Both ``None`` is the read above,
    unchanged.

    With an ``S > 1`` query window (speculative verify), query ``j``
    attends to positions ``<= positions[b] + j`` — including the
    window's own earlier K/V written by :func:`paged_kv_update` in the
    same apply — which makes the logits at each window offset identical
    to what one-token-at-a-time decode would have produced given the
    same prefix, the property speculative acceptance depends on.
    """
    B, S, H, D = q.shape
    bt = pool_k.shape[1]
    T = tables.shape[1]
    if kv_heads is not None or window is not None:
        n = kv_heads or H
        q_pos = positions[:, None] + jnp.arange(S)[None, :]  # [B, S]
        if window is None:
            entries, first, seen = T, jnp.zeros_like(positions), tables
        else:
            entries = min(T, window_table_entries(window, S, bt))
            first = jnp.clip((positions - window + 1) // bt, 0, T - entries)
            seen = jnp.take_along_axis(
                tables, first[:, None] + jnp.arange(entries)[None, :], axis=1)
        k = pool_k[seen].reshape(B, entries * bt, n, D)
        v = pool_v[seen].reshape(B, entries * bt, n, D)
        k_pos = (first * bt)[:, None] + jnp.arange(entries * bt)[None, :]
        mask = k_pos[:, None, None, :] <= q_pos[:, None, :, None]
        if window is not None:
            mask &= k_pos[:, None, None, :] > q_pos[:, None, :, None] - window
        return _grouped_query_attention(q, k, v, mask)
    k = pool_k[tables].reshape(B, T * bt, H * D)
    v = pool_v[tables].reshape(B, T * bt, H * D)
    q_pos = positions[:, None] + jnp.arange(S)[None, :]  # [B, S]
    k_pos = jnp.arange(T * bt)
    mask = k_pos[None, None, None, :] <= q_pos[:, None, :, None]  # [B,1,S,L]
    if S * (H // head_groups) <= _FOLDED_QUERY_ROWS:
        return _folded_heads_attention(q, k, v, mask, head_groups)
    return dot_product_attention(q, k.reshape(B, T * bt, H, D),
                                 v.reshape(B, T * bt, H, D), mask=mask)


def _block_attn_update(q, k_blk, v_blk, acc, m, denom, scale, mask=None):
    """One online-softmax accumulation step against a K/V block.

    ``acc``: running numerator [B,S,H,D] (f32); ``m``: running max [B,H,S,1];
    ``denom``: running sum of exp [B,H,S,1]. ``mask`` (broadcastable to
    [B,H,Sq,Sk]): True = attend.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    blk_max = jnp.max(scores, axis=-1, keepdims=True)
    new_m = jnp.maximum(m, blk_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m)
    new_denom = denom * correction + jnp.sum(p, axis=-1, keepdims=True)
    p_v = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk).astype(
        jnp.float32
    )
    # correction is [B,H,S,1] -> align to [B,S,H,1] for the accumulator
    corr_t = jnp.transpose(correction, (0, 2, 1, 3))
    new_acc = acc * corr_t + p_v
    return new_acc, new_m, new_denom


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   stripe: bool = False):
    """Ring attention over a sharded sequence axis.

    To be called **inside** ``shard_map`` (or an equivalent SPMD context)
    where ``q/k/v`` are the per-device sequence shards ``[B, S/p, H, D]`` and
    ``axis_name`` names the mesh axis carrying the sequence dimension. Each
    of the ``p`` steps computes the local block's contribution with online
    softmax, then rotates K/V one hop around the ring (``lax.ppermute`` over
    ICI); compute and the next hop's communication overlap under XLA async
    collectives.
    """
    if stripe and not causal:
        # Mirror ring_flash_attention: stripe only affects the causal
        # mask, so accepting it here would silently give a direct
        # shard_map caller contiguous semantics on striped inputs.
        raise ValueError("stripe=True only changes causal masking; "
                         "non-causal rings are already balanced")
    p = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    S_local = q.shape[1]
    scale = q.shape[-1] ** -0.5
    # Derive the accumulators from q so they carry q's device-varying axes
    # (a plain jnp.zeros would be axis-invariant and reject the scan carry
    # under shard_map's varying-axes check).
    acc = (q * 0.0).astype(jnp.float32)
    stat = jnp.transpose((q[..., :1] * 0.0).astype(jnp.float32), (0, 2, 1, 3))
    m = stat - jnp.inf  # [B, H, S, 1]
    denom = stat
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(carry, step):
        acc, m, denom, k_cur, v_cur = carry
        mask = None
        if causal:
            # K/V shard visiting at `step` originated on device (my - step) % p.
            src = (my - step) % p
            if stripe:
                # Striped layout (ring_flash.stripe_shard): shard m's local
                # index j is global token j*p + m — every hop is a (near-)
                # triangle, balancing causal work across the ring.
                rows = jnp.arange(S_local)[:, None] * p + my
                cols = jnp.arange(S_local)[None, :] * p + src
            else:
                rows = my * S_local + jnp.arange(S_local)[:, None]  # global q
                cols = src * S_local + jnp.arange(S_local)[None, :]  # global k
            mask = (rows >= cols)[None, None]  # [1,1,Sq,Sk]
        acc, m, denom = _block_attn_update(
            q, k_cur, v_cur, acc, m, denom, scale, mask=mask
        )
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, denom, k_nxt, v_nxt), None

    (acc, m, denom, _, _), _ = lax.scan(
        body, (acc, m, denom, k, v), jnp.arange(p)
    )
    denom_t = jnp.transpose(denom, (0, 2, 1, 3))  # [B,S,H,1]
    return (acc / jnp.maximum(denom_t, 1e-30)).astype(q.dtype)


def ring_self_attention(q, k, v, mesh, seq_axis: str = "sp",
                        causal: bool = False, stripe: bool = False):
    """Convenience wrapper: run :func:`ring_attention` under ``shard_map`` on
    ``mesh``, sharding the sequence dimension of ``[B, S, H, D]`` inputs over
    ``seq_axis`` and the batch over ``dp`` if present. ``stripe=True``
    expects inputs in the striped token layout
    (:func:`distkeras_tpu.ops.ring_flash.stripe_shard`)."""
    from jax import shard_map

    if stripe and not causal:
        raise ValueError("stripe=True only changes causal masking")
    spec = sp_batch_spec(mesh, seq_axis, q.shape[0])

    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          stripe=stripe),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
