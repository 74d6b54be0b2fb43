"""Ring flash attention: Pallas flash kernels composed over a sequence-
sharded mesh axis — long-context attention that is exact, trainable, and
never materializes anything bigger than a VMEM tile.

Composition (forward): each device holds ``S/p`` of Q/K/V. Per hop it runs
the flash kernel against the visiting K/V shard, getting that shard's
partial output and per-row logsumexp; partials merge exactly via

    lse = logaddexp(lse, lse_i)
    o   = o * exp(lse_old − lse) + o_i * exp(lse_i − lse)

then K/V rotate one ICI hop (``ppermute``). Causal masking is the ring
three-case: a shard from earlier positions attends fully, the device's own
shard uses the triangular kernel mask, later shards are skipped.

Backward (custom VJP): the merged result *is* dense attention over the full
sequence, so its gradient is the standard FlashAttention backward evaluated
with the **global** logsumexp and Δ = rowsum(dO∘O). The ring runs again:
per hop the dq kernel accumulates into the local dq, and the dk/dv kernels
accumulate into gradient buffers that **rotate with their shards**, arriving
home after the full circle. Memory stays O(S/p · D) per device in both
passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.ops.pallas.flash_attention import (
    _flash_forward,
    dkv_call as _dkv_call,
    dq_call as _dq_call,
)

__all__ = ["ring_flash_attention", "stripe_shard", "stripe_unshard"]


def _stripe_permute(x, p, axis, to_striped):
    """Both stripe directions are the same blocked transpose — expressed
    as reshape+swapaxes (a cheap XLA-fusable transpose, not a gather)."""
    S = x.shape[axis]
    if S % p:
        raise ValueError(f"sequence {S} not divisible by {p} stripes")
    shape = x.shape
    inner = (S // p, p) if to_striped else (p, S // p)
    x = x.reshape(*shape[:axis], *inner, *shape[axis + 1:])
    x = jnp.swapaxes(x, axis, axis + 1)
    return x.reshape(shape)


def stripe_shard(x, p, axis: int = 1):
    """Natural token order -> striped layout: after the usual contiguous
    mesh split into ``p`` shards, shard ``m`` holds tokens ``m, m+p,
    m+2p, ...`` (position ``(m, j)`` = global token ``j*p + m``). Apply to
    q/k/v (and labels/position ids) BEFORE sharding; invert the outputs
    with :func:`stripe_unshard`. Positional embeddings must be added in
    natural order first — the permutation moves tokens, not positions."""
    return _stripe_permute(x, p, axis, to_striped=True)


def stripe_unshard(x, p, axis: int = 1):
    """Inverse of :func:`stripe_shard`."""
    return _stripe_permute(x, p, axis, to_striped=False)


def _fold(x):  # [B, S, H, D] -> [BH, S, D]
    B, S, H, D = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(B * H, S, D)


def _unfold(x, B, H):  # [BH, S, D] -> [B, S, H, D]
    BH, S, D = x.shape
    return jnp.moveaxis(x.reshape(B, H, S, D), 1, 2)


def _hop_forward(q, k_cur, v_cur, mode, block_q, interpret):
    """(o_i, lse_i) for one visiting shard.
    mode: 0=skip, 1=causal (diagonal included), 2=full, 3=strict causal
    (diagonal excluded — the striped layout's later-stripe hops)."""
    bh, s, d = q.shape

    def skip(_):
        return (
            jnp.zeros((bh, s, d), q.dtype),
            jnp.full((bh, s, 1), -jnp.inf, jnp.float32),
        )

    def diag(_):
        return _flash_forward(q, k_cur, v_cur, True, block_q,
                              min(block_q, k_cur.shape[1]), interpret)

    def full(_):
        return _flash_forward(q, k_cur, v_cur, False, block_q,
                              min(block_q, k_cur.shape[1]), interpret)

    def strict(_):
        return _flash_forward(q, k_cur, v_cur, True, block_q,
                              min(block_q, k_cur.shape[1]), interpret,
                              causal_shift=1)

    return lax.switch(mode, [skip, diag, full, strict], None)


def _make_ring(axis_name, causal, block_q, interpret, stripe=False):
    # Per-hop kernel mask. Contiguous layout (stripe=False): the ring
    # three-case — earlier shard full, own shard causal, later shard
    # skipped; under causal masking the work is triangular in the shard
    # index, so the last shard does p hops of work while shard 0 does one,
    # and the lock-step ring idles at ~50% utilization. Striped layout
    # (stripe=True; Striped Attention, Brandon et al. 2023): shard m holds
    # tokens m, m+p, m+2p, ... — global position jq*p + my vs jk*p + src
    # makes every hop either inclusive-causal (src <= my) or strict-causal
    # (src > my): NO skipped hops, near-identical work per hop on every
    # device, ~2x causal ring utilization. Callers permute tokens with
    # stripe_shard()/stripe_unshard().
    def hop_mode(src, my):
        if not causal:
            return jnp.full((), 2, jnp.int32)
        if stripe:
            return jnp.where(src <= my, 1, 3)
        return jnp.where(src == my, 1, jnp.where(src < my, 2, 0))

    @jax.custom_vjp
    def ring(q, k, v):
        o, _ = _ring_fwd_impl(q, k, v)
        return o

    def _ring_fwd_impl(q, k, v):
        p = lax.axis_size(axis_name)
        my = lax.axis_index(axis_name)
        perm = [(i, (i + 1) % p) for i in range(p)]
        bh, s, d = q.shape
        o0 = jnp.zeros((bh, s, d), jnp.float32)
        lse0 = jnp.full((bh, s, 1), -jnp.inf, jnp.float32)
        o0 = lax.pcast(o0, axis_name, to="varying")
        lse0 = lax.pcast(lse0, axis_name, to="varying")

        def hop(carry, step):
            o, lse, k_cur, v_cur = carry
            src = (my - step) % p
            mode = hop_mode(src, my)
            o_i, lse_i = _hop_forward(q, k_cur, v_cur, mode, block_q, interpret)
            new_lse = jnp.logaddexp(lse, lse_i)
            w_old = jnp.exp(lse - new_lse)
            w_new = jnp.exp(lse_i - new_lse)
            o = o * w_old + o_i.astype(jnp.float32) * w_new
            return (o, new_lse, lax.ppermute(k_cur, axis_name, perm),
                    lax.ppermute(v_cur, axis_name, perm)), None

        (o, lse, _, _), _ = lax.scan(hop, (o0, lse0, k, v), jnp.arange(p))
        return o.astype(q.dtype), lse

    def fwd(q, k, v):
        o, lse = _ring_fwd_impl(q, k, v)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        p = lax.axis_size(axis_name)
        my = lax.axis_index(axis_name)
        perm = [(i, (i + 1) % p) for i in range(p)]
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
        )
        dq0 = jnp.zeros_like(q, jnp.float32)
        dk0 = jnp.zeros_like(k, jnp.float32)
        dv0 = jnp.zeros_like(v, jnp.float32)
        dq0 = lax.pcast(dq0, axis_name, to="varying")
        dk0 = lax.pcast(dk0, axis_name, to="varying")
        dv0 = lax.pcast(dv0, axis_name, to="varying")

        def hop(carry, step):
            dq, dk_cur, dv_cur, k_cur, v_cur = carry
            src = (my - step) % p
            mode = hop_mode(src, my)

            def skip(_):
                return (
                    jnp.zeros_like(q),
                    jnp.zeros_like(k_cur),
                    jnp.zeros_like(v_cur),
                )

            def run(is_causal, shift=0):
                def f(_):
                    dq_i = _dq_call(q, k_cur, v_cur, do, lse, delta, is_causal,
                                    block_q, interpret, causal_shift=shift)
                    dk_i, dv_i = _dkv_call(k_cur, v_cur, q, do, lse, delta,
                                           is_causal,
                                           min(block_q, k_cur.shape[1]),
                                           interpret, causal_shift=shift)
                    return dq_i, dk_i, dv_i

                return f

            dq_i, dk_i, dv_i = lax.switch(
                mode, [skip, run(True), run(False), run(True, shift=1)], None
            )
            dq = dq + dq_i.astype(jnp.float32)
            dk_cur = dk_cur + dk_i.astype(jnp.float32)
            dv_cur = dv_cur + dv_i.astype(jnp.float32)
            # gradients rotate WITH their shards so they arrive home
            return (
                dq,
                lax.ppermute(dk_cur, axis_name, perm),
                lax.ppermute(dv_cur, axis_name, perm),
                lax.ppermute(k_cur, axis_name, perm),
                lax.ppermute(v_cur, axis_name, perm),
            ), None

        (dq, dk, dv, _, _), _ = lax.scan(
            hop, (dq0, dk0, dv0, k, v), jnp.arange(p)
        )
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    ring.defvjp(fwd, bwd)
    return ring


def ring_flash_attention(
    q,
    k,
    v,
    mesh,
    seq_axis: str = "sp",
    causal: bool = False,
    block_q: int = 128,
    interpret: bool | None = None,
    stripe: bool = False,
):
    """Ring flash attention over ``[B, S, H, D]`` inputs with the sequence
    dimension sharded over ``mesh[seq_axis]``. Exact (matches dense
    attention) and differentiable; batch shards over ``dp`` when present.

    ``stripe=True`` (causal only): inputs are in the striped token layout
    (:func:`stripe_shard`) — every ring hop then carries near-equal work
    on every device instead of the contiguous layout's triangular skew
    (shard 0 does 1 hop of work, shard p-1 does p), roughly doubling
    causal utilization at identical numerics. Outputs stay striped; invert
    with :func:`stripe_unshard`.
    """
    if stripe and not causal:
        raise ValueError("stripe=True only changes causal masking; "
                         "non-causal rings are already balanced")
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, H, D = q.shape
    p = mesh.shape[seq_axis]
    if S % p:
        raise ValueError(f"seq_len {S} not divisible by {seq_axis}={p}")
    s_local = S // p
    # block must divide the per-device shard or the Pallas grids silently
    # drop the tail rows. Prefer MXU/VPU-aligned divisors: a multiple of 128
    # (full lane tile) when one divides, else a multiple of 8 (sublane) —
    # unaligned blocks compile under interpret mode but fail or tile badly
    # under the real Mosaic TPU compiler.
    cap = min(block_q, s_local)
    requested = block_q
    block_q = 0
    for align in (128, 8):
        if cap < align:
            continue
        for b in range(cap - cap % align, 0, -align):
            if s_local % b == 0:
                block_q = b
                break
        if block_q:
            break
    if not block_q:
        if interpret:
            block_q = cap
            while s_local % block_q:
                block_q -= 1
        elif cap < 8:
            raise ValueError(
                f"block_q={requested} is below the TPU sublane width; pass a "
                f"multiple of 8 (the per-device shard is {s_local} rows)"
            )
        else:
            raise ValueError(
                f"no 8-aligned query block <= {cap} divides the per-device "
                f"sequence shard {s_local} (seq_len {S} / {seq_axis}={p}); "
                f"pad the sequence so each shard is a multiple of 8"
            )

    from distkeras_tpu.ops.attention import sp_batch_spec

    spec = sp_batch_spec(mesh, seq_axis, B)
    ring = _make_ring(seq_axis, causal, block_q, interpret, stripe=stripe)

    def local(q, k, v):  # per-device [B_loc, S_loc, H, D]
        o = ring(_fold(q), _fold(k), _fold(v))
        return _unfold(o, q.shape[0], q.shape[2])

    # check_vma off: pallas_call out_shapes don't carry vma annotations
    fn = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
