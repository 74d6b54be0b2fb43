"""The latent read's kernels (``ops/pallas/paged_latent.py``), run in
interpret mode on the CPU: a decode step, and a prefill chunk's whole read,
against the plain XLA body of one step (``_gathered_step`` below: the step's
rows gathered once, scored and summed with the softmax online in float32);
and the whole read, decode and prefill, against the expanded attention of
the same rows (``ops/attention.py:latent_attention``).

The state is compared in float32 to float32 rounding; the read's output in
bfloat16 to bfloat16 rounding. The kernels are checked at the latent blocks'
widths, 32 heads (Xing4) and 128 (openPangu-Ultra-MoE), as well as at a
test size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import attention
from distkeras_tpu.ops.pallas.paged_latent import (
    paged_latent_chunk,
    paged_latent_step,
)

SCALE = 0.3


def _gathered_step(query, pool, ids, positions, j, acc, m, denom, *, scale):
    """One step of the read in plain XLA for a window of ``S`` queries a
    row: the step's rows gathered once into ``[B, E * bt, lanes]``, read
    for the scores and the values. ``query`` ``[B, S, H, lanes]``; ``acc``
    ``[B, S, H, R]``, ``m`` and ``denom`` ``[B, H, S]``, float32."""
    B, S = query.shape[:2]
    C, bt = pool.shape[:2]
    E, R = ids.shape[1], acc.shape[-1]
    q_pos = positions[:, None] + jnp.arange(S)[None, :]  # [B, S]
    rows = pool[jnp.minimum(ids, C - 1)].reshape(B, E * bt, -1)
    scores = jnp.einsum("bshc,blc->bhsl", query, rows,
                        preferred_element_type=jnp.float32)
    k_pos = j * E * bt + jnp.arange(E * bt)
    seen = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]  # [B,1,S,L]
    scores = jnp.where(seen, scores * scale, -1e30)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))  # [B, H, S]
    p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
    fade = jnp.exp(m - m_new)
    denom = denom * fade + jnp.sum(p, axis=-1)
    part = jnp.einsum("bhsl,blr->bshr", p.astype(pool.dtype), rows[..., :R],
                      preferred_element_type=jnp.float32)
    acc = acc * jnp.transpose(fade, (0, 2, 1))[..., None] + part
    return acc, m_new, denom


def _tables(rng, positions, pool_blocks, entries, bt):
    """Each row's blocks, scattered over the pool, from the start of its
    table to its position; the rest unallocated (``pool_blocks``)."""
    tables = np.full((len(positions), entries), pool_blocks, np.int32)
    free = rng.permutation(pool_blocks)
    for b, pos in enumerate(positions):
        if pos >= 0:
            n = pos // bt + 1
            tables[b, :n], free = free[:n], free[n:]
    return tables


def _state(B, H, R):
    return (jnp.zeros((B, 1, H, R), jnp.float32),
            jnp.full((B, H, 1), -1e30, jnp.float32),
            jnp.zeros((B, H, 1), jnp.float32))


def _close(got, want, rtol):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol,
                                   atol=rtol)


# Rows: one whose reach ends mid-block (13: 3 blocks and 2 positions of a
# fourth), one at position 0, one over every step, one reaching exactly a
# step's edge (23: the last position of step 1), and a free slot (its table
# all unallocated, its position left at 30).
@pytest.mark.parametrize("H,R,rope,lanes", [(4, 32, 8, 128),
                                            (32, 512, 64, 640),
                                            (128, 512, 64, 640)])
def test_each_step_is_the_gathered_body(H, R, rope, lanes):
    """Step by step over four steps of three 4-token blocks (12
    positions), the last past every row's reach: the same running max,
    denominator and accumulator as the XLA body, and a row with nothing in
    a step keeps its state bit for bit."""
    rng = np.random.default_rng(37)
    bt, E, C = 4, 3, 48
    positions = np.array([13, 0, 34, 23, 30], np.int32)
    tables = _tables(rng, [13, 0, 34, 23, -1], C, 4 * E, bt)
    assert (tables[4] == C).all() and (tables[0, 4:] == C).all()
    pool = np.zeros((C, bt, lanes), np.float32)
    pool[..., :R + rope] = rng.standard_normal((C, bt, R + rope))
    query = np.zeros((5, 1, H, lanes), np.float32)
    query[..., :R + rope] = rng.standard_normal((5, 1, H, R + rope))
    pool, query = jnp.asarray(pool), jnp.asarray(query)
    positions = jnp.asarray(positions)
    kernel = gathered = _state(5, H, R)
    for j in range(4):
        ids = jnp.asarray(tables[:, j * E:(j + 1) * E])
        before = kernel
        kernel = paged_latent_step(query, pool, ids, positions, jnp.int32(j),
                                   *kernel, scale=SCALE)
        gathered = _gathered_step(query, pool, ids, positions, j, *gathered,
                                  scale=SCALE)
        live = np.array([True, True, True, True, False])
        _close([s[live] for s in kernel], [s[live] for s in gathered], 2e-4)
        # nothing in this step: row 1 (position 0) after step 0, rows 0 and
        # 3 after step 1, row 2 after step 2, the free slot always
        idle = [b for b, pos in enumerate([13, 0, 34, 23, -1])
                if pos < j * E * bt]
        for s, s0 in zip(kernel, before):
            assert (np.asarray(s)[idle] == np.asarray(s0)[idle]).all()
    assert idle == [0, 1, 2, 3, 4]  # the last step reaches no row


def test_the_decode_read_is_the_expanded_attention_in_bfloat16():
    """The whole decode read (``paged_latent_attention`` with one query a
    row, the kernel inside its loop) at the latent block's widths — 32
    heads, ``R`` 512 and rope 64 in 640 lanes — against the plain expanded
    causal attention of the same rows, both from bfloat16 rows and
    weights; rows at different depths, over several steps."""
    rng = np.random.default_rng(4)
    B, H, nope, rope, dv, R, bt, lanes = 3, 32, 128, 64, 128, 512, 16, 640
    L, key_block = 160, 64  # 10 blocks a row, steps of 4 blocks
    T = L // bt
    k = jax.random.PRNGKey(37)
    latent = jax.random.normal(k, (B, L, R + rope)).astype(jnp.bfloat16)
    w_ukv = (jax.random.normal(jax.random.fold_in(k, 1), (R, H, nope + dv))
             * R ** -0.5).astype(jnp.bfloat16)
    q_nope = jax.random.normal(jax.random.fold_in(k, 2), (B, L, H, nope),
                               jnp.bfloat16)
    q_rope = jax.random.normal(jax.random.fold_in(k, 3), (B, L, H, rope),
                               jnp.bfloat16)
    plain = attention.latent_attention(q_nope, q_rope, latent, w_ukv, SCALE)
    tables = jnp.asarray(rng.permutation(B * T).reshape(B, T), jnp.int32)
    pool = jnp.zeros((B * T, bt, lanes), jnp.bfloat16).at[
        tables.reshape(-1), :, :R + rope].set(latent.reshape(B * T, bt, -1))
    positions = jnp.asarray([L - 1, 70, 9])
    take = jax.vmap(lambda x, i: x[i])
    idx = positions[:, None]
    got = attention.paged_latent_attention(
        take(q_nope, idx), take(q_rope, idx), pool, tables, positions, w_ukv,
        SCALE, key_block=key_block)
    want = take(plain, idx)
    assert got.shape == want.shape == (B, 1, H, dv)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert err.max() < 2e-2 * max(1.0, float(np.abs(np.asarray(
        want, np.float32)).max()))


@pytest.mark.parametrize("H,R,rope,lanes,bt,S", [(4, 32, 8, 128, 4, 8),
                                                 (32, 512, 64, 640, 16, 32),
                                                 (128, 512, 64, 640, 16, 16)])
def test_a_chunk_read_is_the_gathered_body_over_every_step(H, R, rope, lanes,
                                                           bt, S):
    """A prefill chunk's whole read (``paged_latent_chunk``) against the XLA
    body run over every step and divided by its denominator: rows whose
    windows start at 0, mid-block, past a key tile's edge, and deep in the
    table (their last queries reach its end)."""
    rng = np.random.default_rng(38)
    T = 80 // bt * 2  # table entries: 160 positions
    positions = [0, 13, 70, 160 - S]
    C = len(positions) * T + 3
    tables = _tables(rng, [p + S - 1 for p in positions], C, T, bt)
    pool = np.zeros((C, bt, lanes), np.float32)
    pool[..., :R + rope] = rng.standard_normal((C, bt, R + rope))
    query = np.zeros((len(positions), S, H, lanes), np.float32)
    query[..., :R + rope] = rng.standard_normal(query[..., :R + rope].shape)
    pool, query = jnp.asarray(pool), jnp.asarray(query)
    tables, positions = jnp.asarray(tables), jnp.asarray(positions, jnp.int32)
    got = paged_latent_chunk(query, pool, tables, positions, scale=SCALE,
                             rank=R)
    B = len(positions)
    state = (jnp.zeros((B, S, H, R), jnp.float32),
             jnp.full((B, H, S), -1e30, jnp.float32),
             jnp.zeros((B, H, S), jnp.float32))
    E = 2
    for j in range(T // E):
        state = _gathered_step(query, pool, tables[:, j * E:(j + 1) * E],
                               positions, j, *state, scale=SCALE)
    acc, _, denom = state
    want = acc / jnp.maximum(jnp.transpose(denom, (0, 2, 1)), 1e-30)[..., None]
    assert got.shape == (B, H, S, R) and got.dtype == pool.dtype
    _close([got], [jnp.transpose(want, (0, 2, 1, 3))], 2e-4)


def test_the_prefill_read_is_the_expanded_attention_in_bfloat16():
    """A prefill chunk's read (``paged_latent_attention`` with a window of
    queries a row) at the latent block's widths against the plain expanded
    causal attention of the same rows, from bfloat16 rows and weights:
    windows at the start of a row and deep in it."""
    rng = np.random.default_rng(5)
    B, H, nope, rope, dv, R, bt, lanes = 2, 32, 128, 64, 128, 512, 16, 640
    L, S = 160, 16
    T = L // bt
    k = jax.random.PRNGKey(38)
    latent = jax.random.normal(k, (B, L, R + rope)).astype(jnp.bfloat16)
    w_ukv = (jax.random.normal(jax.random.fold_in(k, 1), (R, H, nope + dv))
             * R ** -0.5).astype(jnp.bfloat16)
    q_nope = jax.random.normal(jax.random.fold_in(k, 2), (B, L, H, nope),
                               jnp.bfloat16)
    q_rope = jax.random.normal(jax.random.fold_in(k, 3), (B, L, H, rope),
                               jnp.bfloat16)
    plain = attention.latent_attention(q_nope, q_rope, latent, w_ukv, SCALE)
    tables = jnp.asarray(rng.permutation(B * T).reshape(B, T), jnp.int32)
    pool = jnp.zeros((B * T, bt, lanes), jnp.bfloat16).at[
        tables.reshape(-1), :, :R + rope].set(latent.reshape(B * T, bt, -1))
    positions = jnp.asarray([L - S, 0])
    take = jax.vmap(lambda x, p: jax.lax.dynamic_slice_in_dim(x, p, S))
    got = attention.paged_latent_attention(
        take(q_nope, positions), take(q_rope, positions), pool, tables,
        positions, w_ukv, SCALE, key_block=64)
    want = take(plain, positions)
    assert got.shape == want.shape == (B, S, H, dv)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert err.max() < 2e-2 * max(1.0, float(np.abs(np.asarray(
        want, np.float32)).max()))
