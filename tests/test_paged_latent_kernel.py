"""The decode body of the latent read (``ops/pallas/paged_latent.py``), run
in interpret mode on the CPU: one step against the gathered XLA body that a
prefill chunk keeps (``ops/attention.py:_gathered_latent_step``), and the
whole decode read against the expanded attention of the same rows
(``ops/attention.py:latent_attention``).

The state is compared in float32 to float32 rounding; the read's output in
bfloat16 to bfloat16 rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import attention
from distkeras_tpu.ops.pallas.paged_latent import paged_latent_step

SCALE = 0.3


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
                                            (32, 512, 64, 640)])
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
        gathered = attention._gathered_latent_step(
            query, pool, ids, positions, j, *gathered, scale=SCALE)
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
