"""The one-token paged read's kernel (``ops/pallas/paged_decode.py``), run in
interpret mode on the CPU, against the XLA read it replaces on a decode
tick (``ops/attention.py:_gathered_attention``: each row's table entries
gathered from the pool, then masked attention over the copy) over the same
bfloat16 pool. Both head layouts the serving blocks use — GPT-2's 12 heads
of 64 lanes folded into 768, Command A+'s 16 query heads on one 128-lane
K/V head — in a full layer and a window layer, with the kernel's own step
size (one step here) and with steps of three entries (started one by one)
and of six (in groups of two), so that rows cross steps and a step copies
part of its entries.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import attention
from distkeras_tpu.ops.pallas import paged_decode

BT, T, WINDOW = 4, 12, 10

# Rows: ends mid-block (13: 3 blocks and 2 positions of a fourth), ends on
# a block's last position (11), starts a block (12), position 0, a free
# slot (its table all unallocated, its position left at 30), and a row
# longer than the window (37: positions 28-37 in a window of 10).
POSITIONS = [13, 11, 12, 0, 30, 37]
FREE = 4


@pytest.mark.parametrize("entries", [None, 3, 6])
@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("H,kv_heads,D", [(12, 12, 64), (16, 1, 128)])
def test_the_kernel_is_the_gathered_read(H, kv_heads, D, window, entries):
    rng = np.random.default_rng(H * 100 + (window or 0) + (entries or 0))
    B, C, lanes = len(POSITIONS), 64, kv_heads * D
    tables = np.full((B, T), C, np.int32)
    free = rng.permutation(C)
    for b, pos in enumerate(POSITIONS):
        if b != FREE:  # blocks from the start of the table to the position
            n = pos // BT + 1
            tables[b, :n], free = free[:n], free[n:]
    pool_k, pool_v = (jnp.asarray(rng.standard_normal((C, BT, lanes)),
                                  jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.bfloat16)
    tables, positions = jnp.asarray(tables), jnp.asarray(POSITIONS, jnp.int32)
    # the scores in the dtype the XLA read forms them in
    grouped = kv_heads < H or window is not None
    score_dtype = jnp.float32 if grouped else jnp.bfloat16
    if entries is None:
        got = paged_decode.paged_decode_attention(
            q, pool_k, pool_v, tables, positions, kv_heads=kv_heads,
            window=window, score_dtype=score_dtype)
    else:
        got = paged_decode._call(q, pool_k, pool_v, tables, positions,
                                 kv_heads=kv_heads, window=window,
                                 entries=entries,
                                 score_dtype=jnp.dtype(score_dtype),
                                 interpret=True)
    want = attention._gathered_attention(
        q, pool_k, pool_v, tables, positions, 1,
        kv_heads if grouped else None, window)
    assert got.shape == want.shape == (B, 1, H, D) and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    live = [b for b in range(B) if b != FREE]
    # to bfloat16 rounding: two units in the last place of a value in [1, 2)
    np.testing.assert_allclose(got[live], want[live], rtol=2 ** -6,
                               atol=2 ** -6)
    assert np.isfinite(got[FREE]).all()


@pytest.mark.parametrize("window", [None, WINDOW])
def test_the_span_is_the_blocks_a_row_can_see(window):
    """What the kernel copies, and what the tick counters count: the
    entries that cover ``0..pos`` (full) or ``pos - window + 1..pos``; a
    free slot none."""
    tables = np.zeros((len(POSITIONS), T), np.int32)
    tables[FREE] = 64
    first, count = paged_decode.paged_decode_span(
        jnp.asarray(tables), jnp.asarray(POSITIONS, jnp.int32), BT, 64, window)
    want_first = [0 if window is None else max(0, (p - WINDOW + 1) // BT)
                  for p in POSITIONS]
    want_count = [p // BT - f + 1 for p, f in zip(POSITIONS, want_first)]
    want_count[FREE] = 0
    assert list(np.asarray(first)) == want_first
    assert list(np.asarray(count)) == want_count
    if window is not None:
        assert want_first[-1] == 7 and want_count[-1] == 3  # 28-39 for 28-37
