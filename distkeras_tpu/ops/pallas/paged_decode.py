"""One-token decode attention over a paged K/V pool — a Pallas TPU kernel.

:func:`distkeras_tpu.ops.attention.paged_attention` reads K/V pools
``[C, bt, lanes]`` (a block of ``bt`` tokens a row, a token's K/V heads
side by side in ``lanes``) through each row's block table. Its XLA read
gathers every entry a row's table may hold — the whole virtual context for
a full layer, the entries that cover a window for a window layer — into a
copy in HBM, whatever the row holds. For one query a row (a decode tick)
this kernel reads the pool in place: a grid over rows, each row's blocks
copied by DMA from the pool leaf as stored into fast memory, and only the
blocks the row can see:

- a full layer, the entries that cover positions ``0..positions[b]``;
- a window layer, from the entry holding ``positions[b] - window + 1`` up
  to the one holding ``positions[b]``.

A row is read in two passes of steps of several entries: its keys, step
by step, into scores kept in fast memory (float32) with their running max;
the softmax's denominator over all of them; then its values, step by step,
against the normalised probabilities. Each block is copied once, into one
of two buffers, so the next step's copies — at a row's last, the next
row's first — are in flight while a step's products run. Normalising
before the value product, as the XLA read does, keeps the kernel's output
bit for bit the XLA read's on the CPU (an online softmax, which rounds
unnormalised probabilities, changed greedy tokens).

Heads: the query is laid out block-diagonally, ``[H, lanes]``, each head's
``D`` values in the lanes of its K/V head and exact zeros elsewhere, and
scored against the keys as stored. With one K/V head (16 query heads on a
128-lane head) that is the plain ``[H, D] x [D, keys]`` product; with
every head its own K/V (``H`` heads of 64 lanes folded into ``H * 64``) it
is :func:`~distkeras_tpu.ops.attention._folded_heads_attention`'s form, so
a ``D`` below the 128-lane tile never becomes a minor dimension. Each head
keeps its own lanes of the value product.

The arithmetic is the XLA read's, operation for operation: scores from
the pool's dtype, formed in ``score_dtype`` (the folded read's product
gives the query's dtype, the grouped read's float32), then in float32
scaled by ``D ** -0.5`` and masked by ``k_pos <= q_pos`` (and ``k_pos >
q_pos - window``), the softmax's probabilities rounded to the pool's dtype
for the value product, summed in float32. A row whose first
entry is unallocated (``>= C``: a free slot's table is all such entries)
reads nothing and returns zeros. Entries are clamped to the pool as the
gather clamps them, so no copy leaves the pool. Off the TPU the kernel
runs in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_span"]

_NEG = -1e30  # the masked score, as the XLA read has it

# The most bytes of one pool leaf a step copies, in a power of two of
# entries: 64 of a 4 KB block (one 128-lane K/V head), 8 of a 24 KB one
# (768 lanes).
_STEP_BYTES = 256 * 1024

# A whole step's copies are started in groups of this many, each group
# unrolled: a descriptor a few scalar instructions after the last, and a
# kernel small enough that a program traces and lowers it in well under
# a second (unrolling all 64 of a step made it seconds a program).
_GROUP = 4


def paged_decode_span(tables, positions, page_tokens: int, pool_blocks: int,
                      window: int | None = None):
    """The table entries the one-token read copies for each row: ``(first,
    count)``, int32 ``[B]`` each. A full layer reads entries ``0..
    positions[b] // page_tokens``; a window layer from the entry that holds
    ``positions[b] - window + 1``. A row whose first such entry is
    unallocated (``>= pool_blocks``) reads none."""
    T = tables.shape[1]
    last = jnp.minimum(positions // page_tokens, T - 1)
    if window is None:
        first = jnp.zeros_like(last)
    else:
        first = jnp.clip((positions - window + 1) // page_tokens, 0, last)
    allocated = jnp.take_along_axis(tables, first[:, None], axis=1)[:, 0]
    count = jnp.where(allocated < pool_blocks, last - first + 1, 0)
    return first.astype(jnp.int32), count.astype(jnp.int32)


def _kernel(tables_ref, first_ref, count_ref, base_ref, pos_ref, q_ref,
            k_hbm, v_hbm, o_ref, buf, scores_ref, sems, *, entries: int,
            page_tokens: int, table_entries: int, pool_blocks: int,
            kv_heads: int, scale: float, window: int | None, score_dtype):
    b, rows = pl.program_id(0), pl.num_programs(0)
    E, bt, T = entries, page_tokens, table_entries
    G = math.gcd(E, _GROUP)  # E is a table's width where that is smaller

    def steps(row):
        return lax.div(count_ref[row] + E - 1, E)

    # A row's copies are units: its key steps, then its value steps, each
    # into the buffer slot after the previous unit's.
    def copy(row, j, e, slot, pool):
        entry = first_ref[row] + j * E + e
        block = jnp.minimum(tables_ref[row * T + entry], pool_blocks - 1)
        at = e * bt if isinstance(e, int) else pl.multiple_of(e * bt, bt)
        return pltpu.make_async_copy(pool.at[block],
                                     buf.at[slot, pl.ds(at, bt)],
                                     sems.at[slot])

    def each(n, fn):
        def body(e, carry):
            fn(e)
            return carry
        lax.fori_loop(0, n, body, 0)

    def blocks(row, j):
        return jnp.minimum(count_ref[row] - j * E, E)

    def start(row, u):
        """Start the copies of unit ``u`` of ``row``."""
        n_steps = steps(row)
        slot = (base_ref[row] + u) % 2
        j = jnp.where(u < n_steps, u, u - n_steps)
        n = blocks(row, j)
        for pool, is_pool in ((k_hbm, u < n_steps), (v_hbm, u >= n_steps)):

            @pl.when(jnp.logical_and(is_pool, n == E))
            def _whole():  # every step but a row's last: groups unrolled
                def group(g):
                    for e in range(G):
                        copy(row, j, g * G + e, slot, pool).start()
                each(E // G, group)

            @pl.when(jnp.logical_and(is_pool, n < E))
            def _part():
                each(n, lambda e: copy(row, j, e, slot, pool).start())

    def wait(u, j):
        """Wait for unit ``u`` of this row, step ``j`` of its pool."""
        n, slot = blocks(b, j), (base_ref[b] + u) % 2

        @pl.when(n == E)
        def _whole():  # the semaphore counts bytes: one wait for all E
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[slot]).wait()

        @pl.when(n < E)
        def _part():
            each(n, lambda e: copy(b, j, e, slot, k_hbm).wait())
        return buf[slot]  # [E * bt, lanes]

    n_steps = steps(b)

    def fetch_after(u):
        """At unit ``u`` (-1: a row with none): start the next unit, this
        row's, or after its last the next row's first."""
        own = u + 1 < 2 * n_steps
        row = jnp.where(own, b, jnp.minimum(b + 1, rows - 1))

        @pl.when(jnp.logical_or(own, jnp.logical_and(b + 1 < rows,
                                                     count_ref[row] > 0)))
        def _():
            start(row, jnp.where(own, u + 1, 0))

    @pl.when(b == 0)
    def _first():
        # Past a row's blocks the scores are masked, but a probability of 0
        # times an unset lane could still be NaN in the value product: the
        # buffer holds pool rows or zeros, nothing else.
        buf[...] = jnp.zeros_like(buf)

        @pl.when(count_ref[0] > 0)
        def _():
            start(0, 0)

    q = q_ref[...]  # [H, lanes]
    H, lanes = q.shape
    pos = pos_ref[b]

    @pl.when(n_steps == 0)
    def _empty():
        fetch_after(-1)

    def score(j, m):
        fetch_after(j)
        keys = wait(j, j)
        scores = lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, E * bt]
        scores = scores.astype(score_dtype).astype(jnp.float32)
        k_pos = ((first_ref[b] + j * E) * bt
                 + lax.broadcasted_iota(jnp.int32, (1, E * bt), 1))
        seen = k_pos <= pos
        if window is not None:
            seen = jnp.logical_and(seen, k_pos > pos - window)
        scores = jnp.where(seen, scores * scale, _NEG)
        scores_ref[j] = scores
        return jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))

    m = lax.fori_loop(0, n_steps, score, jnp.full((H, 1), _NEG, jnp.float32))

    def total(j, d):
        return d + jnp.sum(jnp.exp(scores_ref[j] - m), axis=-1, keepdims=True)

    d = lax.fori_loop(0, n_steps, total, jnp.zeros((H, 1), jnp.float32))

    def accumulate(j, acc):
        u = n_steps + j
        fetch_after(u)
        values = wait(u, j)
        p = jnp.exp(scores_ref[j] - m) / d
        return acc + jnp.dot(p.astype(values.dtype), values,
                             preferred_element_type=jnp.float32)

    out = lax.fori_loop(0, n_steps, accumulate,
                        jnp.zeros((H, lanes), jnp.float32))  # [H, lanes]
    G, D = H // kv_heads, lanes // kv_heads
    if kv_heads > 1:  # head i * G + g keeps the lanes of its K/V head i
        head = lax.broadcasted_iota(jnp.int32, (G, lanes), 1) // D
        out = sum(jnp.where(head == i, out[i * G:(i + 1) * G], 0.0)
                  for i in range(kv_heads))
    o_ref[...] = out.astype(o_ref.dtype)


def paged_decode_attention(q, pool_k, pool_v, tables, positions, *,
                           kv_heads: int, window: int | None = None,
                           score_dtype=jnp.float32):
    """Attention of one query a row over paged K/V, read in place.

    ``q`` ``[B, 1, H, D]``, row ``b``'s query at virtual position
    ``positions[b]`` (int32 ``[B]``); ``pool_k`` / ``pool_v`` ``[C, bt,
    kv_heads * D]``; ``tables`` int32 ``[B, T]`` (ids ``>= C``
    unallocated); query head ``h`` reads K/V head ``h // (H / kv_heads)``;
    ``window`` as for :func:`~distkeras_tpu.ops.attention.paged_attention`.
    Returns ``[B, 1, H, D]`` in ``q``'s dtype."""
    C, bt, lanes = pool_k.shape
    fit = max(1, _STEP_BYTES // (bt * lanes * jnp.dtype(pool_k.dtype).itemsize))
    entries = min(tables.shape[1], 1 << (fit.bit_length() - 1))
    return _call(q, pool_k, pool_v, tables, positions, kv_heads=kv_heads,
                 window=window, entries=entries,
                 score_dtype=jnp.dtype(score_dtype),
                 interpret=jax.default_backend() != "tpu")


# Jitted, so the kernel is traced and lowered once a program however many
# layers call it with the same static arguments.
@functools.partial(jax.jit, static_argnames=("kv_heads", "window", "entries",
                                             "score_dtype", "interpret"))
def _call(q, pool_k, pool_v, tables, positions, *, kv_heads, window, entries,
          score_dtype, interpret):
    B, _, H, D = q.shape
    C, bt, lanes = pool_k.shape
    T = tables.shape[1]
    n, G, E = kv_heads, H // kv_heads, entries
    itemsize = jnp.dtype(pool_k.dtype).itemsize
    first, count = paged_decode_span(tables, positions, bt, C, window)
    units = 2 * ((count + E - 1) // E)  # a row's key steps and value steps
    base = jnp.cumsum(units) - units  # the row's first unit, all rows counted
    reach = T if window is None else min(T, (window - 1) // bt + 2)
    # [B, H, lanes]: head i * G + g's D values in the lanes of K/V head i
    own = jnp.eye(n, dtype=bool)[:, None, :, None]  # [n, 1, n, 1]
    query = jnp.where(own, q.reshape(B, n, G, 1, D), 0)
    query = query.reshape(B, H, lanes).astype(pool_k.dtype)
    kernel = functools.partial(
        _kernel, entries=E, page_tokens=bt, table_entries=T, pool_blocks=C,
        kv_heads=n, scale=D ** -0.5, window=window, score_dtype=score_dtype)
    keys = B * T * bt  # at most
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, lanes), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, G, lanes), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, E * bt, lanes), pool_k.dtype),
                            pltpu.VMEM((-(-reach // E), H, E * bt),
                                       jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, G, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * H * keys * lanes, transcendentals=H * keys,
            bytes_accessed=2 * keys * lanes * itemsize),
        name="paged_decode_attention",
        interpret=interpret,
    )(tables.reshape(-1), first, count, base, positions, query, pool_k,
      pool_v)
    # [B, G, n, D] -> head i * G + g
    out = jnp.transpose(out.reshape(B, G, n, D), (0, 2, 1, 3))
    return out.reshape(B, 1, H, D)
