"""One step of the absorbed latent read for one-token queries — a Pallas TPU
kernel.

:func:`distkeras_tpu.ops.attention.paged_latent_attention` reads a latent
pool ``[C, bt, lanes]`` in steps of ``E`` table entries, keeping the
softmax online in float32 across steps. For a decode tick (one query a
row) this is the body of one step: a grid over rows, each row's blocks of
the step copied by DMA from the pool, left in HBM, into one buffer of fast
memory, and that one copy read for the scores and for the values. The
next row's copies are in flight while a row's products run (two buffers).
The XLA body, which a prefill chunk keeps, gathers the step's rows into a
copy in HBM and reads that copy twice.

Which blocks a row reads: the step's table entries that cover positions up
to ``positions[b]`` (its query's own). A row whose first entry of the step
is unallocated (``>= C``) reads nothing in it and leaves its state as it
was: a free slot's table is all such entries, and a live row's blocks are
allocated from the start of its table up to its position. An entry is
clamped to the pool as the XLA body clamps it, so no copy leaves the pool.

The arithmetic is the XLA body's, operation for operation: scores in
float32 from the pool's dtype, masked and scaled, the running max and
denominator, the probabilities rounded to the pool's dtype for the value
product. Off the TPU the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_latent_step", "paged_latent_chunk"]

_NEG = -1e30  # the masked score, as the XLA body has it


def _kernel(ids_ref, pos_ref, step_ref, q_ref, pool_ref, acc_ref, m_ref,
            d_ref, acc_out, m_out, d_out, buf, sems, *, entries: int,
            page_tokens: int, pool_blocks: int, scale: float, rank: int):
    b, rows = pl.program_id(0), pl.num_programs(0)
    E, bt = entries, page_tokens
    first = step_ref[0] * (E * bt)  # the step's first position

    def blocks(row):
        """How many of the step's entries ``row`` reads."""
        reach = lax.div(jnp.maximum(pos_ref[row] - first + bt, 0), bt)
        return jnp.where(ids_ref[row * E] < pool_blocks,
                         jnp.minimum(reach, E), 0)

    def copy(row, e, slot):
        block = jnp.minimum(ids_ref[row * E + e], pool_blocks - 1)
        at = e * bt if isinstance(e, int) else pl.multiple_of(e * bt, bt)
        return pltpu.make_async_copy(
            pool_ref.at[block], buf.at[slot, pl.ds(at, bt)], sems.at[slot])

    def each(n, fn):
        def body(e, carry):
            fn(e)
            return carry
        lax.fori_loop(0, n, body, 0)

    def start_row(row):
        n, slot = blocks(row), row % 2

        @pl.when(n == E)
        def _whole():  # most steps: unrolled, no branch a block
            for e in range(E):
                copy(row, e, slot).start()

        @pl.when(jnp.logical_and(n > 0, n < E))
        def _part():
            each(n, lambda e: copy(row, e, slot).start())

    @pl.when(b == 0)
    def _first():
        # Past a row's blocks the scores are masked, but a probability of 0
        # times an unset lane could still be NaN in the value product: the
        # buffer holds pool rows or zeros, nothing else.
        buf[...] = jnp.zeros_like(buf)
        start_row(0)

    @pl.when(b + 1 < rows)
    def _next():
        start_row(b + 1)

    n, slot = blocks(b), b % 2

    @pl.when(n == E)
    def _wait_whole():  # the semaphore counts bytes: one wait for all E
        pltpu.make_async_copy(buf.at[1 - slot], buf.at[slot],
                              sems.at[slot]).wait()

    @pl.when(jnp.logical_and(n > 0, n < E))
    def _wait_part():
        each(n, lambda e: copy(b, e, slot).wait())

    @pl.when(n > 0)
    def _read():
        keys = buf[slot]  # [E * bt, lanes]
        scores = lax.dot_general(
            q_ref[...], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, E * bt]
        k_pos = first + lax.broadcasted_iota(jnp.int32, (1, E * bt), 1)
        seen = k_pos <= pos_ref[b]
        scores = jnp.where(seen, scores * scale, _NEG)
        m = m_ref[...]  # [H, 1]
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        fade = jnp.exp(m - m_new)
        d_out[...] = d_ref[...] * fade + jnp.sum(p, axis=-1, keepdims=True)
        m_out[...] = m_new
        part = jnp.dot(p.astype(keys.dtype), keys[:, :rank],
                       preferred_element_type=jnp.float32)  # [H, R]
        acc_out[...] = acc_ref[...] * fade + part

    @pl.when(n == 0)
    def _keep():
        acc_out[...] = acc_ref[...]
        m_out[...] = m_ref[...]
        d_out[...] = d_ref[...]


def paged_latent_step(query, pool, ids, positions, step, acc, m, denom, *,
                      scale: float):
    """One step of the latent read for one query a row.

    ``query`` ``[B, 1, H, lanes]`` (the absorbed query and the turned rope
    query, in the pool's dtype, zero past them); ``pool`` ``[C, bt,
    lanes]``; ``ids`` int32 ``[B, E]``, the step's table entries;
    ``positions`` int32 ``[B]``; ``step`` int32, the step's index (its
    first position is ``step * E * bt``). The online state, updated in
    place and returned: ``acc`` ``[B, 1, H, R]`` float32 (``R`` = its last
    dimension: the lanes the values are summed over), ``m`` and ``denom``
    ``[B, H, 1]`` float32."""
    return _step(query, pool, ids, positions, step, acc, m, denom,
                 scale=scale, interpret=jax.default_backend() != "tpu")


# A jitted step is traced and lowered once a program however many layers
# call it: each lowering of the kernel takes ~0.7 s of every process's
# start-up, compile cache or not.
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _step(query, pool, ids, positions, step, acc, m, denom, *, scale,
          interpret):
    B, _, H, lanes = query.shape
    C, bt = pool.shape[:2]
    E, R = ids.shape[1], acc.shape[-1]
    kernel = functools.partial(_kernel, entries=E, page_tokens=bt,
                               pool_blocks=C, scale=scale, rank=R)
    row4 = lambda width: pl.BlockSpec(  # noqa: E731
        (None, None, H, width), lambda b, *_: (b, 0, 0, 0))
    stat = pl.BlockSpec((None, H, 1), lambda b, *_: (b, 0, 0))
    itemsize = jnp.dtype(pool.dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row4(lanes), pl.BlockSpec(memory_space=pl.ANY),
                      row4(R), stat, stat],
            out_specs=[row4(R), stat, stat],
            scratch_shapes=[
                pltpu.VMEM((2, E * bt, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(acc.shape, acc.dtype),
                   jax.ShapeDtypeStruct(m.shape, m.dtype),
                   jax.ShapeDtypeStruct(denom.shape, denom.dtype)],
        # the operands count the three scalar-prefetch arrays first
        input_output_aliases={5: 0, 6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * E * bt * (lanes + R),
            transcendentals=B * H * E * bt,
            bytes_accessed=B * E * bt * lanes * itemsize),
        name="paged_latent_step",
        interpret=interpret,
    )(ids.reshape(-1), positions, jnp.reshape(step, (1,)).astype(jnp.int32),
      query, pool, acc, m, denom)


# A prefill chunk's read: queries a tile of one head, and keys a tile.
_CHUNK_QUERIES = 512
_CHUNK_KEYS = 1024


def _chunk_kernel(pos_ref, q_ref, k_ref, o_ref, acc, m, d, *, q_tile: int,
                  k_tile: int, scale: float, rank: int):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first = pos_ref[b] + i * q_tile  # the tile's first query's position

    @pl.when(j == 0)
    def _start():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, _NEG)
        d[...] = jnp.zeros_like(d)

    @pl.when(j * k_tile <= first + q_tile - 1)  # a key the tile can see
    def _read():
        keys = k_ref[...]  # [k_tile, lanes]
        scores = lax.dot_general(
            q_ref[...], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [q_tile, k_tile]
        q_pos = first + lax.broadcasted_iota(jnp.int32, (q_tile, 1), 0)
        k_pos = j * k_tile + lax.broadcasted_iota(jnp.int32, (1, k_tile), 1)
        seen = k_pos <= q_pos
        scores = jnp.where(seen, scores * scale, _NEG)
        m_new = jnp.maximum(m[...], jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        fade = jnp.exp(m[...] - m_new)
        d[...] = d[...] * fade + jnp.sum(p, axis=-1, keepdims=True)
        m[...] = m_new
        acc[...] = acc[...] * fade + jnp.dot(
            p.astype(keys.dtype), keys[:, :rank],
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[...] = (acc[...] / jnp.maximum(d[...], 1e-30)).astype(o_ref.dtype)


def paged_latent_chunk(query, pool, tables, positions, *, scale: float,
                       rank: int):
    """The whole latent read of a window of queries a row (a prefill
    chunk), normalised: ``[B, H, S, rank]`` in the pool's dtype, the
    values summed over a row's first ``rank`` lanes (its ``c_kv``).

    ``query`` ``[B, S, H, lanes]`` (as for :func:`paged_latent_step`, query
    ``s`` of row ``b`` at ``positions[b] + s``); ``tables`` int32 ``[B,
    T]``, ids ``>= C`` unallocated. Each row's blocks are gathered once, in
    table order, into ``[B, T * bt, lanes]``; the kernel then runs flash
    attention over them, a grid over rows, heads, tiles of queries and
    tiles of keys with the softmax online in fast memory. A key tile past
    what every query of a tile can see is neither copied nor computed.
    The arithmetic is the decode step's: scores in float32, masked and
    scaled, the probabilities rounded to the pool's dtype for the values,
    the sum divided by the denominator (at least 1e-30) at the end."""
    return _chunk(query, pool, tables, positions, scale=scale, rank=rank,
                  interpret=jax.default_backend() != "tpu")


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _chunk(query, pool, tables, positions, *, scale, rank, interpret):
    B, S, H, lanes = query.shape
    C, bt = pool.shape[:2]
    T = tables.shape[1]
    k_tile = bt * min(T, max(1, _CHUNK_KEYS // bt))
    entries = k_tile // bt
    steps = -(-T // entries)
    tables = jnp.pad(tables, ((0, 0), (0, steps * entries - T)),
                     constant_values=C)
    keys = pool[jnp.minimum(tables, C - 1)].reshape(B, steps * k_tile, lanes)
    q_tile = min(S, _CHUNK_QUERIES)
    if S % q_tile:
        q_tile = S
    kernel = functools.partial(_chunk_kernel, q_tile=q_tile, k_tile=k_tile,
                               scale=scale, rank=rank)

    def key_block(b, h, i, j, pos):
        # past the tile's last query: the last tile it reads, no new copy
        return b, jnp.minimum(j, (pos[b] + (i + 1) * q_tile - 1) // k_tile), 0

    itemsize = jnp.dtype(pool.dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, S // q_tile, steps),
            in_specs=[
                pl.BlockSpec((None, None, q_tile, lanes),
                             lambda b, h, i, j, pos: (b, h, i, 0)),
                pl.BlockSpec((None, k_tile, lanes), key_block)],
            out_specs=pl.BlockSpec((None, None, q_tile, rank),
                                   lambda b, h, i, j, pos: (b, h, i, 0)),
            scratch_shapes=[pltpu.VMEM((q_tile, rank), jnp.float32),
                            pltpu.VMEM((q_tile, 1), jnp.float32),
                            pltpu.VMEM((q_tile, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, S, rank), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=48 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * S * steps * k_tile * (lanes + rank),
            transcendentals=B * H * S * steps * k_tile,
            bytes_accessed=B * H * (S * (lanes + rank) + S // q_tile * steps
                                    * k_tile * lanes) * itemsize),
        name="paged_latent_chunk",
        interpret=interpret,
    )(positions, jnp.transpose(query, (0, 2, 1, 3)), keys)
