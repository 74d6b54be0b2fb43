"""Continuous-batching decode engine: one compiled step, rotating slots.

The offline path (:func:`distkeras_tpu.inference.generate.generate`)
decodes a *closed* batch: every row starts together, the whole batch runs
``max_new_tokens`` steps, stragglers pad out the scan. An online server
cannot do that — requests arrive whenever they arrive, and draining the
batch to admit one request wastes every other slot's compute.

This engine keeps the shape discipline that makes the offline path fast
(static shapes, ONE compiled decode step for the lifetime of the server)
while making the batch *open*:

- each of the ``slots`` rows of the decode batch is an independent
  request at its **own** sequence position (``BertConfig.decode_slots``
  turns the positional indices into per-row vectors);
- a finished request frees its row; a queued request is admitted between
  decode iterations by a **prefill** program (compiled once per
  power-of-two prompt-length bucket) that writes the prompt's K/V
  straight into the request's blocks of the KV pool — the decode step
  itself never retraces and never stops for admission: the prefill is
  enqueued behind the decode tick in flight and its first token is read
  at the loop's next harvest (step 4 of :meth:`ServingEngine.run`);
- with **chunked prefill** (``prefill_chunk``), the prompt is split into
  fixed-size chunks and ONE chunk runs per engine iteration, interleaved
  with decode ticks (step 4b) — admitting a long prompt never holds the
  decode batch back by more than one chunk's device time, bounding every
  in-flight request's inter-token latency;
- free rows keep decoding garbage (their output is discarded, and their
  writes are dropped) — the cost of a fixed-shape batch, and exactly the
  trade the training side makes with padded microbatches.

**The KV cache is ONE block pool**, shared by the decode slots and the
prefix cache (:class:`~distkeras_tpu.serving.prefix_cache.KVBlockPool`);
there is no other layout. (``generate()`` keeps its dense
``[B, L, H, D]`` cache as the offline path, and a speculative engine's
draft model keeps a small dense per-slot cache of its own.)

- each slot's KV lives in fixed-size blocks (``kv_block_tokens``)
  addressed through a per-slot block table; the compiled decode step
  gathers K/V via traced table indices
  (:func:`distkeras_tpu.ops.attention.paged_attention`), so ONE
  executable serves every table layout and context length;
- **sizing**: ``kv_pool_mb`` (a byte budget) or ``kv_pool_blocks`` sizes
  the pool; with neither, it holds ``slots × ceil(limit /
  kv_block_tokens)`` blocks — every slot can hold a whole context, so
  nothing is ever preempted. A budget makes capacity scale with
  *resident tokens*, not ``slots × max_seq_len``: more concurrent slots
  per byte, and requests may use the model's whole trained context
  because blocks are chained on demand, never pre-reserved;
- **prefix sharing is inherent**: a prompt's longest cached block-chain
  prefix is a **zero-copy** hit (the table points at the shared
  ref-counted blocks, only the uncached tail is prefilled) and a
  finished slot's blocks are **adopted** into the trie in place
  (zero-copy insert);
- a budgeted pool may be **oversubscribed**: when it runs dry, the engine
  preempts the lowest-priority youngest slot — its complete blocks are
  adopted (so re-admission re-matches them), the rest freed, and the
  request requeued at the front of its priority class
  (``Scheduler.requeue``); already-streamed tokens are folded into the
  resume prefill, so greedy output stays token-identical across the
  round trip. Requests whose full context can never fit are rejected
  with the typed ``kv_oom`` error at submit.

**Speculative decoding** (``draft_model``/``spec_k``) breaks the
one-full-model-dispatch-per-token latency floor: a small draft model
proposes K tokens per tick in ONE scanned dispatch, ONE batched target
call scores all K window positions per slot, and a masked accept
commits the longest verify-consistent DRAFT prefix — up to K tokens per
greedy row per tick, while ``temperature > 0`` rows ride the same tick
committing one sampled token from the verify's position-0 logits.
Committed values are always draft tokens (one-token-apply-computed,
the same lowering shape as ``generate()``'s scan), never tokens read
off the wide window's logits — the property that makes speculation
token-identical to ``generate()`` instead of almost-identical (see
``_spec_accept``). A row that accepts zero drafts is owed a one-token
fallback tick, so progress is unconditional. Everything is
shape-stable in K: rejected drafts roll back by simply not advancing
the host ``_lens`` watermark (the OOB-drop scatter already guarantees
an unallocated overhang cannot scribble) — so draft,
verify, and the one-token fallback each stay at exactly one compiled
executable under the armed ``RecompileAuditor``, variable acceptance
lengths and all.

Per-request sampling: ``temperature <= 0`` rows take the argmax branch
inside the same compiled step (a ``jnp.where`` select, not a retrace), so
greedy and sampled requests coexist in one batch. ``top_k`` is
engine-wide static config.

**Sharded serving** (``mesh``): hand the engine a
:func:`distkeras_tpu.parallel.mesh.serving_mesh` and ONE replica runs
the model GSPMD-sharded over the mesh's ``tp`` axis — models bigger
than one chip, served by the same engine:

- params are laid out per their logical-axis annotations
  (:func:`distkeras_tpu.parallel.sharding.infer_variable_shardings`) and
  **placed shard-then-place** (:func:`...gspmd.place_sharded`): each
  device receives only its slice, at boot and at every hot swap — the
  arXiv:2004.13336 move applied to weight rollout;
- the KV bytes — the block pools — shard over the **heads** dimension
  (:func:`...sharding.kv_pytree_shardings`), while block tables, slot
  state, the scheduler, and every index stay replicated host metadata
  (the pool's *meaning* is host-side bookkeeping);
- every compiled callable — prefill, decode, draft, verify, fallback —
  is jitted with **explicit ``in_shardings``/``out_shardings``**, so
  layouts are pinned facts of each executable (stable across calls =
  still exactly ONE executable per callable under the armed auditor)
  rather than per-call propagation guesses;
- greedy output stays **token-identical** to the unsharded engine: the
  only tensor-parallel cross-device reductions (attention out-proj,
  mlp_out) keep float32 partial sums until after the all-reduce
  (``models.bert._F32AccumDense``), so layout noise stays far below the
  bf16 resolution ``greedy_ids`` quantizes to.

The draft model of a speculative engine stays **replicated** — it is
small by definition, and replicating it trades a little memory for zero
collectives in the latency-critical draft scan.

**Overlapped decode pipeline** (``pipeline_depth=1``, the default): the
run loop dispatches tick N+1 *before* consuming tick N's tokens. JAX
dispatch is asynchronous — the jit call returns as soon as the work is
enqueued — so the only point the host must wait for the device is the
one D2H per tick (``np.asarray`` on the tick's token vector, the
**harvest**). Serializing harvest right after dispatch (the old
``_decode_sync``) made the accelerator idle through the FULL host gap
between ticks: token streaming, slot teardown, admission bookkeeping,
scheduler/metrics work, and the event-loop turn that reads sockets.
Pipelined, all of that runs while the device executes the next tick:

- ``self._tokens`` stays a device array end to end and is **double
  buffered** — the decode step no longer donates its token operand, so
  dispatching tick N+1 never invalidates the buffer tick N's harvest
  is still going to read (16 bytes per tick of extra alloc, nothing);
- a **pipeline barrier** (harvest + stream + teardown of the in-flight
  tick) runs only at the events that need HARVESTED state: an
  admission or paged growth from a DRY pool (it preempts or parks, or
  evicts with a spill to the host tier), an admission that re-admits
  blocks from the host tier, the admission and the chunks of a request
  whose last chunk is read back on the host at once (``sample``,
  constrained, ``score``, ``embed``), every admission of an engine
  with a draft model or pipeline stages, param swap (a swap still
  waits for zero in-flight ticks), KV transfer, cancel/expire
  teardown, and engine idle/exit;
- **an admission is a dispatch** (PR 33), not such an event, whenever
  the pool serves its reservation from the free list (or by evicting
  an unreferenced trie leaf with no host tier): programs run on the
  device in dispatch order; the prefill writes only blocks the
  reservation just gave this slot, which no live row's table names;
  the tick in flight holds its own device copy of the masked tables,
  in which the slot is the sentinel; the admit epilogue does not
  donate the token vector (the tick's harvest handle); and the next
  dispatch re-uploads tables and positions. The first token stays a
  device scalar until the next harvest reads it beside the tick's
  (:meth:`ServingEngine._resolve_admissions`); a row it finishes is
  torn down there like any row that finishes with its next tick
  already in flight. Both routes enter ``serving_prefill`` and
  ``serving_admit`` through the same call with the same arguments, so
  a warm-up through an idle engine warms the burst that follows;
- paged growth the pool can serve the same way is NOT such an event
  either (PR 30): the block is chained into the host table with the
  tick in flight, and the next dispatch re-uploads (step 5c);
- a slot that FINISHES at tick N is detected at N's harvest — after
  N+1 was dispatched, so the in-flight tick ran one speculative row
  for it. Its N+1 output is dropped exactly like a mid-prefill
  garbage row (a tick remembers WHICH request each row was dispatched
  for: an admission may have refilled the slot by then), and the host
  watermark advance the dispatch made for it is rolled back before
  teardown adopts its blocks, so pool accounting never claims the
  speculative in-flight write;
- speculative ticks dispatch asynchronously too, but the NEXT dispatch
  needs their commit counts (host-side position bookkeeping), so a
  spec tick is harvested before anything else is dispatched — spec
  mode hides the inter-iteration host gap (steps 1–4 + socket reads),
  while plain decode gets the full depth-1 overlap;
- greedy output is **token-identical** to ``pipeline_depth=0`` in
  every mode: the same ticks run in the same order over the same
  state, only the host's read of each tick's result is deferred.

Per-tick ``serving_host_gap_seconds`` / ``serving_device_idle_ratio``
(:class:`~distkeras_tpu.serving.metrics.HostGapTracker`) measure what
the pipeline hides, and :meth:`ServingEngine.tick_timeline` keeps a
bounded dispatch→harvest lane for tracez/debugz. What it does NOT hide
is counted: every barrier that found a tick in flight adds to
``serving_pipeline_barriers_total{reason}`` and is a ``barrier`` span
(on the device's clock whenever a profiler session is live, see
:mod:`distkeras_tpu.telemetry.spans`). An engine with many rows
has some row crossing a block boundary on almost every tick; until
PR 30 each such tick drained, and the overlap was mostly lost to
``paged_growth``. ``serving_paged_growth_blocks_total{drained}`` now
says how many blocks were chained with the tick left in flight
(``0``) against behind the barrier (``1``: a dry or spilling pool),
and ``serving_admissions_total{drained}`` the same of admissions (the
``admit`` and ``prefill_tick`` spans carry ``drained`` too).
"""

from __future__ import annotations

import asyncio
import collections
import contextvars
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu.inference.generate import (
    _check_context,
    _context_limit,
    _decode_module,
    _empty_cache,
    accept_prefix_length,
    cache_with_index,
    greedy_ids,
    sample_rows,
)
from distkeras_tpu.serving.metrics import ServingMetrics
from distkeras_tpu.serving.prefix_cache import KVBlockPool
from distkeras_tpu.telemetry import (
    FlightRecorder,
    RecompileAuditor,
    TimelineRecord,
    TraceStore,
    WideEventStore,
    named_program,
    span,
)
from distkeras_tpu.serving.constraints import TokenDFA
from distkeras_tpu.serving.scheduler import (
    REQUEST_KINDS,
    SCORELIKE_KINDS,
    EngineStopped,
    PoolExhausted,
    Request,
    RequestCancelled,
    RequestTimeout,
    Scheduler,
    ServingError,
)

__all__ = ["ServingEngine"]


def _paged_prefill_fn(module, top_k, params, pools, padded, start, true_len,
                      table_row, temp, key):
    """Run a right-padded ``[1, P]`` prompt *chunk* through the decode
    module at offset ``start`` and sample the token that follows it. The
    chunk's K/V writes straight into the shared block pool through the
    slot's block table (no scratch cache, no splice afterwards —
    admission IS the table row). ``start``/``true_len``/``table_row``
    are traced, so ONE program serves every offset, true length, and
    block layout of a given pad width: monolithic prefill is the
    ``start == 0, P == bucket(prompt)`` case, a chunk of a longer prompt
    (or the uncached tail after a prefix hit) the same program at a
    non-zero start. Padding is benign: causal attention means real
    positions never see the pad tail, the sampled token comes from the
    logits at ``true_len - 1``, right-padded garbage past the table's
    allocated blocks is dropped by the scatter, and garbage inside the
    tail block is masked (``k_pos <= q_pos``) until real tokens
    overwrite it."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, padded, train=False,
        mutable=["cache"],
        positions=jnp.full((1,), start, jnp.int32),
        block_tables=table_row[None],
    )
    last = jnp.take(logits[0], true_len - 1, axis=0)[None]  # [1, V]
    tok = sample_rows(last, temp[None], key, top_k)[0]
    return mut["cache"], tok


def _paged_admit_fn(tokens, temps, slot, tok, temp):
    """Admission epilogue: only the sampling state changes — the KV is
    already resident in the pool, so there is nothing to splice.
    ``slot`` is a traced scalar, so one program serves every slot."""
    return tokens.at[slot].set(tok), temps.at[slot].set(temp)


def _paged_decode_fn(module, top_k, sentinel, params, pools, tokens, temps,
                     positions, tables, key):
    """ONE decode iteration for the whole slot batch ``[B] -> [B]``: K/V
    appends scatter into the pool at each row's (traced) position and
    attention gathers through the (traced) block tables — one compiled
    executable for every table layout, admission pattern, and context
    length, which is what keeps the armed ``RecompileAuditor`` silent
    while blocks chain, slots are preempted, and long contexts grow.

    Positions advance DEVICE-SIDE: each row that is live in the masked
    table view (first table entry not the sentinel — exactly the rows
    whose write lands) comes back at ``position + 1``, so steady-state
    ticks re-feed the returned vector instead of rebuilding and
    re-uploading a host array every tick. The host re-uploads from its
    ``_lens`` truth only when the dirty flag says the decodable set or
    a watermark changed — the same gating the block tables use.

    A module that counts what its tick did (``module.tick_counters``
    names the int32 vector it sows into the ``counters`` collection:
    routed pairs, experts touched, window positions read) gets a fourth
    output: the counts appended to the token vector as ONE array, the
    tick's harvest handle, so they ride on the device-to-host copy the
    tick already makes while the tokens alone stay on the device as the
    next tick's input. For every other module the program is the one
    above, output for output."""
    counted = bool(getattr(module, "tick_counters", ()))
    logits, mut = module.apply(
        {"params": params, "cache": pools}, tokens[:, None], train=False,
        mutable=["cache", "counters"] if counted else ["cache"],
        positions=positions, block_tables=tables,
    )
    nxt = sample_rows(logits[:, -1], temps, key, top_k)
    live = (tables[:, 0] != sentinel).astype(positions.dtype)
    out = (mut["cache"], nxt, positions + live)
    if counted:
        out += (jnp.concatenate([nxt, mut["counters"]["tick"][0]]),)
    return out


def _paged_decode_masked_fn(module, top_k, sentinel, params, pools, tokens,
                            temps, positions, tables, mask, key):
    """Constrained twin of :func:`_paged_decode_fn`: a per-slot additive
    token mask ``[slots, V]`` (0 allowed, large-negative forbidden —
    :class:`TokenDFA.mask_row`) lands on the last-position logits BEFORE
    sampling, so a masked greedy row can only emit automaton-legal
    tokens. Unconstrained rows carry an all-zero mask row — the add is
    a no-op for them, which is what lets ONE executable serve mixed
    constrained/unconstrained batches (the compile-count==1 invariant
    is the same as the unmasked step's: the mask is a plain operand,
    re-uploaded host-side only under a dirty flag)."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, tokens[:, None], train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
    )
    nxt = sample_rows(logits[:, -1] + mask, temps, key, top_k)
    live = (tables[:, 0] != sentinel).astype(positions.dtype)
    return mut["cache"], nxt, positions + live


def _paged_prefill_logits_fn(module, params, pools, padded, start, true_len,
                             table_row):
    """Final-chunk prefill that returns the LOGITS row instead of a
    sampled token: the fork fan-out samples n tokens from it
    (:func:`_fork_sample_fn`) and constrained admission masks it
    host-side before picking the first token. KV writes are identical
    to :func:`_paged_prefill_fn` — only the sampling epilogue moved to
    the caller."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, padded, train=False,
        mutable=["cache"],
        positions=jnp.full((1,), start, jnp.int32),
        block_tables=table_row[None],
    )
    last = jnp.take(logits[0], true_len - 1, axis=0)  # [V]
    return mut["cache"], last.astype(jnp.float32)


def _fork_sample_fn(top_k, logits, temps, key):
    """Sample ``n`` independent continuations from ONE prefill logits
    row (the n>1 fork fan-out): the row is broadcast to ``[n, V]`` and
    :func:`sample_rows` draws each fork's first token — categorical
    over a batch samples independently per row under a single key, so
    one dispatch seeds all n forks. Compiles once per distinct n
    (report-only audit, like the pow2 prefill buckets)."""
    n = temps.shape[0]
    rows = jnp.broadcast_to(logits[None, :], (n, logits.shape[0]))
    return sample_rows(rows, temps, key, top_k)


def _score_chunk_fn(module, params, pools, padded, start, true_len,
                    table_row, targets):
    """Scoring prefill chunk: same paged KV writes as
    :func:`_paged_prefill_fn`, but instead of sampling, return each
    chunk position's log-probability of its NEXT prompt token —
    ``picked[j] = log_softmax(logits[j])[targets[j]]`` where
    ``targets[j]`` is the prompt token at global position
    ``start + j + 1``. The host accumulates per chunk and drops the
    pad tail and the final position (nothing follows it)."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, padded, train=False,
        mutable=["cache"],
        positions=jnp.full((1,), start, jnp.int32),
        block_tables=table_row[None],
    )
    logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]
    return mut["cache"], picked


def _embed_chunk_fn(module, params, pools, padded, start, true_len,
                    table_row):
    """Embedding prefill chunk: the trunk's raw hidden states
    (``return_hidden=True`` — pre-head, no extra params) summed over
    the chunk's TRUE positions (the right-pad tail is masked out). The
    host accumulates chunk sums and divides by the prompt length at
    completion — mean pooling without ever materializing ``[P, H]``
    host-side."""
    hidden, mut = module.apply(
        {"params": params, "cache": pools}, padded, train=False,
        mutable=["cache"], return_hidden=True,
        positions=jnp.full((1,), start, jnp.int32),
        block_tables=table_row[None],
    )
    valid = (jnp.arange(hidden.shape[1]) < true_len)[:, None]
    summed = jnp.sum(hidden[0].astype(jnp.float32) * valid, axis=0)
    return mut["cache"], summed


def _kv_gather_fn(cache, ids):
    """Gather pool rows ``ids`` from every KV leaf — the device half of
    a KV block EXPORT (:mod:`distkeras_tpu.serving.kv_transfer`).
    ``ids`` is pow2-padded so compiles stay bounded; the padding rows'
    garbage is sliced off host-side."""
    return jax.tree.map(lambda p: p[ids] if p.ndim > 1 else p[:0], cache)


def _as_pool_rows(rows, leaf):
    """Host-side block rows ``[n, bt, ...]`` of a KVX1 payload in the
    pool leaf's own trailing shape. A payload spells a token's elements
    as its writer stored them — ``[H, D]`` from a peer or a disk tier
    older than the flat leaf, ``[H*D]`` since — over the same bytes in
    the same order."""
    return rows.reshape((rows.shape[0],) + tuple(leaf.shape[1:]))


def _kv_scatter_fn(cache, data, ids):
    """Scatter imported block rows ``data`` into the pool at rows
    ``ids`` — the device half of a KV block IMPORT. ``ids`` pads its
    pow2 bucket with out-of-range ids; ``mode="drop"`` discards those
    writes (the same OOB discipline as the pool's store path). Donates
    the pool."""
    return jax.tree.map(
        lambda p, d: (p.at[ids].set(d.astype(p.dtype), mode="drop")
                      if p.ndim > 1 else p),
        cache, data)


def _spec_draft_fn(module, K, params, cache, prev, tokens, start):
    """Fixed-K greedy draft scan: ONE dispatch proposes K tokens per row.

    ``start`` is the per-row fed-token count (int32 ``[B]``, the target's
    truth); setting the index leaves on entry is the draft-cache
    rollback — rejected draft K/V from the previous tick is simply
    overwritten as the new chain is fed, so no separate rewind pass
    exists. K is static (one compiled program per engine); the scan
    keeps the whole proposal at one device dispatch.

    The pass begins one position EARLY: a heal apply re-feeds ``prev``
    (the token at position ``start - 1``) before the K-step scan feeds
    ``tokens`` onward. Normally that rewrites K/V the draft already
    holds with the same values — but when the previous tick was a
    one-token FALLBACK (zero-accept row or speculate=False row in the
    batch), the target advanced past a position the draft never fed,
    and without the heal that hole would sit behind every later scan's
    attention forever, silently degrading the accept rate (measured:
    1.0 → ~0.79 in mixed traffic with draft == target). The run loop
    interleaves at most one fallback tick between spec ticks while an
    eligible row exists, so one healed position is always enough.

    Every step is a one-token apply — the SAME lowering shape as the
    offline ``generate()`` scan and the engine's fallback decode step.
    That is a correctness property, not an implementation detail: the
    engine commits DRAFT tokens (never tokens read off the wide verify
    window's logits), so with draft == target every committed token is
    bit-for-bit the sequential chain. Different-width lowerings of a
    bfloat16 trunk reorder its internal roundings and can flip argmax
    on near-ties; keeping all committed values one-token-shaped is what
    makes speculative output token-identical to ``generate()`` instead
    of merely almost-always-identical."""
    cache = cache_with_index(cache, jnp.maximum(start - 1, 0))
    _, mut = module.apply(
        {"params": params, "cache": cache}, prev[:, None], train=False,
        mutable=["cache"],
    )
    # The heal apply advanced the index leaves back to ``start`` (for
    # start == 0 rows the clamp makes it 1 — free/garbage rows only,
    # whose cache is rebuilt at admission).
    cache = cache_with_index(mut["cache"], start)

    def step(carry, _):
        cache, tok = carry
        logits, mut = module.apply(
            {"params": params, "cache": cache}, tok[:, None], train=False,
            mutable=["cache"],
        )
        nxt = greedy_ids(logits[:, -1].astype(jnp.float32))
        return (mut["cache"], nxt), nxt

    (cache, _), drafts = lax.scan(step, (cache, tokens), None, length=K)
    return cache, drafts.T  # [B, K]: d_1..d_K


def _spec_accept(logits, drafts, tokens, temps, spec_ok, remaining, key,
                 top_k):
    """Accept epilogue of the verify programs: from the target's
    ``[B, K, V]`` logits over the window ``[last_tok, d_1..d_{K-1}]``,
    decide how many DRAFT tokens each row commits.

    Two deliberate choices make this token-identical to ``generate()``
    instead of almost-identical:

    1. **Committed values are always the drafts themselves** — never
       tokens read off the window's logits. Drafts come from one-token
       applies (the same lowering shape as the sequential chain), while
       a K-wide window reorders the trunk's bfloat16 roundings: its
       argmax can flip on near-ties, so committing a window-derived
       "bonus" token (the textbook formulation) measurably diverged
       ~1/10^3 tokens on the random-init CI models.
    2. **The gate is ε-greedy at the model's compute precision**: draft
       ``d_{j+1}`` is accepted while its verify logit sits within ~2
       bfloat16 ULPs of the window's max — NOT on exact argmax
       equality, which the same cross-width noise spuriously breaks at
       ties (and a spurious reject routes the token through a fallback
       read of wide-written K/V, a coin toss at a tie site). Within the
       ε band the candidates are numerically indistinguishable at the
       precision the model itself computes in, so accepting the draft's
       choice IS greedy decoding. With draft == target this makes the
       committed chain bitwise the sequential chain, spurious-reject
       free; a genuinely wrong draft sits far below the band and is
       rejected as before. The relaxation's one caveat: a DIFFERENT
       draft that proposes the runner-up of an ε-tied pair commits it
       where sequential decode would pick the other member — output can
       then differ from ``generate()`` exactly at (and only at)
       positions the target itself scores as ties at its compute
       precision.

    A row that accepts zero drafts commits nothing this tick; the
    engine interleaves a one-token fallback tick so it always
    progresses. ``temperature > 0`` rows ride the same tick committing
    exactly one token sampled (shared :func:`sample_rows`) from offset
    0's logits — the distribution after ``last_tok``, i.e. what a plain
    decode tick sampled from. Greedy rows that OPTED OUT of speculation
    commit 0 here and are served by the fallback ticks their presence
    forces — their strict-parity promise must not route through wide
    logits. ``remaining`` clamps every row so a near-done request never
    overshoots ``max_new_tokens``.

    Returns ``(out, commit)``: ``out[b, :commit[b]]`` are row ``b``'s
    committed stream tokens."""
    logits = logits.astype(jnp.float32)
    tok0 = sample_rows(logits[:, 0], temps, key, top_k)
    eligible = spec_ok & (temps <= 0)
    top = jnp.max(logits, axis=-1)  # [B, K]
    drafted = jnp.take_along_axis(
        logits, drafts[..., None], axis=-1)[..., 0]  # [B, K]
    # ~2 bf16 ULPs of the max (floored for near-zero logits): far above
    # cross-width reduction noise (~1e-4 here), far below any decided
    # argmax gap.
    eps = jnp.float32(2**-7) * jnp.maximum(jnp.abs(top), 1.0)
    accepted = accept_prefix_length(drafted >= top - eps)
    commit = jnp.where(
        eligible, jnp.minimum(accepted, remaining),
        jnp.where(temps > 0, jnp.minimum(1, remaining), 0))
    out = jnp.concatenate(
        [jnp.where(eligible, drafts[:, 0], tok0)[:, None], drafts[:, 1:]],
        axis=1)
    return out, commit


def _paged_spec_verify_fn(module, top_k, params, pools, tokens, drafts,
                          temps, spec_ok, remaining, room, positions,
                          tables, key):
    """Speculative verify: ONE target-model call scores all K window
    positions (``[last_tok, d_1..d_{K-1}]``) per slot and a masked
    accept commits the longest verify-consistent draft prefix.
    Everything is shape-stable in K, so variable acceptance lengths
    never retrace. The window's K/V scatters through the block tables
    (writes past a row's allocated blocks are dropped by
    ``paged_kv_update``), so ``room`` — the contiguous
    allocated positions from each row's write offset, computed host-side
    — additionally clamps the commit: a row whose lookahead blocks could
    not be allocated under pool pressure commits fewer tokens instead of
    committing tokens whose K/V was dropped. Rollback is the caller NOT
    advancing ``_lens`` past the commit; no device state to rewind.

    Returns ``(pools, new_tokens, out, commit)``: ``out[b, :commit[b]]``
    are row ``b``'s committed stream tokens and ``new_tokens[b]`` its
    next feed token (the last committed one; unchanged on a zero
    commit, so re-running the tick is idempotent)."""
    window = jnp.concatenate([tokens[:, None], drafts[:, :-1]], axis=1)
    logits, mut = module.apply(
        {"params": params, "cache": pools}, window, train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
    )
    out, commit = _spec_accept(logits, drafts, tokens, temps, spec_ok,
                               remaining, key, top_k)
    commit = jnp.minimum(commit, room)
    new_tok = jnp.where(
        commit > 0,
        jnp.take_along_axis(
            out, jnp.maximum(commit - 1, 0)[:, None], axis=1)[:, 0],
        tokens)
    return mut["cache"], new_tok, out, commit


def _draft_prefill_fn(module, params, cache, padded, start, true_len):
    """The draft's prefill: extend its dense single-row cache with a
    right-padded prompt chunk at offset ``start`` (the index leaves are
    set to ``start`` on entry) and rewind them from ``start + P`` to the
    true end. The draft never samples — its proposals come from the
    decode-time scan — so prefill only has to materialize the prompt's
    K/V."""
    cache = cache_with_index(cache, start)
    _, mut = module.apply(
        {"params": params, "cache": cache}, padded, train=False,
        mutable=["cache"],
    )
    return cache_with_index(mut["cache"], start + true_len)


def _draft_admit_fn(cache, slot, pre_cache):
    """Splice a prefilled single-row draft cache into batch row ``slot``
    (a traced scalar: one program serves every slot; every cache leaf
    carries the batch dim first, so the splice is a uniform
    leading-axis ``dynamic_update_slice``)."""
    return jax.tree.map(
        lambda big, small: lax.dynamic_update_slice(
            big, small.astype(big.dtype), (slot,) + (0,) * (small.ndim - 1)
        ),
        cache, pre_cache,
    )


# -- pipeline-parallel stage programs ---------------------------------------
# Per-stage twins of the monolithic programs above, for a ``tp=N,pp=M``
# serving mesh: each stage's jit sees ONLY its own placed param/cache
# subtree (parallel/pp.StagePlan) and compiles against its own tp-only
# sub-mesh. A non-last stage returns the traced activation the next
# stage's jit consumes — jax transfers it between the stage device sets
# at dispatch, and because the activation is ALWAYS committed to the
# producing stage's layout the consuming jit keys one cache entry (jit
# entries key on actual argument placement, so source-consistency is
# what keeps compile-count==1 per stage). ``stage`` is the static
# ``(lo, hi, first, last)`` slice Bert.__call__ takes.

def _pp_paged_prefill_fn(module, stage, params, pools, x, start, table_row):
    """Non-last-stage slice of :func:`_paged_prefill_fn` — this stage's
    layer K/V scatters into ITS pool shard through the (replicated-
    per-stage) table row."""
    act, mut = module.apply(
        {"params": params, "cache": pools}, x, train=False,
        mutable=["cache"],
        positions=jnp.full((1,), start, jnp.int32),
        block_tables=table_row[None], stage=stage,
    )
    return mut["cache"], act


def _pp_paged_prefill_last_fn(module, stage, top_k, params, pools, act,
                              start, true_len, table_row, temp, key):
    """Last-stage slice of :func:`_paged_prefill_fn`."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, act, train=False,
        mutable=["cache"],
        positions=jnp.full((1,), start, jnp.int32),
        block_tables=table_row[None], stage=stage,
    )
    last = jnp.take(logits[0], true_len - 1, axis=0)[None]  # [1, V]
    tok = sample_rows(last, temp[None], key, top_k)[0]
    return mut["cache"], tok


def _pp_paged_decode_fn(module, stage, sentinel, params, pools, x, positions,
                        tables):
    """Non-last-stage slice of :func:`_paged_decode_fn` (``x`` is
    ``tokens[:, None]`` on stage 0, the previous activation after).
    Every stage returns its own ``positions + live`` vector (the advance
    rule is pure table arithmetic, identical across stages), so each
    stage's steady-state tick re-feeds its OWN returned vector and no
    positions ever cross stages."""
    act, mut = module.apply(
        {"params": params, "cache": pools}, x, train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
        stage=stage,
    )
    live = (tables[:, 0] != sentinel).astype(positions.dtype)
    return mut["cache"], act, positions + live


def _pp_paged_decode_last_fn(module, stage, top_k, sentinel, params, pools,
                             act, temps, positions, tables, key):
    """Last-stage slice of :func:`_paged_decode_fn`."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, act, train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
        stage=stage,
    )
    nxt = sample_rows(logits[:, -1], temps, key, top_k)
    live = (tables[:, 0] != sentinel).astype(positions.dtype)
    return mut["cache"], nxt, positions + live


def _pp_paged_verify_first_fn(module, stage, params, pools, tokens, drafts,
                              positions, tables):
    """Stage-0 slice of :func:`_paged_spec_verify_fn` (no index leaves —
    rollback is the host not advancing ``_lens``)."""
    window = jnp.concatenate([tokens[:, None], drafts[:, :-1]], axis=1)
    act, mut = module.apply(
        {"params": params, "cache": pools}, window, train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
        stage=stage,
    )
    return mut["cache"], act


def _pp_paged_verify_fn(module, stage, params, pools, act, positions,
                        tables):
    """Middle-stage slice of :func:`_paged_spec_verify_fn`."""
    act, mut = module.apply(
        {"params": params, "cache": pools}, act, train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
        stage=stage,
    )
    return mut["cache"], act


def _pp_paged_verify_last_fn(module, stage, top_k, params, pools, act,
                             drafts, tokens, temps, spec_ok, remaining,
                             room, positions, tables, key):
    """Last-stage slice of :func:`_paged_spec_verify_fn`."""
    logits, mut = module.apply(
        {"params": params, "cache": pools}, act, train=False,
        mutable=["cache"], positions=positions, block_tables=tables,
        stage=stage,
    )
    out, commit = _spec_accept(logits, drafts, tokens, temps, spec_ok,
                               remaining, key, top_k)
    commit = jnp.minimum(commit, room)
    new_tok = jnp.where(
        commit > 0,
        jnp.take_along_axis(
            out, jnp.maximum(commit - 1, 0)[:, None], axis=1)[:, 0],
        tokens)
    return mut["cache"], new_tok, out, commit


@dataclasses.dataclass
class _PrefillJob:
    """Partial-prefill progress for a slot still being admitted: how far
    into the prompt its pool blocks are written (the matched prefix
    included)."""

    pos: int                      # prompt tokens already in the pool
    matched_tokens: int
    chunks_done: int = 0
    device_s: float = 0.0         # prefill device time (TTFT's other half)


def _is_ready(x) -> bool:
    """True when the device buffer has already materialized: reading it
    is then a plain memcpy, cheaper run inline than through an executor
    round trip. Conservative on jax versions without ``Array.is_ready``
    (False → thread hop)."""
    try:
        return bool(x.is_ready())
    except AttributeError:
        return False


def _tick_ready(tick) -> bool:
    """:func:`_is_ready` of every buffer the tick's harvest will read."""
    if tick.kind == "spec":
        return _is_ready(tick.out) and _is_ready(tick.commit)
    return _is_ready(tick.tokens)


@dataclasses.dataclass
class _InflightTick:
    """A dispatched-but-unharvested decode tick: the device handles the
    harvest will read, the decodable rows the dispatch covered (the
    stream targets — the slot table may gain or lose entries before the
    harvest, and a row must stream iff it was decodable AT DISPATCH and
    its slot still holds the same request: ``states`` says which that
    was, since an admission enqueued with the tick in flight can refill
    a freed slot before the harvest), and — plain ticks — the slots whose
    host ``_lens`` watermark the dispatch optimistically advanced, so a
    teardown detected mid-flight can roll the advance back before
    adopting blocks. With ``pipeline_depth>1`` on a pp mesh each tick
    covers ONE slot micro-batch; ``mb``/``mb_start`` map its mb-local
    token vector back to global slot ids at stream time."""

    kind: str                     # "decode" | "spec"
    rows: tuple                   # decodable slots at dispatch
    states: tuple                 # their _SlotState, row for row
    t_dispatch: float
    tokens: object = None         # plain: device token vector to harvest
    out: object = None            # spec: device [B, K] committed tokens
    commit: object = None         # spec: device per-row commit counts
    caps: object = None           # spec: host per-row draft budgets
    advanced: set = dataclasses.field(default_factory=set)
    mb: int = 0                   # micro-batch index (pp depth>1)
    mb_start: int = 0             # first global slot id of the micro-batch


def _public_provenance(provenance: dict | None) -> dict:
    """The client-facing face of a weights stamp: version + digest
    ONLY. checkpoint.weights_provenance also carries the server-side
    file ``path`` (and trainer stamps arbitrary meta) — stamping that
    into every done line and trace would disclose the server's
    filesystem layout to remote clients."""
    if not provenance:
        return {"version": 0, "digest": None}
    return {"version": int(provenance.get("version") or 0),
            "digest": provenance.get("digest")}


@dataclasses.dataclass
class _SlotState:
    request: Request
    remaining: int  # tokens still to decode after the prefill token
    last_token_t: float
    # Non-None while the slot's prompt is still prefilling (chunked
    # admission): the row sits in the decode batch but its garbage output
    # is discarded until the prompt's last chunk has run.
    prefill: _PrefillJob | None = None
    # When this slot was admitted (preemption prefers the
    # YOUNGEST victim — least sunk work thrown away), the private block
    # ids it owns (block indices first_block, first_block+1, ... of its
    # table), and the pinned shared-prefix match its table head points
    # at (released only at slot teardown — the pin is what stops
    # eviction from reallocating a block the decode step still reads).
    t_admit: float = 0.0
    # Which route admitted the slot: False when its reservation needed
    # nothing a harvest settles, so its prefill chunks go out beside the
    # tick in flight; True when they run behind a pipeline barrier (the
    # ``drained`` label of ``serving_admissions_total``).
    drained: bool = True
    blocks: list = dataclasses.field(default_factory=list)
    first_block: int = 0
    match: object | None = None
    # Speculative decoding: lifetime draft/accept counters for this
    # slot's request (the debugz accept-rate column and per-request
    # trace stamps).
    spec_drafted: int = 0
    spec_accepted: int = 0
    # Request-kind state. Fork rows (kind="sample"): which fork of the
    # shared request this slot is (None for every other kind), its
    # PRIVATE token stream (fork tokens are never streamed as events —
    # the DONE frame carries all n completions), and fork_wait marks a
    # child slot claimed at admission but not yet fanned out (excluded
    # from the decodable set until the parent prefill completes).
    fork_idx: int | None = None
    fork_tokens: list | None = None
    fork_wait: bool = False
    # Constrained decoding: the request's automaton and its current
    # state (advanced host-side per streamed token).
    dfa: object | None = None
    dfa_state: int = 0
    # Scoring/embedding accumulators (prefill-only kinds).
    score_acc: list | None = None
    embed_acc: object | None = None


# Sentinel returned by _prefill_step when a prefill-only (score/embed)
# request's prompt completed — the run loop routes it to
# _finish_scorelike instead of _finish_admission.
_SCORELIKE_DONE = object()


# Returned by _reserve_blocks(in_flight=True) when the reservation needs
# what only a drained pipeline gives (a victim, a spill, a host-tier
# re-admission): nothing was touched, the caller drains and asks again.
_NEEDS_BARRIER = object()


@dataclasses.dataclass
class _FirstTokenPending:
    """Returned by _prefill_step(defer=True) when the prompt completed:
    the first token is still a device scalar, sampled by a prefill that
    was enqueued behind the tick in flight. The loop reads it at its
    next harvest (:meth:`ServingEngine._resolve_admissions`) and only
    then streams it and books the prefill's time."""
    st: "_SlotState"
    slot: int
    tok: object                   # device int32 scalar
    job: _PrefillJob
    prompt_len: int
    t_enqueued: float             # when the last chunk's dispatch returned


@dataclasses.dataclass
class _ForkReady:
    """Returned by _prefill_step when a fork parent's prompt completed:
    the n first tokens (one per fork) sampled from the final chunk's
    logits; the run loop fans the children out from here."""
    tokens: list


class ServingEngine:
    """Fixed-slot continuous-batching server core.

    ``model``/``variables``: a causal LM from the zoo (gpt_tiny/gpt_small)
    and its trained variables — the same pair :func:`generate` takes.
    ``slots``: decode batch width (concurrent in-flight requests).
    ``max_queue``: admission backpressure depth (:class:`QueueFullError`
    beyond it). ``top_k``: engine-wide top-k sampling (None = full vocab).

    ``prefill_chunk``: split each prompt's (uncached) prefill into chunks
    of this many tokens, ONE chunk per engine iteration (round-robin
    across concurrently admitting slots) interleaved with decode ticks —
    bounds the decode stall (and thus every in-flight request's p99
    inter-token latency) by a single chunk's device time instead of a
    whole prompt's, regardless of how many prompts are admitting. None
    (default) keeps monolithic admission. Greedy output is
    token-identical either way.

    The KV cache is ONE block pool
    (:class:`~distkeras_tpu.serving.prefix_cache.KVBlockPool`): slots
    allocate fixed-size blocks (``kv_block_tokens`` tokens) from it and
    the same blocks are the prefix cache — see the module docstring for
    what that buys (zero-copy prefix sharing, capacity ∝ resident
    tokens, preempt-and-requeue oversubscription, long-context
    admission). ``kv_pool_mb`` sizes it by a byte budget,
    ``kv_pool_blocks`` by a block count (exact capacity control, test
    fixtures); with neither it holds ``slots × ceil(limit /
    kv_block_tokens)`` blocks, a whole context for every slot, so the
    pool never runs dry. Prompts sharing a cached prefix (system
    prompts, few-shot templates) skip its prefill, and the scheduler
    prefers cache-hitting requests within a priority class. The pool is
    NOT thread-safe — it is driven by this engine's loop alone.

    ``max_context``: cap each request's context (prompt + new tokens)
    below the model's trained length; it also bounds each slot's block
    table, and so the pool derived when no budget is given.

    ``draft_model``/``draft_variables``/``spec_k``: speculative decoding
    (see the module docstring). The draft must share the target's vocab
    (proposals are target token ids) and keeps its own dense per-slot
    cache beside the target's pool; the zoo pairs gpt_tiny (draft)
    with gpt_small (target). K trades draft work against acceptance:
    each tick costs one scanned draft dispatch (a heal apply + K
    proposal steps) + one K-wide verify and commits up to K tokens per
    greedy row. A request can opt out per-call
    (``submit(..., speculate=False)``); ``temperature > 0`` rows never
    speculate. Rolling weight reloads swap the TARGET's params only —
    the draft is engine-lifetime config, and a stale draft can only
    lower the accept rate, never change committed output.

    ``mesh``: a :func:`distkeras_tpu.parallel.mesh.serving_mesh` turns
    this ONE engine into a GSPMD tensor-parallel replica (see the
    module docstring): params laid out per their logical axes, KV
    pool leaves heads-sharded, tables/slot/scheduler state replicated host
    metadata, every compiled callable pinned to explicit in/out
    shardings. The model's ``num_heads``/``mlp_dim``/``vocab_size``
    must divide the mesh's ``tp`` axis (validated here, typed). Greedy
    output is token-identical to the unsharded engine; hot swaps place
    candidate weights shard-then-place (bytes/tp per device).

    Observability (all default-off; see :mod:`distkeras_tpu.telemetry`):
    ``trace_store`` keeps per-request timeline records queryable by
    trace_id (the ``tracez`` verb); ``flight_recorder`` keeps a bounded
    black box of recent timelines + engine state transitions, dumped as
    last words if the run loop dies; ``slo_s`` arms the latency SLO —
    a request finishing slower bumps ``serving_slo_violations_total``
    and (with a recorder) pins its full timeline as a slow exemplar.
    With all three off, per-request timelines are never built and the
    per-token path does no tracing work at all.

    Drive it with :meth:`submit` + :meth:`run` (asyncio); blocking device
    work (prefill, decode step) runs in the default executor so the event
    loop keeps accepting connections mid-decode.
    """

    def __init__(
        self,
        model,
        variables,
        *,
        slots: int = 4,
        max_queue: int = 64,
        top_k: int | None = None,
        metrics: ServingMetrics | None = None,
        seed: int = 0,
        min_prefill_bucket: int = 8,
        auditor: RecompileAuditor | None = None,
        arm_auditor_after_warmup: bool = False,
        prefill_chunk: int | None = None,
        kv_pool_mb: float = 0.0,
        kv_block_tokens: int = 16,
        kv_pool_blocks: int | None = None,
        kv_host_tier_mb: float = 0.0,
        kv_disk_tier_dir: str | None = None,
        kv_disk_tier_mb: float = 0.0,
        kv_tier_watermark: float = 0.8,
        max_context: int | None = None,
        draft_model=None,
        draft_variables=None,
        spec_k: int = 4,
        mesh=None,
        pipeline_depth: int = 1,
        trace_store: TraceStore | None = None,
        flight_recorder: FlightRecorder | None = None,
        wide_events: "WideEventStore | int | None" = 4096,
        slo_s: float | None = None,
        weight_version: dict | None = None,
        tenant_weights: dict | None = None,
        tenant_quotas: dict | None = None,
        quota_burst_s: float = 2.0,
        constrained: bool = False,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 or None, got {prefill_chunk}")
        if int(pipeline_depth) != pipeline_depth or pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be a non-negative int: 0 (serialized "
                f"dispatch+harvest), 1 (dispatch tick N+1 before consuming "
                f"tick N), or >1 (micro-batched ticks overlapping pipeline "
                f"stages; needs a pp mesh), got {pipeline_depth}")
        self.pipeline_depth = int(pipeline_depth)
        # Dispatched-but-unharvested ticks, oldest first (at most
        # max(1, pipeline_depth) deep), and a bounded dispatch->harvest
        # timeline (the tracez tick lane).
        self._inflight: collections.deque = collections.deque()
        self._tick_log: collections.deque = collections.deque(maxlen=256)
        # Admissions whose prefill was enqueued without waiting for it
        # (_FirstTokenPending), read at the next harvest.
        self._pending_admits: list = []
        # False until the first decode dispatch has run (and therefore
        # compiled): the FIRST dispatch goes through the executor so a
        # multi-second compile cannot freeze the event loop, every later
        # one runs inline on the loop thread — dispatch is non-blocking
        # by design (async jax dispatch), and the executor round trip
        # it used to pay per tick is pure overhead that, on small
        # models, can cost more than the host gap the pipeline hides.
        self._dispatch_warm = False
        self.model = model
        self._spec = draft_model is not None
        self.draft_model = draft_model
        self.spec_k = int(spec_k)
        if self._spec and self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if self._spec and draft_variables is None:
            raise ValueError("draft_model needs draft_variables (the draft's "
                             "trained weights)")
        # GSPMD-sharded serving: ONE replica spread over a device mesh's
        # "tp" axis. Validated up front — a bad mesh must be a typed
        # ValueError here, not a jax lowering error three layers down.
        self.mesh = mesh
        self._tp = 1
        self._pp = 1
        self._replicated = None
        self._param_shardings = None
        self._cache_shardings = None
        self._stage_plan = None
        self._stage_meshes = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from distkeras_tpu.parallel.mesh import pp_stages

            if "tp" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh {dict(mesh.shape)} has no 'tp' axis; "
                    f"build it with parallel.mesh.serving_mesh")
            self._tp = int(mesh.shape["tp"])
            self._pp = int(pp_stages(mesh))
            extra = {a: s for a, s in mesh.shape.items()
                     if a not in ("tp", "pp") and s > 1}
            if extra:
                raise ValueError(
                    f"serving mesh has non-trivial non-tp axes {extra}: "
                    f"data parallelism in serving is N replicas (run.py "
                    f"cluster), not a dp mesh axis inside one engine")
            self._replicated = NamedSharding(mesh, P())
        # Constrained (structured) decoding: the decode executable takes
        # a per-slot token-mask operand. Single-stage only (the mask
        # lands on the LAST stage's logits; threading it through the pp
        # chain is future work).
        self._constrained_mode = bool(constrained)
        if self._constrained_mode and self._pp > 1:
            raise ValueError(
                "constrained=True is not supported on a pp mesh yet")
        # Micro-batch geometry. pipeline_depth > 1 only buys overlap when
        # ticks flow through >1 stage (a single-stage device serializes
        # them anyway), so it requires a pp mesh; the slot batch is then
        # partitioned into max(1, depth) contiguous micro-batches, each
        # with at most one tick in flight at steady state.
        if self.pipeline_depth > 1:
            if self._pp < 2:
                raise ValueError(
                    f"pipeline_depth={self.pipeline_depth} needs a pp>=2 "
                    f"serving mesh (micro-batched ticks only overlap "
                    f"across pipeline stages; --mesh-shape tp=N,pp=M)")
            if self._spec:
                raise ValueError(
                    f"pipeline_depth={self.pipeline_depth} is incompatible "
                    f"with speculative decoding (draft/verify ticks span "
                    f"the whole slot batch); use pipeline_depth<=1")
            if slots % self.pipeline_depth:
                raise ValueError(
                    f"slots={slots} does not divide into pipeline_depth="
                    f"{self.pipeline_depth} equal micro-batches")
        self._mb_count = (max(1, self.pipeline_depth)
                          if self._pp > 1 else 1)
        self._mb_size = int(slots) // self._mb_count
        self._mb_rr = 0
        if self._pp > 1 and kv_host_tier_mb > 0:
            raise ValueError(
                "kv_host_tier_mb > 0 is not supported on a pp mesh yet: "
                "the host tier's gather/scatter programs span the whole "
                "pool, which is stage-partitioned under pp")
        # Geometry probe: the plain decode-slots config, for the trained
        # context limit and the per-token KV byte cost.
        _, base_cfg = _decode_module(model, slots=True)
        if mesh is not None and self._tp > 1:
            bad = [f"{name}={val}"
                   for name, val in base_cfg.tp_split_sizes.items()
                   if val % self._tp]
            if bad:
                raise ValueError(
                    f"model {getattr(model, 'name', model)!r} does not "
                    f"shard over tp={self._tp}: {', '.join(bad)} not "
                    f"divisible — pick a tp that divides all of "
                    f"{', '.join(base_cfg.tp_split_sizes)}")
        base_limit = _context_limit(model, base_cfg)
        if max_context is not None:
            if not 1 <= max_context <= base_limit:
                raise ValueError(
                    f"max_context={max_context} outside [1, trained "
                    f"context {base_limit}]")
            self.limit = int(max_context)
        else:
            self.limit = base_limit
        if kv_block_tokens < 1:
            raise ValueError(
                f"kv_block_tokens must be >= 1, got {kv_block_tokens}")
        bt = int(kv_block_tokens)
        table_blocks = -(-self.limit // bt)
        # Per-token KV byte cost: the model's own statement (K and V
        # of one token through every layer, as the pool holds them).
        bytes_per_block = bt * base_cfg.kv_token_bytes
        if kv_pool_blocks is not None:
            capacity = int(kv_pool_blocks)
        elif kv_pool_mb > 0:
            capacity = int(kv_pool_mb * 2**20) // bytes_per_block
        else:
            # No budget given: a whole context for every slot, so the
            # pool never runs dry and nothing is ever preempted.
            capacity = int(slots) * table_blocks
        if capacity < 1:
            raise ValueError(
                f"kv_pool_mb={kv_pool_mb} / kv_pool_blocks="
                f"{kv_pool_blocks} holds zero {bt}-token blocks (one "
                f"block = {bytes_per_block} bytes)")
        self._module, self._cfg = _decode_module(
            model, slots=True, paged_blocks=capacity, page_tokens=bt,
            page_table_blocks=table_blocks, tp_mesh=mesh)
        self.kv_block_tokens = bt
        self._table_blocks = table_blocks
        # Table sentinel: an id one past the pool marks "unallocated"
        # — paged_kv_update drops writes there, paged_attention masks
        # the reads.
        self._sentinel = capacity
        if top_k is not None and not 1 <= top_k <= self._cfg.vocab_size:
            # Same bound generate() enforces: out-of-range top_k would
            # silently disable (or invert) the filtering via clamped
            # indexing rather than fail loudly.
            raise ValueError(
                f"top_k={top_k} outside [1, vocab_size={self._cfg.vocab_size}]"
            )
        if self._pp > 1:
            # Stage plan + per-stage modules. Each stage's module differs
            # from the engine's only in ``tp_mesh``: the sharding
            # constraints inside its compiled programs must name the
            # stage's OWN tp-only sub-mesh (a constraint against the full
            # tp×pp mesh would pin buffers to devices outside the
            # stage-jit's device set).
            from distkeras_tpu.parallel.mesh import stage_submesh
            from distkeras_tpu.parallel.pp import plan_stages

            self._stage_plan = plan_stages(self._cfg.num_layers, self._pp)
            self._stage_meshes = [stage_submesh(mesh, s)
                                  for s in range(self._pp)]
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._stage_rep = [NamedSharding(m, P())
                               for m in self._stage_meshes]
            self._stage_modules = [
                type(self._module)(
                    dataclasses.replace(self._cfg, tp_mesh=m))
                for m in self._stage_meshes]
        # Device-resident params from the start. An engine booted from a
        # weights FILE used to hold raw numpy leaves here — every jitted
        # dispatch re-converted them, and the first param swap (which
        # device_puts) then RETRACED the decode step: numpy and jax.Array
        # arguments occupy different jit-cache entries. One transfer at
        # construction makes boot and swap paths aval-identical.
        #
        # Sharded: the params' mesh layout comes from the model's
        # logical-axis annotations resolved against the mesh
        # (infer_variable_shardings), and boot goes through the SAME
        # shard-then-place seam every later hot swap uses — each device
        # is sent its slice directly, never a full replicated copy.
        if mesh is not None:
            from distkeras_tpu.parallel.sharding import (
                infer_variable_shardings,
                kv_pytree_shardings,
            )

            if self._pp > 1:
                # Per-stage shard-then-place: the abstract variables are
                # split along the stage plan and each stage's subtrees
                # resolve their logical axes against the stage's OWN
                # sub-mesh — so every param/KV leaf lands only on its
                # stage's devices, at boot and at every later hot swap.
                abstract = jax.eval_shape(
                    lambda r: self._module.init(
                        r, jnp.zeros((self._mb_size, 1), jnp.int32),
                        train=False),
                    jax.random.PRNGKey(0))
                plan = self._stage_plan
                # Abstract UNSPLIT param template: what a reload's tree
                # must look like (request_param_swap validates against
                # this, never the per-stage list with its duplicated
                # tied embedding). Unboxed — the live params carry no
                # LogicallyPartitioned metadata, and re-hanging a
                # reload's leaves on a boxed treedef would retrace
                # every stage jit at the swap rewarm.
                from distkeras_tpu.parallel.sharding import unbox

                self._swap_template = jax.tree.flatten(
                    unbox(abstract["params"]))
                self._param_shardings = [
                    infer_variable_shardings(m, {"params": p})["params"]
                    for m, p in zip(self._stage_meshes,
                                    plan.split_params(abstract["params"]))]
                self._cache_shardings = [
                    kv_pytree_shardings(m, c)
                    for m, c in zip(self._stage_meshes,
                                    plan.split_tree(abstract["cache"]))]
            else:
                abstract = jax.eval_shape(
                    lambda r: self._module.init(
                        r, jnp.zeros((int(slots), 1), jnp.int32),
                        train=False),
                    jax.random.PRNGKey(0))
                self._param_shardings = infer_variable_shardings(
                    mesh, abstract)["params"]
                self._cache_shardings = kv_pytree_shardings(
                    mesh, abstract["cache"])
        from distkeras_tpu.parallel.gspmd import place_sharded

        if self._pp > 1:
            self._params = [
                place_sharded(part, sh)
                for part, sh in zip(
                    self._stage_plan.split_params(variables["params"]),
                    self._param_shardings)]
        else:
            self._params = place_sharded(variables["params"],
                                         self._param_shardings)
        self.slots = int(slots)
        self.metrics = metrics or ServingMetrics()
        self.scheduler = Scheduler(
            max_depth=max_queue,
            registry=self.metrics.registry,
            tenant_weights=tenant_weights,
            tenant_quotas=tenant_quotas,
            quota_burst_s=quota_burst_s,
            # ONE labeler across the scheduler's and the metrics'
            # tenant families: a tenant past the cardinality cap folds
            # into "__other__" consistently everywhere.
            tenant_labeler=getattr(self.metrics, "tenant_labeler", None))
        self._min_bucket = int(min_prefill_bucket)
        self._chunk = None if prefill_chunk is None else int(prefill_chunk)
        self._prefill_rr = 0  # round-robin cursor over prefilling slots
        self._key = jax.random.PRNGKey(seed)

        # Device-resident batch state. ``_cache`` holds the SHARED block
        # pools (per-layer [capacity, bt, H*D] leaves, no per-slot index
        # leaves — positions/tables are passed per call).
        # Sharded: the KV leaves are committed to their heads-sharded
        # layout at creation, and every compiled program's out_shardings
        # pins the same layout, so the bytes never migrate.
        if self._pp > 1:
            # Stage-partitioned state. ``_cache`` is a per-stage list:
            # each stage's slice of the shared pools. ``_tokens`` /
            # ``_temps`` are per-micro-batch vectors committed to the
            # LAST stage — where sampling produces and admission updates
            # them — so every feed of the stage-0 decode program carries
            # the same placement and its jit keys one cache entry.
            self._cache = [
                jax.device_put(part, sh)
                for part, sh in zip(
                    self._stage_plan.split_tree(
                        _empty_cache(self._module, self.slots)),
                    self._cache_shardings)]
            rep_last = self._stage_rep[-1]
            self._tokens = [
                jax.device_put(jnp.zeros((self._mb_size,), jnp.int32),
                               rep_last)
                for _ in range(self._mb_count)]
            self._temps = [
                jax.device_put(jnp.zeros((self._mb_size,), jnp.float32),
                               rep_last)
                for _ in range(self._mb_count)]
        else:
            self._cache = _empty_cache(self._module, self.slots)
            self._tokens = jnp.zeros((self.slots,), jnp.int32)
            self._temps = jnp.zeros((self.slots,), jnp.float32)
            if mesh is not None:
                # Commit the rebound state to its layout NOW: jit cache
                # entries key on the actual argument shardings, so a
                # warmup or swap-rewarm tick on ctor-fresh (uncommitted)
                # tokens would occupy a DIFFERENT executable than every
                # post-admission tick on committed jit outputs — two
                # compiles of one program, which the armed auditor
                # rightly refuses.
                self._cache = jax.device_put(self._cache,
                                             self._cache_shardings)
                self._tokens = jax.device_put(self._tokens,
                                              self._replicated)
                self._temps = jax.device_put(self._temps, self._replicated)
        self._slot_state: list[_SlotState | None] = [None] * self.slots

        self.kv_pool = KVBlockPool(
            capacity, self.kv_block_tokens,
            bytes_per_block=bytes_per_block,
            registry=self.metrics.registry)
        # Host-side per-slot paging state: block tables (row i =
        # slot i's pool row per block index, sentinel = unallocated)
        # and written-KV lengths. The decode step gets (masked)
        # device views of these each tick.
        self._tables = np.full((self.slots, self._table_blocks),
                               self._sentinel, np.int32)
        self._lens = np.zeros((self.slots,), np.int64)
        # Admission parking: when a pop'd request could not get
        # blocks (and nobody lower-priority was preemptible) it is
        # requeued at its class head and admission pauses until the
        # pool's version moves (a free, eviction-eligibility change,
        # or adoption) — re-matching the same head request every
        # iteration would only burn host time and skew hit stats.
        self._parked_at_version: int | None = None
        self._parked_req: Request | None = None
        # Device-side masked table cache with a DIRTY flag: the
        # masked view only changes when a table row mutates
        # (admission reserve / growth / preemption / teardown) or a
        # slot's decodable status flips (prefill completion) — each
        # of those sites sets the flag, and the per-tick upload is
        # skipped while it is clear. The flag replaces an
        # O(slots × blocks) np.array_equal compare that ran on
        # EVERY tick just to conclude "unchanged" bt-1 times out of
        # bt.
        self._mark_tables_dirty()
        self._tables_dev = None
        # Device-side positions with the SAME dirty gating: the
        # decode step returns each live row's position + 1, so the
        # steady-state tick re-feeds the returned device vector and
        # the per-tick host build + H2D upload only happens when the
        # decodable set or a watermark actually changed (admission,
        # growth, preemption, teardown, prefill completion, spec
        # commits).
        self._positions_dev = None
        self._positions_dirty = True
        # Cache-aware admission: the scheduler may prefer (within one
        # priority class, bounded window) the queued request whose
        # prefix is already resident — see Scheduler.pop.
        self.scheduler.cache_probe = self.kv_pool.probe
        # Host-RAM (optionally disk-backed) spill tier under the
        # pool: eviction victims spill D2H as exact KVX1 bytes and
        # re-admit H2D on a trie miss during admission — see
        # serving/kv_tier.py. The spill hook fires inside
        # KVBlockPool._alloc, which only runs on the engine loop (or
        # the executor while the loop awaits it) and always after a
        # pipeline barrier (in-flight growth and admission ask
        # the pool's alloc_spills first), so the gather never races a
        # donated in-flight tick.
        self.kv_tier = None
        if kv_host_tier_mb > 0:
            from distkeras_tpu.serving.kv_tier import HostKVTier

            self.kv_tier = HostKVTier(
                int(kv_host_tier_mb * 2**20), bt,
                disk_dir=kv_disk_tier_dir,
                disk_budget_bytes=int(kv_disk_tier_mb * 2**20),
                watermark=kv_tier_watermark,
                registry=self.metrics.registry)
            self.kv_pool.spill_hook = self._spill_block
            # Allocation BURSTS (a multi-block admission or import
            # evicting several victims at once) spill through the
            # batched hook: one D2H gather for the whole burst,
            # mirroring _readmit_from_tier's one-scatter H2D path.
            self.kv_pool.spill_many_hook = self._spill_blocks
        # Trace context for spill exemplars: the admission /
        # growth / import currently driving allocations.
        self._tier_trace_id: str | None = None
        # Pending KV export/import operations, serviced by the run loop
        # between iterations: (kind, arg, event, result). Always empty
        # under pp (request_kv_export / _import refuse there).
        self._pending_kv: list[tuple] = []

        # Speculative decoding: a small draft model proposes spec_k
        # tokens per tick (one scanned dispatch), ONE batched target
        # call verifies all K+1 positions, and a masked accept commits
        # the longest greedy-consistent prefix — token-identical to
        # generate() by construction. The draft keeps its own DENSE
        # per-slot cache beside the target's pool (it is small
        # by definition — gpt_tiny drafting for gpt_small — so paying
        # worst-case length for it is noise next to the target pool).
        if self._spec:
            self._draft_module, self._draft_cfg = _decode_module(
                draft_model, slots=True,
                decode_cache_len=self.limit + self.spec_k)
            if self._draft_cfg.vocab_size != self._cfg.vocab_size:
                raise ValueError(
                    f"draft model vocab {self._draft_cfg.vocab_size} != "
                    f"target vocab {self._cfg.vocab_size}: draft proposals "
                    "must be target token ids")
            # Sharded engines REPLICATE the draft (params and cache):
            # the draft is small by definition — gpt_tiny drafting for
            # gpt_small — so replication buys a collective-free draft
            # scan on the latency-critical path for a memory cost that
            # is noise next to the sharded target. Under pp the draft
            # lives on STAGE 0's sub-mesh only (its proposals feed the
            # verify chain from the front).
            self._draft_rep = (self._stage_rep[0] if self._pp > 1
                               else self._replicated)
            self._draft_params = (
                jax.device_put(draft_variables["params"])
                if mesh is None else
                jax.device_put(draft_variables["params"], self._draft_rep))
            self._draft_cache = _empty_cache(self._draft_module, self.slots)
            if mesh is not None:
                self._draft_cache = jax.device_put(self._draft_cache,
                                                   self._draft_rep)
            self._draft_row_shapes = jax.eval_shape(
                lambda r: self._draft_module.init(
                    r, jnp.zeros((1, 1), jnp.int32), train=False),
                jax.random.PRNGKey(0),
            )["cache"]
            self._fresh_draft_row = jax.jit(
                named_program(
                    lambda: jax.tree.map(
                        lambda s: jnp.zeros(s.shape, s.dtype),
                        self._draft_row_shapes),
                    "serving_fresh_draft_row"),
                **({} if mesh is None
                   else {"out_shardings": self._draft_rep}))
            # Set when a live row accepted zero drafts: the next tick
            # runs the one-token fallback step (progress guarantee).
            self._spec_owe_fallback = False

        # One jit wrapper per engine so compile counts are per-instance:
        # the decode step must stay at exactly one executable for the
        # server's lifetime (see decode_compile_count()). The live batch
        # cache is donated — the engine rebinds it from each call's
        # outputs, and donation keeps the KV caches updating in place
        # instead of copying per decoded token. For the pools
        # that takes two things, and tests/test_tpu_compile.py holds
        # both in the programs compiled for a v5e: every leaf is in the
        # executable's input_output_alias, AND the leaf is stored
        # [capacity, bt, H*D] — a shape the compiler lays out the way
        # the scatter and the gather want it, so no program converts a
        # whole leaf's layout on the way in, for the gather, and on the
        # way out (three leaf-sized copies a leaf with [.., H, D] at
        # D = 64: the donation held and the tick still followed
        # --kv-pool-mb). The decode step's
        # TOKEN operand is deliberately NOT donated (unlike the cache):
        # that is the pipeline's double buffer — tick N's output tokens
        # are the harvest handle the host reads AFTER tick N+1 has been
        # dispatched with them as input, so the dispatch must not
        # invalidate the buffer ([slots] int32 — the extra copy per tick
        # is 4 bytes per slot). _temps is NOT donated either (it
        # persists across iterations). The admit epilogue leaves the
        # token vector undonated for the same reason: an admission may
        # be enqueued with a tick in flight, whose harvest handle that
        # vector is. The
        # prefill's incoming cache (the shared pools) is donated too: a
        # chunk chain threads it through every call, updating in place.
        # Sharded engines jit every callable with EXPLICIT in_shardings/
        # out_shardings: params in their logical-axis layout, KV leaves
        # heads-sharded, every index/token/table operand replicated. The
        # pinned layouts are part of each executable's signature — stable
        # across calls, so "exactly one executable per callable" survives
        # the mesh — and out_shardings guarantees the rebind-from-output
        # state (cache, tokens) never drifts off its layout.
        # Every program is jitted under the name the auditor wraps it
        # with below, so a profiler trace reads jit_serving_decode(<id>)
        # where the audit report reads serving_decode.
        def _sharded_jit(name, fn, in_sh, out_sh, donate):
            fn = named_program(fn, name)
            if self.mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate)

        rep = self._replicated
        psh = self._param_shardings
        csh = self._cache_shardings
        # What the module's plain decode tick counts, by name (none
        # for most models): harvested with the tokens, published as
        # ``<name>_total`` (ServingMetrics.record_tick_counters).
        self._tick_counters = tuple(
            getattr(self._module, "tick_counters", ())
            if self._pp == 1 and not self._constrained_mode
            else ())
        if self._pp > 1:
            self._build_pp_programs(top_k, auditor)
        else:
            self._prefill = _sharded_jit(
                "serving_prefill",
                functools.partial(_paged_prefill_fn, self._module, top_k),
                (psh, csh, rep, rep, rep, rep, rep, rep), (csh, rep),
                donate=(1,))
            self._admit_jit = _sharded_jit(
                "serving_admit",
                _paged_admit_fn,
                (rep, rep, rep, rep, rep), (rep, rep), donate=(1,))
            if self._constrained_mode:
                # The engine's ONE decode executable IS the masked
                # variant: the mask is a plain [slots, V] operand
                # (all-zero rows for unconstrained slots), so mixed
                # batches share it and compile-count==1 holds.
                self._decode_step = _sharded_jit(
                    "serving_decode",
                    functools.partial(_paged_decode_masked_fn,
                                      self._module, top_k, self._sentinel),
                    (psh, csh, rep, rep, rep, rep, rep, rep),
                    (csh, rep, rep), donate=(1,))
            else:
                self._decode_step = _sharded_jit(
                    "serving_decode",
                    functools.partial(_paged_decode_fn, self._module,
                                      top_k, self._sentinel),
                    (psh, csh, rep, rep, rep, rep, rep),
                    (csh, rep, rep) + (rep,) * bool(self._tick_counters),
                    donate=(1,))
            # Request-kind programs (PR 19). _prefill_logits is the
            # final-chunk prefill that hands the logits row back (fork
            # fan-out, constrained first token); the score/embed chunks
            # reuse the paged prefill's KV writes with a different
            # epilogue. All are control-path (report-only audit): they
            # run once per admission, never per tick.
            self._prefill_logits = _sharded_jit(
                "serving_prefill_logits",
                functools.partial(_paged_prefill_logits_fn, self._module),
                (psh, csh, rep, rep, rep, rep), (csh, rep), donate=(1,))
            self._fork_sample = _sharded_jit(
                "serving_fork_sample",
                functools.partial(_fork_sample_fn, top_k),
                (rep, rep, rep), rep, donate=())
            self._score_chunk = _sharded_jit(
                "serving_score_chunk",
                functools.partial(_score_chunk_fn, self._module),
                (psh, csh, rep, rep, rep, rep, rep), (csh, rep),
                donate=(1,))
            self._embed_chunk = _sharded_jit(
                "serving_embed_chunk",
                functools.partial(_embed_chunk_fn, self._module),
                (psh, csh, rep, rep, rep, rep), (csh, rep), donate=(1,))
            # Constrained-decoding mask state: host truth [slots, V]
            # (zero rows = unconstrained), device copy re-uploaded only
            # under the dirty flag — the same gating the block tables
            # use, with the upload timed into mask_upload_seconds.
            if self._constrained_mode:
                self._mask_host = np.zeros(
                    (int(slots), self._cfg.vocab_size), np.float32)
                self._mask_dev = None
                self._mask_dirty = True
            # KV block migration (serving/kv_transfer.py): gather rows
            # for an export (output replicated — it is host-fetched
            # immediately, and on a sharded engine the all-gather IS
            # the full-heads serialization contract), scatter imported
            # rows back in (upload replicated, pool keeps its
            # heads-sharded layout — the kv_pytree_shardings reshard
            # seam). Both run between ticks via the engine loop's
            # pending-op queue, so they can never race a donated cache.
            self._kv_gather = _sharded_jit(
                "serving_kv_gather",
                _kv_gather_fn, (csh, rep), rep, donate=())
            self._kv_scatter = _sharded_jit(
                "serving_kv_scatter",
                _kv_scatter_fn, (csh, rep, rep), csh, donate=(0,))
        if self._spec and self._pp == 1:
            # Draft cache donated; tokens are NOT (the verify consumes
            # them right after). Verify donates cache + tokens exactly
            # like the fallback decode step it substitutes for. The
            # draft trio runs fully replicated on a sharded engine.
            self._draft_step = _sharded_jit(
                "serving_draft",
                functools.partial(_spec_draft_fn, self._draft_module,
                                  self.spec_k),
                (rep, rep, rep, rep, rep), (rep, rep), donate=(1,))
            self._verify_step = _sharded_jit(
                "serving_verify",
                functools.partial(_paged_spec_verify_fn, self._module,
                                  top_k),
                (psh, csh, rep, rep, rep, rep, rep, rep, rep, rep, rep),
                (csh, rep, rep, rep), donate=(1, 2))
            self._draft_prefill = _sharded_jit(
                "serving_draft_prefill",
                functools.partial(_draft_prefill_fn, self._draft_module),
                (rep, rep, rep, rep, rep), rep, donate=(1,))
            self._draft_admit = _sharded_jit(
                "serving_draft_admit",
                _draft_admit_fn, (rep, rep, rep), rep, donate=(0,))

        # Recompile auditing: the compile-count==1 decode invariant as a
        # RUNTIME check, not just a benchmark assertion. The auditor wraps
        # all three programs; with ``arm_auditor_after_warmup`` the decode
        # step is armed after its first iteration, so any later retrace
        # (admission, dtype drift) raises RecompileError at the offending
        # call instead of silently stretching tail latency.
        self.auditor = auditor
        self._arm_after_warmup = bool(arm_auditor_after_warmup)
        if self._pp == 1:
            self._decode_audit_names = ["serving_decode"] + (
                ["serving_draft", "serving_verify"] if self._spec else [])
        if auditor is not None and self._pp == 1:
            self._prefill = auditor.wrap(self._prefill, "serving_prefill")
            self._admit_jit = auditor.wrap(self._admit_jit, "serving_admit")
            # Report-only (never armed): export/import are rare
            # control-path operations, but their compile counts
            # still belong in the audit report.
            self._kv_gather = auditor.wrap(
                self._kv_gather, "serving_kv_gather")
            self._kv_scatter = auditor.wrap(
                self._kv_scatter, "serving_kv_scatter")
            # Request-kind programs: report-only, like the pow2
            # prefill buckets — admission-path work, never per-tick.
            self._prefill_logits = auditor.wrap(
                self._prefill_logits, "serving_prefill_logits")
            self._fork_sample = auditor.wrap(
                self._fork_sample, "serving_fork_sample")
            self._score_chunk = auditor.wrap(
                self._score_chunk, "serving_score_chunk")
            self._embed_chunk = auditor.wrap(
                self._embed_chunk, "serving_embed_chunk")
            self._decode_step = auditor.wrap(
                self._decode_step, "serving_decode")
            if self._spec:
                self._draft_step = auditor.wrap(
                    self._draft_step, "serving_draft")
                self._verify_step = auditor.wrap(
                    self._verify_step, "serving_verify")
                self._draft_prefill = auditor.wrap(
                    self._draft_prefill, "serving_draft_prefill")
                self._draft_admit = auditor.wrap(
                    self._draft_admit, "serving_draft_admit")

        # Request tracing + flight recording. Timelines are built only
        # when at least one sink exists — with both off the per-request
        # cost is a None attribute and the per-token cost is zero.
        self.trace_store = trace_store
        self.flight_recorder = flight_recorder
        # Hop identity stamped into timeline records (a LocalReplica
        # factory overwrites it with the replica id — several engines
        # share one pid there).
        self.trace_source = (flight_recorder.source
                             if flight_recorder is not None
                             else f"pid:{os.getpid()}")
        # Fleet role for wide-event attribution ("monolithic" unless a
        # disaggregated launcher overwrites it, like trace_source).
        self.serve_role = "monolithic"
        self.slo_s = None if slo_s is None else float(slo_s)
        self._trace_requests = (trace_store is not None
                                or flight_recorder is not None)
        # Wide-event analytics: one flat record per FINISHED request
        # into a bounded columnar ring — default ON (unlike timelines)
        # because the whole cost is one append at done-time, never
        # per-token. An int is a capacity; 0/None disables.
        if isinstance(wide_events, WideEventStore):
            self.wide_events: WideEventStore | None = wide_events
        elif wide_events:
            self.wide_events = WideEventStore(int(wide_events))
        else:
            self.wide_events = None
        if (flight_recorder is not None
                and getattr(flight_recorder, "wide_events", None) is None):
            # Crash dumps carry the wide-event ring tail: the requests
            # the process served right before it died, even when no
            # timeline store was armed.
            flight_recorder.wide_events = self.wide_events
        if self.slo_s is not None:
            self.metrics.set_slo(self.slo_s)

        # Weight provenance: which checkpoint the live params came from
        # ({"version": int, "digest": str} — see
        # checkpoint.weights_provenance). Stamped into every request at
        # admission, every done line, healthz/metricsz/debugz; updated
        # by a successful param swap. An engine started on inline
        # variables gets version 0 / digest None — the field is ALWAYS
        # present so consumers never branch on its existence.
        self.weight_version = _public_provenance(weight_version)
        self.metrics.set_weight_version(self.weight_version)
        # Device-memory accounting: params bytes are fixed at
        # construction; KV-pool bytes come from the pool's capacity and
        # high-water mark at refresh time.
        self._params_bytes = sum(
            getattr(l, "nbytes", 0) for l in jax.tree.leaves(self._params))

        self._running = False
        self._stopping = False
        self._draining = True
        # Fault-injection knob (the SLO bench's breach phase, via the
        # ``inject_latency`` control verb): a host-side sleep per decode
        # iteration. Purely host-time — the device work and compiled
        # executables are untouched, so the armed auditor stays at one
        # compile — but every slot's real ITL/TTFT stretches by it.
        self.inject_decode_delay_s = 0.0
        # Pending parameter swap: (params, done-event, result dict) set by
        # request_param_swap(), consumed by the run loop at the first
        # iteration with no slot in flight.
        self._pending_swap: tuple | None = None

        if self._spec:
            # Warm ALL THREE spec-mode executables (fallback decode,
            # draft scan, verify) on the pristine all-free batch NOW:
            # the run loop arms the auditor after the first real tick,
            # and which path that tick takes depends on traffic — a
            # lazily-compiled fallback (or verify) would then count as a
            # post-arm retrace. Garbage-in, garbage-out is safe here for
            # the same reason free rows may decode garbage every tick.
            self._decode_sync()
            self._spec_sync()
            # Every tick executable exists now: run-loop dispatches can
            # go inline from the first iteration.
            self._dispatch_warm = True

    # -- pipeline-parallel program construction -----------------------------
    def _build_pp_programs(self, top_k, auditor) -> None:
        """Compile the per-stage serving programs for a ``tp=N,pp=M``
        mesh: each pipeline stage gets its OWN jits (prefill slice,
        decode slice, spec verify slice) at explicit
        in/out shardings against the stage's sub-mesh, and thin host
        wrappers chain them under the monolithic call signatures the
        dispatch paths already use.

        Compile-count==1 per stage rests on SOURCE CONSISTENCY, not on
        trust in auto-transfers: a jit cache entry keys on each
        argument's actual committed placement, so every argument
        position must always ARRIVE placed the same way. The invariants
        here: tokens/temps always live on the LAST stage (ctor
        device_put, admit + decode out_shardings); positions/
        tables are device_put per stage once and then re-fed from that
        stage's own outputs; fresh host values (chunk offsets, split
        keys, slot ids) are uncommitted — placement-free — every call;
        and every value that CROSSES a stage boundary (the residual
        activation, last-stage tokens feeding stage 0) goes through
        :meth:`_to_stage` — jax auto-transfers only single-device
        arrays between 1-device stages, and a committed tp-sharded
        array fed to another stage's sub-mesh is a runtime placement
        error, so the handoff is placed explicitly. The target layout
        is the same every call, so each stage jit still keys exactly
        one cache entry.
        """
        S = self._pp
        last = S - 1
        plan = self._stage_plan
        mods = self._stage_modules
        psh = self._param_shardings
        csh = self._cache_shardings
        reps = self._stage_rep
        rep_last = reps[-1]
        hop = self._to_stage

        def sjit(fn, in_sh, out_sh, donate):
            # Jitted by wrap(), which has the program's name.
            return lambda name: jax.jit(
                named_program(fn, name), in_shardings=in_sh,
                out_shardings=out_sh, donate_argnums=donate)

        def wrap(build, name):
            fn = build(name)
            return fn if auditor is None else auditor.wrap(fn, name)

        sent = self._sentinel
        pf = [wrap(sjit(
            functools.partial(_pp_paged_prefill_fn, mods[s],
                              plan.stage_arg(s)),
            (psh[s], csh[s], reps[s], reps[s], reps[s]),
            (csh[s], reps[s]), (1,)), f"serving_prefill_s{s}")
            for s in range(last)]
        pf_last = wrap(sjit(
            functools.partial(_pp_paged_prefill_last_fn, mods[last],
                              plan.stage_arg(last), top_k),
            (psh[last], csh[last]) + (reps[last],) * 6,
            (csh[last], rep_last), (1,)), f"serving_prefill_s{last}")

        def prefill(params, pools, padded, start, true_len, table_row,
                    temp, key):
            pools = list(pools)
            x = padded
            for s in range(last):
                if s:
                    x = hop(x, s)
                pools[s], x = pf[s](params[s], pools[s], x, start,
                                    table_row)
            pools[last], tok = pf_last(params[last], pools[last],
                                       hop(x, last) if last else x,
                                       start, true_len, table_row,
                                       temp, key)
            return pools, tok

        self._prefill = prefill
        admit = wrap(sjit(_paged_admit_fn, (rep_last,) * 5,
                          (rep_last, rep_last), (0, 1)),
                     "serving_admit")

        def admit_wrap(tokens, temps, slot, tok, temp):
            mb, local = divmod(int(slot), self._mb_size)
            tokens, temps = list(tokens), list(temps)
            tokens[mb], temps[mb] = admit(
                tokens[mb], temps[mb], jnp.int32(local), tok, temp)
            return tokens, temps

        self._admit_jit = admit_wrap
        self._decode_steps = [wrap(sjit(
            functools.partial(_pp_paged_decode_fn, mods[s],
                              plan.stage_arg(s), sent),
            (psh[s], csh[s], reps[s], reps[s], reps[s]),
            (csh[s], reps[s], reps[s]), (1,)), f"serving_decode_s{s}")
            for s in range(last)]
        self._decode_steps.append(wrap(sjit(
            functools.partial(_pp_paged_decode_last_fn, mods[last],
                              plan.stage_arg(last), top_k, sent),
            (psh[last], csh[last]) + (reps[last],) * 5,
            (csh[last], rep_last, reps[last]), (1,)),
            f"serving_decode_s{last}"))
        self._decode_step = None  # per-stage under pp: _decode_steps
        self._decode_audit_names = [f"serving_decode_s{s}"
                                    for s in range(S)]

        if self._spec:
            rep0 = reps[0]
            draft = wrap(sjit(
                functools.partial(_spec_draft_fn, self._draft_module,
                                  self.spec_k),
                (rep0,) * 5, (rep0, rep0), (1,)), "serving_draft")

            def draft_step(dp, dc, prev, tokens, start):
                # ``tokens`` is the engine's per-micro-batch list (spec
                # forces mb_count==1); it lives on the LAST stage, the
                # draft runs on stage 0.
                return draft(dp, dc, prev, hop(tokens[0], 0), start)

            self._draft_step = draft_step
            self._draft_prefill = wrap(sjit(
                functools.partial(_draft_prefill_fn, self._draft_module),
                (rep0,) * 5, rep0, (1,)), "serving_draft_prefill")
            self._draft_admit = wrap(sjit(
                _draft_admit_fn, (rep0, rep0, rep0), rep0, (0,)),
                "serving_draft_admit")
            vf0 = wrap(sjit(
                functools.partial(_pp_paged_verify_first_fn, mods[0],
                                  plan.stage_arg(0)),
                (psh[0], csh[0]) + (reps[0],) * 4,
                (csh[0], reps[0]), (1,)), "serving_verify_s0")
            vmid = [wrap(sjit(
                functools.partial(_pp_paged_verify_fn, mods[s],
                                  plan.stage_arg(s)),
                (psh[s], csh[s], reps[s], reps[s], reps[s]),
                (csh[s], reps[s]), (1,)), f"serving_verify_s{s}")
                for s in range(1, last)]
            vlast = wrap(sjit(
                functools.partial(_pp_paged_verify_last_fn, mods[last],
                                  plan.stage_arg(last), top_k),
                (psh[last], csh[last]) + (reps[last],) * 10,
                (csh[last], rep_last, rep_last, rep_last), (1,)),
                f"serving_verify_s{last}")

            def verify(params, pools, tokens, drafts, temps, spec_ok,
                       remaining, room, start, tables, key):
                toks = tokens[0]
                pools = list(pools)
                pools[0], act = vf0(params[0], pools[0], hop(toks, 0),
                                    drafts, start, tables[0])
                for s in range(1, last):
                    pools[s], act = vmid[s - 1](params[s], pools[s],
                                                hop(act, s), start,
                                                tables[s])
                pools[last], new_tok, out, commit = vlast(
                    params[last], pools[last], hop(act, last),
                    hop(drafts, last), toks,
                    temps[0], spec_ok, remaining, room, start,
                    tables[last], key)
                return pools, [new_tok], out, commit

            self._verify_step = verify
            self._decode_audit_names += (
                ["serving_draft"]
                + [f"serving_verify_s{s}" for s in range(S)])

    # -- introspection ------------------------------------------------------
    def decode_compile_count(self) -> int:
        """Number of compiled decode executables (must stay 1: admission
        must never retrace the decode step). -1 when the jit cache probe
        is unavailable; falls back to the auditor's count if one is
        attached (so audited engines keep a real count on jax versions
        without the private probe). Under pp the invariant is per
        STAGE — this returns the max over stages (1 iff every stage
        compiled exactly once); :meth:`decode_compile_counts` has the
        per-stage vector."""
        if self._pp > 1:
            counts = self.decode_compile_counts()
            return -1 if any(c < 0 for c in counts) else max(counts)
        size = self._probe_cache_size(self._decode_step)
        if size is not None:
            return int(size)
        if self.auditor is not None:
            return self.auditor.compiles("serving_decode")
        return -1

    @staticmethod
    def _probe_cache_size(fn) -> int | None:
        probe = getattr(fn, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:
            return None

    def decode_compile_counts(self) -> list[int]:
        """Per-stage decode compile counts (pp engines; ``[count]`` for
        a single-stage engine) — the per-stage face of the
        compile-count==1 invariant."""
        if self._pp == 1:
            return [self.decode_compile_count()]
        counts = []
        for s, fn in enumerate(self._decode_steps):
            size = self._probe_cache_size(fn)
            if size is None and self.auditor is not None:
                size = self.auditor.compiles(f"serving_decode_s{s}")
            counts.append(-1 if size is None else int(size))
        return counts

    def tick_timeline(self, n: int | None = None) -> list[dict]:
        """The bounded dispatch→harvest tick lane (most recent last):
        per tick, its kind, dispatch/harvest stamps, how long the
        harvest blocked on the device, and the measured host gap — the
        tracez view of what the pipeline is (or is not) hiding."""
        log = list(self._tick_log)
        return log if n is None else log[-int(n):]

    def mesh_info(self) -> dict | None:
        """Static view of the engine's device mesh for healthz/debugz:
        axis sizes and the per-shard device names — None unsharded, so
        consumers (router rollups, the deploy controller's fleet verify)
        can tell a sharded replica from a plain one at a glance."""
        if self.mesh is None:
            return None
        from distkeras_tpu.telemetry.device import _device_name

        info = {
            "axes": {a: int(s) for a, s in self.mesh.shape.items()},
            "tp": self._tp,
            "pp": self._pp,
            "devices": [_device_name(d)
                        for d in self.mesh.devices.flatten()],
        }
        if self._pp > 1:
            # Per-stage attribution: devices, owned layer range, and
            # resident params/KV bytes — the fleet-verify view of where
            # each stage's share of the model actually landed.
            stages = []
            for s in range(self._pp):
                lo, hi = self._stage_plan.layer_range(s)
                stages.append({
                    "stage": s,
                    "layers": [lo, hi],
                    "devices": [_device_name(d) for d in
                                self._stage_meshes[s].devices.flatten()],
                    "params_bytes": sum(
                        getattr(l, "nbytes", 0)
                        for l in jax.tree.leaves(self._params[s])),
                    "kv_bytes": sum(
                        getattr(l, "nbytes", 0)
                        for l in jax.tree.leaves(self._cache[s])),
                })
            info["stages"] = stages
        return info

    def _bytes_by_device(self, tree) -> dict[str, int]:
        """Per-device resident bytes of a (possibly sharded) pytree —
        what makes a sharded engine's params/KV attributable per shard
        instead of one engine-wide number. Host metadata only (shard
        shapes), no device sync."""
        from distkeras_tpu.telemetry.device import _device_name

        out: dict[str, int] = {}
        for leaf in jax.tree.leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards is None:
                continue
            for s in shards:
                name = _device_name(s.device)
                out[name] = out.get(name, 0) + int(
                    np.prod(s.data.shape) * s.data.dtype.itemsize)
        return out

    def refresh_memory_metrics(self) -> list[dict]:
        """Probe per-device ``memory_stats()`` (typed sentinel — a
        backend without the API publishes ``available=0``, never a fake
        0 bytes), publish the gauges plus this engine's workload-side
        bytes (params, KV pool reserved/peak), and return the per-device
        rows for healthz. Sharded engines additionally publish the
        params/KV bytes PER MESH DEVICE (labeled gauges + per-row
        fields), so each shard's footprint is attributable. Host-only;
        called per metricsz/healthz scrape, never on the decode path."""
        from distkeras_tpu.telemetry.device import publish_memory_gauges

        kv_bytes = kv_peak = None
        if self.kv_pool.bytes_per_block:
            kv_bytes = self.kv_pool.capacity * self.kv_pool.bytes_per_block
            kv_peak = (self.kv_pool.peak_blocks_used
                       * self.kv_pool.bytes_per_block)
            # Device-tier occupancy of the KV hierarchy (host/disk
            # gauges are kept live by the tier itself).
            self.metrics.set_kv_tier_resident_bytes(
                self.kv_pool.blocks_used * self.kv_pool.bytes_per_block)
        params_by_dev = kv_by_dev = None
        if self.mesh is not None:
            try:
                params_by_dev = self._bytes_by_device(self._params)
                kv_by_dev = self._bytes_by_device(self._cache)
            except Exception:
                params_by_dev = kv_by_dev = None
        try:
            mems = publish_memory_gauges(
                self.metrics.registry,
                params_bytes=self._params_bytes,
                kv_pool_bytes=kv_bytes,
                kv_pool_peak_bytes=kv_peak,
                params_bytes_by_device=params_by_dev,
                kv_bytes_by_device=kv_by_dev)
        except Exception:
            return []
        rows = [m.to_dict() for m in mems]
        if params_by_dev or kv_by_dev:
            for row in rows:
                dev = row.get("device")
                if params_by_dev and dev in params_by_dev:
                    row["params_bytes"] = params_by_dev[dev]
                if kv_by_dev and dev in kv_by_dev:
                    row["kv_bytes"] = kv_by_dev[dev]
        return rows

    def tenant_snapshot(self) -> dict:
        """Per-tenant QoS rollup for healthz/debugz — occupancy (active
        decode slots), queue depth, quota bucket state, over-quota shed
        counts, and lifetime completed/token counters — refreshing the
        labeled tenant gauges on the way (scrape-time, like the memory
        gauges: the triage page for "is one tenant starving the
        fleet")."""
        active: dict[str, int] = {}
        for st in self._slot_state:
            if st is not None:
                t = st.request.tenant
                active[t] = active.get(t, 0) + 1
        out = self.scheduler.tenant_stats()
        for tenant, n in active.items():
            out.setdefault(tenant, {"queued": 0})["active_slots"] = n
        for tenant, counts in self.metrics.tenant_counters().items():
            out.setdefault(tenant, {"queued": 0}).update(counts)
        self.metrics.set_tenant_active(active)
        return out

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self._slot_state if s is not None)

    @property
    def free_slots(self) -> int:
        return self.slots - self.active_slots

    def debugz(self) -> dict:
        """Live state snapshot for the ``debugz`` control verb: the slot
        table (per-slot phase, trace_id, sequence depth, age), the
        scheduler queue with per-request ages, prefix-cache trie
        occupancy, and flight-recorder/SLO status — the "what is the
        engine doing RIGHT NOW" page metricsz's aggregates can't answer.
        JSON-safe; reads live structures without locking (the asyncio
        control handler and the engine loop interleave at await points,
        and a slightly torn read of a diagnostic page is harmless)."""
        now = time.monotonic()
        slots = []
        for i, st in enumerate(self._slot_state):
            if st is None:
                slots.append({"slot": i, "state": "free"})
                continue
            req = st.request
            entry = {
                "slot": i,
                "state": ("prefill" if st.prefill is not None
                          else "fork_wait" if st.fork_wait
                          else "decode"),
                "kind": req.kind,
                "trace_id": req.trace_id,
                "tenant": req.tenant,
                "depth": len(req.prompt) + len(req.out_tokens),
                "remaining": st.remaining,
                "age_s": (round(now - req.t_submit, 6)
                          if req.t_submit is not None else None),
            }
            # Block-table depth: shared prefix blocks + private chain.
            entry["blocks"] = st.first_block + len(st.blocks)
            entry["shared_blocks"] = st.first_block
            if st.dfa is not None:
                # Automaton column: where this constrained stream's
                # host-side state machine sits right now — a stream
                # wedged mid-grammar shows as a stuck state here.
                entry["automaton_state"] = st.dfa_state
            if self._spec and st.spec_drafted:
                # Accept-rate column: this request's committed drafts
                # over its proposed drafts — the per-slot view of how
                # well the draft model is predicting THIS stream.
                entry["accept_rate"] = round(
                    st.spec_accepted / st.spec_drafted, 3)
            if st.prefill is not None:
                entry["prefill"] = {
                    "pos": st.prefill.pos,
                    "prompt_tokens": len(req.prompt),
                    "chunks_done": st.prefill.chunks_done,
                }
            slots.append(entry)
        out = {
            "slots": slots,
            "active_slots": self.active_slots,
            "queue": self.scheduler.debugz(now),
            "tenants": self.tenant_snapshot(),
            "stopping": self._stopping,
            "pending_swap": self._pending_swap is not None,
            "decode_compile_count": self.decode_compile_count(),
            "weight_version": self.weight_version,
            "request_kinds": self.metrics.kind_counters(),
            "pipeline": {
                "depth": self.pipeline_depth,
                "inflight": (self._inflight[-1].kind
                             if self._inflight else None),
                "inflight_ticks": len(self._inflight),
                "ticks_logged": len(self._tick_log),
                "host_gap_p50_s": self.metrics.host_gap.gap_p50,
                "device_idle_ratio": self.metrics.host_gap.idle_ratio,
            },
        }
        if self._pp > 1:
            out["pipeline"]["stages"] = self._pp
            out["pipeline"]["micro_batches"] = self._mb_count
            out["pipeline"]["bubble_fraction"] = (
                self.metrics.bubble.fraction)
        if self.mesh is not None:
            out["mesh"] = self.mesh_info()
        if self._spec:
            drafted = self.metrics.spec_draft_tokens
            out["speculative"] = {
                "spec_k": self.spec_k,
                "draft_model": getattr(self.draft_model, "name",
                                       str(self.draft_model)),
                "draft_tokens": drafted,
                "accepted_tokens": self.metrics.spec_accepted_tokens,
                "accept_rate": (round(
                    self.metrics.spec_accepted_tokens / drafted, 4)
                    if drafted else None),
            }
        out["kv_pool"] = {
            **self.kv_pool.debugz(),
            "blocks_free": self.kv_pool.blocks_free,
            "preemptions": self.metrics.preemptions,
            "oom_rejections": self.metrics.oom_rejections,
            "kv_migrations": self.metrics.kv_migrations,
            "kv_migration_fallbacks":
                self.metrics.kv_migration_fallbacks,
            "kv_migration_bytes": self.metrics.kv_migration_bytes,
            "kv_exports": self.metrics.kv_exports,
        }
        if self.kv_tier is not None:
            # Tier section on the kv_pool page: occupancy of the
            # host/disk levels plus the engine's traffic through
            # them (device resident bytes ride along so all three
            # tiers of the hierarchy read off one dict).
            self.metrics.set_kv_tier_resident_bytes(
                self.kv_pool.blocks_used
                * (self.kv_pool.bytes_per_block or 0))
            out["kv_tier"] = {
                **self.kv_tier.stats(),
                "resident_bytes": self.kv_pool.blocks_used
                * (self.kv_pool.bytes_per_block or 0),
                "spills": self.metrics.kv_spills,
                "spill_bytes": self.metrics.kv_spill_bytes,
                "readmits": self.metrics.kv_readmits,
                "readmit_bytes": self.metrics.kv_readmit_bytes,
                "pushes": self.metrics.kv_pushes,
                "push_bytes": self.metrics.kv_push_bytes,
                "push_fallbacks": self.metrics.kv_push_fallbacks,
            }
        if self.flight_recorder is not None:
            out["flight_recorder"] = self.flight_recorder.stats()
        if self.trace_store is not None:
            out["trace_store"] = self.trace_store.stats()
        if self.slo_s is not None:
            out["slo_s"] = self.slo_s
        return out

    # -- submission ---------------------------------------------------------
    def _build_request(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        priority: int = 0,
        timeout: float | None = None,
        trace_id: str | None = None,
        speculate: bool = True,
        tenant: str = "default",
        resume_tokens=None,
        kind: str = "generate",
        n: int = 1,
        constraint=None,
    ) -> Request:
        """Validation half of submission: everything that can reject a
        request typed BEFORE it touches the scheduler — shared by
        :meth:`submit` and the batched :meth:`submit_many`. Contradictory
        kind combinations (score with max_new_tokens, n>1 outside
        sample, a constraint on an unconstrained engine) reject typed
        HERE — a bad request must fail at admission, never mid-stream.

        ``resume_tokens``: output tokens the client ALREADY received on
        another replica (live slot migration off a draining peer): they
        pre-seed ``out_tokens``, so admission prefills prompt + resume
        and the first sampled token CONTINUES the stream instead of
        restarting it — the same fold-streamed-tokens-into-prefill
        contract paged preemption uses in-process, applied over the
        wire. They count against ``max_new_tokens`` and are never
        re-streamed."""
        if self._stopping:
            raise EngineStopped("engine is shutting down; not admitting")
        kind = str(kind or "generate")
        n = int(n or 1)
        if kind not in REQUEST_KINDS:
            raise ValueError(
                f"unknown request kind {kind!r}; expected one of "
                f"{REQUEST_KINDS}")
        if kind != "generate" and self._pp > 1:
            raise ValueError(
                f"kind={kind!r} requires a single-stage engine (pp=1)")
        if kind in SCORELIKE_KINDS:
            if max_new_tokens > 0:
                raise ValueError(
                    f"kind={kind!r} is prefill-only: max_new_tokens must "
                    f"be 0, got {max_new_tokens}")
            speculate = False
        if kind == "sample":
            if n < 2:
                raise ValueError(
                    f"kind='sample' requires n >= 2 forks, got {n}")
            if n > self.slots:
                raise ValueError(
                    f"n={n} forks exceed the engine's {self.slots} slots")
            if self._spec and speculate:
                raise ValueError(
                    "n>1 forked sampling does not compose with "
                    "speculative decoding; pass speculate=False")
            speculate = False
        elif n != 1:
            raise ValueError(f"n={n} requires kind='sample'")
        dfa = None
        if constraint is not None:
            if not self._constrained_mode:
                raise ValueError(
                    "this engine was not built with constrained=True; "
                    "token-mask constraints are unavailable")
            if kind != "generate":
                raise ValueError(
                    f"constraint requires kind='generate', got {kind!r}")
            dfa = (constraint if isinstance(constraint, TokenDFA)
                   else TokenDFA.from_spec(constraint))
            if dfa.max_token() >= self._cfg.vocab_size:
                raise ValueError(
                    f"constraint references token {dfa.max_token()} "
                    f">= vocab_size {self._cfg.vocab_size}")
        prompt_arr = np.asarray(prompt, np.int32)
        if prompt_arr.ndim == 2 and prompt_arr.shape[0] == 1:
            prompt_arr = prompt_arr[0]
        if prompt_arr.ndim != 1 or prompt_arr.size < 1:
            raise ValueError(f"prompt must be a non-empty 1-D token list; "
                             f"got shape {prompt_arr.shape}")
        if kind in SCORELIKE_KINDS:
            # Prefill-only: the whole prompt must fit the context; no
            # decode budget to bound.
            if prompt_arr.size > self.limit:
                raise ValueError(
                    f"prompt ({prompt_arr.size}) exceeds this engine's "
                    f"context cap {self.limit}")
        else:
            _check_context(self.model, self._cfg, prompt_arr[None, :],
                           max_new_tokens)
        if kind not in SCORELIKE_KINDS \
                and prompt_arr.size + max_new_tokens > self.limit:
            # Tighter than the model's trained context: the engine's
            # max_context cap.
            raise ValueError(
                f"prompt ({prompt_arr.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds this engine's context cap "
                f"{self.limit} (max_context)")
        # Resident K/V at completion: every position except the last
        # sampled token's (never fed back). A request that can never
        # fit the pool is a sizing error — reject typed, up front.
        bt = self.kv_block_tokens
        if kind in SCORELIKE_KINDS:
            # Scorelike feeds every prompt token, so all of them
            # are resident at completion.
            need = -(-prompt_arr.size // bt)
        elif kind == "sample":
            # n forks share the prompt's COMPLETE blocks; each owns
            # the rest (partial tail copy + decode growth) itself.
            resident = prompt_arr.size + max_new_tokens - 1
            shared = prompt_arr.size // bt
            need = shared + n * (-(-resident // bt) - shared)
        else:
            resident = prompt_arr.size + max_new_tokens - 1
            need = -(-resident // bt)
        if need > self.kv_pool.capacity:
            self.metrics.record_oom_reject()
            raise PoolExhausted(
                f"request needs {need} KV blocks at completion; the "
                f"pool holds {self.kv_pool.capacity} — raise "
                f"--kv-pool-mb or lower max_new_tokens")
        req = Request(
            prompt_arr.tolist(), max_new_tokens, temperature=temperature,
            priority=priority, timeout=timeout, trace_id=trace_id,
            speculate=speculate, tenant=tenant,
            kind=kind, n=n, constraint=dfa,
        )
        if resume_tokens:
            try:
                resume = [int(t) for t in resume_tokens]
            except (TypeError, ValueError) as e:
                raise ValueError(f"bad resume_tokens: {e}") from None
            if len(resume) >= max_new_tokens:
                raise ValueError(
                    f"resume_tokens ({len(resume)}) >= max_new_tokens "
                    f"({max_new_tokens}): nothing left to decode")
            # Pre-seed the streamed prefix: _resident_tokens, the resume
            # prefill, quota cost, and the slot's remaining budget all
            # read prompt + out_tokens — the resumed request is
            # indistinguishable from a locally preempted one.
            req.out_tokens = resume
        if self._trace_requests:
            req.trace = TimelineRecord(req.trace_id, "engine",
                                       self.trace_source)
            req.trace.data["kind"] = req.kind
            req.trace.event("submit", prompt_tokens=len(req.prompt),
                            max_new_tokens=req.max_new_tokens,
                            priority=req.priority, tenant=req.tenant,
                            kind=req.kind)
        return req

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        priority: int = 0,
        timeout: float | None = None,
        trace_id: str | None = None,
        speculate: bool = True,
        tenant: str = "default",
        resume_tokens=None,
        kind: str = "generate",
        n: int = 1,
        constraint=None,
    ) -> Request:
        """Validate and enqueue a request; returns the streaming handle.

        Raises :class:`ValueError` (bad prompt / context overflow),
        :class:`QueueFullError` (backpressure),
        :class:`TenantOverQuota` (the tenant's token-rate budget has no
        room), or :class:`EngineStopped` (shutting down) — all before
        any device work.
        """
        req = self._build_request(
            prompt, max_new_tokens, temperature=temperature,
            priority=priority, timeout=timeout, trace_id=trace_id,
            speculate=speculate, tenant=tenant,
            resume_tokens=resume_tokens, kind=kind, n=n,
            constraint=constraint)
        try:
            self.scheduler.submit(req)
        except ServingError:
            self.metrics.record_reject()
            raise
        self.metrics.record_request_kind(req.kind)
        return req

    def submit_many(self, specs) -> list:
        """Batched admission for the binary front door: every spec that
        arrived in one event-loop tick is validated and handed to the
        scheduler in ONE ``submit_many`` call (one clock read, one
        arrival wake-up). Returns a list aligned with ``specs``: a
        :class:`Request` per accepted entry, the typed exception
        (:class:`ServingError` or ``ValueError``-shaped bad input) per
        rejected one — different streams on one connection fail
        independently."""
        built: list = []
        for spec in specs:
            try:
                built.append(self._build_request(
                    spec["prompt"], spec["max_new_tokens"],
                    temperature=float(spec.get("temperature", 0.0)),
                    priority=int(spec.get("priority", 0)),
                    timeout=spec.get("timeout"),
                    trace_id=spec.get("trace_id"),
                    speculate=bool(spec.get("speculate", True)),
                    tenant=str(spec.get("tenant") or "default"),
                    resume_tokens=spec.get("resume_tokens"),
                    kind=str(spec.get("kind") or "generate"),
                    n=int(spec.get("n") or 1),
                    constraint=spec.get("constraint"),
                ))
            except (ServingError, KeyError, TypeError, ValueError) as e:
                built.append(e)
        reqs = [r for r in built if isinstance(r, Request)]
        outcomes = iter(self.scheduler.submit_many(reqs))
        out: list = []
        for r in built:
            if not isinstance(r, Request):
                self.metrics.record_reject()
                out.append(r)
                continue
            err = next(outcomes)
            if err is not None:
                self.metrics.record_reject()
                out.append(err)
            else:
                self.metrics.record_request_kind(r.kind)
                out.append(r)
        return out

    # -- lifecycle ----------------------------------------------------------
    def shutdown(self, drain: bool = True) -> None:
        """Stop admitting. ``drain=True`` finishes in-flight requests
        before :meth:`run` returns; ``drain=False`` errors them out."""
        self._stopping = True
        self._draining = drain
        if self.flight_recorder is not None:
            self.flight_recorder.record_event("shutdown", drain=drain)
        self.scheduler.kick()

    def request_param_swap(self, variables, provenance: dict | None = None):
        """Queue an in-place parameter swap (the replica half of the
        cluster's zero-downtime weight reload).

        ``provenance`` is the new weights' version stamp
        (``checkpoint.weights_provenance`` of the file being reloaded);
        it becomes the engine's :attr:`weight_version` when the swap
        lands, so every post-swap response names the new checkpoint.
        Without one (inline callers), the version is bumped by one with
        no digest — still distinguishable per swap.

        ``variables`` is either a full variables dict (``{"params": ...}``,
        the ``save_weights`` / ``checkpoint.save_weights_file`` layout) or
        a bare params pytree. Leaf shapes and dtypes must match the
        serving model exactly — a mismatched tree raises ``ValueError``
        HERE rather than retracing (or silently corrupting) the compiled
        decode step later.

        The swap itself runs inside the engine loop at the first
        iteration with **no slot in flight** (the loop serializes all
        device work, so there is no race against a decode or prefill in
        the executor): params are device_put, the KV pool's prefix trie is
        flushed (its K/V was computed under the OLD weights), and one
        decode tick rewarms the step — under an armed auditor that tick
        PROVES the swap did not retrace. Returns ``(event, result)``:
        await the event, then check ``result`` for ``"error"``. Under
        continuous direct load the engine may never go idle — the cluster
        router drains the replica first, which is what guarantees the
        swap runs; a standalone server relies on a quiet moment.
        """
        if self._pending_swap is not None:
            # Overwriting would strand the first caller's event forever
            # (a silent false "busy" after its full timeout) and drop one
            # weights file without a trace.
            raise RuntimeError("a parameter swap is already pending")
        tree = variables
        if isinstance(tree, dict) and "params" in tree:
            tree = tree["params"]
        new_leaves, _ = jax.tree.flatten(tree)
        if self._pp > 1:
            # The live per-stage list duplicates the tied embedding
            # (stage 0 + last); validate against the UNSPLIT abstract
            # template the ctor captured, and hand _swap_sync the whole
            # tree — it re-splits along the stage plan.
            cur_leaves, cur_def = self._swap_template
        else:
            cur_leaves, cur_def = jax.tree.flatten(self._params)
        if len(new_leaves) != len(cur_leaves):
            raise ValueError(
                f"reload weights have {len(new_leaves)} leaves; serving "
                f"model has {len(cur_leaves)}")
        for i, (a, b) in enumerate(zip(new_leaves, cur_leaves)):
            a = np.asarray(a) if np.isscalar(a) else a
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                raise ValueError(
                    f"reload weight leaf {i} is {a.dtype}{tuple(a.shape)}; "
                    f"serving model expects {b.dtype}{tuple(b.shape)}")
        # Re-hang the new leaves on the CURRENT treedef: dict vs FrozenDict
        # (or attr-ordering) differences between a weights file and the
        # live tree must not matter as long as the leaves line up.
        params = jax.tree.unflatten(cur_def, new_leaves)
        if provenance is None:
            provenance = {
                "version": int(self.weight_version.get("version") or 0) + 1,
                "digest": None,
            }
        else:
            provenance = _public_provenance(provenance)
        event: asyncio.Event = asyncio.Event()
        result: dict = {}
        self._pending_swap = (params, event, result, provenance)
        self.scheduler.kick()  # wake an idle run loop now
        return event, result

    def cancel_param_swap(self, event: asyncio.Event) -> bool:
        """Withdraw a pending swap (reload-verb timeout path). True if it
        was still pending; False if the loop already consumed it."""
        if self._pending_swap is not None and self._pending_swap[1] is event:
            self._pending_swap = None
            return True
        return False

    # -- KV block migration (serving/kv_transfer.py) -------------------------
    def request_kv_export(self, prompt):
        """Queue a KV block export: serialize the pool's longest
        complete-block chain for ``prompt`` (prefix trie hit — a slot
        that finished or preempted has ADOPTED its blocks there, so
        "export a slot's blocks" and "export a cached prefix" are one
        walk). Serviced by the run loop between iterations; returns
        ``(event, result)`` — await the event, then read ``result``
        (``payload`` bytes + ``matched_tokens``, or ``error``). Raises
        :class:`~distkeras_tpu.serving.kv_transfer.KVTransferError`
        immediately on a pp mesh."""
        from distkeras_tpu.serving.kv_transfer import KVTransferError

        if self._pp > 1:
            raise KVTransferError(
                "KV export is not supported on a pp mesh yet: the "
                "gather program spans the whole pool, which is "
                "stage-partitioned under pp")
        event: asyncio.Event = asyncio.Event()
        result: dict = {}
        self._pending_kv.append(("export", prompt, event, result))
        self.scheduler.kick()
        return event, result

    def request_kv_import(self, payload: bytes):
        """Queue a KV block import: validate a peer's KVX1 payload
        (geometry + weight provenance), adopt its block chain into the
        pool's trie, and upload the rows — after which an admission for
        the same prompt is a zero-copy prefix hit. Same ``(event,
        result)`` contract as :meth:`request_kv_export`; a pool-dry
        receiver adopts what fits (possibly nothing) and reports it in
        ``result`` rather than failing — import must only ever help."""
        from distkeras_tpu.serving.kv_transfer import KVTransferError

        if self._pp > 1:
            raise KVTransferError(
                "KV import is not supported on a pp mesh yet: the "
                "scatter program spans the whole pool, which is "
                "stage-partitioned under pp")
        event: asyncio.Event = asyncio.Event()
        result: dict = {}
        self._pending_kv.append(("import", payload, event, result))
        self.scheduler.kick()
        return event, result

    def _kv_export_sync(self, prompt) -> dict:
        """Executor-thread export: pin the chain, gather its pool rows,
        serialize. The pin only needs to span this call — the engine
        loop serializes every pool mutation."""
        from distkeras_tpu.serving.kv_transfer import (
            MAX_TOTAL_TRANSFER_BYTES,
            KVTransferError,
            serialize_blocks,
        )

        tokens = [int(t) for t in prompt]
        bt = self.kv_block_tokens
        match = self.kv_pool.match_blocks(tokens)
        try:
            n = len(match.ids)
            leaves = []
            if n:
                padded = self._pad_kv_ids(match.ids, fill=0)
                rows = self._kv_gather(self._cache, jnp.asarray(padded))
                leaves = [np.asarray(l)[:n] for l in jax.tree.leaves(rows)
                          if l.ndim > 1]
            # Tier-owner exports: continue the chain from the host/disk
            # tier where the device trie ends — an evicted-but-spilled
            # family stays exportable to the fleet (the directory's
            # owner contract), at zero device cost per tier block.
            n = self._extend_export_from_tier(tokens, n, leaves)
            if n == 0:
                return {"matched_tokens": 0, "blocks": 0, "payload": None}
            payload = serialize_blocks(
                tokens[:n * bt], leaves, block_tokens=bt,
                provenance=self.weight_version)
        finally:
            self.kv_pool.release(match)
        if len(payload) > MAX_TOTAL_TRANSFER_BYTES:
            # Oversize chains split across sequenced KVBLK frames on
            # the wire (kv_transfer.split_frames); only a chain past
            # the TOTAL cap is refused typed.
            raise KVTransferError(
                f"serialized blocks ({len(payload)} bytes) exceed the "
                f"transfer cap ({MAX_TOTAL_TRANSFER_BYTES}); receiver "
                f"falls back to monolithic prefill")
        self.metrics.record_kv_export(len(payload))
        return {"matched_tokens": n * self.kv_block_tokens, "blocks": n,
                "bytes": len(payload), "payload": payload}

    def _kv_import_sync(self, payload) -> dict:
        """Executor-thread import: validate geometry + provenance
        (typed rejects), adopt the chain, scatter the new rows."""
        from distkeras_tpu.serving.kv_transfer import (
            KVTransferError,
            deserialize_blocks,
        )

        header, leaves = deserialize_blocks(payload)
        if int(header["block_tokens"]) != self.kv_block_tokens:
            raise KVTransferError(
                f"block geometry mismatch: peer blocks hold "
                f"{header['block_tokens']} tokens, this pool "
                f"{self.kv_block_tokens}")
        mine = [l for l in jax.tree.leaves(self._cache) if l.ndim > 1]
        theirs = header.get("leaves", [])
        if len(theirs) != len(mine):
            raise KVTransferError(
                f"KV leaf count mismatch: payload has {len(theirs)}, "
                f"this pool {len(mine)}")
        for i, (meta, leaf) in enumerate(zip(theirs, mine)):
            # A block's tokens and a token's elements: a payload may
            # spell the latter [H, D] (a peer older than the flat pool
            # leaf) or [H*D]; the bytes and their order are the same.
            shape = [int(s) for s in meta["shape"]]
            want = (shape[1], int(np.prod(shape[2:])), str(meta["dtype"]))
            have = (leaf.shape[1], int(np.prod(leaf.shape[2:])),
                    np.dtype(leaf.dtype).name)
            if want != have:
                raise KVTransferError(
                    f"KV leaf {i} geometry mismatch: payload "
                    f"{want[2]}{shape[1:]}, this pool "
                    f"{have[2]}{list(leaf.shape[1:])}")
        prov = header.get("provenance") or {}
        mine_prov = self.weight_version
        if (int(prov.get("version") or 0), prov.get("digest")) != (
                int(mine_prov.get("version") or 0),
                mine_prov.get("digest")):
            # KV is a pure function of (weights, tokens): adopting
            # blocks computed under other weights would poison every
            # later hit. Typed reject; the caller prefills monolithic.
            raise KVTransferError(
                f"weight provenance mismatch: blocks computed under "
                f"v{prov.get('version')}/{prov.get('digest')}, serving "
                f"v{mine_prov.get('version')}/{mine_prov.get('digest')}")
        tokens = [int(t) for t in header.get("tokens", [])]
        n_blocks = int(header.get("n_blocks") or 0)
        uploads, resident = self.kv_pool.adopt_foreign(tokens, n_blocks)
        if uploads:
            idxs = [i for i, _ in uploads]
            rows = np.asarray([r for _, r in uploads], np.int32)
            padded = self._pad_kv_ids(rows, fill=self.kv_pool.capacity)
            b = len(padded)
            treedef = jax.tree.structure(self._cache)
            data_leaves = []
            src = iter(leaves)
            for leaf in jax.tree.leaves(self._cache):
                if leaf.ndim <= 1:
                    data_leaves.append(jnp.zeros((b, 0), leaf.dtype))
                    continue
                arr = _as_pool_rows(next(src)[idxs], leaf)
                if len(idxs) < b:  # pad to the pow2 bucket (dropped)
                    pad = np.zeros((b - len(idxs),) + arr.shape[1:],
                                   arr.dtype)
                    arr = np.concatenate([arr, pad], axis=0)
                data_leaves.append(jnp.asarray(arr))
            data = jax.tree.unflatten(treedef, data_leaves)
            self._cache = self._kv_scatter(self._cache, data,
                                           jnp.asarray(padded))
        return {"adopted_blocks": len(uploads),
                "resident_blocks": resident,
                "matched_tokens": resident * self.kv_block_tokens,
                "bytes": len(payload)}

    def _pad_kv_ids(self, ids, fill: int) -> np.ndarray:
        """Pow2-pad a pool row-id vector so the KV gather/scatter
        programs compile once per bucket (the pool's _pad_ids rule,
        applied to transfer ops)."""
        n = len(ids)
        b = 1
        while b < n:
            b *= 2
        out = np.full((b,), fill, np.int32)
        out[:n] = ids
        return out

    # -- tiered KV cache (serving/kv_tier.py) -------------------------------
    def _spill_block(self, chain_tokens, row: int) -> None:
        """Pool spill hook: serialize ONE eviction victim's pool row
        into the host tier as exact KVX1 bytes, keyed by its full
        root→block token chain. Runs inside ``KVBlockPool._alloc`` —
        always on the engine loop, or on the executor while the loop
        awaits it, and always after a pipeline barrier (growth with a
        tick in flight refuses an allocation that would spill:
        :meth:`_chain_tail_block`), so the gather cannot race a donated
        in-flight tick. The payload is the same
        serialization a peer transfer ships, so a spilled block is
        re-admittable locally AND exportable to the fleet."""
        tier = self.kv_tier
        if tier is None:
            return
        from distkeras_tpu.serving.kv_transfer import serialize_blocks

        t0 = time.monotonic()
        bt = self.kv_block_tokens
        padded = self._pad_kv_ids(np.asarray([row], np.int32), fill=0)
        rows = self._kv_gather(self._cache, jnp.asarray(padded))
        leaves = [np.asarray(l)[:1] for l in jax.tree.leaves(rows)
                  if l.ndim > 1]
        chain = [int(t) for t in chain_tokens]
        payload = serialize_blocks(chain[-bt:], leaves, block_tokens=bt,
                                   provenance=self.weight_version)
        if tier.put(chain, payload):
            self.metrics.record_kv_spill(
                len(payload), time.monotonic() - t0,
                trace_id=self._tier_trace_id)
            self.scheduler.note_kv_arrival()

    def _spill_blocks(self, victims) -> None:
        """Batched pool spill hook (``spill_many_hook``): serialize a
        whole allocation burst's eviction victims from ONE D2H gather —
        ``victims`` is the burst's ``(chain_tokens, row)`` list, rows
        still holding their KV bytes. The per-victim path gathers one
        pow2-padded row per eviction; a B-victim burst paid B gathers
        (each a full device round trip) where one batched gather over
        the padded row vector does — the exact shape of
        :meth:`_readmit_from_tier`'s one-scatter H2D side. Per-block
        spill latency is recorded as the burst's share, so the
        ``kv_tier_spill_seconds`` family directly shows the win."""
        tier = self.kv_tier
        if tier is None or not victims:
            return
        if len(victims) == 1:
            self._spill_block(*victims[0])
            return
        from distkeras_tpu.serving.kv_transfer import serialize_blocks

        t0 = time.monotonic()
        bt = self.kv_block_tokens
        n = len(victims)
        rows = np.asarray([int(r) for _, r in victims], np.int32)
        padded = self._pad_kv_ids(rows, fill=0)
        gathered = self._kv_gather(self._cache, jnp.asarray(padded))
        leaves = [np.asarray(l)[:n]
                  for l in jax.tree.leaves(gathered) if l.ndim > 1]
        stored: list[int] = []
        for i, (chain_tokens, _row) in enumerate(victims):
            chain = [int(t) for t in chain_tokens]
            payload = serialize_blocks(
                chain[-bt:], [l[i:i + 1] for l in leaves],
                block_tokens=bt, provenance=self.weight_version)
            if tier.put(chain, payload):
                stored.append(len(payload))
        per_block_s = (time.monotonic() - t0) / n
        for nbytes in stored:
            self.metrics.record_kv_spill(nbytes, per_block_s,
                                         trace_id=self._tier_trace_id)
        if stored:
            self.scheduler.note_kv_arrival()

    def _tier_provenance_ok(self, header) -> bool:
        prov = header.get("provenance") or {}
        mine = self.weight_version
        return (int(prov.get("version") or 0), prov.get("digest")) == (
            int(mine.get("version") or 0), mine.get("digest"))

    def _tier_rows(self, payload):
        """A host-tier payload's per-leaf block rows in the pool's own
        leaf shapes, or None where it cannot be adopted (truncated or
        corrupt, another block geometry, other weights): the caller
        stops its chain there and never raises."""
        from distkeras_tpu.serving.kv_transfer import deserialize_blocks

        mine = [l for l in jax.tree.leaves(self._cache) if l.ndim > 1]
        try:
            header, leaves = deserialize_blocks(payload)
            if (int(header.get("block_tokens") or 0) != self.kv_block_tokens
                    or len(leaves) != len(mine)
                    or not self._tier_provenance_ok(header)):
                return None
            return [_as_pool_rows(l, m) for l, m in zip(leaves, mine)]
        except Exception:
            return None

    def _readmit_from_tier(self, tokens, trace_id: str | None = None) -> int:
        """Extend the device trie along ``tokens`` from the host tier:
        for each complete block past the device-resident prefix, fetch
        its KVX1 payload, adopt a pool row (never preempting — adoption
        only reclaims unreferenced leaves), and H2D-scatter the bytes
        in ONE batched call. Runs on the loop thread during admission,
        after the pipeline barrier, BEFORE the trie match — so the
        re-admitted blocks count as the prefix hits they are. Returns
        the number of blocks re-admitted."""
        tier, pool = self.kv_tier, self.kv_pool
        if tier is None:
            return 0
        bt = self.kv_block_tokens
        toks = [int(t) for t in tokens]
        resident = self._tier_next_block(toks)
        if resident is None:
            return 0
        cap = (len(toks) - 1) // bt
        t0 = time.monotonic()
        staged: list[tuple[int, list]] = []  # (pool_row, per-leaf [1,bt,..])
        nbytes = 0
        k = resident
        while k < cap:
            chain = toks[:(k + 1) * bt]
            payload = tier.get(chain)
            leaves = None if payload is None else self._tier_rows(payload)
            if leaves is None:
                break
            # adopt_foreign re-walks the chain from the root: resident
            # prefix blocks are touched, block k gets a fresh row (or
            # none when the pool is dry — stop there, what fit is
            # already a win).
            uploads, res = pool.adopt_foreign(chain, k + 1)
            if not uploads:
                break
            staged.append((uploads[0][1], leaves))
            nbytes += len(payload)
            k += 1
        if not staged:
            return 0
        rows = np.asarray([r for r, _ in staged], np.int32)
        padded = self._pad_kv_ids(rows, fill=self.kv_pool.capacity)
        b = len(padded)
        treedef = jax.tree.structure(self._cache)
        data_leaves, li = [], 0
        for leaf in jax.tree.leaves(self._cache):
            if leaf.ndim <= 1:
                data_leaves.append(jnp.zeros((b, 0), leaf.dtype))
                continue
            arr = np.concatenate([blk[li] for _, blk in staged], axis=0)
            if len(staged) < b:  # pad to the pow2 bucket (dropped)
                pad = np.zeros((b - len(staged),) + arr.shape[1:],
                               arr.dtype)
                arr = np.concatenate([arr, pad], axis=0)
            data_leaves.append(jnp.asarray(arr))
            li += 1
        data = jax.tree.unflatten(treedef, data_leaves)
        self._cache = self._kv_scatter(self._cache, data,
                                       jnp.asarray(padded))
        self.metrics.record_kv_readmit(len(staged), nbytes,
                                       time.monotonic() - t0,
                                       trace_id=trace_id)
        self.scheduler.note_kv_arrival()
        return len(staged)

    def _extend_export_from_tier(self, tokens, n: int, leaves: list) -> int:
        """Continue an export chain past the device-resident prefix
        using host-tier payloads: deserialize each contiguous tier
        block and append its leaf rows to ``leaves`` (in place).
        Returns the new block count. Export has NO last-block holdback
        (mirrors ``match_blocks``): a peer adopting the chain wants the
        full spilled prefix."""
        tier = self.kv_tier
        if tier is None:
            return n
        bt = self.kv_block_tokens
        n_total = len(tokens) // bt
        extras, k = [], n
        while k < n_total:
            payload = tier.get(tokens[:(k + 1) * bt])
            lv = None if payload is None else self._tier_rows(payload)
            if lv is None:
                break
            extras.append(lv)
            k += 1
        if not extras:
            return n
        if not leaves:
            leaves.extend(
                np.concatenate([e[i] for e in extras], axis=0)
                for i in range(len(extras[0])))
        else:
            for i in range(len(leaves)):
                leaves[i] = np.concatenate(
                    [leaves[i]] + [e[i] for e in extras], axis=0)
        return k

    def _tier_pending(self, req) -> bool:
        """True when a parked request's next uncovered block sits in
        the host tier (or a peer import is queued) — i.e. waiting on a
        tier arrival, not on a slot to free."""
        if self.kv_tier is None:
            return bool(self._pending_kv)
        if self._pending_kv:
            return True
        toks = [int(t) for t in req.prompt]
        bt = self.kv_block_tokens
        resident = self.kv_pool.probe(toks) // bt
        return self.kv_tier.contains(toks[:(resident + 1) * bt])

    async def wait_for_kv(self, tokens, timeout_s: float) -> bool:
        """Await KV residency for ``tokens``' first block in ANY local
        tier (device pool or host tier) — the decode-side wait behind a
        router-scheduled push (``kv_wait``): instead of pulling at
        admission, the server parks the request here until the pushed
        bytes land (the import path fires the scheduler's tier-arrival
        event). Returns True when resident, False on timeout (caller
        pulls or re-prefills — counted fallbacks, never errors)."""
        toks = [int(t) for t in tokens]
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s
        while True:
            if self.kv_pool.probe(toks) > 0 or (
                    self.kv_tier is not None
                    and self.kv_tier.probe(toks) > 0):
                return True
            remaining = deadline - loop.time()
            if remaining <= 0:
                return False
            await self.scheduler.wait_for_kv_arrival(remaining)

    def _swap_sync(self, params) -> None:
        """Executor-thread half of the swap: transfer, flush, rewarm.

        Sharded engines place the candidate SHARD-THEN-PLACE: each host
        leaf is sliced straight into its mesh layout, so a rolling
        weight update to a tp-sharded replica transfers bytes/tp per
        device and never materializes a replicated copy per device —
        the arXiv:2004.13336 move applied to weight rollout."""
        from distkeras_tpu.parallel.gspmd import place_sharded

        if self._pp > 1:
            # Per-stage shard-then-place: the full host tree is split
            # along the stage plan and each stage's subtree is sliced
            # straight into ITS devices' layouts — a rolling update to
            # a tp×pp replica transfers bytes/(tp·pp) per device.
            params = [
                place_sharded(part, sh)
                for part, sh in zip(self._stage_plan.split_params(params),
                                    self._param_shardings)]
        else:
            params = place_sharded(params, self._param_shardings)
        jax.block_until_ready(params)
        self._params = params
        # Pooled K/V is a pure function of (weights, tokens): stale
        # weights make every cached block wrong, so the whole trie is
        # invalidated in one stroke. Safe for the same reason the swap
        # itself is: zero active slots means zero slot-owned blocks, so
        # the flush only drops (now-wrong) trie entries. flush()
        # bypasses _alloc, so no spill fires — old-weight blocks never
        # reach the host tier.
        self.kv_pool.flush()
        if self.kv_tier is not None:
            # The host/disk tiers hold serialized KV from the OLD
            # weights — same purity argument, one stroke.
            self.kv_tier.flush()
        # Rewarm: one decode tick over the (all-free) batch. Garbage
        # output, real proof — the compiled decode step runs against the
        # new params, so an armed auditor raises here if the swap somehow
        # changed an aval, and the first real request pays no first-touch
        # latency.
        self._decode_sync()

    def reopen(self) -> None:
        """Re-arm admission after a drain shutdown. The compiled programs
        and slot caches persist, so a bench can run several load phases on
        one engine without re-paying compilation."""
        if self._running:
            raise RuntimeError("cannot reopen while run() is active")
        self._stopping = False
        self._draining = True
        self.scheduler.reset_loop_state()

    async def run(self, idle_poll_s: float = 0.05) -> None:
        """Main loop: expire, admit, decode, stream — until shutdown."""
        if self._running:
            raise RuntimeError("engine.run() is already active")
        self._running = True
        if self.flight_recorder is not None:
            self.flight_recorder.record_event("engine_start",
                                              slots=self.slots)
        loop = asyncio.get_running_loop()
        try:
            while True:
                now = time.monotonic()
                # 1. Shed queued requests that died waiting: deadline
                # passed, or caller cancelled (client disconnect).
                for req in self.scheduler.expire(now):
                    if req.cancelled:
                        self._finish_error(req, RequestCancelled(
                            "cancelled while queued"))
                    else:
                        self.metrics.record_expire()
                        self._finish_error(req, RequestTimeout(
                            f"deadline exceeded after {req.timeout}s in queue"))
                # 2. Free active slots whose request died mid-decode.
                # Teardown changes batch content — a pipeline barrier
                # first, so no in-flight tick is reading the blocks the
                # teardown releases. (The barrier may FINISH some of the
                # candidates; re-check before tearing down.)
                dead = [i for i, st in enumerate(self._slot_state)
                        if st is not None
                        and (st.request.cancelled
                             or (st.request.deadline is not None
                                 and now > st.request.deadline))]
                if dead:
                    await self._pipeline_barrier(loop, "cancel_expire")
                for i in dead:
                    st = self._slot_state[i]
                    if st is None:
                        continue  # finished at the barrier
                    dl = st.request.deadline
                    if st.request.cancelled:
                        self._finish_error(st.request, RequestCancelled(
                            f"cancelled with {st.remaining} tokens undecoded"))
                        self._free_slot(i, st)
                        self._slot_state[i] = None
                    elif dl is not None and now > dl:
                        self.metrics.record_expire()
                        self._finish_error(st.request, RequestTimeout(
                            f"deadline exceeded after {st.request.timeout}s "
                            f"with {st.remaining} tokens undecoded"))
                        self._free_slot(i, st)
                        self._slot_state[i] = None
                # 3. Shutdown: flush the queue with typed errors.
                if self._stopping:
                    for req in self.scheduler.drain():
                        self._finish_error(
                            req, EngineStopped("engine shut down while queued"))
                # 3b. Pending parameter swap: runs only when NO slot is
                # in flight AND no queued request has streamed tokens (a
                # preempted-and-requeued resume must finish under the
                # weights that produced its streamed prefix — in-flight
                # requests finish under the weights they started with;
                # the cluster router guarantees this by draining the
                # replica first). Before admission, so a queued request
                # never splices old-weight prefix blocks.
                if (self._pending_swap is not None
                        and self.active_slots == 0
                        and not self.scheduler.has_streamed()):
                    # Zero ACTIVE slots can still mean one in-flight
                    # tick (the speculative tick dispatched before its
                    # rows' finishes were known): a swap waits for zero
                    # in-flight ticks, full stop.
                    await self._pipeline_barrier(loop, "param_swap")
                    params, ev, res, prov = self._pending_swap
                    self._pending_swap = None
                    if self.flight_recorder is not None:
                        self.flight_recorder.record_event(
                            "param_swap",
                            version=prov.get("version"),
                            digest=prov.get("digest"))
                    with span("param_swap"):
                        try:
                            await self._in_executor(
                                loop, self._swap_sync, params)
                            self.weight_version = prov
                            self.metrics.set_weight_version(prov)
                            res["ok"] = True
                            res["weight_version"] = prov
                        except Exception as e:
                            res["error"] = e
                        finally:
                            if not res:
                                # BaseException (task cancelled mid-
                                # swap): resolve the waiter before it
                                # propagates, or the reload verb hangs
                                # its full timeout.
                                res["error"] = ServingError(
                                    "engine died mid-swap")
                            ev.set()
                # 3c. KV block transfers (export to / import from a
                # peer replica): serviced between iterations, so the
                # gather/scatter can never race a decode step's donated
                # cache buffers. Device work in the executor, event
                # resolution on the loop thread.
                if self._pending_kv:
                    # Barrier: the export gather / import scatter must
                    # never interleave with a tick that is mid-flight
                    # over the same pool rows.
                    await self._pipeline_barrier(loop, "kv_transfer")
                    ops, self._pending_kv = self._pending_kv, []
                    for kind, arg, ev, res in ops:
                        with span("kv_transfer", kind=kind):
                            try:
                                res.update(await self._in_executor(
                                    loop,
                                    (self._kv_export_sync
                                     if kind == "export"
                                     else self._kv_import_sync), arg))
                            except Exception as e:
                                res["error"] = e
                            finally:
                                ev.set()
                    # Imported blocks ARE a tier arrival: wake any
                    # tier-pending parked admission (and a decode-side
                    # wait_for_kv behind a router-scheduled push).
                    self.scheduler.note_kv_arrival()
                # 4. Admission: prefill queued requests into free slots.
                # Device work runs in the executor; stream/metrics
                # bookkeeping stays on the loop thread (asyncio queues and
                # events are not thread-safe). An admission is a
                # DISPATCH: its reservation is host bookkeeping, its
                # prefill writes only blocks the reservation just gave
                # this slot (no live row's table names them, and the
                # tick in flight holds its own device copy of the masked
                # tables, where the slot is the sentinel), programs run
                # on the device in dispatch order, and the next tick's
                # dispatch re-uploads tables and positions. So the tick
                # in flight stays there and the first token is read at
                # the next harvest (_resolve_admissions). The barrier is
                # kept for what needs harvested state, decided per
                # request from what the loop can observe
                # (_admits_in_flight, and _reserve_blocks(in_flight=True)
                # for a pool that would preempt, park, spill or re-admit
                # from the host tier). A parked queue head (dry pool,
                # nothing freed since) admits nobody and must not drain
                # the pipeline every iteration: the check comes first.
                if not self._stopping:
                    while self.free_slots and len(self.scheduler):
                        if (self._parked_at_version
                                == self.kv_pool.version
                                and self.scheduler.peek()
                                is self._parked_req):
                            # The queue head is parked on a dry pool and
                            # nothing has freed since — re-matching it
                            # every iteration would only burn host time.
                            # The head check keeps the park from gating
                            # ANYONE ELSE: a higher-priority arrival
                            # (which may preempt its way in) or the
                            # parked request expiring/cancelling changes
                            # the head and reopens admission without
                            # waiting for the pool version to move.
                            break
                        # Fresh clock per pop: an earlier admission's
                        # prefill may have taken long enough that more
                        # queued deadlines expired — a stale `now` would
                        # admit (and fully prefill) an already-dead
                        # request.
                        req = self.scheduler.pop(time.monotonic())
                        if req is None:
                            break
                        in_flight = self._admits_in_flight(req)
                        if not in_flight:
                            # The barrier may free MORE slots (a
                            # finishing tick), which only helps.
                            await self._pipeline_barrier(loop, "admission")
                        if (req.kind == "sample"
                                and self.free_slots < req.n):
                            # Fork fan-out needs all n slots claimed UP
                            # FRONT (a later admission must not steal a
                            # child's slot mid-prefill): requeue at the
                            # class head until n slots are free.
                            self.scheduler.requeue(req)
                            break
                        slot = self._slot_state.index(None)
                        reserved = self._reserve_blocks(req, slot, in_flight)
                        if reserved is _NEEDS_BARRIER:
                            in_flight = False
                            await self._pipeline_barrier(loop, "admission")
                            slot = self._slot_state.index(None)
                            reserved = self._reserve_blocks(req, slot)
                        if reserved is None:
                            # Parked: requeued at its class head,
                            # admission resumes when blocks free.
                            break
                        self._parked_at_version = None
                        self._parked_req = None
                        # ADMISSION WAIT ends HERE (slot granted); the
                        # PREFILL DEVICE TIME is recorded separately when
                        # the prefill completes (record_prefill). The two
                        # series — plus chunk-interleave wait in chunked
                        # mode — make up TTFT, so an operator can tell
                        # queueing delay from prefill cost.
                        wait = time.monotonic() - req.t_submit
                        self.metrics.record_admit(wait)
                        # Wide-event columns (unconditional: the done-
                        # time record needs them with tracing off). The
                        # FIRST admission's wait is the queue wait; a
                        # re-admission after preemption keeps it.
                        if req.queue_wait_s is None:
                            req.queue_wait_s = wait
                            req.admit_iteration = self.metrics.iterations
                        # Provenance stamp, FIRST admission only: swaps
                        # run at zero active slots and never while a
                        # preempted resume is queued, so the first stamp
                        # IS completion-time provenance; a re-admission
                        # after preemption must keep the stamp its
                        # streamed prefix was served under.
                        if req.weight_version is None:
                            req.weight_version = self.weight_version
                        if req.trace is not None:
                            req.trace.data["weight_version"] = (
                                req.weight_version)
                            # Rendered as a slice ENDING here: the queue
                            # wait lane segment between submit and admit.
                            req.trace.event("admit", slot=slot,
                                            dur_s=round(wait, 9))
                            req.trace.data["queue_wait_s"] = round(wait, 9)
                            req.trace.data["admit_iteration"] = (
                                self.metrics.iterations)
                        if self.flight_recorder is not None:
                            self.flight_recorder.record_event(
                                "admit", trace_id=req.trace_id, slot=slot)
                        now_t = time.monotonic()
                        # Resume-aware: a preempted request re-admits
                        # with its already-streamed tokens folded into
                        # the prefill, so only the UNdecoded remainder
                        # is owed.
                        st = _SlotState(
                            req,
                            req.max_new_tokens - len(req.out_tokens),
                            now_t, t_admit=now_t, drained=not in_flight)
                        self.metrics.record_admission(st.drained)
                        (st.prefill, st.blocks, st.first_block,
                         st.match) = reserved
                        if req.constraint is not None:
                            st.dfa = req.constraint
                            st.dfa_state = req.constraint.start
                        self._slot_state[slot] = st
                        if req.kind == "sample":
                            # Claim the n-1 child slots NOW (fork_wait:
                            # parked out of the decodable set until the
                            # parent prefill fans out).
                            st.fork_idx = 0
                            req.fork_completions = [None] * req.n
                            for _ in range(req.n - 1):
                                c = self._slot_state.index(None)
                                self._slot_state[c] = _SlotState(
                                    req, st.remaining, now_t,
                                    t_admit=now_t, fork_wait=True)
                        with span("admit", slot=slot,
                                  trace_id=req.trace_id,
                                  prompt_len=len(req.prompt),
                                  queue_wait_s=round(wait, 6),
                                  drained=int(st.drained)):
                            # (The reservation above already pinned the
                            # prompt's cached prefix and pointed the
                            # slot's table at it: a hit skips that
                            # prefix's prefill compute entirely.)
                            if self._chunk is None:
                                # Monolithic prefill: the whole uncached
                                # tail, admitted inline. Normally ONE
                                # call; near-context-limit prompts may
                                # split into a few pow2 sub-chunks (see
                                # _prefill_step's overshoot guard). Off
                                # the barrier route none of them waits
                                # for its token: with nothing decodable
                                # (a first wave) everything queued is
                                # enqueued in this one iteration.
                                tok0 = None
                                while tok0 is None:
                                    tok0 = await self._in_executor(
                                        loop, self._prefill_step, st, slot,
                                        in_flight)
                                self._route_admission(st, slot, tok0)
                # 4b. Chunked prefill: ONE chunk per iteration TOTAL,
                # round-robin across prefilling slots, interleaved with
                # the decode tick below — the decode batch never stalls
                # for more than a single chunk's device time no matter
                # how many prompts are admitting at once (concurrent
                # admissions stretch each other's TTFT instead). Runs
                # during drain shutdown too (a half-prefilled slot must
                # finish for run() to exit).
                if self._chunk is not None:
                    pending = [i for i, st in enumerate(self._slot_state)
                               if st is not None and st.prefill is not None]
                    if pending:
                        start = self._prefill_rr
                        i = min(pending,
                                key=lambda s: (s - start) % self.slots)
                        self._prefill_rr = (i + 1) % self.slots
                        st = self._slot_state[i]
                        if st.drained:
                            # This slot's chunks need settled state (its
                            # kind reads logits back on the host, or its
                            # reservation preempted): barrier before the
                            # chunk runs, as every chunk did until PR 33.
                            await self._pipeline_barrier(
                                loop, "prefill_chunk")
                        # Otherwise the chunk is enqueued behind the tick
                        # in flight, whose harvest paces the loop. With
                        # NO tick in flight (every row mid-prefill: a
                        # first wave) nothing would, and the loop would
                        # queue every chunk of every prompt at once: the
                        # chunk's own token is waited for instead.
                        with span("prefill_tick", slot=i,
                                  offset=st.prefill.pos,
                                  drained=int(st.drained)):
                            tok0 = await self._in_executor(
                                loop, self._prefill_step, st, i,
                                not st.drained and bool(self._inflight))
                        if tok0 is not None:
                            self._route_admission(st, i, tok0)
                # 5. Nothing active? Flush the pipeline (an in-flight
                # tick whose every row finished leaves active == 0 with
                # a garbage tick still pending) and wait.
                if self.active_slots == 0:
                    await self._pipeline_barrier(loop, "idle")
                    if self._stopping:
                        break
                    if (self._parked_req is not None
                            and self._parked_at_version
                            == self.kv_pool.version
                            and self.scheduler.peek() is self._parked_req):
                        # Fully parked: the queue head is waiting on a
                        # dry pool and NOTHING is running that could
                        # free blocks — only an arrival, a cancel/kick,
                        # or a pool-version move (a KV import kicks) can
                        # change the picture. wait_for_request would
                        # return immediately on the non-empty queue and
                        # hot-spin the loop doing only the park check;
                        # wait on the arrival event itself instead (the
                        # timeout keeps deadline expiry responsive).
                        # Tier-pending heads (next uncovered block in
                        # the host tier, or a peer import queued) wait
                        # on the TIER-arrival event: the arrival wakes
                        # them immediately instead of them re-checking
                        # pool.version once per idle poll.
                        if self._tier_pending(self._parked_req):
                            wait = self.scheduler.wait_for_kv_arrival
                        else:
                            wait = self.scheduler.wait_for_wake
                    else:
                        wait = self.scheduler.wait_for_request
                    with span("loop_idle"):
                        await wait(idle_poll_s)
                    continue
                if self._stopping and not self._draining:
                    await self._pipeline_barrier(loop, "shutdown")
                    for i, st in enumerate(self._slot_state):
                        if st is not None:
                            self._finish_error(st.request, EngineStopped(
                                "engine shut down mid-decode"))
                            self._free_slot(i, st)
                            self._slot_state[i] = None
                    break
                # 5c. Paged growth: before the tick, every decoding slot
                # whose next write position crosses into an unallocated
                # block chains one more from the pool. Host bookkeeping
                # only; the decode step itself never changes shape. With
                # many rows some row is at a boundary on nearly every
                # tick (64 rows on 16-token blocks: four a tick), so
                # growth must not cost the overlap:
                #   (a) a block the pool hands out with no victim and no
                #       device work is chained with the tick IN FLIGHT.
                #       That tick holds its own device copy of the
                #       tables and positions, the next dispatch
                #       re-uploads both from host state the dispatch
                #       already advanced, and no live row's table names
                #       a block alloc() can return;
                #   (b) only for the rows a dry or spilling pool refused
                #       (_chain_tail_block says which case is which) is
                #       the barrier kept: preemption, a wedged row's
                #       error and a spill all need harvested state.
                dry = self._grow_in_flight()
                if dry:
                    await self._pipeline_barrier(loop, "paged_growth")
                    self._grow_drained(dry)
                # 6. One decode iteration for the whole batch — skipped
                # while EVERY active slot is still mid-prefill (the whole
                # tick's output would be discarded; the chunk in 4b was
                # this iteration's useful device work). With a draft
                # model, the tick is SPECULATIVE whenever any live row is
                # eligible (greedy + not opted out): draft K, verify
                # once, commit per-row accept prefixes — sampled rows in
                # the same batch commit their usual one token from the
                # verify's position-0 logits. All-sampled batches (and
                # the swap rewarm) take the one-token fallback step.
                rows = await self._tick_step(loop)
                if self.inject_decode_delay_s > 0:
                    # Injected fault (SLO bench): stretch the host side
                    # of every iteration so observed latencies genuinely
                    # breach — never a synthetic metric write.
                    await asyncio.sleep(self.inject_decode_delay_s)
                self.metrics.sample(
                    len(self.scheduler), self.active_slots, self.slots,
                    rows_decoded=rows)
                self.kv_pool.note_decode_tick()
                # Yield so the server can read sockets between iterations.
                await asyncio.sleep(0)
        except BaseException as e:
            # A device failure — or the embedder cancelling the run()
            # task directly (CancelledError is a BaseException) — must
            # not strand clients: every in-flight and queued request gets
            # a terminal error event before the exception propagates
            # (otherwise server handlers block forever on streams nothing
            # will ever finish).
            err = ServingError(f"engine failure: {e!r}")
            # Abandon any in-flight ticks: their device buffers are
            # dropped with the references; nothing host-side depends on
            # their results once every request below is errored out.
            self._inflight.clear()
            self._pending_admits.clear()
            for i, st in enumerate(self._slot_state):
                if st is not None:
                    self._finish_error(st.request, err)
                    # Crash path: free only (no adoption) — keep the
                    # last-words path as simple as possible.
                    self._free_slot(i, st, adopt=False)
                    self._slot_state[i] = None
            for req in self.scheduler.drain():
                self._finish_error(req, err)
            # A pending param swap must resolve too, or the reload verb
            # blocks its full timeout and reports "busy" for an engine
            # that is in fact dead.
            if self._pending_swap is not None:
                _, ev, res, _ = self._pending_swap
                self._pending_swap = None
                res["error"] = err
                ev.set()
            # Same for pending KV transfers: a peer awaiting an export
            # must get its typed failure now, not a hung timeout.
            if self._pending_kv:
                ops, self._pending_kv = self._pending_kv, []
                for _, _, ev, res in ops:
                    res["error"] = err
                    ev.set()
            self._stopping = True
            # Last words: the black box hits disk BEFORE the exception
            # propagates — a chaos-killed (task-cancelled) or device-
            # failed replica leaves its final state for the supervisor.
            if self.flight_recorder is not None:
                self.flight_recorder.crash_dump(error=repr(e))
            raise
        finally:
            self._running = False

    # -- decode pipeline ----------------------------------------------------
    async def _tick_step(self, loop) -> int:
        """One decode (or speculative) tick, pipelined; returns how many
        rows the tick it dispatched decodes (0: none). Plain → plain is
        the fully overlapped path: dispatch tick N+1 FIRST, then harvest
        and stream tick N while N+1 executes — the host bookkeeping for
        N (token pushes, teardown, metrics) plus the whole next loop
        iteration's steps 1–4 and the event-loop turn hide behind N+1's
        device time. A speculative tick (or ``pipeline_depth=0``)
        harvests before the next dispatch, because the next tick's
        position state depends on the commit counts only the harvest
        knows."""
        decodable = self._decodable()
        if not decodable:
            if self._inflight:
                # Every dispatched row disappeared (cancel barrier tore
                # them down before the harvest): flush so the stale
                # handles don't pin device buffers.
                await self._pipeline_barrier(loop, "idle")
            return 0

        def want_spec() -> bool:
            # A zero-accept row (every draft rejected last spec tick)
            # committed nothing; one interleaved fallback tick
            # guarantees it a token before speculation resumes —
            # re-speculating immediately would redraft the same
            # rejected proposal forever.
            return (self._spec
                    and not self._spec_owe_fallback
                    and any(
                        self._slot_state[i].request.temperature <= 0
                        and self._slot_state[i].request.speculate
                        for i in decodable))

        spec_tick = want_spec()
        constrained_live = (self._constrained_mode and any(
            self._slot_state[i].dfa is not None for i in decodable))
        if self._inflight and (
                spec_tick or constrained_live
                or any(t.kind == "spec"
                       for t in self._inflight)):
            # Either the NEXT tick needs settled commit state (it is
            # speculative), or an in-flight one is speculative (its
            # commits gate every later dispatch). Harvest, then
            # re-evaluate: the stream may have finished rows or flipped
            # the owe-fallback state.
            await self._pipeline_barrier(loop, "spec")
            decodable = self._decodable()
            if not decodable:
                return 0
            spec_tick = want_spec()
        if spec_tick:
            for i in decodable:
                req = self._slot_state[i].request
                # Lookahead only for rows that will actually
                # speculate — a sampled or opted-out row writes one
                # real token per tick and needs no window blocks.
                # (_alloc_lookahead never preempts, so no barrier.)
                if req.temperature <= 0 and req.speculate:
                    self._alloc_lookahead(i)
            with span("spec_tick", active=self.active_slots,
                      k=self.spec_k):
                self._inflight.append(await self._dispatch(
                    loop, self._spec_dispatch))
        else:
            with span("decode_tick", active=self.active_slots):
                self._inflight.append(await self._dispatch(
                    loop, self._decode_dispatch))
            # Harvest the oldest tick(s) past the in-flight window,
            # with the newest already on the device: the one D2H waits
            # for the oldest only; everything after it overlaps the
            # later ticks. Depth<=1 keeps at most ONE tick in flight
            # (the PR-14 overlap); depth>1 on a pp mesh keeps up to
            # ``depth`` micro-batch ticks flowing through the stages.
            while len(self._inflight) > max(1, self.pipeline_depth):
                await self._complete_tick(loop, self._inflight.popleft())
            # Admissions enqueued on an EMPTY pipeline (a first wave, a
            # request into an idle engine) met no harvest above: their
            # first tokens are read now, with their rows' first tick
            # already queued behind the prefills.
            await self._resolve_admissions(loop)
        if self._arm_after_warmup and self.auditor is not None:
            # The first dispatch IS the warmup: compilation is
            # synchronous at the jit call (only execution is async), so
            # every executable exists now (the ctor pre-compiled the
            # spec trio) and every later compile is a violated
            # invariant.
            self._arm_after_warmup = False
            self.auditor.arm(*self._decode_audit_names)
        rows = len(self._inflight[-1].rows)
        if self.pipeline_depth == 0:
            await self._pipeline_barrier(loop, "depth0")
        return rows

    async def _dispatch(self, loop, fn) -> _InflightTick:
        """Run one tick dispatch. The first ever goes to the executor
        (it compiles — seconds the event loop must stay responsive
        through); warm dispatches run inline on the loop thread, where
        their only cost is arg prep + the async enqueue — saving the
        executor round trip that would otherwise serialize every tick
        behind a thread hop."""
        if self._dispatch_warm:
            return fn()
        tick = await self._in_executor(loop, fn)
        self._dispatch_warm = True
        return tick

    async def _pipeline_barrier(self, loop, reason: str) -> None:
        """Drain the pipeline: harvest, stream, and tear down the
        in-flight tick (if any). Called before every event that mutates
        batch shape or content — admission, chunked-prefill progress,
        paged growth that must preempt or spill (NOT growth the pool
        serves from its free list: :meth:`_chain_tail_block`), param
        swap, KV transfer, cancel/expire teardown, idle, shutdown — and
        as the depth-0 serializer. Under
        depth>1 this drains ALL stages' in-flight micro-batch ticks,
        oldest first. ``reason`` is one of ``BARRIER_REASONS``; a barrier
        that found a tick to drain (the overlap was lost) is counted under
        it and is a ``barrier`` span, one that found none costs nothing.
        Either way no admission's first token is left unread behind it
        (each harvest reads them; with no tick in flight they are read
        here), so a teardown that follows sees every row's true state."""
        if not self._inflight:
            await self._resolve_admissions(loop)
            return
        self.metrics.record_barrier(reason)
        with span("barrier", reason=reason):
            while self._inflight:
                await self._complete_tick(loop, self._inflight.popleft())

    async def _resolve_admissions(self, loop) -> None:
        """Read the first tokens of the admissions whose prefill was
        enqueued without waiting (one device-to-host copy for all of
        them) and do their host half: stream the token, book the
        prefill, and tear the row down if that token was all it was
        owed. The prefills ran behind whatever tick was in flight when
        they were enqueued, so this is called once that tick has been
        harvested and streamed; a read that would still block takes the
        executor hop, like a tick's harvest."""
        if not self._pending_admits:
            return
        pending, self._pending_admits = self._pending_admits, []
        toks = [p.tok for p in pending]
        hg = self.metrics.host_gap
        ready = all(_is_ready(t) for t in toks)
        with span("harvest", kind="admit", ready=ready):
            hg.harvest_started()
            vals = (jax.device_get(toks) if ready
                    else await self._in_executor(loop, jax.device_get, toks))
            t = time.monotonic()
        for p, tok0 in zip(pending, vals):
            hg.harvest_ended()
            # What the series can honestly hold off the barrier route:
            # the host's dispatch time of every chunk, plus the wait
            # from the last dispatch to this read (device time of the
            # last chunk and of whatever ran before it).
            p.job.device_s += t - p.t_enqueued
            self._book_prefill(p.st.request, p.job, p.prompt_len)
            self._finish_admission(p.st, p.slot, int(tok0))

    async def _complete_tick(self, loop, tick: _InflightTick) -> None:
        """Harvest one dispatched tick and do its host half: stream the
        committed tokens of every row that was decodable at dispatch and
        is still alive, then tear down rows that finished. A row whose
        slot emptied between dispatch and harvest (a finish processed
        while the next tick was already in flight) is dropped exactly
        like a mid-prefill garbage row; so is one whose slot an admission
        has refilled since. Last, the first tokens of the admissions
        enqueued behind this tick are read: their prefills ran while the
        host streamed."""
        # Readiness fast path: when the device already finished the
        # tick (the pipelined steady state — the whole host iteration
        # ran while it computed), the harvest is a ready-buffer memcpy
        # and the executor round trip would cost more than the read.
        # Only a harvest that would genuinely BLOCK takes the thread
        # hop, keeping the event loop responsive through real waits.
        harvest = (self._harvest_spec if tick.kind == "spec"
                   else self._harvest_decode)
        ready = _tick_ready(tick)
        with span("harvest", kind=tick.kind, ready=ready):
            got = (harvest(tick) if ready
                   else await self._in_executor(loop, harvest, tick))
        if tick.kind == "spec":
            out, commit, caps = got
            self._spec_owe_fallback = any(
                int(commit[i]) == 0 for i in tick.rows
                if self._slot_state[i] is not None)
        else:
            nxt = got
            self._spec_owe_fallback = False
        t = time.monotonic()
        with span("stream", active=self.active_slots):
            for i, owner in zip(tick.rows, tick.states):
                st = self._slot_state[i]
                if st is not owner:
                    # The slot emptied (or was refilled by a new
                    # admission) since dispatch: this tick's row output
                    # is speculative garbage.
                    continue
                if tick.kind == "spec":
                    self._stream_spec(st, out[i], int(commit[i]),
                                      int(caps[i]), t)
                else:
                    self._push_token(st, int(nxt[i - tick.mb_start]), t)
                if st.remaining == 0:
                    self._retire_row(i, st)
        await self._resolve_admissions(loop)

    def _retire_row(self, i: int, st: _SlotState) -> None:
        """A row has streamed its last token: finish its request and
        free the slot. Still-dispatched later tick(s) optimistically
        advanced this slot's watermark; the request is finished, so
        roll every such advance back BEFORE adoption — the trie must
        never claim an in-flight speculative write; its block is freed
        instead. Growth may hand that block to another row for the NEXT
        tick with no barrier between (step 5c), and so may an admission
        (step 4), and the stale write is still harmless: programs run
        on the device in dispatch order, so it lands first; the new
        owner starts at the block's offset 0 and attention masks every
        position past a row's length, so the stale offset is
        overwritten before it is ever read; and the trie adopts
        complete blocks only, every offset of which its owner wrote.
        (Likewise a row that already finished INSIDE the in-flight tick
        may be given a tail block it never uses: teardown frees it past
        the adopt watermark, like a speculating row's unused lookahead
        block.)"""
        for later in self._inflight:
            if i in later.advanced:
                self._lens[i] -= 1
                later.advanced.discard(i)
                self._positions_dirty = True
        if st.fork_idx is not None:
            self._finish_fork_row(i, st)
        else:
            self._finish_ok(st.request)
            self._free_slot(i, st)
            self._slot_state[i] = None

    # -- internals ----------------------------------------------------------
    @staticmethod
    def _in_executor(loop, fn, *args):
        """run_in_executor with contextvars propagated (it doesn't, unlike
        asyncio.to_thread) so telemetry spans opened in the executor
        thread parent correctly to the loop-side span that dispatched
        them. copy_context() is copy-on-write — negligible per-call."""
        ctx = contextvars.copy_context()
        return loop.run_in_executor(None, lambda: ctx.run(fn, *args))

    @staticmethod
    def _pow2_fit(P: int, room: int) -> int:
        """Shrink a pad width to the largest power of two that fits the
        remaining cache room (the prefill overshoot guard — see
        :meth:`_prefill_step` for why overshooting would clamp the KV
        write backward over real rows). Shared by the target and draft
        prefill chunkers so the bound can never drift between them."""
        if P > room:
            P = 1
            while P * 2 <= room:
                P *= 2
        return P

    def _bucket(self, n: int, cap: int | None = None) -> int:
        """Prefill pad length: next power of two >= n (>= min bucket),
        capped at the decodable context (and at ``cap`` — the chunk size,
        for a ragged final chunk) — bounds prefill compiles at
        log2(context) programs total."""
        b = self._min_bucket
        while b < n:
            b *= 2
        return min(b, self.limit if cap is None else min(cap, self.limit))

    def _route_admission(self, st: _SlotState, slot: int, tok0) -> None:
        """Dispatch a completed prefill to its kind's finisher: plain
        int first token → decode admission; a first token still on the
        device → the list the next harvest reads; scorelike sentinel →
        prefill-only completion; fork tokens → fan-out."""
        if tok0 is _SCORELIKE_DONE:
            self._finish_scorelike(st, slot)
        elif isinstance(tok0, _ForkReady):
            self._finish_fork(st, slot, tok0.tokens)
        elif isinstance(tok0, _FirstTokenPending):
            self._pending_admits.append(tok0)
        else:
            self._finish_admission(st, slot, tok0)

    def _scorelike_chunk(self, st: _SlotState, slot: int, padded,
                         c: int, s0: int) -> None:
        """One score/embed prefill chunk (executor thread): the same
        paged KV writes as a prefill chunk with the kind's epilogue
        accumulated host-side — per-position next-token logprobs for
        score, the hidden-state sum for embed."""
        job, req = st.prefill, st.request
        hg = self.metrics.host_gap
        table_row = jnp.asarray(self._tables[slot])
        if req.kind == "score":
            targets = np.zeros((padded.shape[1],), np.int32)
            for j in range(c):
                p = job.pos + j + 1
                if p < s0:
                    targets[j] = req.prompt[p]
            self._cache, picked = self._score_chunk(
                self._params, self._cache, jnp.asarray(padded),
                jnp.int32(job.pos), jnp.int32(c), table_row,
                jnp.asarray(targets))
            hg.harvest_started()
            vals = np.asarray(picked)
            hg.harvest_ended()
            if st.score_acc is None:
                st.score_acc = []
            for j in range(c):
                if job.pos + j + 1 < s0:
                    st.score_acc.append(float(vals[j]))
        else:
            self._cache, vec = self._embed_chunk(
                self._params, self._cache, jnp.asarray(padded),
                jnp.int32(job.pos), jnp.int32(c), table_row)
            hg.harvest_started()
            v = np.asarray(vec, dtype=np.float64)
            hg.harvest_ended()
            st.embed_acc = (v if st.embed_acc is None
                            else st.embed_acc + v)

    def _finish_scorelike(self, st: _SlotState, slot: int) -> None:
        """Complete a prefill-only (score/embed) request: publish its
        result on the Request, adopt the prompt's KV into the prefix
        trie (future generates over the same prompt hit it), and free
        the slot — it never entered the decodable set."""
        req = st.request
        t = time.monotonic()
        req.t_first_token = t
        self.metrics.record_first_token(t - req.t_submit,
                                        trace_id=req.trace_id)
        if req.kind == "score":
            req.logprobs = list(st.score_acc or [])
        else:
            s0 = max(1, len(req.prompt))
            vec = (st.embed_acc if st.embed_acc is not None
                   else np.zeros((1,), np.float64))
            req.embedding = [float(v) / s0 for v in vec]
        self._finish_ok(req)
        self._free_slot(slot, st)
        self._slot_state[slot] = None

    def _finish_fork(self, st: _SlotState, slot: int, toks: list) -> None:
        """Fan a completed fork-parent prefill out to its n rows (loop
        thread; async device dispatches only). The prompt's COMPLETE
        blocks are shared copy-on-write through pool refcounts
        (:meth:`KVBlockPool.fork`); a partially filled tail block is the
        one divergent-write site at fork time, so it is eagerly copied
        per child (gather → scatter, counted as a CoW copy). Each row
        then owns its table row, sampling state, and private token
        stream; the DONE frame carries all n completions."""
        req = st.request
        pool = self.kv_pool
        bt = self.kv_block_tokens
        s0 = len(req.prompt)
        n = req.n
        children = [i for i, s in enumerate(self._slot_state)
                    if s is not None and s.fork_wait and s.request is req]
        complete = s0 // bt
        partial = s0 % bt
        shared = [int(b) for b in self._tables[slot][:complete]]
        if shared and children:
            pool.fork(shared, n)
            self.metrics.record_fork_blocks((n - 1) * len(shared))
        tail_data = None
        if partial and children:
            parent_tail = int(self._tables[slot][complete])
            tail_data = self._kv_gather(
                self._cache, jnp.asarray([parent_tail], jnp.int32))
        t = time.monotonic()
        req.t_first_token = t
        self.metrics.record_first_token(t - req.t_submit,
                                        trace_id=req.trace_id)
        rows = [(slot, st)] + [(c, self._slot_state[c])
                               for c in children]
        dry = False
        for k, (i, row_st) in enumerate(rows):
            row_st.fork_idx = k
            row_st.fork_tokens = [int(toks[k])]
            row_st.fork_wait = False
            row_st.last_token_t = t
            row_st.remaining = req.max_new_tokens - 1
            if i != slot:
                table = self._tables[i]
                table[:] = self._sentinel
                table[:complete] = shared
                row_st.blocks = list(shared)
                if partial:
                    ids = pool.alloc(1)
                    if ids is None:
                        dry = True
                        break
                    table[complete] = ids[0]
                    row_st.blocks.append(int(ids[0]))
                    self._cache = self._kv_scatter(
                        self._cache, tail_data,
                        jnp.asarray([int(ids[0])], jnp.int32))
                    pool.note_cow_copy()
                self._lens[i] = s0
            with span("cache_admit", slot=i):
                self._tokens, self._temps = self._admit_jit(
                    self._tokens, self._temps, np.int32(i),
                    np.int32(toks[k]), np.float32(req.temperature))
        self._mark_tables_dirty()
        if dry:
            # Pool dry mid-fan-out (the admission precheck bounds the
            # completion footprint, not a racing peer's growth): error
            # the whole group typed, never a partial fork.
            self.metrics.record_oom_reject()
            self._finish_error(req, PoolExhausted(
                "KV pool exhausted during fork fan-out"))
            self._teardown_fork(req)
            return
        if req.trace is not None:
            req.trace.event("fork", n=n, shared_blocks=len(shared),
                            cow_copies=(n - 1) if partial else 0)
        if req.max_new_tokens <= 1:
            for i, row_st in rows:
                self._finish_fork_row(i, row_st)

    def _finish_fork_row(self, i: int, st: _SlotState) -> None:
        """One fork row finished: bank its completion; the LAST row to
        finish resolves the shared request (one DONE with all n)."""
        req = st.request
        req.fork_completions[st.fork_idx] = list(st.fork_tokens or [])
        self._free_slot(i, st, adopt=False)
        self._slot_state[i] = None
        if all(c is not None for c in req.fork_completions):
            self._finish_ok(req)

    def _teardown_fork(self, req: Request) -> None:
        """Free every slot of a fork group (error paths): shared blocks
        drop one refcount per row, so the pool drains exactly."""
        for i, s in enumerate(self._slot_state):
            if s is not None and s.request is req:
                self._free_slot(i, s, adopt=False)
                self._slot_state[i] = None

    def _finish_admission(self, st: _SlotState, slot: int, tok0: int) -> None:
        """Loop-thread bookkeeping once a slot's prefill completed: stream
        the first token (TTFT stamp — unless this is a preempted request
        resuming, whose TTFT already happened on its first admission) and
        free the slot if one token was all the request wanted."""
        t = time.monotonic()
        if st.request.t_first_token is None:
            self._push_token(st, tok0, t, first=True)
            st.remaining -= 1
        else:
            # Resumed after preemption: the prefill over prompt + already
            # -streamed tokens sampled the next CONTINUATION token.
            # _push_token(first=False) decrements remaining itself.
            self._push_token(st, tok0, t)
        if st.remaining == 0:
            # (off the barrier route the row's first decode tick may
            # already be in flight: its advance is rolled back and its
            # output dropped, as for any row that finishes mid-flight)
            self._retire_row(slot, st)

    def _book_prefill(self, req: Request, job: _PrefillJob,
                      prompt_len: int) -> None:
        """A prompt's prefill is complete and timed: the metrics' series
        and the request's wide-event and trace columns."""
        self.metrics.record_prefill(
            job.device_s, job.chunks_done, job.matched_tokens, prompt_len)
        req.prefill_device_s += job.device_s
        req.prefill_chunks += job.chunks_done
        req.prefix_hit_tokens = int(job.matched_tokens or 0)
        if req.trace is not None:
            req.trace.data.update(
                prefill_device_s=round(job.device_s, 9),
                prefill_chunks=job.chunks_done,
                cache_hit_tokens=job.matched_tokens)

    def _prefill_step(self, st: _SlotState, slot: int, defer: bool = False):
        """Run ONE prefill chunk for the slot (executor thread; device
        work only). Returns None while the prompt is still incomplete;
        on the final chunk there is nothing to move — the chunks already
        wrote into the slot's pool blocks — so only the sampling state
        (first token, temperature) is set and the request's first token
        comes back. With ``defer`` (a plain ``generate`` slot off the
        barrier route) the chunk is only ENQUEUED: nothing waits for its
        token, and the final chunk hands back a
        :class:`_FirstTokenPending` for the loop's next harvest. The
        programs and the arguments they are entered with are the same
        either way, so a warm-up through one route warms the other."""
        req, job = st.request, st.prefill
        tokens = self._resident_tokens(req)
        s0 = len(tokens)
        rem = s0 - job.pos
        c = rem if self._chunk is None else min(self._chunk, rem)
        if self._chunk is None:
            P = self._bucket(c)
        elif c == self._chunk:
            P = self._chunk  # full chunk: ONE fixed-size program
        else:
            P = self._bucket(c, cap=self._chunk)  # ragged final chunk
        # The pad width must never overshoot the context (self.limit,
        # NOT the table reach, which rounds UP past it when the block
        # size doesn't divide it): a chunk reaching past max_seq_len
        # would make the positional dynamic_slice clamp BACKWARD and
        # embed its real tokens at wrong positions. submit() caps every
        # sequence at self.limit, so the bound loses nothing.
        # Rather than compiling a bespoke non-power-of-two width per
        # matched length, shrink to the largest power of two that fits
        # and let the NEXT call(s) finish the remainder — the compile
        # set stays pow2-bounded and no token is prefilled twice.
        # (Monolithic admission loops on this method until it returns a
        # token, so near-context-limit prompts just take an extra
        # sub-chunk or two.)
        room = self.limit - job.pos
        if P > room:
            P = self._pow2_fit(P, room)
            c = min(c, P)  # room >= rem >= 1, so P >= 1 and c >= 1
        padded = np.zeros((1, P), np.int32)
        padded[0, :c] = tokens[job.pos:job.pos + c]
        self._key, sub = jax.random.split(self._key)
        # Host values go to the programs as NumPy scalars and arrays: jit
        # uploads them with the call, where a jnp.int32() apiece is an
        # eager transfer of its own (an admission's host time is what an
        # admitting iteration adds to every row's gap between tokens).
        temp = np.float32(req.temperature)
        t0 = time.monotonic()
        # The chunk counts in the host-gap tracker as dispatched device
        # work: without this, admission phases would book their (device-
        # busy) prefill time as "device idle" in the gap window between
        # a decode harvest and the next decode dispatch.
        hg = self.metrics.host_gap
        final = job.pos + c >= s0
        special = None
        with span("prefill", bucket=P, offset=job.pos, prompt_len=s0):
            if req.kind in SCORELIKE_KINDS:
                # Prefill-only kinds: same KV writes, different epilogue
                # (per-token logprobs / hidden-state sum) accumulated
                # host-side per chunk.
                tok = tok0 = None
                self._scorelike_chunk(st, slot, padded, c, s0)
                hg.tick_dispatched()
            elif final and (req.kind == "sample" or st.dfa is not None):
                # The final chunk hands the LOGITS row back instead of a
                # sampled token: the fork fan-out samples n first tokens
                # from it; constrained admission masks it first.
                self._cache, logits = self._prefill_logits(
                    self._params, self._cache, jnp.asarray(padded),
                    jnp.int32(job.pos), jnp.int32(c),
                    jnp.asarray(self._tables[slot]))
                hg.tick_dispatched()
                hg.harvest_started()
                if req.kind == "sample":
                    self._key, sub = jax.random.split(self._key)
                    temps_n = jnp.full((req.n,), req.temperature,
                                       jnp.float32)
                    forks = self._fork_sample(logits, temps_n, sub)
                    special = _ForkReady(
                        [int(t) for t in np.asarray(forks)])
                    tok = tok0 = None
                else:
                    row = (np.asarray(logits)
                           + st.dfa.mask_row(st.dfa_state,
                                             self._cfg.vocab_size))
                    if req.temperature > 0:
                        z = row.astype(np.float64) / req.temperature
                        z -= z.max()
                        p = np.exp(z)
                        p /= p.sum()
                        rng = np.random.default_rng(
                            int(np.asarray(sub)[0]))
                        tok0 = int(rng.choice(row.shape[0], p=p))
                    else:
                        tok0 = int(np.argmax(row))
                    tok = np.int32(tok0)
                hg.harvest_ended()
            else:
                # (the table row is handed over as a COPY: the upload
                # may still be reading it when growth next writes the
                # row, now that nothing waits for the chunk)
                self._cache, tok = self._prefill(
                    self._params, self._cache, padded,
                    np.int32(job.pos), np.int32(c),
                    self._tables[slot].copy(), temp, sub)
                if defer:
                    # (a chunk nobody reads is not booked as dispatched:
                    # the tracker pairs each dispatch with a harvest)
                    tok0 = None
                    if final:
                        hg.tick_dispatched()
                else:
                    hg.tick_dispatched()
                    hg.harvest_started()
                    tok0 = int(tok)  # blocks: honest device time per chunk
                    hg.harvest_ended()
        chunk_s = time.monotonic() - t0
        job.device_s += chunk_s
        job.chunks_done += 1
        if req.trace is not None:
            req.trace.event("prefill_chunk", offset=job.pos, tokens=c,
                            bucket=P, dur_s=round(chunk_s, 9))
        job.pos += c
        # Written-KV watermark: a preemption between chunks adopts /
        # frees exactly the positions written so far.
        self._lens[slot] = job.pos
        if job.pos < s0:
            return None
        # Prompt complete.
        if req.kind in SCORELIKE_KINDS or special is not None:
            # score/embed never join the decodable set; a fork parent's
            # per-row admits happen at fan-out on the loop thread.
            self._book_prefill(req, job, s0)
            st.prefill = None
            return special if special is not None else _SCORELIKE_DONE
        with span("cache_admit", slot=slot):
            self._tokens, self._temps = self._admit_jit(
                self._tokens, self._temps, np.int32(slot), tok, temp)
        # The slot joins the decodable set: the masked table view
        # gains its row, so the next tick must re-upload.
        self._mark_tables_dirty()
        st.prefill = None
        if defer:
            return _FirstTokenPending(st, slot, tok, job, s0,
                                      time.monotonic())
        self._book_prefill(req, job, s0)
        if self._spec:
            # The draft's prompt K/V, built once the target prefill
            # finished (executor thread — the loop stays responsive).
            # After this the slot's fed-token truth is s0 for BOTH
            # models; the first spec tick picks it up from here.
            with span("draft_prefill", slot=slot, prompt_len=s0):
                self._draft_prefill_slot(slot, tokens)
        return tok0

    def _decodable(self) -> list[int]:
        """Slots whose row is live and past prefill — the rows whose
        tick output is streamed (everyone else decodes garbage)."""
        return [i for i in range(self.slots)
                if self._slot_state[i] is not None
                and self._slot_state[i].prefill is None
                and not self._slot_state[i].fork_wait]

    def _states_of(self, rows) -> tuple:
        """The requests' slot states behind ``rows``, for a tick to
        remember which it was dispatched for."""
        return tuple(self._slot_state[i] for i in rows)

    def _mark_tables_dirty(self) -> None:
        """A table row (or the decodable set) changed: the next dispatch
        must rebuild + re-upload both the masked device tables and the
        device positions vector (they share the gating — every event
        that mutates one invalidates the other's cached view)."""
        self._tables_dirty = True
        self._positions_dirty = True

    def _upload_tables(self, decodable):
        """Device view of the block tables, MASKED to the sentinel for
        rows that must not write (free slots, mid-prefill slots — their
        garbage output is discarded, and the dropped scatter guarantees
        it cannot scribble on live blocks). Rebuilt + re-uploaded only when
        the dirty flag says the masked view could have changed — set at
        the sites that mutate a table row (admission reserve, growth,
        preemption, teardown) and at prefill completion (the decodable
        set grew) — NOT by an O(slots × blocks) compare every tick.
        (Safe to hold across ticks: the decode jits donate cache/tokens
        only.)"""
        if self._pp > 1:
            # pp callers outside _pp_decode_dispatch (the spec verify
            # chain) always run at mb_count==1, so micro-batch 0 IS the
            # whole slot batch.
            return self._pp_tables(0, decodable)
        if self._tables_dirty or self._tables_dev is None:
            tables = np.full_like(self._tables, self._sentinel)
            for i in decodable:
                tables[i] = self._tables[i]
            self._tables_dev = jnp.asarray(tables)
            self._tables_dirty = False
        return self._tables_dev

    def _upload_mask(self):
        """Device view of the per-slot token mask, re-uploaded only when
        a DFA advanced (or a constrained slot was torn down) since the
        last tick — the dirty-flag pattern the block tables use, so the
        steady state re-feeds the cached device array and the masked
        decode step stays at one executable. The upload is timed into
        ``mask_upload_seconds``."""
        if self._mask_dirty or self._mask_dev is None:
            t0 = time.monotonic()
            if self.mesh is not None:
                self._mask_dev = jax.device_put(self._mask_host,
                                                self._replicated)
            else:
                self._mask_dev = jnp.asarray(self._mask_host)
            self.metrics.record_mask_upload(time.monotonic() - t0)
            self._mask_dirty = False
        return self._mask_dev

    def _set_slot_mask(self, i: int, st: _SlotState) -> None:
        """Refresh slot ``i``'s mask row from its DFA state (no-op rows
        stay all-zero); clears the row for non-DFA slots."""
        if not self._constrained_mode:
            return
        if st is not None and st.dfa is not None:
            row = st.dfa.mask_row(st.dfa_state, self._cfg.vocab_size)
            self._mask_host[i, :] = row
            # Mask-upload attribution: this request's DFA advance is
            # what forces the next tick's re-upload.
            st.request.mask_uploads += 1
        else:
            self._mask_host[i, :] = 0.0
        self._mask_dirty = True

    def _pp_tables(self, mb: int, rows) -> list:
        """Per-STAGE committed device views of micro-batch ``mb``'s
        masked tables (same dirty gating as :meth:`_upload_tables`; the
        dirty flag invalidates every micro-batch's cached view, each
        rebuilt lazily at its next dispatch). Committing each copy to
        its stage's replicated layout keeps every stage-jit argument
        placement identical across rebuild and steady-state ticks — the
        source-consistency rule compile-count==1 per stage rests on."""
        if self._tables_dirty or self._tables_dev is None:
            self._tables_dev = [None] * self._mb_count
            self._tables_dirty = False
        if self._tables_dev[mb] is None:
            lo = mb * self._mb_size
            tables = np.full((self._mb_size, self._table_blocks),
                             self._sentinel, np.int32)
            for i in rows:
                tables[i - lo] = self._tables[i]
            self._tables_dev[mb] = [jax.device_put(tables, rep)
                                    for rep in self._stage_rep]
        return self._tables_dev[mb]

    def _pp_positions(self, mb: int, rows) -> list:
        """Per-stage committed positions vectors for micro-batch ``mb``
        (each stage's steady-state tick re-feeds its OWN returned
        vector; a dirty rebuild re-commits to every stage's layout)."""
        if self._positions_dirty or self._positions_dev is None:
            self._positions_dev = [None] * self._mb_count
            self._positions_dirty = False
        if self._positions_dev[mb] is None:
            lo = mb * self._mb_size
            positions = np.zeros((self._mb_size,), np.int32)
            for i in rows:
                positions[i - lo] = self._lens[i]
            self._positions_dev[mb] = [jax.device_put(positions, rep)
                                       for rep in self._stage_rep]
        return self._positions_dev[mb]

    def _decode_dispatch(self) -> _InflightTick:
        """Enqueue ONE plain decode tick (executor thread) and return
        WITHOUT waiting for the device: JAX dispatch is asynchronous, so
        the host is free the moment the work is queued. All host-side
        bookkeeping that the tick's outcome does NOT depend on happens
        here — position watermarks advance by exactly one per decodable
        row, recorded in ``advanced`` so a teardown detected while the
        tick is still in flight can roll its row back."""
        if self._pp > 1:
            return self._pp_decode_dispatch()
        self._key, sub = jax.random.split(self._key)
        rows = tuple(self._decodable())
        harvest = ()
        tables_dev = self._upload_tables(rows)
        if self._positions_dirty or self._positions_dev is None:
            positions = np.zeros((self.slots,), np.int32)
            for i in rows:
                positions[i] = self._lens[i]
            # Sharded: commit the rebuilt vector to the replicated
            # layout the decode step's out_shardings pins — jit
            # cache entries key on actual argument shardings, so an
            # uncommitted host upload here would occupy a DIFFERENT
            # executable than the steady-state ticks that re-feed
            # the committed jit output (same reason the ctor
            # commits tokens/temps).
            if self.mesh is not None:
                self._positions_dev = jax.device_put(
                    np.asarray(positions), self._replicated)
            else:
                self._positions_dev = jnp.asarray(positions)
            self._positions_dirty = False
        if self._constrained_mode:
            mask_dev = self._upload_mask()
            self._cache, self._tokens, self._positions_dev = (
                self._decode_step(
                    self._params, self._cache, self._tokens,
                    self._temps, self._positions_dev, tables_dev,
                    mask_dev, sub))
        else:
            # (a counting module's step hands back a fourth array,
            # tokens + counts: the handle the harvest reads)
            self._cache, self._tokens, self._positions_dev, *harvest = (
                self._decode_step(
                    self._params, self._cache, self._tokens,
                    self._temps, self._positions_dev, tables_dev,
                    sub))
        # Each decodable row appends exactly one K/V vector (the
        # device advances its own positions copy identically).
        for i in rows:
            self._lens[i] += 1
        t = self.metrics.host_gap.tick_dispatched()
        return _InflightTick(kind="decode", rows=rows,
                             states=self._states_of(rows), t_dispatch=t,
                             tokens=harvest[0] if harvest else self._tokens,
                             advanced=set(rows))

    def _to_stage(self, x, s):
        """Place a cross-stage value on stage ``s``'s replicated
        sharding. jax auto-transfers single-device arrays between
        1-device stages, but a committed tp-sharded array fed to a jit
        on a DISJOINT sub-mesh is a runtime placement error — so every
        stage-boundary handoff is placed explicitly. The target layout
        is identical every call, so the consumer jit still keys one
        cache entry."""
        return jax.device_put(x, self._stage_rep[s])

    def _pp_decode_dispatch(self) -> _InflightTick:
        """One micro-batch decode tick through the stage chain
        (executor thread). The micro-batch is picked round-robin,
        skipping to the next one with decodable rows (an all-idle
        engine still dispatches — the warmup path decodes garbage on
        whichever micro-batch the cursor is at, exactly like the
        unsharded warmup). Every stage's jit is dispatched back to
        back; jax chains them through the activation future, so the
        host returns after enqueueing all S programs and the device
        timeline is stage 0 → ... → stage S-1. With depth>1 the NEXT
        call dispatches the next micro-batch while these stages drain —
        stage s is busy with micro-batch m while stage s-1 runs m+1 —
        which is what turns the per-stage idle bubble into overlap."""
        self._key, sub = jax.random.split(self._key)
        decodable = self._decodable()
        mb, rows = self._mb_rr, ()
        for off in range(self._mb_count):
            cand = (self._mb_rr + off) % self._mb_count
            lo = cand * self._mb_size
            cand_rows = tuple(i for i in decodable
                              if lo <= i < lo + self._mb_size)
            if cand_rows:
                mb, rows = cand, cand_rows
                break
        self._mb_rr = (mb + 1) % self._mb_count
        lo = mb * self._mb_size
        last = self._pp - 1
        x = self._tokens[mb][:, None]
        tables = self._pp_tables(mb, rows)
        pos = self._pp_positions(mb, rows)
        new_pos = [None] * self._pp
        for s in range(last):
            self._cache[s], x, new_pos[s] = self._decode_steps[s](
                self._params[s], self._cache[s],
                self._to_stage(x, s), pos[s], tables[s])
        self._cache[last], nxt, new_pos[last] = (
            self._decode_steps[last](
                self._params[last], self._cache[last],
                self._to_stage(x, last),
                self._temps[mb], pos[last], tables[last], sub))
        self._positions_dev[mb] = new_pos
        for i in rows:
            self._lens[i] += 1
        self._tokens[mb] = nxt
        t = self.metrics.host_gap.tick_dispatched()
        return _InflightTick(kind="decode", rows=rows,
                             states=self._states_of(rows), t_dispatch=t,
                             tokens=nxt, advanced=set(rows),
                             mb=mb, mb_start=lo)

    def _harvest_decode(self, tick: _InflightTick) -> np.ndarray:
        """The one D2H per plain tick (executor thread): blocks until
        the device finishes the tick, then hands its token vector to
        the loop thread for streaming."""
        hg = self.metrics.host_gap
        hg.harvest_started()
        nxt = np.asarray(tick.tokens)
        t = hg.harvest_ended()
        if len(nxt) > self.slots:  # tokens, then the tick's counts
            self.metrics.record_tick_counters(self._tick_counters,
                                              nxt[self.slots:])
            nxt = nxt[:self.slots]
        if self._pp > 1:
            self.metrics.bubble.record(tick.t_dispatch, t, self._pp)
        self._tick_log.append({
            "kind": tick.kind, "rows": len(tick.rows),
            "t_dispatch": tick.t_dispatch, "t_harvest": t,
            "harvest_wait_s": round(hg.last_harvest_wait, 9),
            "host_gap_s": round(hg.last_gap, 9),
        })
        return nxt

    def _decode_sync(self) -> np.ndarray:
        """Serialized dispatch + harvest: the ``pipeline_depth=0`` tick
        and the ctor-warmup / swap-rewarm path (both must complete on
        the spot — a rewarm's whole job is proving the step ran)."""
        return self._harvest_decode(self._decode_dispatch())

    # -- speculative decoding (draft/verify) --------------------------------
    def _spec_dispatch(self) -> _InflightTick:
        """Enqueue one speculative tick (executor thread; device work
        only): fixed-K greedy draft scan, ONE batched K-position verify,
        masked accept — returned as an :class:`_InflightTick` whose
        harvest reads ``out``/``commit`` off the device. Unlike a plain
        tick, NO position bookkeeping advances here: the advance is the
        commit count, which only the harvest knows — which is also why
        the run loop never dispatches past an unharvested spec tick
        (the next tick's positions depend on it). All shapes are static
        in ``spec_k``, so the armed compile-count==1 contract holds per
        callable no matter how acceptance varies."""
        self._key, sub = jax.random.split(self._key)
        decodable = self._decodable()
        spec_ok = np.zeros((self.slots,), bool)
        remaining = np.zeros((self.slots,), np.int32)
        positions = np.zeros((self.slots,), np.int32)
        prev = np.zeros((self.slots,), np.int32)
        for i in decodable:
            st = self._slot_state[i]
            spec_ok[i] = (st.request.temperature <= 0
                          and st.request.speculate)
            remaining[i] = st.remaining
            positions[i] = self._lens[i]
            # The token at position fed-1, for the draft's heal apply:
            # the resident sequence's second-to-last element (the last
            # one is the unfed feed token) — read directly rather than
            # materializing prompt+out (O(context) per tick). Admission
            # streams at least one token before the first tick, so the
            # element always exists.
            out_t = st.request.out_tokens
            if len(out_t) >= 2:
                prev[i] = out_t[-2]
            elif out_t:
                prev[i] = st.request.prompt[-1]
            else:
                prev[i] = st.request.prompt[-2 if len(
                    st.request.prompt) >= 2 else -1]
        start = jnp.asarray(positions)
        self._draft_cache, drafts = self._draft_step(
            self._draft_params, self._draft_cache, jnp.asarray(prev),
            self._tokens, start)
        # ``room`` doubles as the accounting cap: a commit clamped
        # by allocation pressure must not read as draft rejection
        # in the accept-rate metric.
        caps = np.zeros((self.slots,), np.int32)
        for i in decodable:
            caps[i] = self._spec_room(i)
        if self._constrained_mode:
            # Speculation under masks: forbidden drafts are
            # rejected BEFORE the verify can commit them — each
            # constrained greedy row's cap is clamped to the
            # DFA-valid prefix of its draft window (one host sync
            # of the drafts, only when a constrained row is live).
            # Sampled constrained rows cap at 0: their one-token
            # commit would come from UNMASKED verify logits, so
            # they are served by masked fallback ticks instead.
            drafts_host = None
            for i in decodable:
                sti = self._slot_state[i]
                if sti.dfa is None:
                    continue
                if not spec_ok[i]:
                    caps[i] = 0
                    continue
                if drafts_host is None:
                    drafts_host = np.asarray(drafts)
                caps[i] = min(
                    int(caps[i]),
                    sti.dfa.valid_prefix(sti.dfa_state,
                                         drafts_host[i]))
        tables_dev = self._upload_tables(decodable)
        self._cache, self._tokens, out, commit = self._verify_step(
            self._params, self._cache, self._tokens, drafts,
            self._temps, jnp.asarray(spec_ok), jnp.asarray(remaining),
            jnp.asarray(caps), start, tables_dev, sub)
        t = self.metrics.host_gap.tick_dispatched()
        return _InflightTick(kind="spec", rows=tuple(decodable),
                             states=self._states_of(decodable),
                             t_dispatch=t, out=out, commit=commit,
                             caps=caps)

    def _harvest_spec(self, tick: _InflightTick):
        """Spec-tick harvest (executor thread): the one D2H reads the
        committed-token matrix and commit counts, then the position
        watermarks advance by each row's ACTUAL commit — the part a
        plain tick can do at dispatch and a spec tick cannot."""
        hg = self.metrics.host_gap
        hg.harvest_started()
        out = np.asarray(tick.out)
        commit = np.asarray(tick.commit)
        t = hg.harvest_ended()
        if self._pp > 1:
            self.metrics.bubble.record(tick.t_dispatch, t, self._pp)
        for i in tick.rows:
            self._lens[i] += int(commit[i])
        # Commits are variable-width: the cached device positions
        # no longer match _lens.
        self._positions_dirty = True
        self._tick_log.append({
            "kind": tick.kind, "rows": len(tick.rows),
            "t_dispatch": tick.t_dispatch, "t_harvest": t,
            "harvest_wait_s": round(hg.last_harvest_wait, 9),
            "host_gap_s": round(hg.last_gap, 9),
        })
        return out, commit, tick.caps

    def _spec_sync(self):
        """Serialized spec tick (ctor warmup / depth 0): dispatch and
        harvest on the spot. Returns ``(out, commit, caps)`` —
        ``out[i, :commit[i]]`` are slot ``i``'s committed tokens this
        tick (0..K for live greedy rows — 0 means every draft was
        rejected and the run loop owes the batch a fallback tick —
        exactly 1 for temperature>0 rows riding the same batch, 0 for
        garbage rows) and ``caps[i]`` is the draft budget the row
        REALLY had (spec_k, minus allocation pressure) for honest
        accept accounting."""
        return self._harvest_spec(self._spec_dispatch())

    def _spec_room(self, i: int) -> int:
        """Contiguous allocated K/V positions from slot ``i``'s write
        offset (capped at the window size): how many of this tick's
        window writes will actually land. The verify clamps the row's
        commit to this, so speculation under pool pressure degrades to
        fewer tokens per tick instead of committing tokens whose K/V
        the OOB-drop scatter discarded."""
        bt = self.kv_block_tokens
        lens = int(self._lens[i])
        blk = lens // bt
        allocated = 0
        while (blk < self._table_blocks
               and self._tables[i, blk] != self._sentinel):
            allocated += bt
            blk += 1
        return min(allocated - lens % bt, self.spec_k)

    def _alloc_lookahead(self, i: int) -> None:
        """Opportunistic pre-tick growth for a speculating slot: chain
        blocks so the whole K-wide verify window can land. Unlike
        :meth:`_ensure_tail_block` this NEVER preempts — a dry pool just
        shrinks the row's ``_spec_room`` (fewer tokens per tick), which
        beats evicting a peer for lookahead capacity that rejected
        drafts may never use. Extra blocks are reclaimed at teardown /
        preemption by the adopt watermark like any tail block."""
        st = self._slot_state[i]
        bt = self.kv_block_tokens
        last = (int(self._lens[i]) + self.spec_k - 1) // bt
        for blk in range(int(self._lens[i]) // bt,
                         min(last, self._table_blocks - 1) + 1):
            if self._tables[i, blk] != self._sentinel:
                continue
            ids = self.kv_pool.alloc(1)
            if ids is None:
                return
            self._tables[i, blk] = ids[0]
            st.blocks.extend(ids)
            self._mark_tables_dirty()

    def _draft_prefill_slot(self, slot: int, tokens) -> None:
        """Build the draft's prompt K/V for a freshly admitted slot
        (executor thread): pow2-bucketed chunks through the draft
        prefill program into a scratch row, then one splice into the
        batched draft cache. Runs once per admission, after the TARGET
        prefill completed — the draft is small, so this costs a fraction
        of the prefill the admission already paid; on a prefix-cache hit
        the draft recomputes its prompt K/V (the cache pools hold target
        K/V only; caching draft K/V would double trie bookkeeping to
        save work that is cheap by the draft's definition)."""
        row = self._fresh_draft_row()
        pos, s0 = 0, len(tokens)
        while pos < s0:
            c = s0 - pos
            P = self._pow2_fit(self._bucket(c), self.limit - pos)
            c = min(c, P)
            padded = np.zeros((1, P), np.int32)
            padded[0, :c] = tokens[pos:pos + c]
            row = self._draft_prefill(
                self._draft_params, row, jnp.asarray(padded),
                jnp.int32(pos), jnp.int32(c))
            pos += c
        self._draft_cache = self._draft_admit(
            self._draft_cache, jnp.int32(slot), row)

    # -- KV-pool internals (host bookkeeping; no device work) ---------------
    @staticmethod
    def _resident_tokens(req: Request) -> list:
        """The slot's full resident sequence: prompt plus already-
        streamed tokens (a preempted request resumes with its output
        folded back in, so adoption keys, resume prefill, and block math
        must all see the SAME sequence). Skips the list copy when
        nothing has streamed."""
        return (req.prompt + req.out_tokens if req.out_tokens
                else req.prompt)

    def _blocks_for(self, first_token: int, last_token: int) -> int:
        """Blocks covering token positions [first_token, last_token]."""
        bt = self.kv_block_tokens
        return last_token // bt - first_token // bt + 1

    def _admits_in_flight(self, req: Request) -> bool:
        """Whether ``req`` may be admitted with a decode tick left in
        flight, by what engine and request are: its prefill only writes
        blocks that are the slot's own and its first token can wait for
        the next harvest. Not so for the kinds whose final chunk is read
        back on the host at once (a fork fan-out, a constrained first
        token, a score or an embedding), with a draft model (its prefill
        follows the target's), across pipeline stages, or at
        ``pipeline_depth=0`` (the serialized loop the tests compare
        against). What the POOL needs is asked of
        :meth:`_reserve_blocks`."""
        return (self.pipeline_depth > 0 and self._pp == 1
                and not self._spec and req.kind == "generate"
                and req.constraint is None)

    def _tier_next_block(self, toks: list) -> int | None:
        """The index of the block that follows the device-resident
        prefix of ``toks``, when the host tier holds it and a match
        could use it (:meth:`_readmit_from_tier` would scatter it
        back); else None."""
        bt = self.kv_block_tokens
        # Same last-block holdback as match(): prefill needs >= 1
        # uncached token, so a block match() won't use is a wasted row.
        cap = max(0, (len(toks) - 1) // bt)
        resident = self.kv_pool.probe(toks) // bt
        if resident < cap and self.kv_tier.contains(
                toks[:(resident + 1) * bt]):
            return resident
        return None

    def _reserve_blocks(self, req: Request, slot: int,
                        in_flight: bool = False):
        """Admission-time reservation (loop thread; zero device work):
        pin the longest shared prefix chain, allocate private blocks for
        the rest of the prompt, and point the slot's block table at
        both. Returns ``(job, blocks, first_block, match)``, or None
        after parking the request (requeued at its class head) because
        the pool is dry and nobody strictly lower-priority is running.

        With ``in_flight`` (a decode tick may be in flight and is to
        stay there) it does only what is host bookkeeping: blocks off
        the free list, or unreferenced trie leaves evicted with no host
        tier. Where it would have to re-admit blocks from the host tier
        (a scatter into the pool, evictions that spill), spill
        (``alloc_spills``: a gather from the tick's output), or find a
        victim or park (``alloc`` gives None), it leaves request, pool
        pins and table as they were and returns ``_NEEDS_BARRIER``: the
        same test growth makes (:meth:`_chain_tail_block`).

        Admission only preempts STRICTLY lower-priority slots — an
        equal-priority preemption would let a full pool thrash between
        peers; growth (:meth:`_ensure_tail_block`) is the path that may
        preempt within a class, because there a slot is wedged without
        a block."""
        pool = self.kv_pool
        tokens = self._resident_tokens(req)
        if (in_flight and self.kv_tier is not None
                and self._tier_next_block([int(t) for t in tokens])
                is not None):
            return _NEEDS_BARRIER
        if self.kv_tier is not None:
            # Host-tier re-admission BEFORE the match: blocks the trie
            # evicted (but the tier kept) scatter back H2D and then
            # count as the prefix hits they are. Eviction cascades from
            # the adopt are fine — they spill lower-value leaves. The
            # spill exemplar points at the admission that triggered it.
            self._tier_trace_id = req.trace_id
            try:
                self._readmit_from_tier(tokens, trace_id=req.trace_id)
            finally:
                self._tier_trace_id = None
        # Scorelike requests skip the prefix match: a matched prefix
        # skips its chunks' compute, and the whole point of a scoring
        # prefill is the per-position values that compute produces.
        match = pool.match(tokens if req.kind not in SCORELIKE_KINDS
                           else tokens[:0])
        m = match.matched_tokens
        first_block = m // self.kv_block_tokens
        needed = self._blocks_for(m, len(tokens) - 1)
        spills = in_flight and pool.alloc_spills(needed)
        ids = None if spills else pool.alloc(needed)
        if ids is None and in_flight:
            pool.release(match)
            return _NEEDS_BARRIER
        while ids is None:
            # Fork and scorelike rows are never preemption victims: a
            # fork row's private stream cannot resume through the
            # requeue path, and a scorelike row's accumulator would be
            # silently truncated by a prefix-matched re-admission.
            victims = [
                (i, s) for i, s in enumerate(self._slot_state)
                if s is not None and s.request.priority > req.priority
                and s.request.kind == "generate"]
            if not victims:
                pool.release(match)
                self.scheduler.requeue(req)
                self._parked_at_version = pool.version
                self._parked_req = req
                return None
            i, _ = max(victims,
                       key=lambda v: (v[1].request.priority, v[1].t_admit))
            self._preempt_slot(i)
            ids = pool.alloc(needed)
        row = self._tables[slot]
        row[:] = self._sentinel
        row[:first_block] = match.ids
        row[first_block:first_block + needed] = ids
        self._mark_tables_dirty()
        self._lens[slot] = m
        if req.trace is not None and m:
            req.trace.event("prefix_splice", tokens=m, blocks=first_block)
        job = _PrefillJob(pos=m, matched_tokens=m)
        return job, ids, first_block, match

    def _needs_tail_block(self, i: int) -> bool:
        """True when slot ``i``'s next write position crosses into an
        unallocated block. A row whose ``_lens`` already reached the
        table's capacity needs nothing: that is a finishing row's
        optimistic depth-1 advance (``submit`` caps prompt + max_new at
        the context limit, so the limit is only reached on a row's
        final tick) — its in-flight write landed in the LAST block and
        the pending harvest tears it down; indexing the table one past
        the end for it would be an engine-killing IndexError."""
        blk = int(self._lens[i]) // self.kv_block_tokens
        return (blk < self._table_blocks
                and self._tables[i, blk] == self._sentinel)

    def _chain(self, i: int, ids) -> None:
        """Point slot ``i``'s next write position at the fresh block
        ``ids`` (one row of the pool) and mark the device views stale."""
        blk = int(self._lens[i]) // self.kv_block_tokens
        self._tables[i, blk] = ids[0]
        self._slot_state[i].blocks.extend(ids)
        self._mark_tables_dirty()

    def _chain_tail_block(self, i: int) -> bool:
        """The half of growth that is safe with a tick in flight: chain
        one block when the pool gives it with NO victim and NO device
        work, else touch nothing and return False (the caller drains the
        pipeline and runs :meth:`_ensure_tail_block`). Which case is
        which, by what :meth:`KVBlockPool.alloc` would do:

        - a row off the free list, or an unreferenced trie leaf evicted
          with no host tier attached: host bookkeeping only — chained;
        - a leaf evicted with a tier attached (``alloc_spills``):
          the spill hook (:meth:`_spill_blocks`) gathers the victim's
          rows from ``self._cache``, the in-flight tick's output, and
          waits for them on the host — refused, left to the barrier;
        - nothing free and nothing evictable (``alloc`` gives None):
          growth has to preempt a slot or error the wedged row, both of
          which tear down state only a harvest settles — refused."""
        pool = self.kv_pool
        if pool.alloc_spills(1):
            return False
        ids = pool.alloc(1)
        if ids is None:
            return False
        self._chain(i, ids)
        return True

    def _grow_in_flight(self) -> list[int]:
        """Step 5c's first pass, with whatever tick is in flight left
        alone: chain a tail block to every decoding row that needs one
        and that :meth:`_chain_tail_block` can serve; return the rows it
        could not (empty on all but a dry or spilling pool). ``alloc``
        never frees, so once one row is refused every later one is: the
        refused rows are a suffix, and the pool sees this pass and the
        drained one allocate in slot order, as a depth-0 loop does."""
        # (the decodable rows: a fork child still waiting for its
        # parent's prefill has nothing to write, and the block growth
        # used to give it was lost when the fan-out rebuilt its table)
        growing = [i for i in self._decodable()
                   if self._needs_tail_block(i)]
        if not growing:
            return growing
        with span("paged_growth", blocks=len(growing), drained=0):
            dry = [i for i in growing if not self._chain_tail_block(i)]
        self.metrics.record_paged_growth(len(growing) - len(dry),
                                         drained=False)
        return dry

    def _grow_drained(self, dry: list[int]) -> None:
        """Step 5c's second pass, behind the ``paged_growth`` barrier:
        today's preempting growth for the rows the first pass left. The
        barrier may have FINISHED some of them (and freed blocks), and an
        earlier row's preemption may have taken a later one: re-check,
        as step 2 of the loop does for ``dead``."""
        chained = 0
        with span("paged_growth", blocks=len(dry), drained=1):
            for i in dry:
                if (self._slot_state[i] is not None
                        and self._needs_tail_block(i)
                        and self._ensure_tail_block(i)):
                    chained += 1
        self.metrics.record_paged_growth(chained, drained=True)

    def _ensure_tail_block(self, i: int) -> bool:
        """Pre-tick growth behind the barrier: make sure slot ``i``'s
        next write position has a block, preempting the lowest-priority
        youngest slot — itself included — when the pool is dry. Returns
        False when slot ``i`` itself was the fairest victim (it is gone;
        the tick runs without it). Never called with a tick in flight:
        preemption adopts the victim's blocks by its harvested length,
        and an eviction may spill (see :meth:`_chain_tail_block`)."""
        st = self._slot_state[i]
        if not self._needs_tail_block(i):
            return True
        ids = self.kv_pool.alloc(1)
        while ids is None:
            victims = [(j, s) for j, s in enumerate(self._slot_state)
                       if s is not None
                       and s.request.kind == "generate"]
            if not victims:
                # Only fork/scorelike rows are resident and the pool is
                # dry: the wedged row's whole request errors typed (a
                # fork row tears its group down with it) — there is no
                # preemptable generate slot to relieve the pressure.
                self.metrics.record_oom_reject()
                self._finish_error(st.request, PoolExhausted(
                    "KV pool exhausted with no preemptible slot"))
                if st.fork_idx is not None or st.fork_wait:
                    self._teardown_fork(st.request)
                else:
                    self._free_slot(i, st, adopt=False)
                    self._slot_state[i] = None
                return False
            j, _ = max(victims,
                       key=lambda v: (v[1].request.priority, v[1].t_admit))
            self._preempt_slot(j)
            if j == i:
                return False
            ids = self.kv_pool.alloc(1)
        self._chain(i, ids)
        return True

    def _preempt_slot(self, i: int) -> None:
        """Evict slot ``i`` for its KV blocks and requeue its request at
        the front of its priority class (oversubscription's relief
        valve). The complete blocks of its written K/V are ADOPTED into
        the prefix trie — evictable if the pressure persists, but a
        prompt re-admission re-matches them and resumes nearly free —
        and its streamed tokens ride along in ``req.out_tokens``, so the
        resume prefill continues the sequence token-identically."""
        st = self._slot_state[i]
        req = st.request
        valid = int(self._lens[i])
        tokens = self._resident_tokens(req)
        self.kv_pool.adopt(tokens[:valid], st.blocks, st.first_block)
        self.kv_pool.release(st.match)
        st.blocks = []
        st.match = None
        st.prefill = None
        self._tables[i, :] = self._sentinel
        self._set_slot_mask(i, None)
        self._mark_tables_dirty()
        self._lens[i] = 0
        self._slot_state[i] = None
        self.metrics.record_preemption()
        req.preemptions += 1
        if req.trace is not None:
            req.trace.event("preempt", slot=i, resident_tokens=valid,
                            streamed=len(req.out_tokens))
        if self.flight_recorder is not None:
            self.flight_recorder.record_event(
                "preempt", trace_id=req.trace_id, slot=i)
        self.scheduler.requeue(req)

    def _free_slot(self, i: int, st: _SlotState,
                         adopt: bool = True) -> None:
        """Slot teardown: adopt the complete
        blocks of whatever K/V the slot computed into the prefix trie
        (zero-copy insert — a follow-up prompt sharing the prefix, or a
        multi-turn continuation sharing prompt+output, re-matches them),
        free the rest, and unpin the shared chain."""
        self._set_slot_mask(i, None)
        req = st.request
        if st.fork_idx is not None or st.fork_wait:
            # Fork rows never adopt: their decoded tail lives in
            # st.fork_tokens (not req.out_tokens), so the adoption key
            # would be wrong — and their shared prompt blocks are
            # refcounted, freed for real only by the LAST row.
            adopt = False
        # Peak KV footprint for the wide event, captured before the
        # block list is cleared (fork rows accumulate across the group).
        req.kv_blocks = max(req.kv_blocks, len(st.blocks))
        valid = int(self._lens[i])
        if adopt and valid:
            tokens = self._resident_tokens(req)
            self.kv_pool.adopt(tokens[:valid], st.blocks, st.first_block)
        else:
            self.kv_pool.free(st.blocks)
        self.kv_pool.release(st.match)
        st.blocks = []
        st.match = None
        self._tables[i, :] = self._sentinel
        self._mark_tables_dirty()
        self._lens[i] = 0

    def _stream_spec(self, st: _SlotState, row_out, commit: int,
                     cap: int, t: float) -> None:
        """Stream one slot's committed tokens from a speculative tick
        and book the accept accounting. ``commit`` was clamped in-kernel
        to the row's remaining budget (and its allocated room —
        ``cap``), so the push loop can never overshoot
        ``max_new_tokens``. The tokens of one tick share a timestamp —
        they really did arrive together, which is what the inter-token
        histogram should say."""
        req = st.request
        if req.temperature <= 0 and req.speculate:
            # Drafts the row could actually have used: spec_k clamped
            # by BOTH its remaining budget and the allocated
            # room the commit was clamped to. Counting the clamped-away
            # drafts would dilute the accept rate with "request
            # finished" / "pool pressure" — neither is draft quality,
            # and the metric's whole job is to isolate draft quality.
            # Every committed token IS an accepted draft in this
            # design, so accepted == commit.
            usable = min(self.spec_k, st.remaining, cap)
            if usable > 0:
                st.spec_drafted += usable
                st.spec_accepted += commit
                req.spec_drafted += usable
                req.spec_accepted += commit
                self.metrics.record_spec(usable, commit,
                                         trace_id=req.trace_id)
                if req.trace is not None:
                    req.trace.data["spec_drafted"] = st.spec_drafted
                    req.trace.data["spec_accepted"] = st.spec_accepted
        for j in range(commit):
            self._push_token(st, int(row_out[j]), t)

    def _push_token(self, st: _SlotState, tok: int, t: float,
                    first: bool = False) -> None:
        req = st.request
        if first:
            req.t_first_token = t
            self.metrics.record_first_token(t - req.t_submit,
                                            trace_id=req.trace_id)
            if req.trace is not None:
                req.trace.event("first_token",
                                ttft_s=round(t - req.t_submit, 9))
        else:
            self.metrics.record_inter_token(t - st.last_token_t,
                                            trace_id=req.trace_id)
            st.remaining -= 1
        st.last_token_t = t
        if st.dfa is not None:
            # Advance the automaton host-side; reaching a terminal
            # state (no outgoing edges) force-finishes the request.
            nxt_state = st.dfa.step(st.dfa_state, tok)
            if nxt_state is None:
                st.remaining = 0
            else:
                st.dfa_state = nxt_state
                if st.dfa.is_terminal(st.dfa_state):
                    st.remaining = 0
            self._set_slot_mask(self._slot_state.index(st), st)
        if st.fork_tokens is not None:
            # Fork rows keep a private stream; the DONE frame carries
            # all n completions — nothing is streamed as token events.
            st.fork_tokens.append(tok)
            return
        req.out_tokens.append(tok)
        req.events.put_nowait(("token", tok))

    def _finish_ok(self, req: Request) -> None:
        if req.t_done is not None:
            # A fork group's n rows share one Request: only the first
            # terminal transition counts.
            return
        req.t_done = time.monotonic()
        self.scheduler.release_quota(req)
        self.metrics.record_finish(req.t_done - req.t_submit)
        done_tokens = (sum(len(c) for c in req.fork_completions)
                       if req.fork_completions is not None
                       else len(req.out_tokens))
        self.metrics.record_tenant_done(req.tenant, done_tokens)
        self._finalize_trace(req, "ok")
        done = {
            "tokens": done_tokens,
            "ttft_s": req.ttft,
            "latency_s": req.t_done - req.t_submit,
            "weight_version": req.weight_version,
            "tenant": req.tenant,
            "kind": req.kind,
        }
        if req.fork_completions is not None:
            done["completions"] = req.fork_completions
        if req.logprobs is not None:
            done["logprobs"] = req.logprobs
        if req.embedding is not None:
            done["embedding"] = req.embedding
        req.events.put_nowait(("done", done))
        req.done.set()

    def _finish_error(self, req: Request, err: ServingError) -> None:
        if req.t_done is not None:
            return
        req.error = err
        req.t_done = time.monotonic()
        # Quota credit on EVERY terminal path: a charged request that
        # expired in queue must hand its unused tokens back, or a
        # bursty tenant's failed work double-charges its budget.
        self.scheduler.release_quota(req)
        self._finalize_trace(req, err.code, message=str(err))
        req.events.put_nowait(("error", err))
        req.done.set()

    def _finalize_trace(self, req: Request, status: str,
                        message: str | None = None) -> None:
        """Terminal bookkeeping for one request: SLO verdict (counter
        even with tracing off), the wide-event append, and timeline
        finalization into the trace store / flight recorder. Cheap
        no-op when nothing is armed."""
        latency = (req.t_done - req.t_submit
                   if req.t_done is not None and req.t_submit is not None
                   else None)
        slow = (self.slo_s is not None and latency is not None
                and latency > self.slo_s)
        if slow:
            self.metrics.record_slo_violation()
        # The wide event is emitted BEFORE the trace-gated return: one
        # flat record per finished request regardless of whether
        # timelines are armed.
        if self.wide_events is not None:
            self._emit_wide_event(req, status, latency, slow)
        rec = req.trace
        if rec is None:
            return
        req.trace = None  # finalize exactly once
        if status == "ok":
            rec.event("done", tokens=len(req.out_tokens))
        else:
            rec.event("error", code=status,
                      message=(message or "")[:200] or None)
        d = rec.data
        d["status"] = status
        d["tenant"] = req.tenant
        d["tokens_out"] = len(req.out_tokens)
        d["prompt_tokens"] = len(req.prompt)
        if latency is not None:
            d["latency_s"] = round(latency, 9)
        if req.ttft is not None:
            d["ttft_s"] = round(req.ttft, 9)
        if "admit_iteration" in d:
            # Decode ticks this request lived through (its share of the
            # batch's iterations between admission and completion).
            d["decode_iterations"] = (self.metrics.iterations
                                      - d.pop("admit_iteration"))
        if slow:
            d["slo_violation"] = True
        recd = rec.to_dict()
        if self.trace_store is not None:
            self.trace_store.put(recd)
        if self.flight_recorder is not None:
            self.flight_recorder.record_timeline(recd, slow=slow)

    def _emit_wide_event(self, req: Request, status: str,
                         latency: float | None, slow: bool) -> None:
        """Assemble and append the one canonical flat record for a
        finished request — every column from state the engine already
        holds (no new per-token work anywhere feeds this; the counters
        are plain attribute writes at per-request events). Called once
        per request from the terminal path."""
        prov = req.weight_version or self.weight_version or {}
        forks = 0
        out_tokens = len(req.out_tokens)
        if req.fork_completions is not None:
            done_forks = [c for c in req.fork_completions if c is not None]
            forks = len(done_forks)
            out_tokens = sum(len(c) for c in done_forks)
        migration = ""
        kv_info = getattr(req, "kv_migration", None)
        if isinstance(kv_info, dict):
            migration = ("fallback" if kv_info.get("fallback")
                         else "imported")
        err_kind = ""
        if status != "ok":
            err_kind = (type(req.error).__name__
                        if req.error is not None else status)
        mesh_desc = ""
        if self.mesh is not None:
            mesh_desc = ",".join(f"{a}={int(s)}"
                                 for a, s in self.mesh.shape.items())
        record = {
            "trace_id": req.trace_id,
            "t_done": time.time(),
            "tenant": req.tenant,
            "kind": req.kind,
            "priority": req.priority,
            "replica": self.trace_source,
            "role": self.serve_role,
            "mesh": mesh_desc,
            "pp_depth": self._pp,
            "pp_stage": None,  # filled by per-stage launchers
            "weight_version": prov.get("version"),
            "weight_digest": prov.get("digest") or "",
            "prompt_tokens": len(req.prompt),
            "output_tokens": out_tokens,
            "max_new_tokens": req.max_new_tokens,
            "prefix_hit_tokens": req.prefix_hit_tokens,
            "kv_blocks": req.kv_blocks,
            "forks": forks,
            "n": req.n,
            "preemptions": req.preemptions,
            "migration": migration,
            "queue_wait_s": req.queue_wait_s,
            "prefill_device_s": (req.prefill_device_s
                                 if req.prefill_chunks else None),
            "prefill_chunks": req.prefill_chunks,
            "ttft_s": req.ttft,
            "latency_s": latency,
            "decode_iterations": (
                self.metrics.iterations - req.admit_iteration
                if req.admit_iteration is not None else None),
            "spec_drafted": req.spec_drafted,
            "spec_accepted": req.spec_accepted,
            "spec_accept_rate": (req.spec_accepted / req.spec_drafted
                                 if req.spec_drafted else None),
            "mask_uploads": req.mask_uploads,
            "constrained": int(req.constraint is not None),
            "cache_overtaken": int(req.cache_overtaken),
            "speculate": int(req.speculate),
            "temperature": req.temperature,
            "status": status,
            "error_kind": err_kind,
            "slo_verdict": "slow" if slow else "ok",
            "timeout_s": req.timeout,
            "stream": int(req.kind == "generate"),
        }
        self.wide_events.append(record)
