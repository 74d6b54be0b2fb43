"""KV block pool: one fixed-size-block memory manager for prefix caching
AND paged decode slots.

Real serving traffic is dominated by shared prefixes — system prompts,
few-shot templates, multi-turn histories. The PR 1 engine recomputed every
request's KV cache from scratch; :class:`PrefixCache` (PR 3) lets
admission *reuse* the computation: the KV rows of previously-prefilled
prompt prefixes live in a fixed pool of **blocks** (``block_tokens``
tokens each), keyed by a radix trie over the prompt's token blocks, and a
cache hit splices the matched blocks straight into the request's prefill
cache with ``dynamic_update_slice``.

:class:`KVBlockPool` generalizes the same pool to be the engine's ONLY
KV memory manager (paged decode, PR 6): decode slots allocate their KV in
blocks from this pool too, addressed through per-slot block tables, so

- capacity scales with *actual* resident tokens, not
  ``slots × max_seq_len`` (no dense worst-case pre-reservation);
- a prefix-cache hit is **zero-copy**: the slot's block table simply
  points at the shared trie blocks (ref-counted so they cannot be
  evicted or overwritten from under a reader) — the copy-on-write
  discipline degenerates to "never write a shared block": sharing is
  block-aligned and appends always land in freshly allocated private
  blocks, so the copy case cannot arise by construction;
- a finished (or preempted) slot's complete blocks are **adopted** into
  the trie in place — prefix caching with no store copy at all;
- when the pool runs dry the engine can preempt a slot and requeue its
  request (blocks freed here, re-admission recomputes or re-matches the
  adopted chain).

``KVBlockPool`` is pure host bookkeeping — the device arrays live in the
engine's cache pytree (the paged module's ``pool_key``/``pool_value``
variables) and are threaded through its compiled programs; the pool
decides *which rows mean what*. ``PrefixCache`` keeps owning its device
arrays (the dense engine's splice/store path is unchanged).

Why sharing is safe: in a causal LM the K/V at position ``p`` depend only
on tokens ``[0, p]``, so two prompts sharing a token prefix share that
prefix's K/V exactly. A block is only ever stored/adopted from fully
computed positions and only ever matched by the exact token sequence
(trie edges are the block's token tuple), so a hit cannot alias a
different prompt.

Shape discipline (same stance as the engine's compiled programs): the
pool is ONE allocation per KV leaf, ``[capacity, block_tokens, H, D]``,
sized up-front from a **byte budget**; store/splice/materialize compile
once per power-of-two block-count bucket; eviction is pure host
bookkeeping (LRU over unreferenced trie leaves).

Ref-counting pins a matched chain for as long as a reader needs it (an
admission splicing it, or — paged — a slot whose block table points at
it); LRU eviction only considers nodes with no live references and no
children (evicting a mid-chain node would strand its descendants).

NOT thread-safe: the trie and pool are mutated without locks, relying on
the owning :class:`~distkeras_tpu.serving.engine.ServingEngine`'s loop
serializing every call. Do not drive one pool from two concurrently
running engines.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu.telemetry import named_program

__all__ = ["KVBlockPool", "PrefixCache", "PrefixMatch"]


def _store_fn(block_tokens, pool, cache, slots, off0):
    """Copy the ``len(slots)`` consecutive cache blocks starting at token
    ``off0`` into pool rows ``slots`` — ONE scatter per leaf for a whole
    insert. An insert's new blocks are always a contiguous suffix of the
    prompt's block chain (a trie node cannot exist without its parent),
    so one batched program replaces per-block stores — that matters on
    backends where donation cannot alias (CPU): each store call would
    otherwise copy the entire pool. ``slots`` is padded to a power-of-two
    bucket with out-of-range ids; ``mode="drop"`` discards those updates
    (and their clamped garbage source blocks) wholesale."""
    b = slots.shape[0]
    offs = off0 + jnp.arange(b, dtype=jnp.int32) * block_tokens

    def put(p, c):
        if p.shape[0] == 0:  # index-leaf placeholder: no pooled storage
            return p
        blk = jax.vmap(
            lambda o: lax.dynamic_slice(
                c[0], (o,) + (0,) * (c.ndim - 2),
                (block_tokens,) + c.shape[2:]))(offs)
        return p.at[slots].set(blk.astype(p.dtype), mode="drop")

    return jax.tree.map(put, pool, cache)


def _splice_fn(block_tokens, cache, pool, ids):
    """Write pool rows ``ids`` as the cache's token prefix
    ``[0, len(ids) * block_tokens)``. ``ids`` is a concrete-length vector,
    so one program compiles per (power-of-two-bucketed) match length; the
    gather + one leading ``dynamic_update_slice`` per leaf is the whole
    hit path — no attention, no matmuls."""

    def sp(c, p):
        if c.ndim == 1:  # index leaves: the prefill chunk sets these
            return c
        blk = p[ids]  # [n, block_tokens, ...]
        flat = blk.reshape((1, ids.shape[0] * block_tokens) + blk.shape[2:])
        return lax.dynamic_update_slice(
            c, flat.astype(c.dtype), (0,) * c.ndim)

    return jax.tree.map(sp, cache, pool)


def _materialize_fn(block_tokens, shapes, pool, ids):
    """Build a FRESH single-row cache whose token prefix is pool rows
    ``ids`` and whose tail is zeros — in one fused program per pow2
    bucket. The splice path this replaces first materialized a full
    max-length zeros cache (``_fresh_row_cache``) and then overwrote its
    prefix with a second (donating) program; on backends where donation
    cannot alias (CPU) that copied the whole leaf per admission. Here
    the spliced region is never built as zeros at all — gather + static
    pad, leaves the splice fully covers cost nothing extra."""

    def mk(s, p):
        if s.ndim == 1:  # index leaves: the prefill chunk sets these
            return jnp.zeros(s.shape, s.dtype)
        blk = p[ids]  # [n, block_tokens, ...]
        flat = blk.reshape(
            (1, ids.shape[0] * block_tokens) + blk.shape[2:]).astype(s.dtype)
        pad = [(0, 0), (0, s.shape[1] - flat.shape[1])]
        pad += [(0, 0)] * (s.ndim - 2)
        return jnp.pad(flat, pad)

    return jax.tree.map(mk, shapes, pool)


class _Node:
    """One trie edge = one cached block. Children are keyed by the next
    block's token tuple (exact-match radix trie)."""

    __slots__ = ("slot", "refs", "last_used", "parent", "key", "children")

    def __init__(self, slot: int, parent, key):
        self.slot = slot
        self.refs = 0
        self.last_used = 0
        self.parent = parent
        self.key = key
        self.children: dict = {}


@dataclasses.dataclass
class PrefixMatch:
    """A pinned match: ``release()`` it (via :meth:`PrefixCache.release`)
    once the matched blocks are no longer being read — after the splice
    (dense mode) or when the slot whose table points at them frees/adopts
    (paged mode)."""

    nodes: list
    ids: np.ndarray  # pool slots of the matched chain, int32 [n]
    matched_tokens: int
    released: bool = False


class _BlockTrie:
    """Shared core of both pool classes: the block allocator (free list +
    LRU eviction of unreferenced trie leaves) and the radix trie over
    token blocks (probe/match/release). Subclasses call
    :meth:`_init_trie` and provide ``_note_occupancy``."""

    def _init_trie(self, capacity: int, block_tokens: int) -> None:
        self.block_tokens = int(block_tokens)
        self.capacity = int(capacity)
        self._root = _Node(-1, None, None)
        self._by_slot: dict[int, _Node] = {}
        self._free = list(range(self.capacity - 1, -1, -1))
        self._clock = itertools.count(1)
        # Lazy LRU heap of (last_used, slot): every touch pushes a fresh
        # entry; _alloc pops, discarding entries whose stamp no longer
        # matches the node (stale) — amortized O(log n) eviction instead
        # of scanning every cached block per allocation.
        self._lru: list[tuple[int, int]] = []
        # Host-side stats (exact, source of truth for stats()).
        self.lookups = self.hit_requests = 0
        self.hit_tokens = self.miss_tokens = 0
        self.inserted_blocks = self.evicted_blocks = 0
        self.flushes = 0
        # Bumped whenever blocks become free or evictable — the engine's
        # "is it worth retrying a parked admission" heuristic.
        self.version = 0
        self._metrics: dict | None = None
        # Optional ``hook(chain_tokens, slot)`` called just before an
        # eviction victim's trie node is destroyed — the tiered-KV
        # engine uses it to spill the victim block (D2H) into the host
        # tier. Called while the victim's node is still intact (chain
        # reconstructible) and its pool row still holds the KV bytes.
        self.spill_hook = None
        # Batched variant, ``hook(list[(chain_tokens, slot)])``: when
        # set, multi-block allocation bursts (alloc(n), insert,
        # adopt_foreign) COLLECT their victims and fire one call at the
        # end of the burst — one D2H gather for the whole burst instead
        # of one per victim. The victims' pool rows still hold their KV
        # bytes at flush time: the burst only hands rows out, nothing
        # overwrites them until the caller scatters after the grant.
        # Takes precedence over ``spill_hook`` inside a burst;
        # single-victim paths still use ``spill_hook`` when no burst is
        # open.
        self.spill_many_hook = None
        self._spill_batch: list | None = None  # open burst's victims

    # -- introspection ------------------------------------------------------
    @property
    def blocks_used(self) -> int:
        return self.capacity - len(self._free)

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    def debugz(self, top: int = 16) -> dict:
        """Trie occupancy grouped by **prefix family** — the root's
        children, i.e. the distinct first blocks (system prompts,
        templates). Per family: subtree block/token counts, live pins,
        and chain depth, sorted by blocks so the page leads with the
        biggest resident; ``top`` bounds the list (the full family count
        is still reported). The occupancy view ``stats()`` can't give:
        WHICH prompts own the pool, not just how full it is."""
        fams = []
        for key, child in self._root.children.items():
            blocks = refs = depth = 0
            stack = [(child, 1)]
            while stack:
                n, d = stack.pop()
                blocks += 1
                refs += n.refs
                depth = max(depth, d)
                stack.extend((c, d + 1) for c in n.children.values())
            fams.append({
                # First 8 tokens of the family's first block: enough to
                # recognize a system prompt, bounded output regardless
                # of block size.
                "family_head": list(key[:8]),
                "blocks": blocks,
                "tokens": blocks * self.block_tokens,
                "pinned_refs": refs,
                "max_chain_depth": depth,
            })
        fams.sort(key=lambda f: (-f["blocks"], f["family_head"]))
        return {
            "blocks_used": self.blocks_used,
            "capacity_blocks": self.capacity,
            "block_tokens": self.block_tokens,
            "families": len(fams),
            "top_families": fams[:int(top)],
        }

    def flush(self) -> None:
        """Invalidate every cached block at once (weight reload: pooled
        K/V is a function of the weights, so a param swap makes all of it
        wrong). Host bookkeeping only — the device pools stay allocated
        and their rows are simply free to overwrite; cumulative hit/miss
        counters keep counting across the flush. Must be called with no
        reader in flight (no pinned matches and — paged — no slot-owned
        blocks): the engine's swap path guarantees that by running with
        zero active slots; any match object still held afterwards
        releases onto orphaned nodes, harmlessly."""
        self._root = _Node(-1, None, None)
        self._by_slot.clear()
        self._free = list(range(self.capacity - 1, -1, -1))
        self._lru = []
        self.flushes += 1
        self.version += 1
        if self._metrics is not None:
            self._note_occupancy()

    # -- trie walk ----------------------------------------------------------
    @staticmethod
    def _chain_tokens(node: _Node) -> list:
        """Full root→``node`` token chain, reconstructed by walking the
        parent links (each edge key is one block's token tuple)."""
        keys = []
        while node.parent is not None:
            keys.append(node.key)
            node = node.parent
        out = []
        for key in reversed(keys):
            out.extend(key)
        return out

    def _blocks(self, tokens, n_blocks: int):
        bt = self.block_tokens
        for i in range(n_blocks):
            yield tuple(int(t) for t in tokens[i * bt:(i + 1) * bt])

    def probe(self, tokens) -> int:
        """Matched-token count for ``tokens`` WITHOUT pinning or counting
        — the scheduler's cache-aware admission score."""
        node, matched = self._root, 0
        for key in self._blocks(tokens, self._match_cap(tokens)):
            node = node.children.get(key)
            if node is None:
                break
            matched += self.block_tokens
        return matched

    def _match_cap(self, tokens) -> int:
        # Never match the WHOLE prompt: prefill needs >= 1 uncached token
        # to produce the logits the first sampled token comes from.
        return max(0, (len(tokens) - 1) // self.block_tokens)

    def match(self, tokens) -> PrefixMatch:
        """Longest cached block-chain prefix of ``tokens``, pinned
        (ref-counted) until :meth:`release`."""
        self.lookups += 1
        node, chain = self._root, []
        for key in self._blocks(tokens, self._match_cap(tokens)):
            nxt = node.children.get(key)
            if nxt is None:
                break
            chain.append(nxt)
            node = nxt
        now = next(self._clock)
        for n in chain:
            n.refs += 1
            self._touch(n, now)
        matched = len(chain) * self.block_tokens
        self.hit_tokens += matched
        self.miss_tokens += len(tokens) - matched
        self.hit_requests += bool(chain)
        if self._metrics is not None:
            self._metrics["lookups"].inc()
            self._metrics["hit_tokens"].inc(matched)
            self._metrics["miss_tokens"].inc(len(tokens) - matched)
            if chain:
                self._metrics["hit_requests"].inc()
        return PrefixMatch(
            chain, np.asarray([n.slot for n in chain], np.int32), matched)

    def match_blocks(self, tokens) -> PrefixMatch:
        """Longest cached chain over ALL complete blocks of ``tokens``,
        pinned like :meth:`match` but WITHOUT the last-block holdback
        (:meth:`_match_cap`) and without touching the hit/miss stats —
        this is the EXPORT walk (kv_transfer): a peer adopting the
        chain wants the full resident prefix, and an export lookup is
        not an admission, so it must not skew the cache-efficiency
        series operators alert on."""
        node, chain = self._root, []
        for key in self._blocks(tokens, len(tokens) // self.block_tokens):
            nxt = node.children.get(key)
            if nxt is None:
                break
            chain.append(nxt)
            node = nxt
        now = next(self._clock)
        for n in chain:
            n.refs += 1
            self._touch(n, now)
        return PrefixMatch(
            chain, np.asarray([n.slot for n in chain], np.int32),
            len(chain) * self.block_tokens)

    def release(self, match: PrefixMatch | None) -> None:
        if match is None or match.released:
            return
        match.released = True
        for n in match.nodes:
            n.refs -= 1
        if match.nodes:
            self.version += 1  # pinned chains may have become evictable

    # -- eviction -----------------------------------------------------------
    def _touch(self, node: _Node, now: int) -> None:
        node.last_used = now
        heapq.heappush(self._lru, (now, node.slot))
        if len(self._lru) > 4 * self.capacity:
            # Stale entries are only consumed by _alloc, which a
            # hit-dominated workload (no inserts once warm) never runs —
            # compact to one live entry per node so the heap stays
            # O(capacity) over a long-running server, amortized O(1) per
            # touch (one rebuild per >= 3·capacity pushes).
            self._lru = [(n.last_used, n.slot)
                         for n in self._by_slot.values()]
            heapq.heapify(self._lru)

    def _alloc(self, protect: _Node | None) -> int | None:
        if self._free:
            return self._free.pop()
        victim, skipped = None, []
        while self._lru:
            stamp, slot = heapq.heappop(self._lru)
            n = self._by_slot.get(slot)
            if n is None or n.last_used != stamp:
                continue  # stale: slot was evicted or re-touched since
            if n.refs or n.children or n is protect:
                # Currently unevictable, but may become a leaf later
                # with no further touch — keep its entry alive.
                skipped.append((stamp, slot))
                continue
            victim = n
            break
        for item in skipped:
            heapq.heappush(self._lru, item)
        if victim is None:
            return None  # everything pinned or mid-chain
        if self._spill_batch is not None:
            # Inside a burst: collect the chain NOW (the node is about
            # to be unlinked) and spill at the burst's end in one call.
            self._spill_batch.append(
                (self._chain_tokens(victim), victim.slot))
        elif self.spill_hook is not None:
            # Spill BEFORE the node is unlinked: the hook needs the full
            # root→victim chain and the still-valid pool row. A hook
            # failure must never break allocation — the spill tier is an
            # optimization, the evicted block was always droppable.
            try:
                self.spill_hook(self._chain_tokens(victim), victim.slot)
            except Exception:  # pragma: no cover - defensive
                pass
        del victim.parent.children[victim.key]
        del self._by_slot[victim.slot]
        self.evicted_blocks += 1
        if self._metrics is not None:
            self._metrics["evictions"].inc()
        return victim.slot

    def _begin_spill_burst(self) -> bool:
        """Open a victim-collection burst (no-op without a batched
        hook, or when nested inside an already-open burst). Returns
        whether THIS call opened it — only the opener flushes."""
        if self.spill_many_hook is None or self._spill_batch is not None:
            return False
        self._spill_batch = []
        return True

    def _flush_spill_burst(self) -> None:
        """Fire the batched spill hook over the burst's victims. Runs
        before the allocating call returns, so every victim row still
        holds its KV bytes. Hook failures are swallowed like the
        per-victim hook's — spilling is an optimization."""
        batch, self._spill_batch = self._spill_batch, None
        if batch:
            try:
                self.spill_many_hook(batch)
            except Exception:  # pragma: no cover - defensive
                pass

    def _note_occupancy(self) -> None:  # pragma: no cover - overridden
        pass


class PrefixCache(_BlockTrie):
    """Device-owning block pool + radix trie over prompt prefixes — the
    DENSE engine's prefix cache (paged engines use :class:`KVBlockPool`,
    where the device arrays live in the engine's cache pytree instead).

    ``template``: the single-row decode cache pytree (concrete arrays or
    ``jax.eval_shape`` structs) — KV leaves ``[1, L, H, D]`` define the
    pool geometry; 1-D index leaves get no pooled storage.
    ``block_tokens``: granularity of sharing — smaller blocks match more
    of a prefix but cost more trie nodes and splice slots per hit.
    ``budget_bytes``: hard cap on pool memory; capacity =
    ``budget_bytes // bytes_per_block`` blocks, allocated up-front.
    ``registry``: optional :class:`~distkeras_tpu.telemetry.registry.
    MetricsRegistry` — hit/miss/eviction counters and occupancy gauges
    for ``metricsz``.
    ``mesh``: a serving mesh (GSPMD tensor-parallel engine) — the block
    pools are then allocated heads-sharded over the mesh's ``tp`` axis
    (:func:`distkeras_tpu.parallel.sharding.kv_pytree_shardings`, the
    same rule the engine applies to its batch cache) and the rows
    ``materialize``/``splice`` build are pinned to the engine's sharded
    row layout — a cache hit never moves KV bytes between devices, only
    row ids. Trie/allocator state is host bookkeeping either way.
    ``stage_meshes``: a pp engine's per-stage tp submeshes — ``template``
    is then a per-stage LIST of row subtrees (the engine's
    ``StagePlan.split_tree`` carve of the single-row cache), the pool
    becomes one per-stage pool placed on its stage's devices, and
    ``splice``/``materialize``/``insert`` take/return per-stage cache
    lists. ONE trie spans all stages: a block's trie node stands for the
    same token positions in every stage's pool, so the host bookkeeping
    (match/insert/evict) stays stage-agnostic while the bytes never
    leave their stage.
    """

    def __init__(self, template, *, block_tokens: int = 16,
                 budget_bytes: int = 64 * 2**20, registry=None, mesh=None,
                 stage_meshes=None):
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got {block_tokens}")
        self._stages = len(stage_meshes) if stage_meshes is not None else 0
        kv_leaves = [a for a in jax.tree.leaves(template) if a.ndim > 1]
        if not kv_leaves:
            raise ValueError("cache template has no KV leaves")
        L = kv_leaves[0].shape[1]
        if block_tokens > L:
            raise ValueError(
                f"block_tokens={block_tokens} exceeds cache length {L}")
        self.max_blocks = L // int(block_tokens)
        self.bytes_per_block = sum(
            int(block_tokens) * int(np.prod(a.shape[2:])) * a.dtype.itemsize
            for a in kv_leaves)
        capacity = int(budget_bytes) // self.bytes_per_block
        if capacity < 1:
            raise ValueError(
                f"budget_bytes={budget_bytes} holds zero blocks "
                f"(one block = {self.bytes_per_block} bytes)")
        self._init_trie(capacity, block_tokens)
        self.mesh = mesh

        def mk_pool(a):
            return (jnp.zeros((0,), jnp.int32) if a.ndim == 1 else
                    jnp.zeros((self.capacity, self.block_tokens)
                              + a.shape[2:], a.dtype))

        # Named for the profiler trace (a partial alone is jit__unknown).
        store_fn = named_program(
            functools.partial(_store_fn, self.block_tokens), "prefix_store")
        splice_fn = named_program(
            functools.partial(_splice_fn, self.block_tokens), "prefix_splice")
        if self._stages:
            from distkeras_tpu.parallel.sharding import kv_pytree_shardings

            self._pool = [jax.tree.map(mk_pool, part) for part in template]
            self._row_shapes = [
                jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), part)
                for part in template]
            pool_sh = [kv_pytree_shardings(m, p)
                       for m, p in zip(stage_meshes, self._pool)]
            row_sh = [kv_pytree_shardings(m, r)
                      for m, r in zip(stage_meshes, self._row_shapes)]
            self._pool = [jax.device_put(p, sh)
                          for p, sh in zip(self._pool, pool_sh)]
            self._store = [
                jax.jit(store_fn, donate_argnums=(0,), out_shardings=sh)
                for sh in pool_sh]
            self._splice = [
                jax.jit(splice_fn, donate_argnums=(0,), out_shardings=sh)
                for sh in row_sh]
            self._materialize = [
                jax.jit(named_program(
                    functools.partial(_materialize_fn, self.block_tokens,
                                      shapes), "prefix_materialize"),
                        out_shardings=sh)
                for shapes, sh in zip(self._row_shapes, row_sh)]
        else:
            self._pool = jax.tree.map(mk_pool, template)
            self._row_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), template)
            pool_sh = row_sh = None
            if mesh is not None:
                from distkeras_tpu.parallel.sharding import (
                    kv_pytree_shardings,
                )

                pool_sh = kv_pytree_shardings(mesh, self._pool)
                row_sh = kv_pytree_shardings(mesh, self._row_shapes)
                self._pool = jax.device_put(self._pool, pool_sh)
            self._store = jax.jit(
                store_fn, donate_argnums=(0,),
                **({} if mesh is None else {"out_shardings": pool_sh}))
            self._splice = jax.jit(
                splice_fn,
                donate_argnums=(0,),  # the cache being built; pool persists
                **({} if mesh is None else {"out_shardings": row_sh}))
            self._materialize = jax.jit(
                named_program(
                    functools.partial(_materialize_fn, self.block_tokens,
                                      self._row_shapes),
                    "prefix_materialize"),
                **({} if mesh is None else {"out_shardings": row_sh}))
        if registry is not None:
            self._metrics = _register_trie_metrics(registry)
            self._metrics["capacity"].set(self.capacity)

    def stats(self) -> dict:
        total = self.hit_tokens + self.miss_tokens
        return {
            "block_tokens": self.block_tokens,
            "capacity_blocks": self.capacity,
            "blocks_used": self.blocks_used,
            "bytes_used": self.blocks_used * self.bytes_per_block,
            "bytes_per_block": self.bytes_per_block,
            "lookups": self.lookups,
            "hit_requests": self.hit_requests,
            "hit_tokens": self.hit_tokens,
            "miss_tokens": self.miss_tokens,
            "hit_rate": (self.hit_tokens / total) if total else 0.0,
            "inserted_blocks": self.inserted_blocks,
            "evicted_blocks": self.evicted_blocks,
            "flushes": self.flushes,
        }

    # -- device ops ---------------------------------------------------------
    def _pad_ids(self, ids, fill: int) -> np.ndarray:
        """Pad a pool-row id list to its power-of-two bucket (capped at
        the per-cache block capacity) so store/splice compile once per
        bucket. ``fill`` picks the padding semantics: a valid row id
        (splice: reads garbage the mask hides) or an out-of-range id
        (store: ``mode=\"drop\"`` discards those writes)."""
        n = len(ids)
        b = 1
        while b < n:
            b *= 2
        b = min(b, self.max_blocks)
        out = np.full((b,), fill, np.int32)
        out[:n] = ids
        return out

    def splice(self, cache, ids: np.ndarray):
        """Return ``cache`` with pool rows ``ids`` written as its token
        prefix. ``ids`` is padded to a power-of-two bucket so compiles
        stay bounded; rows written past the true match are garbage the
        causal mask hides until the tail prefill / decode overwrites
        them. Donates ``cache``."""
        ids_dev = jnp.asarray(self._pad_ids(ids, 0))
        if self._stages:
            return [sp(c, p, ids_dev) for sp, c, p
                    in zip(self._splice, cache, self._pool)]
        return self._splice(cache, self._pool, ids_dev)

    def materialize(self, ids: np.ndarray):
        """Build a FRESH single-row cache with pool rows ``ids`` as its
        token prefix and zeros past it — the hit-path replacement for
        "allocate a full zeros cache, then splice": the leaves the
        splice covers are never materialized as zeros first (and never
        round-trip through a donation the backend may have to copy).
        Same pad-width bucketing as :meth:`splice`."""
        ids_dev = jnp.asarray(self._pad_ids(ids, 0))
        if self._stages:
            return [mk(p, ids_dev) for mk, p
                    in zip(self._materialize, self._pool)]
        return self._materialize(self._pool, ids_dev)

    def insert(self, tokens, cache) -> int:
        """Store every complete block of ``tokens`` not already cached,
        copying K/V rows out of the fully-prefilled single-row ``cache``
        in ONE batched device call. Allocation evicts LRU unreferenced
        leaves; when nothing is evictable the insert stops early (the
        chain must stay contiguous). Returns the newly stored count."""
        keys = list(self._blocks(tokens, len(tokens) // self.block_tokens))
        now = next(self._clock)
        node, idx = self._root, 0
        while idx < len(keys):  # walk (and touch) the existing prefix
            child = node.children.get(keys[idx])
            if child is None:
                break
            self._touch(child, now)
            node = child
            idx += 1
        take: list[int] = []
        opened = self._begin_spill_burst()
        try:
            for _ in keys[idx:]:
                slot = self._alloc(protect=node)
                if slot is None:
                    break
                take.append(slot)
        finally:
            if opened:
                self._flush_spill_burst()
        if not take:
            return 0
        n = len(take)
        ids_dev = jnp.asarray(self._pad_ids(take, self.capacity))
        off = jnp.int32(idx * self.block_tokens)
        if self._stages:
            self._pool = [st(p, c, ids_dev, off) for st, p, c
                          in zip(self._store, self._pool, cache)]
        else:
            self._pool = self._store(self._pool, cache, ids_dev, off)
        for key, slot in zip(keys[idx:idx + n], take):
            child = _Node(slot, node, key)
            node.children[key] = child
            self._by_slot[slot] = child
            self._touch(child, now)
            node = child
        self.inserted_blocks += n
        self.version += 1
        if self._metrics is not None:
            self._metrics["inserts"].inc(n)
            self._note_occupancy()
        return n

    def _note_occupancy(self) -> None:
        self._metrics["used"].set(self.blocks_used)
        self._metrics["bytes"].set(self.blocks_used * self.bytes_per_block)


def _register_trie_metrics(registry) -> dict:
    """The prefix-sharing metric family — shared by both pool classes so
    an operator reads ONE set of hit/miss/eviction series whether the
    engine runs dense (PrefixCache) or paged (KVBlockPool)."""
    return {
        "hit_tokens": registry.counter(
            "prefix_cache_hit_tokens_total",
            help="prompt tokens whose prefill was skipped via the "
                 "prefix cache"),
        "miss_tokens": registry.counter(
            "prefix_cache_miss_tokens_total",
            help="prompt tokens prefilled from scratch"),
        "hit_requests": registry.counter(
            "prefix_cache_hit_requests_total",
            help="lookups matching at least one block"),
        "lookups": registry.counter(
            "prefix_cache_lookups_total", help="prefix lookups"),
        "evictions": registry.counter(
            "prefix_cache_evicted_blocks_total",
            help="blocks evicted (LRU under the byte budget)"),
        "inserts": registry.counter(
            "prefix_cache_inserted_blocks_total",
            help="blocks stored/adopted into the prefix trie"),
        "used": registry.gauge(
            "prefix_cache_blocks_used", help="pool blocks in use"),
        "capacity": registry.gauge(
            "prefix_cache_blocks_capacity",
            help="pool block capacity"),
        "bytes": registry.gauge(
            "prefix_cache_bytes_used", help="pool bytes in use"),
    }


class KVBlockPool(_BlockTrie):
    """Host-side allocator + prefix trie over ONE shared KV block pool —
    the paged engine's single memory manager for decode slots AND the
    prefix cache.

    Unlike :class:`PrefixCache` this class owns NO device arrays: the
    pools (``[capacity, block_tokens, H*D]`` per layer K/V) are the
    paged module's cache variables, threaded through the engine's
    compiled programs. The pool hands out *row ids*:

    - :meth:`alloc` — take ``n`` private blocks for a slot (all-or-
      nothing; evicts LRU unreferenced trie leaves when the free list is
      dry; ``None`` means the caller must preempt someone or park);
    - :meth:`free` — return private blocks;
    - :meth:`match`/:meth:`release` — pin/unpin a shared prefix chain
      (the slot's block table points at the pinned rows, zero-copy);
    - :meth:`adopt` — a finished/preempted slot's complete blocks become
      trie nodes in place (zero-copy prefix-cache insert); already-
      cached duplicates are freed instead.

    Write-sharing is impossible by construction (block-aligned matches;
    appends go to private blocks), so the copy-on-write refcount's only
    job is to keep shared rows from being evicted/reallocated under a
    reader — there is never a copy to make.

    ``kv_pool_blocks_{total,used,free}`` gauges plus the shared
    ``prefix_cache_*`` hit/miss series publish into ``registry``.
    """

    def __init__(self, capacity: int, block_tokens: int, *,
                 bytes_per_block: int = 0, registry=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got {block_tokens}")
        self._init_trie(capacity, block_tokens)
        self.bytes_per_block = int(bytes_per_block)
        # High-water mark of blocks in use — what a byte budget must
        # actually cover; serving_bench turns it into tokens-per-byte.
        self.peak_blocks_used = 0
        # Copy-on-write sharing for forked sampling (kind="sample"):
        # ``_fork_refs[row] = k`` means k ADDITIONAL owners share the row
        # beyond the one that will eventually free it last. ``free`` on
        # such a row decrements instead of returning it to the free list
        # — the row truly frees only when its last owner lets go. Rows
        # here are PRIVATE slot rows (complete, never-again-written
        # prompt blocks shared by n fork rows), distinct from trie
        # pinning (``_Node.refs``), which protects SHARED trie rows.
        self._fork_refs: dict[int, int] = {}
        self.forked_blocks_total = 0  # cumulative extra shares handed out
        self.fork_cow_copies = 0      # tail blocks copied at fork time
        self._g_pool = None
        if registry is not None:
            self._metrics = _register_trie_metrics(registry)
            self._metrics["capacity"].set(self.capacity)
            self._g_pool = {
                "total": registry.gauge(
                    "kv_pool_blocks_total",
                    help="KV block pool capacity (blocks)"),
                "used": registry.gauge(
                    "kv_pool_blocks_used",
                    help="KV blocks held by decode slots or the prefix "
                         "trie"),
                "free": registry.gauge(
                    "kv_pool_blocks_free", help="KV blocks on the free "
                                                "list"),
                # A total and not a gauge: its delta over a window, over
                # the decode iterations' delta and the capacity, is the
                # pool's mean fill while the window decoded.
                "block_ticks": registry.counter(
                    "kv_pool_block_ticks_total",
                    help="KV blocks in use, summed over decode loop "
                         "iterations"),
            }
            self._g_pool["total"].set(self.capacity)
            self._note_occupancy()

    def stats(self) -> dict:
        total = self.hit_tokens + self.miss_tokens
        return {
            "block_tokens": self.block_tokens,
            "capacity_blocks": self.capacity,
            "blocks_used": self.blocks_used,
            "blocks_free": self.blocks_free,
            "peak_blocks_used": self.peak_blocks_used,
            "bytes_per_block": self.bytes_per_block,
            "bytes_used": self.blocks_used * self.bytes_per_block,
            "lookups": self.lookups,
            "hit_requests": self.hit_requests,
            "hit_tokens": self.hit_tokens,
            "miss_tokens": self.miss_tokens,
            "hit_rate": (self.hit_tokens / total) if total else 0.0,
            "inserted_blocks": self.inserted_blocks,
            "evicted_blocks": self.evicted_blocks,
            "flushes": self.flushes,
            "fork_shared_blocks": len(self._fork_refs),
            "forked_blocks_total": self.forked_blocks_total,
            "fork_cow_copies": self.fork_cow_copies,
        }

    # -- slot allocation ----------------------------------------------------
    @property
    def next_alloc_spills(self) -> bool:
        """True when the next :meth:`alloc` cannot be host bookkeeping
        alone: the free list is empty, so it has to evict a trie leaf,
        and a spill hook is attached, so the eviction reads the victim's
        rows back from the device. The engine's growth asks before it
        allocates with a decode tick in flight."""
        return not self._free and (self.spill_many_hook is not None
                                   or self.spill_hook is not None)

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` private block rows, evicting LRU unreferenced trie
        leaves as needed. All-or-nothing: on shortfall every row taken is
        returned and ``None`` comes back (the caller preempts a slot or
        parks the request) — a partial grant would leave a slot unable
        to write its next token with no way to make progress."""
        if n <= 0:
            return []
        got: list[int] = []
        opened = self._begin_spill_burst()
        try:
            while len(got) < n:
                slot = self._alloc(protect=None)
                if slot is None:
                    self._free.extend(got)
                    return None
                got.append(slot)
        finally:
            # Flush even on shortfall: the victims were evicted either
            # way, and their rows (returned to the free list unwritten)
            # still hold the bytes to spill.
            if opened:
                self._flush_spill_burst()
        self.peak_blocks_used = max(self.peak_blocks_used, self.blocks_used)
        if self._metrics is not None:
            self._note_occupancy()
        return got

    def fork(self, ids, n: int) -> None:
        """Register ``n - 1`` additional owners for each private row in
        ``ids`` — the copy-on-write share under forked sampling: one
        prefill's complete prompt blocks are pointed at by all ``n`` fork
        rows' block tables, and each fork :meth:`free`\\ s them at its own
        teardown. Complete blocks are never written again (appends go to
        fresh private tail blocks), so sharing needs no copy — the only
        copy-on-write moment is the PARTIAL tail block, which the engine
        duplicates per fork at fork time (:attr:`fork_cow_copies`)."""
        extra = max(0, int(n) - 1)
        if not extra or not len(ids):
            return
        for i in ids:
            self._fork_refs[int(i)] = self._fork_refs.get(int(i), 0) + extra
        self.forked_blocks_total += extra * len(ids)

    def note_cow_copy(self, n: int = 1) -> None:
        """Count ``n`` tail blocks physically copied at fork time (the
        divergent-write half of copy-on-write)."""
        self.fork_cow_copies += int(n)

    def free(self, ids) -> None:
        """Return private rows to the free list. Only rows handed out by
        :meth:`alloc` and not since adopted may be freed. Rows shared
        across fork groups (:meth:`fork`) decrement their extra-owner
        count instead — the row returns to the free list only on its
        LAST owner's free, which keeps block accounting exact under
        copy-on-write sampling."""
        if not len(ids):
            return
        released: list[int] = []
        for i in ids:
            i = int(i)
            extra = self._fork_refs.get(i)
            if extra:
                if extra == 1:
                    del self._fork_refs[i]
                else:
                    self._fork_refs[i] = extra - 1
                continue
            released.append(i)
        if not released:
            return
        self._free.extend(released)
        self.version += 1
        if self._metrics is not None:
            self._note_occupancy()

    def flush(self) -> None:
        """Pool flush additionally clears fork shares: a flush runs with
        zero active slots, so no fork group can still own rows."""
        self._fork_refs.clear()
        super().flush()

    def adopt(self, tokens, ids, first_block: int) -> int:
        """Zero-copy prefix-cache insert: chain the slot's private rows
        ``ids`` — holding the K/V of ``tokens``' blocks ``first_block,
        first_block+1, ...`` — into the trie, making them shareable (and
        evictable once unreferenced). Blocks the trie already holds (a
        concurrent request cached the same prefix first) free our
        duplicate row instead; rows past ``tokens``' complete blocks are
        freed too. Returns the count actually adopted."""
        keys = list(self._blocks(tokens, len(tokens) // self.block_tokens))
        node = self._root
        for key in keys[:first_block]:
            child = node.children.get(key)
            if child is None:
                # The matched prefix chain this slot hung off was flushed
                # or evicted out from under a non-pinned walk — cannot
                # attach a disconnected suffix; just free the rows.
                self.free(ids)
                return 0
            node = child
        now = next(self._clock)
        adopted = 0
        extra: list[int] = []
        for key, slot in zip(keys[first_block:], ids):
            child = node.children.get(key)
            if child is not None:
                extra.append(int(slot))  # duplicate: cached copy wins
                self._touch(child, now)
                node = child
                continue
            child = _Node(int(slot), node, key)
            node.children[key] = child
            self._by_slot[int(slot)] = child
            self._touch(child, now)
            node = child
            adopted += 1
        tail = len(keys) - first_block
        extra.extend(int(s) for s in ids[max(0, tail):])
        if extra:
            self.free(extra)
        self.inserted_blocks += adopted
        self.version += 1  # adopted rows are now evictable
        if self._metrics is not None:
            if adopted:
                self._metrics["inserts"].inc(adopted)
            self._note_occupancy()
        return adopted

    def adopt_foreign(self, tokens, n_blocks: int):
        """Receiving half of a KV block migration (kv_transfer): chain
        the first ``n_blocks`` complete blocks of ``tokens`` into the
        trie, allocating a fresh pool row for each block not already
        resident. Returns ``(uploads, resident_blocks)``: ``uploads``
        is the ``(block_index, pool_row)`` list the engine must scatter
        the payload's data into (already-cached duplicates need no
        upload — the resident copy is bit-identical by the provenance
        contract), and ``resident_blocks`` is the contiguous prefix now
        matchable. A dry pool stops the walk early — the contiguous
        prefix adopted so far still serves, and adoption NEVER evicts a
        decode slot's blocks or preempts local work (foreign blocks
        must only ever help): only unreferenced trie leaves may be
        reclaimed, exactly like a local insert."""
        keys = list(self._blocks(tokens, int(n_blocks)))
        node = self._root
        now = next(self._clock)
        uploads: list[tuple[int, int]] = []
        resident = 0
        opened = self._begin_spill_burst()
        try:
            for i, key in enumerate(keys):
                child = node.children.get(key)
                if child is None:
                    slot = self._alloc(protect=node)
                    if slot is None:
                        break  # pool dry: keep the contiguous prefix
                    child = _Node(slot, node, key)
                    node.children[key] = child
                    self._by_slot[slot] = child
                    self.inserted_blocks += 1
                    uploads.append((i, slot))
                self._touch(child, now)
                node = child
                resident += 1
        finally:
            if opened:
                self._flush_spill_burst()
        if uploads:
            self.peak_blocks_used = max(self.peak_blocks_used,
                                        self.blocks_used)
            self.version += 1
            if self._metrics is not None:
                self._metrics["inserts"].inc(len(uploads))
                self._note_occupancy()
        return uploads, resident

    def note_decode_tick(self) -> None:
        """Once per decode loop iteration (the engine's): add the blocks
        in use to ``kv_pool_block_ticks_total``."""
        if self._g_pool is not None:
            self._g_pool["block_ticks"].inc(self.blocks_used)

    def _note_occupancy(self) -> None:
        if self._metrics is not None:
            self._metrics["used"].set(self.blocks_used)
            self._metrics["bytes"].set(
                self.blocks_used * self.bytes_per_block)
        if self._g_pool is not None:
            self._g_pool["used"].set(self.blocks_used)
            self._g_pool["free"].set(self.blocks_free)
