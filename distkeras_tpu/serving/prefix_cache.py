"""KV block pool: one fixed-size-block memory manager for prefix caching
AND decode slots.

Real serving traffic is dominated by shared prefixes — system prompts,
few-shot templates, multi-turn histories. :class:`KVBlockPool` is the
serving engine's ONLY KV memory manager: decode slots allocate their KV
in **blocks** (``block_tokens`` tokens each) from one pool, addressed
through per-slot block tables, and the same blocks, keyed by a radix
trie over the prompt's token blocks, are the prefix cache. So

- capacity scales with *actual* resident tokens, not
  ``slots × max_seq_len`` (no worst-case pre-reservation);
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
variables, ``[capacity, block_tokens, H*D]`` per layer) and are threaded
through its compiled programs; the pool decides *which rows mean what*,
and eviction is LRU over unreferenced trie leaves.

Why sharing is safe: in a causal LM the K/V at position ``p`` depend only
on tokens ``[0, p]``, so two prompts sharing a token prefix share that
prefix's K/V exactly. A block is only ever adopted from fully computed
positions and only ever matched by the exact token sequence (trie edges
are the block's token tuple), so a hit cannot alias a different prompt.

Ref-counting pins a matched chain for as long as a slot's block table
points at it; LRU eviction only considers nodes with no live references
and no children (evicting a mid-chain node would strand its
descendants).

NOT thread-safe: the trie and pool are mutated without locks, relying on
the owning :class:`~distkeras_tpu.serving.engine.ServingEngine`'s loop
serializing every call. Do not drive one pool from two concurrently
running engines.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools

import numpy as np

__all__ = ["KVBlockPool", "PrefixMatch"]


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
    """A pinned match: ``release()`` it (via :meth:`KVBlockPool.release`)
    once the matched blocks are no longer being read — when the slot
    whose table points at them frees/adopts."""

    nodes: list
    ids: np.ndarray  # pool slots of the matched chain, int32 [n]
    matched_tokens: int
    released: bool = False


def _register_trie_metrics(registry) -> dict:
    """The prefix-sharing metric family (hit/miss/eviction series)."""
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


class KVBlockPool:
    """Host-side allocator + prefix trie over ONE shared KV block pool —
    the engine's single memory manager for decode slots AND the prefix
    cache: a block allocator (free list + LRU eviction of unreferenced
    trie leaves) and a radix trie over token blocks
    (probe/match/release).

    This class owns NO device arrays: the pools (``[capacity,
    block_tokens, H*D]`` per layer K/V) are the paged module's cache
    variables, threaded through the engine's compiled programs. The
    pool hands out *row ids*:

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

    ``kv_pool_blocks_{total,used,free}`` gauges plus the
    ``prefix_cache_*`` hit/miss series publish into ``registry``
    (an optional :class:`~distkeras_tpu.telemetry.registry.
    MetricsRegistry`).
    """

    def __init__(self, capacity: int, block_tokens: int, *,
                 bytes_per_block: int = 0, registry=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got {block_tokens}")
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
        # set, multi-block allocation bursts (alloc(n),
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
        self.bytes_per_block = int(bytes_per_block)
        # High-water mark of blocks in use — what a byte budget must
        # actually cover.
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
        reader in flight (no pinned matches, no slot-owned blocks, and so
        no fork group still sharing rows): the engine's swap path
        guarantees that by running with zero active slots; any match
        object still held afterwards releases onto orphaned nodes,
        harmlessly."""
        self._fork_refs.clear()
        self._root = _Node(-1, None, None)
        self._by_slot.clear()
        self._free = list(range(self.capacity - 1, -1, -1))
        self._lru = []
        self.flushes += 1
        self.version += 1
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

    # -- slot allocation ----------------------------------------------------
    def alloc_spills(self, n: int) -> bool:
        """True when :meth:`alloc` of ``n`` rows cannot be host
        bookkeeping alone: the free list holds fewer, so it has to evict
        a trie leaf, and a spill hook is attached, so the eviction reads
        the victim's rows back from the device. The engine's growth and
        its admission ask before they allocate with a decode tick in
        flight."""
        return len(self._free) < n and (self.spill_many_hook is not None
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
        self._note_occupancy()

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
