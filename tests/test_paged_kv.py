"""The KV cache: one block pool for decode slots AND the prefix cache.

The invariants under test, all on CPU with a tiny causal LM:

- greedy streams are token-identical to offline ``generate()``, from a
  budgeted pool and from the pool an engine derives when given no
  budget, including prefix-cache hit/miss and evict-round-trip cases
  (the pool doubles as the prefix cache);
- the single-compiled-decode-step invariant survives paging: an ARMED
  ``RecompileAuditor`` stays silent across admissions, block-table
  growth, preemptions, and long-context requests;
- oversubscription: a pool sized to force preemption under load still
  completes every request token-identically (preempt -> adopt blocks ->
  requeue -> resume prefill folds streamed tokens back in), and the
  request's timeline shows both admission hops under one trace_id;
- long-context admission: a request longer than the context a
  whole-context-per-slot pool of the same bytes affords is served to
  completion because blocks chain on demand instead of being
  pre-reserved;
- requests that can NEVER fit the pool are rejected with the typed
  ``kv_oom`` error at submit, before any device work;
- pool health is observable: ``kv_pool_blocks_{total,used,free}``
  gauges, ``kv_preemptions_total`` / ``kv_oom_rejections_total``
  counters, and per-slot block-table depth in ``debugz``.

``KVBlockPool`` unit behavior (alloc/free/adopt/match, refcounts, LRU
eviction, registry metrics; model-free) rides along at the top — it is
the host-side allocator and prefix trie everything above leans on.
"""

import asyncio

import jax
import numpy as np
import pytest

from distkeras_tpu.inference.generate import generate
from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.serving import (
    KVBlockPool,
    PoolExhausted,
    ServingEngine,
    ServingMetrics,
)

VOCAB = 64


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _want(lm, prompt, n):
    model, variables = lm
    return generate(model, variables, np.asarray([prompt], np.int32), n,
                    greedy=True)[0].tolist()


async def _run_engine(engine, coro):
    task = asyncio.create_task(engine.run())
    try:
        return await coro
    finally:
        engine.shutdown(drain=True)
        await task


# -- KVBlockPool unit behavior (model-free) ----------------------------------

def test_block_pool_alloc_is_all_or_nothing():
    pool = KVBlockPool(4, 2)
    got = pool.alloc(3)
    assert len(got) == 3 and pool.blocks_free == 1
    # Shortfall: nothing is kept, the partial grant is rolled back.
    assert pool.alloc(2) is None
    assert pool.blocks_free == 1
    pool.free(got)
    assert pool.blocks_free == 4


def test_block_pool_adopt_then_match_zero_copy():
    """A finished slot's complete blocks become trie nodes IN PLACE: a
    later match returns the same pool row ids (no store copy), pinned so
    alloc-side eviction cannot reallocate them."""
    pool = KVBlockPool(4, 2)
    ids = pool.alloc(3)
    tokens = [1, 2, 3, 4, 5]  # blocks (1,2), (3,4); 5 is incomplete
    adopted = pool.adopt(tokens, ids, 0)
    assert adopted == 2
    # The incomplete tail block's row went back to the free list.
    assert pool.blocks_free == 2
    m = pool.match([1, 2, 3, 4, 9, 9])
    assert m.matched_tokens == 4
    assert list(m.ids) == [int(i) for i in ids[:2]]  # the SAME rows
    # Pinned rows survive allocation pressure (all-or-nothing fails
    # rather than evicting a pinned chain).
    assert pool.alloc(3) is None
    pool.release(m)
    assert len(pool.alloc(3)) == 3  # now the LRU chain was evictable


def test_block_pool_adopt_duplicate_frees_loser():
    """Two slots computing the same prefix: the second adoption keeps the
    cached copy and frees its duplicate rows."""
    pool = KVBlockPool(4, 2)
    a = pool.alloc(1)
    assert pool.adopt([1, 2], a, 0) == 1
    b = pool.alloc(1)
    assert pool.adopt([1, 2], b, 0) == 0  # duplicate: cached copy wins
    assert pool.blocks_free == 3  # b's row was freed, a's retained
    assert pool.match([1, 2, 7]).matched_tokens == 2


def test_block_pool_version_moves_on_free_and_adopt():
    """The engine's admission-parking heuristic watches ``version``: it
    must move whenever blocks become free or evictable."""
    pool = KVBlockPool(4, 2)
    v0 = pool.version
    ids = pool.alloc(2)
    pool.free(ids)
    assert pool.version > v0
    v1 = pool.version
    ids = pool.alloc(1)
    pool.adopt([1, 2], ids, 0)
    assert pool.version > v1


BT = 4  # block tokens of the trie tests below


def _cache_chain(pool, tokens):
    """What a finished slot does: take a private row for every complete
    block of ``tokens`` and adopt the chain into the trie. Returns the
    blocks adopted (0 when the pool cannot give the rows)."""
    ids = pool.alloc(len(tokens) // pool.block_tokens)
    return 0 if ids is None else pool.adopt(tokens, ids, 0)


def test_block_pool_match_caps_below_the_whole_prompt():
    pool = KVBlockPool(4, BT)
    prompt = list(range(10))  # 2 complete blocks + ragged tail
    assert _cache_chain(pool, prompt) == 2
    m = pool.match(prompt)
    assert m.matched_tokens == 2 * BT and len(m.ids) == 2
    pool.release(m)
    # A prompt that IS exactly the cached blocks never fully matches:
    # prefill needs >= 1 uncached token to produce the first logits.
    m = pool.match(prompt[:8])
    assert m.matched_tokens == BT
    pool.release(m)
    # Diverging after one block matches only the shared block.
    m = pool.match(prompt[:4] + [99, 98, 97, 96, 95])
    assert m.matched_tokens == BT
    pool.release(m)
    assert pool.probe(prompt) == 2 * BT  # probe agrees, no pinning
    s = pool.stats()
    assert s["lookups"] == 3 and s["hit_requests"] == 3
    assert s["blocks_used"] == 2


def test_block_pool_refcount_blocks_eviction_until_release():
    pool = KVBlockPool(2, BT)
    a, b = [1] * 9, [2] * 9  # 2 complete blocks each: the whole pool
    assert _cache_chain(pool, a) == 2
    m = pool.match(a)  # pins both blocks
    assert _cache_chain(pool, b) == 0  # everything pinned
    assert pool.stats()["evicted_blocks"] == 0
    pool.release(m)
    assert _cache_chain(pool, b) == 2  # LRU-evicts a's chain
    assert pool.stats()["evicted_blocks"] == 2
    assert pool.probe(a) == 0 and pool.probe(b) == 2 * BT
    assert pool.blocks_used == 2  # never exceeds the capacity


def test_block_pool_lru_prefers_least_recently_used_leaf():
    pool = KVBlockPool(2, BT)
    a, b = [1] * 5, [2] * 5  # one block each
    _cache_chain(pool, a)
    _cache_chain(pool, b)
    pool.release(pool.match([1] * 5))  # touch a: b becomes the LRU leaf
    _cache_chain(pool, [3] * 5)
    assert pool.probe([1] * 4 + [0]) == BT  # a survived
    assert pool.probe([2] * 4 + [0]) == 0  # b evicted
    assert pool.probe([3] * 4 + [0]) == BT


def test_block_pool_registry_metrics_published():
    from distkeras_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    pool = KVBlockPool(2, BT, registry=reg)
    _cache_chain(pool, [1] * 9)
    pool.release(pool.match([1] * 9))
    snap = reg.snapshot()
    assert snap["prefix_cache_blocks_capacity"]["value"] == 2
    assert snap["prefix_cache_blocks_used"]["value"] == 2
    assert snap["prefix_cache_hit_tokens_total"]["value"] == 2 * BT
    assert snap["prefix_cache_inserted_blocks_total"]["value"] == 2
    assert snap["prefix_cache_lookups_total"]["value"] == 1
    assert snap["kv_pool_blocks_total"]["value"] == 2
    assert snap["kv_pool_blocks_free"]["value"] == 0


def _pool_with_two_cached_chains():
    """A full 4-block pool whose rows all sit in unpinned trie chains
    ([1,2]->[1,2,3,4] and [5,6]->[5,6,7,8]): any further alloc must
    evict."""
    pool = KVBlockPool(4, 2)
    a = pool.alloc(2)
    assert pool.adopt([1, 2, 3, 4], a, 0) == 2
    b = pool.alloc(2)
    assert pool.adopt([5, 6, 7, 8], b, 0) == 2
    assert pool.blocks_free == 0
    return pool


def test_block_pool_spill_many_batches_the_eviction_burst():
    """With ``spill_many_hook`` set, a multi-block alloc's eviction
    victims arrive in ONE call (the tiered engine turns that into one
    D2H gather) — and the batch matches the per-victim ``spill_hook``
    sequence exactly, victim for victim."""
    pool = _pool_with_two_cached_chains()
    batches: list[list] = []
    pool.spill_many_hook = lambda victims: batches.append(list(victims))
    # The batched hook takes precedence inside the burst: the
    # per-victim hook must stay silent.
    singles: list[tuple] = []
    pool.spill_hook = lambda chain, slot: singles.append((chain, slot))
    got = pool.alloc(4)
    assert got is not None and len(got) == 4
    assert len(batches) == 1 and len(batches[0]) == 4
    assert singles == []
    # Parity: the identically-built pool with ONLY the per-victim hook
    # spills the same (chain, slot) sequence, one call per victim.
    pool2 = _pool_with_two_cached_chains()
    pool2.spill_hook = lambda chain, slot: singles.append((chain, slot))
    assert pool2.alloc(4) is not None
    assert ([(list(c), s) for c, s in batches[0]]
            == [(list(c), s) for c, s in singles])
    # Every victim carried its full root->leaf chain.
    chains = sorted(tuple(c) for c, _ in batches[0])
    assert chains == [(1, 2), (1, 2, 3, 4), (5, 6), (5, 6, 7, 8)]


def test_block_pool_spill_burst_flushes_even_on_shortfall():
    """An alloc that fails midway already evicted its victims; the
    burst must still hand them to the spill tier (the rows go back to
    the free list unwritten, so the bytes are intact at flush time)."""
    pool = KVBlockPool(3, 2)
    a = pool.alloc(1)
    assert pool.adopt([1, 2], a, 0) == 1  # evictable chain
    b = pool.alloc(1)
    assert pool.adopt([3, 4], b, 0) == 1
    m = pool.match([3, 4, 9])  # pins [3,4]: unevictable
    assert m.matched_tokens == 2
    batches: list[list] = []
    pool.spill_many_hook = lambda victims: batches.append(list(victims))
    assert pool.alloc(3) is None  # 1 free + 1 evictable < 3
    assert len(batches) == 1
    assert [(list(c), s) for c, s in batches[0]] == [([1, 2], a[0])]
    pool.release(m)


# -- the pool leaf [C, bt, H*D]: written and read as stored -------------------

@pytest.mark.parametrize("S,head_groups", [
    (1, 1),    # a decode tick: heads stay folded
    (1, 2),    # the same under tp=2: two groups of heads, never mixed
    (5, 1),    # a speculative verify window, still folded
    (64, 1),   # a prefill chunk: heads split on the chunk's own gather
])
def test_paged_ops_match_dense_attention_over_the_same_kv(S, head_groups):
    import jax.numpy as jnp

    from distkeras_tpu.ops.attention import (
        dot_product_attention,
        paged_attention,
        paged_kv_update,
    )

    B, H, D, bt, T, C = 3, 4, 8, 4, 20, 70
    L = T * bt
    r = np.random.default_rng(S * 10 + head_groups)
    dense_k, dense_v = (r.standard_normal((B, L, H, D)).astype(np.float32)
                        for _ in range(2))
    q, new_k, new_v = (jnp.asarray(r.standard_normal((B, S, H, D)),
                                   jnp.float32) for _ in range(3))
    tables = r.permutation(C)[:B * T].reshape(B, T).astype(np.int32)
    positions = np.asarray([0, 7, L - S], np.int32)
    # The pool holds each row's resident K/V at its table's blocks, a
    # token's heads side by side; blocks no table names hold junk.
    pool_k, pool_v = (r.standard_normal((C, bt, H * D)).astype(np.float32)
                      for _ in range(2))
    for pool, dense in ((pool_k, dense_k), (pool_v, dense_v)):
        pool[tables] = dense.reshape(B, T, bt, H * D)
    # A row whose table is all sentinel writes nothing.
    tables[1] = C
    before = pool_k.copy()
    pk = paged_kv_update(jnp.asarray(pool_k), new_k, jnp.asarray(tables),
                         jnp.asarray(positions), bt)
    pv = paged_kv_update(jnp.asarray(pool_v), new_v, jnp.asarray(tables),
                         jnp.asarray(positions), bt)
    assert pk.shape == (C, bt, H * D)
    changed = np.flatnonzero((np.asarray(pk) != before).any(axis=(1, 2)))
    assert set(changed) <= set(tables[[0, 2]].ravel())
    got = paged_attention(q, pk, pv, jnp.asarray(tables),
                          jnp.asarray(positions), head_groups=head_groups)
    # The dense twin: the same writes at the same positions, same mask.
    for b in (0, 2):
        p = int(positions[b])
        dense_k[b, p:p + S] = np.asarray(new_k[b])
        dense_v[b, p:p + S] = np.asarray(new_v[b])
    q_pos = positions[:, None] + np.arange(S)[None, :]
    mask = np.arange(L)[None, None, None, :] <= q_pos[:, None, :, None]
    want = dot_product_attention(q, jnp.asarray(dense_k),
                                 jnp.asarray(dense_v), mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], rtol=2e-6, atol=2e-6)


def test_engine_pool_leaves_fold_heads_into_the_minor_dimension(lm):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, kv_pool_blocks=12,
                           kv_block_tokens=4)
    cfg = model.config
    leaves = [l for l in jax.tree.leaves(engine._cache) if l.ndim > 1]
    assert len(leaves) == 2 * cfg.num_layers
    assert {l.shape for l in leaves} == {(12, 4, cfg.hidden_size)}


# -- the pool's size ----------------------------------------------------------

def test_pool_capacity_from_a_byte_budget(lm):
    model, variables = lm
    one = ServingEngine(model, variables, slots=1, kv_pool_blocks=1,
                        kv_block_tokens=4)
    per_block = one.kv_pool.bytes_per_block
    # K and V of 4 tokens through every layer, as the pool stores them.
    assert per_block == sum(
        l.nbytes for l in jax.tree.leaves(one._cache) if l.ndim > 1)
    engine = ServingEngine(model, variables, slots=1, kv_block_tokens=4,
                           kv_pool_mb=(3 * per_block + 1) / 2**20)
    assert engine.kv_pool.capacity == 3
    with pytest.raises(ValueError, match="holds zero"):
        ServingEngine(model, variables, slots=1, kv_block_tokens=4,
                      kv_pool_mb=7 / 2**20)
    with pytest.raises(ValueError, match="capacity"):
        KVBlockPool(0, 4)


def test_engine_given_no_budget_holds_a_whole_context_for_every_slot(
        lm, rng):
    """With neither ``kv_pool_mb`` nor ``kv_pool_blocks`` the pool is
    ``slots x ceil(limit / kv_block_tokens)`` blocks: every slot serves
    its full context at once, nothing is preempted, and the streams are
    generate()'s."""
    model, variables = lm  # trained context 32
    engine = ServingEngine(model, variables, slots=3, max_queue=8,
                           kv_block_tokens=5)
    assert engine.kv_pool.capacity == 3 * 7  # ceil(32 / 5) a slot
    capped = ServingEngine(model, variables, slots=2, max_context=16,
                           kv_block_tokens=4)
    assert capped.kv_pool.capacity == 2 * 4
    prompts = [_prompt(rng, n) for n in (20, 7, 12, 9)]
    new = [32 - len(p) for p in prompts]  # each to the end of its context

    async def work():
        reqs = [engine.submit(p, n) for p, n in zip(prompts, new)]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, n) for p, n in zip(prompts, new)]
    assert engine.metrics.preemptions == 0
    assert engine.metrics.oom_rejections == 0
    assert 0 < engine.kv_pool.peak_blocks_used <= engine.kv_pool.capacity
    assert engine.decode_compile_count() in (1, -1)


@pytest.mark.parametrize("removed", ["prefix_cache_mb", "prefix_block_tokens",
                                     "prefix_cache", "paged"])
def test_engine_refuses_the_removed_layout_options(lm, removed):
    """A stale deployment script fails at start, not silently: the
    options that selected or sized the dense cache are gone."""
    model, variables = lm
    with pytest.raises(TypeError, match=removed):
        ServingEngine(model, variables, slots=1, **{removed: 1})


# -- parity + the compile invariant -------------------------------------------

def test_parity_vs_generate_with_armed_auditor(lm, rng):
    """THE tentpole invariant: greedy output is token-identical to
    offline generate(), from a budgeted pool and from the pool an
    engine derives for itself, across staggered admissions into freed
    slots — and the ARMED auditor proves the decode step compiled
    exactly once while block tables changed under it every admission."""
    from distkeras_tpu.telemetry import RecompileAuditor

    model, variables = lm
    auditor = RecompileAuditor()
    paged = ServingEngine(model, variables, slots=2, max_queue=8,
                          kv_pool_blocks=64, kv_block_tokens=4,
                          auditor=auditor, arm_auditor_after_warmup=True)
    derived = ServingEngine(model, variables, slots=2, max_queue=8)
    prompts = [_prompt(rng, n) for n in (5, 9, 3, 7, 4)]

    async def work(engine):
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(engine.submit(p, 6))
            await asyncio.sleep(0.01 * i)  # arrive mid-decode, post-arming
        return [await r.result() for r in reqs]

    got_paged = asyncio.run(_run_engine(paged, work(paged)))
    got_derived = asyncio.run(_run_engine(derived, work(derived)))
    want = [_want(lm, p, 6) for p in prompts]
    assert got_paged == want
    assert got_derived == want
    assert auditor.compiles("serving_decode") == 1
    assert auditor.report()["serving_decode"]["armed"]
    assert paged.decode_compile_count() in (1, -1)
    # Slot teardown adopted every finished sequence's complete blocks
    # into the trie; nothing leaked to a non-free, non-trie limbo.
    assert paged.active_slots == 0
    assert all((t == paged._sentinel).all() for t in paged._tables)


@pytest.mark.parametrize("prefill_chunk", [4, None])
def test_paged_prefix_hits_are_zero_copy_and_parity_exact(lm, rng,
                                                          prefill_chunk):
    """Prefix caching is inherent: repeated prompt prefixes match the
    blocks ADOPTED from earlier slots (no store copy ever ran) and the
    hit's output stays token-identical to generate(), under chunked and
    under monolithic admission."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=16,
                           kv_pool_blocks=32, kv_block_tokens=4,
                           prefill_chunk=prefill_chunk)
    shared = _prompt(rng, 12)
    prompts = [shared + _prompt(rng, k) for k in (3, 4, 5, 3)]

    async def drive():
        outs = []
        for p in prompts:  # sequential: later prompts hit earlier ones
            outs.append(await engine.submit(p, 5).result())
        return outs

    outs = asyncio.run(_run_engine(engine, drive()))
    assert outs == [_want(lm, p, 5) for p in prompts]
    s = engine.kv_pool.stats()
    assert s["hit_requests"] >= 3  # every repeat matched the prefix
    assert s["hit_tokens"] >= 3 * 12
    # Zero-copy: blocks entered the trie by adoption, not a device store
    # (the engine has no store program at all).
    assert s["inserted_blocks"] > 0
    assert engine.decode_compile_count() in (1, -1)
    assert engine.metrics.summary()["prefix_hit_rate"] > 0.4


def test_paged_hit_after_evict_round_trip(lm, rng):
    """Evicting a cached prefix under pool pressure costs performance,
    never correctness: A cached -> displaced by B/C -> A re-prefilled
    and re-adopted -> A hits again; parity holds throughout."""
    model, variables = lm
    # 5 blocks x 4 tokens: a finished 15-token sequence adopts 3 blocks,
    # so b's from-scratch admission (3 private + 1 growth) must evict
    # part of a's resident chain.
    engine = ServingEngine(model, variables, slots=1, max_queue=16,
                           kv_pool_blocks=5, kv_block_tokens=4)
    a, b = _prompt(rng, 11), _prompt(rng, 11)

    async def drive():
        outs = []
        for p in (a, a, b, a, a):  # hit, evict via b, miss, re-hit
            outs.append(await engine.submit(p, 4).result())
        return outs

    outs = asyncio.run(_run_engine(engine, drive()))
    wa, wb = _want(lm, a, 4), _want(lm, b, 4)
    assert outs == [wa, wa, wb, wa, wa]
    s = engine.kv_pool.stats()
    assert s["evicted_blocks"] > 0  # pressure really displaced blocks
    assert s["hit_requests"] >= 2


# -- oversubscription: preempt-and-requeue -----------------------------------

def test_preempt_and_requeue_completes_token_identical(lm, rng):
    """THE satellite invariant: a pool sized to force preemption under
    concurrent load must still complete every request with output
    token-identical to the unconstrained run — and the preempted
    request's timeline shows the preemption and BOTH admission hops
    under one trace_id."""
    from distkeras_tpu.telemetry import RecompileAuditor, TraceStore

    model, variables = lm
    auditor = RecompileAuditor()
    store = TraceStore()
    # 4 slots x (12-token prompt + 10 new) needs ~4 * 6 blocks at
    # completion; 13 blocks can hold ~2 full sequences, so concurrent
    # decode growth MUST preempt.
    tight = ServingEngine(model, variables, slots=4, max_queue=16,
                          kv_pool_blocks=13, kv_block_tokens=4,
                          trace_store=store, auditor=auditor,
                          arm_auditor_after_warmup=True)
    roomy = ServingEngine(model, variables, slots=4, max_queue=16,
                          kv_pool_blocks=64, kv_block_tokens=4)
    prompts = [_prompt(rng, 12) for _ in range(4)]

    async def work(engine):
        reqs = [engine.submit(p, 10) for p in prompts]
        return [await r.result() for r in reqs]

    got_tight = asyncio.run(_run_engine(tight, work(tight)))
    got_roomy = asyncio.run(_run_engine(roomy, work(roomy)))
    want = [_want(lm, p, 10) for p in prompts]
    assert got_tight == want, "preempt-and-requeue changed output"
    assert got_roomy == want
    assert tight.metrics.preemptions > 0, (
        "pool was supposed to be tight enough to force preemption")
    # The armed auditor held through every preemption + re-admission.
    assert auditor.compiles("serving_decode") == 1
    # The preempted request's merged timeline: one trace_id, a preempt
    # event, and an admission hop on EACH side of it.
    preempted = [rec for rec in store.recent(10)
                 if any(e[0] == "preempt" for e in rec["events"])]
    assert preempted, "no preempted request left a timeline"
    for rec in preempted:
        names = [e[0] for e in rec["events"]]
        assert names.count("admit") >= 2, names
        assert names.index("admit") < names.index("preempt") < (
            len(names) - 1 - names[::-1].index("admit"))
        assert rec["trace_id"]  # one id spans both hops


def test_oversubscribed_sequential_load_never_wedges(lm, rng):
    """Many queued requests against a pool that fits ~one at a time:
    admission parks on the dry pool, unparks as slots free, and every
    request completes correctly (no deadlock, no starvation)."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, max_queue=32,
                           kv_pool_blocks=7, kv_block_tokens=4)
    prompts = [_prompt(rng, 9) for _ in range(6)]

    async def work():
        reqs = [engine.submit(p, 6) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 6) for p in prompts]


# -- long-context admission + typed OOM --------------------------------------

def test_paged_serves_context_beyond_a_whole_context_per_slot(lm, rng):
    """The capacity headline in miniature: at the SAME byte budget an
    engine that holds a whole context for every slot must cap that
    context (max_context) to afford its slots, rejecting longer requests
    up front — the budgeted pool chains blocks on demand and serves the
    same request to completion, token-identically."""
    model, variables = lm
    # A whole context a slot at this budget: 2 slots x 16 positions.
    # 8 blocks x 4 tokens is the same 32 positions' worth of KV bytes.
    capped = ServingEngine(model, variables, slots=2, max_context=16,
                           kv_block_tokens=4)
    paged = ServingEngine(model, variables, slots=2,
                          kv_pool_blocks=8, kv_block_tokens=4)
    assert capped.kv_pool.capacity == paged.kv_pool.capacity
    long_prompt = _prompt(rng, 20)  # + 6 new = 26 > the capped 16

    with pytest.raises(ValueError, match="context cap"):
        capped.submit(long_prompt, 6)

    async def drive():
        return await paged.submit(long_prompt, 6).result()

    got = asyncio.run(_run_engine(paged, drive()))
    assert got == _want(lm, long_prompt, 6)


@pytest.mark.parametrize("block_tokens,prefix,tails,new,chunk,min_hit", [
    # The block size does NOT divide the context (the table reach
    # rounds up to 72 > 64): the second run of the same 61 tokens hits
    # 60 of them and the tail chunk prefills 1 token at pos 60, padded
    # past the end of the trained context.
    (12, 61, (0, 0), 3, None, 60),
    # Matched 8 + tail 41 -> bucket 64 > the 56 positions of room left,
    # monolithic and as a chunk's ragged remainder.
    (8, 8, (2, 41), 4, None, 8),
    (8, 8, (2, 41), 4, 48, 8),
])
def test_paged_prefill_bucket_never_overshoots_trained_context(
        rng, block_tokens, prefix, tails, new, chunk, min_hit):
    """Regression: with max_seq_len == trained length (no slack in the
    positional table) a prefix hit's tail chunk must be padded no wider
    than the context room that is left — an overshooting pad width makes
    the positional dynamic_slice clamp BACKWARD and embeds the chunk's
    real tokens at wrong positions. The pad-width bound must be the
    context limit, not the table reach."""
    model = gpt_tiny(seq_len=64, vocab_size=VOCAB)
    variables = model.init(0)
    engine = ServingEngine(model, variables, slots=1, max_queue=8,
                           kv_pool_blocks=16, kv_block_tokens=block_tokens,
                           prefill_chunk=chunk)
    pre = _prompt(rng, prefix)
    # The first prompt caches the prefix, the second one hits it.
    first, second = (pre + _prompt(rng, k) for k in tails)

    async def drive():
        return [await engine.submit(p, new).result()
                for p in (first, second)]

    outs = asyncio.run(_run_engine(engine, drive()))
    want = generate(model, variables, np.asarray([second], np.int32), new,
                    greedy=True)[0].tolist()
    assert outs[1] == want, "positional clamp corrupted the hit"
    assert engine.kv_pool.stats()["hit_tokens"] >= min_hit


def test_pool_exhausted_is_typed_and_counted(lm, rng):
    """A request whose full context can NEVER fit the pool is a sizing
    error: typed ``kv_oom`` reject at submit, before any device work,
    with the counter bumped — unlike transient pressure, which queues."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1,
                           kv_pool_blocks=3, kv_block_tokens=4)
    with pytest.raises(PoolExhausted) as ei:
        engine.submit(_prompt(rng, 10), 8)  # 17 resident > 12 poolable
    assert ei.value.code == "kv_oom"
    assert engine.metrics.oom_rejections == 1


# -- observability ------------------------------------------------------------

def test_pool_gauges_counters_and_debugz_block_depth(lm, rng):
    """Satellite: kv_pool_blocks_{total,used,free} gauges and the
    preemption/oom counters publish to the registry, and the debugz slot
    table carries per-slot block-table depth while a request decodes."""
    model, variables = lm
    metrics = ServingMetrics()
    engine = ServingEngine(model, variables, slots=2, max_queue=8,
                           kv_pool_blocks=16, kv_block_tokens=4,
                           metrics=metrics)
    seen = {}

    async def drive():
        req = engine.submit(_prompt(rng, 9), 8)
        async for _ in req.tokens():
            if "dz" not in seen:
                seen["dz"] = engine.debugz()
        return req

    asyncio.run(_run_engine(engine, drive()))
    snap = metrics.registry.snapshot()
    assert snap["kv_pool_blocks_total"]["value"] == 16
    assert (snap["kv_pool_blocks_used"]["value"]
            + snap["kv_pool_blocks_free"]["value"]) == 16
    assert snap["kv_preemptions_total"]["kind"] == "counter"
    assert snap["kv_oom_rejections_total"]["kind"] == "counter"
    # Mid-decode debugz: the busy slot reported its block-table depth.
    busy = [s for s in seen["dz"]["slots"] if s["state"] != "free"]
    assert busy and busy[0]["blocks"] >= 3  # 9 prompt tokens -> >= 3 blocks
    assert "shared_blocks" in busy[0]
    kp = seen["dz"]["kv_pool"]
    assert kp["capacity_blocks"] == 16 and kp["blocks_free"] < 16
