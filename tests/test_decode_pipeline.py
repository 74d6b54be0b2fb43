"""Overlapped decode pipeline: depth-1 dispatch-before-harvest must be
token-identical to the serialized depth-0 engine in EVERY mode, with the
armed RecompileAuditor silent, and its bookkeeping (the one speculative
in-flight tick after a slot finishes) must leave pool accounting exact.

The parity pairs here are engine-vs-engine AND engine-vs-generate():
pipelining only defers the host's READ of each tick — the same ticks run
in the same order over the same state — so any divergence is a pipeline
bug, not model noise.
"""

import asyncio
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from distkeras_tpu.inference.generate import generate  # noqa: E402
from distkeras_tpu.models.bert import gpt_tiny  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.telemetry import RecompileAuditor  # noqa: E402


@pytest.fixture(scope="module")
def tiny_lm():
    model = gpt_tiny(seq_len=64, vocab_size=61)
    return model, model.init(0)


def _prompts(n, seed=0, lo=3, hi=11, vocab=61):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(k)).tolist()
            for k in rng.integers(lo, hi, size=n)]


def _run_engine(engine, prompts, new_tokens):
    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        reqs = [engine.submit(p, new_tokens) for p in prompts]
        outs = [await r.result() for r in reqs]
        engine.shutdown(drain=True)
        await task
        return outs

    return asyncio.run(main())


def _engine(tiny_lm, depth, **kw):
    model, variables = tiny_lm
    return ServingEngine(model, variables, slots=2, pipeline_depth=depth,
                         auditor=RecompileAuditor(),
                         arm_auditor_after_warmup=True, **kw)


MODES = {
    "derived_pool": {},  # no budget: a whole context for every slot
    "paged": {"kv_pool_blocks": 24, "kv_block_tokens": 8},
    "chunked_prefix": {"prefill_chunk": 4, "kv_block_tokens": 4},
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_pipeline_parity_token_identical(tiny_lm, mode):
    """Depth 1 == depth 0, greedy, per mode, at slots=2 — with the
    auditor armed after warmup (a pipelined retrace would raise). The
    engine-vs-engine pair is THE pipeline invariant: the same ticks in
    the same order, only the harvest deferred. (generate() parity at
    slots>1 carries the documented pre-existing batch-width tie
    envelope, so the absolute anchor runs at slots=1 below.)"""
    prompts = _prompts(8, seed=1)
    new_tokens = 10
    got = {}
    for depth in (0, 1):
        engine = _engine(tiny_lm, depth, **MODES[mode])
        got[depth] = _run_engine(engine, prompts, new_tokens)
        assert engine.decode_compile_count() in (1, -1)
        assert engine.auditor.compiles("serving_decode") == 1
    assert got[0] == got[1], f"{mode}: depth-1 output diverged from depth-0"


@pytest.mark.parametrize("mode", sorted(MODES))
def test_pipelined_engine_matches_generate_slots1(tiny_lm, mode):
    """Absolute anchor: the pipelined engine at slots=1 (where the
    engine's bitwise-parity promise is unconditional) reproduces
    generate() token for token, per mode."""
    model, variables = tiny_lm
    prompts = _prompts(5, seed=2)
    new_tokens = 8
    kw = dict(MODES[mode])
    engine = ServingEngine(model, variables, slots=1, pipeline_depth=1,
                           auditor=RecompileAuditor(),
                           arm_auditor_after_warmup=True, **kw)
    got = _run_engine(engine, prompts, new_tokens)
    assert engine.auditor.compiles("serving_decode") == 1
    for p, toks in zip(prompts, got):
        ref = generate(model, variables, np.asarray([p], np.int32),
                       new_tokens, greedy=True)[0].tolist()
        assert toks == ref, f"{mode}: pipelined stream diverged from generate"


def test_pipeline_parity_paged_preempt_resume(tiny_lm):
    """An oversubscribed pool (preempt + requeue + resume) stays
    token-identical under the pipelined loop: growth that has to preempt
    is a barrier (growth the pool can serve is not, since PR 30), so the
    round trip always sees fully-harvested state."""
    model, variables = tiny_lm
    prompts = _prompts(6, seed=7, lo=8, hi=16)
    new_tokens = 12
    got = {}
    for depth in (0, 1):
        engine = _engine(tiny_lm, depth, kv_pool_blocks=7,
                         kv_block_tokens=4)
        got[depth] = _run_engine(engine, prompts, new_tokens)
        assert engine.auditor.compiles("serving_decode") == 1
    assert got[0] == got[1]
    for p, toks in zip(prompts, got[1]):
        ref = generate(model, variables, np.asarray([p], np.int32),
                       new_tokens, greedy=True)[0].tolist()
        assert toks == ref


def test_pipeline_parity_speculative(tiny_lm):
    """Speculative mode under the pipelined loop (a spec tick harvests
    before the next dispatch; fallback ticks interleave) — draft==target
    sanity config, slots=1 for the bitwise promise, auditor armed over
    draft/verify/fallback."""
    model, variables = tiny_lm
    prompts = _prompts(5, seed=11)
    new_tokens = 9
    got = {}
    for depth in (0, 1):
        engine = ServingEngine(
            model, variables, slots=1, pipeline_depth=depth,
            draft_model=model, draft_variables=variables, spec_k=3,
            auditor=RecompileAuditor(), arm_auditor_after_warmup=True)
        got[depth] = _run_engine(engine, prompts, new_tokens)
        for name in ("serving_decode", "serving_draft", "serving_verify"):
            assert engine.auditor.compiles(name) == 1, name
    assert got[0] == got[1]
    for p, toks in zip(prompts, got[1]):
        ref = generate(model, variables, np.asarray([p], np.int32),
                       new_tokens, greedy=True)[0].tolist()
        assert toks == ref


def test_pipeline_parity_sharded_tp2(tiny_lm):
    """GSPMD tp=2 engine, pipelined: explicit shardings + deferred
    harvest keep one executable per callable and token identity."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (tier-1 runs with virtual CPUs)")
    from distkeras_tpu.parallel.mesh import serving_mesh

    model = gpt_tiny(seq_len=64, vocab_size=64)
    variables = model.init(0)
    prompts = _prompts(4, seed=5, vocab=64)
    new_tokens = 8
    got = {}
    for depth in (0, 1):
        engine = ServingEngine(
            model, variables, slots=2, pipeline_depth=depth,
            mesh=serving_mesh({"tp": 2}, devices=jax.devices()[:2]),
            auditor=RecompileAuditor(), arm_auditor_after_warmup=True)
        got[depth] = _run_engine(engine, prompts, new_tokens)
        assert engine.auditor.compiles("serving_decode") == 1
    assert got[0] == got[1]
    for p, toks in zip(prompts, got[1]):
        ref = generate(model, variables, np.asarray([p], np.int32),
                       new_tokens, greedy=True)[0].tolist()
        assert toks == ref


def test_one_extra_tick_after_finish_accounting_exact(tiny_lm):
    """When a slot finishes at tick N, tick N+1 is already in flight and
    ran one speculative row for it. The teardown must (a) drop that
    row's output, (b) roll back the optimistic watermark advance before
    adopting blocks — so the trie never claims the in-flight write and
    the pool's block accounting balances exactly — and (c) leave the
    adopted chain re-matchable: a follow-up identical prompt is a
    full-prefix hit with a token-identical continuation."""
    model, variables = tiny_lm
    engine = _engine(tiny_lm, 1, kv_pool_blocks=24, kv_block_tokens=4)
    pool = engine.kv_pool
    prompt = _prompts(1, seed=13, lo=9, hi=10)[0]
    new_tokens = 7

    out1 = _run_engine(engine, [prompt], new_tokens)[0]
    # All slot-owned state was released: tables fully sentinel, lens 0.
    assert all(int(b) == engine._sentinel
               for b in np.asarray(engine._tables).ravel())
    assert np.all(np.asarray(engine._lens) == 0)
    # The adopted chain covers exactly the COMPLETE blocks of the
    # harvested sequence: prompt + streamed tokens minus the last
    # sampled token (never fed) — the speculative in-flight write's
    # position must NOT be claimed.
    used = pool.capacity - pool.blocks_free
    fed = len(prompt) + new_tokens - 1
    assert used == fed // engine.kv_block_tokens

    # Re-admitting the same prompt must hit the adopted prefix and
    # continue token-identically.
    engine.reopen()
    hits_before = pool.stats()["hit_requests"]
    out2 = _run_engine(engine, [prompt], new_tokens)[0]
    assert out2 == out1
    assert pool.stats()["hit_requests"] > hits_before
    ref = generate(model, variables, np.asarray([prompt], np.int32),
                   new_tokens, greedy=True)[0].tolist()
    assert out1 == ref


def test_full_context_finish_at_block_boundary(tiny_lm):
    """A request whose prompt + max_new fills max_context EXACTLY, with
    the block size dividing the limit: at depth 1 the finishing tick's
    optimistic watermark advance puts ``_lens`` at the limit one full
    loop iteration before the harvest frees the slot, so the growth
    probe observes a live slot whose next-write block index is one past
    the table's last column. That row needs no growth (it is finishing);
    probing it must not index out of bounds and kill the engine."""
    model, variables = tiny_lm
    limit = 32
    got = {}
    for depth in (0, 1):
        engine = ServingEngine(
            model, variables, slots=2, pipeline_depth=depth,
            kv_pool_blocks=12, kv_block_tokens=8, max_context=limit,
            auditor=RecompileAuditor(), arm_auditor_after_warmup=True)
        prompt = _prompts(1, seed=23, lo=12, hi=13)[0]  # 12 tokens
        got[depth] = _run_engine(engine, [prompt], limit - len(prompt))[0]
        assert len(got[depth]) == limit - len(prompt)
        assert engine.auditor.compiles("serving_decode") == 1
    assert got[0] == got[1]


def test_parked_idle_engine_does_not_hot_spin(tiny_lm):
    """A fully-parked paged queue (pool dry, head parked, zero active
    slots) must WAIT on the arrival event, not re-enter the loop every
    iteration doing only the park check — and must still admit the
    parked request the moment blocks free (pool version moves + kick).

    The dry pool is constructed the way a disaggregated decode replica
    sees it: block rows held outside the engine (here: a direct
    ``pool.alloc``), so admission can neither allocate nor find a
    preemption victim and the head parks with nothing running."""
    model, variables = tiny_lm

    async def main():
        engine = ServingEngine(model, variables, slots=2,
                               pipeline_depth=1, kv_pool_blocks=8,
                               kv_block_tokens=4)
        pool = engine.kv_pool
        held = pool.alloc(8)  # the whole pool, from outside the engine
        assert held is not None
        # Count loop iterations via the expire() call at the top of
        # every iteration (metrics.sample is skipped on the idle path).
        iters = 0
        orig_expire = engine.scheduler.expire

        def counting_expire(now):
            nonlocal iters
            iters += 1
            return orig_expire(now)

        engine.scheduler.expire = counting_expire
        task = asyncio.create_task(engine.run(idle_poll_s=0.05))
        req = engine.submit([1, 2, 3, 4, 5], 4)  # needs 2 blocks: parks
        await asyncio.sleep(0.3)
        assert not req.done.is_set(), "request ran on a dry pool?"
        it0 = iters
        await asyncio.sleep(0.25)
        spun = iters - it0
        pool.free(held)          # blocks return; pool version moves
        engine.scheduler.kick()  # the wake the import path also sends
        toks = await asyncio.wait_for(req.result(), 10)
        engine.shutdown(drain=True)
        await task
        return spun, toks

    spun, toks = asyncio.run(main())
    assert toks, "parked request never completed after blocks freed"
    # 0.25 s at a 0.05 s idle poll ≈ 5 wakeups; a hot spin is thousands.
    assert spun <= 30, f"parked engine spun {spun} iterations in 0.25s"


def test_pipeline_depth_validated(tiny_lm):
    model, variables = tiny_lm
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServingEngine(model, variables, pipeline_depth=2)


def test_tick_timeline_and_debugz_surface(tiny_lm):
    """The dispatch→harvest tick lane and the debugz pipeline block are
    populated by a real run, JSON-safe, and rendered by the pretty
    pages."""
    engine = _engine(tiny_lm, 1)
    _run_engine(engine, _prompts(3, seed=17), 6)
    lane = engine.tick_timeline()
    assert lane, "no ticks logged"
    for tk in lane:
        assert tk["kind"] in ("decode", "spec")
        assert tk["t_harvest"] >= tk["t_dispatch"]
        assert tk["host_gap_s"] >= 0.0
    dz = engine.debugz()
    assert dz["pipeline"]["depth"] == 1
    assert dz["pipeline"]["inflight"] is None  # drained at shutdown
    json.dumps(dz)  # JSON-safe
    s = engine.metrics.summary()
    assert "host_gap_p50_s" in s and "device_idle_ratio" in s

    from distkeras_tpu.serving.debugz import format_debugz, format_tracez

    page = format_debugz(dz)
    assert "pipeline: depth=1" in page
    lane_txt = format_tracez({"recent": [], "records": 0,
                              "ticks": lane[-5:]})
    assert "tick lane" in lane_txt


def _programs(engine):
    """The engine's jitted programs, by attribute."""
    return {k: f for k, f in vars(engine).items()
            if callable(f) and hasattr(f, "lower")}


def test_every_program_of_a_paged_engine_is_named(tiny_lm):
    """A profiler trace tells programs apart by ``jit_<__name__>``: none
    of a paged engine's may be ``_unknown`` (a bare functools.partial) or
    a lambda, each reads as the auditor names it, and exactly one is a
    ``decode`` (the draft's step is ``serving_draft``)."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, kv_pool_blocks=24,
                           kv_block_tokens=8, draft_model=model,
                           draft_variables=variables)
    names = {k: f.__name__ for k, f in _programs(engine).items()}
    assert len(names) >= 14
    assert all(n.startswith("serving_") for n in names.values()), names
    assert [n for n in names.values() if "decode" in n] == ["serving_decode"]
    assert names["_draft_step"] == "serving_draft"
    # the name is what the compiled module is called, not a label beside it
    assert engine._fresh_draft_row.lower().as_text().startswith(
        "module @jit_serving_fresh_draft_row")
    # with an auditor, the audit report and the trace use one name
    audited = _engine(tiny_lm, 1, kv_pool_blocks=24, kv_block_tokens=8)
    _run_engine(audited, _prompts(2, seed=3), 4)
    assert {"serving_decode", "serving_prefill", "serving_admit"} <= set(
        audited.auditor.report())


def test_tick_counters_and_barrier_spans_equal_a_count_of_ones_own(tiny_lm):
    """``serving_slot_ticks_total``, ``kv_pool_block_ticks_total`` and
    ``serving_pipeline_barriers_total{reason}`` against what this test
    counts at the same places; every ``barrier`` span carries one of the
    fixed reasons and there is one per counted barrier."""
    from distkeras_tpu import telemetry as T
    from distkeras_tpu.serving.metrics import BARRIER_REASONS

    engine = _engine(tiny_lm, 1, kv_pool_blocks=24, kv_block_tokens=8)
    rows, blocks, barriers = [], [], []
    dispatch, sample = engine._decode_dispatch, engine.metrics.sample
    barrier = engine._pipeline_barrier

    def counting_dispatch():
        tick = dispatch()
        rows.append(len(tick.rows))
        return tick

    def counting_sample(*args, **kw):
        blocks.append(engine.kv_pool.blocks_used)
        return sample(*args, **kw)

    def counting_barrier(loop, reason):
        if engine._inflight:
            barriers.append(reason)
        return barrier(loop, reason)

    engine._decode_dispatch = counting_dispatch
    engine.metrics.sample = counting_sample
    engine._pipeline_barrier = counting_barrier

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        await asyncio.sleep(0.03)  # nothing queued yet: the loop idles
        reqs = [engine.submit(p, 12) for p in _prompts(6, seed=23)]
        for r in reqs:
            await r.result()
        engine.shutdown(drain=True)
        await task

    tracer = T.enable_tracing()
    try:
        asyncio.run(main())
    finally:
        T.disable_tracing()
    snap = engine.metrics.registry.snapshot()
    assert len(rows) > 12 and len(blocks) >= len(rows)
    assert snap["serving_slot_ticks_total"]["value"] == sum(rows)
    assert snap["kv_pool_block_ticks_total"]["value"] == sum(blocks)
    assert snap["serving_decode_iterations_total"]["value"] == len(blocks)
    assert set(barriers) <= set(BARRIER_REASONS)
    # admission always drains; growth only from a dry pool, and 24 blocks
    # are roomy (the dry case: test_paged_growth_from_a_dry_pool_...)
    assert "admission" in barriers and "paged_growth" not in barriers
    grown = snap["serving_paged_growth_blocks_total{drained=0}"]["value"]
    assert grown > 0
    assert snap["serving_paged_growth_blocks_total{drained=1}"]["value"] == 0
    for reason in BARRIER_REASONS:
        key = "serving_pipeline_barriers_total{reason=%s}" % reason
        assert snap[key]["value"] == barriers.count(reason), reason
    begins = [(name, attrs) for ph, name, _t, _lane, _parent, attrs
              in tracer.events() if ph == "B"]
    spans = [attrs["reason"] for name, attrs in begins if name == "barrier"]
    assert sorted(spans) == sorted(barriers)
    growth = [attrs for name, attrs in begins if name == "paged_growth"]
    assert growth and all(a["drained"] == 0 for a in growth)
    assert sum(a["blocks"] for a in growth) == grown
    harvests = [attrs for name, attrs in begins if name == "harvest"]
    assert len(harvests) == len(rows)
    assert all(a["kind"] == "decode" and a["ready"] in (True, False)
               for a in harvests)
    assert any(name == "loop_idle" for name, _ in begins)
    with pytest.raises(KeyError):
        engine.metrics.record_barrier("because")


# -- paged growth with the tick in flight (PR 30) ----------------------------

def _watch(engine):
    """Counting wrappers at the places the engine counts: the barriers
    that found a tick in flight, by reason, and every tail block chained,
    as ``(slot, block, rows of the newest tick in flight or None)``."""
    barriers, chained = [], []
    barrier, chain = engine._pipeline_barrier, engine._chain

    def counting_barrier(loop, reason):
        if engine._inflight:
            barriers.append(reason)
        return barrier(loop, reason)

    def counting_chain(i, ids):
        chained.append((i, int(ids[0]), engine._inflight[-1].rows
                        if engine._inflight else None))
        return chain(i, ids)

    engine._pipeline_barrier = counting_barrier
    engine._chain = counting_chain
    return barriers, chained


def _growth_counts(engine):
    snap = engine.metrics.registry.snapshot()
    return tuple(
        snap["serving_paged_growth_blocks_total{drained=%d}" % d]["value"]
        for d in (0, 1))


def _generate(tiny_lm, prompt, new_tokens):
    model, variables = tiny_lm
    return generate(model, variables, np.asarray([prompt], np.int32),
                    new_tokens, greedy=True)[0].tolist()


def test_paged_growth_keeps_the_tick_in_flight_under_a_roomy_pool(tiny_lm):
    """Four rows on 4-token blocks cross a block boundary on most ticks.
    With a roomy pool no ``paged_growth`` barrier finds a tick to drain:
    every tail block is chained with the tick in flight left alone, the
    counter says how many, and the streams are those of depth 0 and of
    generate()."""
    model, variables = tiny_lm
    prompts = _prompts(4, seed=31, lo=3, hi=9)
    new_tokens = 18
    got = {}
    for depth in (0, 1):
        engine = ServingEngine(
            model, variables, slots=4, pipeline_depth=depth,
            kv_pool_blocks=64, kv_block_tokens=4,
            auditor=RecompileAuditor(), arm_auditor_after_warmup=True)
        barriers, chained = _watch(engine)
        got[depth] = _run_engine(engine, prompts, new_tokens)
        assert engine.auditor.compiles("serving_decode") == 1
        assert "paged_growth" not in barriers
        # every row grows by ceil-ish(18 / 4) blocks past its prompt's
        assert len(chained) >= 4 * (new_tokens // 4 - 1)
        assert _growth_counts(engine) == (len(chained), 0)
        in_flight = [c for c in chained if c[2] is not None]
        if depth:
            # the mechanism engaged: nearly all growth met a tick in
            # flight (the first tick after the admissions' barrier has
            # none), and the grown row rode in that tick
            assert len(in_flight) >= len(chained) - 4
            assert all(i in rows for i, _blk, rows in in_flight)
        else:
            assert not in_flight
    assert got[0] == got[1]
    for p, toks in zip(prompts, got[1]):
        assert toks == _generate(tiny_lm, p, new_tokens)


def test_paged_growth_from_a_dry_pool_drains_before_it_preempts(tiny_lm):
    """The oversubscribed pool of the preempt-and-resume parity test: a
    row that cannot be served without a victim still drains the pipeline
    first. No preemption, and no call of the preempting growth at all,
    ever sees a tick in flight; ``drained=1`` counts its blocks; parity
    with depth 0 and generate() holds."""
    # (seed 8: twice the victim is the OTHER row, so the wedged row is
    # then given its block behind the barrier; seed 7's rows always
    # preempt themselves)
    prompts = _prompts(6, seed=8, lo=8, hi=16)
    new_tokens = 12
    got, preempted = {}, {}
    for depth in (0, 1):
        engine = _engine(tiny_lm, depth, kv_pool_blocks=7,
                         kv_block_tokens=4)
        barriers, chained = _watch(engine)
        calls = []
        for name in ("_preempt_slot", "_ensure_tail_block"):
            def wrapped(i, _fn=getattr(engine, name), _name=name):
                calls.append((_name, bool(engine._inflight)))
                return _fn(i)
            setattr(engine, name, wrapped)
        got[depth] = _run_engine(engine, prompts, new_tokens)
        assert engine.auditor.compiles("serving_decode") == 1
        preempted[depth] = sum(n == "_preempt_slot" for n, _ in calls)
        assert preempted[depth] > 0
        assert not any(inflight for _name, inflight in calls)
        undrained, drained = _growth_counts(engine)
        assert drained > 0 and undrained + drained == len(chained)
        if depth:
            assert barriers.count("paged_growth") > 0
            assert engine.metrics.registry.snapshot()[
                "serving_pipeline_barriers_total{reason=paged_growth}"][
                    "value"] == barriers.count("paged_growth")
    assert got[0] == got[1]
    for p, toks in zip(prompts, got[1]):
        assert toks == _generate(tiny_lm, p, new_tokens)


def test_block_freed_by_a_finish_is_handed_on_with_its_stale_write_in_flight(
        tiny_lm):
    """The reuse invariant. Row A (5 + 6 tokens) finishes at tick 5; tick
    6, dispatched before that harvest, writes A's garbage K/V at offset 2
    of A's partial tail block, which the harvest frees. On the next
    iteration row B (6-token prompt) reaches position 12 and needs a
    block, the pool of 6 has exactly that one free, and growth chains it
    with tick 6 still in flight. B then writes offsets 0..3 of it in
    later ticks and reads none before it wrote it: its stream is
    generate()'s, the pool ends holding B's adopted chain only, and the
    decode step compiled once."""
    a, b = _prompts(1, seed=41, lo=5, hi=6)[0], _prompts(
        1, seed=43, lo=6, hi=7)[0]
    new_a, new_b = 6, 16
    got = {}
    for depth in (0, 1):
        engine = _engine(tiny_lm, depth, kv_pool_blocks=6,
                         kv_block_tokens=4)
        pool = engine.kv_pool
        barriers, chained = _watch(engine)
        freed, free = [], pool.free

        def recording_free(ids):
            freed.append(([int(i) for i in ids], len(chained)))
            return free(ids)

        pool.free = recording_free

        async def main():
            task = asyncio.create_task(engine.run(idle_poll_s=0.01))
            reqs = [engine.submit(a, new_a), engine.submit(b, new_b)]
            outs = [await r.result() for r in reqs]
            engine.shutdown(drain=True)
            await task
            return outs

        got[depth] = asyncio.run(main())
        assert engine.auditor.compiles("serving_decode") == 1
        assert "paged_growth" not in barriers
        assert _growth_counts(engine) == (len(chained), 0)
        # B's adopted chain and nothing else: A's two adopted blocks were
        # evicted for B's last two, A's tail and B's tail were freed
        assert pool.blocks_used == (len(b) + new_b - 1) // 4 == 5
        if depth:
            # A's teardown freed one block (its partial tail) ...
            (a_tail,), at = next(f for f in freed if f[0])
            # ... and the very next block chained is that one, to B (slot
            # 1), while the tick in flight still carries A's row (slot 0)
            slot, block, rows = chained[at]
            assert (slot, block) == (1, a_tail)
            assert rows is not None and 0 in rows and 1 in rows
    assert got[0] == got[1]
    assert got[1] == [_generate(tiny_lm, a, new_a),
                      _generate(tiny_lm, b, new_b)]


def test_growth_that_would_spill_to_the_host_tier_drains_first(tiny_lm):
    """With a host tier attached an allocation from an empty free list
    evicts a trie leaf WITH a spill: a D2H gather from the engine's
    cache, which with a tick in flight is that tick's output. Such
    growth keeps its barrier: every spill runs with nothing in flight,
    its blocks count under ``drained=1``, and the stream is generate()'s."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=1, pipeline_depth=1,
                           kv_pool_blocks=5, kv_block_tokens=4,
                           kv_host_tier_mb=4.0)
    barriers, chained = _watch(engine)
    spills = []
    for name in ("_spill_block", "_spill_blocks"):
        def wrapped(*args, _fn=getattr(engine, name)):
            spills.append(bool(engine._inflight))
            return _fn(*args)
        setattr(engine, name, wrapped)
    engine.kv_pool.spill_hook = engine._spill_block
    engine.kv_pool.spill_many_hook = engine._spill_blocks
    first, second = _prompts(1, seed=51, lo=8, hi=9)[0], _prompts(
        1, seed=53, lo=6, hi=7)[0]

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        # 8 + 5 tokens leave three adopted leaves; the second request
        # takes the two free blocks at admission and grows twice
        outs = [await engine.submit(first, 5).result(),
                await engine.submit(second, 8).result()]
        engine.shutdown(drain=True)
        await task
        return outs

    outs = asyncio.run(main())
    assert outs == [_generate(tiny_lm, first, 5),
                    _generate(tiny_lm, second, 8)]
    assert spills and not any(spills)
    assert engine.kv_tier.stats()["host_entries"] >= 2
    assert barriers.count("paged_growth") == 2
    undrained, drained = _growth_counts(engine)
    assert drained == 2 and undrained + drained == len(chained)


def test_wedged_fork_row_errors_behind_the_barrier(tiny_lm):
    """Only fork rows resident and the pool dry: growth has no victim to
    preempt and errors the wedged row's request typed, tearing its fork
    group down. That too waits for the barrier: the teardown sees no
    tick in flight, and the pool gets every block back."""
    from distkeras_tpu.serving.scheduler import PoolExhausted

    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1,
                           kv_pool_blocks=9, kv_block_tokens=4)
    # submit() refuses what the pool could never hold (7 blocks here), so
    # the pool is made dry as a decode replica sees it: rows held outside
    held = engine.kv_pool.alloc(3)
    barriers, _chained = _watch(engine)
    torn, teardown = [], engine._teardown_fork

    def watching_teardown(req):
        torn.append(bool(engine._inflight))
        return teardown(req)

    engine._teardown_fork = watching_teardown
    prompt = _prompts(1, seed=61, lo=5, hi=6)[0]

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        # two rows of 5 + 11 positions want 1 shared + 2 x 3 blocks of 6
        req = engine.submit(prompt, 12, kind="sample", n=2,
                            temperature=1.0)
        with pytest.raises(PoolExhausted):
            await req.result()
        engine.shutdown(drain=True)
        await task

    asyncio.run(main())
    assert torn == [False]
    assert "paged_growth" in barriers
    assert engine.metrics.oom_rejections == 1
    engine.kv_pool.free(held)
    engine.kv_pool.flush()
    assert engine.kv_pool.blocks_free == engine.kv_pool.capacity


def test_fork_child_waiting_for_its_parent_is_given_no_block(tiny_lm):
    """A ``sample`` request's child rows hold their slots (``fork_wait``)
    while the parent prefills in chunks and another row keeps the decode
    step running. Growth used to chain a block to each waiting child,
    which the fan-out then dropped from its table without freeing. Every
    block in use afterwards is a node of the trie."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=4, pipeline_depth=1,
                           kv_pool_blocks=64, kv_block_tokens=4,
                           prefill_chunk=4)
    prompt = _prompts(1, seed=71, lo=17, hi=18)[0]

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        other = engine.submit([3, 4, 5], 30)
        fork = engine.submit(prompt, 6, kind="sample", n=3, speculate=False)
        await fork.result()
        await other.result()
        engine.shutdown(drain=True)
        await task
        return fork

    fork = asyncio.run(main())
    want = _generate(tiny_lm, prompt, 6)
    assert fork.fork_completions == [want, want, want]
    pool = engine.kv_pool
    assert pool._fork_refs == {}
    assert pool.blocks_used == len(pool._by_slot)
