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
import time

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


async def _until(cond, limit_s: float, what: str) -> None:
    """Poll ``cond`` on the running loop until it holds, failing after
    ``limit_s`` seconds."""
    deadline = time.monotonic() + limit_s
    while not cond():
        assert time.monotonic() < deadline, f"no {what} in {limit_s} s"
        await asyncio.sleep(0.005)


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
        # Each wait is on its own condition: parked, admitted, finished.
        await _until(lambda: engine._parked_req is req, 30, "park")
        assert not req.done.is_set(), "request ran on a dry pool?"
        it0 = iters
        await asyncio.sleep(0.25)  # the window the spin is counted over
        spun = iters - it0
        assert req.queue_wait_s is None, "admitted on a dry pool?"
        pool.free(held)          # blocks return; pool version moves
        engine.scheduler.kick()  # the wake the import path also sends
        await _until(lambda: req.queue_wait_s is not None, 5,
                     "admission once blocks freed")
        # (its first prefill compiles here, which a loaded machine slows)
        toks = await asyncio.wait_for(req.result(), 120)
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
        assert tk["harvest_wait_s"] >= 0.0
        assert tk.get("loop_wait_s", 0.0) >= 0.0
    dz = engine.debugz()
    assert dz["pipeline"]["depth"] == 1
    assert dz["pipeline"]["inflight"] is None  # drained at shutdown
    json.dumps(dz)  # JSON-safe
    pl = dz["pipeline"]
    assert pl["harvests"] >= 1 and 0 <= pl["harvests_ready"] <= pl["harvests"]
    assert pl["loop_wait_harvest_s"] >= 0.0 and pl["loop_wait_yield_s"] >= 0.0

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
        # a request whose prompt is read back on the host chunk by
        # chunk still drains the pipeline before it is admitted
        long = engine.submit(_prompts(1, seed=29)[0], 24)
        while len(long.out_tokens) < 2:
            await asyncio.sleep(0)
        await engine.submit(_prompts(1, seed=37)[0], 0,
                            kind="score").result()
        await long.result()
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
    # of the eight admissions only the score request's drains (the rest
    # are enqueued beside the tick in flight: PR 33); growth only from a
    # dry pool, and 24 blocks are roomy (the dry case:
    # test_paged_growth_from_a_dry_pool_...)
    assert barriers.count("admission") == 1
    assert "paged_growth" not in barriers
    assert _admission_counts(engine) == (7, 1)
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
    assert all(a["kind"] in ("decode", "admit")
               and a["ready"] in (True, False) for a in harvests)
    assert sum(a["kind"] == "decode" for a in harvests) == len(rows)
    # first tokens are read in batches: at most one read per admission
    assert 1 <= sum(a["kind"] == "admit" for a in harvests) <= 7
    admits = [attrs for name, attrs in begins if name == "admit"]
    assert sorted(a["drained"] for a in admits) == [0] * 7 + [1]
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


def _drained_split(engine, counter):
    snap = engine.metrics.registry.snapshot()
    return tuple(snap["%s{drained=%d}" % (counter, d)]["value"]
                 for d in (0, 1))


def _growth_counts(engine):
    return _drained_split(engine, "serving_paged_growth_blocks_total")


def _admission_counts(engine):
    return _drained_split(engine, "serving_admissions_total")


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


# -- an admission is a dispatch (PR 33) --------------------------------------

# Whole-prompt admission and chunked prefill are the one _prefill_step.
ADMIT_MODES = {
    "whole_prompt": {},
    "chunked": {"prefill_chunk": 4},
}


def _watch_admissions(engine):
    """``(barriers, steps)``: the barriers that found a tick in flight, by
    reason, and for every prefill step ``(request, was a tick in flight,
    was the step only enqueued)``."""
    barriers, _chained = _watch(engine)
    steps, step = [], engine._prefill_step

    def recording_step(st, slot, defer=False):
        steps.append((st.request, bool(engine._inflight), defer))
        return step(st, slot, defer)

    engine._prefill_step = recording_step
    return barriers, steps


def _serve(engine, background, arrivals):
    """One request first; once it has streamed two tokens (so there is a
    tick in flight from here on) the ``arrivals``, each ``(prompt,
    new_tokens, submit kwargs)``. Returns the background's tokens and
    the arrivals' finished requests."""
    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        first = engine.submit(*background)
        while len(first.out_tokens) < 2:
            await asyncio.sleep(0)
        reqs = [engine.submit(p, n, **kw) for p, n, kw in arrivals]
        for r in reqs:
            await asyncio.wait_for(r.result(), 60)
        out = await asyncio.wait_for(first.result(), 60)
        engine.shutdown(drain=True)
        await asyncio.wait_for(task, 60)
        return out, reqs

    return asyncio.run(main())


@pytest.mark.parametrize("mode", sorted(ADMIT_MODES))
def test_arrivals_are_enqueued_with_the_tick_in_flight(tiny_lm, mode):
    """Requests that arrive while another decodes are admitted without a
    pipeline barrier: every prefill step is only enqueued, most of them
    beside a tick in flight, ``serving_admissions_total{drained=0}``
    counts all of them, and the streams are those of ``generate()`` and
    of the ``pipeline_depth=0`` engine, which still drains for each."""
    model, variables = tiny_lm
    background = (_prompts(1, seed=81, lo=5, hi=6)[0], 40)
    arrivals = [(p, 9, {}) for p in _prompts(5, seed=83, lo=3, hi=14)]
    got = {}
    for depth in (0, 1):
        engine = ServingEngine(
            model, variables, slots=4, pipeline_depth=depth,
            kv_pool_blocks=64, kv_block_tokens=4,
            auditor=RecompileAuditor(), arm_auditor_after_warmup=True,
            **ADMIT_MODES[mode])
        barriers, steps = _watch_admissions(engine)
        out, reqs = _serve(engine, background, arrivals)
        got[depth] = [out] + [r.out_tokens for r in reqs]
        assert engine.auditor.compiles("serving_decode") == 1
        if depth:
            assert not {"admission", "prefill_chunk"} & set(barriers)
            assert _admission_counts(engine) == (6, 0)
            # the mechanism engaged: but for the first request's, and a
            # chunk that found every row mid-prefill, the steps met a tick
            # in flight and did not wait for their token
            assert sum(inflight for _s, inflight, _d in steps) >= 5
            assert all(defer == (inflight or mode == "whole_prompt")
                       for _s, inflight, defer in steps)
        else:
            assert _admission_counts(engine) == (0, 6)
            assert not any(defer for _s, _i, defer in steps)
    assert got[0] == got[1]
    assert got[1] == [_generate(tiny_lm, p, n)
                      for p, n, *_ in [background] + arrivals]


@pytest.mark.parametrize("mode", sorted(ADMIT_MODES))
def test_a_first_token_that_finishes_its_request(tiny_lm, mode):
    """One token owed: the row's first decode tick is already in flight
    when the first token is read at the harvest and ends the request.
    The row is torn down there like any finishing row (the advance of
    the tick in flight rolled back, its output dropped), the slot is
    refilled at once by the next arrival, and the pool ends with every
    block either free or a node of the trie."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1,
                           kv_pool_blocks=64, kv_block_tokens=4,
                           **ADMIT_MODES[mode])
    barriers, steps = _watch_admissions(engine)
    background = (_prompts(1, seed=85, lo=5, hi=6)[0], 30)
    arrivals = [(p, n, {}) for p, n in zip(
        _prompts(4, seed=87, lo=6, hi=11), (1, 1, 5, 1))]
    out, reqs = _serve(engine, background, arrivals)
    assert [out] + [r.out_tokens for r in reqs] == [
        _generate(tiny_lm, p, n) for p, n, *_ in [background] + arrivals]
    assert not {"admission", "prefill_chunk"} & set(barriers)
    assert _admission_counts(engine) == (5, 0)
    assert any(defer and inflight for _s, inflight, defer in steps)
    assert np.all(np.asarray(engine._lens) == 0)
    pool = engine.kv_pool
    assert pool.blocks_used == len(pool._by_slot)
    # each adopted chain is the harvested sequence's complete blocks
    assert pool.blocks_used == sum(
        (len(p) + n - 1) // 4 for p, n, *_ in [background] + arrivals)


@pytest.mark.parametrize("mode", sorted(ADMIT_MODES))
@pytest.mark.parametrize("ending", ["cancel", "shutdown"])
def test_teardown_with_an_admission_pending(tiny_lm, mode, ending):
    """A request is cancelled, or the engine shut down without a drain,
    while its prefill is enqueued and its first token unread. The
    teardown's barrier reads that token first, so the row is torn down
    from its true state: typed errors, the background's stream whole (on
    cancel), every block back."""
    from distkeras_tpu.serving.scheduler import (EngineStopped,
                                                 RequestCancelled)

    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1,
                           kv_pool_blocks=64, kv_block_tokens=4,
                           **ADMIT_MODES[mode])
    background = (_prompts(1, seed=89, lo=5, hi=6)[0], 24)
    arrival = _prompts(1, seed=91, lo=9, hi=10)[0]
    step, seen = engine._prefill_step, []

    def ending_step(st, slot, defer=False):
        out = step(st, slot, defer)
        if st.request.prompt == arrival and st.prefill is None:
            # the arrival's last chunk has just been enqueued
            assert defer and engine._inflight
            seen.append(len(engine._pending_admits))
            if ending == "cancel":
                st.request.cancel()
            else:
                engine.shutdown(drain=False)
        return out

    engine._prefill_step = ending_step

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        first = engine.submit(*background)
        while len(first.out_tokens) < 2:
            await asyncio.sleep(0)
        req = engine.submit(arrival, 12)
        errors = []
        for r in (req, first):
            try:
                await asyncio.wait_for(r.result(), 60)
                errors.append(None)
            except (RequestCancelled, EngineStopped) as e:
                errors.append(type(e))
        engine.shutdown(drain=True)
        await asyncio.wait_for(task, 60)
        return first, req, errors

    first, req, errors = asyncio.run(main())
    assert seen == [0]  # enqueued, and not yet handed to the loop
    want = _generate(tiny_lm, arrival, 12)
    if ending == "cancel":
        assert errors == [RequestCancelled, None]
        assert first.out_tokens == _generate(tiny_lm, *background)
        # the token read before the teardown is the request's own
        assert req.out_tokens == want[:len(req.out_tokens)]
        assert 1 <= len(req.out_tokens) < 12
    else:
        assert errors == [EngineStopped, EngineStopped]
        assert req.out_tokens == want[:1]
    assert not engine._pending_admits and not engine._inflight
    assert np.all(np.asarray(engine._lens) == 0)
    pool = engine.kv_pool
    assert pool.blocks_used == len(pool._by_slot)


def _pp2_mesh():
    from distkeras_tpu.parallel.mesh import serving_mesh

    return serving_mesh({"tp": 1, "pp": 2}, devices=jax.devices()[:2])


# What still needs settled state keeps its barrier: the engine's options,
# the arrival's submit options, and whether the arrival itself is then
# admitted behind the barrier (a parked request is admitted later, when
# blocks have freed).
BARRIER_KEPT = {
    # 12 + 2 positions hold four of the pool's six blocks: the arrival's
    # three need a victim, and its priority is the better one
    "dry_pool_preempts": (dict(kv_pool_blocks=6), dict(priority=-1), True),
    # the same pool with nobody to preempt: the arrival parks
    "dry_pool_parks": (dict(kv_pool_blocks=6), {}, False),
    "sample": ({}, dict(kind="sample", n=2, speculate=False), True),
    "constrained": (dict(constrained=True),
                    dict(constraint={"start": 0,
                                     "edges": [[0, 1, 1], [1, 2, 0]]}),
                    True),
    "draft_model": (dict(draft=True), {}, True),
    "pp2": (dict(mesh=_pp2_mesh), {}, True),
}


@pytest.mark.parametrize("mode", sorted(ADMIT_MODES))
@pytest.mark.parametrize("case", sorted(BARRIER_KEPT))
def test_the_barrier_is_kept_where_settled_state_is_needed(
        tiny_lm, case, mode):
    """Each of these arrivals finds a tick in flight and drains it before
    it is admitted (``admission``; its chunks ``prefill_chunk``), counts
    under ``drained=1``, never has a prefill step that is only enqueued,
    and streams what it streamed before: ``generate()``'s tokens, or the
    automaton's."""
    model, variables = tiny_lm
    options, submit, admitted_drained = BARRIER_KEPT[case]
    options = dict(options)
    if "mesh" in options:
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices (tier-1 runs with virtual CPUs)")
        options["mesh"] = options["mesh"]()
        model = gpt_tiny(seq_len=64, vocab_size=64)
        variables = model.init(0)
    if options.pop("draft", False):
        options.update(draft_model=model, draft_variables=variables,
                       spec_k=3)
    options.setdefault("kv_pool_blocks", 64)
    engine = ServingEngine(model, variables, slots=4, pipeline_depth=1,
                           kv_block_tokens=4, **options,
                           **ADMIT_MODES[mode])
    barriers, steps = _watch_admissions(engine)
    background = (_prompts(1, seed=93, lo=12, hi=13)[0], 9)
    arrival = _prompts(1, seed=95, lo=9, hi=10)[0]
    out, (req,) = _serve(engine, background, [(arrival, 6, submit)])

    def want(prompt, n):
        return generate(model, variables, np.asarray([prompt], np.int32),
                        n, greedy=True)[0].tolist()

    assert out == want(*background)
    if case == "sample":
        assert req.fork_completions == [want(arrival, 6)] * 2
    elif case == "constrained":
        assert req.out_tokens == [1, 2, 1, 2, 1, 2]
    else:
        assert req.out_tokens == want(arrival, 6)
    assert "admission" in barriers
    undrained, drained = _admission_counts(engine)
    assert drained >= admitted_drained
    if case in ("draft_model", "pp2"):
        assert undrained == 0  # no admission of such an engine is enqueued
    if admitted_drained:
        if mode == "chunked" and case != "dry_pool_preempts":
            # (the preempted background was the only row decoding: the
            # arrival's chunks then find no tick to drain)
            assert "prefill_chunk" in barriers
        assert not any(defer for r, _i, defer in steps if r is req)
    assert np.all(np.asarray(engine._lens) == 0)


@pytest.mark.parametrize("mode", sorted(ADMIT_MODES))
def test_a_warm_up_through_an_idle_engine_warms_the_burst(tiny_lm, mode):
    """What keeps ``setup_s`` where it is, off the chip. The benchmark
    warms a server with one request per prefill bucket, sent one after
    another into an idle engine; its clients then arrive in a burst, most
    of them beside a tick in flight. Both routes must enter every program
    with ONE argument signature (dtype, weak type, committedness), or the
    burst would trace and lower each bucket's program again, which the
    persistent compile cache does not spare. So: after the warm-up the
    auditor is armed on the three programs of the path, the burst compiles
    nothing, and each program's jit cache holds one entry per bucket."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=4, pipeline_depth=1,
                           kv_pool_blocks=64, kv_block_tokens=4,
                           auditor=RecompileAuditor(), **ADMIT_MODES[mode])
    names = ("serving_prefill", "serving_admit", "serving_decode")
    barriers, steps = _watch_admissions(engine)
    # whole prompts pad to 8, 16 and 32; a chunk of 4 is one program
    warm = [_prompts(1, seed=s, lo=n, hi=n + 1)[0]
            for s, n in ((101, 5), (103, 12), (105, 20))]
    burst = _prompts(6, seed=107, lo=3, hi=30)
    buckets = 3 if mode == "whole_prompt" else 1

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        for p in warm:
            await engine.submit(p, 4).result()
            await asyncio.sleep(0.03)  # the engine idles between them
        warmed = {n: engine.auditor.compiles(n) for n in names}
        engine.auditor.arm(*names)
        first = engine.submit(warm[0], 40)
        while len(first.out_tokens) < 2:
            await asyncio.sleep(0)
        outs = [await r.result()
                for r in [engine.submit(p, 6) for p in burst]]
        await first.result()
        engine.shutdown(drain=True)
        await task
        return warmed, outs

    warmed, outs = asyncio.run(main())
    assert warmed == {"serving_prefill": buckets, "serving_admit": 1,
                      "serving_decode": 1}
    assert {n: engine.auditor.compiles(n) for n in names} == warmed
    assert engine._prefill._cache_size() == buckets
    assert engine._admit_jit._cache_size() == 1
    assert engine._decode_step._cache_size() == 1
    assert outs == [_generate(tiny_lm, p, 6) for p in burst]
    # the burst did take the other route: enqueued beside a tick in flight
    assert sum(inflight and defer for _r, inflight, defer in steps) >= 6
    assert not {"admission", "prefill_chunk"} & set(barriers)


@pytest.mark.parametrize("mode", sorted(ADMIT_MODES))
def test_a_spilling_pool_and_a_tier_readmission_keep_the_barrier(
        tiny_lm, mode):
    """With a host tier attached, a reservation the free list cannot
    serve evicts a trie leaf WITH a spill (a gather from the tick's
    output), and a prompt whose next block the tier holds is scattered
    back into the pool first. Both admissions drain the tick in flight
    (``admission``, ``drained=1``); every spill runs with nothing in
    flight; the streams are generate()'s."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1,
                           kv_pool_blocks=8, kv_block_tokens=4,
                           kv_host_tier_mb=4.0, **ADMIT_MODES[mode])
    barriers, _steps = _watch_admissions(engine)
    spills = []
    for name in ("_spill_block", "_spill_blocks"):
        def wrapped(*args, _fn=getattr(engine, name)):
            spills.append(bool(engine._inflight))
            return _fn(*args)
        setattr(engine, name, wrapped)
    engine.kv_pool.spill_hook = engine._spill_block
    engine.kv_pool.spill_many_hook = engine._spill_blocks
    first, decoding, arrival, later = (
        _prompts(1, seed=s, lo=n, hi=n + 1)[0]
        for s, n in ((111, 8), (119, 6), (115, 13), (117, 5)))

    async def beside(background, new_tokens, prompt, n):
        bg = engine.submit(background, new_tokens)
        while len(bg.out_tokens) < 2:
            await asyncio.sleep(0)
        return await engine.submit(prompt, n).result(), await bg.result()

    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        # 8 + 5 tokens leave three adopted leaves and five free blocks
        outs = [await engine.submit(first, 5).result()]
        assert _admission_counts(engine) == (1, 0)
        # the arrival's four blocks: two are free, two leaves spill
        outs += await beside(decoding, 10, arrival, 2)
        counts = [(_admission_counts(engine), barriers.count("admission"))]
        # the first prompt again: its blocks come back from the tier
        outs += await beside(later, 10, first, 5)
        counts.append((_admission_counts(engine),
                       barriers.count("admission")))
        engine.shutdown(drain=True)
        await task
        return outs, counts

    outs, counts = asyncio.run(main())
    assert outs == [_generate(tiny_lm, p, n) for p, n in (
        (first, 5), (arrival, 2), (decoding, 10), (first, 5), (later, 10))]
    assert counts[0] == ((2, 1), 1)
    (_undrained, drained), admission_barriers = counts[1]
    assert drained >= 2 and admission_barriers >= 2
    assert spills and not any(spills)
    assert engine.kv_tier.stats()["host_entries"] >= 2
    assert engine.metrics.registry.snapshot()[
        "kv_tier_readmits_total"]["value"] >= 1
