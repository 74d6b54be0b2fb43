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
    "dense": {},
    "paged": {"kv_pool_blocks": 24, "kv_block_tokens": 8},
    "chunked_prefix": {"prefill_chunk": 4, "prefix_cache_mb": 0.5},
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
    token-identical under the pipelined loop: growth/preemption are
    barriers, so the round trip always sees fully-harvested state."""
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
    assert {"admission", "paged_growth"} <= set(barriers)
    for reason in BARRIER_REASONS:
        key = "serving_pipeline_barriers_total{reason=%s}" % reason
        assert snap[key]["value"] == barriers.count(reason), reason
    begins = [(name, attrs) for ph, name, _t, _lane, _parent, attrs
              in tracer.events() if ph == "B"]
    spans = [attrs["reason"] for name, attrs in begins if name == "barrier"]
    assert sorted(spans) == sorted(barriers)
    harvests = [attrs for name, attrs in begins if name == "harvest"]
    assert len(harvests) == len(rows)
    assert all(a["kind"] == "decode" and a["ready"] in (True, False)
               for a in harvests)
    assert any(name == "loop_idle" for name, _ in begins)
    with pytest.raises(KeyError):
        engine.metrics.record_barrier("because")
