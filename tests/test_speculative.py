"""Speculative decoding (draft/verify) in the serving engine.

The invariants under test, all on CPU with a tiny causal LM drafting
for itself (the sanity config — acceptance ~100%, so deep accept
prefixes and the remaining-budget clamp are exercised) and for a
*different* draft (low acceptance — rejection, zero-accept fallback
ticks, and rollback dominate):

- greedy streams are token-identical to one-shot ``generate()`` AND to
  a non-speculating engine, across plain, mixed-temperature, and
  shared-prefix batches, from a derived and from a tight pool, including requests that use
  the whole trained context (verify-window overhang);
- the armed ``RecompileAuditor`` stays silent: draft, verify, and the
  one-token fallback decode each compile exactly once, no matter how
  acceptance lengths vary;
- preemption-and-requeue mid-speculation resumes token-identically
  (accepted-and-streamed tokens fold into the resume prefill), and a
  pool too dry for lookahead blocks degrades throughput, never output;
- rolling weight reload under speculation swaps the TARGET only and
  post-swap output matches the new weights;
- accept accounting: ``spec_draft_tokens_total`` /
  ``spec_accepted_tokens_total`` counters, the accept-len histogram,
  summary keys, and the debugz accept-rate column.

Engines are deliberately few and shared within tests — every
ServingEngine construction compiles its program set (plus the ctor
warmup of the spec trio), which is what dominates this file's runtime.
"""

import asyncio

import numpy as np
import pytest

from distkeras_tpu.inference.generate import generate
from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.telemetry import RecompileAuditor

VOCAB = 64

SPEC_CALLABLES = ("serving_decode", "serving_draft", "serving_verify")


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


@pytest.fixture(scope="module")
def other_lm():
    """A draft with different weights than the target: most proposals
    get rejected, so the zero-accept fallback path dominates."""
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(11)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _want(lm, prompt, n):
    model, variables = lm
    return generate(model, variables, np.asarray([prompt], np.int32), n,
                    greedy=True)[0].tolist()


def _spec_engine(lm, draft_lm=None, *, auditor=None, spec_k=4, **kw):
    model, variables = lm
    dm, dv = draft_lm if draft_lm is not None else lm
    return ServingEngine(
        model, variables, draft_model=dm, draft_variables=dv,
        spec_k=spec_k, auditor=auditor,
        arm_auditor_after_warmup=auditor is not None, **kw)


async def _run_engine(engine, coro):
    task = asyncio.create_task(engine.run())
    try:
        return await coro
    finally:
        engine.shutdown(drain=True)
        await task


def _drive_staggered(engine, jobs, **submit_kw):
    """``jobs``: (prompt, max_new_tokens) pairs, submitted staggered so
    later ones admit into freed slots mid-decode."""
    async def work():
        reqs = []
        for i, (p, n) in enumerate(jobs):
            reqs.append(engine.submit(p, n, **submit_kw))
            await asyncio.sleep(0.01 * i)
        return [await r.result() for r in reqs]

    return asyncio.run(_run_engine(engine, work()))


def _assert_compile_once(auditor):
    for name in SPEC_CALLABLES:
        assert auditor.compiles(name) == 1, name


# -- parity -------------------------------------------------------------------

def test_spec_greedy_parity_vs_generate_and_plain_engine(lm, rng):
    """Sanity config (draft==target): token-identical to generate() AND
    to a non-speculating engine, through staggered admissions into
    freed slots, INCLUDING a request that uses the whole trained
    context (20 + 12 == 32: the verify window overhangs the request
    limit on its final ticks) — with the auditor armed after the first
    tick."""
    model, variables = lm
    auditor = RecompileAuditor()
    engine = _spec_engine(lm, auditor=auditor, slots=2, max_queue=8)
    plain = ServingEngine(model, variables, slots=2, max_queue=8)
    jobs = [(_prompt(rng, n), 6) for n in (5, 9, 3, 7)]
    jobs.append((_prompt(rng, 20), 12))  # context-limit edge

    outs = _drive_staggered(engine, jobs)
    plain_outs = _drive_staggered(plain, jobs)
    for (p, n), got, plain_got in zip(jobs, outs, plain_outs):
        want = _want(lm, p, n)
        assert got == want  # vs offline generate()
        assert plain_got == want  # and vs the non-speculating engine
    _assert_compile_once(auditor)
    assert auditor.report()["serving_verify"]["armed"]
    assert engine.decode_compile_count() in (1, -1)
    s = engine.metrics.summary()
    # Draft == target: every usable draft accepted.
    assert s["spec_draft_tokens"] > 0
    assert s["spec_accept_rate"] == 1.0


def test_spec_low_acceptance_draft_still_parity_exact(lm, other_lm, rng):
    """A draft with unrelated weights: most proposals are rejected, so
    output flows through rollbacks and interleaved fallback ticks — and
    must STILL be token-identical to generate()."""
    auditor = RecompileAuditor()
    engine = _spec_engine(lm, other_lm, auditor=auditor, slots=2,
                          max_queue=8)
    jobs = [(_prompt(rng, n), 6) for n in (5, 9, 3, 7)]
    outs = _drive_staggered(engine, jobs)
    for (p, n), got in zip(jobs, outs):
        assert got == _want(lm, p, n)
    _assert_compile_once(auditor)
    s = engine.metrics.summary()
    # Rejection must actually have happened for this test to cover the
    # rollback + fallback paths.
    assert s["spec_accept_rate"] < 1.0


def test_spec_mixed_temperature_and_opt_out_one_batch(lm, rng):
    """Greedy rows speculate while temperature>0 rows (and an explicit
    speculate=False greedy row) ride the SAME batch — greedy output
    stays parity-exact, sampled output stays valid, and the opt-out
    greedy row is served by interleaved fallback ticks (strict parity),
    never booking draft statistics."""
    engine = _spec_engine(lm, slots=3, max_queue=8, seed=3)
    p = _prompt(rng, 5)
    p2 = _prompt(rng, 6)

    async def work():
        greedy = engine.submit(p, 8)
        hot = engine.submit(p, 8, temperature=5.0)
        optout = engine.submit(p2, 8, speculate=False)
        return (await greedy.result(), await hot.result(),
                await optout.result())

    g, h, o = asyncio.run(_run_engine(engine, work()))
    assert g == _want(lm, p, 8)
    assert o == _want(lm, p2, 8)  # opt-out: still greedy-exact
    assert all(0 <= t < VOCAB for t in h)
    # Only the speculating greedy row booked drafts — nothing from the
    # hot or opt-out rows — and in the sanity config it accepted all of
    # the (remaining-clamped) drafts it could use.
    dz = engine.debugz()
    assert dz["speculative"]["spec_k"] == 4
    assert engine.metrics.spec_draft_tokens > 0
    assert (engine.metrics.spec_accepted_tokens
            == engine.metrics.spec_draft_tokens)


def test_spec_shared_prefix_chunked_parity(lm, rng):
    """Speculation composes with chunked prefill + the prefix cache:
    shared-prefix batches stay parity-exact and still hit."""
    engine = _spec_engine(lm, slots=2, max_queue=16, prefill_chunk=4,
                          kv_block_tokens=4)
    shared = _prompt(rng, 12)
    prompts = [shared + _prompt(rng, k) for k in (3, 4, 5, 3)]

    async def drive():
        outs = []
        for p in prompts:  # sequential: later prompts hit earlier ones
            outs.append(await engine.submit(p, 5).result())
        return outs

    outs = asyncio.run(_run_engine(engine, drive()))
    assert outs == [_want(lm, p, 5) for p in prompts]
    assert engine.kv_pool.stats()["hit_requests"] >= 3
    assert engine.metrics.summary()["spec_accept_rate"] == 1.0


# -- paged: lookahead, preemption, resume ------------------------------------

def test_spec_paged_preempt_resume_and_room_clamp_parity(lm, rng):
    """ONE undersized pool covers the whole paged story: preemption
    fires while streams are mid-speculation (accepted-and-streamed
    tokens fold into the resume prefill), lookahead block allocs fail
    under pressure (the room clamp degrades tokens/tick, never
    correctness), a request uses the full trained context, and every
    stream still finishes token-identical with the armed auditor
    silent."""
    auditor = RecompileAuditor()
    engine = _spec_engine(lm, auditor=auditor, slots=2, max_queue=8,
                          kv_pool_blocks=8, kv_block_tokens=4)
    jobs = [(_prompt(rng, 9), 10), (_prompt(rng, 8), 10)]
    outs = _drive_staggered(engine, jobs)
    for (p, n), got in zip(jobs, outs):
        assert got == _want(lm, p, n)
    assert engine.metrics.preemptions >= 1  # pressure actually happened
    _assert_compile_once(auditor)
    # Full-context request on the same (reopened) engine: 20 + 12 == 32
    # fills the whole pool — 8 blocks at completion == capacity.
    engine.reopen()
    p = _prompt(rng, 20)
    out = _drive_staggered(engine, [(p, 12)])[0]
    assert out == _want(lm, p, 12)
    _assert_compile_once(auditor)
    # Draft == target, so every VERIFIED draft was accepted — but the
    # room clamp under pool pressure commits fewer than proposed on
    # some ticks (the designed degradation), so the rate sits just
    # below 1.0 rather than at it.
    rate = engine.metrics.summary()["spec_accept_rate"]
    assert 0.8 < rate <= 1.0, rate


# -- reload / swap ------------------------------------------------------------

def test_spec_rolling_reload_swaps_target_only(lm, rng):
    """request_param_swap under speculation: output before the swap
    matches the old weights, after matches the new — with the SAME
    draft (stale relative to the new target), which may cost accept
    rate but never correctness. The armed auditor proves the swap and
    the post-swap spec ticks never retraced."""
    model, variables = lm
    new_vars = model.init(7)
    auditor = RecompileAuditor()
    engine = _spec_engine(lm, auditor=auditor, slots=2, max_queue=8)
    p = _prompt(rng, 5)

    async def work():
        before = await engine.submit(p, 6).result()
        ev, res = engine.request_param_swap(new_vars)
        await ev.wait()
        assert res.get("ok"), res
        after = await engine.submit(p, 6).result()
        return before, after

    before, after = asyncio.run(_run_engine(engine, work()))
    assert before == _want(lm, p, 6)
    want_new = generate(model, new_vars, np.asarray([p], np.int32), 6,
                        greedy=True)[0].tolist()
    assert after == want_new
    _assert_compile_once(auditor)


# -- observability ------------------------------------------------------------

def test_spec_metrics_histogram_and_debugz_render(lm, rng):
    """Registry counters/histogram, summary keys, the debugz
    speculative section + per-slot accept column, and its text
    rendering — one engine serves all of it."""
    from distkeras_tpu.serving.debugz import format_debugz

    engine = _spec_engine(lm, slots=1, max_queue=4)
    p = _prompt(rng, 5)
    new_tokens = 24  # long enough that ticks remain after the first

    async def work():
        req = engine.submit(p, new_tokens)
        # Snapshot the debugz page mid-stream, once the slot has booked
        # draft statistics (the accept column needs a live slot); bail
        # to the done-check rather than spinning if it finishes first.
        page = None
        while not req.done.is_set():
            st = engine._slot_state[0]
            if st is not None and st.spec_drafted:
                page = format_debugz(engine.debugz())
                break
            await asyncio.sleep(0)
        out = await req.result()
        return page, out

    page, out = asyncio.run(_run_engine(engine, work()))
    assert out == _want(lm, p, new_tokens)
    assert page is not None, "request finished before a spec tick ran"
    assert "speculative: draft=gpt_tiny k=4" in page
    assert "accept" in page  # the slot-table column rendered
    snap = engine.metrics.registry.snapshot()
    drafted = snap["spec_draft_tokens_total"]["value"]
    accepted = snap["spec_accepted_tokens_total"]["value"]
    assert drafted > 0 and accepted == drafted  # sanity config
    hist = snap["serving_spec_accept_len"]
    assert hist["count"] >= 1  # one observation per speculating tick
    assert hist["sum"] == accepted
    s = engine.metrics.summary()
    assert s["spec_accept_rate"] == 1.0
    dz = engine.debugz()
    assert dz["speculative"]["accept_rate"] == 1.0
    assert dz["speculative"]["draft_model"] == "gpt_tiny"


def test_accept_length_reference_semantics():
    """The exported accept-rule helpers: prefix acceptance stops at the
    first rejection (accept_prefix_length), and the strict
    token-equality form (greedy_accept_length) — the reference
    semantics the engine's ε-relaxed gate is measured against."""
    import jax.numpy as jnp

    from distkeras_tpu.inference.generate import (
        accept_prefix_length,
        greedy_accept_length,
    )

    drafts = jnp.array([[1, 2, 3], [1, 9, 3], [9, 9, 9]], jnp.int32)
    target = jnp.array([[1, 2, 3], [1, 2, 3], [1, 2, 3]], jnp.int32)
    assert greedy_accept_length(drafts, target).tolist() == [3, 1, 0]
    # A later re-match after a mismatch must NOT count (d_{j+1} was
    # conditioned on the rejected d_j).
    assert accept_prefix_length(
        jnp.array([[True, False, True]])).tolist() == [1]


def test_spec_ctor_validation(lm):
    model, variables = lm
    with pytest.raises(ValueError, match="draft_variables"):
        ServingEngine(model, variables, draft_model=model)
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(model, variables, draft_model=model,
                      draft_variables=variables, spec_k=0)
    other_vocab = gpt_tiny(seq_len=32, vocab_size=VOCAB * 2)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(model, variables, draft_model=other_vocab,
                      draft_variables=other_vocab.init(0))
