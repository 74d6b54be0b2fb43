"""Continuous-batching serving engine (distkeras_tpu.serving).

The invariants under test, all on CPU with a tiny causal LM:

- greedy streams match one-shot ``generate()`` token-for-token even when
  requests are admitted mid-decode into freed slots;
- admission never retraces the decode step (compile-count probe stays 1);
- slot admit/free bookkeeping (active count bounded, slots reused, all
  free after drain);
- backpressure (``QueueFullError`` at max depth), deadline expiry
  (``RequestTimeout`` queued AND mid-decode), graceful-shutdown drain
  (``EngineStopped`` for the queue, completion for in-flight slots);
- scheduler ordering (priority-FIFO) and the TCP server/client wire.
"""

import asyncio

import numpy as np
import pytest

from distkeras_tpu.inference.generate import generate
from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.serving import (
    EngineStopped,
    QueueFullError,
    Request,
    RequestCancelled,
    RequestTimeout,
    Scheduler,
    ServingClient,
    ServingEngine,
    ServingMetrics,
    ServingServer,
)

VOCAB = 64


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _want(lm, prompt, n):
    model, variables = lm
    return generate(model, variables, np.asarray([prompt], np.int32), n,
                    greedy=True)[0].tolist()


async def _run_engine(engine, coro):
    """Drive ``engine.run()`` alongside ``coro``; shuts down on exit."""
    task = asyncio.create_task(engine.run())
    try:
        return await coro
    finally:
        engine.shutdown(drain=True)
        await task


# -- scheduler unit behavior -------------------------------------------------

def test_scheduler_priority_fifo_and_backpressure():
    async def go():
        s = Scheduler(max_depth=3)
        a = Request([1], 1, priority=1)
        b = Request([2], 1, priority=0)
        c = Request([3], 1, priority=1)
        for r in (a, b, c):
            s.submit(r)
        with pytest.raises(QueueFullError):
            s.submit(Request([4], 1))
        # b first (lower priority value), then a before c (FIFO in tie).
        assert s.pop() is b and s.pop() is a and s.pop() is c
        assert s.pop() is None

    asyncio.run(go())


def test_scheduler_peek_is_nondestructive_head():
    """peek() shows the next pop without consuming it — the paged
    engine's admission-park gate watches it so a parked head that is
    displaced (higher-priority arrival, cancel/expire) reopens
    admission without waiting for the pool version to move."""
    async def go():
        s = Scheduler(max_depth=4)
        assert s.peek() is None
        a = Request([1], 1, priority=1)
        s.submit(a)
        assert s.peek() is a and s.peek() is a  # non-destructive
        b = Request([2], 1, priority=0)
        s.submit(b)
        assert s.peek() is b  # higher priority displaced the head
        assert s.pop() is b and s.peek() is a

    asyncio.run(go())


def test_scheduler_expires_queued_deadlines():
    async def go():
        s = Scheduler(max_depth=4)
        fast = Request([1], 1, timeout=0.0)
        slow = Request([2], 1)
        s.submit(fast, now=100.0)
        s.submit(slow, now=100.0)
        expired = s.expire(now=101.0)
        assert expired == [fast]
        assert s.pop(now=101.0) is slow

    asyncio.run(go())


# -- engine core -------------------------------------------------------------

def test_continuous_batching_parity_and_single_compile(lm, rng):
    """Staggered submissions through fewer slots than requests: later
    requests are admitted into freed slots while earlier ones decode, and
    every greedy stream still matches one-shot generate()."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, max_queue=8)
    prompts = [_prompt(rng, n) for n in (5, 9, 3, 7, 4)]

    async def work():
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(engine.submit(p, 6))
            await asyncio.sleep(0.01 * i)  # arrive mid-decode
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    for p, got in zip(prompts, outs):
        assert got == _want(lm, p, 6)
    # 5 requests through 2 slots admitted mid-decode: still ONE compiled
    # decode executable (continuous batching never retraces). -1 means
    # the probe's private jax attribute vanished in an upgrade — tolerate
    # it rather than false-failing.
    assert engine.decode_compile_count() in (1, -1)
    # Slot bookkeeping: everything freed after the drain.
    assert engine.active_slots == 0
    assert all(s is None for s in engine._slot_state)


def test_slot_reuse_and_occupancy_bound(lm, rng):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=8)

    async def work():
        r1 = engine.submit(_prompt(rng, 4), 3)
        r2 = engine.submit(_prompt(rng, 6), 3)
        o1, o2 = await r1.result(), await r2.result()
        return o1, o2

    o1, o2 = asyncio.run(_run_engine(engine, work()))
    assert len(o1) == 3 and len(o2) == 3
    # One slot served both sequentially; occupancy never exceeded 1 slot.
    assert engine.metrics.completed == 2
    assert max(engine.metrics._occupancy) <= 1.0


def test_backpressure_rejects_with_typed_error(lm, rng):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=2)
    # No run() loop: the queue only fills. max_queue=2 admits two, the
    # third is shed BEFORE any device work, with the typed error.
    engine.submit(_prompt(rng, 3), 2)
    engine.submit(_prompt(rng, 3), 2)
    with pytest.raises(QueueFullError):
        engine.submit(_prompt(rng, 3), 2)
    assert engine.metrics.rejected == 1


def test_submit_validates_before_queueing(lm):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1)
    with pytest.raises(ValueError, match="trained context"):
        engine.submit(list(range(28)), 8)  # 28 + 8 > 32
    with pytest.raises(ValueError, match="non-empty"):
        engine.submit([], 4)
    assert len(engine.scheduler) == 0


def test_timeout_expires_queued_request(lm, rng):
    """A request whose deadline passes while WAITING for a slot gets
    RequestTimeout, while the slot-holder completes normally."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=4)

    async def work():
        long_req = engine.submit(_prompt(rng, 4), 10)
        doomed = engine.submit(_prompt(rng, 4), 2, timeout=0.0)
        out = await long_req.result()
        with pytest.raises(RequestTimeout):
            await doomed.result()
        return out

    out = asyncio.run(_run_engine(engine, work()))
    assert len(out) == 10
    assert engine.metrics.expired == 1


def test_timeout_expires_mid_decode(lm, rng):
    """A deadline passing mid-generation frees the slot early: the stream
    ends in RequestTimeout after at least the prefill token arrived."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1)

    async def work():
        req = engine.submit(_prompt(rng, 4), 28)
        # Wait until admitted (first token streamed), then move the
        # deadline into the past — deterministic mid-decode expiry with
        # no dependence on this machine's decode-step wall time.
        kind, _ = await req.events.get()
        assert kind == "token"
        req.timeout = -1.0
        with pytest.raises(RequestTimeout):
            await req.result()
        return req

    req = asyncio.run(_run_engine(engine, work()))
    assert 1 <= len(req.out_tokens) < 28
    assert engine.active_slots == 0


def test_cancel_frees_slot_mid_decode_and_in_queue(lm, rng):
    """cancel() releases a held slot (client-disconnect path) so queued
    work takes it over, and drops a still-queued request."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=4)

    waiting_prompt = _prompt(rng, 5)

    async def work():
        holder = engine.submit(_prompt(rng, 4), 28)
        kind, _ = await holder.events.get()
        assert kind == "token"  # holder owns the slot
        waiting = engine.submit(waiting_prompt, 3)
        doomed = engine.submit(_prompt(rng, 3), 3)
        holder.cancel()
        doomed.cancel()
        out = await waiting.result()  # takes over the freed slot
        with pytest.raises(RequestCancelled):
            await holder.result()
        with pytest.raises(RequestCancelled):
            await doomed.result()
        return out

    out = asyncio.run(_run_engine(engine, work()))
    assert out == _want(lm, waiting_prompt, 3)
    assert engine.active_slots == 0


def test_graceful_shutdown_drains_active_rejects_queued(lm, rng):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=4)

    async def go():
        task = asyncio.create_task(engine.run())
        active = engine.submit(_prompt(rng, 4), 8)
        # Wait for admission (first token) so `active` holds the slot.
        kind, _ = await active.events.get()
        assert kind == "token"
        queued = engine.submit(_prompt(rng, 4), 4)
        engine.shutdown(drain=True)
        with pytest.raises(EngineStopped):
            engine.submit(_prompt(rng, 3), 2)  # late arrival: typed reject
        out = await active.result()  # drained to completion
        with pytest.raises(EngineStopped):
            await queued.result()  # queued work is shed
        await task
        return out

    out = asyncio.run(go())
    assert len(out) == 8
    assert engine.active_slots == 0


def test_sampled_and_greedy_coexist_one_program(lm, rng):
    """temperature>0 rows sample, temperature<=0 rows stay argmax — in
    the same compiled step (no retrace between them)."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, seed=3)
    p = _prompt(rng, 5)

    async def work():
        greedy = engine.submit(p, 6)
        hot = engine.submit(p, 6, temperature=5.0)
        return await greedy.result(), await hot.result()

    g, h = asyncio.run(_run_engine(engine, work()))
    assert g == _want(lm, p, 6)
    assert all(0 <= t < VOCAB for t in h)
    assert engine.decode_compile_count() in (1, -1)


def test_metrics_summary_and_stream(lm, rng, tmp_path):
    import json

    from distkeras_tpu.tracing import MetricStream

    model, variables = lm
    path = tmp_path / "serving.jsonl"
    metrics = ServingMetrics(MetricStream.to_jsonl(str(path)))
    engine = ServingEngine(model, variables, slots=2, metrics=metrics)

    async def work():
        reqs = [engine.submit(_prompt(rng, n), 4) for n in (3, 5, 4)]
        return [await r.result() for r in reqs]

    asyncio.run(_run_engine(engine, work()))
    s = metrics.emit_summary()
    for key in ("ttft_p50_s", "ttft_p95_s", "ttft_p99_s",
                "inter_token_p50_s", "tokens_per_sec",
                "slot_occupancy_mean"):
        assert key in s, key
    assert s["requests_completed"] == 3
    assert s["tokens_out"] == 12
    lines = [json.loads(l) for l in open(path)]
    # Per-iteration series plus the final summary record.
    assert any("summary" in rec for rec in lines)
    assert any("queue_depth" in rec for rec in lines)


# -- chunked prefill ----------------------------------------------------------

def test_chunked_prefill_parity_and_ttft_split(lm, rng):
    """Chunked admission (one prefill chunk per decode tick) is greedy
    token-identical to generate(), never retraces the armed decode step,
    and records the TTFT split (admission wait vs prefill device time)."""
    from distkeras_tpu.telemetry import RecompileAuditor

    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=8,
                           prefill_chunk=4, auditor=auditor,
                           arm_auditor_after_warmup=True)
    prompts = [_prompt(rng, n) for n in (13, 5, 9, 3, 11)]

    async def work():
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(engine.submit(p, 5))
            await asyncio.sleep(0.01 * i)
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    for p, got in zip(prompts, outs):
        assert got == _want(lm, p, 5)
    assert auditor.compiles("serving_decode") == 1
    assert engine.decode_compile_count() in (1, -1)
    s = engine.metrics.summary()
    # 13-token prompt through 4-token chunks = 4 chunks.
    assert s["prefill_chunks_max"] == 4.0
    # The split: both halves of TTFT recorded per request.
    assert s["prefill_device_p50_s"] > 0
    assert "queue_wait_p50_s" in s
    snap = engine.metrics.registry.snapshot()
    assert snap["serving_prefill_device_seconds"]["count"] == len(prompts)
    assert snap["serving_queue_wait_seconds"]["count"] == len(prompts)


def test_the_first_chunk_compiles_the_other_widths_behind_it(lm, rng):
    """With a chunk size and a persistent compile cache (the suite's), the
    first prefill chunk starts a thread for each other width a chunk can
    be padded to (here 4 and 8 beside the full 16) and one for the decode
    tick, each compiling that program; the program's first entry waits for
    it, and the engine decodes as generate() does."""
    import jax

    assert jax.config.jax_compilation_cache_dir
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, max_queue=8,
                           prefill_chunk=16, min_prefill_bucket=4)
    prompts = [_prompt(rng, 16), _prompt(rng, 7)]

    async def work():
        first = await engine.submit(prompts[0], 3).result()
        behind = dict(engine._behind)
        return first, behind, await engine.submit(prompts[1], 3).result()

    first, behind, second = asyncio.run(_run_engine(engine, work()))
    assert sorted(behind) == [4, 8]  # the first request entered decode
    assert all(f.result() is None for f in behind.values())
    assert sorted(engine._behind) == [4]  # the 7-token chunk took 8
    assert first == _want(lm, prompts[0], 3)
    assert second == _want(lm, prompts[1], 3)


def test_scheduler_cache_aware_pop_prefers_hits_within_class():
    async def go():
        scores = {(7, 7): 8, (1, 1): 0, (2, 2): 4}
        s = Scheduler(max_depth=8,
                      cache_probe=lambda p: scores.get(tuple(p), 0))
        cold = Request([1, 1], 1)
        warm = Request([7, 7], 1)
        lukewarm = Request([2, 2], 1)
        urgent = Request([1, 1], 1, priority=-1)
        for r in (cold, warm, lukewarm):
            s.submit(r)
        # Best hit first within the class; FIFO among the rest.
        assert s.pop() is warm
        s.submit(urgent)
        # A better-priority class is NEVER jumped by a cache hit.
        assert s.pop() is urgent
        assert s.pop() is lukewarm and s.pop() is cold
        # Without a probe, pure priority-FIFO (regression guard).
        s2 = Scheduler(max_depth=4)
        x, y = Request([7, 7], 1), Request([1, 1], 1)
        s2.submit(x)
        s2.submit(y)
        assert s2.pop() is x and s2.pop() is y
        # Starvation bound: a cold head under sustained warm traffic is
        # served once its overtake budget is exhausted.
        s3 = Scheduler(max_depth=16, cache_probe=lambda p: p[0])
        cold3 = Request([0], 1)
        s3.submit(cold3)
        for _ in range(s3.max_overtake):
            s3.submit(Request([9], 1))
            assert s3.pop() is not cold3  # warm hit jumps ahead
        s3.submit(Request([9], 1))
        assert s3.pop() is cold3  # budget spent: FIFO wins

    asyncio.run(go())


# -- telemetry integration ---------------------------------------------------

def test_recompile_auditor_armed_is_runtime_invariant(lm, rng):
    """THE engine invariant, as a runtime check instead of a benchmark
    assertion: with the auditor armed after the first decode iteration,
    admissions into freed slots mid-decode must not retrace the decode
    step — any retrace would raise RecompileError and fail this test."""
    from distkeras_tpu.telemetry import RecompileAuditor

    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=8,
                           auditor=auditor, arm_auditor_after_warmup=True)
    prompts = [_prompt(rng, n) for n in (5, 9, 3, 7, 4)]

    async def work():
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(engine.submit(p, 6))
            await asyncio.sleep(0.01 * i)  # arrive mid-decode, post-arming
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    for p, got in zip(prompts, outs):
        assert got == _want(lm, p, 6)
    # Armed + completed == the invariant held at runtime; the counts agree.
    assert auditor.compiles("serving_decode") == 1
    assert auditor.report()["serving_decode"]["armed"]
    assert engine.decode_compile_count() in (1, -1)
    # The admit splice compiles at most once per process: it wraps the
    # module-level _admit_fn, so jax shares its executable cache across
    # engines — an earlier engine in this test session may have already
    # paid the one compile (0 new compiles here is the cache working).
    assert auditor.compiles("serving_admit") <= 1
    assert auditor.report()["serving_admit"]["calls"] == len(prompts)


def test_engine_spans_export_chrome_trace(lm, rng):
    """A traced serving run yields one Perfetto-loadable timeline:
    admit/prefill/decode_tick spans present, B/E matched per lane even
    though engine iterations and client tasks interleave on one loop."""
    import distkeras_tpu.telemetry as T

    model, variables = lm
    tracer = T.enable_tracing()
    try:
        engine = ServingEngine(model, variables, slots=2)

        async def work():
            reqs = [engine.submit(_prompt(rng, n), 4) for n in (3, 6)]
            return [await r.result() for r in reqs]

        asyncio.run(_run_engine(engine, work()))
    finally:
        T.disable_tracing()
    trace = tracer.chrome_trace()
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "B"}
    assert {"admit", "prefill", "decode_tick", "stream"} <= names
    # Matched B/E per lane (the Perfetto structural requirement).
    stacks = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev["name"])
        elif ev["ph"] == "E":
            assert stacks.get(ev["tid"]), "E without matching B"
            assert stacks[ev["tid"]].pop() == ev["name"]
    assert all(not s for s in stacks.values())
    # prefill nests under admit (executor thread lane tracks the caller's
    # context because contextvars flow into run_in_executor).
    prefill_b = next(e for e in trace["traceEvents"]
                     if e["ph"] == "B" and e["name"] == "prefill")
    assert prefill_b["args"]["parent"] == "admit"


def test_serving_metrics_publish_to_registry(lm, rng):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2)

    async def work():
        reqs = [engine.submit(_prompt(rng, n), 4) for n in (3, 5)]
        return [await r.result() for r in reqs]

    asyncio.run(_run_engine(engine, work()))
    snap = engine.metrics.registry.snapshot()
    assert snap["serving_requests_completed_total"]["value"] == 2
    assert snap["serving_tokens_out_total"]["value"] == 8
    assert snap["serving_ttft_seconds"]["count"] == 2
    assert snap["scheduler_submitted_total"]["value"] == 2
    # Counter compatibility surface still reads through.
    assert engine.metrics.completed == 2 and engine.metrics.tokens_out == 8


# -- TCP front end -----------------------------------------------------------

def test_tcp_server_streams_and_matches_generate(lm, rng):
    model, variables = lm
    p1, p2 = _prompt(rng, 6), _prompt(rng, 4)

    async def go():
        engine = ServingEngine(model, variables, slots=2)
        server = ServingServer(engine, port=0)
        await server.start()

        async def one(p):
            streamed = []
            async with ServingClient("127.0.0.1", server.port) as c:
                done = await c.generate(p, 5, on_token=streamed.append)
            return streamed, done

        (s1, d1), (s2, d2) = await asyncio.gather(one(p1), one(p2))
        await server.stop(drain=True)
        return (s1, d1), (s2, d2)

    (s1, d1), (s2, d2) = asyncio.run(go())
    assert s1 == d1["tokens"] == _want(lm, p1, 5)
    assert s2 == d2["tokens"] == _want(lm, p2, 5)
    assert d1["ttft_ms"] > 0 and d1["latency_ms"] >= d1["ttft_ms"]


def test_tcp_server_metricsz_and_healthz_verbs(lm, rng):
    """Live metrics exposition over the existing JSONL protocol: one
    request line in, one reply line out — JSON snapshot, the Prometheus
    text page, and the engine health view."""
    model, variables = lm

    async def go():
        engine = ServingEngine(model, variables, slots=2)
        server = ServingServer(engine, port=0)
        await server.start()
        async with ServingClient("127.0.0.1", server.port) as c:
            await c.generate(_prompt(rng, 4), 3)
            snap = await c.metricsz()
            prom = await c.metricsz(format="prometheus")
            health = await c.healthz()
            c._writer.write(b'{"cmd": "nope"}\n')
            await c._writer.drain()
            import json as _json

            bad = _json.loads(await c._reader.readline())
            # The connection still serves generation after control verbs.
            toks = [t async for t in c.stream(_prompt(rng, 3), 2)]
        await server.stop(drain=True)
        return snap, prom, health, bad, toks

    snap, prom, health, bad, toks = asyncio.run(go())
    assert snap["serving_requests_completed_total"]["value"] == 1
    assert snap["serving_ttft_seconds"]["count"] == 1
    assert "# TYPE serving_ttft_seconds histogram" in prom
    assert "serving_requests_completed_total 1" in prom
    assert health["slots"] == 2 and health["active_slots"] == 0
    assert health["decode_compile_count"] in (1, -1)
    assert bad["code"] == "bad_request"
    assert len(toks) == 2


def test_server_stop_drain_completes_inflight_rejects_new(lm, rng):
    """THE contract the cluster's rolling reload stands on: during
    ``server.stop(drain=True)`` a mid-stream request runs to completion
    (its tokens keep flowing and match generate()) while new admissions
    on already-open connections are rejected with the typed ``stopped``
    error."""
    model, variables = lm
    p = _prompt(rng, 4)

    async def go():
        engine = ServingEngine(model, variables, slots=1)
        server = ServingServer(engine, port=0)
        await server.start()
        streamer = ServingClient("127.0.0.1", server.port)
        late = ServingClient("127.0.0.1", server.port)
        await streamer.connect()
        await late.connect()  # connected BEFORE the listener closes
        stream = streamer.stream(p, 12)
        first = await stream.__anext__()  # admitted, mid-stream
        stop_task = asyncio.create_task(server.stop(drain=True))
        await asyncio.sleep(0)  # let stop() close admission
        # A new request over the still-open connection is shed with the
        # typed error, not a hang and not a dropped connection.
        with pytest.raises(EngineStopped):
            await late.generate(_prompt(rng, 3), 2)
        # The in-flight stream drains to its full length.
        toks = [first] + [t async for t in stream]
        await streamer.aclose()
        await late.aclose()
        await stop_task
        return toks

    toks = asyncio.run(go())
    assert toks == _want(lm, p, 12)


def test_client_control_verbs_reconnect_with_backoff(lm, rng):
    """metricsz/healthz survive a server bounce: the client drops its
    dead connection and redials with capped backoff (RetryingClient's
    pattern) instead of surfacing a raw ConnectionResetError; the budget
    exhausts into a typed ConnectionError when nobody is listening."""
    model, variables = lm

    async def go():
        engine = ServingEngine(model, variables, slots=1)
        server = ServingServer(engine, port=0)
        await server.start()
        port = server.port
        client = ServingClient("127.0.0.1", port, base_delay_s=0.01)
        h1 = await client.healthz()  # pins a live connection
        await server.stop(drain=True)
        # Same-port restart — a replica bounce as a monitor would see it.
        server2 = ServingServer(
            ServingEngine(model, variables, slots=1), port=port)
        await server2.start()
        h2 = await client.healthz()  # stale conn -> reconnect -> answer
        await server2.stop(drain=True)
        # stop() only closes the LISTENER; drop our live connection so
        # the next verb must redial a port nobody listens on.
        await client.aclose()
        with pytest.raises(ConnectionError, match="healthz"):
            await client.healthz()  # budget exhausts into a typed error
        return h1, h2

    h1, h2 = asyncio.run(go())
    assert h1["slots"] == 1 and h2["slots"] == 1


def test_server_reload_verb_swaps_weights(lm, rng, tmp_path):
    """The replica-side half of the rolling reload: the ``reload`` verb
    hot-swaps params from a weights file on a live server — outputs
    before match the old weights, after match the new, and bad input
    fails typed without disturbing serving."""
    from distkeras_tpu.checkpoint import save_weights_file
    from distkeras_tpu.serving.client import ServerError

    model, variables = lm
    new_vars = model.init(7)
    path = str(tmp_path / "w.bin")
    save_weights_file(path, new_vars)
    p = _prompt(rng, 5)

    async def go():
        engine = ServingEngine(model, variables, slots=2)
        server = ServingServer(engine, port=0)
        await server.start()
        async with ServingClient("127.0.0.1", server.port) as c:
            before = (await c.generate(p, 4))["tokens"]
            rep = await c.reload(path)
            after = (await c.generate(p, 4))["tokens"]
            with pytest.raises(ServerError):
                await c.reload(str(tmp_path / "missing.bin"))
            still = (await c.generate(p, 4))["tokens"]
        await server.stop(drain=True)
        return before, rep, after, still

    before, rep, after, still = asyncio.run(go())
    assert rep["ok"]
    assert before == _want(lm, p, 4)
    want_new = generate(model, new_vars, np.asarray([p], np.int32), 4,
                        greedy=True)[0].tolist()
    assert after == still == want_new


def test_tcp_server_rejects_bad_and_overflow_requests(lm, rng):
    model, variables = lm

    async def go():
        engine = ServingEngine(model, variables, slots=1, max_queue=1)
        server = ServingServer(engine, port=0)
        await server.start()
        codes = []
        async with ServingClient("127.0.0.1", server.port) as c:
            # Context overflow -> bad_request (ValueError server-side).
            c._writer.write(b'{"prompt": [1], "max_new_tokens": 99}\n')
            await c._writer.drain()
            import json as _json

            codes.append(_json.loads(await c._reader.readline()).get("code"))
            # Malformed -> bad_request.
            c._writer.write(b'{"max_new_tokens": 2}\n')
            await c._writer.drain()
            codes.append(_json.loads(await c._reader.readline()).get("code"))
            # Uncastable timeout -> bad_request at submit, NOT a TypeError
            # later inside the engine loop's deadline arithmetic (which
            # would kill serving for every connection).
            c._writer.write(
                b'{"prompt": [1], "max_new_tokens": 2, "timeout": "zzz"}\n')
            await c._writer.drain()
            codes.append(_json.loads(await c._reader.readline()).get("code"))
            # The engine survived: a well-formed request still completes.
            toks = [t async for t in c.stream([1, 2], 2)]
        await server.stop()
        return codes, toks

    codes, toks = asyncio.run(go())
    assert codes == ["bad_request", "bad_request", "bad_request"]
    assert len(toks) == 2
