"""The serving host's tick, named: who paces each tick
(``serving_harvests_total{ready}``), how long the engine task waits for
the event loop (``serving_loop_wait_seconds_total{at}``), and the spans
of the front door's turn (``front_read``, ``front_write``), the engine's
end-of-iteration yield (``loop_yield``) and a harvest's blocking read
(``harvest_read``), on a tiny model on the CPU."""

import asyncio
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from distkeras_tpu.models.bert import gpt_tiny  # noqa: E402
from distkeras_tpu.serving import (  # noqa: E402
    ServingClient,
    ServingEngine,
    ServingServer,
)
from distkeras_tpu.serving import wire  # noqa: E402
from distkeras_tpu.serving.debugz import format_debugz  # noqa: E402
from distkeras_tpu.serving.metrics import (  # noqa: E402
    LOOP_WAITS,
    ServingMetrics,
)
from distkeras_tpu.telemetry import (  # noqa: E402
    disable_tracing,
    enable_tracing,
)

# the families the two counters replaced, by prefix
REMOVED = ("serving_host_gap", "serving_device_idle")


@pytest.fixture(scope="module")
def tiny_lm():
    model = gpt_tiny(seq_len=64, vocab_size=61)
    return model, model.init(0)


@pytest.fixture
def tracer():
    t = enable_tracing()
    yield t
    disable_tracing()


def _prompts(n, seed=0, lo=3, hi=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 61, size=int(k)).tolist()
            for k in rng.integers(lo, hi, size=n)]


def _counter(engine, name, **labels):
    key = name + "{" + ",".join(
        f"{k}={v}" for k, v in sorted(labels.items())) + "}"
    return engine.metrics.registry.snapshot()[key]["value"]


def _serve(engine, prompts, new_tokens, blocker_s=0.0):
    """Run ``engine`` over ``prompts``; with ``blocker_s``, a task holds
    the event loop that long at every one of its turns meanwhile."""
    async def main():
        task = asyncio.create_task(engine.run(idle_poll_s=0.01))
        stop = asyncio.Event()

        async def blocker():
            while not stop.is_set():
                time.sleep(blocker_s)
                await asyncio.sleep(0)

        hog = asyncio.create_task(blocker()) if blocker_s else None
        reqs = [engine.submit(p, new_tokens) for p in prompts]
        outs = [await r.result() for r in reqs]
        stop.set()
        if hog is not None:
            await hog
        engine.shutdown(drain=True)
        await task
        return outs

    return asyncio.run(main())


def _harvests(engine):
    return sum(_counter(engine, "serving_harvests_total", ready=r)
               for r in (0, 1))


def test_pipelined_run_counts_one_harvest_per_harvested_tick(tiny_lm):
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1)
    completed = 0
    orig = engine._complete_tick

    async def counting(loop, tick):
        nonlocal completed
        completed += 1
        return await orig(loop, tick)

    engine._complete_tick = counting
    before = _harvests(engine)  # the constructor's warm-up harvests none
    _serve(engine, _prompts(3, seed=1), 6)
    assert before == 0
    assert completed > 0
    assert _harvests(engine) == completed
    # each of them also stamped its tick on the lane
    assert sum("loop_wait_s" in tk for tk in engine.tick_timeline()) == \
        min(completed, 256)


def test_harvest_ready_label_is_the_span_attribute(tiny_lm, tracer):
    """``ready`` is computed once a harvest: the counter's label and the
    ``harvest`` span's attribute agree, tick for tick."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1)
    tracer.clear()
    _serve(engine, _prompts(3, seed=2), 5)
    ready = [attrs["ready"] for ph, name, _, _, _, attrs in tracer.events()
             if ph == "B" and name == "harvest"
             and attrs.get("kind") in ("decode", "spec")]
    assert ready
    assert sum(ready) == _counter(engine, "serving_harvests_total", ready=1)
    assert len(ready) - sum(ready) == _counter(
        engine, "serving_harvests_total", ready=0)


def test_loop_wait_at_yield_grows_by_a_blocked_loop(tiny_lm):
    """Another task holding the event loop 20 ms at each of its turns
    makes every end-of-iteration yield wait at least that long."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1)
    _serve(engine, _prompts(2, seed=3), 4, blocker_s=0.02)
    n = engine.metrics.registry.snapshot()[
        "serving_decode_iterations_total"]["value"]
    waited = _counter(engine, "serving_loop_wait_seconds_total", at="yield")
    assert n >= 3
    assert waited >= n * 0.02


def test_loop_wait_at_harvest_is_the_tick_lanes_sum(tiny_lm):
    """``at=harvest`` adds up, tick by tick, the wait from the read's
    return on the executor thread to the engine task's resumption."""
    model, variables = tiny_lm
    engine = ServingEngine(model, variables, slots=2, pipeline_depth=1)
    t0 = time.monotonic()
    _serve(engine, _prompts(3, seed=4), 5, blocker_s=0.005)
    wall = time.monotonic() - t0
    waited = _counter(engine, "serving_loop_wait_seconds_total",
                      at="harvest")
    lane = [tk["loop_wait_s"] for tk in engine.tick_timeline()
            if "loop_wait_s" in tk]
    assert 0 < waited < wall
    assert sum(lane) == pytest.approx(waited, abs=1e-6 * len(lane))


def _served_spans(tiny_lm, tracer, wire_mode):
    model, variables = tiny_lm

    async def go():
        engine = ServingEngine(model, variables, slots=2, pipeline_depth=1)
        server = ServingServer(engine, port=0)
        await server.start()
        tracer.clear()
        async with ServingClient("127.0.0.1", server.port,
                                 wire_mode=wire_mode) as c:
            done = await c.generate([1, 2, 3, 4], 5)
            proto = c.proto
        await server.stop(drain=True)
        return done, proto

    done, proto = asyncio.run(go())
    assert proto == wire_mode and len(done["tokens"]) == 5
    lanes = tracer.chrome_trace()["traceEvents"]
    names = {e["tid"]: e["args"]["name"] for e in lanes
             if e["ph"] == "M" and e["name"] == "thread_name"}
    return [(name, names[lane], parent, attrs)
            for ph, name, _, lane, parent, attrs in tracer.events()
            if ph == "B"]


@pytest.mark.parametrize("wire_mode", [wire.PROTO_BIN1, wire.PROTO_JSONL])
def test_a_served_request_records_the_host_tick_spans(tiny_lm, tracer,
                                                      wire_mode):
    spans = _served_spans(tiny_lm, tracer, wire_mode)
    seen = {name for name, _, _, _ in spans}
    assert {"front_read", "front_write", "loop_yield", "harvest",
            "harvest_read"} <= seen
    reads = [(lane, parent) for name, lane, parent, attrs in spans
             if name == "harvest_read" and not attrs]
    assert reads
    for lane, parent in reads:
        # on an executor thread, under the loop-side harvest it serves
        assert lane.startswith("thread:") and parent == "harvest"
    yields = [lane for name, lane, _, _ in spans if name == "loop_yield"]
    assert all(lane.startswith("task:") for lane in yields)
    writes = [attrs for name, _, _, attrs in spans if name == "front_write"]
    if wire_mode == wire.PROTO_BIN1:
        # the hello reply is still a JSON line; then one coalesced write
        # per flush, with its size
        sized = [a["bytes"] for a in writes if "bytes" in a]
        assert len(writes) - len(sized) == 1
        assert sized and min(sized) > 0
    else:
        # one line a token, then the done line
        assert len(writes) >= 6


def test_frame_sink_flush_is_one_front_write_of_its_bytes(tracer):
    class Writer:
        transport = None

        def __init__(self):
            self.chunks = []

        def write(self, data):
            self.chunks.append(bytes(data))

        async def drain(self):
            pass

    async def go():
        writer = Writer()
        sink = wire.FrameSink(writer)
        tracer.clear()
        for tok in (5, 6, 7):
            sink.add_token(1, tok)
        sink.add_token(2, 9)
        sink.send_done(2, {"tokens": [9]})
        await asyncio.sleep(0)  # the call_soon flush
        await sink.aclose()
        return writer.chunks

    chunks = asyncio.run(go())
    writes = [attrs for ph, name, _, _, _, attrs in tracer.events()
              if ph == "B" and name == "front_write"]
    assert len(chunks) == len(writes) == 1
    assert writes[0]["bytes"] == len(chunks[0])


def test_metrics_record_the_two_families_by_label():
    m = ServingMetrics()
    m.record_harvest(True)
    m.record_harvest(True)
    m.record_harvest(False)
    m.record_loop_wait("harvest", 0.25)
    m.record_loop_wait("yield", 0.5)
    m.record_loop_wait("yield", 0.125)
    snap = m.registry.snapshot()
    assert snap["serving_harvests_total{ready=1}"]["value"] == 2
    assert snap["serving_harvests_total{ready=0}"]["value"] == 1
    assert snap["serving_loop_wait_seconds_total{at=harvest}"]["value"] \
        == 0.25
    assert snap["serving_loop_wait_seconds_total{at=yield}"]["value"] \
        == 0.625
    assert set(LOOP_WAITS) == {"harvest", "yield"}
    assert m.host_tick() == {"harvests": 3.0, "harvests_ready": 2.0,
                             "loop_wait_harvest_s": 0.25,
                             "loop_wait_yield_s": 0.625}
    with pytest.raises(KeyError):
        m.record_loop_wait("elsewhere", 0.1)


def test_removed_host_gap_metrics_are_absent_everywhere(tiny_lm):
    """The gap and idle ratio that read ~0 on every pipelined tick are
    gone from metricsz (both forms), healthz, debugz and the summary;
    the two families that replace them are there."""
    model, variables = tiny_lm

    async def go():
        engine = ServingEngine(model, variables, slots=2, pipeline_depth=1)
        server = ServingServer(engine, port=0)
        await server.start()
        async with ServingClient("127.0.0.1", server.port) as c:
            await c.generate([3, 1, 4], 4)
            snap = await c.metricsz()
            text = await c.metricsz(format="prometheus")
            health = await c.healthz()
        await server.stop(drain=True)
        return engine, snap, text, health

    engine, snap, text, health = asyncio.run(go())
    for name in REMOVED:
        assert not any(k.startswith(name) for k in snap)
        assert name not in text
    assert "serving_harvests_total{ready=1}" in snap
    assert "serving_loop_wait_seconds_total{at=yield}" in snap
    pl = health["pipeline"]
    assert "host_gap_p50_s" not in pl and "device_idle_ratio" not in pl
    assert pl["harvests"] >= 1 and pl["loop_wait_yield_s"] > 0
    summary = engine.metrics.summary()
    assert not any(k.startswith(("host_gap", "device_idle"))
                   for k in summary)
    page = format_debugz(engine.debugz())
    assert "host_gap" not in page and "device_idle" not in page
    assert "harvests_ready=" in page and "loop_wait harvest=" in page
    assert all("host_gap_s" not in tk for tk in engine.tick_timeline())
