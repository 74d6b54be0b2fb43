"""Per-request distributed tracing, flight recorder, debugz.

Covers the observability layer end to end:

- unit: TimelineRecord/TraceStore bounds + Chrome export (one lane per
  request, every event carries its trace_id), FlightRecorder ring
  semantics (fixed slots, overwrite-oldest) and dump round trips;
- engine: a traced request's timeline carries queue wait, prefill
  chunks with device time, first token, terminal status; the debugz
  verb's slot/queue tables; histogram exemplars name the worst request;
- the disabled path: no store/recorder -> no timeline objects at all,
  and TTFT exemplars still work (they ride the always-on trace_id);
- the armed RecompileAuditor stays silent with tracing + flight
  recorder + SLO all on (tracing must not perturb the compiled step);
- cluster: trace-id CONTINUITY across a router retry — chaos-kill a
  replica mid-queue and the merged tracez shows both replica hops under
  ONE trace_id; a mid-stream loss's replica_lost error carries the
  trace_id; a chaos-killed replica leaves a flight-recorder dump the
  supervisor references in its restart log.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.serving import (
    LocalReplica,
    ServingClient,
    ServingCluster,
    ServingEngine,
)
from distkeras_tpu.serving.client import ServerError
from distkeras_tpu.serving.server import ServingServer
from distkeras_tpu.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    RecompileAuditor,
    TimelineRecord,
    TraceStore,
    chrome_trace,
    load_flight_dump,
    merge_trace,
    new_trace_id,
)

VOCAB = 64

SUP = dict(health_interval_s=0.05, health_timeout_s=2.0, fail_after=2,
           base_delay_s=0.05, max_delay_s=1.0, stable_after_s=0.5)


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


# -- units --------------------------------------------------------------------

def test_trace_store_bounds_and_chrome_export():
    store = TraceStore(capacity=3)
    tids = [new_trace_id() for _ in range(5)]
    assert len(set(tids)) == 5
    for tid in tids:
        rec = TimelineRecord(tid, "engine", "r0")
        rec.event("submit", prompt_tokens=4)
        rec.event("admit", dur_s=0.01)
        rec.data["status"] = "ok"
        store.put(rec)
    assert len(store) == 3 and store.evicted == 2
    assert store.get(tids[0]) is None  # oldest evicted
    assert store.get(tids[-1])["data"]["status"] == "ok"

    ct = chrome_trace(store.recent(10))
    names = [e for e in ct["traceEvents"] if e["ph"] == "M"]
    assert len(names) == 3  # one lane (metadata name) per request
    lanes = {e["tid"] for e in ct["traceEvents"]}
    assert len(lanes) == 3
    # Every non-metadata event carries its trace_id; dur_s events render
    # as complete slices.
    body = [e for e in ct["traceEvents"] if e["ph"] != "M"]
    assert all(e["args"]["trace_id"] in tids for e in body)
    assert any(e["ph"] == "X" and e["dur"] > 0 for e in body)


def test_trace_store_keeps_multiple_hops_per_id():
    store = TraceStore(capacity=8)
    tid = new_trace_id()
    for src in ("r0", "r1"):
        rec = TimelineRecord(tid, "engine", src)
        rec.event("submit")
        store.put(rec)
    hops = store.get_all(tid)
    assert [h["source"] for h in hops] == ["r0", "r1"]
    merged = merge_trace(tid, hops)
    assert merged["hops"] == ["r0", "r1"]
    assert [e[2] for e in merged["events"]] == ["submit", "submit"]


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4, timeline_capacity=2, slow_capacity=2,
                        dump_path=str(tmp_path / "black_box.json"),
                        source="r7")
    for i in range(7):
        fr.record_event(f"e{i}", n=i)
    # Fixed slots, overwrite-oldest: only the last `capacity` survive.
    assert [e[1] for e in fr._events.items()] == ["e3", "e4", "e5", "e6"]
    assert fr.stats()["events_recorded"] == 7

    for i in range(3):
        fr.record_timeline({"trace_id": f"t{i}", "data": {}}, slow=(i == 1))
    path = fr.dump()
    dump = load_flight_dump(path)
    assert dump["source"] == "r7"
    assert [e["kind"] for e in dump["events"]] == ["e3", "e4", "e5", "e6"]
    assert [t["trace_id"] for t in dump["timelines"]] == ["t1", "t2"]
    assert [t["trace_id"] for t in dump["slow_exemplars"]] == ["t1"]

    # crash_dump never raises, even with an unwritable path.
    bad = FlightRecorder(capacity=2, dump_path="/nonexistent-dir/x.json")
    assert bad.crash_dump(error="boom") is None


def test_histogram_exemplars_track_worst_per_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
    h.observe(0.05, exemplar="fast")
    h.observe(0.08, exemplar="fast2")
    h.observe(0.5, exemplar="mid")
    h.observe(5.0, exemplar="slow")
    ex = h.exemplars()
    assert ex["0.1"]["trace_id"] == "fast2" and ex["0.1"]["value"] == 0.08
    assert ex["1.0"]["trace_id"] == "mid"
    assert ex["+Inf"]["trace_id"] == "slow"
    snap = reg.snapshot()["t_seconds"]
    assert snap["exemplars"]["+Inf"]["trace_id"] == "slow"


# -- engine + server ----------------------------------------------------------

def test_engine_timeline_debugz_and_auditor_silence(lm, rng, artifact_dir):
    """One traced request through the full server: the timeline record
    has the canonical phases, debugz shows the live tables, exemplars
    name the request — and the ARMED auditor proves tracing + flight
    recorder + SLO never retrace the decode step."""
    model, variables = lm
    store = TraceStore(64)
    recorder = FlightRecorder(
        64, dump_path=str(artifact_dir / "flight-single.json"), source="e0")
    engine = ServingEngine(
        model, variables, slots=2, max_queue=8,
        kv_block_tokens=4, prefill_chunk=4,
        auditor=RecompileAuditor(), arm_auditor_after_warmup=True,
        trace_store=store, flight_recorder=recorder,
        slo_s=1e-9)  # everything violates: exercises the slow ring
    prompt = _prompt(rng, 9)

    async def go():
        server = ServingServer(engine, port=0)
        await server.start()
        try:
            async with ServingClient("127.0.0.1", server.port) as c:
                my_tid = new_trace_id()
                done = await c.generate(prompt, 5, trace_id=my_tid)
                assert done["trace_id"] == my_tid
                assert c.last_trace_id == my_tid
                # Second request warms the prefix cache path too.
                done2 = await c.generate(prompt[:8] + _prompt(rng, 3), 4)
                dz = await c.debugz()
                tz = await c.tracez(my_tid)
                health = await c.healthz()
                metrics = await c.metricsz()
            return done, done2, dz, tz, health, metrics
        finally:
            await server.stop(drain=True)

    done, done2, dz, tz, health, metrics = asyncio.run(go())
    tid = done["trace_id"]

    # Timeline: canonical phases in order, with the summary data.
    hops = tz["hops"]
    assert len(hops) == 1 and hops[0]["trace_id"] == tid
    names = [e[0] for e in hops[0]["events"]]
    assert names[0] == "submit" and names[-1] == "done"
    assert "admit" in names and "first_token" in names
    assert names.count("prefill_chunk") == hops[0]["data"]["prefill_chunks"]
    d = hops[0]["data"]
    assert d["status"] == "ok" and d["tokens_out"] == 5
    assert d["queue_wait_s"] >= 0 and d["prefill_device_s"] > 0
    assert d["prompt_tokens"] == len(prompt)
    assert d["slo_violation"] is True  # the 1ns SLO
    assert d["decode_iterations"] >= 1

    # debugz: slot/queue tables, prefix-cache families, recorder stats.
    assert [s["state"] for s in dz["slots"]] == ["free", "free"]
    assert dz["queue"]["depth"] == 0
    assert dz["kv_pool"]["families"] >= 1
    fam = dz["kv_pool"]["top_families"][0]
    assert fam["blocks"] >= 1 and fam["tokens"] >= 4
    assert dz["flight_recorder"]["timelines_recorded"] == 2
    assert dz["slo_s"] == 1e-9

    # healthz/metricsz: SLO counter + exemplars riding the trace_id.
    assert health["slo_violations"] == 2
    ttft_ex = metrics["serving_ttft_seconds"]["exemplars"]
    # Only two requests ran: every bucket's worst sample names one.
    assert ttft_ex and all(v["trace_id"] in (tid, done2["trace_id"])
                           for v in ttft_ex.values())
    itl_ex = metrics["serving_inter_token_seconds"]["exemplars"]
    assert itl_ex, "inter-token histogram recorded no exemplars"
    assert metrics["serving_slo_violations_total"]["value"] == 2

    # Flight recorder: both timelines in the ring, both slow exemplars.
    assert len(recorder.slow_exemplars()) == 2

    # THE invariant: all of it on, decode still compiled exactly once.
    assert engine.auditor.compiles("serving_decode") == 1
    assert engine.auditor.report()["serving_decode"]["armed"]
    # Artifacts for CI's on-failure upload: black box, metrics snapshot
    # JSONL, and the one-lane-per-request Chrome trace.
    from distkeras_tpu.telemetry import write_snapshot_jsonl

    recorder.dump()
    write_snapshot_jsonl(engine.metrics.registry,
                         str(artifact_dir / "metrics-snapshot.jsonl"))
    store.export_chrome_trace(str(artifact_dir / "request-trace.json"))
    exported = json.load(open(artifact_dir / "request-trace.json"))
    lanes = {e["tid"] for e in exported["traceEvents"]}
    assert len(lanes) == 2  # one lane per request


def test_disabled_path_builds_no_timelines(lm, rng):
    """No store, no recorder, no SLO: requests never grow a timeline
    object (the per-token path has nothing to touch), yet trace ids
    still flow end to end for correlation."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=4)
    assert engine.trace_store is None and engine.flight_recorder is None

    async def go():
        task = asyncio.create_task(engine.run())
        req = engine.submit(_prompt(rng, 5), 4, trace_id="cafe01")
        assert req.trace is None  # never built
        out = await req.result()
        engine.shutdown(drain=True)
        await task
        return req, out

    req, out = asyncio.run(go())
    assert req.trace is None and req.trace_id == "cafe01"
    assert len(out) == 4
    # Exemplars still recorded (they ride the always-present trace_id).
    snap = engine.metrics.registry.snapshot()
    assert any(v["trace_id"] == "cafe01"
               for v in snap["serving_ttft_seconds"]["exemplars"].values())


def test_engine_crash_dumps_flight_recorder(lm, rng, tmp_path):
    """The run loop dying (cancellation == LocalReplica chaos kill)
    writes the last-words dump before the exception propagates."""
    model, variables = lm
    path = str(tmp_path / "last_words.json")
    engine = ServingEngine(
        model, variables, slots=1, max_queue=4,
        flight_recorder=FlightRecorder(32, dump_path=path, source="dying"))

    async def go():
        task = asyncio.create_task(engine.run())
        req = engine.submit(_prompt(rng, 20), 10)
        async for _ in req.tokens():
            break  # mid-stream
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    asyncio.run(go())
    dump = load_flight_dump(path)
    assert dump["source"] == "dying"
    kinds = [e["kind"] for e in dump["events"]]
    assert "engine_start" in kinds and "crash" in kinds


# -- cluster: trace continuity across retry -----------------------------------

def _traced_factory(lm_pair, recorders, dump_dir, **engine_kwargs):
    """LocalReplica factory whose engines all carry trace stores and
    flight recorders dumping under ``dump_dir``."""
    model, variables = lm_pair

    def make(i):
        def build():
            recorder = FlightRecorder(
                128, dump_path=str(dump_dir / f"flight-r{i}.json"),
                source=f"r{i}")
            recorders[i] = recorder
            eng = ServingEngine(
                model, variables, slots=2, max_queue=16,
                trace_store=TraceStore(256), flight_recorder=recorder,
                **engine_kwargs)
            eng.trace_source = f"r{i}"
            return eng

        return LocalReplica(build)

    return make


def test_trace_continuity_across_router_retry(lm, rng, artifact_dir):
    """Chaos-kill a replica while requests are queued on it: a retried
    (zero-streamed) request's MERGED timeline shows both replica hops
    under one trace_id; any mid-stream loss's replica_lost error carries
    its trace_id; and the killed replica's flight-recorder dump lands in
    the supervisor's restart log."""
    prompts = [_prompt(rng, 4 + (i % 5)) for i in range(12)]

    async def go():
        recorders = {}
        cluster = ServingCluster(
            _traced_factory(lm, recorders, artifact_dir), 2,
            supervisor_kwargs=SUP, registry=MetricsRegistry())
        results, failures = {}, {}

        async def client_task(idx, p):
            streamed = []
            c = ServingClient("127.0.0.1", cluster.port)
            try:
                done = await c.generate(p, 8, on_token=streamed.append)
                results[idx] = done
            except (ServerError, ConnectionError) as e:
                failures[idx] = (e, len(streamed), c.last_trace_id)
            finally:
                await c.aclose()

        async with cluster:
            tasks = [asyncio.create_task(client_task(i, p))
                     for i, p in enumerate(prompts)]
            while len(results) < 2:
                await asyncio.sleep(0.01)
            await cluster.replicas["r0"].handle.kill()
            await asyncio.gather(*tasks)

            # Merged traces come off the router while it can still reach
            # the surviving + restarted replicas.
            deadline = time.monotonic() + 30
            while cluster.supervisor.ready_count < 2:
                assert time.monotonic() < deadline, "no restart"
                await asyncio.sleep(0.02)
            async with ServingClient("127.0.0.1", cluster.port) as c:
                merged = {idx: await c.tracez(done["trace_id"])
                          for idx, done in results.items()}
            log = cluster.supervisor.restart_log_entries()
        return results, failures, merged, log

    results, failures, merged, log = asyncio.run(go())

    # Completions all carry ids; find one that retried (two hops).
    retried = {idx: m for idx, m in merged.items()
               if m["router"] and m["router"]["data"].get("retries", 0) > 0}
    assert retried, "chaos kill produced no zero-streamed retry"
    for idx, m in retried.items():
        tid = results[idx]["trace_id"]
        assert m["trace_id"] == tid
        router_hops = m["router"]["data"]["hops"]
        assert len(router_hops) >= 2, (
            f"retried request {tid} shows hops {router_hops}")
        assert "retry" in [e[2] for e in m["events"]]
        # The SECOND hop's engine timeline survived (the first died with
        # r0 — its record is in r0's flight dump, referenced below).
        assert any(h["data"].get("status") == "ok"
                   for h in m["engine_hops"])
        assert all(h["trace_id"] == tid for h in m["engine_hops"])

    # Mid-stream losses carry the trace_id on the typed error. A killed
    # LocalReplica's handlers may flush the replica's own engine-failure
    # line ("error") before the connection drops ("replica_lost") — both
    # are mid-stream terminal errors and both must name the request.
    # (test_replica_lost_error_carries_trace_id forces the pure
    # connection-drop path deterministically.)
    for idx, (err, streamed, tid) in failures.items():
        assert streamed >= 1
        if isinstance(err, ServerError):
            assert err.code in ("replica_lost", "error"), err.code
            assert err.trace_id == tid, (
                f"mid-stream {err.code} error lost its trace_id: {err}")

    # The supervisor's restart log references r0's last-words dump, and
    # the dump itself holds timelines from before the kill.
    death = [e for e in log if e.get("rid") == "r0" and "why" in e]
    assert death, log
    assert death[0]["flight_recorder"].endswith("flight-r0.json")
    assert isinstance(death[0]["last_words"], dict)
    dump = load_flight_dump(death[0]["flight_recorder"])
    assert dump["source"] == "r0"
    assert any(e["kind"] == "crash" for e in dump["events"])
    restarted = [e for e in log if e.get("restarted")]
    assert restarted and restarted[0]["rid"] == "r0"


def test_replica_lost_error_carries_trace_id():
    """Force the router's OWN mid-stream loss path: a backend that
    streams one token and then drops the connection (no terminal line,
    the SIGKILL wire shape). The client's typed replica_lost error must
    carry the request's trace_id."""
    from distkeras_tpu.serving.cluster.replicas import READY, ReplicaHandle
    from distkeras_tpu.serving.cluster.router import Router
    from distkeras_tpu.serving.cluster.supervisor import ReplicaSupervisor

    class _FakeHandle(ReplicaHandle):
        alive = True

        async def start(self):
            raise NotImplementedError

        async def kill(self):
            pass

        async def terminate(self):
            pass

    async def backend(reader, writer):
        await reader.readline()
        writer.write(b'{"token": 7}\n')
        await writer.drain()
        writer.close()  # dies mid-stream

    async def go():
        srv = await asyncio.start_server(backend, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        sup = ReplicaSupervisor(lambda i: _FakeHandle(), 1,
                                base_delay_s=60.0)  # park any restart
        info = sup.replicas["r0"]
        info.host, info.port, info.status = "127.0.0.1", port, READY
        router = Router(sup, port=0, trace_capacity=16)
        await router.start()
        try:
            c = ServingClient("127.0.0.1", router.port)
            with pytest.raises(ServerError) as ei:
                await c.generate([1, 2, 3], 4, trace_id="feed1234")
            await c.aclose()
            merged = (await router._tracez({"cmd": "tracez",
                                           "trace_id": "feed1234"}))
        finally:
            await router.stop()
            await sup.stop()
            srv.close()
        return ei.value, merged["tracez"]

    err, trace = asyncio.run(go())
    assert err.code == "replica_lost"
    assert err.trace_id == "feed1234"
    assert trace["router"]["data"]["status"] == "replica_lost"
    assert [e[2] for e in trace["events"]][0] == "request"


def test_router_debugz_aggregates_fleet(lm, rng, tmp_path):
    async def go():
        recorders = {}
        cluster = ServingCluster(
            _traced_factory(lm, recorders, tmp_path, slo_s=30.0), 2,
            supervisor_kwargs=SUP, registry=MetricsRegistry())
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port) as c:
                await c.generate(_prompt(rng, 5), 4)
                dz = await c.debugz()
        return dz

    dz = asyncio.run(go())
    assert dz["router"]["replicas_ready"] == 2
    assert dz["router"]["trace_store"]["records"] == 1
    assert set(dz["replicas"]) == {"r0", "r1"}
    for rid, entry in dz["replicas"].items():
        sub = entry["debugz"]
        assert len(sub["slots"]) == 2
        assert sub["queue"]["depth"] == 0
        assert sub["flight_recorder"]["source"] == rid
        assert sub["slo_s"] == 30.0
    # The pretty printer renders both shapes without blowing up.
    from distkeras_tpu.serving.debugz import format_debugz

    page = format_debugz(dz)
    assert "router: 2/2 ready" in page and "replica r0" in page


def test_debugz_cli_json(lm, rng):
    """`run.py debugz` against a live server: the subcommand fetches and
    prints both the JSON payload and the pretty page. The server runs on
    a daemon thread's event loop because debugz_main owns its own
    asyncio.run."""
    import contextlib
    import io
    import threading

    from distkeras_tpu.run import debugz_main

    model, variables = lm
    engine = ServingEngine(model, variables, slots=1, max_queue=4,
                           trace_store=TraceStore(16))
    started = threading.Event()
    holder: dict = {}

    def serve_forever():
        async def go():
            server = ServingServer(engine, port=0)
            await server.start()
            holder["port"] = server.port
            holder["stop"] = asyncio.Event()
            started.set()
            await holder["stop"].wait()
            await server.stop(drain=True)

        holder["loop"] = asyncio.new_event_loop()
        holder["loop"].run_until_complete(go())

    t = threading.Thread(target=serve_forever, daemon=True)
    t.start()
    assert started.wait(30)
    try:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = debugz_main(["--host", "127.0.0.1",
                              "--port", str(holder["port"]), "--json"])
        assert rc == 0
        payload = json.loads(buf.getvalue())
        assert [s["state"] for s in payload["slots"]] == ["free"]
        buf2 = io.StringIO()
        with contextlib.redirect_stdout(buf2):
            assert debugz_main(["--host", "127.0.0.1",
                                "--port", str(holder["port"])]) == 0
        assert "active_slots=0" in buf2.getvalue()
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        t.join(timeout=30)


def test_tracing_shim_deprecation_warning():
    from distkeras_tpu import tracing

    from distkeras_tpu.telemetry import spans

    with pytest.warns(DeprecationWarning, match="distkeras_tpu.telemetry"):
        assert tracing.span is spans.span
    with pytest.warns(DeprecationWarning):
        assert tracing.Tracer is spans.Tracer
