"""Multi-replica serving cluster (distkeras_tpu.serving.cluster).

In-process replicas (LocalReplica: one engine + server per replica on
ephemeral ports, all on one event loop) so the cluster invariants run on
CPU in seconds. Under test:

- the router speaks the single-server wire protocol: streams route
  through it with greedy parity against generate(), and healthz/metricsz
  aggregate the fleet;
- prefix-cache affinity pins a prompt family to one replica;
- THE chaos invariant: SIGKILL-equivalent replica death under concurrent
  load loses no zero-streamed request (retried on the survivor), the
  supervisor restarts the corpse with backoff, and it rejoins routing;
- zero-downtime rolling reload: under continuous load, a reload verb
  swaps weights one replica at a time with no client-visible error,
  completions keep flowing DURING the roll, outputs are token-identical
  to generate() under the matching weights, and each replica's armed
  RecompileAuditor proves the decode step never retraced (compile==1);
- bad weights are rejected loudly and the fleet keeps serving the old
  params.
"""

import asyncio
import time

import numpy as np
import pytest

from distkeras_tpu.checkpoint import save_weights_file
from distkeras_tpu.inference.generate import generate
from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.serving import (
    LocalReplica,
    ServingClient,
    ServingCluster,
    ServingEngine,
)
from distkeras_tpu.serving.client import ServerError
from distkeras_tpu.telemetry import MetricsRegistry, RecompileAuditor

VOCAB = 64

# Fast-failure supervisor settings for tests: probe often, restart fast.
SUP = dict(health_interval_s=0.05, health_timeout_s=2.0, fail_after=2,
           base_delay_s=0.05, max_delay_s=1.0, stable_after_s=0.5)


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _want(lm_pair, prompt, n, variables=None):
    model, default_vars = lm_pair
    return generate(model, variables or default_vars,
                    np.asarray([prompt], np.int32), n,
                    greedy=True)[0].tolist()


def _factory(lm_pair, engines=None, audit=False, **engine_kwargs):
    """Replica factory over a shared (model, variables); ``engines`` (a
    dict) collects live engines by replica index for invariant checks.
    ``audit=True`` gives each engine its OWN armed RecompileAuditor
    (sharing one across replicas would double-count compiles)."""
    model, variables = lm_pair

    def make(i):
        def build():
            kw = dict(engine_kwargs)
            if audit:
                kw.update(auditor=RecompileAuditor(),
                          arm_auditor_after_warmup=True)
            eng = ServingEngine(model, variables, slots=2, max_queue=16,
                                **kw)
            if engines is not None:
                engines[i] = eng
            return eng

        return LocalReplica(build)

    return make


async def _wait_until(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        await asyncio.sleep(0.02)


# -- routing + aggregation ----------------------------------------------------

def test_router_parity_and_fleet_aggregation(lm, rng):
    prompts = [_prompt(rng, n) for n in (5, 9, 3, 7)]

    async def go():
        cluster = ServingCluster(_factory(lm), 2, supervisor_kwargs=SUP,
                                 registry=MetricsRegistry())
        async with cluster:
            async def one(p):
                async with ServingClient("127.0.0.1", cluster.port) as c:
                    return (await c.generate(p, 6))["tokens"]

            outs = await asyncio.gather(*(one(p) for p in prompts))
            async with ServingClient("127.0.0.1", cluster.port) as c:
                health = await c.healthz()
                metrics = await c.metricsz()
            return outs, health, metrics, cluster

    outs, health, metrics, cluster = asyncio.run(go())
    for p, got in zip(prompts, outs):
        assert got == _want(lm, p, 6)
    assert health["router"]["replicas_ready"] == 2
    assert health["router"]["outstanding_total"] == 0
    assert set(health["replicas"]) == {"r0", "r1"}
    for entry in health["replicas"].values():
        assert entry["healthz"]["slots"] == 2  # per-replica healthz rode up
    # Per-replica metric snapshots aggregate under replica ids, and the
    # whole fleet together completed every request exactly once.
    done = sum(
        snap["serving_requests_completed_total"]["value"]
        for snap in metrics["replicas"].values())
    assert done == len(prompts)
    assert metrics["router"]["router_requests_total"]["value"] == len(prompts)


def test_fleet_telemetry_push_and_sloz_end_to_end(lm, rng):
    """The pushed-metrics plane, end to end: replicas stream metric
    deltas to the router on a cadence (mux ``telemetry_start``
    subscriptions, one per replica), the router folds them into
    fleet-merged histograms whose count covers every served request,
    the Prometheus page carries both the per-replica and the
    ``fleet="all"`` series, and ``sloz``/``healthz`` serve the burn-rate
    engine's state over the same store."""
    from distkeras_tpu.serving.slo import default_objectives

    prompts = [_prompt(rng, n) for n in (5, 7, 4, 6)]

    async def go():
        cluster = ServingCluster(
            _factory(lm), 2, supervisor_kwargs=SUP,
            # CPU tiny-model fleets legitimately breach production-shaped
            # latency targets during warmup; relaxed thresholds keep the
            # ttft/itl objectives asserting "ok" below.
            router_kwargs={
                "telemetry_interval_s": 0.05,
                "telemetry_window_s": 0.25,
                "slo_objectives": default_objectives(
                    ttft_threshold_s=30.0, itl_threshold_s=30.0),
            })
        async with cluster:
            async def one(p):
                async with ServingClient("127.0.0.1", cluster.port) as c:
                    return (await c.generate(p, 6))["tokens"]

            outs = await asyncio.gather(*(one(p) for p in prompts))
            router = cluster.router
            # Every request produced one TTFT observation; the pushed
            # deltas must converge the fleet merge onto all of them.
            await _wait_until(
                lambda: (router.fleet.fleet_hist_state(
                    "serving_ttft_seconds") or {}).get("count", 0)
                >= len(prompts),
                timeout=20.0, what="fleet-merged TTFT to cover all "
                                   "requests")
            async with ServingClient("127.0.0.1", cluster.port) as c:
                health = await c.healthz()
                sloz = (await c._control({"cmd": "sloz"}))["sloz"]
                prom = await c.metricsz(format="prometheus")
            fleet_snap = router.fleet.registry.snapshot()
            return outs, health, sloz, prom, fleet_snap

    outs, health, sloz, prom, fleet_snap = asyncio.run(go())
    for p, got in zip(prompts, outs):
        assert got == _want(lm, p, 6)  # telemetry never skews serving
    # healthz folds the plane in: overall SLO state + aggregation stats.
    assert health["router"]["slo"] in ("ok", "warn", "page")
    telem = health["router"]["telemetry"]
    assert telem["pushes"] > 0 and telem["push_errors"] == 0
    assert telem["push_subscriptions"] == 2  # both replicas push (mux)
    assert telem["interval_s"] == 0.05
    assert set(telem["replicas"]) == {"r0", "r1"}
    # sloz: the burn-rate snapshot plus the same aggregation rollup.
    assert sloz["aggregation"]["pushes"] >= telem["pushes"]
    assert 0 <= sloz["aggregation"]["staleness_s"] < 5.0
    by_name = {o["objective"]: o for o in sloz["objectives"]}
    assert by_name["ttft_p99"]["state"] == "ok"
    assert by_name["itl_p99"]["state"] == "ok"
    # The fleet Prometheus page renders the merged series both ways.
    assert 'fleet="all"' in prom
    assert "serving_ttft_seconds" in prom
    # Gauges stay per-replica only (summing occupancy ratios is a lie).
    assert 'serving_slot_occupancy{fleet="all"}' not in prom
    for rid in ("r0", "r1"):
        assert any("serving_slot_occupancy" in k and f"replica={rid}" in k
                   for k in fleet_snap)


def test_affinity_pins_prompt_family_to_one_replica(lm, rng):
    family = _prompt(rng, 16)  # >= affinity_tokens: one prompt family

    async def go():
        cluster = ServingCluster(
            _factory(lm), 2, supervisor_kwargs=SUP,
            router_kwargs={"affinity_tokens": 16},
            registry=MetricsRegistry())
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port) as c:
                for _ in range(6):
                    await c.generate(family + _prompt(rng, 2), 4)
                metrics = await c.metricsz()
        return metrics

    metrics = asyncio.run(go())
    completed = sorted(
        snap["serving_requests_completed_total"]["value"]
        for snap in metrics["replicas"].values())
    # Every request in the family landed on the SAME replica (sequential
    # submission: outstanding stayed 0, so the pin never spilled).
    assert completed == [0.0, 6.0]
    assert metrics["router"]["router_affinity_picks_total"]["value"] == 6


# -- chaos: replica death under load ------------------------------------------

def test_replica_death_retries_zero_streamed_and_restarts(lm, rng):
    """THE chaos acceptance test: under concurrent load, hard-kill one
    replica of two. Every request that had streamed zero tokens completes
    via retry on the survivor (token-identical to generate()); only
    mid-stream requests may fail, and with a typed terminal error. The
    supervisor restarts the dead replica and it rejoins routing."""
    prompts = [_prompt(rng, 4 + (i % 5)) for i in range(12)]

    async def go():
        cluster = ServingCluster(_factory(lm), 2, supervisor_kwargs=SUP,
                                 registry=MetricsRegistry())
        results: dict[int, list[int]] = {}
        failures: dict[int, tuple[str, int]] = {}

        async with cluster:
            async def client_task(idx, p):
                streamed = []
                c = ServingClient("127.0.0.1", cluster.port)
                try:
                    done = await c.generate(p, 8, on_token=streamed.append)
                    results[idx] = done["tokens"]
                except (ServerError, ConnectionError) as e:
                    failures[idx] = (str(e), len(streamed))
                finally:
                    await c.aclose()

            tasks = [asyncio.create_task(client_task(i, p))
                     for i, p in enumerate(prompts)]
            # Let the fleet get properly mid-stream, then kill r0 hard.
            await _wait_until(lambda: len(results) >= 2, what="first done")
            await cluster.replicas["r0"].handle.kill()
            await asyncio.gather(*tasks)

            # Supervisor notices (router feedback or health probe),
            # restarts, and the replica rejoins. Wait for the restart
            # ITSELF before waiting on ready_count: when every in-flight
            # request drains before the ~0.1 s probe window closes,
            # ready_count still reads a stale 2 off the not-yet-probed
            # corpse and the restart assertion would race the probe.
            await _wait_until(
                lambda: cluster.replicas["r0"].restarts >= 1,
                what="supervisor restart of r0")
            await _wait_until(
                lambda: cluster.supervisor.ready_count == 2,
                what="replica rejoin")
            assert cluster.replicas["r0"].restarts >= 1

            # The restarted replica serves traffic again: flood enough
            # sequential requests that least-outstanding/affinity sends
            # some its way, and every one completes.
            async with ServingClient("127.0.0.1", cluster.port) as c:
                post = [
                    (p, (await c.generate(p, 4))["tokens"])
                    for p in (_prompt(rng, n) for n in (3, 5, 6, 4, 7, 8))
                ]
        return results, failures, post

    results, failures, post = asyncio.run(go())
    # Zero-streamed requests NEVER fail: every failure streamed >= 1
    # token before its replica died (not idempotent, typed error).
    for idx, (msg, streamed) in failures.items():
        assert streamed >= 1, (
            f"request {idx} failed with zero tokens streamed: {msg}")
    assert len(results) + len(failures) == len(prompts)
    # Survivor-side completions (including retried ones) are exact.
    for idx, got in results.items():
        assert got == _want(lm, prompts[idx], 8)
    for p, got in post:
        assert got == _want(lm, p, 4)


# -- zero-downtime rolling reload ---------------------------------------------

def test_rolling_reload_under_load_zero_downtime(lm, rng, tmp_path):
    """Reload new weights through a loaded 2-replica cluster: no client
    sees an error, completions keep landing DURING the roll (never fewer
    than N-1 replicas serving), post-roll outputs are token-identical to
    generate() under the NEW weights, and each replica's armed auditor
    proves its decode step compiled exactly once across the swap."""
    model, variables = lm
    new_vars = model.init(1)
    weights_path = str(tmp_path / "new_weights.bin")
    save_weights_file(weights_path, new_vars)
    pool = [_prompt(rng, n) for n in (4, 6, 5, 7)]
    want_old = {tuple(p): _want(lm, p, 5) for p in pool}
    want_new = {tuple(p): _want(lm, p, 5, variables=new_vars) for p in pool}

    async def go():
        engines: dict[int, ServingEngine] = {}
        cluster = ServingCluster(
            _factory(lm, engines=engines, audit=True),
            2, supervisor_kwargs=SUP, registry=MetricsRegistry())
        completions: list[tuple[float, tuple, list[int]]] = []
        stop = asyncio.Event()

        async def worker(k):
            async with ServingClient("127.0.0.1", cluster.port) as c:
                while not stop.is_set():
                    p = pool[(k + len(completions)) % len(pool)]
                    done = await c.generate(p, 5)
                    completions.append(
                        (time.monotonic(), tuple(p), done["tokens"],
                         done.get("weight_version")))

        async with cluster:
            workers = [asyncio.create_task(worker(k)) for k in range(3)]
            await _wait_until(lambda: len(completions) >= 4,
                              what="warmup completions")
            t0 = time.monotonic()
            async with ServingClient("127.0.0.1", cluster.port) as c:
                rep = await c.reload(weights_path, timeout=60.0)
            t1 = time.monotonic()
            # A few more completions on the new weights.
            n_after = len(completions) + 4
            await _wait_until(lambda: len(completions) >= n_after,
                              what="post-reload completions")
            stop.set()
            await asyncio.gather(*workers, return_exceptions=False)
            # Drive EACH replica's engine directly post-roll: proves both
            # actually serve the new weights (routing affinity may have
            # starved one of organic traffic) and arms any auditor whose
            # engine had only seen its swap-rewarm tick so far.
            per_replica = {
                i: await eng.submit(pool[0], 5).result()
                for i, eng in engines.items()
            }
            audits = {
                i: (eng.auditor.compiles("serving_decode"),
                    eng.auditor.report()["serving_decode"]["armed"])
                for i, eng in engines.items()
            }
            # A crash AFTER the roll must not resurrect the boot
            # weights: the supervisor brings the fresh replica (whose
            # factory rebuilds with the OLD variables) to the fleet's
            # current weights before readmitting it.
            await cluster.replicas["r0"].handle.kill()
            await _wait_until(lambda: cluster.supervisor.ready_count < 2,
                              what="death detection")
            await _wait_until(lambda: cluster.supervisor.ready_count == 2,
                              what="post-reload restart")
            restarted = await engines[0].submit(pool[0], 5).result()
            # Weight-provenance rollup at the ROUTER: after the roll and
            # the restart (brought to current weights), the fleet must
            # be single-version on the reloaded file's stamp.
            async with ServingClient("127.0.0.1", cluster.port) as c:
                fleet_health = await c.healthz()
        return (rep, completions, t0, t1, audits, per_replica, restarted,
                fleet_health)

    (rep, completions, t0, t1, audits, per_replica,
     restarted, fleet_health) = asyncio.run(go())
    from distkeras_tpu.checkpoint import weights_provenance

    new_prov = weights_provenance(weights_path)
    assert new_prov["version"] == 1 and new_prov["digest"]
    # Per-request provenance: pre-roll requests carry the boot stamp
    # (version 0, inline variables), post-roll requests the reloaded
    # file's version+digest — old vs new visible on every done line.
    for t, p, got, wv in completions:
        assert isinstance(wv, dict), "done line lost weight_version"
        if t < t0:
            assert wv["version"] == 0
        elif t > t1:
            assert wv["version"] == new_prov["version"]
            assert wv["digest"] == new_prov["digest"]
    router_h = fleet_health["router"]
    key = f"{new_prov['version']}:{new_prov['digest']}"
    assert router_h["weight_versions"] == {key: 2}
    assert router_h["mixed_weight_versions"] is False
    assert restarted == want_new[tuple(pool[0])], \
        "restarted replica rejoined on stale boot weights"
    for i, got in per_replica.items():
        assert got == want_new[tuple(pool[0])], f"replica {i} serves stale"
    assert rep["ok"] and sorted(rep["reloaded"]) == ["r0", "r1"]
    assert rep["failed"] == {}
    # No client-visible error: every worker iteration completed (worker
    # exceptions would have propagated from gather).
    # Zero downtime: completions landed INSIDE the reload window.
    during = [c for c in completions if t0 <= c[0] <= t1]
    assert during, "no request completed while the reload was rolling"
    # Token parity: before the roll -> old weights; after it -> new
    # weights; inside the window either (depends which replica served).
    for t, p, got, _wv in completions:
        if t < t0:
            assert got == want_old[p]
        elif t > t1:
            assert got == want_new[p]
        else:
            assert got in (want_old[p], want_new[p])
    # The armed auditor held through the swap on both replicas: exactly
    # one decode executable each, before AND after the param swap.
    for i, (compiles, armed) in audits.items():
        assert compiles == 1 and armed, f"replica {i}: {audits[i]}"


def test_reload_rejects_mismatched_weights_and_keeps_serving(lm, rng,
                                                             tmp_path):
    wrong = gpt_tiny(seq_len=32, vocab_size=32)  # different embed shape
    path = str(tmp_path / "wrong.bin")
    save_weights_file(path, wrong.init(0))
    p = _prompt(rng, 5)

    async def go():
        cluster = ServingCluster(_factory(lm), 2, supervisor_kwargs=SUP,
                                 registry=MetricsRegistry())
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port) as c:
                before = (await c.generate(p, 4))["tokens"]
                rep = await c.reload(path)
                after = (await c.generate(p, 4))["tokens"]
                health = await c.healthz()
        return before, rep, after, health

    before, rep, after, health = asyncio.run(go())
    assert not rep["ok"]
    assert set(rep["failed"]) == {"r0", "r1"}  # both rejected, loudly
    # Old weights kept serving, replicas readmitted.
    assert before == after == _want(lm, p, 4)
    assert health["router"]["replicas_ready"] == 2


# -- engine-level swap unit -----------------------------------------------

def test_engine_param_swap_flushes_prefix_cache_and_is_exact(lm, rng):
    """request_param_swap alone (no cluster): post-swap greedy output is
    token-identical to generate() under the new params, the pool's trie
    is flushed (old-weight K/V must never be shared again), and a
    mismatched tree raises before touching engine state."""
    model, variables = lm
    new_vars = model.init(2)
    engine = ServingEngine(model, variables, slots=1, max_queue=8,
                           kv_block_tokens=4)
    shared = _prompt(rng, 9)
    p1, p2 = shared + _prompt(rng, 2), shared + _prompt(rng, 3)

    async def go():
        task = asyncio.create_task(engine.run())
        try:
            out_old = await engine.submit(p1, 4).result()
            assert engine.kv_pool.blocks_used > 0
            event, result = engine.request_param_swap(new_vars)
            await asyncio.wait_for(event.wait(), 30)
            assert "error" not in result
            assert engine.kv_pool.blocks_used == 0  # flushed
            assert engine.kv_pool.stats()["flushes"] == 1
            out_new1 = await engine.submit(p1, 4).result()
            out_new2 = await engine.submit(p2, 4).result()
            return out_old, out_new1, out_new2
        finally:
            engine.shutdown(drain=True)
            await task

    out_old, out_new1, out_new2 = asyncio.run(go())
    assert out_old == _want(lm, p1, 4)
    assert out_new1 == _want(lm, p1, 4, variables=new_vars)
    # Re-cached under the NEW weights, the second hit is still exact.
    assert out_new2 == _want(lm, p2, 4, variables=new_vars)
    assert engine.kv_pool.stats()["hit_requests"] >= 1
    assert engine.decode_compile_count() in (1, -1)

    with pytest.raises(ValueError, match="leaf|leaves"):
        engine.request_param_swap(
            gpt_tiny(seq_len=32, vocab_size=32).init(0))


# -- process-mode integration (the `run serve --replicas N` shape) ------------

@pytest.mark.slow
def test_process_replica_cluster_end_to_end(lm, rng):
    """Real child processes behind the router — the deployment shape
    `python -m distkeras_tpu.run serve --replicas N` wires up. One
    greedy round trip (parity against the parent's identically-seeded
    weights) plus fleet healthz. Slow lane: each replica pays a full jax
    import + compile."""
    from distkeras_tpu.serving.cluster import ProcessReplica

    p = _prompt(rng, 5)

    async def go():
        extra = ["--model", "gpt_tiny",
                 "--model-args", '{"seq_len": 32, "vocab_size": 64}',
                 "--slots", "2", "--seed", "0"]
        cluster = ServingCluster(lambda i: ProcessReplica(extra), 2,
                                 supervisor_kwargs=dict(
                                     health_interval_s=0.5))
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port) as c:
                done = await c.generate(p, 4)
                health = await c.healthz()
        return done, health

    done, health = asyncio.run(go())
    assert done["tokens"] == _want(lm, p, 4)
    assert health["router"]["replicas_ready"] == 2
