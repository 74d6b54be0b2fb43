"""GSPMD tensor-parallel serving: sharded engine vs unsharded parity.

The sharded-serving contract under test, all on the suite's forced
virtual CPU devices (the CI variant re-runs this file at a different
forced count — tests read ``len(jax.devices())``, never assume 8):

- a ``serving_mesh`` engine is **token-identical** to the unsharded
  ``generate()`` reference on greedy decode — derived pool, tight pool
  (preempt/resume included), chunked + prefix-cached, and speculative
  modes — at tp=2 and tp=4;
- **compile-once survives the mesh**: every callable (decode, draft,
  verify) stays at exactly one executable under an armed
  ``RecompileAuditor``, explicit in/out shardings and all;
- params and KV leaves are REALLY sharded (NamedSharding carrying
  ``tp``), block tables and slot state stay replicated host metadata;
- a hot param swap places candidates shard-then-place into the SAME
  layout (no retrace, provenance flips, new-weight parity);
- a sharded 2-replica cluster rolls a reload through the router with
  zero client errors and per-replica ``(version, digest)`` flips;
- bad meshes and non-divisible models fail typed at construction;
- per-device memory attribution: a sharded engine's params/KV bytes are
  published per mesh device.
"""

import asyncio
import time

import jax
import numpy as np
import pytest

from distkeras_tpu.inference.generate import generate
from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.parallel.mesh import parse_mesh_shape, serving_mesh
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.telemetry import RecompileAuditor

VOCAB = 64

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="sharded serving needs >= 2 (virtual) devices")


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=64, vocab_size=VOCAB)
    return model, model.init(0)


@pytest.fixture(scope="module")
def mesh2():
    return serving_mesh({"tp": 2}, devices=jax.devices()[:2])


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _want(lm_pair, prompt, n, variables=None):
    model, default_vars = lm_pair
    return generate(model, variables or default_vars,
                    np.asarray([prompt], np.int32), n,
                    greedy=True)[0].tolist()


async def _run_engine(engine, coro):
    task = asyncio.create_task(engine.run())
    try:
        return await coro
    finally:
        engine.shutdown(drain=True)
        await task


def _tp_specs(tree):
    """The set of PartitionSpec strings across a pytree's leaves."""
    return {str(getattr(leaf.sharding, "spec", leaf.sharding))
            for leaf in jax.tree.leaves(tree)}


# -- validation ---------------------------------------------------------------

def test_mesh_shape_parsing_and_validation():
    assert parse_mesh_shape("tp=2") == {"tp": 2}
    assert parse_mesh_shape("4") == {"tp": 4}
    assert parse_mesh_shape("tp=2,dp=1") == {"tp": 2, "dp": 1}
    for bad in ("", "tp", "tp=x", "tp=0", "tp=2,tp=4"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)
    n = len(jax.devices())
    # A product that does not divide the visible device count is a typed
    # error, not a deep jax traceback.
    with pytest.raises(ValueError, match="divide"):
        serving_mesh({"tp": n + 1})
    if n % 3:
        with pytest.raises(ValueError, match="divide"):
            serving_mesh({"tp": 3})
    with pytest.raises(ValueError, match="tp"):
        serving_mesh({"dp": 1})
    if n >= 4:
        # dp>1 inside one serving replica is rejected AT THE MESH (the
        # CLI layer), not only by the engine ctor — `cluster` must fail
        # one typed line, never spawn N crash-looping children.
        with pytest.raises(ValueError, match="replicas"):
            serving_mesh({"tp": 2, "dp": 2})
    # Default: one big tp replica over everything visible.
    assert dict(serving_mesh().shape) == {"tp": n}


def test_engine_rejects_unshardable_configs(lm, mesh2):
    model, variables = lm
    # vocab 65 does not divide tp=2 -> typed, names the offender.
    odd = gpt_tiny(seq_len=64, vocab_size=65)
    with pytest.raises(ValueError, match="vocab_size"):
        ServingEngine(odd, odd.init(0), slots=2, mesh=mesh2)
    # A serving mesh must carry tp; dp>1 inside ONE engine is rejected
    # (data parallelism in serving is N replicas).
    from distkeras_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match="tp"):
        ServingEngine(model, variables, slots=2,
                      mesh=make_mesh({"dp": 2},
                                     devices=jax.devices()[:2]))
    if len(jax.devices()) >= 4:
        dp_mesh = make_mesh({"dp": 2, "tp": 2},
                            devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="replicas"):
            ServingEngine(model, variables, slots=2, mesh=dp_mesh)


# -- parity: derived pool / tight pool / chunked+cached / speculative ----------

def test_sharded_greedy_parity_compile_once(lm, mesh2, rng):
    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=4, max_queue=16,
                           mesh=mesh2, auditor=auditor,
                           arm_auditor_after_warmup=True)
    # Params and the KV pool the engine sized for itself really
    # sharded; sampling state replicated.
    assert any("'tp'" in s for s in _tp_specs(engine._params))
    assert any("'tp'" in s for s in _tp_specs(engine._cache))
    assert _tp_specs(engine._tokens) == {"PartitionSpec()"}
    prompts = [_prompt(rng, n) for n in (3, 5, 8, 13, 6, 4, 9, 7)]

    async def work():
        reqs = [engine.submit(p, 8) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 8) for p in prompts]
    assert auditor.compiles("serving_decode") == 1
    assert engine.mesh_info()["tp"] == 2
    assert len(engine.mesh_info()["devices"]) == 2


def test_sharded_prefix_cache_chunked_parity(lm, mesh2, rng):
    """Sharded engine with prefix hits AND chunked prefill: a hit points
    the slot's table at shared head-sharded pool rows, tails chunk
    through the sharded prefill — output still token-identical."""
    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=mesh2, kv_block_tokens=8, prefill_chunk=8,
                           auditor=auditor, arm_auditor_after_warmup=True)
    assert any("'tp'" in s for s in _tp_specs(engine._cache))
    shared = _prompt(rng, 16)
    prompts = [shared + _prompt(rng, 4) for _ in range(4)]

    async def work():
        # Sequential: the 2nd+ requests hit the 1st's adopted blocks.
        outs = []
        for p in prompts:
            outs.append(await engine.submit(p, 6).result())
        return outs

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 6) for p in prompts]
    assert engine.kv_pool.hit_tokens > 0, "no prefix hit exercised"
    assert auditor.compiles("serving_decode") == 1


def test_sharded_paged_preempt_resume_parity(lm, mesh2, rng):
    """Paged sharded engine with a pool tight enough to force
    preemption: preempt -> adopt -> requeue -> resume stays
    token-identical on a HEADS-SHARDED pool, tables stay host
    metadata, and the armed auditor holds compile-once throughout."""
    model, variables = lm
    auditor = RecompileAuditor()
    tight = ServingEngine(model, variables, slots=4, max_queue=16,
                          mesh=mesh2, kv_pool_blocks=13,
                          kv_block_tokens=4, auditor=auditor,
                          arm_auditor_after_warmup=True)
    assert any("'tp'" in s for s in _tp_specs(tight._cache))
    assert isinstance(tight._tables, np.ndarray)  # replicated host state
    prompts = [_prompt(rng, 12) for _ in range(4)]

    async def work():
        reqs = [tight.submit(p, 10) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(tight, work()))
    assert outs == [_want(lm, p, 10) for p in prompts]
    assert tight.metrics.preemptions > 0, (
        "pool was supposed to be tight enough to force preemption")
    assert auditor.compiles("serving_decode") == 1


def test_sharded_speculative_parity_compile_once(lm, mesh2, rng):
    """Speculative sharded engine (draft==target, replicated draft on a
    sharded target over one paged pool): greedy rows commit draft
    prefixes, a sampled row and an opt-out greedy row ride the same
    batch, everything token-identical, and decode/draft/verify each
    stay at ONE executable."""
    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=mesh2, kv_pool_mb=1.0,
                           draft_model=model, draft_variables=variables,
                           spec_k=4, auditor=auditor,
                           arm_auditor_after_warmup=True)
    # The draft is replicated: no tp axis anywhere in its state.
    assert not any("'tp'" in s for s in _tp_specs(engine._draft_params))
    prompts = [_prompt(rng, n) for n in (3, 6, 9, 5)]

    async def work():
        greedy = [engine.submit(p, 8) for p in prompts]
        optout = engine.submit(prompts[0], 8, speculate=False)
        sampled = engine.submit(prompts[1], 8, temperature=0.8)
        outs = [await r.result() for r in greedy]
        return outs, await optout.result(), await sampled.result()

    outs, optout, sampled = asyncio.run(_run_engine(engine, work()))
    want = [_want(lm, p, 8) for p in prompts]
    assert outs == want
    assert optout == want[0]
    assert len(sampled) == 8
    assert engine.metrics.spec_accepted_tokens > 0
    compiles = {n: auditor.compiles(n)
                for n in ("serving_decode", "serving_draft",
                          "serving_verify")}
    assert compiles == {"serving_decode": 1, "serving_draft": 1,
                        "serving_verify": 1}, compiles


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="tp=4 needs >= 4 devices")
def test_tp4_paged_parity(lm, rng):
    model, variables = lm
    mesh4 = serving_mesh({"tp": 4}, devices=jax.devices()[:4])
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=mesh4, kv_pool_mb=1.0, auditor=auditor,
                           arm_auditor_after_warmup=True)
    prompts = [_prompt(rng, n) for n in (4, 7, 11)]

    async def work():
        reqs = [engine.submit(p, 8) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 8) for p in prompts]
    assert auditor.compiles("serving_decode") == 1
    assert engine.mesh_info()["axes"]["tp"] == 4


# -- hot swap: shard-then-place -----------------------------------------------

def test_sharded_param_swap_no_retrace(lm, mesh2, rng):
    """request_param_swap on a sharded engine: the candidate is placed
    straight into its mesh layout (post-swap params still carry tp),
    provenance flips, the armed auditor proves the swap-rewarm did not
    retrace, and post-swap output matches generate() under the NEW
    weights."""
    model, variables = lm
    new_vars = model.init(7)
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=mesh2, auditor=auditor,
                           arm_auditor_after_warmup=True)
    p = _prompt(rng, 6)

    async def work():
        before = await engine.submit(p, 6).result()
        ev, res = engine.request_param_swap(
            new_vars, provenance={"version": 9, "digest": "d9"})
        await asyncio.wait_for(ev.wait(), 60)
        assert res.get("ok"), res
        after = await engine.submit(p, 6).result()
        return before, after

    before, after = asyncio.run(_run_engine(engine, work()))
    assert before == _want(lm, p, 6)
    assert after == _want(lm, p, 6, variables=new_vars)
    assert engine.weight_version == {"version": 9, "digest": "d9"}
    assert any("'tp'" in s for s in _tp_specs(engine._params)), (
        "swap dropped the params' tp layout")
    assert auditor.compiles("serving_decode") == 1


# -- sharded fleet: rolling reload --------------------------------------------

def test_sharded_rolling_reload_zero_errors(lm, mesh2, rng, tmp_path):
    """Two SHARDED LocalReplicas behind the router: a rolling reload
    under continuous client load flips every replica's (version,
    digest) with zero client-visible errors; fleet healthz rolls up a
    single version and a consistent mesh per replica."""
    from distkeras_tpu.checkpoint import save_weights_file, \
        weights_provenance
    from distkeras_tpu.serving import (
        LocalReplica, ServingClient, ServingCluster,
    )
    from distkeras_tpu.telemetry import MetricsRegistry

    model, variables = lm
    new_vars = model.init(3)
    weights_path = str(tmp_path / "w2.bin")
    save_weights_file(weights_path, new_vars)
    pool = [_prompt(rng, n) for n in (4, 6, 5)]

    engines = {}

    def factory(i):
        def build():
            eng = ServingEngine(model, variables, slots=2, max_queue=16,
                                mesh=mesh2,
                                auditor=RecompileAuditor(),
                                arm_auditor_after_warmup=True)
            engines[i] = eng
            return eng

        return LocalReplica(build)

    async def go():
        cluster = ServingCluster(
            factory, 2, registry=MetricsRegistry(),
            supervisor_kwargs=dict(health_interval_s=0.05,
                                   base_delay_s=0.05))
        completions = []
        stop = asyncio.Event()

        async def worker(k):
            async with ServingClient("127.0.0.1", cluster.port) as c:
                while not stop.is_set():
                    prompt = pool[(k + len(completions)) % len(pool)]
                    done = await c.generate(prompt, 5)
                    completions.append(
                        (time.monotonic(), tuple(prompt), done["tokens"],
                         done.get("weight_version")))

        async with cluster:
            workers = [asyncio.create_task(worker(k)) for k in range(3)]
            deadline = time.monotonic() + 60
            while len(completions) < 4:
                assert time.monotonic() < deadline
                await asyncio.sleep(0.02)
            async with ServingClient("127.0.0.1", cluster.port) as c:
                rep = await c.reload(weights_path, timeout=120.0)
            t1 = time.monotonic()
            n_after = len(completions) + 4
            while len(completions) < n_after:
                assert time.monotonic() < deadline + 60
                await asyncio.sleep(0.02)
            stop.set()
            await asyncio.gather(*workers)
            async with ServingClient("127.0.0.1", cluster.port) as c:
                health = await c.healthz()
        return rep, completions, t1, health

    rep, completions, t1, health = asyncio.run(go())
    assert rep["ok"] and sorted(rep["reloaded"]) == ["r0", "r1"]
    assert rep["failed"] == {}
    prov = weights_provenance(weights_path)
    key = f"{prov['version']}:{prov['digest']}"
    # Per-replica flip, rolled up at the router; meshes consistent.
    assert health["router"]["weight_versions"] == {key: 2}
    assert health["router"]["mixed_weight_versions"] is False
    for rid, entry in health["replicas"].items():
        sub = entry.get("healthz") or {}
        assert sub.get("mesh", {}).get("axes", {}).get("tp") == 2, (
            rid, sub.get("mesh"))
    # Zero client errors (a worker exception would have propagated) and
    # post-roll parity on the new weights.
    want_new = {tuple(p): _want(lm, p, 5, variables=new_vars)
                for p in pool}
    post = [c for c in completions if c[0] > t1]
    assert post, "no completion landed after the roll"
    for _, p, got, wv in post:
        assert got == want_new[p]
        assert wv["version"] == prov["version"]
        assert wv["digest"] == prov["digest"]
    for i, eng in engines.items():
        assert eng.auditor.compiles("serving_decode") == 1, f"replica {i}"


# -- observability ------------------------------------------------------------

def test_sharded_memory_attribution(lm, mesh2):
    """refresh_memory_metrics on a sharded engine: params/KV bytes are
    attributed per mesh device — healthz rows carry per-device
    params_bytes/kv_bytes, and the registry grows device-labeled
    model_params_bytes / kv_pool_reserved_bytes series."""
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, mesh=mesh2,
                           kv_pool_mb=1.0)
    rows = engine.refresh_memory_metrics()
    mesh_devs = set(engine.mesh_info()["devices"])
    by_dev = {r["device"]: r for r in rows if r["device"] in mesh_devs}
    assert set(by_dev) == mesh_devs
    for r in by_dev.values():
        assert r.get("params_bytes", 0) > 0
        assert r.get("kv_bytes", 0) > 0
    # The sharded halves of the pool really are halves: KV per device
    # is strictly less than the whole pool's bytes.
    total_kv = engine.kv_pool.capacity * engine.kv_pool.bytes_per_block
    for r in by_dev.values():
        assert r["kv_bytes"] < total_kv
    snap = engine.metrics.registry.snapshot()
    labeled = [k for k in snap
               if k.startswith("model_params_bytes{") and "device=" in k]
    assert len(labeled) >= 2, sorted(snap)[:40]


def test_healthz_mesh_info_unsharded_absent(lm):
    model, variables = lm
    engine = ServingEngine(model, variables, slots=2)
    assert engine.mesh_info() is None
    assert "mesh" not in engine.debugz()


# -- e2e: a real `run.py serve --mesh` child process --------------------------

@pytest.mark.slow
def test_serve_mesh_e2e_child_process(rng):
    """`run.py serve --mesh-shape tp=2 --force-host-devices 2` as a real
    child: the banner names the mesh, a TCP stream is token-identical
    to the parent's (unsharded) generate(), and healthz carries the
    mesh plus per-device params/KV attribution."""
    import json
    import os
    import signal
    import subprocess
    import sys

    from distkeras_tpu.serving import ServingClient

    child = subprocess.Popen(
        [sys.executable, "-m", "distkeras_tpu.run", "serve",
         "--model", "gpt_tiny", "--port", "0",
         "--mesh-shape", "tp=2", "--force-host-devices", "2",
         "--kv-pool-mb", "4", "--audit-recompiles", "arm"],
        stdout=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        line = child.stdout.readline()
        assert line, "serve child exited before its banner"
        banner = json.loads(line)
        assert banner["mesh"]["axes"]["tp"] == 2
        assert len(banner["mesh"]["devices"]) == 2
        port = banner["port"]
        model = gpt_tiny()
        variables = model.init(0)
        prompt = _prompt(rng, 7)
        want = generate(model, variables, np.asarray([prompt], np.int32),
                        8, greedy=True)[0].tolist()

        async def go():
            async with ServingClient("127.0.0.1", port) as c:
                done = await c.generate(prompt, 8)
                health = await c.healthz()
            return done, health

        done, health = asyncio.run(go())
        assert done["tokens"] == want, "sharded child diverged"
        assert health["mesh"]["axes"]["tp"] == 2
        per_dev = [r for r in health["device_memory"]
                   if r.get("params_bytes")]
        assert len(per_dev) == 2, health["device_memory"]
    finally:
        child.send_signal(signal.SIGTERM)
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
