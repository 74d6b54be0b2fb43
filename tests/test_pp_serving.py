"""Pipeline-parallel serving: pp-meshed engine vs unsharded parity.

The pp-serving contract under test, all on the suite's forced virtual
CPU devices (the CI ``tier1-pp-4dev`` variant re-runs this file at a
different forced count — tests read ``len(jax.devices())``, never
assume 8):

- a ``tp=1,pp=2`` engine is **token-identical** to the unsharded
  ``generate()`` reference on greedy decode — from the pool the engine
  derives and from a tight one (preempt/resume included), chunked +
  prefix-cached, and speculative modes;
- **compile-once survives the stage split**: every per-stage callable
  stays at exactly one executable under an armed ``RecompileAuditor``;
- per-stage placement is REAL: stage s's params and KV leaves live only
  on stage s's devices, boot and after a hot swap alike;
- ``pipeline_depth>=pp`` micro-batching streams the SAME tokens as
  depth 0 and records a ``bubble_fraction``;
- a pp+tp combined mesh (device-gated) keeps all of the above;
- bad stage plans and bad depths fail typed at construction;
- mesh_info/debugz/healthz carry the pp axis: per-stage device lists,
  per-stage params/KV bytes, a ``stages:`` line on the pretty page.
"""

import asyncio
import json

import jax
import numpy as np
import pytest

from distkeras_tpu.inference.generate import generate
from distkeras_tpu.models.bert import gpt_tiny
from distkeras_tpu.parallel.mesh import serving_mesh
from distkeras_tpu.parallel.pp import plan_stages
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.telemetry import RecompileAuditor

VOCAB = 64

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="pipeline-parallel serving needs >= 2 (virtual) devices")


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=64, vocab_size=VOCAB)
    return model, model.init(0)


@pytest.fixture(scope="module")
def pp2():
    return serving_mesh({"tp": 1, "pp": 2}, devices=jax.devices()[:2])


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


def _stage_device_sets(engine):
    return [set(m.devices.flatten()) for m in engine._stage_meshes]


def _assert_stage_placement(engine, trees):
    """Every leaf of per-stage subtree s must reside ONLY on stage s's
    devices — the whole point of pp placement."""
    stage_devs = _stage_device_sets(engine)
    for s, tree in enumerate(trees):
        for leaf in jax.tree.leaves(tree):
            assert set(leaf.devices()) <= stage_devs[s], (
                f"stage {s} leaf leaked onto foreign devices: "
                f"{leaf.devices()} vs {stage_devs[s]}")


def _stage_compiles(auditor, pp, name="serving_decode"):
    return [auditor.compiles(f"{name}_s{s}") for s in range(pp)]


# -- validation ---------------------------------------------------------------

def test_stage_plan_validation():
    plan = plan_stages(4, 2)
    assert plan.layers_per_stage == 2
    assert plan.layer_range(0) == (0, 2) and plan.layer_range(1) == (2, 4)
    assert plan.stage_arg(0) == (0, 2, True, False)
    assert plan.stage_arg(1) == (2, 4, False, True)
    # token_embed is placed on BOTH ends (tied head reads it back).
    assert plan.owner_stages("token_embed") == (0, 1)
    assert plan.owner_stages("pos_embed") == (0,)
    assert plan.owner_stages("ln_final") == (1,)
    with pytest.raises(ValueError, match="pp=0"):
        plan_stages(4, 0)
    with pytest.raises(ValueError, match="at least one layer"):
        plan_stages(2, 4)
    with pytest.raises(ValueError, match="divide"):
        plan_stages(3, 2)


def test_engine_rejects_bad_depth_and_unsplittable_model(lm, pp2):
    model, variables = lm
    # Depth > 1 without a pp mesh is a typed error, not a hang.
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServingEngine(model, variables, slots=4, pipeline_depth=2)
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServingEngine(model, variables, slots=4, mesh=pp2,
                      pipeline_depth=-1)
    # Slots must divide into equal micro-batches.
    with pytest.raises(ValueError, match="micro-batch"):
        ServingEngine(model, variables, slots=3, mesh=pp2,
                      pipeline_depth=2)
    # gpt_tiny has 2 layers: a 2-device pp=2 mesh splits 1+1; a model
    # whose layer count does not divide pp fails typed at construction.
    from distkeras_tpu.models.bert import BertConfig, _make

    cfg3 = BertConfig(vocab_size=VOCAB, hidden_size=32, num_layers=3,
                      num_heads=2, mlp_dim=64, max_seq_len=64,
                      causal=True)
    odd = _make(cfg3, 64, "gpt_3layer")
    with pytest.raises(ValueError, match="divide"):
        ServingEngine(odd, odd.init(0), slots=2, mesh=pp2)
    # Speculative decoding runs verify over the whole slot batch —
    # micro-batched depth is rejected, not silently ignored.
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServingEngine(model, variables, slots=4, mesh=pp2,
                      kv_pool_mb=1.0, draft_model=model,
                      draft_variables=variables, spec_k=3,
                      pipeline_depth=2)


# -- parity: derived pool / tight pool / chunked+cached / speculative ----------

def test_pp_greedy_parity_compile_once_per_stage(lm, pp2, rng):
    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=4, max_queue=16,
                           mesh=pp2, auditor=auditor,
                           arm_auditor_after_warmup=True)
    # Per-stage placement of params AND the KV pool the engine sized
    # for itself (a whole context for every slot).
    _assert_stage_placement(engine, engine._params)
    _assert_stage_placement(engine, engine._cache)
    prompts = [_prompt(rng, n) for n in (3, 5, 8, 13, 6, 4, 9, 7)]

    async def work():
        reqs = [engine.submit(p, 8) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 8) for p in prompts]
    assert _stage_compiles(auditor, 2) == [1, 1]


def test_pp_paged_preempt_resume_parity(lm, pp2, rng):
    """Paged pp engine with a pool tight enough to force preemption:
    preempt -> adopt -> requeue -> resume stays token-identical on
    stage-partitioned pools, and every stage holds compile-once."""
    model, variables = lm
    auditor = RecompileAuditor()
    tight = ServingEngine(model, variables, slots=4, max_queue=16,
                          mesh=pp2, kv_pool_blocks=13,
                          kv_block_tokens=4, auditor=auditor,
                          arm_auditor_after_warmup=True)
    _assert_stage_placement(tight, tight._params)
    _assert_stage_placement(tight, tight._cache)
    assert isinstance(tight._tables, np.ndarray)  # replicated host state
    prompts = [_prompt(rng, 12) for _ in range(4)]

    async def work():
        reqs = [tight.submit(p, 10) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(tight, work()))
    assert outs == [_want(lm, p, 10) for p in prompts]
    assert tight.metrics.preemptions > 0, (
        "pool was supposed to be tight enough to force preemption")
    assert _stage_compiles(auditor, 2) == [1, 1]


def test_pp_prefix_cache_chunked_parity(lm, pp2, rng):
    """pp engine with prefix hits AND chunked prefill: a hit points the
    slot's table at shared rows of every stage's pool, tails chunk
    through the staged prefill — output still token-identical, one trie
    spanning all stages."""
    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=pp2, kv_block_tokens=8, prefill_chunk=8,
                           auditor=auditor,
                           arm_auditor_after_warmup=True)
    # The pool is per-stage, each stage-local.
    _assert_stage_placement(engine, engine._cache)
    shared = _prompt(rng, 16)
    prompts = [shared + _prompt(rng, 4) for _ in range(4)]

    async def work():
        outs = []
        for p in prompts:
            outs.append(await engine.submit(p, 6).result())
        return outs

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 6) for p in prompts]
    assert engine.kv_pool.hit_tokens > 0, "no prefix hit exercised"
    assert _stage_compiles(auditor, 2) == [1, 1]


def test_pp_speculative_parity_compile_once(lm, pp2, rng):
    """Speculative pp engine (replicated draft, staged verify over one
    stage-partitioned paged pool): greedy rows commit draft prefixes,
    opt-out and sampled rows ride the same batch, everything
    token-identical, every staged verify at ONE executable."""
    model, variables = lm
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=pp2, kv_pool_mb=1.0,
                           draft_model=model, draft_variables=variables,
                           spec_k=4, auditor=auditor,
                           arm_auditor_after_warmup=True)
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
    assert _stage_compiles(auditor, 2, "serving_verify") == [1, 1]
    assert auditor.compiles("serving_draft") == 1


# -- depth > 1: micro-batched overlap -----------------------------------------

def test_pp_depth_identical_tokens_and_bubble_metric(lm, pp2, rng):
    """``pipeline_depth>=pp`` micro-batching is pure overlap: the SAME
    greedy tokens as the serialized depth-0 engine, per-stage
    compile-once, and a recorded ``bubble_fraction``."""
    model, variables = lm
    prompts = [_prompt(rng, n) for n in (3, 5, 8, 13, 6, 4, 9, 7)]
    by_depth = {}
    for depth in (0, 2):
        auditor = RecompileAuditor()
        engine = ServingEngine(model, variables, slots=4, max_queue=16,
                               mesh=pp2, pipeline_depth=depth,
                               auditor=auditor,
                               arm_auditor_after_warmup=True)

        async def work(engine=engine):
            reqs = [engine.submit(p, 8) for p in prompts]
            return [await r.result() for r in reqs]

        by_depth[depth] = asyncio.run(_run_engine(engine, work()))
        assert _stage_compiles(auditor, 2) == [1, 1], depth
        if depth >= 2:
            assert engine._mb_count == depth
            frac = engine.metrics.bubble.fraction
            assert frac is not None and 0.0 <= frac <= 1.0
            assert "bubble_fraction" in engine.metrics.summary()
    assert by_depth[0] == by_depth[2] == [
        _want(lm, p, 8) for p in prompts]


def test_pp_paged_depth_preempt_mid_microbatch_parity(lm, pp2, rng):
    """Depth-2 micro-batched PAGED decode under a pool tight enough to
    preempt mid-flight: a slot preempted in one micro-batch resumes
    (possibly in another tick) token-identical, stages stay at one
    executable."""
    model, variables = lm
    auditor = RecompileAuditor()
    tight = ServingEngine(model, variables, slots=4, max_queue=16,
                          mesh=pp2, pipeline_depth=2,
                          kv_pool_blocks=13, kv_block_tokens=4,
                          auditor=auditor, arm_auditor_after_warmup=True)
    prompts = [_prompt(rng, 12) for _ in range(6)]

    async def work():
        reqs = [tight.submit(p, 10) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(tight, work()))
    assert outs == [_want(lm, p, 10) for p in prompts]
    assert tight.metrics.preemptions > 0, (
        "pool was supposed to be tight enough to force preemption")
    assert _stage_compiles(auditor, 2) == [1, 1]


# -- hot swap: shard-then-place per stage -------------------------------------

def test_pp_param_swap_no_retrace(lm, pp2, rng):
    """request_param_swap on a pp engine: the candidate is split and
    placed straight into each stage's layout (post-swap leaves still
    stage-local), the armed auditor proves no stage retraced, and
    post-swap output matches generate() under the NEW weights."""
    model, variables = lm
    new_vars = model.init(7)
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=pp2, auditor=auditor,
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
    _assert_stage_placement(engine, engine._params)
    assert _stage_compiles(auditor, 2) == [1, 1]


# -- pp + tp combined ---------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="tp=2,pp=2 needs >= 4 devices")
def test_pp_tp_combined_parity(lm, rng):
    """tp=2,pp=2 on 4 devices: params tp-sharded WITHIN each stage,
    stages device-disjoint, greedy output still token-identical with
    per-stage compile-once — the full second-axis claim."""
    model, variables = lm
    mesh = serving_mesh({"tp": 2, "pp": 2}, devices=jax.devices()[:4])
    auditor = RecompileAuditor()
    engine = ServingEngine(model, variables, slots=4, max_queue=16,
                           mesh=mesh, kv_pool_mb=1.0,
                           pipeline_depth=2, auditor=auditor,
                           arm_auditor_after_warmup=True)
    _assert_stage_placement(engine, engine._params)
    stage_devs = _stage_device_sets(engine)
    assert not (stage_devs[0] & stage_devs[1]), "stages share devices"
    assert all(len(d) == 2 for d in stage_devs)
    # tp really shards within a stage: some param leaf spans BOTH of
    # its stage's devices.
    assert any(len(leaf.devices()) == 2
               for leaf in jax.tree.leaves(engine._params[0]))
    prompts = [_prompt(rng, n) for n in (4, 7, 11, 5)]

    async def work():
        reqs = [engine.submit(p, 8) for p in prompts]
        return [await r.result() for r in reqs]

    outs = asyncio.run(_run_engine(engine, work()))
    assert outs == [_want(lm, p, 8) for p in prompts]
    assert _stage_compiles(auditor, 2) == [1, 1]
    info = engine.mesh_info()
    assert info["axes"] == {"tp": 2, "pp": 2}


# -- observability: mesh_info / debugz / healthz ------------------------------

def test_pp_mesh_info_debugz_healthz_stages(lm, pp2, rng):
    from distkeras_tpu.serving import ServingClient, ServingServer
    from distkeras_tpu.serving.debugz import format_debugz

    model, variables = lm
    engine = ServingEngine(model, variables, slots=2, max_queue=16,
                           mesh=pp2, pipeline_depth=2)

    async def go():
        server = ServingServer(engine, port=0)
        await server.start()
        async with ServingClient("127.0.0.1", server.port) as c:
            await c.generate(_prompt(rng, 6), 4)
            health = await c.healthz()
        await server.stop(drain=True)
        return health

    health = asyncio.run(go())
    # healthz: the pipeline block carries the pp axis + measured bubble.
    assert health["pipeline"]["stages"] == 2
    assert health["pipeline"]["micro_batches"] == 2
    assert "bubble_fraction" in health["pipeline"]
    # mesh_info (also embedded in healthz["mesh"]): per-stage devices,
    # layer ranges, and resident params/KV bytes.
    for info in (engine.mesh_info(), health["mesh"]):
        assert info["pp"] == 2
        stages = info["stages"]
        assert [st["stage"] for st in stages] == [0, 1]
        assert stages[0]["layers"] == [0, 1]
        assert stages[1]["layers"] == [1, 2]
        for st in stages:
            assert len(st["devices"]) == 1
            assert st["params_bytes"] > 0
            assert st["kv_bytes"] > 0
        assert set(stages[0]["devices"]).isdisjoint(stages[1]["devices"])
    # debugz: JSON-safe dict + a stages: line on the pretty page,
    # without breaking the existing pipeline: line format.
    dz = engine.debugz()
    json.dumps(dz)
    assert dz["pipeline"]["stages"] == 2
    assert dz["pipeline"]["micro_batches"] == 2
    page = format_debugz(dz)
    assert "pipeline: depth=2" in page
    assert "stages: 2 pp stage(s) x 2 micro-batch(es)" in page
