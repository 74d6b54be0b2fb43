"""Multi-tenant QoS: weighted deficit round robin + token-rate quotas
(distkeras_tpu.serving.scheduler) and their engine/wire integration.

The contract under test:

- within one priority class, tenants share token bandwidth by DRR
  (weighted; single-tenant degenerates to exact FIFO — covered by the
  original scheduler tests in test_serving.py);
- priority classes still dominate tenants (no tenant fairness across
  classes);
- quotas reject TYPED at submit (``TenantOverQuota``), never kill an
  admitted stream, and unused charge is credited back at completion;
- preempt/park requeue still lands at the FRONT of its class across
  tenants (the paged-KV contract);
- end to end: an engine with bin1 framing, batched admission, tenant
  scheduling and quotas — armed RecompileAuditor stays silent and
  greedy output is token-identical to the JSONL path and generate().
"""

import asyncio
from collections import Counter

import numpy as np
import pytest

from distkeras_tpu.serving import (
    Request,
    Scheduler,
    TenantOverQuota,
)

pytestmark = []


# -- scheduler-level --------------------------------------------------------
def test_drr_fair_service_within_class():
    async def go():
        s = Scheduler(max_depth=64, drr_quantum=4)
        for _ in range(6):
            s.submit(Request([1], 4, tenant="a"), now=0.0)
        for _ in range(6):
            s.submit(Request([2], 4, tenant="b"), now=0.0)
        order = [s.pop(now=1.0).tenant for _ in range(12)]
        # Equal weights + equal cost: neither tenant ever leads by more
        # than one service turn, even though "a" enqueued all of its
        # backlog first — the flooding-tenant starvation shape.
        for i in range(2, 13, 2):
            c = Counter(order[:i])
            assert abs(c["a"] - c["b"]) <= 1, order

    asyncio.run(go())


def test_drr_weights_bias_token_bandwidth():
    async def go():
        s = Scheduler(max_depth=64, drr_quantum=4,
                      tenant_weights={"a": 2.0})
        for _ in range(12):
            s.submit(Request([1], 4, tenant="a"), now=0.0)
        for _ in range(12):
            s.submit(Request([2], 4, tenant="b"), now=0.0)
        first = Counter(s.pop(now=1.0).tenant for _ in range(12))
        # Weight 2 vs 1 under full backlog: ~2/3 of service.
        assert first["a"] >= 7, first

    asyncio.run(go())


def test_priority_classes_dominate_tenant_fairness():
    async def go():
        s = Scheduler(max_depth=16)
        a = Request([1], 4, tenant="a", priority=1)
        b = Request([2], 4, tenant="b", priority=0)
        c = Request([3], 4, tenant="c", priority=1)
        for r in (a, b, c):
            s.submit(r, now=0.0)
        # The better class is served FIRST regardless of tenant DRR.
        assert s.pop(now=0.0) is b
        assert s.pop(now=0.0) is a and s.pop(now=0.0) is c

    asyncio.run(go())


def test_quota_typed_reject_refund_and_isolation():
    async def go():
        s = Scheduler(max_depth=64, tenant_quotas={"a": 10.0},
                      quota_burst_s=1.0)  # capacity 10 tokens
        r1 = Request([1], 8, tenant="a")
        s.submit(r1, now=100.0)
        with pytest.raises(TenantOverQuota):
            s.submit(Request([1], 8, tenant="a"), now=100.0)
        # Unmetered tenants are untouched by a's quota.
        s.submit(Request([1], 8, tenant="b"), now=100.0)
        # r1 finished after 2 tokens: 6 of its 8 charged come back.
        r1.out_tokens = [1, 2]
        s.release_quota(r1)
        s.submit(Request([1], 6, tenant="a"), now=100.0)
        # ...and the refund is idempotent (terminal paths may race).
        s.release_quota(r1)
        stats = s.tenant_stats()
        assert stats["a"]["over_quota_rejects"] == 1
        assert stats["a"]["quota"]["rate_tokens_per_s"] == 10.0

    asyncio.run(go())


def test_quota_refills_over_time():
    async def go():
        s = Scheduler(max_depth=8, tenant_quotas={"a": 10.0},
                      quota_burst_s=1.0)
        s.submit(Request([1], 10, tenant="a"), now=0.0)
        with pytest.raises(TenantOverQuota):
            s.submit(Request([1], 10, tenant="a"), now=0.1)
        # One second later the bucket refilled its full capacity.
        s.submit(Request([1], 10, tenant="a"), now=1.2)

    asyncio.run(go())


def test_requeue_front_crosses_tenants():
    async def go():
        s = Scheduler(max_depth=8)
        x = Request([1], 4, tenant="a")
        y = Request([2], 4, tenant="b")
        s.submit(x, now=0.0)
        s.submit(y, now=0.0)
        assert s.pop(now=0.0) is x
        # Preemption returns x to the FRONT of the whole class — peek
        # and pop must both see it before b's queued request (the paged
        # engine's admission-park gate reads peek()).
        s.requeue(x)
        assert s.peek() is x
        assert s.pop(now=0.0) is x and s.pop(now=0.0) is y

    asyncio.run(go())


def test_submit_many_is_per_request_typed():
    async def go():
        s = Scheduler(max_depth=2, tenant_quotas={"q": 1.0},
                      quota_burst_s=1.0)
        reqs = [Request([1], 1, tenant="q"),   # takes the whole budget
                Request([1], 9, tenant="q"),   # over quota
                Request([1], 1),               # fits
                Request([1], 1)]               # queue full (depth 2)
        out = s.submit_many(reqs, now=0.0)
        assert out[0] is None and out[2] is None
        assert isinstance(out[1], TenantOverQuota)
        assert type(out[3]).__name__ == "QueueFullError"
        assert len(s) == 2

    asyncio.run(go())


def test_serving_config_flags_forward_wire_and_quotas():
    """The deploy canary must validate the production wire config: the
    shared replica-flag builder forwards --wire and the tenant knobs."""
    import argparse

    from distkeras_tpu.run import _parse_tenant_rates, _serving_config_flags

    args = argparse.Namespace(
        top_k=None,
        prefill_chunk=None, paged=False, kv_pool_mb=0.0,
        kv_block_tokens=16, max_context=None, draft_model=None,
        wire="bin1", tenant_quota=["acme=100", "free=10"],
        tenant_weight=["acme=2"])
    flags = _serving_config_flags(args)
    assert flags[flags.index("--wire") + 1] == "bin1"
    assert flags.count("--tenant-quota") == 2
    assert "acme=100" in flags and "free=10" in flags
    assert flags[flags.index("--tenant-weight") + 1] == "acme=2"
    assert _parse_tenant_rates(["a=1.5", "b=2"], "--x") == {
        "a": 1.5, "b": 2.0}
    with pytest.raises(SystemExit):
        _parse_tenant_rates(["nope"], "--x")
    with pytest.raises(SystemExit):
        _parse_tenant_rates(["a=fast"], "--x")


# -- engine + wire, end to end ----------------------------------------------
VOCAB = 64


@pytest.fixture(scope="module")
def lm():
    from distkeras_tpu.models.bert import gpt_tiny

    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


def test_bin1_tenants_quotas_auditor_token_identical(lm):
    """THE acceptance invariant: with bin1 framing, batched admission,
    tenant DRR and quotas all enabled, the armed RecompileAuditor stays
    silent and greedy output is token-identical to the JSONL path and
    to one-shot generate()."""
    from distkeras_tpu.inference.generate import generate
    from distkeras_tpu.serving import (
        ServingClient, ServingEngine, ServingServer,
    )
    from distkeras_tpu.telemetry import RecompileAuditor

    model, variables = lm
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=(n,)).tolist()
               for n in (5, 7, 3, 6)]
    auditor = RecompileAuditor()
    engine = ServingEngine(
        model, variables, slots=2, max_queue=16,
        auditor=auditor, arm_auditor_after_warmup=True,
        tenant_quotas={"hot": 16.0}, quota_burst_s=1.0,
        tenant_weights={"vip": 2.0})

    async def go():
        server = ServingServer(engine, port=0)
        await server.start()
        port = server.port
        # JSONL first (sequential), then bin1 (pipelined + batched):
        # same prompts, same tenants, must stream identical tokens.
        outs_jsonl = []
        async with ServingClient("127.0.0.1", port) as c:
            for p, t in zip(prompts, ("a", "b", "vip", "hot")):
                done = await c.generate(p, 4, tenant=t)
                assert done["tenant"] == t
                outs_jsonl.append(done["tokens"])
        async with ServingClient("127.0.0.1", port,
                                 wire_mode="bin1") as c:
            assert c.proto == "bin1"
            dones = await asyncio.gather(*(
                c.generate(p, 4, tenant=t)
                for p, t in zip(prompts, ("a", "b", "vip", "hot"))))
            outs_bin = [d["tokens"] for d in dones]
            batch = await c.generate_batch(prompts, 4, tenant="batch")
            outs_batch = [d["tokens"] for d in batch]
            # Quota enforcement over the wire: "hot" holds 16 tokens of
            # burst; a request that can NEVER fit is typed-rejected at
            # submit while the stream-level API stays usable
            # (25 tokens fits the context cap, never the 16-token
            # burst).
            with pytest.raises(TenantOverQuota):
                await c.generate(prompts[1][:3], 25, tenant="hot")
            health = await c.healthz()
        await server.stop()
        return outs_jsonl, outs_bin, outs_batch, health

    outs_jsonl, outs_bin, outs_batch, health = asyncio.run(go())
    assert outs_jsonl == outs_bin == outs_batch
    for p, got in zip(prompts, outs_jsonl):
        want = generate(model, variables, np.asarray([p], np.int32), 4,
                        greedy=True)[0].tolist()
        assert got == want
    # The auditor was armed after warmup and never raised: compile-once
    # held through bin1 + batched admission + tenant scheduling.
    assert engine.decode_compile_count() in (1, -1)
    tenants = health["tenants"]
    assert tenants["hot"]["over_quota_rejects"] == 1
    assert tenants["vip"]["completed"] >= 2  # jsonl + bin1 rounds
    assert "quota" in tenants["hot"]


def test_engine_flood_is_shed_typed_and_isolated(lm):
    """A flooding tenant is shed at submit with TYPED rejects while an
    honest tenant's simultaneously-submitted work completes untouched —
    the scheduler-level adversarial contract, engine-integrated."""
    from distkeras_tpu.serving import ServingEngine

    model, variables = lm
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, size=(4,)).tolist()
               for _ in range(12)]
    engine = ServingEngine(
        model, variables, slots=2, max_queue=32,
        tenant_quotas={"flood": 8.0}, quota_burst_s=1.0)

    async def go():
        task = asyncio.create_task(engine.run())
        honest, sheds = [], 0
        for i, p in enumerate(prompts):
            honest.append(engine.submit(p, 2, tenant="honest"))
            try:
                engine.submit(p, 4, tenant="flood")
            except TenantOverQuota:
                sheds += 1
        outs = [await r.result() for r in honest]
        engine.shutdown(drain=True)
        await task
        return outs, sheds

    outs, sheds = asyncio.run(go())
    assert len(outs) == len(prompts) and all(len(o) == 2 for o in outs)
    # 8 tok/s, 1 s burst: two 4-token requests fit, the rest shed typed.
    assert sheds == len(prompts) - 2, sheds
    snap = engine.tenant_snapshot()
    assert snap["flood"]["over_quota_rejects"] == sheds
    assert snap["honest"]["completed"] == len(prompts)
