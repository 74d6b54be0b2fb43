"""Typed request kinds: forked sampling, scoring/embedding, constrained
decoding — one subsystem threaded client -> wire -> router -> scheduler
-> engine.

The invariants under test, all on CPU with a tiny causal LM (the
router/QoS/wire layers run jax-free on EchoServer):

- **fork parity**: ``kind="sample"`` with ``n`` forks is token-identical
  to ``n`` sequential greedy generates — ONE prefill, ``n`` decode rows
  sharing the prompt's KV blocks copy-on-write;
- **CoW accounting is exact**: fork refcounts drain to zero and the pool
  returns to full capacity after a flush — no leaked or double-freed
  block, with ``kv_fork_blocks_total`` counting the shared rows;
- **scoring** returns per-token logprobs matching a hand-rolled dense
  forward pass + log_softmax; **embedding** returns the mean-pooled
  final hidden state — both prefill-only (no decode slot occupied);
- **constrained decoding** obeys the token automaton on EVERY emitted
  token, greedy and under speculative verify (forbidden drafts are
  rejected before they can commit), with the mask uploaded under the
  dirty-flag pattern so the ARMED ``RecompileAuditor`` proves the
  decode step still compiled exactly once across a mixed batch of all
  kinds;
- **admission-typed validation**: contradictory combos (score with
  max_new_tokens>0, n>1 outside sample, constraint on an unconstrained
  engine) reject as ``bad_request`` at submit, never mid-stream;
- **QoS**: scorelike traffic is its own ``tenant#score`` class — a
  flooding scoring tenant sheds TYPED while the same tenant's
  interactive decode is untouched;
- the whole contract survives real TCP on BOTH protocols (JSONL and
  bin1 extras), and EchoServer emulates it so router tests stay
  jax-free.
"""

import asyncio
import json

import numpy as np
import pytest

from distkeras_tpu.serving import wire
from distkeras_tpu.serving.scheduler import Request, Scheduler, TenantOverQuota

VOCAB = 64


# -- wire: kind extras ride the bin1 whitelist (jax-free) --------------------

def test_wire_roundtrip_kind_extras():
    spec = {"prompt": [1, 2, 3], "max_new_tokens": 0,
            "temperature": 0.0, "priority": 0, "timeout": None,
            "speculate": False, "kind": "score"}
    assert wire.decode_request(wire.encode_request(spec)) == spec
    spec2 = {"prompt": [5, 6], "max_new_tokens": 4, "temperature": 0.5,
             "priority": 0, "timeout": None, "speculate": False,
             "kind": "sample", "n": 3}
    assert wire.decode_request(wire.encode_request(spec2)) == spec2
    con = {"start": 0, "edges": [[0, 1, 0]]}
    spec3 = {"prompt": [5], "max_new_tokens": 2, "temperature": 0.0,
             "priority": 0, "timeout": None, "speculate": True,
             "constraint": con}
    assert wire.decode_request(wire.encode_request(spec3)) == spec3


def test_request_flags_distinguishes_extras_payloads():
    """The router's fast path peeks the flags byte to bounce
    extras-bearing REQs (kinds, kv hints) onto the kind-aware classic
    dispatch — a plain generate must NOT carry the extras flag."""
    plain = wire.encode_request(
        {"prompt": [1, 2], "max_new_tokens": 4, "temperature": 0.0,
         "priority": 0, "timeout": None, "speculate": True})
    kinded = wire.encode_request(
        {"prompt": [1, 2], "max_new_tokens": 0, "temperature": 0.0,
         "priority": 0, "timeout": None, "speculate": False,
         "kind": "embed"})
    assert not wire.request_flags(plain) & wire._F_EXTRAS
    assert wire.request_flags(kinded) & wire._F_EXTRAS
    assert wire.request_flags(b"") == 0  # malformed: typed later, not here


# -- scheduler: scorelike QoS class (jax-free) -------------------------------

def test_scorelike_requests_form_their_own_qos_class():
    r = Request(list(range(8)), 0, kind="score", tenant="acme")
    assert r.qos_tenant == "acme#score"
    g = Request([1, 2], 4, tenant="acme")
    assert g.qos_tenant == "acme"
    # Scorelike quota charge is prompt-shaped, generate charge is
    # decode-shaped.
    assert r.consumed_tokens() == 8
    assert g.consumed_tokens() == 0  # nothing generated yet


def test_flooding_scoring_tenant_sheds_typed_decode_unaffected():
    """A scoring flood from tenant ``bulk`` hits the ``bulk#score``
    quota and rejects TYPED at submit; the SAME tenant's interactive
    generates (different QoS class) sail through untouched."""
    async def go():
        s = Scheduler(max_depth=64, tenant_quotas={"bulk#score": 16.0},
                      quota_burst_s=1.0)  # capacity: 16 prompt tokens
        first = Request(list(range(12)), 0, kind="score", tenant="bulk")
        s.submit(first)
        assert first.qos_tenant == "bulk#score"
        with pytest.raises(TenantOverQuota):
            s.submit(Request(list(range(12)), 0, kind="score",
                             tenant="bulk"))
        # Interactive decode from the same tenant: unmetered class.
        for _ in range(4):
            s.submit(Request([1, 2, 3], 8, tenant="bulk"))
        stats = s.tenant_stats()
        assert stats["bulk#score"]["over_quota_rejects"] == 1
        assert "over_quota_rejects" not in stats.get("bulk", {}) or \
            stats["bulk"]["over_quota_rejects"] == 0

    asyncio.run(go())


# -- router: scoring steers at prefill-shaped replicas (jax-free) ------------

def test_router_pick_routes_scoring_to_prefill_shaped():
    import types

    from distkeras_tpu.serving.cluster.replicas import READY, ReplicaInfo
    from distkeras_tpu.serving.cluster.router import Router

    def info(rid, role, outstanding=0):
        r = ReplicaInfo(rid=rid, index=0, handle=None, status=READY,
                        role=role)
        r.outstanding = outstanding
        return r

    sup = types.SimpleNamespace(
        replicas={
            "p0": info("p0", "prefill", 1),
            "d0": info("d0", "decode", 0),
            "m0": info("m0", "monolithic", 3),
        },
        on_replica_death=[])
    router = Router(sup, trace_capacity=0)
    # Generation: prefill replicas never take dispatches.
    pick = router._pick([1, 2, 3], set())
    assert pick.role != "prefill"
    # Scoring: prefill-shaped work prefers prefill/monolithic rows
    # (least-outstanding among them), keeping decode slots for streams.
    pick = router._pick([1, 2, 3], set(), kind="score")
    assert pick.rid == "p0"
    pick = router._pick([1, 2, 3], set(), kind="embed")
    assert pick.rid == "p0"
    # ... but falls back to ANY ready replica rather than failing.
    sup.replicas = {"d0": info("d0", "decode", 0)}
    assert router._pick([1], set(), kind="score").rid == "d0"


# -- EchoServer emulates the kinds (jax-free; satellite 1) -------------------

async def _echo_jsonl(server, spec):
    reader, writer = await asyncio.open_connection("127.0.0.1",
                                                   server.port)
    writer.write((json.dumps(spec) + "\n").encode())
    await writer.drain()
    recs = []
    while True:
        rec = json.loads(await reader.readline())
        recs.append(rec)
        if "token" not in rec:  # done / error / control reply
            break
    writer.close()
    return recs


def test_echo_server_emulates_kinds_jsonl_and_bin1():
    from distkeras_tpu.serving.client import ServingClient
    from distkeras_tpu.serving.cluster.replicas import EchoServer

    async def go():
        server = EchoServer(echo_tokens=3)
        await server.start()
        # JSONL shapes.
        recs = await _echo_jsonl(server, {
            "prompt": [7, 8], "max_new_tokens": 5, "kind": "sample",
            "n": 2})
        done = recs[-1]
        assert done["kind"] == "sample"
        assert done["completions"] == [[7, 7, 7], [7, 7, 7]]
        assert done["tokens"] == []
        recs = await _echo_jsonl(server, {
            "prompt": [7, 8, 9], "max_new_tokens": 0, "kind": "score"})
        assert recs[-1]["logprobs"] == [0.0, 0.0]
        recs = await _echo_jsonl(server, {
            "prompt": [7], "max_new_tokens": 0, "kind": "embed"})
        assert len(recs[-1]["embedding"]) == 4
        # Contradictory combos reject typed (satellite 2's contract,
        # mirrored so router tests exercise it jax-free).
        recs = await _echo_jsonl(server, {
            "prompt": [7], "max_new_tokens": 3, "kind": "score"})
        assert recs[-1]["code"] == "bad_request"
        recs = await _echo_jsonl(server, {
            "prompt": [7], "max_new_tokens": 3, "n": 4})
        assert recs[-1]["code"] == "bad_request"
        # bin1: the same shapes ride the extras whitelist.
        async with ServingClient("127.0.0.1", server.port,
                                 wire_mode="bin1") as c:
            done = await c.sample([7, 8], 5, 2)
            assert done["completions"] == [[7, 7, 7], [7, 7, 7]]
            done = await c.score([7, 8, 9])
            assert done["logprobs"] == [0.0, 0.0]
            done = await c.embed([7])
            assert len(done["embedding"]) == 4

            # A contradictory combo rejects typed over bin1 too.
            async def bad():
                async for _ in c.stream([7], 3, kind="score"):
                    pass
            with pytest.raises(Exception):
                await bad()
        assert server.kind_requests["sample"] == 2
        assert server.kind_requests["score"] == 2
        assert server.kind_requests["embed"] == 2
        mz = (await _echo_jsonl(server,
                                {"cmd": "metricsz"}))[0]["metricsz"]
        assert mz['serving_requests_total{kind="sample"}']["value"] == 2
        await server.stop()

    asyncio.run(go())


# -- engine: the three kinds end to end --------------------------------------

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from distkeras_tpu.inference.generate import (  # noqa: E402
    _decode_module,
    _empty_cache,
    generate,
)
from distkeras_tpu.models.bert import gpt_tiny  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny(seq_len=32, vocab_size=VOCAB)
    return model, model.init(0)


@pytest.fixture
def rng():
    return np.random.default_rng(13)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _want(lm, prompt, n):
    model, variables = lm
    return generate(model, variables, np.asarray([prompt], np.int32), n,
                    greedy=True)[0].tolist()


async def _run_engine(engine, coro):
    task = asyncio.create_task(engine.run())
    try:
        return await coro
    finally:
        engine.shutdown(drain=True)
        await task


def test_submit_validation_rejects_contradictions_typed(lm):
    """Satellite 2: every contradictory combo is a typed reject AT
    admission — the stream never starts."""
    model, variables = lm
    eng = ServingEngine(model, variables, slots=2, kv_pool_blocks=32,
                        kv_block_tokens=4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], 4, kind="score")  # score decodes nothing
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0, kind="embed", n=2)  # n outside sample
    with pytest.raises(ValueError):
        eng.submit([1, 2], 4, n=3)  # n requires kind="sample"
    with pytest.raises(ValueError):
        eng.submit([1, 2], 4, kind="sample", n=1)  # fork of one
    with pytest.raises(ValueError):
        eng.submit([1, 2], 4, kind="sample", n=99)  # n > slots
    with pytest.raises(ValueError):
        eng.submit([1, 2], 4, kind="nope")
    with pytest.raises(ValueError):
        # Mask hook not compiled in: reject up front, not mid-stream.
        eng.submit([1, 2], 4,
                   constraint={"start": 0, "edges": [[0, 1, 0]]})
    import jax

    from distkeras_tpu.parallel.mesh import serving_mesh

    staged = ServingEngine(
        model, variables, slots=2,
        mesh=serving_mesh({"tp": 1, "pp": 2}, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="single-stage"):
        staged.submit([1, 2], 0, kind="score")  # kinds need pp=1


def test_fork_parity_and_exact_cow_accounting(lm, rng):
    """Tentpole (a): one prefill, n CoW forks — token-identical to n
    sequential generates, and the pool's fork refcounts drain exactly
    (flush returns EVERY block; no leak, no double-free)."""
    model, variables = lm
    eng = ServingEngine(model, variables, slots=4, max_queue=16,
                        kv_pool_blocks=64, kv_block_tokens=4)
    p = _prompt(rng, 9)  # 2 complete blocks + a partial tail
    want = _want(lm, p, 6)

    async def work():
        req = eng.submit(p, 6, kind="sample", n=3, speculate=False)
        await req.result()
        return req

    req = asyncio.run(_run_engine(eng, work()))
    assert req.fork_completions == [want, want, want]
    pool = eng.kv_pool
    assert pool.forked_blocks_total > 0
    assert eng.metrics.fork_blocks > 0
    assert pool._fork_refs == {}  # every shared ref consumed
    used_before_flush = pool.blocks_used
    pool.flush()
    assert pool.blocks_free == pool.capacity, (
        f"leaked {pool.capacity - pool.blocks_free} blocks "
        f"(used pre-flush: {used_before_flush})")


def test_score_logprobs_match_dense_forward(lm, rng):
    """Tentpole (b): engine scoring == hand-rolled forward pass +
    log_softmax, chunked prefill and paging notwithstanding."""
    model, variables = lm
    module, _ = _decode_module(model)
    eng = ServingEngine(model, variables, slots=2, max_queue=8,
                        kv_pool_blocks=64, kv_block_tokens=4)
    p = _prompt(rng, 11)

    logits, _ = module.apply(
        {"params": variables["params"], "cache": _empty_cache(module, 1)},
        jnp.asarray([p], jnp.int32), train=False, mutable=["cache"])
    logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
    want = [float(logp[i, p[i + 1]]) for i in range(len(p) - 1)]

    async def work():
        req = eng.submit(p, 0, kind="score")
        toks = await req.result()
        return toks, req

    toks, req = asyncio.run(_run_engine(eng, work()))
    assert toks == []  # nothing decoded
    # bf16 trunk: paged vs dense attention reorder roundings at the
    # 2^-9 ULP scale; a positional bug would be off by whole units.
    np.testing.assert_allclose(req.logprobs, want, atol=2e-2)
    assert req.ttft > 0  # prefill completion stamped first-token time


def test_embed_matches_mean_pooled_hidden(lm, rng):
    model, variables = lm
    module, _ = _decode_module(model)
    eng = ServingEngine(model, variables, slots=2, max_queue=8,
                        kv_pool_blocks=64, kv_block_tokens=4)
    p = _prompt(rng, 7)

    hidden, _ = module.apply(
        {"params": variables["params"], "cache": _empty_cache(module, 1)},
        jnp.asarray([p], jnp.int32), train=False, mutable=["cache"],
        return_hidden=True)
    want = np.asarray(hidden[0], np.float64).mean(axis=0)

    async def work():
        req = eng.submit(p, 0, kind="embed")
        await req.result()
        return req

    req = asyncio.run(_run_engine(eng, work()))
    # Same bf16-ULP tolerance story as the scoring parity test.
    np.testing.assert_allclose(req.embedding, want, rtol=5e-2, atol=5e-2)


def _alternating_dfa():
    """Tokens must alternate 1, 2, 1, 2, ... forever (no terminal)."""
    return {"start": 0, "edges": [[0, 1, 1], [1, 2, 0]]}


def test_masked_greedy_obeys_automaton_every_token(lm, rng):
    """Tentpole (c): the per-slot mask forces every emitted token onto a
    DFA edge; a terminal state ends the stream early."""
    model, variables = lm
    eng = ServingEngine(model, variables, slots=2, max_queue=8,
                        kv_pool_blocks=64, kv_block_tokens=4,
                        constrained=True)
    p = _prompt(rng, 6)
    # 3, then 4, then STOP (state 2 has no outgoing edges).
    terminal = {"start": 0, "edges": [[0, 3, 1], [1, 4, 2]]}

    async def work():
        alt = eng.submit(p, 6, constraint=_alternating_dfa())
        fin = eng.submit(p, 6, constraint=terminal)
        plain = eng.submit(p, 6)  # unconstrained neighbor, same batch
        return (await alt.result(), await fin.result(),
                await plain.result())

    alt, fin, plain = asyncio.run(_run_engine(eng, work()))
    assert alt == [1, 2, 1, 2, 1, 2]
    assert fin == [3, 4]  # terminal state stopped the stream early
    assert plain == _want(lm, p, 6)  # the mask never leaks across slots


def test_speculative_verify_under_masks_parity(lm, rng):
    """Forbidden draft tokens are rejected BEFORE they can commit: a
    constrained stream on a speculative engine emits the same tokens as
    on a plain constrained engine, while unconstrained neighbors still
    speculate."""
    model, variables = lm
    spec = ServingEngine(model, variables, slots=2, max_queue=8,
                         kv_pool_blocks=64, kv_block_tokens=4,
                         draft_model=model, draft_variables=variables,
                         spec_k=4, constrained=True)
    p = _prompt(rng, 6)

    async def work(engine):
        con = engine.submit(p, 6, constraint=_alternating_dfa())
        plain = engine.submit(p, 6)
        return await con.result(), await plain.result()

    con, plain = asyncio.run(_run_engine(spec, work(spec)))
    assert con == [1, 2, 1, 2, 1, 2]
    assert plain == _want(lm, p, 6)
    assert spec.metrics.spec_draft_tokens > 0


def test_mixed_batch_armed_auditor_compile_once(lm, rng):
    """THE compile invariant survives the kinds: one decode executable
    serves generate + sample forks + constrained rows in one mixed
    batch, while score/embed ride the prefill path — under an ARMED
    auditor, ``serving_decode`` compiled exactly once."""
    from distkeras_tpu.telemetry import RecompileAuditor

    model, variables = lm
    auditor = RecompileAuditor()
    eng = ServingEngine(model, variables, slots=4, max_queue=16,
                        kv_pool_blocks=64, kv_block_tokens=4,
                        constrained=True, auditor=auditor,
                        arm_auditor_after_warmup=True)
    prompts = [_prompt(rng, n) for n in (5, 7, 6, 4, 8)]

    async def work():
        gen = eng.submit(prompts[0], 5)
        await asyncio.sleep(0.02)  # decode starts; auditor arms
        fork = eng.submit(prompts[1], 4, kind="sample", n=2,
                          speculate=False)
        con = eng.submit(prompts[2], 4,
                         constraint=_alternating_dfa())
        score = eng.submit(prompts[3], 0, kind="score")
        embed = eng.submit(prompts[4], 0, kind="embed")
        return [await r.result()
                for r in (gen, fork, con, score, embed)]

    out = asyncio.run(_run_engine(eng, work()))
    assert out[0] == _want(lm, prompts[0], 5)
    assert out[2] == [1, 2, 1, 2]
    assert auditor.compiles("serving_decode") == 1
    kinds = eng.metrics.kind_counters()
    assert kinds["generate"] >= 2  # plain + constrained
    assert kinds["sample"] == 1 and kinds["score"] == 1
    assert kinds["embed"] == 1
    dz = eng.debugz()
    assert dz["request_kinds"] == kinds


def test_tcp_end_to_end_kinds_jsonl_and_bin1(lm, rng):
    """The whole subsystem over real TCP, BOTH protocols: client
    helpers -> wire extras -> server -> engine -> typed done records
    carrying kind/completions/logprobs/embedding."""
    from distkeras_tpu.serving import ServingServer
    from distkeras_tpu.serving.client import ServingClient

    model, variables = lm
    p = _prompt(rng, 6)
    want = _want(lm, p, 4)

    async def go():
        eng = ServingEngine(model, variables, slots=4, max_queue=16,
                            kv_pool_blocks=64, kv_block_tokens=4,
                            constrained=True)
        server = ServingServer(eng, port=0)
        await server.start()
        outs = {}
        for mode in ("jsonl", "bin1"):
            async with ServingClient("127.0.0.1", server.port,
                                     wire_mode=mode) as c:
                sample = await c.sample(p, 4, 2, speculate=False)
                score = await c.score(p)
                embed = await c.embed(p)
                con = await c.generate(
                    p, 4, constraint=_alternating_dfa())
                outs[mode] = (sample, score, embed, con)
                # Contradiction: typed bad_request, not a dead stream.
                from distkeras_tpu.serving.client import _CODE_TO_ERROR
                with pytest.raises(
                        _CODE_TO_ERROR.get("bad_request", Exception)):
                    await c.generate(p, 3, kind="score")
        await server.stop(drain=True)
        return outs

    outs = asyncio.run(go())
    for mode in ("jsonl", "bin1"):
        sample, score, embed, con = outs[mode]
        assert sample["kind"] == "sample"
        assert sample["completions"] == [want, want]
        assert sample["tokens"] == []
        assert score["kind"] == "score"
        assert len(score["logprobs"]) == len(p) - 1
        assert embed["kind"] == "embed"
        assert len(embed["embedding"]) > 0
        assert con["tokens"] == [1, 2, 1, 2]
    # Protocol parity: bin1 and jsonl carried identical payloads.
    assert outs["jsonl"][0]["completions"] == outs["bin1"][0]["completions"]
    np.testing.assert_allclose(outs["jsonl"][1]["logprobs"],
                               outs["bin1"][1]["logprobs"], atol=1e-6)
