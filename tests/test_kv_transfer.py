"""KV block migration + disaggregated prefill/decode serving.

The invariants under test:

- the KVX1 serialization layer round-trips **bitwise** (codec-level,
  engine-level unsharded, and through a heads-resharding tp=2 import —
  the payload always carries full heads, so compatible meshes adopt
  losslessly), and corrupt/incompatible payloads are typed
  ``KVTransferError`` rejects;
- a **weight-provenance mismatch** is a typed reject before any device
  work (KV is a pure function of (weights, tokens));
- every transfer failure — unreachable peer, pool-dry receiver,
  provenance mismatch — falls back to **monolithic** prefill with zero
  client-visible errors and correct tokens;
- a disaggregated fleet (prefill + decode roles behind the router) is
  **token-identical** to ``generate()`` under the armed
  ``RecompileAuditor``, with compile-count==1 on BOTH roles;
- cross-replica prefix sharing: a hot prompt is prefilled once per
  FLEET (the second identical request is a trie hit on the prefill
  replica and a block adoption on the decode side);
- **drain-by-migration**: a rolling reload with ``migrate=True`` moves
  live streams off the draining replica mid-generation — every stream
  completes token-identically with zero client errors;
- the router-level handoff/fallback logic runs **jax-free** against
  EchoReplica fleets (the KVBLK frames and kv_export/kv_prefill verbs
  are emulated; the pull client is the real one).
"""

import asyncio
import json

import numpy as np
import pytest

from distkeras_tpu.serving import wire
from distkeras_tpu.serving.kv_transfer import (
    KVTransferError,
    deserialize_blocks,
    peek_header,
    serialize_blocks,
)
from distkeras_tpu.serving.prefix_cache import KVBlockPool

VOCAB = 64
SUP = dict(health_interval_s=0.2, base_delay_s=0.2)


@pytest.fixture(scope="module")
def lm():
    from distkeras_tpu.models.bert import gpt_tiny

    model = gpt_tiny(seq_len=64, vocab_size=VOCAB)
    return model, model.init(0)


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, size=(n,)).tolist()


def _engine(lm, **kw):
    from distkeras_tpu.serving import ServingEngine

    model, variables = lm
    kw.setdefault("slots", 2)
    kw.setdefault("kv_pool_blocks", 64)
    kw.setdefault("kv_block_tokens", 4)
    return ServingEngine(model, variables, **kw)


def _ref(lm, prompt, n):
    from distkeras_tpu.inference.generate import generate

    model, variables = lm
    return generate(model, variables, np.asarray([prompt], np.int32),
                    n, greedy=True)[0].tolist()


async def _kv_op(fn, arg):
    event, result = fn(arg)
    await asyncio.wait_for(event.wait(), 30)
    return result


# -- codec units (jax-free) --------------------------------------------------
def test_kvx1_codec_bitwise_and_typed_rejects():
    tokens = list(range(8))
    leaves = [np.arange(2 * 4 * 3 * 2, dtype=np.float32).reshape(2, 4, 3, 2),
              np.arange(2 * 4 * 5, dtype=np.int32).reshape(2, 4, 5)]
    payload = serialize_blocks(tokens, leaves, block_tokens=4,
                               provenance={"version": 3, "digest": "ab"})
    header, out = deserialize_blocks(payload)
    assert header["tokens"] == tokens
    assert header["provenance"] == {"version": 3, "digest": "ab"}
    for a, b in zip(leaves, out):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    # Re-serialization of the decoded form is byte-identical.
    assert serialize_blocks(header["tokens"], out,
                            block_tokens=header["block_tokens"],
                            provenance=header["provenance"]) == payload
    # Typed rejects: bad magic, truncated leaf, trailing junk, token
    # count not covering the blocks.
    with pytest.raises(KVTransferError):
        peek_header(b"NOPE" + payload[4:])
    with pytest.raises(KVTransferError):
        deserialize_blocks(payload[:-3])
    with pytest.raises(KVTransferError):
        deserialize_blocks(payload + b"x")
    with pytest.raises(KVTransferError):
        serialize_blocks(tokens[:-1], leaves, block_tokens=4)


def test_request_extras_ride_the_binary_wire():
    spec = {"prompt": [1, 2, 3], "max_new_tokens": 4,
            "kv_from": {"host": "h", "port": 9},
            "resume_tokens": [7, 8, 9]}
    out = wire.decode_request(wire.encode_request(spec))
    assert out["kv_from"] == {"host": "h", "port": 9}
    assert out["resume_tokens"] == [7, 8, 9]
    # Plain requests stay byte-identical to pre-extras frames (no
    # extras flag, no trailing blob) and decode without the keys.
    plain = wire.encode_request({"prompt": [1], "max_new_tokens": 2})
    dec = wire.decode_request(plain)
    assert "kv_from" not in dec and "resume_tokens" not in dec
    # The affinity hash still clamps to the prompt bytes with extras
    # appended.
    assert wire.affinity_prefix(
        wire.encode_request(spec), 16) == np.asarray(
            [1, 2, 3], "<i4").tobytes()


def test_kvblk_frames_ride_the_scanner():
    """KVBLK frames split correctly through FrameDecoder — both the
    struct fallback and (when built) the native fastwire scan, which is
    frame-type-agnostic by design."""
    blob = serialize_blocks(list(range(4)), [], block_tokens=4)
    data = (wire.encode_frame(wire.T_KVBLK, 7, blob)
            + wire.encode_json_frame(wire.T_CTRLR, 8, {"ok": 1}))
    # Pure-python scan (small buffer).
    frames = wire.FrameDecoder().feed(data)
    assert [(t, s) for t, s, _ in frames] == [(wire.T_KVBLK, 7),
                                              (wire.T_CTRLR, 8)]
    assert frames[0][2] == blob
    if wire.native_available():
        # Pad past the small-buffer crossover so the native scan runs.
        big = data * 400
        frames = wire.FrameDecoder().feed(big)
        assert len(frames) == 800
        assert frames[0][2] == blob


def test_adopt_foreign_pool_dry_and_partial():
    pool = KVBlockPool(4, 4)
    tokens = list(range(16))  # 4 complete blocks
    uploads, resident = pool.adopt_foreign(tokens, 4)
    assert len(uploads) == 4 and resident == 4
    # Re-adoption of the same chain uploads nothing (already resident).
    uploads, resident = pool.adopt_foreign(tokens, 4)
    assert uploads == [] and resident == 4
    # A DRY pool (every block privately held, nothing evictable) adopts
    # what fits — here nothing — and never raises or evicts slot blocks.
    dry = KVBlockPool(2, 4)
    held = dry.alloc(2)
    assert held is not None and dry.blocks_free == 0
    uploads, resident = dry.adopt_foreign(tokens, 4)
    assert uploads == [] and resident == 0
    # Partial adoption keeps the contiguous prefix.
    part = KVBlockPool(2, 4)
    uploads, resident = part.adopt_foreign(tokens, 4)
    assert len(uploads) == 2 and resident == 2


# -- engine-level transfer ---------------------------------------------------
@pytest.mark.parametrize("spelling", ["flat", "per_head"])
def test_export_import_bitwise_roundtrip_and_identical_continuation(
        lm, rng, spelling):
    """``per_head``: the payload as a peer older than the flat pool leaf
    sends it — header shapes ``[n, bt, H, D]`` over the same bytes in the
    same order — is adopted just the same (the wire format did not move
    with the stored shape)."""
    async def main():
        e1 = _engine(lm)
        e2 = _engine(lm)
        t1 = asyncio.create_task(e1.run())
        t2 = asyncio.create_task(e2.run())
        prompt = _prompt(rng, 13)
        ref = _ref(lm, prompt, 6)
        got = await (e1.submit(prompt, 6)).result()
        assert got == ref
        res = await _kv_op(e1.request_kv_export, prompt)
        assert "error" not in res and res["matched_tokens"] >= 12
        payload = res["payload"]
        header, leaves = deserialize_blocks(payload)
        if spelling == "per_head":
            heads = lm[0].config.num_heads
            split = [l.reshape(l.shape[:2] + (heads, -1)) for l in leaves]
            payload = serialize_blocks(
                header["tokens"], split,
                block_tokens=header["block_tokens"],
                provenance=header["provenance"])
            assert [l.tobytes() for l in split] == [
                l.tobytes() for l in leaves]
        imp = await _kv_op(e2.request_kv_import, payload)
        assert imp["adopted_blocks"] == header["n_blocks"]
        assert imp["matched_tokens"] == res["matched_tokens"]
        # The adopted prefix serves: token-identical continuation, and
        # the pool registers the hit.
        got2 = await (e2.submit(prompt, 6)).result()
        assert got2 == ref
        assert e2.kv_pool.hit_tokens >= imp["matched_tokens"]
        # Re-export from the importer is BITWISE the original payload's
        # leaves (same tokens, same rows' contents).
        res2 = await _kv_op(e2.request_kv_export, prompt)
        _, leaves2 = deserialize_blocks(res2["payload"])
        for a, b in zip(leaves, leaves2):
            assert a.tobytes() == b.tobytes()
        e1.shutdown()
        e2.shutdown()
        await asyncio.gather(t1, t2)

    asyncio.run(main())


def test_sharded_import_reshards_heads_and_roundtrips(lm, rng):
    """An unsharded export adopts into a tp=2 pool (full heads in the
    payload; kv_pytree_shardings replaces them on upload) and exports
    back bitwise-identical — the compatible-mesh reshard contract."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for tp=2")
    from distkeras_tpu.parallel.mesh import serving_mesh

    async def main():
        e1 = _engine(lm)
        e2 = _engine(lm, mesh=serving_mesh({"tp": 2},
                                           devices=jax.devices()[:2]))
        t1 = asyncio.create_task(e1.run())
        t2 = asyncio.create_task(e2.run())
        prompt = _prompt(rng, 12)
        ref = _ref(lm, prompt, 5)
        assert await (e1.submit(prompt, 5)).result() == ref
        res = await _kv_op(e1.request_kv_export, prompt)
        _, leaves = deserialize_blocks(res["payload"])
        imp = await _kv_op(e2.request_kv_import, res["payload"])
        assert "error" not in imp and imp["adopted_blocks"] >= 1
        assert await (e2.submit(prompt, 5)).result() == ref
        res2 = await _kv_op(e2.request_kv_export, prompt)
        _, leaves2 = deserialize_blocks(res2["payload"])
        for a, b in zip(leaves, leaves2):
            assert a.tobytes() == b.tobytes()
        e1.shutdown()
        e2.shutdown()
        await asyncio.gather(t1, t2)

    asyncio.run(main())


def test_provenance_mismatch_is_a_typed_reject(lm, rng):
    async def main():
        e1 = _engine(lm)
        e2 = _engine(lm, weight_version={"version": 7, "digest": "beef"})
        t1 = asyncio.create_task(e1.run())
        t2 = asyncio.create_task(e2.run())
        prompt = _prompt(rng, 12)
        await (e1.submit(prompt, 4)).result()
        res = await _kv_op(e1.request_kv_export, prompt)
        imp = await _kv_op(e2.request_kv_import, res["payload"])
        err = imp.get("error")
        assert isinstance(err, KVTransferError)
        assert "provenance" in str(err)
        assert err.code == "kv_transfer"
        # Nothing was adopted: the pool is untouched.
        assert e2.kv_pool.blocks_used == 0
        # Geometry mismatch rejects typed too.
        bad = _engine(lm, kv_block_tokens=8)
        t3 = asyncio.create_task(bad.run())
        imp = await _kv_op(bad.request_kv_import, res["payload"])
        assert isinstance(imp.get("error"), KVTransferError)
        assert "geometry" in str(imp["error"])
        e1.shutdown(), e2.shutdown(), bad.shutdown()
        await asyncio.gather(t1, t2, t3)

    asyncio.run(main())


def test_pp_engine_rejects_kv_transfer_typed(lm):
    """The gather and scatter programs span the whole pool, which a pp
    mesh partitions by stage: export and import are refused typed."""
    import jax

    from distkeras_tpu.parallel.mesh import serving_mesh

    staged = _engine(lm, mesh=serving_mesh({"tp": 1, "pp": 2},
                                           devices=jax.devices()[:2]))
    with pytest.raises(KVTransferError, match="pp mesh"):
        staged.request_kv_export([1, 2, 3])
    with pytest.raises(KVTransferError, match="pp mesh"):
        staged.request_kv_import(b"")


# -- fleet-level disaggregation ----------------------------------------------
def _roles_cluster(lm, roles, registry=None, auditors=None,
                   router_kwargs=None, **engine_kw):
    from distkeras_tpu.serving import LocalReplica, ServingCluster
    from distkeras_tpu.telemetry import RecompileAuditor

    def factory(i):
        def build():
            kw = dict(engine_kw)
            if auditors is not None:
                auditors[i] = RecompileAuditor()
                kw.update(auditor=auditors[i],
                          arm_auditor_after_warmup=True)
            return _engine(lm, **kw)

        return LocalReplica(build)

    kwargs = {"affinity_tokens": 4, "min_handoff_tokens": 4}
    kwargs.update(router_kwargs or {})
    return ServingCluster(factory, len(roles), roles=roles,
                          registry=registry, supervisor_kwargs=SUP,
                          router_kwargs=kwargs)


def test_disaggregated_fleet_token_identical_armed_auditor(lm, rng):
    """The acceptance case: 1 prefill + 2 decode replicas behind the
    router, armed auditors everywhere — greedy output token-identical
    to generate(), every request migrated (zero fallbacks), and
    compile-count==1 on BOTH roles."""
    from distkeras_tpu.serving import ServingClient
    from distkeras_tpu.telemetry import MetricsRegistry

    async def main():
        registry = MetricsRegistry()
        auditors = {}
        cluster = _roles_cluster(lm, ["prefill", "decode", "decode"],
                                 registry=registry, auditors=auditors)
        prompts = [_prompt(rng, 12) for _ in range(5)]
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port,
                                     wire_mode="auto") as c:
                assert c.proto == wire.PROTO_BIN1
                for p in prompts:
                    done = await c.generate(p, 6)
                    assert done["tokens"] == _ref(lm, p, 6)
                    km = done.get("kv_migration")
                    assert km and "fallback" not in km, km
                    assert km["matched_tokens"] >= 12
            snap = registry.snapshot()
            assert snap["router_kv_handoffs_total"]["value"] == len(
                prompts)
            assert snap["router_kv_handoff_fallbacks_total"][
                "value"] == 0
            # Compile-count==1 on both roles, armed auditors silent.
            for rid, info in cluster.replicas.items():
                assert info.handle.engine.decode_compile_count() in (
                    0, 1), rid  # 0 = a decode replica that never ticked
            prefill_engine = cluster.replicas["r0"].handle.engine
            assert prefill_engine.metrics.kv_exports == len(prompts)
            decode_migrations = sum(
                cluster.replicas[r].handle.engine.metrics.kv_migrations
                for r in ("r1", "r2"))
            assert decode_migrations == len(prompts)
            # Fleet healthz rolls roles + migration sums up.
            async with ServingClient("127.0.0.1", cluster.port) as c:
                h = await c.healthz()
            assert h["router"]["roles"] == {"prefill": 1, "decode": 2}
            assert h["router"]["kv_migrations"]["migrations"] == len(
                prompts)
            for rid in ("r1", "r2"):
                assert h["replicas"][rid]["role"] == "decode"

    asyncio.run(main())


def test_prefix_share_prefills_once_per_fleet(lm, rng):
    """The same prompt through the fleet twice: the SECOND kv_prefill is
    a trie hit on the prefill replica (no recompute), whichever decode
    replica serves it."""
    from distkeras_tpu.serving import ServingClient

    async def main():
        cluster = _roles_cluster(lm, ["prefill", "decode", "decode"])
        prompt = _prompt(rng, 16)
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port) as c:
                ref = _ref(lm, prompt, 4)
                for _ in range(2):
                    done = await c.generate(prompt, 4)
                    assert done["tokens"] == ref
                    assert "fallback" not in (done.get("kv_migration")
                                              or {"fallback": 1})
            pe = cluster.replicas["r0"].handle.engine
            # Second kv_prefill matched the adopted chain: fleet-level
            # "prefilled once" — the pool saw a hit covering the
            # prompt's complete blocks.
            assert pe.kv_pool.hit_requests >= 1
            assert pe.kv_pool.hit_tokens >= 12

    asyncio.run(main())


def test_transfer_failure_falls_back_with_zero_client_errors(lm, rng):
    """Fault injection: the decode replica's pull target is unreachable
    (the router handed off, then the prefill replica vanished). The
    request must complete token-identically with a recorded fallback —
    never a client-visible error."""
    from distkeras_tpu.serving import ServingClient, ServingServer

    async def main():
        engine = _engine(lm)
        server = ServingServer(engine, port=0, kv_transfer_timeout_s=2.0)
        await server.start()
        prompt = _prompt(rng, 12)
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.port)
            # A spec pointing at a dead peer port: the pull fails, the
            # fallback prefills monolithic.
            spec = {"prompt": prompt, "max_new_tokens": 5,
                    "kv_from": {"host": "127.0.0.1", "port": 1}}
            writer.write((json.dumps(spec) + "\n").encode())
            await writer.drain()
            toks, done = [], None
            while done is None:
                rec = json.loads(await reader.readline())
                assert "error" not in rec, rec
                if "token" in rec:
                    toks.append(rec["token"])
                elif rec.get("done"):
                    done = rec
            assert done["tokens"] == _ref(lm, prompt, 5)
            assert "fallback" in done["kv_migration"]
            assert engine.metrics.kv_migration_fallbacks == 1
            assert engine.metrics.kv_migrations == 0
            writer.close()
        finally:
            await server.stop()

    asyncio.run(main())


def test_pool_dry_import_adopts_nothing_and_reports(lm, rng):
    """A receiver whose pool is fully privately held adopts zero blocks
    — the server maps that to a fallback, never an error."""
    async def main():
        e1 = _engine(lm)
        t1 = asyncio.create_task(e1.run())
        prompt = _prompt(rng, 12)
        await (e1.submit(prompt, 4)).result()
        res = await _kv_op(e1.request_kv_export, prompt)
        e2 = _engine(lm, kv_pool_blocks=4)
        held = e2.kv_pool.alloc(4)  # every block privately held
        assert held is not None
        imp = e2._kv_import_sync(res["payload"])
        assert imp["adopted_blocks"] == 0
        assert imp["resident_blocks"] == 0
        e1.shutdown()
        await t1

    asyncio.run(main())


def test_drain_via_migration_rolling_reload_under_load(lm, rng,
                                                       tmp_path):
    """Live slot migration: a rolling reload with migrate=True moves
    in-flight streams off each draining replica instead of waiting
    them out — every stream completes token-identically (the reload
    re-installs the SAME weight bytes) with zero client errors, and
    the roll reports the migrations."""
    from distkeras_tpu.checkpoint import save_weights_file
    from distkeras_tpu.serving import ServingClient
    from distkeras_tpu.telemetry import MetricsRegistry

    model, variables = lm
    path = str(tmp_path / "weights.npz")
    save_weights_file(path, variables)

    async def main():
        registry = MetricsRegistry()
        cluster = _roles_cluster(lm, ["monolithic", "monolithic"],
                                 registry=registry)
        prompts = [_prompt(rng, 8) for _ in range(4)]
        refs = [_ref(lm, p, 40) for p in prompts]
        async with cluster:
            async def one(p, ref):
                async with ServingClient("127.0.0.1",
                                         cluster.port) as c:
                    done = await c.generate(p, 40)
                    assert done["tokens"] == ref, "migrated stream "
                    "diverged"

            tasks = [asyncio.create_task(one(p, r))
                     for p, r in zip(prompts, refs)]
            # Let the streams get into flight, then roll with
            # migration.
            await asyncio.sleep(0.4)
            async with ServingClient("127.0.0.1", cluster.port) as c:
                rep = await c.reload(path, timeout=120.0, migrate=True)
            assert rep["ok"], rep
            await asyncio.gather(*tasks)
            migrated = rep.get("migrated_streams", 0)
            snap = registry.snapshot()
            assert migrated >= 1, (rep, snap)
            assert snap["router_stream_migrations_total"][
                "value"] >= 1
            assert snap["router_streams_lost_total"]["value"] == 0

    asyncio.run(main())


# -- jax-free router handoff (Echo fleet) ------------------------------------
def test_echo_fleet_handoff_and_fallback_jax_free():
    """Router handoff logic against an engine-free Echo fleet: the
    happy path runs the REAL KVBLK pull (fetch_blocks against the
    emulated kv_export), and a kv_fail prefill replica exercises the
    fallback path — generation never fails either way."""
    from distkeras_tpu.serving import ServingClient, ServingCluster
    from distkeras_tpu.serving.cluster.replicas import EchoReplica
    from distkeras_tpu.telemetry import MetricsRegistry

    async def run_fleet(kv_fail):
        registry = MetricsRegistry()
        cluster = ServingCluster(
            lambda i: EchoReplica(kv_fail=kv_fail, kv_block_tokens=4),
            3, roles=["prefill", "decode", "decode"], registry=registry,
            supervisor_kwargs=SUP,
            router_kwargs={"affinity_tokens": 4,
                           "min_handoff_tokens": 4})
        async with cluster:
            async with ServingClient("127.0.0.1", cluster.port,
                                     wire_mode="auto") as c:
                done = await c.generate([5, 6, 7, 8, 9], 1)
                assert done["tokens"] == [5]
                km = done.get("kv_migration")
            prefill = cluster.replicas["r0"].handle.server
            snap = registry.snapshot()
            return km, prefill, snap

    async def main():
        km, prefill, snap = await run_fleet(kv_fail=False)
        assert km and "fallback" not in km, km
        assert km["matched_tokens"] == 4  # one 4-token block
        assert prefill.kv_prefills == 1 and prefill.kv_exports == 1
        assert snap["router_kv_handoffs_total"]["value"] == 1
        km, prefill, snap = await run_fleet(kv_fail=True)
        assert km is None  # no handoff arranged -> no kv_from
        assert snap["router_kv_handoff_fallbacks_total"]["value"] == 1
        assert snap["router_kv_handoffs_total"]["value"] == 0

    asyncio.run(main())


# -- multi-frame chunking + peer connection pool (jax-free) ------------------
def test_split_frames_join_bitwise_roundtrip():
    """An oversize payload splits into sequenced KVXC chunk frames with
    a terminal marker and reassembles BITWISE; a payload that fits one
    frame stays byte-identical to the pre-chunking wire (old receivers
    keep working)."""
    from distkeras_tpu.serving.kv_transfer import (
        FrameJoiner,
        is_chunk_frame,
        split_frames,
    )

    small = b"KVX1" + bytes(range(256)) * 10
    assert split_frames(small) == [small]
    assert not is_chunk_frame(small)

    rng = np.random.default_rng(3)
    big = bytes(rng.integers(0, 256, size=5000, dtype=np.uint8))
    frames = split_frames(big, max_frame_bytes=1024)
    assert len(frames) > 1
    assert all(is_chunk_frame(f) for f in frames)
    assert all(len(f) <= 1024 for f in frames)
    joiner = FrameJoiner()
    out = None
    for i, f in enumerate(frames):
        whole = joiner.feed(f)
        if i < len(frames) - 1:
            assert whole is None  # terminal marker not yet seen
        else:
            out = whole
    assert out == big  # bitwise


def test_frame_joiner_typed_rejects():
    """Out-of-order / duplicate / disagreeing-total / over-cap chunk
    sequences are typed KVTransferError rejects, never a hang or an
    unbounded buffer."""
    from distkeras_tpu.serving.kv_transfer import (
        FrameJoiner,
        split_frames,
    )

    big = bytes(range(256)) * 20
    frames = split_frames(big, max_frame_bytes=512)
    assert len(frames) >= 3
    # out of order
    j = FrameJoiner()
    j.feed(frames[0])
    with pytest.raises(KVTransferError, match="out of order"):
        j.feed(frames[2])
    # duplicate (same seq twice)
    j = FrameJoiner()
    j.feed(frames[0])
    with pytest.raises(KVTransferError, match="out of order"):
        j.feed(frames[0])
    # bare payload mid-sequence
    j = FrameJoiner()
    j.feed(frames[0])
    with pytest.raises(KVTransferError, match="mid chunk"):
        j.feed(b"KVX1whatever")
    # total cap enforced during reassembly
    j = FrameJoiner(max_total_bytes=600)
    with pytest.raises(KVTransferError, match="cap"):
        for f in frames:
            j.feed(f)
    # oversize refusal at the split site
    from distkeras_tpu.serving import kv_transfer as kvt

    with pytest.raises(KVTransferError, match="cap"):
        split_frames(b"x" * (kvt.MAX_TOTAL_TRANSFER_BYTES + 1))


def test_fetch_blocks_pools_peer_connections():
    """The decode-side pull path reuses ONE negotiated connection per
    peer across migrations (the router's pooled-conn pattern): N pulls
    = 1 dial, and a peer restart (dead pooled socket) costs one
    transparent re-dial, never a fallback."""
    from distkeras_tpu.serving.cluster.replicas import EchoServer
    from distkeras_tpu.serving.kv_transfer import (
        PeerConnectionPool,
        fetch_blocks,
    )

    async def main():
        server = EchoServer(kv_block_tokens=4)
        await server.start()
        pool = PeerConnectionPool()
        try:
            for _ in range(4):
                payload = await fetch_blocks(
                    "127.0.0.1", server.port, [1, 2, 3, 4, 5],
                    timeout=5, pool=pool)
                assert payload is not None
                header = peek_header(payload)
                assert header["block_tokens"] == 4
            assert pool.dials == 1, pool.stats()
            assert pool.reuses == 3, pool.stats()

            # A restarted peer presents a dead pooled socket (the old
            # incarnation's connections die with its process): simulate
            # by closing the idle transport; the checkout probe must
            # discard it and re-dial transparently — never a fallback.
            for conns in pool._idle.values():
                for _r, w in conns:
                    w.close()
            await asyncio.sleep(0)  # let the transport close
            payload = await fetch_blocks(
                "127.0.0.1", server.port, [1, 2, 3, 4, 5],
                timeout=5, pool=pool)
            assert payload is not None
            assert pool.dials == 2, pool.stats()
        finally:
            await server.stop()
            pool.close_all()

    asyncio.run(main())


def test_chunked_export_reassembles_over_the_wire(lm, rng):
    """End-to-end multi-frame transfer against a REAL jax server: the
    export side splits via split_frames, fetch_blocks reassembles, and
    the re-imported chain round-trips bitwise — proven by forcing the
    per-frame bound below one block's bytes so every export chunks."""
    from distkeras_tpu.serving import kv_transfer as kvt
    from distkeras_tpu.serving.server import ServingServer

    prompt = _prompt(rng, 16)

    async def main():
        engine = _engine(lm)
        server = ServingServer(engine, port=0)
        await server.start()  # owns the engine.run() task
        try:
            req = engine.submit(prompt, 1)
            await req.result()
            # Direct export for the reference payload.
            ref = await _kv_op(engine.request_kv_export, prompt)
            assert ref.get("payload"), ref
            # Force chunking: every frame far smaller than the payload.
            orig = kvt.MAX_TRANSFER_BYTES
            kvt.MAX_TRANSFER_BYTES = 1024
            try:
                pulled = await fetch_blocks_patched(
                    "127.0.0.1", server.port, prompt)
            finally:
                kvt.MAX_TRANSFER_BYTES = orig
            assert pulled == ref["payload"]  # bitwise through the wire
        finally:
            await server.stop()

    async def fetch_blocks_patched(host, port, tokens):
        from distkeras_tpu.serving.kv_transfer import (
            PeerConnectionPool,
            fetch_blocks,
        )

        pool = PeerConnectionPool()
        try:
            return await fetch_blocks(host, port, tokens, timeout=10,
                                      pool=pool)
        finally:
            pool.close_all()

    asyncio.run(main())
