"""The third decoder block (``models/xing4.py``: multi-head latent attention
over a latent paged pool, hyper-connected residual streams, bias-corrected
sigmoid routing over whole experts) on the CPU at a small size, against the
plain reference the benchmark keeps
(``chipbench/models/xing4_block/reference.py``: float32, expanded attention,
imports nothing of the program).

Sizes: hidden 64, 4 heads (nope 16, rope 8, v 16), ranks 48/32, 4 streams,
one dense layer then two expert layers of 8 (top-2) and one shared, blocks
of 4 tokens, the latent read in steps of 8 positions; seeded random
weights, made by the reference and handed to the program. In float32 the
two agree to rounding (the CPU's products are exact), so every comparison
here is of logits to ``TOL``; the reference in bfloat16 (which rounds the
streams' mixing, a bfloat16 residual stream) or in float8 lies far outside.
"""

import asyncio
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.inference.generate import _decode_module, generate
from distkeras_tpu.models import xing4
from distkeras_tpu.models.xing4 import Xing4, Xing4Config
from distkeras_tpu.ops import attention, moe
from distkeras_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK_DIR = os.path.join(ROOT, "chipbench", "models", "xing4_block")
VOCAB, BT = 256, 4
# float32 against float32, three layers of four streams deep; the reference
# in bfloat16 reads ~5e-2 from it, in float8 ~2
TOL = 2e-4
TINY = {"vocab_size": VOCAB, "hidden_size": 64, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_routed_experts": 8, "first_expert": 0,
        "num_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
        "routed_scaling_factor": 2, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {"factor": 4, "original_max_position_embeddings": 16,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                         "mscale_all_dim": 1, "type": "yarn"},
        "max_seq_len": 64}


def _load(part):
    spec = importlib.util.spec_from_file_location(
        "xing4_block_" + part, os.path.join(BLOCK_DIR, part + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF, PROGRAM = _load("reference"), _load("program")


def _f32_model(seed=11, **changes):
    """(program config in float32, params, the reference's weights)."""
    cfg = dataclasses.replace(PROGRAM.program_config(TINY), dtype=jnp.float32,
                              latent_key_block=8, **changes)
    w = REF.make_weights(TINY, seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), PROGRAM._nested(w))
    return cfg, params, w


@pytest.fixture
def rng():
    return np.random.default_rng(36)


def test_the_tiny_preset_is_the_reference_sizes():
    tiny = xing4.xing4_tiny(dtype="float32").config
    assert tiny == dataclasses.replace(PROGRAM.program_config(TINY),
                                       dtype=jnp.float32, latent_key_block=8)


# ------------------------------------------------- (a) against the reference

def test_full_forward_matches_the_reference_and_lower_precisions_do_not(rng):
    cfg, params, w = _f32_model()
    ids = rng.integers(1, VOCAB, size=32)
    got = np.asarray(Xing4(cfg).apply({"params": params},
                                      jnp.asarray(ids)[None])[0])
    want = np.asarray(REF.forward(w, ids, TINY, "f32", block=8))
    assert np.abs(got - want).max() < TOL
    for kind in ("bf16", "fp8"):  # TOL would catch either
        other = np.asarray(REF.forward(w, ids, TINY, kind, block=8))
        assert np.abs(other - want).max() > 20 * TOL, kind


def _paged(cfg, blocks=40, table=16, rows=2):
    module, _ = _decode_module(
        type("M", (), {"config": cfg, "name": "t"})(), slots=True,
        paged_blocks=blocks, page_tokens=BT, page_table_blocks=table)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda r: module.init(
            r, jnp.zeros((rows, 1), jnp.int32), train=False),
            jax.random.PRNGKey(0))["cache"])
    return module, cache


def _apply(module, params):
    return jax.jit(lambda cache, ids, positions, tables: module.apply(
        {"params": params, "cache": cache}, ids, train=False,
        mutable=["cache", "counters"], positions=positions,
        block_tables=tables))


@pytest.mark.parametrize("chunks", [(5, 20), (6, 7)])
def test_paged_prefill_then_decode_matches_the_reference_logits(rng, chunks):
    """Chunked prefill through the latent pool, then decode steps, two rows
    side by side at different depths: every position's logits are the
    reference's full forward pass (expanded attention), across the block
    (4) and read-step (8) edges; chunks of either size equal one shot."""
    cfg, params, w = _f32_model()
    seqs = [rng.integers(1, VOCAB, size=n) for n in (37, 22)]
    prompt_len = (20, 10)
    want = [np.asarray(REF.forward(w, np.pad(s, (0, 40 - len(s))), TINY,
                                   "f32", block=8)) for s in seqs]
    sentinel = 40
    tables = np.full((2, 16), sentinel, np.int32)
    tables[0, :10] = np.arange(10) + 3           # any blocks, in any order
    tables[1, :6] = [20, 14, 31, 15, 29, 17]

    def run(chunk):
        module, cache = _paged(cfg)
        assert set(cache["layer_0"]["attention"]) == {"pool_latent"}
        # one latent row a token: 32 + 8 lanes, padded to the 128-lane tile
        assert cache["layer_0"]["attention"]["pool_latent"].shape == (40, BT, 128)
        apply = _apply(module, params)
        got = [[], []]
        for row in (0, 1):
            for lo in range(0, prompt_len[row], chunk):
                ids = seqs[row][lo:min(lo + chunk, prompt_len[row])]
                logits, mut = apply(cache, jnp.asarray(ids)[None],
                                    jnp.asarray([lo], jnp.int32),
                                    jnp.asarray(tables[row])[None])
                assert "counters" not in mut  # a chunk counts nothing
                cache = mut["cache"]
                got[row].append(np.asarray(logits[0]))
        pos = np.asarray(prompt_len, np.int32)
        while (pos < [len(s) for s in seqs]).any():
            live = pos < [len(s) for s in seqs]
            ids = [seqs[r][pos[r]] if live[r] else 0 for r in (0, 1)]
            masked = np.where(live[:, None], tables, sentinel)
            logits, mut = apply(cache, jnp.asarray(ids, jnp.int32)[:, None],
                                jnp.asarray(pos), jnp.asarray(masked))
            cache = mut["cache"]
            counts = dict(zip(xing4.TICK_COUNTERS,
                              np.asarray(mut["counters"]["tick"][0])))
            assert counts["moe_pairs"] == 2 * 2 * live.sum()  # 2 expert layers
            assert counts["moe_pairs_held"] == counts["moe_pairs"]  # all held
            assert counts["kv_positions_resident"] == 3 * (pos[live] + 1).sum()
            # the blocks the decode read copied: those up to each position
            assert counts["latent_blocks_read"] == 3 * (pos[live] // BT + 1).sum()
            for r in (0, 1):
                if live[r]:
                    got[r].append(np.asarray(logits[r]))
            pos = pos + live
        return [np.concatenate(g) for g in got]

    chunked, one_shot = run(chunks[0]), run(chunks[1])
    for r in (0, 1):
        n = len(seqs[r])
        assert np.abs(chunked[r] - want[r][:n]).max() < TOL
        assert np.abs(chunked[r] - one_shot[r]).max() < TOL


def test_a_prefix_hit_gives_the_logits_of_a_cold_prefill(rng):
    """Row B's table shares the first three latent blocks that row A wrote
    (a prefix hit hands blocks, not tokens): prefilling only B's tail from
    position 12 gives the logits a cold prefill of all of B gives."""
    cfg, params, _ = _f32_model()
    prefix = rng.integers(1, VOCAB, size=12)
    a = np.concatenate([prefix, rng.integers(1, VOCAB, size=7)])
    b = np.concatenate([prefix, rng.integers(1, VOCAB, size=9)])
    module, cache = _paged(cfg, rows=1)
    apply = _apply(module, params)
    row_a = np.full((16,), 40, np.int32)
    row_a[:5] = [7, 3, 11, 2, 9]
    _, mut = apply(cache, jnp.asarray(a)[None], jnp.asarray([0], jnp.int32),
                   jnp.asarray(row_a)[None])
    row_b = np.full((16,), 40, np.int32)
    row_b[:6] = [7, 3, 11, 20, 21, 22]  # A's three prefix blocks, then B's own
    hit, _ = apply(mut["cache"], jnp.asarray(b[12:])[None],
                   jnp.asarray([12], jnp.int32), jnp.asarray(row_b)[None])
    _, cold_cache = _paged(cfg, rows=1)
    cold, _ = apply(cold_cache, jnp.asarray(b)[None],
                    jnp.asarray([0], jnp.int32), jnp.asarray(row_b)[None])
    assert np.abs(np.asarray(hit[0]) - np.asarray(cold[0, 12:])).max() < TOL


def test_the_absorbed_read_agrees_with_the_expanded_attention():
    """The latent read of a pool, absorbed (q~ = q_nope W_UK^T against the
    latent row as key and value), against the plain expanded causal
    attention of the same rows (per-head keys and values built from each),
    for a decode step and a window of 5 queries, over several read steps."""
    k = jax.random.PRNGKey(4)
    B, H, nope, rope, dv, R, bt, T = 2, 3, 8, 4, 8, 16, 4, 8
    L = T * bt
    latent = jax.random.normal(k, (B, L, R + rope))
    w_ukv = jax.random.normal(jax.random.fold_in(k, 1), (R, H, nope + dv)) * R ** -0.5
    q_nope = jax.random.normal(jax.random.fold_in(k, 2), (B, L, H, nope))
    q_rope = jax.random.normal(jax.random.fold_in(k, 3), (B, L, H, rope))
    plain = attention.latent_attention(q_nope, q_rope, latent, w_ukv, 0.3)
    # the rows of each sequence in a shuffled pool of blocks
    order = np.random.default_rng(1).permutation(B * T)
    tables = jnp.asarray(order.reshape(B, T), jnp.int32)
    pool = jnp.zeros((B * T, bt, 128)).at[tables.reshape(-1), :, :R + rope].set(
        latent.reshape(B * T, bt, R + rope))  # lanes past R + rope: zeros
    for start, S in ((21, 1), (9, 5), (L - 1, 1)):
        positions = jnp.asarray([start, start - 3])
        idx = positions[:, None] + jnp.arange(S)
        take = jax.vmap(lambda x, i: x[i])
        got = attention.paged_latent_attention(
            take(q_nope, idx), take(q_rope, idx), pool, tables, positions,
            w_ukv, 0.3, key_block=8)
        assert np.abs(np.asarray(got - take(plain, idx))).max() < 1e-4


# ---------------------------------------------- (b) the pieces on their own

def test_sinkhorn_gives_a_doubly_stochastic_matrix():
    """Rows and columns sum to 1 to a few ``hc_eps`` (each division adds
    one) where the logits spread as an untrained layer's do; twenty steps
    leave columns of a wider spread further off (std 2: 3e-2), which is the
    published iteration count's own, not the program's."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (200, 4, 4)) * 0.5
    h = np.asarray(xing4.sinkhorn(jnp.exp(logits), 20, 1e-6))
    assert (h > 0).all()
    assert np.abs(h.sum(-1) - 1).max() < 4e-6
    assert np.abs(h.sum(-2) - 1).max() < 4e-6
    # and through the model's coefficient path: the program's H_res
    cfg, params, w = _f32_model()
    X = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 4, 64))
    _, h_res, h_post = xing4.HyperConnection(cfg).apply(
        {"params": params["layer_1"]["ffn_hc"]}, X)
    assert np.abs(np.asarray(h_res).sum(-1) - 1).max() < 1e-5
    assert np.abs(np.asarray(h_res).sum(-2) - 1).max() < 1e-3
    assert ((np.asarray(h_post) > 0) & (np.asarray(h_post) < 2)).all()


def test_the_correction_bias_moves_the_choice_and_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    ids, weights = moe.sigmoid_topk_route(logits, 2)
    # a bias that favours experts 6 and 7 by more than any score gap
    bias = jnp.zeros((8,)).at[6:].set(1.0)
    b_ids, b_weights = moe.sigmoid_topk_route(logits, 2, bias=bias)
    order = np.argsort(np.asarray(b_ids), -1)
    assert (np.take_along_axis(np.asarray(b_ids), order, -1) == [6, 7]).all()
    scores = np.asarray(jax.nn.sigmoid(logits))[:, 6:]  # unbiased, normalised
    assert np.allclose(np.take_along_axis(np.asarray(b_weights), order, -1),
                       scores / scores.sum(-1, keepdims=True))
    s_ids, s_weights = moe.sigmoid_topk_route(logits, 2, scale=2.0)
    assert (np.asarray(s_ids) == np.asarray(ids)).all()
    assert np.allclose(s_weights, 2 * weights) and np.allclose(
        np.asarray(s_weights).sum(-1), 2.0)


def _route_as_it_was(logits, k):
    """``sigmoid_topk_route`` before the bias and the scale were added."""
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        top, ids = jax.lax.top_k(scores, k)
        return ids.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)


def test_the_route_without_bias_or_scale_is_the_program_it_was():
    """Command A+'s router: the same outputs and the same lowered program."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (640, 128))
    old = jax.jit(lambda x: _route_as_it_was(x, 8))
    new = jax.jit(lambda x: moe.sigmoid_topk_route(x, 8))
    for a, b in zip(old(logits), new(logits)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert old.lower(logits).as_text() == new.lower(logits).as_text()


# ----------------------------------------------------- (c) through the engine

def _engine(model, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_queue", 8)
    kw.setdefault("kv_pool_blocks", 64)
    kw.setdefault("kv_block_tokens", BT)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(model, {"params": params}, **kw)


async def _serve(engine, prompts, n):
    task = asyncio.create_task(engine.run())
    try:
        out = []
        for p in prompts:  # one after another: the later ones hit the prefix
            out.append(await engine.submit(p, n).result())
        return out
    finally:
        engine.shutdown(drain=True)
        await task


def test_the_engine_serves_it_with_prefix_hits_on_latent_blocks(rng):
    """The zoo's ``xing4_tiny`` through the same engine, scheduler, pool and
    chunked prefill as every model: greedy tokens of prompts that share a
    20-token prefix lie on the reference's best logit (float32: to
    rounding) whether their prefix was computed or handed over as latent
    blocks; the decode step compiles once with the auditor armed; the
    tick's counters (the latent blocks the decode read copied among them)
    and the prefill counters arrive."""
    from distkeras_tpu import run
    from distkeras_tpu.telemetry import RecompileAuditor

    model = run.load_model("xing4_tiny", {"dtype": "float32"})
    _, params, w = _f32_model()
    auditor = RecompileAuditor()
    engine = _engine(model, params, auditor=auditor,
                     arm_auditor_after_warmup=True)
    prefix = rng.integers(1, VOCAB, size=20).tolist()
    prompts = [prefix + rng.integers(1, VOCAB, size=n).tolist()
               for n in (3, 9, 14)]
    served = asyncio.run(_serve(engine, prompts, 10))
    for prompt, tokens in zip(prompts, served):
        logits = REF.position_logits(w, TINY, prompt, tokens, "f32", 64, block=8)
        assert REF.token_gaps(logits, tokens).max() < TOL
    assert engine.kv_pool.hit_tokens >= 2 * 20
    assert auditor.compiles("serving_decode") == 1
    counters = {k: v["value"] for k, v in
                engine.metrics.registry.snapshot().items() if "value" in v}
    ticks = counters["serving_counted_ticks_total"]
    assert ticks >= 9
    assert counters["moe_pairs_total"] >= 2 * 2 * ticks
    assert counters["moe_pairs_held_total"] == counters["moe_pairs_total"]
    # the decode read's blocks hold the live rows' positions and no more
    # than the part of a block past each row's last one (3 layers; a live
    # row makes 2 x 2 pairs a tick)
    resident = counters["kv_positions_resident_total"]
    row_layers = 3 * counters["moe_pairs_total"] // 4
    assert resident > 0
    assert resident <= counters["latent_blocks_read_total"] * BT < (
        resident + BT * row_layers)
    # what the prefill programs computed: none of the hit positions
    computed = counters["serving_prefill_tokens_total"]
    assert computed == sum(map(len, prompts)) - engine.kv_pool.hit_tokens
    assert counters["serving_prefill_key_positions_total"] > computed


@pytest.fixture(scope="module")
def served_tiny():
    cfg, params, w = _f32_model(seed=3)
    model = xing4.xing4_tiny(dtype="float32")
    return model, params


async def _kv_op(fn, arg):
    event, result = fn(arg)
    await asyncio.wait_for(event.wait(), 30)
    return result


def test_kv_transfer_carries_latent_blocks_bitwise(served_tiny, rng):
    """An export of a prompt's latent blocks adopts into a second engine
    (the KVX1 payload's leaves are ``[n, bt, R + rope]`` rows, bytes as
    stored): the continuation is token-identical and a re-export is
    bitwise the first."""
    from distkeras_tpu.serving.kv_transfer import deserialize_blocks

    model, params = served_tiny
    prompt = rng.integers(1, VOCAB, size=13).tolist()

    async def main():
        e1, e2 = _engine(model, params), _engine(model, params)
        t1, t2 = asyncio.create_task(e1.run()), asyncio.create_task(e2.run())
        try:
            want = await e1.submit(prompt, 6).result()
            res = await _kv_op(e1.request_kv_export, prompt)
            header, leaves = deserialize_blocks(res["payload"])
            assert len(leaves) == 3 and leaves[0].shape[1:] == (BT, 128)
            imp = await _kv_op(e2.request_kv_import, res["payload"])
            assert imp["adopted_blocks"] == header["n_blocks"] == 3
            got = await e2.submit(prompt, 6).result()
            res2 = await _kv_op(e2.request_kv_export, prompt)
            _, leaves2 = deserialize_blocks(res2["payload"])
            return want, got, leaves, leaves2, e2.kv_pool.hit_tokens
        finally:
            e1.shutdown()
            e2.shutdown()
            await asyncio.gather(t1, t2)

    want, got, leaves, leaves2, hits = asyncio.run(main())
    assert got == want and hits >= 12
    assert [a.tobytes() for a in leaves] == [b.tobytes() for b in leaves2]


def test_the_host_tier_spills_and_readmits_latent_blocks(served_tiny, rng):
    """Pool pressure spills a prompt's latent blocks to the host tier; the
    next request on that prefix re-admits them and is token-identical."""
    model, params = served_tiny
    engine = _engine(model, params, slots=1, kv_pool_blocks=5,
                     kv_host_tier_mb=4.0)
    a, b = (rng.integers(1, VOCAB, size=11).tolist() for _ in range(2))

    async def drive():
        task = asyncio.create_task(engine.run())
        try:
            out = [await engine.submit(p, 4).result() for p in (a, b, a)]
            return out, engine.metrics.kv_spills, engine.metrics.kv_readmits
        finally:
            engine.shutdown(drain=True)
            await task

    (first, _, again), spills, readmits = asyncio.run(drive())
    assert spills >= 2 and readmits >= 1
    assert again == first


def test_typed_refusals(served_tiny):
    """No dense cache (``generate()``, a speculative draft); no split of a
    latent leaf over ``tp`` (it has no heads)."""
    from jax.sharding import Mesh

    from distkeras_tpu.parallel.sharding import (
        LatentLeafError,
        kv_pytree_shardings,
    )

    model, params = served_tiny
    with pytest.raises(ValueError, match="paged"):
        generate(model, {"params": params}, np.asarray([[1, 2, 3]], np.int32),
                 2, greedy=True)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(LatentLeafError):
        ServingEngine(model, {"params": params}, slots=2, kv_pool_blocks=16,
                      kv_block_tokens=BT, mesh=mesh)
    # one device on the axis: nothing to split, the leaf stays whole
    one = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    pool = {"attention": {"pool_latent": jax.ShapeDtypeStruct((8, 4, 128),
                                                              jnp.bfloat16)}}
    assert kv_pytree_shardings(one, pool)["attention"]["pool_latent"].spec == ()
    with pytest.raises(ValueError, match="held_experts"):
        Xing4Config(num_experts=8, held_experts=(6, 4))


def test_the_zoo_holds_the_stage_at_its_published_widths():
    from distkeras_tpu import run

    stage = run.load_model("xing4_pp_stage", {})
    cfg = stage.config
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim) == (
        3584, 32, 768, 512, 64, 128, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_dim,
            cfg.num_shared_experts, cfg.dense_dim, cfg.vocab_size,
            cfg.hc_mult) == (64, 4, 1024, 1, 9216, 131072, 4)
    assert cfg.num_layers == 5 and cfg.first_dense_layers == 1
    assert round(stage.count_params() / 1e9, 3) == 4.048  # shapes only
    # (512 + 64) lanes padded to 640, x 2 B x 5 layers
    assert cfg.latent_width == 576 and cfg.kv_token_bytes == 6400
    # YaRN: mscale = 0.1 ln 64 + 1, squared into the softmax scale
    assert cfg.softmax_scale == pytest.approx(
        (0.1 * np.log(64) + 1) ** 2 / np.sqrt(192))
