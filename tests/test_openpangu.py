"""The latent-MoE decoder's second residual form (``models/xing4.py`` with
``sandwich_norm``: openPangu-Ultra-MoE's plain residual with four RMSNorms a
layer, sigmoid routing without a correction bias) on the CPU at a small
size, against the plain reference the benchmark keeps
(``chipbench/models/openpangu_moe_block/reference.py``: float32, expanded
attention, imports nothing of the program).

Sizes: hidden 64, 4 heads (nope 16, rope 8, v 16), ranks 48/32, one dense
layer then two expert layers of 8 (top-2, scaled 2.5) and one shared, the
routed experts held in shares; blocks of 4 tokens, the latent read in
steps of 8 positions; seeded random weights, made by the reference and
handed to the program. In float32 the two agree to rounding, so every
comparison here is of logits to ``TOL``; the reference with its products'
operands in bfloat16 or float8 lies far outside.
"""

import asyncio
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.inference.generate import _decode_module
from distkeras_tpu.models import xing4
from distkeras_tpu.models.xing4 import LatentAttention, Xing4
from distkeras_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK_DIR = os.path.join(ROOT, "chipbench", "models", "openpangu_moe_block")
VOCAB, BT = 256, 4
# float32 against float32, three layers deep; the reference in bfloat16
# reads ~1 from it at this size (a routing flip moves a position's logits
# far), in float8 ~3
TOL = 2e-4
TINY = {"vocab_size": VOCAB, "hidden_size": 64, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_router_outputs": 8,
        "first_expert": 0, "num_experts": 4, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-5, "rope_theta": 25600000, "max_seq_len": 64}


def _load(part):
    spec = importlib.util.spec_from_file_location(
        "openpangu_block_" + part, os.path.join(BLOCK_DIR, part + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF, PROGRAM = _load("reference"), _load("program")


def _f32_model(seed=11):
    """(program config in float32, params, the reference's weights)."""
    cfg = dataclasses.replace(PROGRAM.program_config(TINY), dtype=jnp.float32,
                              latent_key_block=8)
    w = REF.make_weights(TINY, seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), PROGRAM._nested(w))
    return cfg, params, w


@pytest.fixture
def rng():
    return np.random.default_rng(38)


def test_the_tiny_preset_is_the_reference_sizes():
    tiny = xing4.openpangu_tiny(dtype="float32").config
    assert tiny == _f32_model()[0]
    assert tiny.sandwich_norm and not tiny.router_bias


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
    (4) and read-step (8) edges; chunks of either size equal one shot. The
    tick counts the pairs of the two expert layers, the half of them whose
    expert is held here, and the latent blocks the decode read copied."""
    cfg, params, w = _f32_model()
    seqs = [rng.integers(1, VOCAB, size=n) for n in (37, 22)]
    prompt_len = (20, 10)
    want = [np.asarray(REF.forward(w, np.pad(s, (0, 40 - len(s))), TINY,
                                   "f32", block=8)) for s in seqs]
    sentinel = 40
    tables = np.full((2, 16), sentinel, np.int32)
    tables[0, :10] = np.arange(10) + 3           # any blocks, in any order
    tables[1, :6] = [20, 14, 31, 15, 29, 17]
    held = pairs = 0

    def run(chunk):
        nonlocal held, pairs
        module, cache = _paged(cfg)
        assert set(cache["layer_0"]["attention"]) == {"pool_latent"}
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
            assert 0 <= counts["moe_pairs_held"] <= counts["moe_pairs"]
            held, pairs = held + counts["moe_pairs_held"], pairs + counts["moe_pairs"]
            assert counts["kv_positions_resident"] == 3 * (pos[live] + 1).sum()
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
    assert 0 < held < pairs  # 4 of 8 experts held: some pairs, not all


def test_a_prefix_hit_gives_the_logits_of_a_cold_prefill(rng):
    """Row B's table shares the first three latent blocks that row A wrote:
    prefilling only B's tail from position 12 gives the logits a cold
    prefill of all of B gives."""
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


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(rng, shares):
    """An expert layer cut by experts over 2 or 4 chips (every chip holds
    all heads, the router's 8 outputs and the shared expert): each share's
    program equals the reference given the same share, and its expert
    layer's routed terms, summed over the shares with the shared expert
    and attention counted once, give the uncut reference's layer."""
    whole = {**TINY, "num_hidden_layers": 1, "first_k_dense_replace": 0,
             "num_experts": 8}
    w = REF.make_weights(whole, 5)
    ids = rng.integers(1, VOCAB, size=24)
    x0 = jnp.asarray(w["token_embed"].astype(jnp.float32))[ids][None]
    p, ex, eps = "layer_0/", 8 // shares, 1e-5
    routed = []
    for c in range(shares):
        cut = {**whole, "num_experts": ex, "first_expert": c * ex}
        part = dict(w)
        for name in ("gate", "up", "down"):
            part[p + "experts/" + name] = w[p + "experts/" + name][
                c * ex:(c + 1) * ex]
        assert {k: v.shape for k, v in part.items()} == {
            k: tuple(s) for k, s in REF.weight_spec(cut).items()}
        cfg = dataclasses.replace(PROGRAM.program_config(cut),
                                  dtype=jnp.float32, latent_key_block=8)
        params = jax.tree.map(lambda a: a.astype(jnp.float32),
                              PROGRAM._nested(part))
        # the share's sublayers as its forward pass computes them
        hidden, seen = Xing4(cfg).apply(
            {"params": params}, jnp.asarray(ids)[None], return_hidden=True,
            capture_intermediates=lambda mdl, method: (
                isinstance(mdl, LatentAttention) and method == "__call__"
                or method == "_ffn"),
            mutable=["intermediates"])
        # the reference given the same share says the same
        assert np.abs(np.asarray(REF.hidden_states(
            part, cut, ids, "f32", block=8)) - np.asarray(hidden[0])).max() < TOL
        layer, seen = params["layer_0"], seen["intermediates"]["layer_0"]
        # attention: every share holds every head, so any share's is the
        # layer's, counted once
        y, _ = seen["attention"]["__call__"][0]
        x1 = x0 + xing4._rms_norm(y, layer["post_attention_norm_scale"], eps)
        m = xing4._rms_norm(x1, layer["ffn_norm_scale"], eps)
        routed.append(np.asarray(seen["_ffn"][0][0]))
    # every share computed the shared expert: count it once
    shared = np.asarray(REF._ffn(w, p, m[0], {**whole, "num_experts": 0}, "f32"))
    total = sum(routed) - (shares - 1) * shared
    uncut = x1 + xing4._rms_norm(jnp.asarray(total),
                                 w[p + "post_ffn_norm_scale"], eps)
    want = np.asarray(REF.hidden_states(w, whole, ids, "f32", block=8))
    assert np.abs(np.asarray(uncut[0]) - want).max() < TOL
    # and no share alone is the layer: each holds only some of the terms
    assert all(np.abs(r[0] - np.asarray(total)[0]).max() > 20 * TOL
               for r in routed)


# ----------------------------------------------------- (b) through the engine

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
    """The zoo's ``openpangu_tiny`` through the same engine, scheduler,
    pool and chunked prefill as every model: greedy tokens of prompts that
    share a 20-token prefix lie on the reference's best logit whether the
    prefix was computed or handed over as latent blocks; the decode step
    compiles once; the tick's counters arrive, half the pairs held; the
    ``prefill_tick`` spans name every position the prefill programs
    computed, by ``offset`` and ``tokens``."""
    from distkeras_tpu import run
    from distkeras_tpu.telemetry import RecompileAuditor
    from distkeras_tpu.telemetry import spans as T

    model = run.load_model("openpangu_tiny", {"dtype": "float32"})
    _, params, w = _f32_model()
    auditor = RecompileAuditor()
    engine = ServingEngine(model, {"params": params}, slots=3, max_queue=8,
                           kv_pool_blocks=64, kv_block_tokens=BT,
                           prefill_chunk=8, auditor=auditor,
                           arm_auditor_after_warmup=True)
    prefix = rng.integers(1, VOCAB, size=20).tolist()
    prompts = [prefix + rng.integers(1, VOCAB, size=n).tolist()
               for n in (3, 9, 14)]
    tracer = T.enable_tracing()
    try:
        served = asyncio.run(_serve(engine, prompts, 10))
    finally:
        T.disable_tracing()
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
    assert 0 < counters["moe_pairs_held_total"] < counters["moe_pairs_total"]
    assert counters["latent_blocks_read_total"] * BT >= \
        counters["kv_positions_resident_total"] > 0
    chunks = [attrs for ph, name, *_, attrs in tracer.events()
              if ph == "B" and name == "prefill_tick"]
    computed = counters["serving_prefill_tokens_total"]
    assert computed == sum(map(len, prompts)) - engine.kv_pool.hit_tokens
    assert sum(c["tokens"] for c in chunks) == computed
    assert all(0 < c["tokens"] <= 8 for c in chunks)
    # a chunk's keys: its own positions and every one before them
    assert sum(c["offset"] + c["tokens"] for c in chunks) <= \
        counters["serving_prefill_key_positions_total"]


def test_the_zoo_holds_the_stage_at_its_published_widths():
    from distkeras_tpu import run

    stage = run.load_model("openpangu_ultra_moe_ep16_stage", {})
    cfg = stage.config
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim) == (
        7680, 128, 1536, 512, 64, 128, 128)
    assert (cfg.num_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.expert_dim, cfg.num_shared_experts, cfg.dense_dim,
            cfg.vocab_size) == (256, (0, 16), 8, 2048, 1, 18432, 19200)
    assert cfg.num_layers == 5 and cfg.first_dense_layers == 1
    assert cfg.sandwich_norm and not cfg.router_bias
    assert cfg.routed_scaling_factor == 2.5 and cfg.rms_norm_eps == 1e-5
    assert round(stage.count_params() / 1e9, 3) == 4.919  # shapes only
    # (512 + 64) lanes padded to 640, x 2 B x 5 layers
    assert cfg.latent_width == 576 and cfg.kv_token_bytes == 6400
    # plain RoPE: no YaRN factor, so no mscale in the softmax scale
    assert cfg.softmax_scale == pytest.approx(1 / np.sqrt(192))
    assert np.allclose(xing4.yarn_inv_freq(64, 25600000.0, 1.0, 4096, 32, 1),
                       25600000.0 ** (-np.arange(0, 64, 2) / 64))
