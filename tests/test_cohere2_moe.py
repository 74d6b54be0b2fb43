"""The second decoder block (``models/cohere2_moe.py``: parallel block,
grouped-query attention, window and full layers in one paged stack, sigmoid
top-k routed experts told which they hold, shared experts beside them) on
the CPU at a small size, against the plain reference the benchmark keeps
(``chipbench/models/cohere2_moe_block/reference.py``: float32, imports
nothing of the program).

Sizes: hidden 64, 8 query heads on 2 K/V heads of 16, 8 experts (top-2) with
2 shared, window 8, four layers sliding-sliding-sliding-full, blocks of 4
tokens; seeded random weights, made by the reference and handed to the
program. In float32 the two agree to rounding, so every comparison here is
of logits, to a float32 tolerance.
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
from distkeras_tpu.models import cohere2_moe
from distkeras_tpu.models.cohere2_moe import Cohere2Moe, Cohere2MoeConfig
from distkeras_tpu.ops import moe
from distkeras_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK_DIR = os.path.join(ROOT, "chipbench", "models", "cohere2_moe_block")
VOCAB, WINDOW, BT = 256, 8, 4
TOL = 2e-4  # float32 against float32 (the CPU's products are exact), four layers deep


def _load(part):
    spec = importlib.util.spec_from_file_location(
        "cohere2_moe_block_" + part, os.path.join(BLOCK_DIR, part + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF, PROGRAM = _load("reference"), _load("program")


def _sizes(**changes):
    sizes = {"vocab_size": VOCAB, "hidden_size": 64, "num_attention_heads": 8,
             "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
             "num_experts": 8, "first_expert": 0, "num_router_outputs": 8,
             "num_experts_per_tok": 2, "num_shared_experts": 2,
             "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
             "sliding_window": WINDOW, "rope_theta": 50000,
             "layer_norm_eps": 1e-5, "logit_scale": 1, "max_seq_len": 64}
    return {**sizes, **changes}


def _f32_model(sizes, seed=11):
    """(program config in float32, params, the reference's weights)."""
    cfg = dataclasses.replace(PROGRAM.program_config(sizes), dtype=jnp.float32)
    w = REF.make_weights(sizes, seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), PROGRAM._nested(w))
    return cfg, params, w


@pytest.fixture
def rng():
    return np.random.default_rng(29)


# ------------------------------------------------- (a) paged pool vs reference

def _paged(cfg, blocks=40, table=16, rows=2):
    module, dec = _decode_module(
        type("M", (), {"config": cfg, "name": "t"})(), slots=True,
        paged_blocks=blocks, page_tokens=BT, page_table_blocks=table)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda r: module.init(
            r, jnp.zeros((rows, 1), jnp.int32), train=False),
            jax.random.PRNGKey(0))["cache"])
    return module, cache


def test_paged_prefill_then_decode_matches_the_reference_logits(rng):
    """Chunked prefill through the paged pool, then decode steps, two rows
    side by side at different depths: every position's logits are the
    reference's full forward pass, across the window (8) and block (4)
    edges; chunks of 5 equal one shot."""
    sizes = _sizes()
    cfg, params, w = _f32_model(sizes)
    seqs = [rng.integers(1, VOCAB, size=n) for n in (37, 22)]
    prompt_len = (20, 10)
    want = [np.asarray(REF.forward(w, np.pad(s, (0, 40 - len(s))), sizes,
                                   "f32", block=8)) for s in seqs]
    sentinel = 40
    tables = np.full((2, 16), sentinel, np.int32)
    tables[0, :10] = np.arange(10) + 3           # any blocks, in any order
    tables[1, :6] = [20, 14, 31, 15, 29, 17]

    def run(chunk):
        module, cache = _paged(cfg)
        apply = jax.jit(lambda cache, ids, positions, tables: module.apply(
            {"params": params, "cache": cache}, ids, train=False,
            mutable=["cache", "counters"], positions=positions,
            block_tables=tables))
        got = [[], []]
        for row in (0, 1):  # prefill, a row at a time, in chunks
            for lo in range(0, prompt_len[row], chunk):
                ids = seqs[row][lo:min(lo + chunk, prompt_len[row])]
                logits, mut = apply(cache, jnp.asarray(ids)[None],
                                    jnp.asarray([lo], jnp.int32),
                                    jnp.asarray(tables[row])[None])
                assert "counters" not in mut  # a chunk counts nothing
                cache = mut["cache"]
                got[row].append(np.asarray(logits[0]))
        pos = np.asarray(prompt_len, np.int32)
        while (pos < [len(s) for s in seqs]).any():  # decode, rows together
            live = pos < [len(s) for s in seqs]
            ids = [seqs[r][pos[r]] if live[r] else 0 for r in (0, 1)]
            masked = np.where(live[:, None], tables, sentinel)
            logits, mut = apply(cache, jnp.asarray(ids, jnp.int32)[:, None],
                                jnp.asarray(pos), jnp.asarray(masked))
            cache = mut["cache"]
            counts = dict(zip(cohere2_moe.TICK_COUNTERS,
                              np.asarray(mut["counters"]["tick"][0])))
            assert counts["moe_pairs"] == 4 * 2 * live.sum()
            assert counts["moe_pairs_held"] == counts["moe_pairs"]  # all held
            assert counts["kv_positions_resident"] == 3 * (pos[live] + 1).sum()
            # the blocks the decode read copied: those that cover what a
            # row sees, 0..pos (full) or pos - WINDOW + 1..pos (window)
            seen = pos // BT - np.clip((pos - WINDOW + 1) // BT, 0, None) + 1
            assert counts["kv_window_positions_read"] == 3 * BT * seen[live].sum()
            assert counts["kv_blocks_read"] == (
                3 * seen[live].sum() + (pos[live] // BT + 1).sum())
            for r in (0, 1):
                if live[r]:
                    got[r].append(np.asarray(logits[r]))
            pos = pos + live
        return [np.concatenate(g) for g in got]

    chunked, one_shot = run(5), run(20)
    for r in (0, 1):
        n = len(seqs[r])
        assert np.abs(chunked[r] - want[r][:n]).max() < TOL
        assert np.abs(chunked[r] - one_shot[r]).max() < TOL


def test_engine_serves_it_through_the_paged_pool_and_counts_its_ticks(rng):
    """The same engine, scheduler, pool and chunked prefill as gpt_small's:
    greedy tokens of prompts that cross the window and block edges lie on
    the reference's best logit (float32: to rounding), the decode step
    compiles once with the auditor armed, and the tick's counters arrive
    with the harvest."""
    from distkeras_tpu.telemetry import RecompileAuditor

    sizes = _sizes(num_experts=4, first_expert=2)  # a share: experts 2-5
    model = cohere2_moe.command_a_plus_tiny(
        seq_len=64, vocab_size=VOCAB, held_experts=(2, 4), dtype="float32")
    _, params, w = _f32_model(sizes)
    auditor = RecompileAuditor()
    engine = ServingEngine(model, {"params": params}, slots=3, max_queue=8,
                           kv_pool_blocks=64, kv_block_tokens=BT,
                           prefill_chunk=8, auditor=auditor,
                           arm_auditor_after_warmup=True)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (5, 19, 33, 11)]

    async def work():
        task = asyncio.create_task(engine.run())
        try:
            reqs = []
            for p in prompts:
                reqs.append(engine.submit(p, 12))
                await asyncio.sleep(0.01)
            return [await r.result() for r in reqs]
        finally:
            engine.shutdown(drain=True)
            await task

    served = asyncio.run(work())
    for prompt, tokens in zip(prompts, served):
        logits = REF.position_logits(w, sizes, prompt, tokens, "f32", 64, block=8)
        assert REF.gaps_below_best(logits, tokens).max() < TOL
    assert auditor.compiles("serving_decode") == 1
    counters = {k: v["value"] for k, v in
                engine.metrics.registry.snapshot().items() if "value" in v}
    ticks = counters["serving_counted_ticks_total"]
    assert ticks >= 12
    assert counters["moe_pairs_total"] >= 4 * 2 * ticks
    assert 0 < counters["moe_pairs_held_total"] < counters["moe_pairs_total"]
    assert counters["moe_experts_touched_total"] <= 4 * 4 * ticks
    read = counters["kv_window_positions_read_total"]
    assert read > 0 and read % BT == 0
    # a window layer reads the blocks that cover what a row sees: at most
    # window / block + 1 = 3 blocks of 4 a row; a full layer all a row holds
    row_ticks = counters["moe_pairs_total"] // (4 * 2)
    assert read <= 3 * 3 * BT * row_ticks
    full = counters["kv_blocks_read_total"] * BT - read
    assert 3 * full >= counters["kv_positions_resident_total"]


def test_gpt_tiny_ticks_carry_no_counters(rng):
    from distkeras_tpu.models.bert import gpt_tiny

    model = gpt_tiny(seq_len=32, vocab_size=64)
    engine = ServingEngine(model, model.init(0), slots=2, kv_pool_blocks=16,
                           kv_block_tokens=4)
    assert engine._tick_counters == ()

    async def work():
        task = asyncio.create_task(engine.run())
        try:
            return await engine.submit([1, 2, 3], 4).result()
        finally:
            engine.shutdown(drain=True)
            await task

    assert len(asyncio.run(work())) == 4
    names = engine.metrics.registry.snapshot()
    assert not [k for k in names if "moe_" in k or "counted_ticks" in k]


# ------------------------------------------------------ (b) the shares add up

@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(rng, shares):
    """A layer cut by heads and by experts over 2 or 4 chips: the parts the
    shares give (each its heads' rows of Wo, its held experts' terms), with
    the shared experts counted once, sum to the uncut reference's layer."""
    whole = _sizes(num_key_value_heads=4, layer_types=["sliding_attention"])
    w = REF.make_weights(whole, 5)
    ids = rng.integers(1, VOCAB, size=24)
    x0 = np.asarray(w["token_embed"].astype(jnp.float32))[ids]
    for layer_type in ("sliding_attention", "full_attention"):
        whole["layer_types"] = [layer_type]
        uncut = np.asarray(REF.hidden_states(w, whole, ids, "f32", block=8)) - x0
        hq, hkv, ex = 8 // shares, 4 // shares, 8 // shares
        total = np.zeros_like(uncut)
        for c in range(shares):
            cut = {**whole, "num_attention_heads": hq, "num_key_value_heads": hkv,
                   "num_experts": ex, "first_expert": c * ex}
            part = dict(w)
            p = "layer_0/"
            for name, heads in (("query", hq), ("key", hkv), ("value", hkv)):
                cols = slice(c * heads * 16, (c + 1) * heads * 16)
                part[p + f"attention/{name}/kernel"] = w[
                    p + f"attention/{name}/kernel"][:, cols]
            part[p + "attention/out/kernel"] = w[p + "attention/out/kernel"][
                c * hq * 16:(c + 1) * hq * 16]
            for name in ("gate", "up", "down"):
                part[p + "experts/" + name] = w[p + "experts/" + name][
                    c * ex:(c + 1) * ex]
            assert {k: v.shape for k, v in part.items()} == {
                k: tuple(s) for k, s in REF.weight_spec(cut).items()}
            cfg = dataclasses.replace(PROGRAM.program_config(cut),
                                      dtype=jnp.float32)
            params = jax.tree.map(lambda a: a.astype(jnp.float32),
                                  PROGRAM._nested(part))
            hidden = Cohere2Moe(cfg).apply({"params": params},
                                           jnp.asarray(ids)[None],
                                           return_hidden=True)
            total += np.asarray(hidden[0]) - x0
            # the reference given the same share says the same
            assert np.abs(np.asarray(REF.hidden_states(
                part, cut, ids, "f32", block=8)) - np.asarray(hidden[0])).max() < TOL
        # every share computed the shared experts: count them once
        n = REF._layer_norm(jnp.asarray(x0), w["layer_0/ln_scale"], 1e-5)
        shared = np.asarray(REF._ffn(w, "layer_0/", n, {**whole, "num_experts": 0},
                                     "f32"))
        assert np.abs(total - (shares - 1) * shared - uncut).max() < TOL


# ------------------------------------------- (c) dropless, however lopsided

def _dense_experts(x, wg, wu, wd, ids, weights, held):
    """Every held expert on every token, weighted by the router (0 where
    the token did not choose it): the plain form."""
    out = np.zeros(x.shape, np.float32)
    for e in range(held[1]):
        f = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        weight = jnp.sum(jnp.where(ids == held[0] + e, weights, 0.0), axis=-1)
        out += np.asarray(weight[:, None] * f)
    return out


@pytest.mark.parametrize("routing", ["even", "all_on_one_held", "none_held"])
def test_dropless_experts_under_any_routing(rng, routing):
    """300 tokens x 2 choices over a share of experts 2-4 of 8: with a
    router rigged so every token picks held expert 3 (three steps of 128
    rows for one expert) nothing is dropped; with one rigged so nobody
    picks a held expert nothing is computed and nothing added."""
    N, H, F, held = 300, 32, 48, (2, 3)
    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (N, H))
    wg, wu = (jax.random.normal(jax.random.fold_in(k, i), (3, H, F)) * H ** -0.5
              for i in (1, 2))
    wd = jax.random.normal(jax.random.fold_in(k, 3), (3, F, H)) * F ** -0.5
    logits = jax.random.normal(jax.random.fold_in(k, 4), (N, 8))
    if routing == "all_on_one_held":
        logits = logits.at[:, 3].add(20.0)
    elif routing == "none_held":
        logits = logits.at[:, 2:5].add(-20.0)
    ids, weights = moe.sigmoid_topk_route(logits, 2)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    y, stats = jax.jit(lambda *a: moe.dropless_experts(*a, held=held))(
        x, wg, wu, wd, ids, weights)
    assert np.abs(np.asarray(y) - _dense_experts(x, wg, wu, wd, ids, weights,
                                                 held)).max() < 1e-4
    stats = {name: int(v) for name, v in stats.items()}
    assert stats["pairs"] == 2 * N
    if routing == "all_on_one_held":
        assert stats["expert_rows_max"] == N and stats["pairs_held"] >= N
    elif routing == "none_held":
        assert stats["pairs_held"] == stats["experts_touched"] == 0
        assert not np.asarray(y).any()
    else:
        assert stats["experts_touched"] == 3
    # rows that do not count (a free decode slot) reach no expert
    live = jnp.arange(N) % 3 == 0
    y_live, stats_live = moe.dropless_experts(x, wg, wu, wd, ids, weights, held,
                                              live=live)
    assert not np.asarray(y_live)[~np.asarray(live)].any()
    assert np.abs(np.asarray(y_live)[np.asarray(live)]
                  - np.asarray(y)[np.asarray(live)]).max() < 1e-4
    assert int(stats_live["pairs"]) == 2 * int(live.sum())


def test_a_rigged_router_through_the_model(rng):
    """The same through the whole model: a router of zeros scores every
    expert alike, so every token of every layer picks experts 0 and 1 (ties
    go to the lowest index, in the program as in the reference). A share
    that holds experts 0-3 gets every pair on two of its experts; a share
    that holds 4-7 gets none, and adds the shared experts alone."""
    ids = rng.integers(1, VOCAB, size=16)
    out = {}
    for first in (0, 4):
        cut = _sizes(num_experts=4, first_expert=first)
        cfg, _, w = _f32_model(cut)
        for i in range(4):
            w[f"layer_{i}/router/kernel"] = jnp.zeros((64, 8), jnp.bfloat16)
        params = jax.tree.map(lambda a: a.astype(jnp.float32), PROGRAM._nested(w))
        got = Cohere2Moe(cfg).apply({"params": params}, jnp.asarray(ids)[None])
        want = REF.forward(w, ids, cut, "f32", block=8)
        assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < TOL
        out[first] = np.asarray(got[0])
    assert np.abs(out[0] - out[4]).max() > 100 * TOL  # the held pairs count


# --------------------------------------------- (e) what a decode module is

def test_decode_module_takes_both_blocks_and_refuses_the_rest():
    from distkeras_tpu.models.bert import Bert, bert_tiny_mlm, gpt_tiny
    from distkeras_tpu.models.mlp import mnist_mlp

    module, cfg = _decode_module(gpt_tiny(seq_len=16), slots=True,
                                 paged_blocks=8, page_tokens=4,
                                 page_table_blocks=4)
    assert isinstance(module, Bert) and cfg.decode and cfg.decode_slots
    assert cfg.kv_token_bytes == 2 * 2 * 128 * 2  # 2 layers, K and V, bf16
    assert set(cfg.tp_split_sizes) == {"num_heads", "mlp_dim", "vocab_size"}
    module, cfg = _decode_module(cohere2_moe.command_a_plus_tiny(), slots=True,
                                 paged_blocks=8, page_tokens=4,
                                 page_table_blocks=4)
    assert isinstance(module, Cohere2Moe) and cfg.decode and cfg.paged_blocks == 8
    assert cfg.kv_token_bytes == 4 * 2 * 2 * 16 * 2  # 4 layers, 2 K/V heads of 16
    assert cfg.num_layers == 4 and cfg.vocab_size == 256
    real = cohere2_moe.command_a_plus_tp8ep8().config
    assert real.kv_token_bytes == 2048 and real.max_seq_len == 16384
    with pytest.raises(ValueError, match="causal"):
        _decode_module(bert_tiny_mlm(seq_len=16))
    with pytest.raises(ValueError, match="decode module"):
        _decode_module(mnist_mlp())
    with pytest.raises(ValueError, match="decode module"):
        _decode_module(object())
    # no dense cache for this block (the offline path's, the speculative
    # draft's): a typed refusal, not a shape error
    model = cohere2_moe.command_a_plus_tiny()
    with pytest.raises(ValueError, match="paged"):
        generate(model, model.init(0), np.asarray([[1, 2, 3]], np.int32), 2,
                 greedy=True)
    with pytest.raises(ValueError, match="held_experts"):
        Cohere2MoeConfig(num_experts=8, held_experts=(6, 4))
    with pytest.raises(ValueError, match="layer type"):
        Cohere2MoeConfig(layer_types=("chunked_attention",))


def test_the_zoo_serves_the_new_entries_by_name():
    from distkeras_tpu import run

    model = run.load_model("command_a_plus_tiny", {"held_experts": [2, 2]})
    assert model.config.held_experts == (2, 2)
    assert model.count_params() > 0 and model.output_dim == 256
    big = run.load_model("command_a_plus_tp8ep8", {})
    assert round(big.count_params() / 1e9, 2) == 4.23  # shapes only
    assert big.config.layer_types.count("sliding_attention") == 3


def test_a_request_line_longer_than_64_kb_gets_an_answer():
    """A 14 k-token prompt is ~100 KB of JSON on the line protocol: the
    server reads it (and here, where the model's context is 32, answers
    with the typed refusal) instead of dropping the connection."""
    from distkeras_tpu.models.bert import gpt_tiny
    from distkeras_tpu.serving import ServingClient, ServingServer
    from distkeras_tpu.serving.scheduler import ServingError

    model = gpt_tiny(seq_len=32, vocab_size=64)

    async def go():
        engine = ServingEngine(model, model.init(0), slots=1)
        server = ServingServer(engine, port=0, wire_mode="jsonl")
        await server.start()
        try:
            async with ServingClient("127.0.0.1", server.port,
                                     max_retries=0) as c:
                with pytest.raises(ServingError) as err:
                    await c.generate([63] * 14336, 4)
                assert not isinstance(err.value, ConnectionError)
                return await c.generate([1, 2, 3], 2)  # and still serves
        finally:
            await server.stop(drain=True)

    assert len(asyncio.run(go())["tokens"]) == 2
