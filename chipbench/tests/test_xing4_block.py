"""The ``xing4_0`` block's benchmark files, on the CPU, at tiny sizes: the
configuration with its published numbers and named cuts, the counts of ``model_math.py``
against the reference's ``weight_spec`` and the program's own tree, the
control, the two new readers on synthetic inputs, and one whole serving
run with shared prefixes through the harness in a temp checkout."""

import json
import os
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cell as cell_mod  # noqa: E402

CELL = "xing4-29b-a4b-pp.doc_qa_closed"
CODE = cell_mod.ModelCode({"model": {"code": "chipbench/models/xing4_block"}},
                          ROOT)
TINY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
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
        "max_seq_len": 128}
CATALOG = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl, if any


def _config():
    with open(os.path.join(BENCH, "configs", "xing4-29b-a4b-pp.json")) as f:
        return json.load(f)


def _read(name, ctx):
    module = cell_mod._load_file(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "t_" + name)
    return module.read(ctx)


def test_the_configuration_holds_the_published_numbers_and_names_its_cuts():
    config = _config()
    cuts = {"num_hidden_layers": (5, 40), "first_k_dense_replace": (1, 2),
            "num_nextn_predict_layers": (0, 1)}
    assert sorted(config["reduced"]) == sorted(cuts)
    for key, (held, published) in cuts.items():
        assert config[key] == held and config["published"][key] == published
    sizes = config["model"]["config"]
    for key in sizes:  # as it is run: the sizes the reference and program get
        if key in config:
            assert sizes[key] == config[key], key
    assert sizes["num_experts"] == sizes["n_routed_experts"] == 64  # all held
    assert config["assumed"] and config["deployment"]
    if os.path.isfile(CATALOG):  # every key of the catalog's entry, unchanged
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == config["source"])
        for key, value in row["config"].items():
            assert key in config, key
            assert config[key] == value or key in config["reduced"], key
    cell = cell_mod.load_cell(CELL)
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "itl_p99_ms", "setup_s"}
    layers = set(dict(cell.per_layer))
    assert {"latent_attention_roofline", "mfu.serve.cached",
            "decode_roofline.layered", "moe_expert_load_max_over_mean"} <= layers
    assert not {"mfu.serve", "decode_roofline", "moe_held_pair_share",
                "kv_window_read_share"} & layers
    assert "served_logit_gap" in cell.limits
    # the longest request fits the context the server is given
    tr = cell.traffic
    longest = (tr["shared_prefix"]["tokens"] + tr["prompt_len"]["max"]
               + tr["output_len"]["max"])
    assert longest <= sizes["max_seq_len"]


def test_the_counts_agree_with_the_trees_and_the_hand_arithmetic():
    import jax
    import numpy as np

    math = CODE.model_math
    for sizes in (TINY, _config()["model"]["config"]):
        spec = CODE.reference.weight_spec(sizes)  # shapes only
        total = sum(int(np.prod(shape, dtype=np.int64)) for shape in spec.values())
        assert total == math.param_count(sizes)
    cut = _config()["model"]["config"]
    program = CODE.program.program_config(cut)
    from distkeras_tpu.models.xing4 import Xing4

    tree = jax.eval_shape(Xing4(program).init, jax.random.PRNGKey(0),
                          jax.numpy.zeros((1, 8), jax.numpy.int32))["params"]
    assert {k: tuple(v.shape) for k, v in CODE.program.flat(tree).items()} == \
        CODE.reference.weight_spec(cut)
    # the expected counts: 4.048 G; 128.2 M a dense layer, 745.0 M an
    # expert layer, 939.5 M of embedding and head; 5,760 B a token
    assert round(math.param_count(cut) / 1e9, 3) == 4.048
    assert math.kv_bytes_per_token(cut, 2) == 5760
    assert round(math.routed_params(cut) / 4 / 1e6, 1) == 704.6
    assert math.routed_experts_per_token(cut) == 4.0
    assert math.latent_read_bytes(cut, 2, [10, 20]) == 5760 * 30
    m = TINY
    per_key = 2 * 4 * 3 * (16 + 8 + 16)
    assert math.attention_flops_per_key(m) == per_key
    assert math.decode_flops(m, 20) == (math.layer_matmul_flops_per_token(m)
                                        + 20 * per_key + 2 * 64 * 256)
    keys = sum(p + 1 for p in range(3, 30))
    assert math.prefill_flops(m, 3, 30) == (
        27 * math.layer_matmul_flops_per_token(m) + keys * per_key + 2 * 64 * 256)
    fixed = math.param_count(m) - 2 * 8 * 3 * 64 * 32
    assert math.decode_min_bytes(m, 2, 2, 10, 25, [5, 20]) == (
        10 * fixed * 2 + 25 * 3 * 64 * 32 * 2 + 3 * 40 * 2 * 25)


def test_control_in_fp8_comes_out_not_correct_and_bf16_passes():
    """At the tiny size, over one sequence: the mean gap (over 64
    positions) by which the token a lower precision puts first lies below
    the reference's best. bfloat16 stays under the limit the tiny
    rehearsal uses; float8_e4m3 does not."""
    import numpy as np

    ref = CODE.reference
    w = ref.make_weights(TINY, 2**31 + 3)
    rng = np.random.default_rng(3)
    prompt, served = (rng.integers(1, 256, size=n).tolist() for n in (40, 64))
    exact = ref.position_logits(w, TINY, prompt, served, "f32", 128, block=16)
    assert exact.shape == (64, 256)
    gaps = {}
    for kind in ("bf16", "fp8"):
        first = ref.position_logits(w, TINY, prompt, served, kind, 128,
                                    block=16).argmax(-1)
        gaps[kind] = float(ref.gaps_below_best(exact, first).max())
    assert gaps["bf16"] <= SERVE_LIMIT < gaps["fp8"], gaps
    # blocks are an implementation detail of the reference
    other = ref.position_logits(w, TINY, prompt, served, "f32", 128, block=32)
    assert float(abs(exact - other).max()) < 1e-4


def _trace(ops):
    """A reduced trace: one decode program holding ``ops`` (name ->
    seconds), and a prefill program."""
    return {"window_s": 1.0, "devices": [{
        "ops": dict(ops, **{"fusion.9 = bf16[1,512,3584]": 0.2}),
        "programs": {
            "jit_serving_decode(7)": {"count": 30, "total_s": 0.9,
                                      "starts_s": [0.03 * i for i in range(30)],
                                      "ops": set(ops)},
            "jit_serving_prefill(9)": {"count": 2, "total_s": 0.05,
                                       "starts_s": [0.0, 0.5],
                                       "ops": {"fusion.9 = bf16[1,512,3584]"}}}}]}


def test_the_new_readers_on_synthetic_inputs():
    config = _config()
    config["latent_read_ops"] = {"pattern": r"^while\.7 "}
    sizes, math = config["model"]["config"], CODE.model_math
    events = [(5.0 + 0.001 * i, 33000 + i) for i in range(960)] + [(9.0, 100)]
    ops = {"while.7 = (f32[32,1,32,512])": 0.45, "fusion.3 = bf16[32,3584]": 0.2}
    ctx = {"counters": {"metricsz_delta": {}}, "config": config,
           "trace": _trace(ops), "window_s": 30.0,
           "spans": {"trace_t0": 5.0, "token_events": events},
           "peaks": {"hbm_bytes_per_s": 819e9,
                     "flops_per_s": {"bfloat16": 197e12}}, "math": math}
    want = math.latent_read_bytes(sizes, 2, [c for _, c in events[:960]])
    assert _read("latent_attention_roofline", ctx) == pytest.approx(
        100 * want / 819e9 / 0.45)
    assert 0 < _read("latent_attention_roofline", ctx) < 100
    # no prefill counters (the parent commit): nothing to read, no error
    assert _read("mfu.serve.cached", ctx) is None
    delta = {"serving_prefill_tokens_total": 40000,
             "serving_prefill_key_positions_total": 40000 * 33000}
    ctx["counters"] = {"metricsz_delta": delta, "first_token_prompts": [33000] * 150}
    flops = (sum(math.decode_flops(sizes, c) for _, c in events)
             + math.counted_prefill_flops(sizes, 40000, 40000 * 33000)
             + 150 * math.head_flops_per_token(sizes))
    assert _read("mfu.serve.cached", ctx) == pytest.approx(
        100 * flops / 30.0 / 197e12)
    # a configuration that names no latent operations, or a model without
    # the count: nothing to read
    bare = dict(config)
    del bare["latent_read_ops"]
    assert _read("latent_attention_roofline", dict(ctx, config=bare)) is None
    cohere = cell_mod.ModelCode(
        {"model": {"code": "chipbench/models/cohere2_moe_block"}}, ROOT)
    assert _read("latent_attention_roofline",
                 dict(ctx, math=cohere.model_math)) is None
    assert _read("mfu.serve.cached", dict(ctx, math=cohere.model_math)) is None
    assert _read("latent_attention_roofline", dict(ctx, trace=None)) is None


# ---------------------------------------------------------------- rehearsal

# The compared gap is a mean over 64 served positions; at this size the
# reference in bfloat16 (streams' mixing rounded too) reads 0.014 on the
# control test's sequence, in float8 0.23.
SERVE_LIMIT = 0.05
SERVE_CONFIG = {
    "name": "tiny", "entry": "serve", "dtype": "bfloat16", "kv_dtype_bytes": 2,
    "min_prefill_bucket": 16,
    "model": {"code": "mybench/models/my_block", "seq_len": 128, "config": TINY},
    "serve_flags": ["--paged", "--kv-block-tokens", "4", "--slots", "4",
                    "--kv-pool-mb", "1", "--max-context", "128",
                    "--max-queue", "64", "--audit-recompiles", "report",
                    "--prefill-chunk", "16", "--min-prefill-bucket", "16"],
    "decode_program": {"name": "serving_decode$"},
    "latent_read_ops": {"pattern": "while"}}
SERVE_MIX = {"kind": "closed", "clients": 4, "requests": 600, "shape_seed": 1,
             "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                            "min": 4, "max": 32},
             "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                            "min": 4, "max": 32},
             "shared_prefix": {"count": 2, "tokens": 40, "zipf_exponent": 0},
             "check_requests": 3, "trace_offset_s": 0.2, "trace_seconds": 0.5,
             "limits": {"served_logit_gap": SERVE_LIMIT}}


def _checkout(tmp_path):
    """A checkout-shaped temp dir whose benchmark holds this block's code
    and the accepted readers, all as copies."""
    root = tmp_path / "checkout"
    bench = root / "mybench"
    for part in ("configs", "traffic"):
        (bench / part).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    shutil.copytree(os.path.join(BENCH, "models", "xing4_block"),
                    bench / "models" / "my_block",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "distkeras_tpu"), root / "distkeras_tpu")
    os.symlink(os.path.join(ROOT, "chipbench"), root / "chipbench")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["device_kinds"]["cpu"] = {"flops_per_s": {"bfloat16": 1e12},
                                    "hbm_bytes_per_s": 1e11}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    (bench / "configs" / "tiny.json").write_text(json.dumps(SERVE_CONFIG))
    (bench / "traffic" / "mix.json").write_text(json.dumps(SERVE_MIX))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    layers = [{"name": f[:-3], "moves": "serve_tokens_per_s",
               "workloads": ["tiny.mix"]}
              for f in sorted(os.listdir(bench / "layer_metrics"))
              if f.endswith(".py")]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "mybench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny", "traffic": "mix",
                       "chips": 1}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]
                       if m["name"] in ("serve_tokens_per_s", "itl_p99_ms",
                                        "setup_s")],
        "per_layer": layers}))
    return str(root)


def test_serve_rehearsal_of_the_block_at_a_tiny_size(tmp_path):
    """One whole serving run on the CPU through the harness: the server's
    child (``run serve`` with the seed's weights), shared prefixes served
    from latent blocks, chunked prefill, absorbed decode, the counters over
    metricsz, the reference's check of what was served."""
    from harness import main

    line = main.run_cell("tiny.mix", 2**31 + 29, 2.0, True, time.perf_counter(),
                         root=_checkout(tmp_path), require_platform=None)
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["correct"] is True, line["compared"]
    metrics = line["metrics"]
    assert metrics["compiles_in_window.serve"]["value"] == 0
    assert metrics["prefix_hit_share"]["value"] > 50
    assert metrics["moe_held_pair_share"]["value"] == 100  # all 8 held
    assert 1 <= metrics["moe_expert_load_max_over_mean"]["value"] <= 8
    assert metrics["mfu.serve.cached"]["value"] > 0
    assert "mfu.serve" not in metrics  # prefix hits: it reads nothing
    # no device plane in a CPU trace: the trace's readers return nothing
    assert "latent_attention_roofline" not in metrics
    assert "decode_roofline.layered" not in metrics
