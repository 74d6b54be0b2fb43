"""The ``cohere2_moe`` block's benchmark files, on the CPU, at tiny sizes:
the configuration as the contract wants it, the counts of
``model_math.py`` against the reference's ``weight_spec`` (shapes only at
the cut sizes), the control, the four readers on recorded counters, and one
whole serving run through the harness in a temp checkout."""

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

CELL = "command-a-plus-tp8ep8.mixed_len_closed"
CODE = cell_mod.ModelCode(
    {"model": {"code": "chipbench/models/cohere2_moe_block"}}, ROOT)
TINY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
        "num_experts": 4, "first_expert": 0, "num_router_outputs": 8,
        "num_experts_per_tok": 2, "num_shared_experts": 2,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "sliding_window": 8, "rope_theta": 50000, "layer_norm_eps": 1e-5,
        "logit_scale": 1, "max_seq_len": 128}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(BENCH, "configs", "command-a-plus-tp8ep8.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_published_numbers_and_names_its_cuts():
    config = _config()
    cuts = {"num_experts": (16, 128), "num_attention_heads": (16, 128),
            "num_key_value_heads": (1, 8), "vocab_size": (32768, 262144),
            "num_hidden_layers": (4, 32)}
    assert sorted(config["reduced"]) == sorted(cuts)
    for key, (held, published) in cuts.items():
        assert config[key] == held and config["published"][key] == published
    # no width is cut
    for key, value in {"hidden_size": 4096, "head_dim": 128,
                       "intermediate_size": 4096, "num_experts_per_tok": 8,
                       "num_shared_experts": 4, "sliding_window": 4096,
                       "rope_theta": 50000}.items():
        assert config[key] == value
    # as it is run: the sizes the reference and the program are given
    sizes = config["model"]["config"]
    for key in sizes:
        if key in config and key != "layer_types":
            assert sizes[key] == config[key], key
    assert sizes["layer_types"] == config["layer_types"][:config["num_hidden_layers"]]
    assert sizes["num_router_outputs"] == config["published"]["num_experts"]
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
    assert "decode_roofline" not in dict(cell.per_layer)
    assert {"decode_roofline.layered", "moe_held_pair_share",
            "moe_expert_load_max_over_mean", "kv_window_read_share",
            "mfu.serve"} <= set(dict(cell.per_layer))
    assert "served_logit_gap" in cell.limits


def test_weight_spec_sums_to_the_counts_of_model_math_at_the_cut_sizes():
    import numpy as np

    math = CODE.model_math
    for sizes in (TINY, _config()["model"]["config"]):
        spec = CODE.reference.weight_spec(sizes)  # shapes only
        total = sum(int(np.prod(shape, dtype=np.int64)) for shape in spec.values())
        assert total == math.param_count(sizes)
    cut = _config()["model"]["config"]
    assert round(math.param_count(cut) / 1e9, 2) == 4.23
    assert math.param_count(cut) * 2 == math.decode_weight_bytes(cut, 2)
    # the ISSUE's arithmetic: 1,025 M a layer, 134 M of embedding
    layer = (math.param_count(cut) - 32768 * 4096 - 4096) / 4
    assert round(layer / 1e6) == 1025
    assert math.routed_experts_per_token(cut) == 1.0
    assert math.kv_bytes_per_token(cut, 2) == 2048


def test_operations_and_bytes_agree_with_hand_counts():
    math = CODE.model_math
    m = TINY
    h, d, f, hq, hkv = 64, 16, 96, 8, 2
    per_token = 4 * 2 * (h * d * (2 * hq + 2 * hkv) + h * 8
                         + (2 + 2 * 4 / 8) * 3 * h * f)
    assert math.layer_matmul_flops_per_token(m) == per_token
    # a query at context 20: the full layer sees 20 keys, the three window
    # layers 8 each
    assert math.attention_flops(m, 20) == 4 * hq * d * (20 + 3 * 8)
    assert math.attention_flops(m, 5) == 4 * hq * d * 4 * 5
    keys = sum(p + 1 + 3 * min(p + 1, 8) for p in range(3, 30))
    assert math.prefill_flops(m, 3, 30) == (
        27 * per_token + 4 * hq * d * keys + 2 * h * 256)
    assert math.decode_flops(m, 20) == (
        per_token + math.attention_flops(m, 20) + 2 * h * 256)
    fixed = math.param_count(m) - 4 * 4 * 3 * h * f
    assert math.decode_min_bytes(m, 2, 2, 10, 25, [5, 20]) == (
        10 * fixed * 2 + 25 * 3 * h * f * 2
        + 2 * hkv * d * 2 * (4 * 5 + 20 + 3 * 8))


def test_control_in_fp8_comes_out_not_correct_and_bf16_passes():
    """At the tiny size, over one sequence that crosses the window: the gap
    by which the token a lower precision puts first lies below the
    reference's best. bfloat16 (what the configuration states) stays far
    under the limit the tiny rehearsal uses; float8_e4m3 does not."""
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
    # the compared gap is a mean over 64 positions; one foreign token shows
    assert len(ref.gaps_below_best(exact, served)) == 1
    best = np.asarray(exact.argmax(-1))
    assert ref.gaps_below_best(exact, best).max() == 0
    foreign = best.copy()
    foreign[7] = int(exact[7].argmin())
    assert ref.gaps_below_best(exact, foreign)[0] == pytest.approx(
        float(exact[7].max() - exact[7].min()) / 64)
    # blocks are an implementation detail of the reference
    other = ref.position_logits(w, TINY, prompt, served, "f32", 128, block=32)
    assert float(abs(exact - other).max()) < 1e-5


def test_the_new_readers_on_recorded_counters():
    def read(name, ctx):
        module = cell_mod._load_file(
            os.path.join(BENCH, "layer_metrics", name + ".py"), "t_" + name)
        return module.read(ctx)

    delta = {"moe_pairs_total": 4 * 64 * 8 * 100, "moe_pairs_held_total": 26000,
             "moe_experts_touched_total": 6300, "serving_counted_ticks_total": 100,
             "moe_expert_tokens_max_total": 4000,
             "kv_window_positions_read_total": 3 * 64 * 4112 * 100,
             "kv_positions_resident_total": 3 * 64 * 3000 * 100}
    config = _config()
    ctx = {"counters": {"metricsz_delta": delta}, "config": config}
    assert read("moe_held_pair_share", ctx) == pytest.approx(12.695, abs=1e-3)
    assert read("moe_expert_load_max_over_mean", ctx) == pytest.approx(
        4000 / (26000 / 16))
    assert read("kv_window_read_share", ctx) == pytest.approx(100 * 4112 / 3000)
    # a program without these counters (the parent commit, another model):
    # nothing to read, and no error
    bare = {"counters": {"metricsz_delta": {"serving_slot_ticks_total": 9}},
            "config": config, "trace": None, "spans": {}, "math": CODE.model_math}
    for name in ("moe_held_pair_share", "moe_expert_load_max_over_mean",
                 "kv_window_read_share", "decode_roofline.layered"):
        assert read(name, bare) is None
    # the roofline: 10 traced steps of 15 ms, 63 experts touched a step
    sizes, math = config["model"]["config"], CODE.model_math
    trace = {"window_s": 1.0, "devices": [{"programs": {
        "jit_serving_decode(7)": {"count": 10, "total_s": 0.15,
                                  "starts_s": [0.1 * i for i in range(10)],
                                  "ops": set()},
        "jit_serving_prefill(9)": {"count": 5, "total_s": 0.1,
                                   "starts_s": [0.0] * 5, "ops": set()}}}]}
    events = [(5.0 + 0.001 * i, 3000 + i) for i in range(640)] + [(9.0, 100)]
    ctx = {"counters": {"metricsz_delta": delta}, "config": config,
           "trace": trace, "spans": {"trace_t0": 5.0, "token_events": events},
           "peaks": {"hbm_bytes_per_s": 819e9}, "math": math}
    want = math.decode_min_bytes(sizes, 2, 2, 10, 630, [c for _, c in events[:640]])
    assert read("decode_roofline.layered", ctx) == pytest.approx(
        100 * want / 819e9 / 0.15)
    assert 50 < read("decode_roofline.layered", ctx) < 100


# ---------------------------------------------------------------- rehearsal

# The compared gap is a mean over 64 served positions. Over six seeds on the
# CPU, 64 served tokens, the tiny reference in bfloat16 reads 0-0.0010 (a
# router near-tie between bfloat16 and float32 flips one token's experts)
# and in float8 0.0036-0.015.
SERVE_LIMIT = 0.002
SERVE_CONFIG = {
    "name": "tiny", "entry": "serve", "dtype": "bfloat16", "kv_dtype_bytes": 2,
    "min_prefill_bucket": 16,
    "model": {"code": "mybench/models/my_block", "seq_len": 128, "config": TINY},
    "serve_flags": ["--paged", "--kv-block-tokens", "4", "--slots", "4",
                    "--kv-pool-mb", "1", "--max-context", "128",
                    "--max-queue", "64", "--audit-recompiles", "report",
                    "--prefill-chunk", "16"],
    "decode_program": {"name": "serving_decode$"}}
SERVE_MIX = {"kind": "closed", "clients": 4, "requests": 600, "shape_seed": 1,
             "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                            "min": 16, "max": 64},
             "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                            "min": 4, "max": 32},
             "shared_prefix": None, "check_requests": 3,
             "trace_offset_s": 0.2, "trace_seconds": 0.5,
             "limits": {"served_logit_gap": SERVE_LIMIT}}


def _checkout(tmp_path):
    """A checkout-shaped temp dir whose benchmark holds this block's code
    and the accepted readers, all as copies."""
    root = tmp_path / "checkout"
    bench = root / "mybench"
    for part in ("configs", "traffic"):
        (bench / part).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    shutil.copytree(os.path.join(BENCH, "models", "cohere2_moe_block"),
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
    child, chunked prefill, paged decode, the counters over metricsz, the
    reference's check of what was served."""
    from harness import main

    line = main.run_cell("tiny.mix", 2**31 + 29, 2.0, True, time.perf_counter(),
                         root=_checkout(tmp_path), require_platform=None)
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["correct"] is True, line["compared"]
    metrics = line["metrics"]
    assert metrics["compiles_in_window.serve"]["value"] == 0
    # 4 of 8 experts held, 2 a token: half the pairs where routing is even
    assert 30 < metrics["moe_held_pair_share"]["value"] < 70
    assert 1 <= metrics["moe_expert_load_max_over_mean"]["value"] <= 4
    assert metrics["kv_window_read_share"]["value"] > 0
    assert metrics["decode_occupancy"]["value"] > 50
    assert metrics["mfu.serve"]["value"] > 0
    # no device plane in a CPU trace: the trace's readers return nothing
    assert "decode_roofline.layered" not in metrics
