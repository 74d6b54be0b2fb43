"""The ``pangu_ultra_moe`` block's benchmark files, on the CPU, at tiny
sizes: the configuration with its published numbers and named cuts, the
program's parameter tree against the reference's, the counts of
``model_math.py`` against the hand arithmetic, the control, the new reader
``latent_prefill_roofline`` on a hand-made trace, and one whole serving run
with shared prefixes through the harness in a temp checkout."""

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

CELL = "openpangu-ultra-moe-ep16.doc_qa_64_closed"
CODE = cell_mod.ModelCode(
    {"model": {"code": "chipbench/models/openpangu_moe_block"}}, ROOT)
TINY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_router_outputs": 8,
        "first_expert": 0, "num_experts": 4, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-5, "rope_theta": 25600000, "max_seq_len": 128}
CATALOG = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl, if any


def _config():
    with open(os.path.join(BENCH, "configs", "openpangu-ultra-moe-ep16.json")) as f:
        return json.load(f)


def _read(name, ctx):
    module = cell_mod._load_file(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "t_" + name)
    return module.read(ctx)


def test_the_configuration_holds_the_published_numbers_and_names_its_cuts():
    config = _config()
    cuts = {"num_hidden_layers": (5, 61), "first_k_dense_replace": (1, 3),
            "n_routed_experts": (16, 256), "vocab_size": (19200, 153600),
            "num_nextn_predict_layers": (0, 1)}
    assert sorted(config["reduced"]) == sorted(cuts)
    for key, (held, published) in cuts.items():
        assert config[key] == held and config["published"][key] == published
    sizes = config["model"]["config"]
    for key in sizes:  # as it is run: the sizes the reference and program get
        if key in config:
            assert sizes[key] == config[key], key
    # the held experts are the file's n_routed_experts; the router keeps
    # the published count
    assert sizes["num_experts"] == config["n_routed_experts"] == 16
    assert sizes["num_router_outputs"] == config["published"]["n_routed_experts"]
    assert config["sandwich_norm"] is True and "router" in config["assumed"]
    assert config["deployment"].startswith("data-parallel attention with 16-way")
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
    assert {"latent_prefill_roofline", "latent_attention_roofline",
            "mfu.serve.cached", "decode_roofline.layered",
            "moe_held_pair_share", "moe_expert_load_max_over_mean"} <= layers
    assert not {"mfu.serve", "decode_roofline", "kv_window_read_share"} & layers
    assert "served_logit_gap" in cell.limits
    # the traffic is doc_qa_closed's but for its clients, its shapes' seed
    # and who sends it
    with open(os.path.join(BENCH, "traffic", "doc_qa_closed.json")) as f:
        base = json.load(f)
    tr = cell.traffic
    assert tr["clients"] == 64 and tr["shape_seed"] != base["shape_seed"]
    assert {k: v for k, v in tr.items() if k not in ("clients", "shape_seed", "who")} \
        == {k: v for k, v in base.items() if k not in ("clients", "shape_seed", "who")}
    longest = (tr["shared_prefix"]["tokens"] + tr["prompt_len"]["max"]
               + tr["output_len"]["max"])
    assert longest <= sizes["max_seq_len"]


def test_the_trees_agree_and_the_counts_are_the_hand_arithmetic():
    import jax
    import numpy as np

    from distkeras_tpu.models.xing4 import Xing4

    math = CODE.model_math
    cut = _config()["model"]["config"]
    for sizes in (TINY, cut):
        spec = CODE.reference.weight_spec(sizes)  # shapes only
        total = sum(int(np.prod(shape, dtype=np.int64)) for shape in spec.values())
        assert total == math.param_count(sizes)
        tree = jax.eval_shape(
            Xing4(CODE.program.program_config(sizes)).init,
            jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), jax.numpy.int32))
        assert {k: tuple(v.shape) for k, v in CODE.program.flat(
            tree["params"]).items()} == spec
    # by hand: attention 196.6 M a layer, the dense layer 621.2 M,
    # an expert layer 1,000.7 M, the vocabulary slice 294.9 M: 4.919 G
    assert round(math.param_count(cut) / 1e9, 3) == 4.919
    assert round(math.routed_params(cut) / 4 / 16 / 1e6, 1) == 47.2
    assert math.routed_experts_per_token(cut) == 0.5
    assert math.kv_bytes_per_token(cut, 2) == 5760
    assert math.attention_flops_per_key(cut) == 2 * 128 * 5 * 320
    assert math.latent_read_bytes(cut, 2, [10, 20]) == 5760 * 30
    m = TINY
    per_key = 2 * 4 * 3 * (16 + 8 + 16)
    assert math.attention_flops_per_key(m) == per_key
    assert math.decode_flops(m, 20) == (math.layer_matmul_flops_per_token(m)
                                        + 20 * per_key + 2 * 64 * 256)
    keys = sum(p + 1 for p in range(3, 30))
    assert math.prefill_flops(m, 3, 30) == (
        27 * math.layer_matmul_flops_per_token(m) + keys * per_key + 2 * 64 * 256)
    fixed = math.param_count(m) - 2 * 4 * 3 * 64 * 32
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


@pytest.mark.parametrize("matmul", ["f32", "bf16"])
def test_a_request_on_a_document_run_before_reads_as_run_alone(matmul):
    """``position_logits`` takes the latent rows of the blocks a request
    shares with one run before and runs only its own: the logits are those
    of the request run with nothing kept, and a product with a bfloat16
    weight (``_mmw``) is the product at ``highest``."""
    import jax.numpy as jnp
    import numpy as np

    ref = CODE.reference
    w = ref.make_weights(TINY, 2**33 + 5)
    rng = np.random.default_rng(5)
    doc = rng.integers(1, 256, size=48).tolist()
    asks = [(doc + rng.integers(1, 256, size=n).tolist(),
             rng.integers(1, 256, size=24).tolist()) for n in (5, 19)]
    ref._SEEN["key"] = None
    kept = [ref.position_logits(w, TINY, p, s, matmul, 128, block=16)
            for p, s in asks]
    assert [len(r[0]) for r in ref._SEEN["runs"]] == [80, 96]
    for (prompt, served), got in zip(asks, kept):
        ref._SEEN["key"] = None  # nothing kept: the request alone
        alone = ref.position_logits(w, TINY, prompt, served, matmul, 128,
                                    block=16)
        assert float(abs(got - alone).max()) < 1e-5
    a = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    b = w["layer_0/attention/q_down"]
    assert b.dtype == jnp.bfloat16
    assert float(abs(ref._mmw("sc,cq->sq", a, b, matmul)
                     - ref._mm("sc,cq->sq", a, b, matmul)).max()) < 1e-5


def test_the_programs_of_a_request_can_be_compiled_before_it_comes():
    """``compile_requests_ahead`` (the server's process runs it while it
    warms up) compiles every program ``position_logits`` then needs for
    a request within its context: the request finds nothing left to
    compile ahead of it."""
    import numpy as np

    ref = CODE.reference
    w = ref.make_weights(TINY, 2**33 + 7)
    ref.compile_requests_ahead(w, TINY, 4096)
    known = set(ref._AHEAD)
    rng = np.random.default_rng(7)
    ref.position_logits(w, TINY, rng.integers(1, 256, 2100).tolist(),
                        rng.integers(1, 256, 40).tolist(), "f32", 4096)
    assert set(ref._AHEAD) == known


# ------------------------------------------ latent_prefill_roofline, by hand

READ = "paged_latent_chunk.3 = bf16[1,128,512,512]"


def _xspace(spans, runs, ops):
    """A serialized profiler trace: host spans ``dk:prefill_tick`` (start,
    end in us, and their attributes), executions of ``jit_serving_prefill``
    and ``jit_serving_decode`` (name, start, end) and operations (name,
    start, end) on one TPU's plane."""
    from jax.profiler import ProfileData

    def events(items, names):
        out = ""
        for name, lo, hi, *stats in items:
            out += (f"events {{ metadata_id: {names.index(name) + 1} "
                    f"offset_ps: {int(lo * 1e6)} duration_ps: {int((hi - lo) * 1e6)}")
            for i, value in enumerate(stats[0] if stats else ()):
                out += f" stats {{ metadata_id: {i + 1} int64_value: {value} }}"
            out += " }\n"
        return out

    def metadata(names):
        return "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                       f'name: "{n}" }} }}\n' for i, n in enumerate(names))

    module_names = sorted({n for n, *_ in runs})
    op_names = sorted({n for n, *_ in ops})
    names = module_names + op_names
    stats = [s for s in ("offset", "tokens") if spans and len(spans[0][3]) > 0]
    text = (
        'planes { id: 1 name: "/device:TPU:0"\n'
        f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n{events(runs, names)}}}\n'
        f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n{events(ops, names)}}}\n'
        f"{metadata(names)}}}\n"
        'planes { id: 2 name: "/host:CPU"\n'
        'lines { id: 1 name: "python" timestamp_ns: 0\n'
        + events([("dk:prefill_tick", lo, hi, vals) for lo, hi, _, vals in spans],
                 ["dk:prefill_tick"]) + "}\n"
        + metadata(["dk:prefill_tick"])
        + "".join(f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{s}" }} }}\n'
                  for i, s in enumerate(stats))
        + "}\n")
    return ProfileData.text_proto_to_serialized_xspace(text)


def _prefill_ctx(tmp_path, monkeypatch, spans, runs, ops, config=None):
    from harness import host_spans

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(spans, runs, ops))
    monkeypatch.setattr(host_spans, "trace_file", lambda: str(path))
    return {"config": config or _config(), "math": CODE.model_math,
            "trace": {"window_s": 1.0},
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "flops_per_s": {"bfloat16": 197e12}}}


@pytest.mark.parametrize("late", [0, 60])
def test_latent_prefill_roofline_reads_a_hand_made_trace(tmp_path, monkeypatch,
                                                         late):
    """Three chunks traced: the first's execution starts before any span
    (its span began before the trace: left out), the second and third are
    paired with their executions, a fourth span's execution is not in the
    trace (left out). Only the read the configuration names counts, and
    only inside the prefill program's executions. ``late``: the device
    starts each chunk 60 us later, after the NEXT span has begun; the
    k-th execution is still the k-th span's."""
    sizes, math = _config()["model"]["config"], CODE.model_math
    spans = [(100, 110, None, (32768, 512)), (200, 210, None, (33280, 300)),
             (900, 905, None, (32768, 128))]
    runs = [("jit_serving_prefill(9)", 10, 90),       # no span before it
            ("jit_serving_decode(7)", 120, 150),
            ("jit_serving_prefill(9)", 150 + late, 190 + late),  # span 0's
            ("jit_serving_prefill(9)", 320 + late, 400 + late)]  # span 1's
    ops = [(READ, 20, 80), (READ, 160 + late, 180 + late),
           ("fusion.3 = bf16[512,7680]", 180 + late, 190 + late),
           (READ, 330 + late, 380 + late), (READ, 125, 140)]  # last: in decode
    ctx = _prefill_ctx(tmp_path, monkeypatch, spans, runs, ops)

    def least(offset, tokens):
        pairs = sum(p + 1 for p in range(offset, offset + tokens))
        return max(math.attention_flops_per_key(sizes) * pairs / 197e12,
                   math.kv_bytes_per_token(sizes, 2) * (offset + tokens) / 819e9)

    want = 100 * (least(32768, 512) + least(33280, 300)) / ((20 + 50) * 1e-6)
    assert _read("latent_prefill_roofline", ctx) == pytest.approx(want)
    # compute-bound: 512 queries over ~33 k keys read 36 k operations a byte
    assert least(32768, 512) > 10 * math.kv_bytes_per_token(sizes, 2) * 33280 / 819e9


def test_latent_prefill_roofline_reads_nothing_without_a_match(tmp_path,
                                                               monkeypatch):
    spans = [(100, 110, None, (0, 512))]
    runs = [("jit_serving_prefill(9)", 150, 290)]
    ctx = _prefill_ctx(tmp_path, monkeypatch, spans, runs, [(READ, 160, 260)])
    assert _read("latent_prefill_roofline", ctx) > 0
    # no operation the configuration names
    other = dict(_config(), latent_prefill_ops={"program": "serving_prefill$",
                                                "pattern": "^nothing"})
    assert _read("latent_prefill_roofline", dict(ctx, config=other)) is None
    # a configuration that names no such operations; no trace
    bare = {k: v for k, v in _config().items() if k != "latent_prefill_ops"}
    assert _read("latent_prefill_roofline", dict(ctx, config=bare)) is None
    assert _read("latent_prefill_roofline", dict(ctx, trace=None)) is None
    # a program whose spans carry no positions (one from before they did)
    ctx = _prefill_ctx(tmp_path, monkeypatch, [(100, 110, None, ())], runs,
                       [(READ, 160, 260)])
    assert _read("latent_prefill_roofline", ctx) is None


# ---------------------------------------------------------------- rehearsal

# The compared gap is a mean over 64 served positions; at this size the
# reference in bfloat16 reads 0.005 on the control test's sequence, in
# float8 0.13.
SERVE_LIMIT = 0.04
SERVE_CONFIG = {
    "name": "tiny", "entry": "serve", "dtype": "bfloat16", "kv_dtype_bytes": 2,
    "min_prefill_bucket": 16,
    "model": {"code": "mybench/models/my_block", "seq_len": 128, "config": TINY},
    "serve_flags": ["--paged", "--kv-block-tokens", "4", "--slots", "4",
                    "--kv-pool-mb", "1", "--max-context", "128",
                    "--max-queue", "64", "--audit-recompiles", "report",
                    "--prefill-chunk", "16", "--min-prefill-bucket", "16"],
    "decode_program": {"name": "serving_decode$"},
    "latent_read_ops": {"pattern": "while"},
    "latent_prefill_ops": {"program": "serving_prefill$", "pattern": "while"}}
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
    and the readers, all as copies."""
    root = tmp_path / "checkout"
    bench = root / "mybench"
    for part in ("configs", "traffic"):
        (bench / part).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    shutil.copytree(os.path.join(BENCH, "models", "openpangu_moe_block"),
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
    from latent blocks, chunked prefill, absorbed decode, half the
    experts held, the counters over metricsz, the reference's check of
    what was served."""
    from harness import main

    line = main.run_cell("tiny.mix", 2**31 + 29, 2.0, True, time.perf_counter(),
                         root=_checkout(tmp_path), require_platform=None)
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["correct"] is True, line["compared"]
    metrics = line["metrics"]
    assert metrics["compiles_in_window.serve"]["value"] == 0
    assert metrics["prefix_hit_share"]["value"] > 50
    assert 0 < metrics["moe_held_pair_share"]["value"] < 100  # 4 of 8 held
    assert metrics["mfu.serve.cached"]["value"] > 0
    # no device plane in a CPU trace: the trace's readers return nothing
    assert "latent_prefill_roofline" not in metrics
    assert "latent_attention_roofline" not in metrics
