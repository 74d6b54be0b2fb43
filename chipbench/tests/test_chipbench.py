"""Tests of the benchmark's own code, on the CPU, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q -p no:cacheprovider

No test touches the chip or describes a TPU topology. The two rehearsals
drive a whole run through the harness's Python API (the command line has no
CPU mode) in a temp dir that ADDS a configuration, a traffic file, a cell
and a per-layer metric — and the model's code, a limits file — and edits
no file of chipbench/: what a later PR is allowed to do.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cell as cell_mod, compare, traffic  # noqa: E402

# The block's code, found as a configuration names it (no JAX until
# ``CODE.reference`` is asked for).
CODE = cell_mod.ModelCode({"model": {"code": "chipbench/models/bert_block"}}, ROOT)
model_math = CODE.model_math

TINY = {"vocab_size": 1024, "hidden_size": 128, "num_layers": 2,
        "num_heads": 4, "mlp_dim": 512, "dropout_rate": 0.0}
LENGTHS = {"prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 16, "max": 64},
           "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 4, "max": 32}}
# Tiny-size limits, set the way PERF.md section 2 sets the real ones. On the
# CPU over a few seeds the program reads loss 2e-5..7e-5, grad 0.003..0.011,
# update 0.003..0.0045, served gap 0.001..0.005; the fp8 control reads
# update 0.012..0.017 (grad 0.014..0.046: too near the program's at this
# size, so the update is the number that fails it); half a batch reads grad
# 0.13..0.22, an unchanged state 1.
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.03, "update_gap": 0.008}
SERVE_LIMITS = {"served_logit_gap": 0.05}


# ---------------------------------------------------------------- traffic

def test_traffic_is_deterministic_in_seed_and_keeps_its_clips():
    open_mix = {"kind": "open", "rate_per_s": 20.0, "shape_seed": 3, **LENGTHS,
                "shared_prefix": {"count": 4, "tokens": 32, "zipf_exponent": 1.0}}
    a = traffic.request_schedule(open_mix, 1024, 2**31 + 5, 30.0)
    b = traffic.request_schedule(open_mix, 1024, 2**31 + 5, 30.0)
    c = traffic.request_schedule(open_mix, 1024, 7, 30.0)
    assert a == b and a != c
    # another seed: the same sizes and arrivals, in another order
    assert [r.due_s for r in a] == [r.due_s for r in c]
    key = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs)
    assert key(a) == key(c)
    assert all(32 + 16 <= len(r.prompt) <= 32 + 64 for r in a)
    assert all(4 <= r.max_new_tokens <= 32 for r in a)
    assert all(1 <= t < 1024 for r in a for t in r.prompt)
    assert abs(len(a) / 30.0 - 20.0) < 3.0  # the rate the file states
    closed = traffic.request_schedule(
        {"kind": "closed", "requests": 50, "shape_seed": 3, **LENGTHS}, 1024, 1, 5.0)
    assert len(closed) == 50 and all(r.due_s is None for r in closed)
    other = traffic.request_schedule(
        {"kind": "closed", "requests": 50, "shape_seed": 3, **LENGTHS}, 1024, 2, 5.0)
    # a closed loop: the same sizes in the SAME order, other token ids
    assert [(len(r.prompt), r.max_new_tokens) for r in closed] == [
        (len(r.prompt), r.max_new_tokens) for r in other]
    assert closed[0].prompt != other[0].prompt
    rows = traffic.token_rows({"recipe": "zipf_mlm", "mask_rate": 0.15,
                               "mask_id": 0}, 64, 32, 1024, 2**33)
    again = traffic.token_rows({"recipe": "zipf_mlm", "mask_rate": 0.15,
                                "mask_id": 0}, 64, 32, 1024, 2**33)
    assert (rows[0] == again[0]).all() and (rows[1] > 0).all()
    assert 0.08 < (rows[0] == 0).mean() < 0.25


def test_warmup_prompts_cover_the_buckets_the_traffic_reaches():
    mix = {"kind": "open", **LENGTHS,
           "shared_prefix": {"count": 2, "tokens": 64, "zipf_exponent": 1.0}}
    lens = [len(p) for p in traffic.warmup_prompts(mix, 1024, 1, 16)]
    # cold: 64+16..64+64 pads to 128; then the prefix, then tails 16/32/64
    assert lens == [128, 80, 80, 96, 128]
    assert [len(p) for p in traffic.warmup_prompts(
        {"kind": "closed", **LENGTHS}, 1024, 1, 16)] == [16, 32, 64]


def test_open_loop_lateness_is_measured_from_due_time():
    from harness import serve_entry

    def rec(due, sent, times, want):
        r = serve_entry.Record(traffic.Request(0, (1,) * 8, want, None, 0))
        r.due, r.sent, r.token_times = due, sent, times
        r.tokens = [5] * len(times)
        r.done = True
        return r

    late = rec(10.0, 10.4, [10.5, 10.6, 10.7], 3)     # sent 0.4 s late
    prompt = rec(None, 11.0, [11.1, 11.3], 2)         # closed loop: from send
    silent = rec(12.0, 12.0, [], 4)                   # never answered
    running = rec(None, 9.0, [9.5, 10.0, 20.5, 21.0], 4)  # sent before it
    outside = rec(30.0, 30.0, [30.1], 1)              # after the window
    mine, failed, e2e, spans, edges = serve_entry.window_metrics(
        [late, prompt, silent, running, outside], 9.8, 20.0)
    # the edges are token arrivals: the first at or after 9.8 and 20.0
    assert edges == (10.0, 20.5)
    assert mine == [late, prompt, silent] and failed == [silent]
    assert spans["ttft_s"][0] == pytest.approx(0.5)   # from DUE, not sent
    assert spans["ttft_s"][1] == pytest.approx(0.1)
    assert spans["ttft_s"][2] == serve_entry.DRAIN_S  # a miss, not left out
    assert spans["loadgen_lag_s"] == [pytest.approx(0.4), 0.0]
    # the two tokens that are the edges are not counted
    assert e2e["serve_tokens_per_s"] == pytest.approx(5 / 10.5)
    assert e2e["itl_p95_ms"] == pytest.approx(200.0)
    assert spans["token_events"][0] == (10.5, 8)
    # no token after the close: the edge stays where the clock put it
    assert serve_entry.window_metrics([late, prompt], 10.0, 20.0)[4] == (10.5, 20.0)


# ------------------------------------------------------------- arithmetic

def test_flop_and_byte_counts_agree_with_hand_counts():
    m = {"vocab_size": 10, "hidden_size": 4, "num_layers": 2, "num_heads": 2,
         "mlp_dim": 8, "max_seq_len": 6, "causal": False}
    layer = 4 * (16 + 4) + (32 + 8) + (32 + 4) + 4 * 4   # = 172
    assert model_math.param_count(m) == 40 + 24 + 8 + 10 + 2 * layer
    # per token, forward: projections 2*(4*16 + 2*32) = 256 a layer
    assert model_math.layer_matmul_flops_per_token(m) == 512
    assert model_math.attention_flops(m, 6) == 2 * 2 * 2 * 6 * 4   # = 192
    assert model_math.head_flops_per_token(m) == 80
    assert model_math.train_flops_per_token(m, 6) == 3 * (512 + 192 + 80)
    causal = {**m, "causal": True}
    assert model_math.train_flops_per_token(causal, 6) == 3 * (512 + 32 * 3.5 + 80)
    # prefill of positions 2..4 sees 3 + 4 + 5 keys; the head once
    assert model_math.prefill_flops(causal, 2, 5) == 3 * 512 + 32 * 12 + 80
    assert model_math.decode_flops(causal, 5) == 512 + 160 + 80
    assert model_math.kv_bytes_per_token(m, 2) == 2 * 2 * 4 * 2
    assert model_math.decode_weight_bytes(m, 2) == (426 - 24) * 2
    assert model_math.train_step_min_bytes(m, 12) == 426 * 32 + 96
    real = {"vocab_size": 50257, "hidden_size": 768, "num_layers": 12,
            "num_heads": 12, "mlp_dim": 3072, "max_seq_len": 1024}
    assert model_math.param_count(real) == 124_439_808 + 50257  # GPT-2 small + head bias
    assert model_math.kv_bytes_per_token(real, 2) == 36_864


def test_worst_leaf_and_judge():
    ref = {"losses": [10.0, 9.0], "grad_norms": {"a": 1.0, "b": 2.0, "c": 1e-6},
           "change_norms": {"a": 0.1, "b": 0.1, "c": 0.1}}
    prog = {"losses": [10.1, 9.0], "grad_norms": {"a": 1.1, "b": 2.0, "c": 0.3},
            "change_norms": {"a": 0.1, "b": 0.12, "c": 0.0}}
    n = compare.train_numbers(prog, ref)
    assert n["loss_gap"] == pytest.approx(0.01)
    # c's gradient is nought beside the median leaf's: held to the median
    assert n["grad_gap"] == pytest.approx(0.3 - 1e-6)
    # ... and its change is not compared at all
    assert n["update_gap"] == pytest.approx(0.2)
    ok, compared = compare.judge(n, {"loss_gap": 0.02, "update_gap": 0.25})
    assert ok and compared["loss_gap"] == {"value": n["loss_gap"], "limit": 0.02}
    assert not compare.judge(n, {"grad_gap": 0.1})[0]
    assert not compare.judge({}, {"loss_gap": 1.0})[0]        # no number
    assert not compare.judge({"x": float("nan")}, {"x": 1.0})[0]
    assert not compare.judge(n, {})[0]                        # nothing compared


# ------------------------------------------------------------------ trace

def test_trace_reduce_on_the_recorded_tiny_trace():
    """tests/data/tiny.xplane.pb: two steps (10,754 operations) of one
    chip, cut from a v5e trace of bert-base-mlm.seq512_b32 (my chip run,
    PR 24; names shortened to 64 characters); expected.json beside it holds
    the busy time, span, program time and longest gap as other code summed
    them from the file when it was cut."""
    from harness import trace_reduce

    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps_of([(1, 2), (4, 5)], 0, 6) == [(2, 4), (0, 1), (5, 6)]
    with open(os.path.join(HERE, "data", "expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce(os.path.join(HERE, "data", "tiny.xplane.pb"),
                              None, 1)
    dev = got["devices"][0]
    assert dev["name"] == want["device"]
    # (the reader gets whole nanoseconds; expected.json was summed from the
    # file's picoseconds by other code, with numpy)
    assert dev["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    assert got["window_s"] == pytest.approx(want["span_s"], rel=1e-4)
    assert 0 < dev["busy_s"] <= got["window_s"]
    step = trace_reduce.find_program(got, {"name": "^jit_step$"})
    assert [n.split("(")[0] for n in step["names"]] == [want["program"]]
    assert step["count"] == want["count"] == len(step["starts_s"])
    assert step["total_s"] == pytest.approx(want["program_total_s"], rel=1e-4)
    # a program is named by the cell's files or not found: never guessed
    by_op = {"name": "step", "op": r"f32\[30522,768\]"}
    assert trace_reduce.find_program(got, by_op)["count"] == want["count"]
    for spec in (None, {}, {"name": "no_such_program"},
                 {"name": "step", "op": "no_such_operation"}):
        assert trace_reduce.find_program(got, spec) is None
    assert dev["gaps"][0][1] - dev["gaps"][0][0] == pytest.approx(
        want["longest_gap_s"], abs=3e-9)
    assert len(got["breakdown"]["device_ops"]) <= 10
    assert got["breakdown"]["device_ops"][0][0] == want["top_op"]


# ------------------------------------------------------------- rehearsals

def _bench_dir(tmp_path, config, mix, limits, e2e, extra_metric=None,
               own_limits=False):
    """A checkout-shaped temp dir whose benchmark is NEW files only: a
    configuration, a traffic mix, a cell, a per-layer metric; the readers
    and peaks of chipbench/ are copied beside them unchanged."""
    root = tmp_path / "checkout"
    bench = root / "mybench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    # the model's code too comes as files of the new benchmark's own
    shutil.copytree(os.path.join(BENCH, "models", "bert_block"),
                    bench / "models" / "my_block",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "distkeras_tpu"), root / "distkeras_tpu")
    os.symlink(os.path.join(ROOT, "chipbench"), root / "chipbench")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["device_kinds"]["cpu"] = {"flops_per_s": {"bfloat16": 1e12},
                                    "hbm_bytes_per_s": 1e11}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    if own_limits:  # a pair of files that are there: limits in a third
        (bench / "limits").mkdir()
        (bench / "limits" / "tiny.mix.json").write_text(json.dumps(limits))
        limits = {}
    (bench / "traffic" / "mix.json").write_text(json.dumps({**mix, "limits": limits}))
    (bench / "layer_metrics" / "my_count.py").write_text(
        'SOURCE = "program_counter"\nUNIT = "n"\n\n\n'
        'def read(ctx):\n    return ctx["counters"].get("%s")\n' % extra_metric)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    # every reader the benchmark has, plus the new one; each finds
    # something to read in this cell or returns nothing
    layers = [{"name": f[:-3], "moves": e2e[0], "workloads": ["tiny.mix"]}
              for f in sorted(os.listdir(bench / "layer_metrics"))
              if f.endswith(".py")]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "mybench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny", "traffic": "mix",
                       "chips": 1}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]
                       if m["name"] in e2e + ["setup_s"]],
        "per_layer": layers}))
    return str(root)


TRAIN_CONFIG = {"name": "tiny", "entry": "train", "dtype": "bfloat16",
                "model": {"code": "mybench/models/my_block", "seq_len": 64,
                          "config": {
                    **TINY, "max_seq_len": 64, "causal": False}}}
TRAIN_MIX = {"kind": "train", "trainer": "SingleTrainer", "mesh": None,
             "batch_size": 8, "optimizer": "adam", "learning_rate": 1e-4,
             "loss": "categorical_crossentropy",
             "data": {"recipe": "zipf_mlm", "mask_rate": 0.15, "mask_id": 0,
                      "rows": 256},
             "warmup_steps": 4, "compare_steps": 3, "reference_block_rows": 4,
             "trace_steps": 3, "step_program": {"name": "^jit_step$"}}
SERVE_CONFIG = {"name": "tiny", "entry": "serve", "dtype": "bfloat16",
                "kv_dtype_bytes": 2, "min_prefill_bucket": 16,
                "model": {"code": "mybench/models/my_block", "seq_len": 256,
                          "config": {
                    **TINY, "max_seq_len": 256, "causal": True}},
                "serve_flags": ["--paged", "--kv-block-tokens", "16",
                                "--slots", "4", "--kv-pool-mb", "8",
                                "--max-context", "256", "--max-queue", "64",
                                "--audit-recompiles", "report"]}
SERVE_MIX = {"kind": "closed", "clients": 4, "requests": 600, "shape_seed": 1, **LENGTHS, "shared_prefix": None,
             "check_requests": 3, "trace_offset_s": 0.2, "trace_seconds": 0.5}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def _train_line(tmp_path, trace=False, seconds=1.0):
    from harness import main

    root = _bench_dir(tmp_path, TRAIN_CONFIG, TRAIN_MIX, TRAIN_LIMITS,
                      ["train_tokens_per_s"], "steps")
    return main.run_cell("tiny.mix", 2**31 + 11, seconds, trace,
                         time.perf_counter(), root=root, require_platform=None)


def test_train_rehearsal_with_new_files_only(tmp_path):
    """One whole training run on the CPU: a cell, configuration, traffic mix
    and per-layer metric added as files, nothing edited; the line has the
    contract's keys, says ``cpu``, and is correct."""
    line = _train_line(tmp_path)
    assert list(line) == RESULT_KEYS            # `compared` comes last
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert "memory_peak_bytes" in line["device"] and "kind" in line["device"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert set(line["compared"]) == set(TRAIN_LIMITS)
    traced = _train_line(tmp_path / "t", trace=True)
    assert traced["metrics"]["my_count"]["value"] == traced["attempted"]
    assert traced["metrics"]["compiles_in_window.train"]["value"] == 0
    assert traced["metrics"]["step_ms_p50"]["value"] > 0
    # no device plane in a CPU trace: the trace's readers return nothing
    assert "train_step_roofline" not in traced["metrics"]
    assert "device_idle_share.train" not in traced["metrics"]


def test_serve_rehearsal_with_new_files_only(tmp_path):
    from harness import main

    root = _bench_dir(tmp_path, SERVE_CONFIG, SERVE_MIX, SERVE_LIMITS,
                      ["serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"],
                      "requests", own_limits=True)
    line = main.run_cell("tiny.mix", 2**31 + 12, 2.0, True,
                         time.perf_counter(), root=root, require_platform=None)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["metrics"]["my_count"]["value"] == line["attempted"]
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert line["metrics"]["prefix_hit_share"]["value"] == 0
    assert line["metrics"]["queue_wait_p95_ms"]["value"] >= 0
    assert 0 <= line["metrics"]["loadgen_stall_max_ms"]["value"] < 2000
    # the run by where its numbers were taken: the server's own count of the
    # rows it ticked is the clients' count of tokens, to a tick or two
    split = line["split"]
    assert split["server"]["slot_ticks"] == pytest.approx(
        split["clients"]["serve_tokens_per_s"] * split["clients"]["window_s"],
        rel=0.05)
    assert len(split["halves"]) == 2 and split["halves"][0]["serve_tokens_per_s"] > 0
    assert list(line)[-1] == "compared"          # still last
    assert 0 <= line["compared"]["served_logit_gap"]["value"] < 0.05


# ------------------------------------------------- the control and the faults

def test_control_in_fp8_comes_out_not_correct():
    """The reference, put in the program's place and computed in float8
    (the step below the configuration's bfloat16), fails a limit; computed
    in bfloat16, as the configuration states, it passes."""
    reference = CODE.reference

    sizes = TRAIN_CONFIG["model"]["config"]
    feats, labels = traffic.token_rows(TRAIN_MIX["data"], 24, 64, 1024, 5)
    batches = [(feats[i * 8:(i + 1) * 8], labels[i * 8:(i + 1) * 8])
               for i in range(3)]
    ref = reference.train_steps(sizes, 5, batches, 1e-4, "f32", 4)
    stated = reference.train_steps(sizes, 5, batches, 1e-4, "bf16", 4)
    control = reference.train_steps(sizes, 5, batches, 1e-4, "fp8", 4)
    assert compare.judge(compare.train_numbers(stated, ref), TRAIN_LIMITS)[0]
    assert not compare.judge(compare.train_numbers(control, ref), TRAIN_LIMITS)[0]
    # served model: the token float8 puts first lies further below the
    # reference's best than any token the bfloat16 path puts first
    gsizes = SERVE_CONFIG["model"]["config"]
    w = reference.make_weights(gsizes, 5)
    seq = list(range(1, 97))
    ref_logits = reference.position_logits(w, gsizes, seq[:16], seq[16:], "f32", 128)
    gap = {}
    for kind in ("bf16", "fp8"):
        first = reference.position_logits(
            w, gsizes, seq[:16], seq[16:], kind, 128).argmax(-1)
        gap[kind] = float(reference.gaps_below_best(ref_logits, first).max())
    assert gap["bf16"] <= SERVE_LIMITS["served_logit_gap"] < gap["fp8"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_comes_out_not_correct(tmp_path, monkeypatch, fault):
    """The rest of a run, with the timed path broken underneath."""
    from distkeras_tpu.training import trainers

    real = trainers.make_train_step

    def broken(*args, **kwargs):
        step = real(*args, **{**kwargs, "donate": False})
        if fault == "state_unchanged":
            return lambda state, batch: (state, step(state, batch)[1])
        return lambda state, batch: step(
            state, {k: v[:len(v) // 2] for k, v in batch.items()})

    monkeypatch.setattr(trainers, "make_train_step", broken)
    line = _train_line(tmp_path, seconds=0.3)
    assert line["correct"] is False
    if fault == "state_unchanged":  # the first moment is zero: a gap of 1
        assert line["compared"]["grad_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_an_altered_token_comes_out_not_correct(tmp_path, monkeypatch):
    """One served token in sixteen replaced as the client receives it (the
    server is another process; this is the nearest place a test reaches)."""
    from harness import main, serve_entry

    real = serve_entry._stream

    async def altered(host, port, rec):
        await real(host, port, rec)
        rec.tokens[::16] = [(t + 1) % 1024 or 1 for t in rec.tokens[::16]]

    monkeypatch.setattr(serve_entry, "_stream", altered)
    root = _bench_dir(tmp_path, SERVE_CONFIG, SERVE_MIX, SERVE_LIMITS,
                      ["serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"],
                      "requests")
    line = main.run_cell("tiny.mix", 3, 1.0, False, time.perf_counter(),
                         root=root, require_platform=None)
    assert line["correct"] is False
    assert line["compared"]["served_logit_gap"]["value"] > 0.05


# ------------------------------------------------------------ the command

def test_the_command_refuses_to_run_off_the_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    argv = ["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"]
    got = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), *argv],
                         env=env, capture_output=True, text=True, timeout=300)
    assert got.returncode != 0 and "refusing to run" in got.stderr
    assert not got.stdout.strip().startswith("{")   # no result line
    # and in a directory that holds only BENCHMARK.json and chipbench/
    bare = tmp_path / "bare"
    shutil.copytree(BENCH, bare / "chipbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    got = subprocess.run([sys.executable, str(bare / "chipbench" / "run.py"), *argv],
                         env=env, capture_output=True, text=True, timeout=300)
    assert got.returncode != 0 and not got.stdout.strip()


@pytest.mark.parametrize("tool", [
    ["limits.py", "--workload", "bert-base-mlm.seq512_b32", "--seeds", "1"],
    ["sweep.py", "--config", "gpt2-small", "--traffic", "chat_open",
     "--rates", "1", "--seconds", "1"]])
def test_the_tools_that_set_limits_and_rates_refuse_too(tool):
    """limits.py and sweep.py print what limits and a rate are set from:
    they have no platform option and no CPU mode either."""
    got = subprocess.run([sys.executable, os.path.join(BENCH, tool[0]), *tool[1:]],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=300)
    assert got.returncode != 0 and "refusing to run" in got.stderr
    assert not got.stdout.strip()
    got = subprocess.run([sys.executable, os.path.join(BENCH, tool[0]), *tool[1:],
                          "--platform", "cpu"], capture_output=True, text=True)
    assert got.returncode == 2 and "unrecognized arguments" in got.stderr


def test_benchmark_json_names_files_that_are_there():
    from harness import cell as cell_mod

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)["device_kinds"]
    with pytest.raises(SystemExit):
        cell_mod.load_peaks(os.path.join(BENCH, "peaks.json"), "TPU v9")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = cell_mod.load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer and cell.limits
        for name, path in cell.per_layer:
            assert os.path.isfile(path), path
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        reporters = {x["name"]: x.get("workloads") for x in bench["end_to_end"]}
        for w in m.get("workloads", []):
            assert reporters[m["moves"]] is None or w in reporters[m["moves"]]
