"""CLI runner tests (the Job/Punchcard payload format)."""

import json
import subprocess
import sys

import numpy as np
import pytest


@pytest.fixture
def job(tmp_path):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 28)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    data = tmp_path / "d.npz"
    np.savez(data, features=x, label=y)
    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({
        "trainer": "DOWNPOUR", "worker_optimizer": "adam",
        "learning_rate": 0.01, "num_workers": 2, "batch_size": 16,
        "num_epoch": 2, "communication_window": 4,
    }))
    return data, cfg, tmp_path


@pytest.mark.slow
def test_cli_end_to_end(job):
    data, cfg, tmp = job
    out = tmp / "weights.bin"
    metrics = tmp / "metrics.jsonl"
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms','cpu');"
         "from distkeras_tpu.run import main; import sys; sys.exit(main())",
         "--config", str(cfg), "--data", str(data), "--model", "higgs_mlp",
         "--out", str(out), "--metrics-out", str(metrics), "--shuffle"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["trainer"] == "DOWNPOUR"
    assert summary["steps"] > 0
    assert out.exists()
    lines = [json.loads(l) for l in open(metrics)]
    assert len(lines) == summary["steps"]


def test_cli_train_statusz_and_stamped_out(job, capsys):
    """In-process `run.py train`: the statusz writer leaves a final
    worker-table snapshot, the summary line carries staleness/goodput,
    --out is provenance-stamped, and the `statusz` subcommand renders
    the snapshot for humans."""
    data, cfg, tmp = job
    out = tmp / "weights.bin"
    statusz = tmp / "statusz.json"
    from distkeras_tpu.run import main

    rc = main(["train", "--config", str(cfg), "--data", str(data),
               "--model", "higgs_mlp", "--out", str(out),
               "--statusz-out", str(statusz),
               "--statusz-interval", "0.2"])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["statusz"] == str(statusz)
    assert "staleness_p99" in summary and "goodput_ratio" in summary
    payload = json.loads(statusz.read_text())
    assert {w["worker"] for w in payload["workers"]} == {0, 1}
    assert payload["ps"]["num_commits"] >= 2
    # The saved weights carry the provenance stamp serve/reload read.
    from distkeras_tpu.checkpoint import load_weights_meta

    assert load_weights_meta(str(out))["version"] == 1

    rc = main(["statusz", "--file", str(statusz)])
    assert rc == 0
    page = capsys.readouterr().out
    assert "workers:" in page and "staleness:" in page


def test_serving_config_flags_forwarded_to_replicas():
    """The ONE replica-flag builder shared by `cluster` and `deploy`:
    pool/chunk/speculation configuration reaches every replica child —
    which is what makes deploy's canary validate candidates under the
    fleet's REAL serving config instead of the one-token default."""
    import argparse

    from distkeras_tpu.run import _serving_config_flags

    args = argparse.Namespace(
        top_k=8, prefill_chunk=32,
        paged=True, kv_pool_mb=64.0, kv_block_tokens=8, max_context=48,
        draft_model="gpt_tiny", draft_args='{"seq_len": 64}',
        draft_weights=None, spec_k=6)
    flags = _serving_config_flags(args)
    for pair in (["--kv-pool-mb", "64.0"],
                 ["--kv-block-tokens", "8"], ["--prefill-chunk", "32"],
                 ["--max-context", "48"], ["--top-k", "8"],
                 ["--draft-model", "gpt_tiny"],
                 ["--draft-args", '{"seq_len": 64}'], ["--spec-k", "6"]):
        joined = " ".join(flags)
        assert " ".join(pair) in joined, (pair, flags)
    # The default: no budget and no spec flags leak into the children
    # (each sizes its own pool), and the no-op --paged is never passed on.
    plain = argparse.Namespace(
        top_k=None, prefill_chunk=None,
        paged=False, kv_pool_mb=0.0, kv_block_tokens=16,
        max_context=None, draft_model=None, draft_args="{}",
        draft_weights=None, spec_k=4)
    assert "--paged" not in flags
    flags = _serving_config_flags(plain)
    assert "--kv-pool-mb" not in flags and "--draft-model" not in flags
    assert flags[:2] == ["--kv-block-tokens", "16"]

    assert all(isinstance(f, str) for f in _serving_config_flags(args))


def test_deploy_and_serve_parsers_accept_serving_config(capsys):
    """Every flag the replica builder emits must exist on BOTH parent
    parsers — a flag deploy's parser rejects could never reach its
    canary replicas."""
    import pytest as _pytest

    from distkeras_tpu.run import deploy_main, serve_main

    for main_fn, argv in ((deploy_main, ["--help"]),
                          (serve_main, ["--help"])):
        with _pytest.raises(SystemExit) as e:
            main_fn(argv)
        assert e.value.code == 0
        text = capsys.readouterr().out
        for flag in ("--draft-model", "--draft-args", "--spec-k",
                     "--paged", "--kv-pool-mb", "--kv-block-tokens",
                     "--prefill-chunk",
                     "--max-context", "--mesh", "--mesh-shape",
                     "--force-host-devices"):
            assert flag in text, (main_fn.__name__, flag)


def _serving_configs():
    import glob
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = []
    for path in sorted(glob.glob(
            os.path.join(root, "chipbench", "configs", "*.json"))):
        with open(path) as f:
            config = json.load(f)
        if config.get("entry") == "serve":
            out.append(pytest.param(config["serve_flags"],
                                    id=config["name"]))
    return out


@pytest.mark.parametrize("serve_flags", _serving_configs())
def test_serve_parser_accepts_the_benchmark_configs_flags(serve_flags,
                                                          monkeypatch):
    """`run serve` takes each served configuration's ``serve_flags``
    whole, the no-op ``--paged`` included: the driver starts parent and
    change from the same files (read here, never edited). The seam is
    the one `chipbench/harness/serve_child.py` uses: `run.load_model`,
    reached only once the arguments have parsed."""
    from distkeras_tpu import run

    class Parsed(Exception):
        pass

    def stop(_name, _kwargs):
        raise Parsed

    monkeypatch.setattr(run, "load_model", stop)
    assert "--paged" in serve_flags
    with pytest.raises(Parsed):
        run.serve_main(["--model", "gpt_tiny", "--port", "0", *serve_flags])


@pytest.mark.parametrize("entry,argv", [
    ("serve_main", []), ("deploy_main", ["--watch-dir", "unused"])])
@pytest.mark.parametrize("flag", ["--prefix-cache-mb", "--prefix-block"])
def test_parsers_refuse_the_removed_prefix_cache_flags(entry, argv, flag,
                                                       capsys):
    """A stale deployment script fails at start, not silently."""
    from distkeras_tpu import run

    with pytest.raises(SystemExit) as e:
        getattr(run, entry)([*argv, flag, "1"])
    assert e.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


def test_serve_mesh_shape_typed_cli_errors():
    """A --mesh-shape that can't parse, or whose device product does
    not divide the visible device count, must die as ONE typed CLI
    line (SystemExit) before any server/engine work — never a deep jax
    traceback."""
    import jax
    import pytest as _pytest

    from distkeras_tpu.run import serve_main

    n = len(jax.devices())
    with _pytest.raises(SystemExit) as e:
        serve_main(["--mesh-shape", f"tp={n + 1}", "--port", "0"])
    assert "divide" in str(e.value) and "--mesh" in str(e.value)
    with _pytest.raises(SystemExit) as e:
        serve_main(["--mesh-shape", "tp=banana", "--port", "0"])
    assert "--mesh-shape" in str(e.value)
    # A mesh shape with no tp axis is equally typed.
    with _pytest.raises(SystemExit) as e:
        serve_main(["--mesh-shape", "dp=1", "--port", "0"])
    assert "tp" in str(e.value)


def test_mesh_flags_forwarded_to_replicas():
    """Cluster/deploy children must inherit the parent's sharding ask:
    the shared flag builder forwards --mesh/--mesh-shape (and the
    forced device count) to every replica."""
    import argparse

    from distkeras_tpu.run import _serving_config_flags

    base = dict(
        top_k=None, prefill_chunk=None,
        paged=False, kv_pool_mb=0.0, kv_block_tokens=16,
        max_context=None, draft_model=None, draft_args="{}",
        draft_weights=None, spec_k=4)
    shaped = argparse.Namespace(**base, mesh=False, mesh_shape="tp=2",
                                force_host_devices=2)
    flags = " ".join(_serving_config_flags(shaped))
    assert "--mesh-shape tp=2" in flags
    assert "--force-host-devices 2" in flags
    bare = argparse.Namespace(**base, mesh=True, mesh_shape=None,
                              force_host_devices=None)
    flags = _serving_config_flags(bare)
    assert "--mesh" in flags and "--mesh-shape" not in flags
    plain = argparse.Namespace(**base, mesh=False, mesh_shape=None,
                               force_host_devices=None)
    assert "--mesh" not in _serving_config_flags(plain)


def test_cli_unknown_model(job):
    data, cfg, _ = job
    r = subprocess.run(
        [sys.executable, "-m", "distkeras_tpu.run", "--config", str(cfg),
         "--data", str(data), "--model", "nope"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "unknown model" in r.stderr
