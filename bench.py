"""Training throughput + MFU of one jitted train step on one TPU chip.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}``

The BASELINE metric family is samples/sec/chip with a >=35% MFU north star
(BASELINE.json), so on the chip ``vs_baseline`` reports achieved-MFU / 0.35;
>1.0 beats the target.

The parent never imports jax (a chip belongs to one process): it runs the
measurement once, in a child, for the requested model, and exits with the
child's code. There is no fallback: the child fails unless
``jax.devices()[0].platform`` is ``tpu``. A CPU run happens only when asked
for by name, ``BENCH_PLATFORM=cpu``, and says so in every field that names
the device.

The child (``--measure``) times the exact jitted train step the trainers
drive (fwd+bwd+optax update, donated state), fed with a device-resident
batch so the measurement is chip throughput, not host IO.

``BENCH_MODEL`` selects the workload (``bert`` default, ``resnet50``,
``resnet18``, ``mlp``); ``BENCH_BATCH``/``BENCH_SEQ``/``BENCH_IMAGE``/
``BENCH_STEPS``/``BENCH_WARMUP`` size it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _extract_json_line(out: bytes) -> str | None:
    """Last parseable JSON line of a captured bench stdout
    (scripts/append_baseline.py reads artifacts with it)."""
    for line in reversed(out.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                json.loads(line)
            except ValueError:
                continue
            return line
    return None


# --------------------------------------------------------------------------
# Child: the actual measurement (the only process that imports jax).
# --------------------------------------------------------------------------

def _model_and_batch(kind: str, batch: int):
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    if kind == "bert":
        from distkeras_tpu.models.bert import bert_base_mlm

        seq = int(os.environ.get("BENCH_SEQ", "128"))
        model = bert_base_mlm(seq_len=seq)
        x = jnp.asarray(rng.integers(0, 30522, size=(batch, seq)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 30522, size=(batch, seq)), jnp.int32)
        return model, {"features": x, "label": y}
    if kind in ("resnet50", "resnet18"):
        from distkeras_tpu.models import resnet

        image = int(os.environ.get("BENCH_IMAGE", "224"))
        model = getattr(resnet, kind)(num_classes=1000, image_size=image)
        x = jnp.asarray(rng.normal(size=(batch, image, image, 3)), jnp.bfloat16)
        y = jnp.asarray(rng.integers(0, 1000, size=(batch,)), jnp.int32)
        return model, {"features": x, "label": y}
    if kind == "mlp":
        from distkeras_tpu.models.mlp import mnist_mlp

        model = mnist_mlp()
        x = jnp.asarray(rng.normal(size=(batch, 784)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 10, size=(batch,)), jnp.int32)
        return model, {"features": x, "label": y}
    raise SystemExit(f"unknown BENCH_MODEL {kind!r}")


def _config_key(metric: str, batch: int, on_cpu: bool, shape: str = "",
                forced: bool = False) -> str:
    """Drift-gate identity: everything that changes per-sample work must be
    in the key (shape = seq/image tag), and forced-CPU proof runs compare
    only among themselves (a noisy proof run must never ratchet the
    baseline the organic driver rows are gated against)."""
    key = f"{metric}/batch{batch}/{'cpu' if on_cpu else 'tpu'}"
    if shape:
        key += f"/{shape}"
    if forced:
        key += "/forced"
    return key


def _previous_same_config(metric: str, batch: int, on_cpu: bool,
                          shape: str = "", forced: bool = False):
    """Most recent recorded same-config measurement, for the drift gate
    (VERDICT r4 weak #1: the r03->r04 CPU regression slid through with
    ``vs_baseline: null``). Driver round artifacts (``BENCH_r*.json``,
    authoritative, committed) win; ``bench_history.json`` (updated by
    every measurement run, covers watcher-ladder configs the driver never
    runs) is the fallback. Returns ``(value, source)`` or ``(None, None)``."""
    import glob
    import re

    best = None  # (round_no, value, source)
    for path in glob.glob(os.path.join(HERE, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f).get("parsed") or {}
        except (OSError, ValueError):
            continue
        det = rec.get("detail") or {}
        if det.get("infrastructure_failure"):
            continue
        if rec.get("metric") != metric or det.get("batch_size") != batch:
            continue
        if ("CPU" in str(det.get("device", "")).upper()) != on_cpu:
            continue
        # Rows recorded before the shape field existed compare as "" — that
        # matches mlp (whose tag IS "") and deliberately never matches
        # bert/resnet (default tags "seq128"/"img224"): those models have
        # no pre-shape-field CPU rows in any BENCH_r*.json, and refusing a
        # shapeless prior is safer than guessing its geometry.
        if str(det.get("shape", "") or "") != shape:
            continue
        if bool(det.get("forced_cpu")) != forced:
            continue
        # Rows can carry a missing/null value (e.g. an aborted measurement
        # child still wrote its record skeleton); skip them instead of
        # crashing the comparison on float(None).
        val = rec.get("value")
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            continue
        rnd = int(m.group(1))
        if best is None or rnd > best[0]:
            best = (rnd, float(val), os.path.basename(path))
    if best is not None:
        return best[1], best[2]
    try:
        with open(os.path.join(HERE, "bench_history.json")) as f:
            hist = json.load(f)
        entry = hist.get(_config_key(metric, batch, on_cpu, shape, forced))
        if entry:
            return float(entry["value"]), "bench_history.json"
    except (OSError, ValueError, KeyError, TypeError):
        pass
    return None, None


def history_entry(old: dict | None, value: float, when: str) -> dict:
    """Next ``bench_history.json`` row: the new value plus a bounded
    trail of displaced entries — the latest-vs-prior drift check
    (scripts/check_bench_regression.py) needs the previous same-config
    row even after an overwrite. Rows predating the trail field just
    start one. Only numeric values enter the trail — a null row from an
    aborted child would otherwise occupy trail slots forever (same
    filter check_bench_regression applies on read). Shared with
    benchmarks/serving_bench.py so training and serving rows keep one
    entry shape."""
    def _numeric(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    entry = {"value": value, "when": when}
    if isinstance(old, dict):
        prev = [
            p for p in old.get("prev", [])
            if isinstance(p, dict) and _numeric(p.get("value"))
        ]
        if _numeric(old.get("value")):
            prev.append({"value": old["value"], "when": old.get("when")})
        if prev:
            entry["prev"] = prev[-20:]
    return entry


def write_history(path: str, hist: dict) -> None:
    """Write-then-rename: the parent kills a bench child on its deadline,
    and a kill landing mid-dump must not truncate the history (the next
    run would silently reset it and lose every drift baseline)."""
    try:
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(hist, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except OSError:
        pass


def load_history(path: str) -> dict:
    """Current ``bench_history.json`` contents, or an empty dict when the
    file is missing/corrupt (a fresh history starts over rather than
    crashing the measurement that wants to record into it)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _record_history(metric: str, batch: int, on_cpu: bool, value: float,
                    shape: str = "", forced: bool = False) -> None:
    path = os.path.join(HERE, "bench_history.json")
    hist = load_history(path)
    key = _config_key(metric, batch, on_cpu, shape, forced)
    hist[key] = history_entry(
        hist.get(key), value,
        time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    write_history(path, hist)


def _measure() -> None:
    kind = os.environ.get("BENCH_MODEL", "bert")
    asked_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    batch = int(os.environ.get("BENCH_BATCH", "64" if kind != "bert" else "32"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))

    import jax

    from distkeras_tpu.utils.platform import use_compile_cache

    if asked_cpu:
        jax.config.update("jax_platforms", "cpu")
    use_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not asked_cpu:
        raise SystemExit(
            f"bench: jax found {device.platform!r} ({device.device_kind}), "
            "not a tpu — no fallback; a CPU run must be asked for by name "
            "(BENCH_PLATFORM=cpu)")
    on_cpu = device.platform == "cpu"

    from distkeras_tpu.ops.losses import get_optimizer
    from distkeras_tpu.tracing import (
        StepTimer,
        compiled_step_flops,
        device_peak_flops,
    )
    from distkeras_tpu.training.step import TrainState, make_train_step

    model, b = _model_and_batch(kind, batch)
    optimizer = get_optimizer("sgd", 0.1)
    step_fn = make_train_step(model, optimizer, "categorical_crossentropy",
                              metrics=())
    state = TrainState.create(model, optimizer, rng=0)

    # XLA's own FLOP count for the whole compiled step (a compile-cache hit
    # after the warmup compile); the hand constant is the cross-check.
    xla_flops = compiled_step_flops(step_fn, state, b)

    for _ in range(warmup):
        state, m = step_fn(state, b)
    jax.block_until_ready(state.params)

    timer = StepTimer()
    timer.start()
    for _ in range(steps):
        state, m = step_fn(state, b)
        jax.block_until_ready(m["loss"])
        timer.tick()

    summary = timer.summary(
        batch_size=batch,
        flops_per_example=model.flops_per_example,
        num_chips=1,
        skip_warmup=1 if steps > 1 else 0,
        flops_per_step=xla_flops,
    )
    sps = summary["samples_per_sec_per_chip"]
    mfu = summary.get("mfu", 0.0)
    hand_flops = (
        3.0 * model.flops_per_example * batch if model.flops_per_example else None
    )
    flops_agreement = (
        round(xla_flops / hand_flops, 3) if (xla_flops and hand_flops) else None
    )
    metric = f"{model.name}_train_samples_per_sec_per_chip"
    # Per-sample work identity beyond the batch size: scaled-down proof
    # runs (seq 64, image 64) must never gate against full-shape rows.
    if kind == "bert":
        shape = f"seq{os.environ.get('BENCH_SEQ', '128')}"
    elif kind in ("resnet50", "resnet18"):
        shape = f"img{os.environ.get('BENCH_IMAGE', '224')}"
    else:
        shape = ""
    # vs_baseline: on TPU, achieved-MFU / the 0.35 north star. On CPU
    # (where MFU vs a TPU peak is meaningless) it gates DRIFT instead:
    # the ratio against the last recorded same-config CPU row.
    prev_value, prev_source = (None, None)
    if on_cpu:
        prev_value, prev_source = _previous_same_config(
            metric, batch, True, shape
        )
    if not on_cpu:
        vs_baseline = round(mfu / 0.35, 4) if mfu else None
        vs_kind = "mfu_over_north_star" if mfu else "mfu_unavailable"
    elif prev_value is not None and prev_value > 0:
        vs_baseline = round(sps / prev_value, 4)
        vs_kind = "cpu_drift_vs_last_recorded"
    else:
        vs_baseline = None
        vs_kind = ("prior_row_unusable" if prev_source is not None
                   else "no_prior_same_config_row")
    _record_history(metric, batch, on_cpu, round(sps, 2), shape)
    print(json.dumps({
        "metric": metric,
        "value": round(sps, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": vs_baseline,
        "detail": {
            "mfu": round(mfu, 4) if mfu else "unavailable",
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
            "shape": shape,
            "vs_baseline_kind": vs_kind,
            "baseline_source": prev_source,
            "model": model.name,
            "batch_size": batch,
            "step_time_mean_s": round(summary["step_time_mean_s"], 5),
            # Tail percentiles (BASELINE cares about straggler steps, not
            # just the mean — a p99 spike is a sync-mesh stall).
            "step_time_p90_s": round(summary["step_time_p90_s"], 5),
            "step_time_p99_s": round(summary["step_time_p99_s"], 5),
            "step_time_var_s2": round(summary["step_time_var_s2"], 8),
            "device": str(device),
            "peak_flops": device_peak_flops() or "unavailable",
            "flops_per_step_xla": xla_flops,
            "flops_per_step_hand": hand_flops,
            "flops_xla_over_hand": flops_agreement,
        },
    }), flush=True)


def main() -> int:
    if "--measure" in sys.argv[1:]:
        _measure()
        return 0
    # The parent stays off jax: one measurement, in a child; its stdout
    # is ours, and so is its exit code.
    return subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--measure"], cwd=HERE)


if __name__ == "__main__":
    sys.exit(main())
