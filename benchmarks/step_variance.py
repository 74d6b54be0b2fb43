"""Step-time variance: socket-PS-style async vs GSPMD all-reduce.

BASELINE.json's second metric is "PS→all-reduce step-time variance": the
reference's socket parameter server serialized all workers' commits through
one lock, making step times jittery; the GSPMD all-reduce path is lock-step
and should show near-zero variance. This benchmark measures both on the
same model/data and prints a JSON comparison.

Runs anywhere: real TPU (1 chip: async threads share the chip) or the
8-device virtual CPU mesh:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/step_variance.py --platform cpu
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None, choices=[None, "cpu", "tpu"])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=60)
    # adag measures the fused bf16 delta-window wire (round-2 comparable);
    # aeasgd measures the round-4 delta-encoded elastic exchange
    # (bit-identical bf16 mirrors both sides — the async column tracks
    # the wire that actually changed).
    ap.add_argument("--protocol", default="adag", choices=["adag", "aeasgd"])
    args = ap.parse_args()

    if args.platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.models.core import Model
    from distkeras_tpu.models.mlp import MLP

    rng = np.random.default_rng(0)
    d = 256
    n = args.steps * args.batch_size * args.workers
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    ds = dk.Dataset.from_arrays(features=x, label=y)

    def model():
        return Model.from_flax(
            MLP(features=(512, 512), num_classes=2), input_shape=(d,)
        )

    # --- async PS path (per-worker step times from history timestamps) ----
    class TimingStream:
        def __init__(self):
            self.t = []

        def emit(self, step, metrics):
            pass

    t0 = time.time()
    if args.protocol == "aeasgd":
        async_trainer = dk.AEASGD(
            model(), worker_optimizer="sgd", learning_rate=0.05,
            num_workers=args.workers, batch_size=args.batch_size,
            num_epoch=1, communication_window=4, rho=1.0,
        )
    else:
        async_trainer = dk.ADAG(
            model(), worker_optimizer="sgd", learning_rate=0.05,
            num_workers=args.workers, batch_size=args.batch_size,
            num_epoch=1, communication_window=4,
        )
    async_trainer.train(ds)
    async_wall = time.time() - t0
    async_steps = len(async_trainer.get_history())
    async_mean = async_wall / max(1, async_steps / args.workers)

    # Steady state: drop each worker's first window (it absorbs the one-off
    # XLA compile, which the sync path's timed loop also excludes).
    wt = async_trainer.window_times
    warm_start = max(h[0][0] for h in wt if h)  # all workers past compile
    steady_steps = sum(n for h in wt for (t, n) in h if t > warm_start)
    t_end = max(t for h in wt for (t, _) in h)
    async_steady = (
        (t_end - warm_start) / max(1, steady_steps / args.workers)
        if steady_steps
        else async_mean
    )

    # --- sync all-reduce path (explicit per-step timing) -------------------
    from distkeras_tpu.data.feed import minibatches
    from distkeras_tpu.ops.losses import get_optimizer
    from distkeras_tpu.parallel.mesh import best_mesh, data_parallel_shardings
    from distkeras_tpu.training.step import TrainState, make_train_step

    mesh = best_mesh()
    ndev = mesh.devices.size
    bs_global = args.batch_size * ndev
    m = model()
    opt = get_optimizer("sgd", 0.05)
    step_fn = make_train_step(m, opt, "categorical_crossentropy", metrics=())
    state = TrainState.create(m, opt, rng=0)
    batch_sh, repl = data_parallel_shardings(mesh)
    state = jax.device_put(state, repl)
    times = []
    it = minibatches(ds, bs_global, num_epoch=2)
    first = next(it)
    sharded = {k: jax.device_put(v, batch_sh) for k, v in first.items()}
    state, mm = step_fn(state, sharded)  # compile
    jax.block_until_ready(mm["loss"])
    for i, b in enumerate(it):
        if i >= args.steps:
            break
        t1 = time.perf_counter()
        sharded = {k: jax.device_put(v, batch_sh) for k, v in b.items()}
        state, mm = step_fn(state, sharded)
        jax.block_until_ready(mm["loss"])
        times.append(time.perf_counter() - t1)

    sync_mean = statistics.fmean(times)
    sync_var = statistics.pvariance(times)
    sync_cv = (sync_var**0.5) / sync_mean

    print(json.dumps({
        "metric": "ps_vs_allreduce_step_time",
        "protocol": args.protocol,
        "sync_allreduce": {
            "mean_s": round(sync_mean, 6),
            "var_s2": round(sync_var, 9),
            "cv": round(sync_cv, 4),
            "devices": ndev,
        },
        "async_ps": {
            "effective_step_mean_s": round(async_mean, 6),
            "steady_state_step_s": round(async_steady, 6),
            "vs_sync": round(async_steady / sync_mean, 2),
            "workers": args.workers,
            "commits": async_trainer.parameter_server.num_commits,
        },
        "note": "sync path is the recommended TPU default; cv is the "
                "jitter headline (lower is better)",
    }))


if __name__ == "__main__":
    main()
