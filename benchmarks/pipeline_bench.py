"""Pipeline schedule bench: GPipe (V=1) vs interleaved virtual stages (V=2+).

Same total model depth (L = pp * V layers), same microbatch count: the
interleaved schedule's fill/drain bubble is (P-1)/(M·V+P-1) vs GPipe's
(P-1)/(M+P-1), so wall-clock per step should drop toward the busy-time
floor as V grows. On the virtual CPU mesh the numbers are relative, not
TPU throughput; the schedule-length ratio is what to look at. Prints one
JSON line.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 python benchmarks/pipeline_bench.py

Memory mode (the pipeline's activation-memory accounting): ``BENCH_MODE=memory`` compiles the *train step* (grad through
``pipeline_apply`` over real transformer EncoderLayer stages) for each
schedule — GPipe-ordered autodiff plain vs ``remat`` stage_fn, V=1 vs 2 —
and reports **XLA's own per-device peak temp allocation**
(``Compiled.memory_analysis().temp_size_in_bytes``), i.e. measured
residency, not a hand model. Alongside each measured number it prints the
analytic saved-state floor (T ticks x microbatch state) and — measured
the same way — the TRUE 1F1B engine (parallel/pipeline_1f1b.py:
hand-rolled backward, ring buffer of <= P in-flight inputs, residency
independent of M), so the docs table's (model, M, V, P) fit claims trace
to this bench.

  BENCH_MODE=memory XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python benchmarks/pipeline_bench.py
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def main():
    import jax

    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from distkeras_tpu.parallel.mesh import make_mesh
    from distkeras_tpu.parallel.pipeline import (
        pipeline_apply,
        schedule_ticks,
        stack_stage_params,
    )

    P = int(os.environ.get("BENCH_PP", str(len(jax.devices()))))
    M = int(os.environ.get("BENCH_MICRO", "8"))
    D = int(os.environ.get("BENCH_DIM", "256"))
    B = int(os.environ.get("BENCH_MB", "8"))
    L = 2 * P  # total depth fixed; V=1 puts 2 layers/stage, V=2 puts 1
    mesh = make_mesh({"pp": P})
    rng = np.random.default_rng(0)

    def layer(w, x):
        return x + jnp.tanh(x @ w)

    weights = [
        np.asarray(rng.normal(size=(D, D)) * 0.2, np.float32) for _ in range(L)
    ]
    mb = np.asarray(rng.normal(size=(M, B, D)), np.float32)

    results = {}
    for V in (1, 2):
        per_stage = L // (P * V)
        groups = [
            {f"w{j}": weights[s * per_stage + j] for j in range(per_stage)}
            for s in range(P * V)
        ]

        def stage_fn(params, x, _n=per_stage):
            for j in range(_n):
                x = layer(params[f"w{j}"], x)
            return x

        stacked = stack_stage_params(groups, virtual_stages=V)
        fn = jax.jit(
            lambda sp, x, _V=V: pipeline_apply(
                stage_fn, sp, x, mesh, virtual_stages=_V
            )
        )
        out = fn(stacked, mb)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        steps = 20
        for _ in range(steps):
            out = fn(stacked, mb)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / steps
        ticks = schedule_ticks(M, P, V)
        busy = M * V  # per-device busy ticks (each 1/V the work of V=1 ticks)
        results[f"v{V}"] = {
            "ms": round(dt * 1e3, 2),
            "ticks": ticks,
            "bubble_frac": round((ticks - busy) / ticks, 3),
        }

    print(json.dumps({
        "metric": "pipeline_gpipe_vs_interleaved",
        "pp": P, "microbatches": M, "layers": L,
        **results,
        "speedup_v2_over_v1": round(
            results["v1"]["ms"] / results["v2"]["ms"], 3
        ),
        # On real parallel devices a tick at V is 1/V the work of a V=1
        # tick, so wall-clock ∝ ticks/V: this is the schedule-level win the
        # single-core CPU mesh cannot show (it serializes all devices, so
        # total work + per-tick overhead dominate there).
        "ideal_parallel_speedup_v2": round(
            results["v1"]["ticks"] / (results["v2"]["ticks"] / 2), 3
        ),
        "backend": jax.default_backend(),
    }))


def memory_mode():
    """Measured peak temp memory of the compiled pipelined train step, per
    schedule. One JSON line; see module docstring."""
    import jax

    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from distkeras_tpu.models.bert import BertConfig, EncoderLayer
    from distkeras_tpu.parallel.mesh import make_mesh
    from distkeras_tpu.parallel.pipeline import (
        pipeline_apply,
        schedule_ticks,
        stack_stage_params,
    )

    P = int(os.environ.get("BENCH_PP", str(len(jax.devices()))))
    M = int(os.environ.get("BENCH_MICRO", "8"))
    D = int(os.environ.get("BENCH_DIM", "128"))
    S = int(os.environ.get("BENCH_SEQ", "64"))
    B_mb = int(os.environ.get("BENCH_MB", "4"))  # rows per microbatch
    mesh = make_mesh({"pp": P})
    cfg = BertConfig(
        vocab_size=64, hidden_size=D, num_heads=max(2, D // 64),
        mlp_dim=4 * D, max_seq_len=S, num_layers=2 * P, dtype=jnp.float32,
    )
    layer_mod = EncoderLayer(cfg)
    x_one = jnp.zeros((B_mb, S, D), jnp.float32)
    layer_params = [
        jax.tree.map(
            lambda m: m.unbox() if hasattr(m, "unbox") else m,
            layer_mod.init(jax.random.PRNGKey(i), x_one)["params"],
        )
        for i in range(2 * P)
    ]
    mb = np.zeros((M, B_mb, S, D), np.float32)
    state_bytes = B_mb * S * D * 4  # one microbatch activation, f32

    results = {}
    for V in (1, 2):
        per_stage = (2 * P) // (P * V)
        groups = [
            {
                f"sub_{j}": layer_params[s * per_stage + j]
                for j in range(per_stage)
            }
            for s in range(P * V)
        ]
        stacked = stack_stage_params(groups, virtual_stages=V)
        ticks = schedule_ticks(M, P, V)

        for remat in (False, True):
            def base_fn(params, x, _n=per_stage):
                for j in range(_n):
                    x = layer_mod.apply({"params": params[f"sub_{j}"]}, x)
                return x

            stage_fn = jax.checkpoint(base_fn) if remat else base_fn

            def loss(sp, x, _V=V, _fn=stage_fn):
                y = pipeline_apply(_fn, sp, x, mesh, virtual_stages=_V)
                return jnp.sum(y * y)

            compiled = jax.jit(jax.grad(loss)).lower(stacked, mb).compile()
            ma = compiled.memory_analysis()
            key = f"v{V}_{'remat' if remat else 'plain'}"
            results[key] = {
                "measured_temp_mb": round(ma.temp_size_in_bytes / 2**20, 2),
                "args_mb": round(ma.argument_size_in_bytes / 2**20, 2),
                "ticks": ticks,
                # Scan-autodiff floor: every tick's carried state is saved
                # for the backward (remat removes the per-layer internals,
                # not the carries).
                "analytic_saved_state_mb": round(
                    ticks * state_bytes / 2**20, 2
                ),
            }

    # --- true 1F1B (hand-rolled backward, parallel/pipeline_1f1b.py) ----
    # Same per-stage depth as the V=1 schedules (2 layers/stage), head +
    # loss fused into the last stage as the engine requires.
    from distkeras_tpu.parallel.pipeline_1f1b import (
        pipeline_1f1b_value_and_grad,
        ticks_1f1b,
    )

    def stage2(params, x):
        for j in range(2):
            x = layer_mod.apply({"params": params[f"sub_{j}"]}, x)
        return x

    def last_fn(params, hp, x, labels_mb):
        y = stage2(params, x)
        return jnp.sum((y @ hp["w"] - labels_mb) ** 2)

    head = {"w": np.zeros((D, 8), np.float32)}
    labels = np.zeros((M, B_mb, S, 8), np.float32)
    groups2 = [
        {f"sub_{j}": layer_params[s * 2 + j] for j in range(2)}
        for s in range(P)
    ]
    stacked2 = stack_stage_params(groups2)
    compiled = jax.jit(
        lambda sp, hp, x, y: pipeline_1f1b_value_and_grad(
            stage2, last_fn, sp, hp, x, y, mesh
        )
    ).lower(stacked2, head, mb, labels).compile()
    ma = compiled.memory_analysis()
    # Hop accounting (round 5): the phase-split scan elides the fill
    # phase's P cotangent hops and the drain phase's P activation hops —
    # each direction permutes on 2M+P-2 of the 2M+2P-2 ticks instead of
    # all of them.
    t1f = ticks_1f1b(M, P)
    hops = 2 * M + P - 2
    results["true_1f1b"] = {
        "measured_temp_mb": round(ma.temp_size_in_bytes / 2**20, 2),
        "args_mb": round(ma.argument_size_in_bytes / 2**20, 2),
        "ticks": ticks_1f1b(M, P),
        "ppermute_hops_per_dir": hops,
        "ppermute_hops_elided": t1f - hops,
        "hop_bytes_saved_per_step_mb": round(
            (t1f - hops) * state_bytes * 2 / 2**20, 2
        ),
        # The ring holds <= P in-flight microbatch inputs per device; the
        # carry also holds ONE M-sized f32 input-cotangent buffer
        # (cot_out), so the floor is (min(P, M) + M) states — linear in M
        # with a far smaller constant than the scanned schedules' per-tick
        # saves (ticks ~ 2M states each, times stage internals).
        "analytic_saved_state_mb": round(
            (min(P, M) + M) * state_bytes / 2**20, 2
        ),
    }

    # --- interleaved-V2 vs 1F1B-at-2M: the equal-bubble comparison ------
    # Schedule fact: 1F1B at M'=2M has bubble (P-1)/(2M+P-1) — EXACTLY the
    # V=2 interleaved schedule's fraction at M. Both do remat-equivalent
    # compute (one recompute per stage application), so measuring 1F1B's
    # temp at 2M against v2_remat's at M compares the two bubble-reduction
    # strategies (interleave chunks vs raise M under a flat-memory
    # schedule) at equal pipeline efficiency. This is the measured case
    # for keeping V>1 on the scanned schedule only:
    # doubling M under 1F1B costs ~one extra cot_out buffer; interleaving
    # under scan-autodiff costs the whole tick-state save.
    mb2 = np.zeros((2 * M, B_mb, S, D), np.float32)
    labels2 = np.zeros((2 * M, B_mb, S, 8), np.float32)
    compiled = jax.jit(
        lambda sp, hp, x, y: pipeline_1f1b_value_and_grad(
            stage2, last_fn, sp, hp, x, y, mesh
        )
    ).lower(stacked2, head, mb2, labels2).compile()
    ma2 = compiled.memory_analysis()
    bubble = round((P - 1) / (2 * M + P - 1), 3)
    results["schedule_tradeoff_equal_bubble"] = {
        "bubble_frac": bubble,
        "v2_remat_at_M_temp_mb": results["v2_remat"]["measured_temp_mb"],
        "true_1f1b_at_2M_temp_mb": round(ma2.temp_size_in_bytes / 2**20, 2),
        "memory_ratio": round(
            results["v2_remat"]["measured_temp_mb"]
            / max(1e-9, ma2.temp_size_in_bytes / 2**20), 2
        ),
        "note": "same bubble, same remat-equivalent compute: raising M "
                "under 1F1B beats interleaving V under scan-autodiff on "
                "memory; interleaved-1F1B only pays when M is capped by "
                "the global batch (see docs/parallel.md)",
    }

    # --- MoE x ep 1F1B (round 5: the composed flagship) -----------------
    # Same measurement for the hand-rolled schedule with an MoE trunk and
    # experts sharded over ep (pp x ep mesh): the flat-in-M claim must
    # survive the composition, so we compile at M and 2M and report both,
    # plus the gpipe-autodiff equivalent at M for contrast.
    if P % 2 == 0:
        from distkeras_tpu.parallel.pipeline_1f1b import (
            pipeline_1f1b_value_and_grad,
        )
        from jax.sharding import NamedSharding

        ep = 2
        pp_moe = P // ep
        mesh_moe = make_mesh({"pp": pp_moe, "ep": ep})
        cfg_moe = BertConfig(
            vocab_size=64, hidden_size=D, num_heads=max(2, D // 64),
            mlp_dim=4 * D, max_seq_len=S, num_layers=2 * pp_moe,
            dtype=jnp.float32, moe_experts=4,
        )
        from flax import linen as fnn

        full_layer = EncoderLayer(cfg_moe)  # full-E init (trainer parity)
        ep_layer = EncoderLayer(cfg_moe, ep_axis="ep", ep_size=ep)
        moe_params = [
            fnn.meta.unbox(
                full_layer.init(jax.random.PRNGKey(i), x_one)
            )["params"]
            for i in range(2 * pp_moe)
        ]
        groups_moe = [
            {f"sub_{j}": moe_params[s * 2 + j] for j in range(2)}
            for s in range(pp_moe)
        ]
        stacked_moe = stack_stage_params(groups_moe)

        from distkeras_tpu.parallel.pipeline import stage_param_specs

        specs_moe = stage_param_specs(stacked_moe, ep_size=ep)
        stacked_moe = jax.tree.map(
            lambda v, s: jax.device_put(v, NamedSharding(mesh_moe, s)),
            stacked_moe, specs_moe,
        )

        def moe_stage(params, x):
            aux = jnp.float32(0.0)
            for j in range(2):
                x, st = ep_layer.apply(
                    {"params": params[f"sub_{j}"]}, x, mutable=["aux_loss"]
                )
                aux = aux + sum(
                    jnp.sum(v) for v in jax.tree.leaves(st["aux_loss"])
                )
            return x, aux

        def moe_last(params, hp, x, labels_mb):
            y, aux = moe_stage(params, x)
            return jnp.sum((y @ hp["w"] - labels_mb) ** 2), aux

        head_moe = {"w": np.zeros((D, 8), np.float32)}
        for tag, M_i in (("moe_1f1b", M), ("moe_1f1b_2m", 2 * M)):
            mb_i = np.zeros((M_i, B_mb, S, D), np.float32)
            lab_i = np.zeros((M_i, B_mb, S, 8), np.float32)
            compiled = jax.jit(
                lambda sp, hp, x, y: pipeline_1f1b_value_and_grad(
                    moe_stage, moe_last, sp, hp, x, y, mesh_moe,
                    param_specs=specs_moe, stage_aux_seed=0.01,
                )
            ).lower(stacked_moe, head_moe, mb_i, lab_i).compile()
            ma = compiled.memory_analysis()
            results[tag] = {
                "measured_temp_mb": round(ma.temp_size_in_bytes / 2**20, 2),
                "microbatches": M_i,
                "ticks": ticks_1f1b(M_i, pp_moe),
            }

        # gpipe-autodiff contrast at M (same trunk, scanned schedule).
        def gpipe_moe_loss(sp, hp, x, y):
            out, aux = pipeline_apply(
                moe_stage, sp, x, mesh_moe, with_aux=True,
                param_specs=specs_moe,
            )
            return (
                jnp.sum((out @ hp["w"] - y) ** 2) + 0.01 * aux
            )

        mb_m = np.zeros((M, B_mb, S, D), np.float32)
        lab_m = np.zeros((M, B_mb, S, 8), np.float32)
        compiled = jax.jit(
            jax.grad(gpipe_moe_loss)
        ).lower(stacked_moe, head_moe, mb_m, lab_m).compile()
        ma = compiled.memory_analysis()
        results["moe_gpipe_plain"] = {
            "measured_temp_mb": round(ma.temp_size_in_bytes / 2**20, 2),
            "microbatches": M,
        }

    print(json.dumps({
        "metric": "pipeline_activation_memory",
        "pp": P, "microbatches": M, "layers": 2 * P, "hidden": D,
        "seq": S, "microbatch_rows": B_mb,
        "state_bytes_per_microbatch": state_bytes,
        **results,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    if os.environ.get("BENCH_MODE") == "memory":
        memory_mode()
    else:
        main()
