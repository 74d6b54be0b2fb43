"""Pallas kernels on silicon: flash attention + fused xent vs XLA paths.

For each shape: verify numerics against the dense/XLA implementation, then
time forward AND forward+backward, and sweep flash block sizes. Prints ONE
JSON line. On CPU (``JAX_PLATFORMS=cpu``) the Pallas kernels run in
interpret mode — numbers are not meaningful there; run on the chip.

  python benchmarks/kernel_bench.py            # default shapes
  BENCH_SEQS=1024,4096 python benchmarks/kernel_bench.py
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def _bench(fn, *args, steps=10):
    out = fn(*args)
    import jax

    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3  # ms


def main():
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.attention import dot_product_attention
    from distkeras_tpu.ops.pallas.flash_attention import flash_attention
    from distkeras_tpu.ops.pallas.fused_xent import fused_softmax_xent

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.default_rng(0)
    report: dict = {"metric": "pallas_kernel_bench", "backend": backend}

    # ---- flash attention: numerics + fwd/bwd timings + block sweep --------
    default_seqs = "1024,2048,4096" if on_tpu else "128"  # interpret is slow
    seqs = [int(s) for s in os.environ.get("BENCH_SEQS", default_seqs).split(",")]
    B, H, D = (4, 8, 64) if on_tpu else (1, 2, 32)
    attn = []
    for S in seqs:
        q, k, v = (
            jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.2, dtype)
            for _ in range(3)
        )
        dense_f = jax.jit(lambda q, k, v: dot_product_attention(q, k, v))
        flash_f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
        # numerics: fwd + grads vs dense (bf16 tolerances)
        o_d, o_f = dense_f(q, k, v), flash_f(q, k, v)
        max_err = float(jnp.max(jnp.abs(o_d.astype(jnp.float32) - o_f.astype(jnp.float32))))
        g_d = jax.jit(jax.grad(lambda q, k, v: dense_f(q, k, v).astype(jnp.float32).sum()))(q, k, v)
        g_f = jax.jit(jax.grad(lambda q, k, v: flash_f(q, k, v).astype(jnp.float32).sum()))(q, k, v)
        grad_err = float(jnp.max(jnp.abs(g_d.astype(jnp.float32) - g_f.astype(jnp.float32))))

        dense_fb = jax.jit(jax.grad(lambda q: dense_f(q, k, v).astype(jnp.float32).sum()))
        flash_fb = jax.jit(jax.grad(lambda q: flash_f(q, k, v).astype(jnp.float32).sum()))
        entry = {
            "seq": S,
            "fwd_max_err": round(max_err, 5),
            "dq_max_err": round(grad_err, 5),
            "dense_fwd_ms": round(_bench(dense_f, q, k, v), 3),
            "flash_fwd_ms": round(_bench(flash_f, q, k, v), 3),
            "dense_fwdbwd_ms": round(_bench(dense_fb, q), 3),
            "flash_fwdbwd_ms": round(_bench(flash_fb, q), 3),
        }
        entry["fwd_speedup"] = round(entry["dense_fwd_ms"] / entry["flash_fwd_ms"], 2)
        entry["fwdbwd_speedup"] = round(
            entry["dense_fwdbwd_ms"] / entry["flash_fwdbwd_ms"], 2
        )
        attn.append(entry)
    report["flash_attention"] = attn

    # block-size sweep at the largest seq (128/128 is a guess).
    # TPU only — on CPU interpret mode the sweep measures the interpreter.
    if on_tpu:
        S = seqs[-1]
        q, k, v = (
            jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.2, dtype)
            for _ in range(3)
        )
        sweep = []
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if S % bq or S % bk:
                    continue
                f = jax.jit(
                    lambda q, k, v, bq=bq, bk=bk: flash_attention(
                        q, k, v, block_q=bq, block_k=bk
                    )
                )
                try:
                    sweep.append(
                        {"bq": bq, "bk": bk, "ms": round(_bench(f, q, k, v), 3)}
                    )
                except Exception as e:  # VMEM overflow etc. — record, go on
                    sweep.append({"bq": bq, "bk": bk, "error": str(e)[:80]})
        ok = [s for s in sweep if "ms" in s]
        if ok:
            best = min(ok, key=lambda s: s["ms"])
            report["flash_block_sweep"] = {"seq": S, "best": best, "grid": sweep}

    # ---- fused xent: numerics + fwd/bwd timings ---------------------------
    T, V = (8192, 30522) if on_tpu else (256, 1024)
    logits = jnp.asarray(rng.normal(size=(T, V)), dtype)
    labels = jnp.asarray(rng.integers(0, V, size=(T,)), jnp.int32)

    def plain(lg, lb):
        lg = lg.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(lg, lb[:, None], 1)[:, 0])

    plain_f = jax.jit(plain)
    fused_f = jax.jit(fused_softmax_xent)
    xent_err = float(jnp.abs(plain_f(logits, labels) - fused_f(logits, labels)))
    plain_fb = jax.jit(jax.grad(plain))
    fused_fb = jax.jit(jax.grad(fused_softmax_xent))
    report["fused_xent"] = {
        "tokens": T,
        "vocab": V,
        "loss_abs_err": round(xent_err, 6),
        "plain_fwd_ms": round(_bench(plain_f, logits, labels), 3),
        "fused_fwd_ms": round(_bench(fused_f, logits, labels), 3),
        "plain_fwdbwd_ms": round(_bench(plain_fb, logits, labels), 3),
        "fused_fwdbwd_ms": round(_bench(fused_fb, logits, labels), 3),
    }

    print(json.dumps(report))


if __name__ == "__main__":
    main()
