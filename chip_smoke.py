#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of models the zoo already has (random weights from --seed):

1. *serve*  — ``python -m distkeras_tpu.run serve --model gpt_small``
   in a child process; this process is only a ``ServingClient``.
2. *train*  — ``dk.SingleTrainer(bert_base_mlm(seq_len=512))``, then the same
   model on the Pallas kernels (flash attention + fused softmax-xent), whose
   compiled step must contain ``tpu_custom_call``.
3. *async*  — ``dk.ADAG(mnist_mlp(), num_workers=2)``, the paper's identity.

``--chips 4`` runs none of those; it runs what exists only across chips:
``SynchronousDistributedTrainer`` dp=4 against a one-device reference, then
a tp=4 ``ServingEngine`` against the unsharded engine.

A chip belongs to one process. This parent never initializes a JAX backend:
every phase is a child process, one at a time. Each child's first act is
``jax.devices()`` and it refuses to go on unless the platform is ``tpu``.
There is no fallback: without a chip the script exits non-zero.
``--rehearse`` walks the same control flow at toy size on whatever platform
is there (the CPU, in a sandbox) and ALWAYS ends ``"ok": false`` — a
rehearsal is never a pass.

Last stdout line, and only that line, is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1140.0  # the contract allows 1200 s, compilation included

# Real sizes, and the toy sizes --rehearse swaps in. Shapes that decide
# control flow (prompt lengths, steps, batch) are the same in both.
REAL = {
    "lm": "gpt_small", "lm_args": {}, "lm_vocab": 50257, "kv_pool_mb": 1024,
    "bert": "bert_base_mlm", "bert_vocab": 30522, "seq": 512,
    "tp_vocab": 50304,  # 50257 does not divide tp=4; the engine refuses it
}
TOY = {
    "lm": "gpt_tiny", "lm_args": {"seq_len": 512}, "lm_vocab": 1024,
    "kv_pool_mb": 8, "bert": "bert_tiny_mlm", "bert_vocab": 1024, "seq": 128,
    "tp_vocab": 1024,
}
NEW_TOKENS = 32
PROMPT_LENS = (16, 120, 300, 100)  # prefill buckets 16, 128, 512 — three
SHARED_PREFIX = 288  # of the 300-token prompt: 18 whole 16-token KV blocks


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def agree(a: list, b: list) -> int:
    """Length of the common prefix of two token lists."""
    return len(list(itertools.takewhile(lambda ab: ab[0] == ab[1],
                                        zip(a, b))))


# --------------------------------------------------------------------------
# Children: everything below here that imports jax runs in a child process.
# --------------------------------------------------------------------------

def claim_device(phase: str, rehearse: bool, want: int = 1) -> dict:
    """A child's first act: take the device, say what it is, and refuse
    anything but a TPU (a rehearsal goes on, and is failed by the parent)."""
    from distkeras_tpu.utils.platform import (
        ensure_virtual_cpu_flags, use_compile_cache,
    )

    if rehearse and want > 1:  # virtual CPU devices stand in for the host
        ensure_virtual_cpu_flags(want)
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    say(phase, f"device {json.dumps(info)} compile_cache={cache}")
    if len(devices) < want:
        raise SystemExit(f"[{phase}] needs {want} devices, found {len(devices)}")
    if info["platform"] != "tpu" and not rehearse:
        raise SystemExit(f"[{phase}] platform is {info['platform']!r}, not "
                         f"'tpu': refusing to run (no fallback)")
    return info


def make_spy():
    """A RecompileAuditor that also looks at the step the trainer really
    runs: times the first (compiling) call, and keeps the arguments'
    abstract values and devices so the caller can lower the same step again
    for its HLO. The trainers' ``auditor=`` hook is the way in."""
    import jax

    from distkeras_tpu.telemetry import RecompileAuditor

    class Spy(RecompileAuditor):
        first_call_s = None
        avals = None
        step = None
        devices = None  # {"opt_state": {...}, "batch": {...}}

        def wrap(self, fn, name):
            audited = super().wrap(fn, name)
            self.step = audited

            def call(state, batch):
                if self.avals is not None:
                    return audited(state, batch)
                # Placed as the trainer placed them (an uncommitted array
                # stays unplaced), so hlo() lowers the very program the
                # trainer ran and finds it in the compile cache.
                self.avals = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype,
                        sharding=a.sharding if a.committed else None),
                    (state, batch))
                self.devices = {
                    "opt_state": {d for leaf in jax.tree.leaves(
                        state.opt_state) for d in leaf.sharding.device_set},
                    "batch": {d for leaf in jax.tree.leaves(batch)
                              for d in leaf.sharding.device_set},
                }
                t0 = time.perf_counter()
                out = audited(state, batch)
                jax.block_until_ready(out[1]["loss"])
                self.first_call_s = time.perf_counter() - t0
                return out

            return call

        def hlo(self) -> str:
            return self.step.lower(*self.avals).compile().as_text()

        def state_bytes(self) -> int:
            return sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(self.avals[0]))

    return Spy()


def token_dataset(rows: int, seq: int, vocab: int, seed: int):
    """Seed-made MLM data (examples/bert_mlm.py's recipe) over a Zipfian
    vocabulary, so a few steps have a unigram distribution to learn."""
    import numpy as np

    import distkeras_tpu as dk

    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab)
    tokens = rng.choice(np.arange(1, vocab), size=(rows, seq), p=p / p.sum())
    corrupted = np.where(rng.random((rows, seq)) < 0.15, 0, tokens)
    return dk.Dataset.from_arrays(features=corrupted.astype(np.int32),
                                  label=tokens.astype(np.int32))


def run_trainer(phase: str, tag: str, trainer, spy, ds) -> dict:
    """trainer.train(ds) with the compile time told apart from the run."""
    import numpy as np

    t0 = time.perf_counter()
    trainer.train(ds)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in trainer.get_history()]
    name = next(iter(spy.report()))
    compiles = spy.compiles(name)
    say(phase, f"{tag}: steps={len(losses)} loss {losses[0]:.4f} -> "
               f"{losses[-1]:.4f} compiles({name})={compiles} "
               f"train() wall {wall:.1f}s, of which the first step "
               f"(compile included) {spy.first_call_s:.1f}s")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{tag}: non-finite loss {losses}")
    if compiles != 1:
        raise AssertionError(f"{tag}: step compiled {compiles} times")
    return {"losses": losses, "compile_s": spy.first_call_s, "wall_s": wall}


def bert_models(cfg: dict):
    """(plain, kernel) — the same widths; the second on the Pallas flash
    kernel, built through models.bert._make as examples/pipeline_gpt.py does."""
    import dataclasses

    from distkeras_tpu.models import bert

    plain = getattr(bert, cfg["bert"])(seq_len=cfg["seq"])
    kernel = bert._make(
        dataclasses.replace(plain.config, use_flash_attention=True),
        cfg["seq"], plain.name + "_flash")
    return plain, kernel


def phase_train(cfg: dict, rehearse: bool, seed: int) -> dict:
    info = claim_device("train", rehearse)
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.data import native
    from distkeras_tpu.telemetry.device import device_memory

    say("train", f"data/native.py: {'native .so' if native.available() else 'pure Python'}")
    batch, steps, kernel_steps = 16, 12, 2
    seq, vocab = cfg["seq"], cfg["bert_vocab"]
    ds = token_dataset(batch * steps, seq, vocab, seed)
    plain_model, kernel_model = bert_models(cfg)
    common = dict(worker_optimizer="adam", learning_rate=1e-4,
                  batch_size=batch, num_epoch=1, seed=seed)

    spy = make_spy()
    plain = run_trainer(
        "train", f"{plain_model.name} seq={seq} batch={batch} plain",
        dk.SingleTrainer(plain_model, loss="categorical_crossentropy",
                         auditor=spy, **common), spy, ds)
    losses = plain["losses"]
    third = max(1, len(losses) // 3)
    if not sum(losses[-third:]) < sum(losses[:third]):
        raise AssertionError(f"loss did not decrease on average: {losses}")
    on = {d.platform for d in spy.devices["opt_state"]}
    mem = device_memory(jax.devices()[0])
    say("train", f"state on {sorted(on)} ({spy.state_bytes() / 2**20:.0f} MiB "
                 f"of params+optimizer); device peak "
                 f"{mem.peak_bytes_in_use} bytes in use")
    if on != {info["platform"]}:
        raise AssertionError(f"train state lives on {on}")
    if mem.available and mem.peak_bytes_in_use < spy.state_bytes():
        raise AssertionError("device peak memory is below the state's size")

    # Same widths, same seed, same first batches — on the kernels.
    spy_k = make_spy()
    ds_k = ds.take(batch * kernel_steps)
    kern = run_trainer(
        "train", f"{kernel_model.name} seq={seq} batch={batch} "
                 f"flash+fused_xent",
        dk.SingleTrainer(kernel_model, loss="fused_categorical_crossentropy",
                         auditor=spy_k, **common), spy_k, ds_k)
    gap = abs(kern["losses"][0] - losses[0])
    say("train", f"first-step loss plain {losses[0]:.4f} vs kernels "
                 f"{kern['losses'][0]:.4f} (|diff| {gap:.4f})")
    if gap > 0.05:  # bf16 activations, f32 loss: ~0.5% of a loss near 10
        raise AssertionError("kernel variant's loss departs from plain")
    t0 = time.perf_counter()
    calls = spy_k.hlo().count("tpu_custom_call")
    say("train", f"kernel step HLO: tpu_custom_call x{calls} "
                 f"(lowered again in {time.perf_counter() - t0:.1f}s)")
    if calls == 0 and not rehearse:
        raise AssertionError("no tpu_custom_call in the compiled kernel "
                             "step: the Pallas kernels fell to interpret")
    compile_s = plain["compile_s"] + kern["compile_s"]
    return {**info, "compile_s": round(compile_s, 1),
            "run_s": round(plain["wall_s"] + kern["wall_s"] - compile_s, 1)}


def phase_async(cfg: dict, rehearse: bool, seed: int) -> dict:
    info = claim_device("async", rehearse)
    import jax
    import numpy as np

    import distkeras_tpu as dk
    from distkeras_tpu.models import mnist_mlp

    rng = np.random.default_rng(seed)
    protos = rng.uniform(0, 1, size=(10, 784))
    labels = rng.integers(0, 10, size=8192)
    x = np.clip(protos[labels] + rng.normal(0, 0.25, size=(8192, 784)), 0, 1)
    ds = dk.Dataset.from_arrays(features=x.astype(np.float32),
                                label=labels.astype(np.float32))
    train, test = ds.split(0.9, seed=seed)
    workers = 2
    devices = jax.local_devices()
    say("async", "ADAG workers -> " + ", ".join(
        f"w{i}:{devices[i % len(devices)]}" for i in range(workers)))
    trainer = dk.ADAG(mnist_mlp(), num_workers=workers,
                      worker_optimizer="adam", learning_rate=3e-3,
                      loss="categorical_crossentropy", batch_size=64,
                      num_epoch=1, seed=seed)
    t0 = time.perf_counter()
    trained = trainer.train(train)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in trainer.get_history()]
    test = dk.ModelPredictor(trained).predict(test)
    test = dk.LabelIndexTransformer(input_col="prediction").transform(test)
    acc = dk.AccuracyEvaluator(prediction_col="prediction_index",
                               label_col="label").evaluate(test)
    say("async", f"ADAG mnist_mlp: steps={len(losses)} loss {losses[0]:.4f} "
                 f"-> {losses[-1]:.4f} test_accuracy={acc:.4f} "
                 f"wall={wall:.1f}s (compile included)")
    if not np.all(np.isfinite(losses)) or acc < 0.9:
        raise AssertionError(f"ADAG did not learn: accuracy {acc}")
    return {**info, "run_s": round(wall, 1)}


def phase_multichip(cfg: dict, rehearse: bool, seed: int) -> dict:
    info = claim_device("dp4", rehearse, want=4)
    import jax
    import numpy as np

    import distkeras_tpu as dk
    from distkeras_tpu.parallel.mesh import make_mesh, serving_mesh

    devices = jax.devices()[:4]
    seq, vocab = cfg["seq"], cfg["bert_vocab"]
    global_batch, steps = 32, 4
    ds = token_dataset(global_batch * steps, seq, vocab, seed)
    model = bert_models(cfg)[0]
    common = dict(worker_optimizer="adam", learning_rate=1e-4,
                  loss="categorical_crossentropy", num_epoch=1, seed=seed)

    spy4 = make_spy()
    dp4 = run_trainer(
        "dp4", f"{model.name} seq={seq} global_batch={global_batch} dp=4",
        dk.SynchronousDistributedTrainer(
            model, mesh=make_mesh({"dp": 4}, devices=devices),
            batch_size=global_batch // 4, auditor=spy4, **common), spy4, ds)
    for what, devs in spy4.devices.items():
        say("dp4", f"{what} has shards on {sorted(str(d) for d in devs)}")
        if len(devs) != 4:
            raise AssertionError(f"{what} is on {len(devs)} devices, not 4")
    reduces = spy4.hlo().count("all-reduce")
    say("dp4", f"dp=4 step HLO: all-reduce x{reduces}")
    if reduces == 0:
        raise AssertionError("no all-reduce in the dp=4 step")

    # The reference: the same global batch and seed on ONE device (the
    # chip's compiler counts 11.5 GiB for it, inside one chip's 16 GB).
    spy1 = make_spy()
    one = run_trainer(
        "dp4", f"{model.name} seq={seq} batch={global_batch} one device",
        dk.SingleTrainer(model, batch_size=global_batch, auditor=spy1,
                         **common), spy1, ds)
    rel = [abs(a - b) / abs(b) for a, b in zip(dp4["losses"], one["losses"])]
    say("dp4", f"loss dp=4 {[round(v, 4) for v in dp4['losses']]} vs one "
               f"device {[round(v, 4) for v in one['losses']]} "
               f"(max rel diff {max(rel):.4f})")
    if max(rel) > 0.02:  # dropout bits differ between the two lowerings
        raise AssertionError("dp=4 loss departs from the one-device run")

    # tp=4 serving against the unsharded engine. Greedy tokens at random
    # bf16 weights are a fragile witness (near-tied logits break on the
    # last rounding, and a sharded matmul sums in another order), so the
    # verdict is the repo's own scoring verb: both engines score the same
    # sequences, teacher-forced, and the log-probs must agree to bf16
    # tolerance — a sharding or position bug is off by whole units.
    from distkeras_tpu.models import bert
    from distkeras_tpu.serving import ServingEngine

    lm = getattr(bert, cfg["lm"])(seq_len=512, vocab_size=cfg["tp_vocab"])
    variables = lm.init(seed)
    rng = random.Random(seed)
    prompts = [[rng.randrange(1, cfg["tp_vocab"]) for _ in range(n)]
               for n in PROMPT_LENS]

    def serve(mesh, score_seqs=None):
        engine = ServingEngine(lm, variables, slots=8, mesh=mesh,
                               kv_pool_mb=cfg["kv_pool_mb"],
                               kv_block_tokens=16)

        async def go():
            task = asyncio.create_task(engine.run())
            try:
                reqs = [engine.submit(p, NEW_TOKENS) for p in prompts]
                tokens = [await r.result() for r in reqs]
                seqs = score_seqs or [p + t for p, t in zip(prompts, tokens)]
                scored = [engine.submit(q, 0, kind="score") for q in seqs]
                for r in scored:
                    await r.result()
                return tokens, seqs, [r.logprobs for r in scored]
            finally:
                engine.shutdown(drain=True)
                await task

        t0 = time.perf_counter()
        tokens, seqs, logprobs = asyncio.run(go())
        return (tokens, seqs, logprobs, engine.mesh_info(),
                time.perf_counter() - t0)

    plain, seqs, lp_one, _, t_one = serve(None)
    sharded, _, lp_tp, mesh_info, t_tp = serve(
        serving_mesh({"tp": 4}, devices=devices), seqs)
    worst = max(abs(a - b) for x, y in zip(lp_tp, lp_one)
                for a, b in zip(x, y))
    same = [agree(a, b) for a, b in zip(sharded, plain)]
    say("tp4", f"{lm.name} vocab={cfg['tp_vocab']} tp=4 on "
               f"{mesh_info['devices']}: {len(prompts)} prompts x "
               f"{NEW_TOKENS} greedy tokens + scoring in {t_tp:.1f}s; "
               f"unsharded {t_one:.1f}s (both compile included)")
    say("tp4", f"log-probs of {sum(map(len, lp_one))} teacher-forced "
               f"positions: max |tp=4 - unsharded| = {worst:.4f}; greedy "
               f"tokens agree on the first {same} of {NEW_TOKENS}")
    if any(len(t) != NEW_TOKENS for t in sharded + plain):
        raise AssertionError("a tp=4 request came back short")
    if not worst <= 0.1:  # ~1% of a log-prob near -10.8
        raise AssertionError("tp=4 log-probs depart from the unsharded "
                             "engine")
    return {**info,
            "compile_s": round(dp4["compile_s"] + one["compile_s"], 1)}


CHILD_PHASES = {"train": phase_train, "async": phase_async,
                "multichip": phase_multichip}


def child_main(phase: str, rehearse: bool, seed: int) -> int:
    try:
        report = CHILD_PHASES[phase](TOY if rehearse else REAL, rehearse, seed)
    except (AssertionError, SystemExit) as e:
        say(phase, f"FAILED: {e}")
        print(json.dumps({"phase": phase, "ok": False, "error": str(e)}),
              flush=True)
        return 1
    print(json.dumps({"phase": phase, "ok": True, **report}), flush=True)
    return 0


# --------------------------------------------------------------------------
# Parent: starts children, talks to the server over TCP, never touches jax.
# --------------------------------------------------------------------------

def run_child(phase: str, rehearse: bool, seed: int,
              timeout_s: float) -> dict:
    """Run one phase as ``chip_smoke.py --phase``; echo its lines; return
    the JSON report on its last line (killed at ``timeout_s``)."""
    argv = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
            "--phase", phase, "--seed", str(seed)]
    if rehearse:
        argv.append("--rehearse")
    t0 = time.perf_counter()
    proc = subprocess.Popen(argv, cwd=HERE, stdout=subprocess.PIPE, text=True)
    killer = threading.Timer(timeout_s, proc.kill)
    killer.start()
    last = ""
    try:
        for line in proc.stdout:
            last = line.rstrip("\n")
            print(last, flush=True)
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    try:
        report = json.loads(last)
    except ValueError:
        report = {}
    if not isinstance(report, dict) or report.get("phase") != phase:
        report = {"phase": phase, "ok": False,
                  "error": f"child ended rc={rc} with no report (timeout "
                           f"{timeout_s:.0f}s)"}
    report["ok"] = bool(report.get("ok")) and rc == 0
    report["wall_s"] = round(time.perf_counter() - t0, 1)
    return report


async def serve_phase(cfg: dict, rehearse: bool, seed: int,
                      timeout_s: float) -> dict:
    """Start the server the way a user does, be its client, SIGTERM it."""
    from distkeras_tpu.serving import wire
    from distkeras_tpu.serving.client import ServingClient

    say("serve", f"serving/wire.py: "
                 f"{'native .so' if wire.native_available() else 'pure Python'}")
    t_start = time.perf_counter()
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "distkeras_tpu.run", "serve",
        "--model", cfg["lm"], "--model-args", json.dumps(cfg["lm_args"]),
        "--kv-pool-mb", str(cfg["kv_pool_mb"]),
        "--kv-block-tokens", "16", "--slots", "8", "--port", "0",
        "--seed", str(seed), "--audit-recompiles", "report",
        cwd=HERE, stdout=asyncio.subprocess.PIPE)
    report = {"phase": "serve", "ok": False}
    try:
        line = await asyncio.wait_for(proc.stdout.readline(), timeout_s)
        banner = json.loads(line)
        report.update(platform=banner["platform"], kind=banner["device_kind"],
                      count=banner["device_count"])
        say("serve", f"banner after {time.perf_counter() - t_start:.1f}s: "
                     f"{line.decode().strip()}")
        if banner["platform"] != "tpu" and not rehearse:
            raise AssertionError(f"server is on {banner['platform']!r}, not "
                                 f"'tpu': refusing to run (no fallback)")
        rng = random.Random(seed)

        def prompt(n):
            return [rng.randrange(1, cfg["lm_vocab"]) for _ in range(n)]

        async def wave(tag, prompts):
            async def one(p):
                async with ServingClient(banner["host"], banner["port"]) as c:
                    return await c.generate(p, NEW_TOKENS, timeout=timeout_s)

            t0 = time.perf_counter()
            done = await asyncio.wait_for(
                asyncio.gather(*(one(p) for p in prompts)), timeout_s)
            wall = time.perf_counter() - t0
            for rec in done:
                if not rec.get("done") or len(rec["tokens"]) != NEW_TOKENS:
                    raise AssertionError(f"{tag}: request not done: {rec}")
            ttft = sorted(rec["ttft_ms"] for rec in done)
            say("serve", f"{tag}: {len(done)} requests done, prompts "
                         f"{[len(p) for p in prompts]} x {NEW_TOKENS} new "
                         f"tokens, wall {wall:.2f}s, "
                         f"{len(done) * NEW_TOKENS / wall:.1f} tokens/s, "
                         f"TTFT ms min/median/max {ttft[0]:.1f}/"
                         f"{ttft[len(ttft) // 2]:.1f}/{ttft[-1]:.1f} on "
                         f"{banner['platform']} {banner['device_kind']}")
            return done, wall

        first = [prompt(n) for n in PROMPT_LENS]
        cold, cold_s = await wave("cold wave (compile included)", first)
        _, warm_s = await wave("warm wave (fresh prompts, same lengths)",
                               [prompt(n) for n in PROMPT_LENS])
        # The 300-token prompt again, beside a sibling that shares its
        # first 288 tokens: both now splice cached KV blocks and prefill
        # only a 12-token tail. Then once more: the same path twice must
        # give the same greedy tokens. The cold run took another path (one
        # 512-wide prefill), and at random weights in bf16 near-tied logits
        # may break differently there — told, not asserted (in float32 the
        # two paths agree token for token).
        longest = PROMPT_LENS.index(max(PROMPT_LENS))
        sibling = first[longest][:SHARED_PREFIX] + prompt(
            PROMPT_LENS[longest] - SHARED_PREFIX)
        again, _ = await wave("repeat + shared-prefix wave",
                              [first[longest], sibling])
        once_more, _ = await wave("repeat wave", [first[longest], prompt(16)])
        if again[0]["tokens"] != once_more[0]["tokens"]:
            raise AssertionError("greedy output of a repeated prompt differs")
        same = agree(cold[longest]["tokens"], again[0]["tokens"])
        say("serve", f"greedy output of the repeated prompt is identical; "
                     f"the cold full-prefill run agrees with it on the "
                     f"first {same} of {NEW_TOKENS} tokens")

        async with ServingClient(banner["host"], banner["port"]) as c:
            health = await c.healthz()
        say("serve", f"healthz: decode_compile_count="
                     f"{health['decode_compile_count']} device_memory="
                     f"{json.dumps(health['device_memory'])}")
        if health["decode_compile_count"] != 1:
            raise AssertionError("decode step did not compile exactly once")

        proc.send_signal(signal.SIGTERM)
        tail = await asyncio.wait_for(proc.stdout.read(), 60.0)
        rc = await asyncio.wait_for(proc.wait(), 60.0)
        summary = json.loads(tail.decode().strip().splitlines()[-1])
        audit = {k: v["compiles"] for k, v in
                 summary.get("recompile_audit", {}).items()}
        say("serve", f"SIGTERM -> drained, rc={rc}; compiles per program "
                     f"{json.dumps(audit)}; kv_pool "
                     f"{json.dumps(summary.get('kv_pool'))}")
        if rc != 0:
            raise AssertionError(f"server exited rc={rc} on SIGTERM")
        if summary["kv_pool"]["hit_requests"] < 3:
            raise AssertionError("the shared prefix was never served from "
                                 "the KV pool")
        report.update(ok=True, compile_s=round(cold_s - warm_s, 1),
                      run_s=round(warm_s, 2))
    except (AssertionError, asyncio.TimeoutError, OSError, ValueError,
            KeyError) as e:
        report["error"] = f"{type(e).__name__}: {e}"
        say("serve", f"FAILED: {report['error']}")
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    report["wall_s"] = round(time.perf_counter() - t_start, 1)
    return report


def parent_main(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "distkeras_tpu")):
        sys.exit("chip_smoke.py: no distkeras_tpu/ beside it — it runs from "
                 "the root of a checkout")
    # The driver's time limit arrives as SIGTERM: leave through the
    # finally blocks, which stop whatever child is running.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.perf_counter()
    cfg = TOY if args.rehearse else REAL

    def left() -> float:
        return max(5.0, BUDGET_S - (time.perf_counter() - t0))

    reports = []
    if args.chips == 4:
        reports.append(run_child("multichip", args.rehearse, args.seed,
                                 left()))
    else:
        reports.append(asyncio.run(
            serve_phase(cfg, args.rehearse, args.seed, min(420.0, left()))))
        reports.append(run_child("train", args.rehearse, args.seed,
                                 min(540.0, left())))
        reports.append(run_child("async", args.rehearse, args.seed,
                                 min(180.0, left())))

    from distkeras_tpu.utils.platform import jax_backends_live

    parent_clean = not jax_backends_live()
    for r in reports:
        times = " ".join(f"{k}={r[k + '_s']}s"
                         for k in ("wall", "compile", "run") if k + "_s" in r)
        print(f"[summary] {r['phase']}: {'PASS' if r['ok'] else 'FAIL'} "
              f"{times} device={r.get('platform')}/{r.get('kind')} "
              f"x{r.get('count')}"
              + (f" error={r['error']}" if r.get("error") else ""),
              flush=True)
    print(f"[summary] total wall {time.perf_counter() - t0:.1f}s; parent "
          f"initialized a JAX backend: {not parent_clean}", flush=True)
    seen = {(r.get("platform"), r.get("kind"), r.get("count"))
            for r in reports}
    platform, kind, count = next(iter(seen)) if len(seen) == 1 else (
        None, None, None)
    ok = (all(r["ok"] for r in reports) and parent_clean
          and platform == "tpu" and count == args.chips
          and not args.rehearse)
    if args.rehearse:
        print("[summary] --rehearse: a rehearsal is never a pass",
              flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip phase (dp=4 trainer "
                         "and tp=4 engine, each against its one-device "
                         "reference)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever platform is there; the "
                         "verdict is always ok=false")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)  # internal: run as a child
    args = ap.parse_args()
    if args.phase:
        return child_main(args.phase, args.rehearse, args.seed)
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
