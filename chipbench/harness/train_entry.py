"""A training cell: the window drives the trainer's own ``train()`` —
feed, audit hook, history and all — not a bare jitted step.

The benchmark owns three things and nothing else of the loop: the dataset,
the model's init hook (so the weights are the seed's, made here) and the
``auditor`` the trainer wraps its step with. Through that one hook it
warms up, reads what ``correct`` compares, marks the window's start after
a blocked step, and ends the run once ``--seconds`` have passed.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from . import cell as cell_mod, compare, traffic as traffic_mod
from .program import claim_devices, memory_peak_bytes


RUN_AHEAD = 2  # steps the host may have in flight (the feed buffers 2)


class WindowClosed(Exception):
    """Raised from the step hook to leave ``trainer.train()``."""


def _first_moment(opt_state):
    """Adam's first moment, wherever the optimizer keeps it."""
    import jax

    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise SystemExit("chipbench: no single Adam first moment in the "
                         "optimizer's state")
    return found[0]


def make_hook(auditor_base, code, *, seed, sizes, seconds, warmup_steps,
              compare_steps, trace_dir, trace_steps):
    """The auditor the trainer gets: a RecompileAuditor whose wrapped step
    is also the benchmark's clock and its window."""
    import jax

    reference, _flat = code.reference, code.program.flat

    class Hook(auditor_base):
        def __init__(self):
            super().__init__()
            self.calls = 0
            self.t0 = None          # window start (host clock)
            self.t_end = None
            self.entries = []       # host time each window step was entered
            self.losses = []        # device scalars of the compared steps
            self.grad_norms = None  # first gradient, per leaf
            self.change_norms = None
            self.compiles_at_t0 = None
            self.last_loss = None
            self.traced_s = None
            self._trace_t0 = None
            self.step_name = None
            self.in_flight = collections.deque(maxlen=RUN_AHEAD)

        def wrap(self, fn, name):
            audited = super().wrap(fn, name)
            self.step_name = name

            def call(state, batch):
                n = self.calls
                self.calls += 1
                if self.t0 is not None:
                    # Hold the host to RUN_AHEAD steps in flight: the loss of
                    # an older step is ready long before it is waited for
                    # while the device is the bottleneck, and without the
                    # wait the window's close would trail the host's queue.
                    if len(self.in_flight) >= RUN_AHEAD:
                        jax.block_until_ready(self.in_flight.popleft())
                    now = time.perf_counter()
                    if self._trace_t0 is not None and \
                            len(self.entries) == trace_steps:
                        jax.block_until_ready(self.last_loss)
                        self.traced_s = time.perf_counter() - self._trace_t0
                        jax.profiler.stop_trace()
                        self._trace_t0 = None
                    if now - self.t0 >= seconds:
                        jax.block_until_ready(self.last_loss)
                        self.t_end = time.perf_counter()
                        raise WindowClosed
                    self.entries.append(now)
                out = audited(state, batch)
                new_state, metrics = out
                self.last_loss = metrics["loss"]
                self.in_flight.append(metrics["loss"])
                if n < compare_steps:
                    self.losses.append(metrics["loss"])
                if n == 0:  # g = mu / (1 - b1) after ONE step from zero
                    mu = _flat(_first_moment(new_state.opt_state))
                    self.grad_norms = reference.leaf_norms(
                        {k: v / (1 - reference.ADAM_B1) for k, v in mu.items()})
                if n == compare_steps - 1:
                    w0 = reference.make_weights(sizes, seed)
                    now_w = _flat(new_state.params)
                    self.change_norms = reference.leaf_norms(
                        {k: now_w[k] - w0[k] for k in w0})
                    del w0
                if n == warmup_steps - 1:
                    jax.block_until_ready(new_state)
                    self.compiles_at_t0 = self.compiles(name)
                    if trace_dir:
                        jax.profiler.start_trace(trace_dir)
                        self._trace_t0 = time.perf_counter()
                    self.t0 = time.perf_counter()
                return out

            return call

    return Hook()


def run(cell, seed: int, seconds: float, trace_dir: str | None,
        t_process_start: float, require_platform: str | None = "tpu",
        control: bool = False) -> dict:
    """One run of a training cell. Returns the pieces of the result line.
    ``control=True`` (chipbench/limits.py) also reads, against the same
    reference, the control and the fault that a limit is set below."""
    device = claim_devices(cell.chips, require_platform)
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.telemetry import RecompileAuditor

    tr, config = cell.traffic, cell.config
    code = cell_mod.ModelCode(config, cell.root)
    reference = code.reference
    sizes = config["model"]["config"]
    seq, vocab = config["model"]["seq_len"], sizes["vocab_size"]
    devices = jax.devices()[:cell.chips]
    per_device = int(tr["batch_size"])
    global_batch = per_device * (cell.chips if tr.get("mesh") else 1)
    features, labels = traffic_mod.token_rows(
        tr["data"], int(tr["data"]["rows"]), seq, vocab, seed)
    if len(features) % global_batch or \
            len(features) < tr["warmup_steps"] * global_batch:
        raise SystemExit("chipbench: data rows must be a multiple of the "
                         "global batch and cover the warm-up steps")
    dataset = dk.Dataset.from_arrays(features=features, label=labels)

    hook = make_hook(
        RecompileAuditor, code, seed=seed, sizes=sizes, seconds=seconds,
        warmup_steps=int(tr["warmup_steps"]),
        compare_steps=int(tr["compare_steps"]), trace_dir=trace_dir,
        trace_steps=int(tr["trace_steps"]))
    kwargs = dict(worker_optimizer=tr["optimizer"],
                  learning_rate=tr["learning_rate"], loss=tr["loss"],
                  batch_size=per_device, num_epoch=10**9,
                  seed=seed % (2**31 - 1), auditor=hook)
    if tr.get("mesh"):
        from distkeras_tpu.parallel.mesh import make_mesh

        kwargs["mesh"] = make_mesh(dict(tr["mesh"]), devices=devices)
    trainer = getattr(dk, tr["trainer"])(
        code.program.program_model(config, seed, reference), **kwargs)
    try:
        trainer.train(dataset)
        raise SystemExit("chipbench: the trainer ran out of data before the "
                         "window closed")
    except WindowClosed:
        pass
    window_s = hook.t_end - hook.t0
    steps = len(hook.entries)
    compiles = hook.compiles(hook.step_name) - hook.compiles_at_t0
    memory_peak = max(memory_peak_bytes(d.memory_stats() or {})
                      for d in devices)
    prog = {"losses": [float(x) for x in hook.losses],
            "grad_norms": {k: float(v) for k, v in hook.grad_norms.items()},
            "change_norms": {k: float(v) for k, v in hook.change_norms.items()}}

    # Free the program's state before the reference takes the chip.
    trainer.history = []
    hook.last_loss = hook.losses = hook.grad_norms = hook.change_norms = None
    hook.in_flight.clear()
    del trainer, dataset
    gc.collect()

    t_ref = time.perf_counter()
    batches = [(features[i * global_batch:(i + 1) * global_batch],
                labels[i * global_batch:(i + 1) * global_batch])
               for i in range(int(tr["compare_steps"]))]
    ref = reference.train_steps(sizes, seed, batches, tr["learning_rate"],
                                "f32", int(tr["reference_block_rows"]))
    numbers = compare.train_numbers(prog, ref)
    reference_s = time.perf_counter() - t_ref
    controls = {}
    if control:
        half = slice(0, global_batch // 2)
        for name, kw in (("fp8", {"matmul": "fp8"}), ("bf16", {"matmul": "bf16"}),
                         ("half_batch", {"matmul": "f32", "rows": half})):
            controls[name] = compare.train_numbers(reference.train_steps(
                sizes, seed, batches, tr["learning_rate"],
                block_rows=int(tr["reference_block_rows"]), **kw), ref)

    tokens = steps * global_batch * seq
    gaps = np.diff(hook.entries)
    return {
        "attempted": steps, "failed": 0, "device": device,
        "numbers": numbers, "controls": controls,
        "window_s": window_s,
        "setup_s": hook.t0 - t_process_start,
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "memory_peak_bytes": int(memory_peak),
        "traced_s": hook.traced_s,
        "counters": {"steps": steps, "tokens": tokens,
                     "tokens_per_step": global_batch * seq,
                     "compiles_in_window": compiles,
                     "reference_s": reference_s},
        "spans": {"step_interval_s": [float(g) for g in gaps]},
    }
