"""User-facing trainers — API parity with ``distkeras/trainers.py``.

Every reference trainer keeps its name and constructor surface
(``keras_model``/``worker_optimizer``/``loss``/``num_workers``/``batch_size``/
``features_col``/``label_col``/``num_epoch``/``communication_window``/
``rho``/``learning_rate``/``momentum``/``parallelism_factor``), and
``train(dataset, shuffle=False)`` returns a trained model. What changed is
the engine underneath:

- ``SingleTrainer``     one jitted step loop on one chip (reference: coalesce
                        to 1 partition + ``SequentialWorker``).
- ``EnsembleTrainer``   N independent replicas trained **in one vmapped,
                        jitted computation** (reference: N Spark partitions).
- ``AveragingTrainer``  same vmapped replicas, weights averaged at the end
                        (reference: arithmetic mean on the driver).
- ``SynchronousDistributedTrainer`` GSPMD data parallelism: batch sharded
                        over a device mesh's ``dp`` axis, gradient all-reduce
                        inserted by XLA over ICI (reference: lock-step
                        socket-PS round trips).
- ``DOWNPOUR``/``ADAG``/``AEASGD``/``EAMSGD``/``DynSGD`` async parameter-
                        server protocols: worker threads drive jitted local
                        steps on their devices and exchange deltas with the
                        single-owner PS every ``communication_window``
                        batches (:mod:`distkeras_tpu.parallel.protocols`).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.data.feed import (
    DeviceFeed,
    index_windows as _index_windows,
    minibatches,
    window_batches,
)
from distkeras_tpu.models.core import Model, TrainedModel
from distkeras_tpu.ops.losses import get_optimizer
from distkeras_tpu.parallel.mesh import best_mesh, data_parallel_shardings
from distkeras_tpu.parallel.protocols import (
    ADAGProtocol,
    AEASGDProtocol,
    AsyncProtocol,
    DOWNPOURProtocol,
    DynSGDProtocol,
    EAMSGDProtocol,
)
from distkeras_tpu.parallel.ps import ParameterServerService
from distkeras_tpu.telemetry import span
from distkeras_tpu.training.step import (
    TrainState,
    make_cached_window_train_step,
    make_train_step,
    make_window_train_step,
)
from distkeras_tpu.utils.rng import worker_seed

__all__ = [
    "Trainer",
    "SingleTrainer",
    "EnsembleTrainer",
    "AveragingTrainer",
    "SynchronousDistributedTrainer",
    "AsynchronousDistributedTrainer",
    "DOWNPOUR",
    "ADAG",
    "AEASGD",
    "EAMSGD",
    "DynSGD",
]


def _as_model(model) -> Model:
    if isinstance(model, Model):
        return model
    return Model.from_keras(model)


class _StepCheckpointer:
    """Shared save/resume scaffold for step-loop trainers (sync + pipeline).

    One copy of the protocol: restore the latest step into the live state
    template (optionally re-placed via ``place``), timed ``wait=False``
    saves during the loop, a final blocking save, and a ``close()`` that is
    safe to call from ``finally`` — so a crash mid-train still finalizes
    any in-flight async save instead of leaving an unfinalized tmp step.
    """

    def __init__(self, directory, interval_s, resume, like, place=None):
        self.mgr = None
        self.start_step = 0
        self.state = None
        self.interval_s = float(interval_s)
        if directory is None:
            return
        from distkeras_tpu.checkpoint import CheckpointManager

        self.mgr = CheckpointManager(directory)
        if resume and self.mgr.latest_step() is not None:
            restored = self.mgr.restore(like={"state": like})["state"]
            self.state = place(restored) if place is not None else restored
            self.start_step = self.mgr.latest_step()
        self._last = time.monotonic()

    def maybe_save(self, step, state):
        if (
            self.mgr is not None
            and time.monotonic() - self._last >= self.interval_s
        ):
            with span("checkpoint_save", step=step):
                self.mgr.save(step, state=state, wait=False)
            self._last = time.monotonic()

    def finalize(self, step, state):
        if self.mgr is not None and step > self.start_step:
            if self.mgr.latest_step() == step:
                # maybe_save already persisted this very step (wait=False
                # async); a second save of the same step raises orbax's
                # StepAlreadyExists and would crash the run at the finish
                # line — just drain the in-flight write instead.
                self.mgr.wait_until_finished()
            else:
                with span("checkpoint_save", step=step):
                    self.mgr.save(step, state=state)

    def close(self):
        if self.mgr is not None:
            self.mgr.close()
            self.mgr = None


class Trainer:
    """Base trainer (reference ``distkeras/trainers.py`` § ``Trainer``):
    holds the model spec, loss, worker optimizer and wall-clock bookkeeping."""

    def __init__(
        self,
        keras_model,
        worker_optimizer="adagrad",
        loss: str = "categorical_crossentropy",
        metrics: tuple[str, ...] = ("accuracy",),
        learning_rate: float | None = None,
        seed: int = 0,
        loss_weights=None,
        metric_stream=None,
        registry=None,
        auditor=None,
    ):
        self.model = _as_model(keras_model)
        # Reference API parity (`Trainer.__init__(..., loss_weights=None)`).
        # Single-output models: a scalar scales the loss; None is a no-op.
        self.loss_weights = loss_weights
        if loss_weights is not None:
            base = loss

            def _weighted(preds, targets, _base=base, _w=float(loss_weights)):
                from distkeras_tpu.ops.losses import get_loss

                return get_loss(_base)(preds, targets) * _w

            loss = _weighted
        self.loss = loss
        self.worker_optimizer = worker_optimizer
        self.metrics = tuple(metrics)
        self.learning_rate = learning_rate
        self.seed = seed
        # Optional distkeras_tpu.tracing.MetricStream receiving per-step
        # records (loss/accuracy/worker) as training runs.
        self.metric_stream = metric_stream
        # Optional telemetry (distkeras_tpu.telemetry): a MetricsRegistry
        # the trainer publishes run counters/last-step gauges into, and a
        # RecompileAuditor that wraps the jitted step so compile counts
        # (and, armed, compile-after-warmup violations) are tracked.
        self.registry = registry
        self.auditor = auditor
        # Optional distkeras_tpu.deploy.WeightPublisher: the trainer
        # side of the continuous-deployment loop. Step-loop trainers
        # call _maybe_publish per step; the async family publishes the
        # PS center from a dedicated thread. run.py wires it from
        # --publish-dir/--publish-every.
        self.publisher = None
        self.history: list[dict] = []
        self._training_start: float | None = None
        self._training_stop: float | None = None

    # -- timing (reference § Trainer.record_training_start/stop) -------------

    def record_training_start(self) -> None:
        self._training_start = time.time()
        self._training_stop = None

    def record_training_stop(self) -> None:
        self._training_stop = time.time()

    def get_training_time(self) -> float:
        if self._training_start is None:
            return 0.0
        stop = self._training_stop if self._training_stop is not None else time.time()
        return stop - self._training_start

    def get_history(self) -> list[dict]:
        return self.history

    def get_averaged_history(self) -> dict:
        """Mean of each metric over recorded steps (and over replicas, for
        the vmapped trainers whose per-step metrics are arrays)."""
        if not self.history:
            return {}
        out = {}
        for k, v in self.history[0].items():
            try:
                out[k] = float(
                    np.mean([np.mean(np.asarray(h[k])) for h in self.history if k in h])
                )
            except (TypeError, ValueError):
                continue
        return out

    def _emit_history(self) -> None:
        if self.metric_stream is not None:
            for i, h in enumerate(self.history):
                self.metric_stream.emit(i, h)
        if self.registry is not None and self.history:
            self.registry.counter(
                "train_steps_total", help="train steps recorded",
            ).inc(len(self.history))
            self.registry.gauge(
                "train_time_seconds", help="wall clock of the last train()",
            ).set(self.get_training_time())
            from distkeras_tpu.telemetry import sanitize_metric_name

            for k, v in self.history[-1].items():
                if isinstance(v, (int, float)):
                    self.registry.gauge(
                        "train_last_" + sanitize_metric_name(k),
                        help="last-step train metric").set(v)

    def _maybe_publish(self, step: int, variables_fn, loss_fn=None) -> None:
        """Per-step publish hook (no-op without a publisher). Both
        callables are lazy — an idle cadence costs two comparisons, no
        device sync, no host copy."""
        if self.publisher is not None:
            self.publisher.maybe_publish(variables_fn, step=step,
                                         loss_fn=loss_fn)

    def _audit(self, step_fn, name: str):
        """Wrap a jitted step with the attached recompile auditor (no-op
        without one). Auditor names are unique per auditor, so a second
        train() on the same trainer runs unaudited rather than failing."""
        if self.auditor is None:
            return step_fn
        try:
            return self.auditor.wrap(step_fn, name)
        except ValueError:  # name already wrapped (trainer re-used)
            return step_fn

    def _optimizer(self):
        return get_optimizer(self.worker_optimizer, self.learning_rate)

    def train(self, dataset: Dataset, shuffle: bool = False) -> TrainedModel:
        raise NotImplementedError

    def evaluate(
        self,
        trained: TrainedModel,
        dataset: Dataset,
        batch_size: int = 1024,
        features_col: str | None = None,
        label_col: str | None = None,
    ) -> dict:
        """Mean eval metrics (loss + accuracy) over a dataset — the inline
        counterpart of the ModelPredictor -> evaluator pipeline."""
        from distkeras_tpu.training.step import make_eval_step

        eval_step = make_eval_step(self.model, self.loss)
        fcol = features_col or getattr(self, "features_col", "features")
        lcol = label_col or getattr(self, "label_col", "label")
        totals: dict[str, float] = {}
        count = 0
        for batch in minibatches(
            dataset, min(batch_size, dataset.num_rows), fcol, lcol,
            drop_remainder=False,
        ):
            m = eval_step(trained.variables, batch)
            n = batch["features"].shape[0]
            for k2, v2 in m.items():
                totals[k2] = totals.get(k2, 0.0) + float(v2) * n
            count += n
        return {k2: v2 / max(1, count) for k2, v2 in totals.items()}


class SingleTrainer(Trainer):
    """Single-device trainer (reference § ``SingleTrainer``: coalesce to one
    partition, run ``SequentialWorker`` in one executor)."""

    def __init__(
        self,
        keras_model,
        worker_optimizer="adagrad",
        loss="categorical_crossentropy",
        metrics=("accuracy",),
        features_col: str = "features",
        label_col: str = "label",
        batch_size: int = 32,
        num_epoch: int = 1,
        learning_rate: float | None = None,
        seed: int = 0,
        grad_accum_steps: int = 1,
        remat: bool = False,
        aux_loss_weight: float = 0.01,
        validation_data: Dataset | None = None,
        loss_weights=None,
        metric_stream=None,
        registry=None,
        auditor=None,
    ):
        super().__init__(keras_model, worker_optimizer, loss, metrics,
                         learning_rate=learning_rate, seed=seed,
                         loss_weights=loss_weights, metric_stream=metric_stream,
                         registry=registry, auditor=auditor)
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.grad_accum_steps = int(grad_accum_steps)
        self.remat = bool(remat)
        self.aux_loss_weight = float(aux_loss_weight)
        # Optional held-out set: evaluated after every epoch into
        # validation_history (val_loss/val_accuracy).
        self.validation_data = validation_data
        self.validation_history: list[dict] = []

    def train(self, dataset: Dataset, shuffle: bool = False) -> TrainedModel:
        self.record_training_start()
        optimizer = self._optimizer()
        step_fn = self._audit(make_train_step(
            self.model, optimizer, self.loss, self.metrics,
            remat=self.remat, grad_accum_steps=self.grad_accum_steps,
            aux_loss_weight=self.aux_loss_weight,
        ), "train_step")
        state = TrainState.create(self.model, optimizer, rng=self.seed)
        self.history = []
        self.validation_history = []
        for epoch in range(self.num_epoch):
            batches = minibatches(
                dataset,
                self.batch_size,
                self.features_col,
                self.label_col,
                num_epoch=1,
                seed=(self.seed + epoch) if shuffle else None,
            )
            # Double-buffered host->HBM feed: the next batch's transfer
            # overlaps the current step's compute.
            for batch in DeviceFeed(batches, buffer_size=2):
                with span("train_step"):
                    state, m = step_fn(state, batch)
                self.history.append(m)
                self._maybe_publish(
                    len(self.history),
                    lambda: jax.device_get(state.variables),
                    loss_fn=lambda: float(m["loss"]))
            if self.validation_data is not None:
                snapshot = TrainedModel(self.model, state.variables)
                with span("validation", epoch=epoch):
                    val = self.evaluate(
                        snapshot, self.validation_data,
                        features_col=self.features_col,
                        label_col=self.label_col,
                    )
                self.validation_history.append(
                    {"epoch": epoch, **{f"val_{k}": v for k, v in val.items()}}
                )
        # Materialize metrics (they were async device scalars).
        self.history = [
            {k: float(v) for k, v in h.items()} for h in self.history
        ]
        self._emit_history()
        self.record_training_stop()
        return TrainedModel(self.model, jax.device_get(state.variables))


class _VmappedReplicasTrainer(Trainer):
    """Shared engine for Ensemble/Averaging trainers: N replicas trained as
    one vmapped, jitted computation — a TPU-first reformulation of the
    reference's "N Spark partitions, N executors" fan-out."""

    def __init__(
        self,
        keras_model,
        worker_optimizer="adagrad",
        loss="categorical_crossentropy",
        metrics=("accuracy",),
        num_models: int = 2,
        features_col: str = "features",
        label_col: str = "label",
        batch_size: int = 32,
        num_epoch: int = 1,
        learning_rate: float | None = None,
        seed: int = 0,
        loss_weights=None,
        metric_stream=None,
        registry=None,
        auditor=None,
    ):
        super().__init__(keras_model, worker_optimizer, loss, metrics,
                         learning_rate=learning_rate, seed=seed,
                         loss_weights=loss_weights, metric_stream=metric_stream,
                         registry=registry, auditor=auditor)
        self.num_models = int(num_models)
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)

    def _train_replicas(self, dataset: Dataset, shuffle: bool):
        optimizer = self._optimizer()
        step_fn = make_train_step(
            self.model, optimizer, self.loss, self.metrics, jit=False
        )
        vstep = self._audit(
            jax.jit(jax.vmap(step_fn), donate_argnums=(0,)), "vmapped_step")

        # Pad the replica axis up to a device-count multiple so the stack
        # ALWAYS shards over devices (round 1 fell back to one device with
        # N× memory whenever N % ndev != 0); padded replicas train on
        # recycled partitions and are dropped at unstack time.
        devices = jax.devices()
        ndev = len(devices)
        n_padded = self.num_models
        if ndev > 1 and self.num_models % ndev:
            n_padded = ((self.num_models + ndev - 1) // ndev) * ndev
        self._n_padded = n_padded

        # One TrainState per replica, stacked on a leading axis.
        states = [
            TrainState.create(self.model, optimizer, rng=worker_seed(self.seed, i))
            for i in range(n_padded)
        ]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

        # Shard the replica axis over devices: N models train on the mesh
        # as one XLA program (the TPU-first form of the reference's
        # N-executor fan-out).
        replica_sharding = None
        if ndev > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = best_mesh()
            replica_sharding = NamedSharding(mesh, P("dp"))
            stacked = jax.device_put(stacked, replica_sharding)

        parts = dataset.partitions(self.num_models)
        iters = [
            minibatches(
                parts[i % self.num_models],
                self.batch_size,
                self.features_col,
                self.label_col,
                num_epoch=self.num_epoch,
                seed=worker_seed(self.seed, i) if shuffle else None,
            )
            for i in range(n_padded)
        ]
        # Lock-step vmapped stepping consumes min(len(iter)) groups: with
        # uneven partitions the longer replicas' tail batches are never
        # stepped. Keep the truncation (the alternative — recycling short
        # streams — silently trains on repeated data) but make it LOUD:
        # expected counts are arithmetic (rows // batch per epoch), so the
        # per-replica drop count costs nothing to compute.
        expected = [
            self.num_epoch
            * (parts[i % self.num_models].num_rows // self.batch_size)
            for i in range(n_padded)
        ]
        self.history = []
        while True:
            batch_group = []
            try:
                for it in iters:
                    batch_group.append(next(it))
            except StopIteration:
                break
            batch = {
                k: np.stack([b[k] for b in batch_group]) for k in batch_group[0]
            }
            if replica_sharding is not None:
                batch = {
                    k: jax.device_put(v, replica_sharding) for k, v in batch.items()
                }
            with span("train_step"):
                stacked, m = vstep(stacked, batch)
            self.history.append(m)
        steps = len(self.history)
        self.dropped_batches = [e - steps for e in expected[: self.num_models]]
        if any(self.dropped_batches):
            import logging

            logging.getLogger(__name__).warning(
                "replica lock-step stopped at %d steps; tail batches dropped "
                "per replica: %s (uneven partitions — replica i gets "
                "rows//batch_size=%s batches/epoch)",
                steps, self.dropped_batches,
                [e // max(self.num_epoch, 1) for e in expected[: self.num_models]],
            )
        # Drop padded replicas from metrics (they trained on recycled data).
        self.history = [
            {k: np.asarray(v)[: self.num_models] for k, v in h.items()}
            for h in self.history
        ]
        return jax.device_get(stacked)

    def _unstack_variables(self, stacked_state) -> list[dict]:
        n = self.num_models
        return [
            jax.tree.map(lambda x: x[i], {"params": stacked_state.params, **stacked_state.model_state})
            for i in range(n)
        ]


class EnsembleTrainer(_VmappedReplicasTrainer):
    """Train N independent models, return all of them
    (reference § ``EnsembleTrainer``)."""

    def train(self, dataset: Dataset, shuffle: bool = False) -> list[TrainedModel]:
        self.record_training_start()
        stacked = self._train_replicas(dataset, shuffle)
        models = [
            TrainedModel(self.model, v) for v in self._unstack_variables(stacked)
        ]
        self.record_training_stop()
        return models


class AveragingTrainer(_VmappedReplicasTrainer):
    """Train N models in parallel, return the weight average
    (reference § ``AveragingTrainer``)."""

    def __init__(self, *args, num_workers: int = 2, **kwargs):
        kwargs.setdefault("num_models", num_workers)
        super().__init__(*args, **kwargs)
        self.num_workers = self.num_models

    def train(self, dataset: Dataset, shuffle: bool = False) -> TrainedModel:
        self.record_training_start()
        stacked = self._train_replicas(dataset, shuffle)
        # Mean over the REQUESTED replicas only — the stack may carry
        # padded throwaway replicas for device-count alignment.
        averaged = jax.tree.map(
            lambda x: np.mean(x[: self.num_models], axis=0),
            {"params": stacked.params, **stacked.model_state},
        )
        self.record_training_stop()
        return TrainedModel(self.model, averaged)


class SynchronousDistributedTrainer(Trainer):
    """Synchronous data parallelism over a device mesh
    (reference § ``SynchronousDistributedTrainer``, rebuilt as GSPMD):
    the global batch (``batch_size × num_workers``) is sharded over the
    mesh's ``dp`` axis; XLA inserts the gradient all-reduce over ICI.
    ``num_workers`` maps to mesh size (defaults to all local devices)."""

    def __init__(
        self,
        keras_model,
        worker_optimizer="adagrad",
        loss="categorical_crossentropy",
        metrics=("accuracy",),
        num_workers: int | None = None,
        batch_size: int = 32,
        features_col: str = "features",
        label_col: str = "label",
        num_epoch: int = 1,
        learning_rate: float | None = None,
        seed: int = 0,
        mesh=None,
        zero1: bool = False,
        shard_sequence: bool = False,
        aux_loss_weight: float = 0.01,
        checkpoint_dir: str | None = None,
        checkpoint_interval_s: float = 60.0,
        resume: bool = False,
        loss_weights=None,
        metric_stream=None,
        registry=None,
        auditor=None,
    ):
        super().__init__(keras_model, worker_optimizer, loss, metrics,
                         learning_rate=learning_rate, seed=seed,
                         loss_weights=loss_weights, metric_stream=metric_stream,
                         registry=registry, auditor=auditor)
        self.num_workers = num_workers
        self.batch_size = int(batch_size)
        self.features_col = features_col
        self.label_col = label_col
        self.num_epoch = int(num_epoch)
        self.mesh = mesh
        self.zero1 = bool(zero1)
        # Orbax step checkpoints (parity with the async family): save every
        # checkpoint_interval_s plus a final save; resume=True restores the
        # latest step and fast-forwards the deterministic batch stream past
        # it, so a resumed run reproduces the uninterrupted one.
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_interval_s = float(checkpoint_interval_s)
        self.resume = bool(resume)
        # Shard the sequence dimension of [B, S] batches over the mesh's sp
        # axis (XLA inserts the activation collectives; ring attention is the
        # shard_map alternative for attention itself).
        self.shard_sequence = bool(shard_sequence)
        self.aux_loss_weight = float(aux_loss_weight)

    def train(self, dataset: Dataset, shuffle: bool = False) -> TrainedModel:
        self.record_training_start()
        mesh = self.mesh if self.mesh is not None else best_mesh(self.num_workers)
        ndev = mesh.devices.size
        # batch_size is per-worker (reference semantics); dp-like axes carry
        # the data parallelism.
        dp_size = 1
        for ax in ("dp", "fsdp"):
            if ax in mesh.axis_names:
                dp_size *= mesh.shape[ax]
        global_batch = self.batch_size * dp_size

        optimizer = self._optimizer()
        model_axes = any(
            a in mesh.axis_names and mesh.shape[a] > 1
            for a in ("tp", "sp", "fsdp", "ep")
        )
        if self.zero1 or (
            model_axes
            and (hasattr(self.model, "boxed_init") or "fsdp" in mesh.axis_names)
        ):
            # GSPMD data+model sharding (logical-axis-annotated model).
            from distkeras_tpu.parallel.gspmd import (
                make_sharded_train_step,
                shard_batch,
                sharded_train_state,
            )

            state, _ = sharded_train_state(
                self.model, optimizer, mesh, rng=self.seed, zero1=self.zero1
            )
            step_fn = make_sharded_train_step(
                self.model, optimizer, self.loss, mesh, metrics=self.metrics,
                aux_loss_weight=self.aux_loss_weight,
            )
            seq_dim = 1 if self.shard_sequence else None
            shard_fn = lambda b: shard_batch(mesh, b, seq_dim=seq_dim)
        else:
            batch_sharding, replicated = data_parallel_shardings(mesh)
            step_fn = make_train_step(self.model, optimizer, self.loss, self.metrics)
            state = TrainState.create(self.model, optimizer, rng=self.seed)
            state = jax.device_put(state, replicated)
            shard_fn = lambda b: {
                k: jax.device_put(v, batch_sharding) for k, v in b.items()
            }

        # The live state is the restore template: its jax.Arrays carry
        # shardings, so a GSPMD state restores distributed.
        ck = _StepCheckpointer(
            self.checkpoint_dir, self.checkpoint_interval_s, self.resume,
            like=state,
        )
        if ck.state is not None:
            state = ck.state

        self.history = []
        # start_batch fast-forwards the deterministic stream past the
        # restored step arithmetically (no skipped-batch gathers).
        batches = minibatches(
            dataset,
            global_batch,
            self.features_col,
            self.label_col,
            num_epoch=self.num_epoch,
            seed=self.seed if shuffle else None,
            start_batch=ck.start_step,
        )
        step_fn = self._audit(step_fn, "sync_train_step")
        feed = DeviceFeed(batches, put_fn=shard_fn, buffer_size=2)
        step_no = ck.start_step
        try:
            for i, batch in enumerate(feed, start=ck.start_step):
                with span("train_step"):
                    state, m = step_fn(state, batch)
                self.history.append(m)
                step_no = i + 1
                ck.maybe_save(step_no, state)
                self._maybe_publish(
                    step_no,
                    lambda: jax.device_get(state.variables),
                    loss_fn=lambda: float(m["loss"]))
            ck.finalize(step_no, state)
        finally:
            ck.close()
        self.history = [{k: float(v) for k, v in h.items()} for h in self.history]
        self._emit_history()
        self.record_training_stop()
        return TrainedModel(self.model, jax.device_get(state.variables))


class AsynchronousDistributedTrainer(Trainer):
    """Async parameter-server skeleton (reference §
    ``AsynchronousDistributedTrainer`` + ``DistributedTrainer``): owns the PS
    lifecycle, fans out ``num_workers`` worker loops, pulls the final center.

    Workers are threads, each driving jitted steps on a device
    (round-robin over local devices); the PS is the single-owner service in
    :mod:`distkeras_tpu.parallel.ps`. ``parallelism_factor`` over-partitions
    the data like the reference's repartition factor.
    """

    protocol_cls: type[AsyncProtocol] = DOWNPOURProtocol

    def __init__(
        self,
        keras_model,
        worker_optimizer="adagrad",
        loss="categorical_crossentropy",
        metrics=("accuracy",),
        num_workers: int = 2,
        devices_per_worker: int = 1,
        batch_size: int = 32,
        features_col: str = "features",
        label_col: str = "label",
        num_epoch: int = 1,
        parallelism_factor: int = 1,
        communication_window: int | None = None,
        learning_rate: float | None = None,
        seed: int = 0,
        transport: str = "inprocess",  # "inprocess" | "grpc"
        master_host: str | None = None,  # remote PS address (grpc transport)
        master_port: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_interval_s: float = 60.0,
        resume: bool = False,
        compress_deltas: bool = False,
        overlap_window: bool = True,
        device_cache: bool | str = "auto",
        track_health: bool = True,
        loss_weights=None,
        metric_stream=None,
        registry=None,
        auditor=None,
        **protocol_kwargs,
    ):
        super().__init__(keras_model, worker_optimizer, loss, metrics,
                         learning_rate=learning_rate, seed=seed,
                         loss_weights=loss_weights, metric_stream=metric_stream,
                         registry=registry, auditor=auditor)
        self.num_workers = int(num_workers)
        # devices_per_worker > 1 turns each worker into an *island*: a sync
        # data-parallel sub-mesh (gradient all-reduce over ICI inside the
        # island) that speaks to the PS as one async participant — the
        # hybrid SURVEY §7 calls for (asynchrony between islands, lock-step
        # within).
        self.devices_per_worker = int(devices_per_worker)
        self.batch_size = int(batch_size)
        self.features_col = features_col
        self.label_col = label_col
        self.num_epoch = int(num_epoch)
        self.parallelism_factor = int(parallelism_factor)
        if transport not in ("inprocess", "grpc"):
            raise ValueError(f"unknown transport {transport!r}")
        self.transport = transport
        self.master_host = master_host
        self.master_port = master_port
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_interval_s = float(checkpoint_interval_s)
        self.resume = bool(resume)
        # bf16 commit deltas: halves PS wire traffic (ha.CompressingClient)
        self.compress_deltas = bool(compress_deltas)
        # Overlap the PS exchange with local compute: the window exchange
        # runs on a background thread while jitted steps continue, and the
        # reply is rebased onto the advanced params (the
        # synchronous exchange made the async step 5.3x the sync step).
        self.overlap_window = bool(overlap_window)
        # "auto": keep a worker's partition resident in HBM (and gather
        # batches on device from index arrays) when it fits comfortably.
        self.device_cache = device_cache
        if communication_window is not None:
            protocol_kwargs["communication_window"] = communication_window
        self.protocol = self._allocate_protocol(**protocol_kwargs)
        self.communication_window = self.protocol.communication_window
        self.parameter_server: ParameterServerService | None = None
        # Async-protocol health telemetry (telemetry.training_health):
        # built fresh per train() and fed by the PS loop + worker
        # threads; ``trainer.training_health.statusz()`` is the live
        # worker-table/staleness/divergence snapshot run.py serves via
        # --statusz-out. track_health=False turns the whole layer off.
        self.track_health = bool(track_health)
        self.training_health = None

    def _allocate_protocol(self, **kwargs) -> AsyncProtocol:
        return self.protocol_cls(**kwargs)

    # "auto" partition budget when the device publishes no memory stats
    # (CPU simulation meshes) — deliberately conservative.
    _DEVICE_CACHE_LIMIT = 256 * 1024 * 1024

    def _device_cache_budget(self, device, state_bytes: int) -> int:
        """HBM bytes one worker may spend keeping its partition resident.

        Derived from the device, not a constant:
        ``memory_stats()['bytes_limit']`` minus three times the training
        state (the resident params + optimizer slots themselves, their
        gradients, and the donation ping-pong copy), minus a 25% headroom
        for activations/XLA workspace. The probe goes through
        :func:`distkeras_tpu.telemetry.device.device_memory` — the typed
        ``available=False`` sentinel (backend has no ``memory_stats``,
        the CPU-mesh case) falls back to the 256 MB constant, and
        statusz/metricsz can tell "no data" from "0 bytes"."""
        if device is not None:
            from distkeras_tpu.telemetry.device import device_memory

            mem = device_memory(device)
            if mem.available and mem.bytes_limit:
                limit = int(mem.bytes_limit)
                return max(0, limit - 3 * int(state_bytes) - limit // 4)
        return self._DEVICE_CACHE_LIMIT

    def _use_device_cache(
        self, part: Dataset, device=None, state_bytes: int = 0
    ) -> bool:
        if not self.device_cache:
            return False
        if self.device_cache == "auto":
            size = sum(
                np.asarray(part[c]).nbytes
                for c in (self.features_col, self.label_col)
            )
            budget = self._device_cache_budget(device, state_bytes)
            use = size < budget
            import logging

            logging.getLogger(__name__).info(
                "device_cache auto: partition %.1f MB vs budget %.1f MB "
                "(device=%s, state %.1f MB) -> %s",
                size / 2**20, budget / 2**20,
                getattr(device, "id", device), state_bytes / 2**20,
                "cache" if use else "host feed",
            )
            return use
        return True

    # reference API parity: DistributedTrainer.service()/stop_service()
    def service(self, center_params):
        budget_fn = getattr(self.protocol, "host_state_budget", None)
        if budget_fn is not None:
            import logging

            n_params = sum(
                int(np.size(leaf))  # metadata read — no D2H materialize
                for leaf in jax.tree.leaves(center_params)
            )
            logging.getLogger(__name__).info(
                "PS host-state budget (%s): %.1f MB worst-case "
                "(%d workers, %d params, mirror_dtype=%s)",
                self.protocol.name,
                budget_fn(n_params, self.num_workers) / 2**20,
                self.num_workers, n_params,
                getattr(self.protocol, "mirror_dtype", "n/a"),
            )
        if self.transport == "grpc":
            from distkeras_tpu.parallel.ps_grpc import GrpcParameterServer

            grpc_ps = GrpcParameterServer(
                self.protocol,
                center_params,
                self.num_workers,
                port=self.master_port or 0,
                registry=self.registry,
                health=self.training_health,
            )
            self.master_port = grpc_ps.start()
            if self.master_host is None:
                self.master_host = "127.0.0.1"
            self._grpc_ps = grpc_ps
            self.parameter_server = grpc_ps.service
            return grpc_ps
        self._grpc_ps = None
        self.parameter_server = ParameterServerService(
            self.protocol, center_params, self.num_workers,
            registry=self.registry, health=self.training_health,
        )
        self.parameter_server.start()
        return self.parameter_server

    def _make_client(self):
        if self.transport == "grpc":
            from distkeras_tpu.parallel.ps_grpc import GrpcClient

            return GrpcClient(self.master_host, self.master_port)
        return self.parameter_server.client()

    def stop_service(self) -> None:
        if getattr(self, "_grpc_ps", None) is not None:
            self._grpc_ps.stop()
            self._grpc_ps = None
        elif self.parameter_server is not None:
            self.parameter_server.stop()

    def train(self, dataset: Dataset, shuffle: bool = False) -> TrainedModel:
        self.record_training_start()
        optimizer = self.protocol.local_optimizer(self._optimizer())
        # The whole communication window runs as ONE compiled lax.scan: one
        # dispatch per window (not per batch) keeps the Python thread — and
        # the GIL — free for the overlapped PS exchange while the device
        # crunches. donate=False: the params snapshot taken at the exchange
        # launch must stay valid while the next window computes.
        window_fn = self._audit(make_window_train_step(
            self.model, optimizer, self.loss, self.metrics, donate=False
        ), "async_window_step")
        cached_window_fn = self._audit(make_cached_window_train_step(
            self.model, optimizer, self.loss, self.metrics, donate=False
        ), "async_cached_window_step")
        init_state = TrainState.create(self.model, optimizer, rng=self.seed)
        center_init = init_state.params
        if self.track_health:
            from distkeras_tpu.telemetry import TrainingHealth

            self.training_health = TrainingHealth(
                registry=self.registry, num_workers=self.num_workers,
                protocol=self.protocol.name)
            self.training_health.set_params_bytes(sum(
                getattr(l, "nbytes", 0)
                for l in jax.tree.leaves(center_init)))
        health = self.training_health
        ckpt_mgr = None
        if self.checkpoint_dir is not None:
            from distkeras_tpu.checkpoint import CheckpointManager

            ckpt_mgr = CheckpointManager(self.checkpoint_dir)
            if self.resume and ckpt_mgr.latest_step() is not None:
                restored = ckpt_mgr.restore(
                    like={"ps": {"center": center_init, "num_updates": 0}}
                )
                center_init = restored["ps"]["center"]
        ps = self.service(center_init)
        if ckpt_mgr is not None:
            import logging

            svc = self.parameter_server
            stop_ckpt = threading.Event()
            log = logging.getLogger(__name__)

            def _periodic_checkpoint():
                while not stop_ckpt.wait(self.checkpoint_interval_s):
                    try:
                        # Provenance: the commit counter doubles as the
                        # snapshot's monotonic weight version, so a
                        # weights file published from this checkpoint
                        # names the exact training position it came from.
                        ckpt_mgr.save(
                            svc.num_commits,
                            ps_center=svc.get_model(),
                            ps_num_updates=svc.num_updates,
                            meta={"weight_version": int(svc.num_commits)},
                        )
                    except Exception:
                        # Snapshotting must never take down training — but a
                        # permanently failing snapshot loop is silent data
                        # loss at restore time: log the first failure with
                        # traceback, count the rest, surface in health().
                        svc.snapshot_failures += 1
                        if svc.snapshot_failures == 1:
                            log.exception("PS checkpoint snapshot failed")
                        else:
                            log.warning(
                                "PS checkpoint snapshot failed (%d so far)",
                                svc.snapshot_failures,
                            )

            ckpt_thread = threading.Thread(
                target=_periodic_checkpoint, name="ps-checkpoint", daemon=True
            )
            ckpt_thread.start()

        devices = jax.local_devices()
        num_partitions = self.num_workers * self.parallelism_factor
        partitions = dataset.partitions(num_partitions)
        window = self.protocol.communication_window

        # Per-worker list of (stacked window metrics, window length,
        # completion wall time); expanded into per-step history rows after
        # the join (keeps device syncs out of the hot loop).
        win_histories: list[list[tuple[dict, int, float]]] = [
            [] for _ in range(self.num_workers)
        ]
        final_states: list[Any] = [None] * self.num_workers
        errors: list[BaseException | None] = [None] * self.num_workers

        dpw = self.devices_per_worker
        if dpw > 1 and self.num_workers * dpw > len(devices):
            raise ValueError(
                f"{self.num_workers} workers x {dpw} devices_per_worker "
                f"> {len(devices)} attached devices"
            )

        # Continuous deployment: a dedicated thread publishes the PS
        # CENTER on the publisher's cadence — the serving fleet deploys
        # from the same periodically-exchanged weights the async
        # protocol maintains, while the workers' hot loops stay
        # untouched (the only worker-side cost is keeping a reference to
        # the latest already-materialized window loss). Started last so
        # no pre-flight ValueError above can leak a running thread.
        pub_stop = threading.Event()
        pub_thread = None
        self._publish_loss = None
        if self.publisher is not None:
            svc_ref = self.parameter_server

            def _publish_loss_now():
                arr = self._publish_loss
                if arr is None:
                    return None
                return float(np.asarray(arr)[-1])

            def _publish_loop():
                import logging

                while not pub_stop.wait(0.2):
                    try:
                        self.publisher.maybe_publish(
                            lambda: {"params": svc_ref.get_model()},
                            step=svc_ref.num_commits,
                            loss_fn=_publish_loss_now)
                    except Exception:
                        # The publisher already swallows its own
                        # failures; this guards the PS accessors — ONE
                        # surprise must not silently kill the thread and
                        # end publishing for the rest of a long run.
                        logging.getLogger(__name__).exception(
                            "weight-publisher tick failed")

            pub_thread = threading.Thread(
                target=_publish_loop, name="weight-publisher", daemon=True)
            pub_thread.start()

        def worker_loop(widx: int):
            try:
                if dpw > 1:
                    # island: sync dp sub-mesh; batch sharded, state replicated
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P

                    from distkeras_tpu.parallel.mesh import make_mesh

                    island_devices = devices[widx * dpw : (widx + 1) * dpw]
                    island_mesh = make_mesh({"dp": dpw}, devices=island_devices)
                    _, repl_sh = data_parallel_shardings(island_mesh)
                    put_state = lambda tree: jax.device_put(tree, repl_sh)
                    # Stacked windows are [W, B, ...]: the batch axis is 1.
                    batch_placement = NamedSharding(island_mesh, P(None, "dp"))
                else:
                    device = devices[widx % len(devices)]
                    put_state = lambda tree: jax.device_put(tree, device)
                    batch_placement = device
                from distkeras_tpu.parallel.ha import (
                    CompressingClient,
                    RetryingClient,
                    StampingClient,
                )

                client = self._make_client()
                if self.transport == "grpc":
                    client = RetryingClient(client)
                if self.compress_deltas:
                    client = CompressingClient(client)
                # Stamped commit ids + PS dedupe = exactly-once commits even
                # through retries (the reference's Spark-retry path was
                # silently at-least-once; SURVEY §5).
                client = StampingClient(client, widx)
                center, carry = self.protocol.worker_begin(client, None)
                if health is not None:
                    health.record_pull(widx)
                params = put_state(center)
                state = TrainState.create(
                    self.model, optimizer, rng=worker_seed(self.seed, widx)
                )
                state = put_state(state)
                state = state.replace(params=params, opt_state=optimizer.init(params))
                my_parts = partitions[widx :: self.num_workers]
                # Hot loop: each communication window is ONE compiled
                # lax.scan dispatch, then ONE fused PS exchange. With
                # ``overlap_window`` the exchange runs on a background
                # thread while the NEXT window computes; the reply is
                # rebased onto the advanced params:
                # ``new = center + (now - snap)``. The in-flight progress
                # ``now - snap`` is neither lost nor double-counted — the
                # next delta's baseline is the fresh center
                # (``carry.window_start``), so it ships with the next
                # commit. The reference hid its PS RTT behind
                # ``train_on_batch`` the same way (SURVEY §3.1); with an
                # idle PS the rebase degenerates to the reference's
                # set_weights(center) cadence.
                exchanger = (
                    ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix=f"ps-exchange-{widx}"
                    )
                    if self.overlap_window
                    else None
                )
                pending: tuple[Any, Any] | None = None  # (future, snapshot)
                # One compiled dispatch for the whole-tree rebase (an eager
                # per-leaf chain costs ~3 dispatches/leaf of pure overhead).
                rebase_fn = jax.jit(
                    lambda b, p, s: jax.tree.map(
                        lambda bb, pp, ss: bb + (pp - ss), b, p, s
                    )
                )

                def _rebase(state, pending_pair):
                    fut, snap = pending_pair
                    new_params, new_carry = fut.result()
                    base = put_state(new_params)
                    return (
                        state.replace(params=rebase_fn(base, state.params, snap)),
                        new_carry,
                    )

                def _drive(state, carry, pending, windows, exec_window):
                    """One window at a time: compute, record, rebase the
                    previous exchange, launch the next."""
                    for item in windows:
                        with span("window_step", worker=widx):
                            state, ms, wsize = exec_window(state, item)
                            jax.block_until_ready(ms["loss"])
                        win_histories[widx].append((ms, wsize, time.time()))
                        if self.publisher is not None:
                            # Already block_until_ready'd above: holding
                            # the newest window's loss array costs no
                            # extra device sync; the publisher thread
                            # materializes ONE float from it lazily.
                            self._publish_loss = ms["loss"]
                        if health is not None:
                            health.record_window(widx, wsize)
                        if pending is not None:
                            with span("ps_rebase", worker=widx):
                                state, carry = _rebase(state, pending)
                            if health is not None:
                                health.record_rebase(widx)
                            pending = None
                        if exchanger is not None:
                            snap = state.params
                            pending = (
                                exchanger.submit(
                                    self.protocol.worker_window,
                                    snap,
                                    carry,
                                    client,
                                ),
                                snap,
                            )
                        else:
                            new_params, carry = self.protocol.worker_window(
                                state.params, carry, client
                            )
                            state = state.replace(params=put_state(new_params))
                    return state, carry, pending

                seed_w = worker_seed(self.seed, widx) if shuffle else None
                try:
                    for part in my_parts:
                        if dpw == 1 and self._use_device_cache(
                            part,
                            device=device,
                            state_bytes=sum(
                                getattr(l, "nbytes", 0)
                                for l in jax.tree.leaves(state)
                            ),
                        ):
                            # Partition lives in HBM whole; the scanned
                            # window gathers batches on device from [W, B]
                            # index arrays — no per-window host feature
                            # traffic.
                            xcol = jax.device_put(
                                np.ascontiguousarray(part[self.features_col]),
                                batch_placement,
                            )
                            ycol = jax.device_put(
                                np.asarray(part[self.label_col]), batch_placement
                            )

                            def exec_cached(state, idx):
                                idx_dev = jax.device_put(idx, batch_placement)
                                s, ms = cached_window_fn(state, xcol, ycol, idx_dev)
                                return s, ms, int(idx.shape[0])

                            state, carry, pending = _drive(
                                state, carry, pending,
                                _index_windows(
                                    part.num_rows, self.batch_size, window,
                                    self.num_epoch, seed_w,
                                ),
                                exec_cached,
                            )
                        else:
                            feed = DeviceFeed(
                                window_batches(
                                    minibatches(
                                        part,
                                        self.batch_size * dpw,
                                        self.features_col,
                                        self.label_col,
                                        num_epoch=self.num_epoch,
                                        seed=seed_w,
                                    ),
                                    window,
                                ),
                                sharding=batch_placement,
                                buffer_size=2,
                            )

                            def exec_fed(state, wbatch):
                                s, ms = window_fn(state, wbatch)
                                return s, ms, int(wbatch["features"].shape[0])

                            state, carry, pending = _drive(
                                state, carry, pending, feed, exec_fed
                            )
                    if pending is not None:
                        state, carry = _rebase(state, pending)
                        pending = None
                finally:
                    if exchanger is not None:
                        exchanger.shutdown(wait=True)
                final_states[widx] = jax.device_get(state.model_state)
            except BaseException as e:  # surfaced to the driver below
                errors[widx] = e

        threads = [
            threading.Thread(target=worker_loop, args=(w,), name=f"worker-{w}")
            for w in range(self.num_workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        center = ps.get_model()
        if pub_thread is not None:
            pub_stop.set()
            pub_thread.join(timeout=10)
            # Final snapshot: the publish directory always ends on the
            # run's final center, even for runs shorter than one cadence
            # interval.
            final_loss = (float(np.asarray(self._publish_loss)[-1])
                          if self._publish_loss is not None else None)
            self.publisher.publish(
                {"params": center},
                step=int(self.parameter_server.num_commits),
                loss=final_loss)
        if ckpt_mgr is not None:
            stop_ckpt.set()
            ckpt_thread.join(timeout=10)
            ckpt_mgr.save(
                self.parameter_server.num_commits,
                ps_center=center,
                ps_num_updates=self.parameter_server.num_updates,
                meta={"weight_version":
                      int(self.parameter_server.num_commits)},
            )
            ckpt_mgr.close()
        self.stop_service()
        for e in errors:
            if e is not None:
                raise e

        self.history = []
        # Per-worker (wall_time, window_len) pairs — steady-state throughput
        # analysis (benchmarks/step_variance.py) without polluting history.
        self.window_times = [
            [(t, wsize) for _, wsize, t in hist] for hist in win_histories
        ]
        for w, hist in enumerate(win_histories):
            for ms, wsize, _ in hist:
                arrs = {k: np.asarray(v) for k, v in ms.items()}
                self.history.extend(
                    {**{k: float(a[j]) for k, a in arrs.items()}, "worker": w}
                    for j in range(wsize)
                )
        model_state = next((s for s in final_states if s), {}) or {}
        variables = {"params": center, **model_state}
        self._emit_history()
        self.record_training_stop()
        return TrainedModel(self.model, variables)


class DOWNPOUR(AsynchronousDistributedTrainer):
    """Downpour SGD (reference § ``DOWNPOUR``)."""

    protocol_cls = DOWNPOURProtocol

    def __init__(self, *args, communication_window: int = 5, **kwargs):
        super().__init__(*args, communication_window=communication_window, **kwargs)


class ADAG(AsynchronousDistributedTrainer):
    """Asynchronous Distributed Adaptive Gradients — accumulated-gradient
    normalization (reference § ``ADAG``)."""

    protocol_cls = ADAGProtocol

    def __init__(self, *args, communication_window: int = 12, **kwargs):
        super().__init__(*args, communication_window=communication_window, **kwargs)


class AEASGD(AsynchronousDistributedTrainer):
    """Asynchronous Elastic Averaging SGD (reference § ``AEASGD``).

    Tuning note: ``alpha = rho * learning_rate`` is the rate at which the
    CENTER tracks the workers per exchange — and the returned model IS the
    center. The reference defaults (rho=5, SGD lr~0.1) give alpha=0.5;
    with adam-scale learning rates (1e-3) the same rho leaves alpha=0.005
    and the center barely leaves its init within a short run — scale rho
    up to land alpha in a working 0.05–0.5 band. Measured on the digits
    acceptance task (20 epochs): rho=1 (alpha=1e-3) → 0.15 accuracy, the
    near-untrained center; rho=50 (alpha=0.05) → single-node parity
    (``tests/test_real_data.py``)."""

    protocol_cls = AEASGDProtocol

    def __init__(
        self,
        *args,
        communication_window: int = 32,
        rho: float = 5.0,
        learning_rate: float = 0.1,
        **kwargs,
    ):
        super().__init__(
            *args,
            communication_window=communication_window,
            rho=rho,
            learning_rate=learning_rate,
            **kwargs,
        )

    def _allocate_protocol(self, **kwargs):
        # The elastic force uses the same learning rate as the local SGD
        # (reference AEASGD kwargs couple them); self.learning_rate is set by
        # Trainer.__init__ before protocol allocation.
        kwargs.setdefault(
            "learning_rate",
            self.learning_rate if self.learning_rate is not None else 0.1,
        )
        return self.protocol_cls(**kwargs)


class EAMSGD(AEASGD):
    """Elastic Averaging Momentum SGD (reference § ``EAMSGD``)."""

    protocol_cls = EAMSGDProtocol

    def __init__(self, *args, momentum: float = 0.9, **kwargs):
        super().__init__(*args, momentum=momentum, **kwargs)


class DynSGD(AsynchronousDistributedTrainer):
    """Staleness-damped async SGD (reference § ``DynSGD``)."""

    protocol_cls = DynSGDProtocol

    def __init__(self, *args, communication_window: int = 5, **kwargs):
        super().__init__(*args, communication_window=communication_window, **kwargs)
