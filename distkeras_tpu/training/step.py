"""The jitted train-step engine.

TPU-native replacement for the reference's per-batch
``model.train_on_batch`` call inside Spark executors
(``distkeras/workers.py`` § ``Worker.train`` hot loop): one pure function
``(TrainState, batch) -> (TrainState, metrics)``, compiled once by XLA and
re-used for every minibatch. All protocol trainers (sync and async) drive
this same engine; distribution is layered on via shardings
(:mod:`distkeras_tpu.parallel`), not by changing the step.
"""

from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct

from distkeras_tpu.models.core import Model
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.metrics import accuracy as accuracy_metric

__all__ = [
    "TrainState",
    "make_train_step",
    "make_window_train_step",
    "make_eval_step",
    "apply_aux_loss",
]


def apply_aux_loss(task_loss, new_model_state: dict, weight: float):
    """Fold sown auxiliary losses (MoE load balancing, ...) into the
    objective and strip them from carried state. Shared by the single-chip
    and GSPMD step engines."""
    aux = new_model_state.pop("aux_loss", None)
    if aux is not None:
        task_loss = task_loss + weight * sum(
            jnp.sum(leaf) for leaf in jax.tree.leaves(aux)
        )
    return task_loss, new_model_state


@struct.dataclass
class TrainState:
    """Everything a training step needs, as one PyTree.

    ``params`` is the trainable subtree; ``model_state`` holds non-trainable
    collections (BatchNorm stats, ...); ``rng`` seeds dropout for this step.
    """

    params: Any
    model_state: Any
    opt_state: Any
    step: jnp.ndarray
    rng: jax.Array

    @property
    def variables(self) -> dict:
        return {"params": self.params, **self.model_state}

    @classmethod
    def create(
        cls,
        model: Model,
        optimizer: optax.GradientTransformation,
        rng: jax.Array | int = 0,
    ) -> "TrainState":
        if isinstance(rng, int):
            rng = jax.random.PRNGKey(rng)
        init_rng, step_rng = jax.random.split(rng)
        variables = model.init(init_rng)
        params = variables["params"]
        model_state = {k: v for k, v in variables.items() if k != "params"}
        return cls(
            params=params,
            model_state=model_state,
            opt_state=optimizer.init(params),
            step=jnp.zeros((), jnp.int32),
            rng=step_rng,
        )


def make_train_step(
    model: Model,
    optimizer: optax.GradientTransformation,
    loss: str | Callable,
    metrics: tuple[str, ...] = ("accuracy",),
    jit: bool = True,
    donate: bool = True,
    remat: bool = False,
    aux_loss_weight: float = 0.01,
    grad_accum_steps: int = 1,
):
    """Build ``step(state, batch) -> (state, metrics_dict)``.

    ``batch`` is ``{"features": [B, ...], "label": [B, ...]}``. The returned
    function is jit-compiled with the state donated (params are updated
    in-place in HBM, halving peak memory vs copy-on-update). ``remat=True``
    wraps the forward pass in ``jax.checkpoint`` — activations are
    recomputed in the backward pass instead of held in HBM, trading FLOPs
    for memory (long sequences / deep models on one chip).
    ``grad_accum_steps=k`` splits the batch into k micro-batches scanned
    sequentially with gradient averaging and ONE optimizer update — a k×
    effective batch at 1/k activation memory.
    """
    loss_fn = get_loss(loss)
    apply_fn = model.apply
    if remat:
        apply_fn = jax.checkpoint(
            model.apply, static_argnums=(2,), policy=None
        )
    accum = max(1, int(grad_accum_steps))

    def forward(params, model_state, features, labels, step_rng):
        variables = {"params": params, **model_state}
        outputs, new_model_state = apply_fn(
            variables, features, True, rngs={"dropout": step_rng}
        )
        task_loss, new_model_state = apply_aux_loss(
            loss_fn(outputs, labels), new_model_state, aux_loss_weight
        )
        return task_loss, (outputs, new_model_state)

    def step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        step_rng = jax.random.fold_in(state.rng, state.step)

        if accum == 1:
            (loss_value, (outputs, new_model_state)), grads = jax.value_and_grad(
                forward, has_aux=True
            )(state.params, state.model_state, batch["features"], batch["label"],
              step_rng)
            out_metrics = {"loss": loss_value}
            if "accuracy" in metrics:
                out_metrics["accuracy"] = accuracy_metric(outputs, batch["label"])
        else:
            B = batch["features"].shape[0]
            if B % accum:
                raise ValueError(
                    f"batch size {B} not divisible by grad_accum_steps "
                    f"{accum} (samples would be silently dropped)"
                )
            micro = B // accum
            feats = batch["features"][: micro * accum].reshape(
                accum, micro, *batch["features"].shape[1:]
            )
            labels = batch["label"][: micro * accum].reshape(
                accum, micro, *batch["label"].shape[1:]
            )

            def micro_step(carry, xs):
                grads_acc, loss_acc, acc_acc, model_state = carry
                f, l, i = xs
                rng_i = jax.random.fold_in(step_rng, i)
                (loss_value, (outputs, new_ms)), grads = jax.value_and_grad(
                    forward, has_aux=True
                )(state.params, model_state, f, l, rng_i)
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                acc = (
                    accuracy_metric(outputs, l)
                    if "accuracy" in metrics
                    else jnp.zeros(())
                )
                return (
                    grads_acc,
                    loss_acc + loss_value,
                    acc_acc + acc,
                    new_ms if new_ms else model_state,
                ), None

            zero_grads = jax.tree.map(jnp.zeros_like, state.params)
            (grads, loss_sum, acc_sum, new_model_state), _ = jax.lax.scan(
                micro_step,
                (zero_grads, jnp.zeros(()), jnp.zeros(()), state.model_state),
                (feats, labels, jnp.arange(accum)),
            )
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss_value = loss_sum / accum
            out_metrics = {"loss": loss_value}
            if "accuracy" in metrics:
                out_metrics["accuracy"] = acc_sum / accum

        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            params=new_params,
            model_state=new_model_state if new_model_state else state.model_state,
            opt_state=new_opt_state,
            step=state.step + 1,
        )
        return new_state, out_metrics

    if jit:
        return jax.jit(step, donate_argnums=(0,) if donate else ())
    return step


def make_window_train_step(
    model: Model,
    optimizer: optax.GradientTransformation,
    loss: str | Callable,
    metrics: tuple[str, ...] = ("accuracy",),
    donate: bool = False,
    **step_kwargs,
):
    """Build ``window(state, batches) -> (state, metrics)`` where ``batches``
    holds a whole communication window stacked on a leading axis
    (``{"features": [W, B, ...], "label": [W, B, ...]}``) and the W steps run
    as ONE ``lax.scan`` inside ONE compiled program.

    This is the async-worker hot loop (reference ``distkeras/workers.py`` §
    ``Worker.train``: W ``train_on_batch`` calls between PS round trips)
    collapsed to a single XLA dispatch: one host→device launch per window
    instead of per batch, so the Python thread is free (and the GIL
    released) for the overlapped PS exchange while the device crunches the
    window. Metrics come back stacked ``[W]`` per key.
    """
    base = make_train_step(
        model, optimizer, loss, metrics, jit=False, donate=False, **step_kwargs
    )

    def window(state: TrainState, batches: dict) -> tuple[TrainState, dict]:
        return jax.lax.scan(base, state, batches)

    return jax.jit(window, donate_argnums=(0,) if donate else ())


def make_cached_window_train_step(
    model: Model,
    optimizer: optax.GradientTransformation,
    loss: str | Callable,
    metrics: tuple[str, ...] = ("accuracy",),
    donate: bool = False,
    **step_kwargs,
):
    """Window step over a device-resident dataset: ``window(state, xcol,
    ycol, idx)`` where ``xcol``/``ycol`` are the WHOLE partition living in
    HBM and ``idx`` is ``[W, B]`` int32 row indices (shuffling = a fresh
    permutation on the host, bytes-per-window = W·B·4 instead of the full
    batch tensors). The scan body gathers its minibatch on device — zero
    host→HBM feature traffic in the steady state. Worth it whenever the
    partition fits HBM comfortably (MNIST/CIFAR-scale; the async trainers
    auto-enable it under ``device_cache="auto"``).
    """
    base = make_train_step(
        model, optimizer, loss, metrics, jit=False, donate=False, **step_kwargs
    )

    def window(state: TrainState, xcol, ycol, idx) -> tuple[TrainState, dict]:
        def body(s, ix):
            batch = {
                "features": jnp.take(xcol, ix, axis=0),
                "label": jnp.take(ycol, ix, axis=0),
            }
            return base(s, batch)

        return jax.lax.scan(body, state, idx)

    return jax.jit(window, donate_argnums=(0,) if donate else ())


def make_eval_step(model: Model, loss: str | Callable | None = None, jit: bool = True):
    """Build ``eval_step(variables, batch) -> metrics_dict`` (no grad)."""
    loss_fn = get_loss(loss) if loss is not None else None

    def eval_step(variables: dict, batch: dict) -> dict:
        outputs, _ = model.apply(variables, batch["features"], train=False)
        out = {"accuracy": accuracy_metric(outputs, batch["label"])}
        if loss_fn is not None:
            out["loss"] = loss_fn(outputs, batch["label"])
        return out

    return jax.jit(eval_step) if jit else eval_step
