"""Loss and optimizer registries keyed by the Keras-style string names the
reference trainers accept (``distkeras/trainers.py`` § ``Trainer.__init__``
takes ``loss`` and ``worker_optimizer`` as strings, compiled into the Keras
model inside each worker — ``distkeras/workers.py`` § ``Worker``).

Losses are pure ``(logits/preds, targets) -> scalar`` functions over whole
batches; optimizers are optax gradient transformations.
"""

from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
import optax

__all__ = ["get_loss", "get_optimizer", "LOSSES"]

LossFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def categorical_crossentropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Softmax CE against one-hot (or soft) targets. Targets with integer
    dtype are treated as class indices."""
    if targets.ndim == logits.ndim - 1 or jnp.issubdtype(targets.dtype, jnp.integer):
        labels = targets.astype(jnp.int32).reshape(targets.shape[: logits.ndim - 1])
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    return optax.softmax_cross_entropy(logits, targets).mean()


def binary_crossentropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    targets = targets.reshape(logits.shape).astype(logits.dtype)
    return optax.sigmoid_binary_cross_entropy(logits, targets).mean()


def mean_squared_error(preds: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((preds - targets.reshape(preds.shape)) ** 2)


def mean_absolute_error(preds: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(preds - targets.reshape(preds.shape)))


def fused_categorical_crossentropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Pallas fused softmax-CE (integer labels; large-vocab heads)."""
    from distkeras_tpu.ops.pallas.fused_xent import fused_softmax_xent

    if targets.ndim == logits.ndim:  # one-hot fed in: fall back
        return categorical_crossentropy(logits, targets)
    return fused_softmax_xent(logits, targets)


LOSSES: dict[str, LossFn] = {
    "categorical_crossentropy": categorical_crossentropy,
    "fused_categorical_crossentropy": fused_categorical_crossentropy,
    "sparse_categorical_crossentropy": categorical_crossentropy,
    "binary_crossentropy": binary_crossentropy,
    "mse": mean_squared_error,
    "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error,
    "mean_absolute_error": mean_absolute_error,
}


def get_loss(loss: str | LossFn) -> LossFn:
    """The loss function, by name or as given, under the named scope
    ``loss`` (metadata on its operations: what a device profile groups by;
    the compiled program is the same)."""
    if not callable(loss):
        try:
            loss = LOSSES[loss]
        except KeyError:
            raise ValueError(f"unknown loss {loss!r}; known: {sorted(LOSSES)}") from None
    return jax.named_scope("loss")(loss)


def get_optimizer(
    optimizer: str | optax.GradientTransformation,
    learning_rate: float | None = None,
) -> optax.GradientTransformation:
    """Map the reference's ``worker_optimizer`` strings to optax.

    Defaults follow Keras 1.x/2.x-era defaults the reference notebooks relied
    on (e.g. adagrad lr=0.01, adam lr=0.001).
    """
    if not isinstance(optimizer, str):
        return optimizer
    name = optimizer.lower()
    lr = learning_rate
    if name == "sgd":
        return optax.sgd(lr if lr is not None else 0.01)
    if name == "momentum":
        return optax.sgd(lr if lr is not None else 0.01, momentum=0.9)
    if name == "adam":
        return optax.adam(lr if lr is not None else 0.001)
    if name == "adamw":
        return optax.adamw(lr if lr is not None else 0.001)
    if name == "adagrad":
        return optax.adagrad(lr if lr is not None else 0.01)
    if name == "adadelta":
        return optax.adadelta(lr if lr is not None else 1.0)
    if name == "rmsprop":
        return optax.rmsprop(lr if lr is not None else 0.001)
    raise ValueError(f"unknown optimizer {optimizer!r}")
