"""The plain reference: a pre-LN transformer (BERT encoder or GPT decoder
over the same block) in straightforward ``jax.numpy`` and float32, with its
loss, gradients and Adam. No kernels, no cache, no batching tricks.

It imports nothing of the program and takes nothing the program has made:
its weights come from ``make_weights`` below, from the seed, and the
harness hands THE SAME arrays to the program through the program's own
init hook. The names of the leaves are the reference's own
(``layer_3/attention/query/kernel``); the harness checks that the program's
tree has the same names and shapes and refuses to run otherwise.

The block, as the program under test implements it and as this follows it:
``x = tok_embed[ids] + pos_embed[:S]``; per layer ``x += out(attn(LN(x)))``
then ``x += mlp_out(gelu_tanh(mlp_in(LN(x))))``; final LN; logits through
the tied embedding plus a bias. LayerNorm epsilon 1e-6, softmax scale
``head_dim ** -0.5``, every projection with a bias.

``matmul`` picks the precision of every matrix product:

- ``"f32"``  — float32 at ``highest`` (the reference proper);
- ``"bf16"`` — operands rounded to bfloat16 (what the configurations state);
- ``"fp8"``  — operands scaled per tensor and rounded to float8_e4m3: the
  control, the step below bfloat16 that would tempt a later PR.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LN_EPS = 1e-6


def weight_spec(model: dict) -> dict:
    """Leaf name -> shape, for a model given as sizes (a configuration
    file's ``model.config``)."""
    h, mlp, vocab = model["hidden_size"], model["mlp_dim"], model["vocab_size"]
    spec = {"token_embed/embedding": (vocab, h),
            "pos_embed": (1, model["max_seq_len"], h),
            "ln_final/scale": (h,), "ln_final/bias": (h,),
            "mlm_bias": (vocab,)}
    for i in range(model["num_layers"]):
        p = f"layer_{i}/"
        for ln in ("ln_attn", "ln_mlp"):
            spec[p + ln + "/scale"] = (h,)
            spec[p + ln + "/bias"] = (h,)
        for proj in ("query", "key", "value", "out"):
            spec[p + f"attention/{proj}/kernel"] = (h, h)
            spec[p + f"attention/{proj}/bias"] = (h,)
        spec[p + "mlp_in/kernel"] = (h, mlp)
        spec[p + "mlp_in/bias"] = (mlp,)
        spec[p + "mlp_out/kernel"] = (mlp, h)
        spec[p + "mlp_out/bias"] = (h,)
    return spec


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed) % 2**64
    data = np.array([seed >> 32 ^ stream, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data))


def make_weights(model: dict, seed: int) -> dict:
    """Every leaf from the seed, on the device, in ONE jitted call, in
    float32 (the type the program keeps its parameters in). Kernels are
    normal with variance 1/fan_in, embeddings normal(0.02), biases
    normal(0.02) and LayerNorm scales 1 + normal(0.02): nothing is zero, so
    a dropped bias or scale shows."""
    spec = weight_spec(model)
    names = sorted(spec)

    @jax.jit
    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape = spec[name]
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith("kernel"):
                out[name] = z * (shape[0] ** -0.5)
            elif name.endswith("scale"):
                out[name] = 1.0 + 0.02 * z
            else:
                out[name] = 0.02 * z
        return out

    return build(seed_key(seed))


def _round_to(x, matmul: str):
    """``x`` with its values rounded to the named type, and the identity
    for a gradient (straight through): the backward products then see the
    same rounded operands and float32 cotangents, as a mixed-precision
    step keeps them."""
    if matmul == "f32":
        return x
    if matmul == "bf16":
        # reduce_precision: a convert pair there and back is folded away
        # by the TPU compiler (my chip run, PR 24: it read a gap of 1e-7)
        rounded = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif matmul == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax  # float8_e4m3fn's largest finite value
        rounded = (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    else:
        raise ValueError(f"unknown matmul precision {matmul!r}")
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(spec: str, a, b, matmul: str):
    return jnp.einsum(spec, _round_to(a, matmul), _round_to(b, matmul),
                      precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(w: dict, ids, model: dict, matmul: str = "f32"):
    """Logits ``[B, S, V]`` in float32 for token ids ``[B, S]``."""
    heads = model["num_heads"]
    B, S = ids.shape
    x = w["token_embed/embedding"][ids] + w["pos_embed"][0, :S]
    causal = jnp.tril(jnp.ones((S, S), bool)) if model["causal"] else None
    for i in range(model["num_layers"]):
        p = f"layer_{i}/"
        y = _layer_norm(x, w[p + "ln_attn/scale"], w[p + "ln_attn/bias"])
        q, k, v = (
            (_mm("bsh,hd->bsd", y, w[p + f"attention/{n}/kernel"], matmul)
             + w[p + f"attention/{n}/bias"]).reshape(B, S, heads, -1)
            for n in ("query", "key", "value"))
        scores = _mm("bqhd,bkhd->bhqk", q, k, matmul) * q.shape[-1] ** -0.5
        if causal is not None:
            scores = jnp.where(causal, scores, -1e30)
        attn = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                   matmul).reshape(B, S, -1)
        x = x + _mm("bsh,hd->bsd", attn, w[p + "attention/out/kernel"],
                    matmul) + w[p + "attention/out/bias"]
        y = _layer_norm(x, w[p + "ln_mlp/scale"], w[p + "ln_mlp/bias"])
        y = _gelu_tanh(_mm("bsh,hm->bsm", y, w[p + "mlp_in/kernel"], matmul)
                       + w[p + "mlp_in/bias"])
        x = x + _mm("bsm,mh->bsh", y, w[p + "mlp_out/kernel"],
                    matmul) + w[p + "mlp_out/bias"]
    x = _layer_norm(x, w["ln_final/scale"], w["ln_final/bias"])
    return _mm("bsh,vh->bsv", x, w["token_embed/embedding"],
               matmul) + w["mlm_bias"]


def mlm_loss(w: dict, ids, labels, model: dict, matmul: str = "f32"):
    """Mean over every position of the softmax cross-entropy against the
    integer labels (the program's ``categorical_crossentropy``)."""
    logp = jax.nn.log_softmax(forward(w, ids, model, matmul), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


@functools.lru_cache(maxsize=8)
def _block_grad(model_items: tuple, matmul: str):
    model = dict(model_items)
    return jax.jit(jax.value_and_grad(
        lambda w, ids, labels: mlm_loss(w, ids, labels, model, matmul)))


def loss_and_grad(w: dict, ids, labels, model: dict, matmul: str,
                  block_rows: int, rows=None):
    """Loss and gradient of one batch, in blocks of rows so that float32
    activations fit beside the weights. ``rows`` (a slice) keeps only part
    of the batch and takes the mean over that part: the "half of the batch
    left out" fault, planted here in the reference's place."""
    if rows is not None:
        ids, labels = ids[rows], labels[rows]
    n = ids.shape[0]
    if n % block_rows:
        raise ValueError(f"{n} rows do not divide into blocks of {block_rows}")
    fn = _block_grad(tuple(sorted(model.items())), matmul)
    loss, grad = 0.0, None
    for lo in range(0, n, block_rows):
        l, g = fn(w, ids[lo:lo + block_rows], labels[lo:lo + block_rows])
        loss = loss + l
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    blocks = n // block_rows
    return loss / blocks, jax.tree.map(lambda g: g / blocks, grad)


@jax.jit
def _adam(w, m, v, g, step, lr):
    m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, m, g)
    v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, v, g)
    c1, c2 = 1 - ADAM_B1 ** step, 1 - ADAM_B2 ** step
    w = jax.tree.map(
        lambda w, m, v: w - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        w, m, v)
    return w, m, v


@jax.jit
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _change_norms(a: dict, b: dict) -> dict:
    return leaf_norms({k: a[k] - b[k] for k in a})


def train_steps(model: dict, seed: int, batches, lr: float,
                matmul: str = "f32", block_rows: int = 4, rows=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps from the seed's
    weights. Returns each step's loss, the norm of every leaf of the first
    gradient, and the norm of every leaf's change over all the steps."""
    w0 = make_weights(model, seed)
    w = w0
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad0 = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        loss, g = loss_and_grad(w, jnp.asarray(ids), jnp.asarray(labels),
                                model, matmul, block_rows, rows)
        if grad0 is None:
            grad0 = {k: float(x) for k, x in leaf_norms(g).items()}
        w, m, v = _adam(w, m, v, g, jnp.float32(t), jnp.float32(lr))
        losses.append(float(loss))
    change = {k: float(x) for k, x in _change_norms(w, w0).items()}
    return {"losses": losses, "grad_norms": grad0, "change_norms": change}


@functools.lru_cache(maxsize=8)
def _logits_fn(model_items: tuple, matmul: str):
    model = dict(model_items)
    return jax.jit(lambda w, ids: forward(w, ids, model, matmul))


def position_logits(w: dict, model: dict, prompt, served, matmul: str,
                    pad_to: int):
    """One forward pass over ``prompt + served`` (padded on the right to
    ``pad_to``; causal attention makes the padding invisible). Returns the
    logits ``[len(served), V]`` at the positions that produced each served
    token."""
    seq = list(prompt) + list(served)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    logits = _logits_fn(tuple(sorted(model.items())), matmul)(w, ids)[0]
    return logits[len(prompt) - 1:len(seq) - 1]  # position i predicts i+1


def gaps_below_best(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position (0 where the token IS the reference's best)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref_logits, axis=-1) - got)
