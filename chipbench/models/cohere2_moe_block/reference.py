"""The plain reference of the ``cohere2_moe`` block (Command A+), given one
chip's share: straightforward ``jax.numpy`` in float32 at ``highest``, no
kernels, no cache, no batching; the sequence goes through in blocks of
tokens only so that a 15 k-token request fits beside the weights.

It imports nothing of the program and takes nothing the program has made:
its weights come from ``make_weights`` below, from the seed, and the harness
hands THE SAME arrays to the program through the program's own init hook.
They are held in bfloat16, the type the configuration states, and are
raised to float32 at each product; every other value is float32.

The equations, each from the published ``config.json`` (``x``: ``[S, h]``):

- norm: ``LN(x) = (x - mean) / sqrt(var + layer_norm_eps) * g``, no bias;
- block (``use_parallel_block``): ``n = LN_l(x)``; ``x <- x + Attn_l(n) +
  FFN_l(n)``, one norm a layer feeding both;
- attention: ``q = n Wq``, ``k = n Wk``, ``v = n Wv``, no bias, no QK-norm;
  query head ``h`` reads K/V head ``h // (heads / kv_heads)``; scale
  ``head_dim ** -0.5``. ``sliding_attention``: rotary on q and k over the
  whole head (``rotary_pct`` 1), interleaved pairs ``(x[2i], x[2i+1])``
  (``rope_gptj``), ``rope_theta``; query ``i`` sees key ``j`` iff
  ``0 <= i - j < sliding_window``. ``full_attention``: no positional signal,
  every ``j <= i``;
- FFN, every layer (``first_k_dense_replace`` 0): ``s = sigmoid(n Wr)`` over
  ALL the model's experts; ``T`` = the ``num_experts_per_tok`` largest;
  ``w_e = s_e / sum_{T} s``; expert ``f(n) = (silu(n Wgate) * (n Wup))
  Wdown``; ``FFN(n) = sum_{e in T, held here} w_e f_e(n) + mean_j
  f_shared_j(n)``;
- ends: ``x0 = E[ids]``; logits ``= logit_scale * LN_f(x_L) E^T``, tied.

ASSUMED (the configuration's file lists the same under ``assumed``):
``intermediate_size`` is the width of ONE expert, routed and shared alike;
``shared_expert_combination_strategy: "average"`` is the mean of the shared
experts' outputs, added unweighted to the routed sum; the window counts the
query's own position; router scores and the choice of experts in float32.

THE SHARE: the sizes name the query heads, K/V heads, routed experts
(``first_expert`` .. ``+ num_experts``) and embedding rows held here; the
router keeps ``num_router_outputs`` and normalises over all the chosen,
held here or not. What the absent chips would add is left out.

``matmul`` picks the precision of every matrix product's operands:
``"f32"`` (the reference proper), ``"bf16"`` (what the configuration
states) and ``"fp8"`` (per-tensor scaled float8_e4m3: the control, the step
below bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024  # tokens a block; a test passes a smaller one


def weight_spec(m: dict) -> dict:
    """Leaf name -> shape, for a model given as sizes (a configuration
    file's ``model.config``)."""
    h, d, f = m["hidden_size"], m["head_dim"], m["intermediate_size"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    spec = {"token_embed": (m["vocab_size"], h), "ln_final_scale": (h,)}
    for i in range(len(m["layer_types"])):
        p = f"layer_{i}/"
        spec[p + "ln_scale"] = (h,)
        spec[p + "attention/query/kernel"] = (h, hq * d)
        spec[p + "attention/key/kernel"] = (h, hkv * d)
        spec[p + "attention/value/kernel"] = (h, hkv * d)
        spec[p + "attention/out/kernel"] = (hq * d, h)
        spec[p + "router/kernel"] = (h, m["num_router_outputs"])
        for group, count in (("experts", m["num_experts"]),
                             ("shared_experts", m["num_shared_experts"])):
            spec[p + group + "/gate"] = (count, h, f)
            spec[p + group + "/up"] = (count, h, f)
            spec[p + group + "/down"] = (count, f, h)
    return spec


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed) % 2**64
    data = np.array([seed >> 32 ^ stream, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data))


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        z = 1.0 + 0.02 * z
    elif kind == "embed":
        z = 0.02 * z
    else:  # a matrix (or a stack of them): variance 1 / fan_in
        z = z * shape[-2] ** -0.5
    return z.astype(jnp.bfloat16)


def make_weights(m: dict, seed: int) -> dict:
    """Every leaf from the seed, on the device, in bfloat16 (the type the
    configuration holds its parameters in), one leaf at a time so that no
    more than one float32 draft of a leaf is alive. Matrices are normal
    with variance 1/fan_in, the embedding normal(0.02), LayerNorm scales
    1 + normal(0.02)."""
    key = seed_key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_spec(m).items())):
        kind = ("scale" if name.endswith("scale") else
                "embed" if name == "token_embed" else "matrix")
        out[name] = _leaf(jax.random.fold_in(key, i), shape, kind)
    return out


def _round_to(x, matmul: str):
    x = x.astype(jnp.float32)
    if matmul == "f32":
        return x
    if matmul == "bf16":
        # reduce_precision: a convert pair there and back is folded away
        # by the TPU compiler (PERF.md section 6, PR 24 (d))
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if matmul == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax  # float8_e4m3fn's largest finite value
        return (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    raise ValueError(f"unknown matmul precision {matmul!r}")


def _mm(spec: str, a, b, matmul: str):
    return jnp.einsum(spec, _round_to(a, matmul), _round_to(b, matmul),
                      precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rotary(x, positions, theta):
    """``x``: ``[S, heads, d]``; pairs ``(x[2i], x[2i+1])`` turned by
    ``positions * theta ** (-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], -1)
    return turned.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "sliding", "theta",
                                             "eps", "matmul"))
def _norm_qkv(x, start, scale, wq, wk, wv, heads, sliding, theta, eps,
              matmul):
    """One block of tokens: ``n = LN(x)`` and its q, k, v (turned by the
    rotary where the layer is a window layer)."""
    n = _layer_norm(x, scale, eps)
    S, (hq, hkv) = x.shape[0], heads
    q = _mm("sh,hd->sd", n, wq, matmul).reshape(S, hq, -1)
    k = _mm("sh,hd->sd", n, wk, matmul).reshape(S, hkv, -1)
    v = _mm("sh,hd->sd", n, wv, matmul).reshape(S, hkv, -1)
    if sliding:
        positions = start + jnp.arange(S)
        q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
    return n, q, k, v


@functools.partial(jax.jit, static_argnames=("window", "matmul"))
def _attend(q, start, k, v, wo, window, matmul):
    """A block's queries (first at position ``start``) over ALL the
    sequence's keys, masked: ``j <= i`` and, with a window, ``i - j <
    window``. Returns ``concat(heads) Wo``."""
    S, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, matmul) * d ** -0.5
    i = (start + jnp.arange(S))[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    scores = jnp.where(seen[None], scores, -1e30)
    out = _mm("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v, matmul)
    return _mm("sd,dh->sh", out.reshape(S, hq * d), wo, matmul)


@functools.partial(jax.jit, static_argnames=("top", "matmul"))
def _route(n, wr, top, matmul):
    """``[S, experts]`` weights: ``s_e / sum_T s`` on the ``top`` largest
    sigmoid scores of a token, 0 elsewhere."""
    scores = jax.nn.sigmoid(_mm("sh,he->se", n, wr, matmul))
    best, ids = jax.lax.top_k(scores, top)
    weights = best / jnp.sum(best, axis=-1, keepdims=True)
    rows = jnp.arange(n.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, ids].set(weights)


@functools.partial(jax.jit, static_argnames=("matmul",))
def _expert(n, wg, wu, wd, matmul):
    gate = jax.nn.silu(_mm("sh,hf->sf", n, wg, matmul))
    return _mm("sf,fh->sh", gate * _mm("sh,hf->sf", n, wu, matmul), wd,
               matmul)


def _ffn(w, p, n, m, matmul):
    """Routed experts held here, weighted, plus the shared experts' mean."""
    weights = _route(n, w[p + "router/kernel"], m["num_experts_per_tok"],
                     matmul)
    out = jnp.zeros_like(n)
    first = m.get("first_expert", 0)
    for e in range(m["num_experts"]):
        out += weights[:, first + e, None] * _expert(
            n, *(w[p + f"experts/{part}"][e] for part in ("gate", "up", "down")),
            matmul)
    shared = m["num_shared_experts"]
    for j in range(shared):
        out += _expert(n, *(w[p + f"shared_experts/{part}"][j]
                            for part in ("gate", "up", "down")),
                       matmul) / shared
    return out


def hidden_states(w: dict, m: dict, ids, matmul: str = "f32",
                  block: int = BLOCK, key_rows: int = 0):
    """The last layer's output ``[S, h]`` (before the final norm) for token
    ids ``[S]``, ``S`` a multiple of ``block``. ``key_rows`` pads every
    layer's keys and values with rows no query can see (they lie after the
    last), so that sequences of any length share one compiled shape."""
    ids = jnp.asarray(ids, jnp.int32)
    S = ids.shape[0]
    if S % block:
        raise ValueError(f"{S} tokens do not divide into blocks of {block}")
    heads = (m["num_attention_heads"], m["num_key_value_heads"])
    x = [w["token_embed"][ids[lo:lo + block]].astype(jnp.float32)
         for lo in range(0, S, block)]
    for i, layer_type in enumerate(m["layer_types"]):
        p = f"layer_{i}/"
        sliding = layer_type == "sliding_attention"
        if not sliding and layer_type != "full_attention":
            raise ValueError(f"unknown layer type {layer_type!r}")
        parts = [_norm_qkv(
            xb, b * block, w[p + "ln_scale"],
            *(w[p + f"attention/{name}/kernel"]
              for name in ("query", "key", "value")),
            heads=heads, sliding=sliding, theta=float(m["rope_theta"]),
            eps=float(m["layer_norm_eps"]), matmul=matmul)
            for b, xb in enumerate(x)]
        unseen = ((0, max(key_rows - S, 0)), (0, 0), (0, 0))
        k = jnp.pad(jnp.concatenate([part[2] for part in parts]), unseen)
        v = jnp.pad(jnp.concatenate([part[3] for part in parts]), unseen)
        for b, (n, q, _, _) in enumerate(parts):
            attn = _attend(q, b * block, k, v, w[p + "attention/out/kernel"],
                           window=m["sliding_window"] if sliding else None,
                           matmul=matmul)
            x[b] = x[b] + attn + _ffn(w, p, n, m, matmul)
    return jnp.concatenate(x)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "matmul"))
def _head(x, ln_scale, embed, eps, scale, matmul):
    return scale * _mm("sh,vh->sv", _layer_norm(x, ln_scale, eps), embed,
                       matmul)


def forward(w: dict, ids, m: dict, matmul: str = "f32", block: int = BLOCK):
    """Logits ``[S, V]`` in float32 for token ids ``[S]``."""
    x = hidden_states(w, m, ids, matmul, block)
    return _head(x, w["ln_final_scale"], w["token_embed"],
                 eps=float(m["layer_norm_eps"]),
                 scale=float(m["logit_scale"]), matmul=matmul)


def position_logits(w: dict, m: dict, prompt, served, matmul: str,
                    pad_to: int, block: int = BLOCK):
    """One forward pass over ``prompt + served`` (padded on the right to
    whole blocks, at most ``pad_to``; causal attention makes the padding
    invisible). Returns the logits ``[len(served), V]`` at the positions
    that produced each served token."""
    seq = list(prompt) + list(served)
    padded = -(-len(seq) // block) * block
    if padded > max(pad_to, block):
        raise ValueError(f"{len(seq)} tokens pass the context of {pad_to}")
    ids = np.zeros((padded,), np.int32)
    ids[:len(seq)] = seq
    x = hidden_states(w, m, ids, matmul, block, key_rows=pad_to)
    # position i predicts i + 1; a power-of-two count of rows bounds the
    # head's compiles
    rows = 1 << max(len(served) - 1, 0).bit_length()
    at = np.minimum(len(prompt) - 1 + np.arange(rows), len(seq) - 2)
    logits = _head(x[at], w["ln_final_scale"], w["token_embed"],
                   eps=float(m["layer_norm_eps"]),
                   scale=float(m["logit_scale"]), matmul=matmul)
    return logits[:len(served)]


GAP_POSITIONS = 64  # positions a compared gap is the mean over


def token_gaps(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position (0 where the token IS the reference's best)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref_logits, axis=-1) - got)


def gaps_below_best(ref_logits, tokens) -> np.ndarray:
    """The gaps the harness takes the widest of: :func:`token_gaps`
    summed over each run of ``GAP_POSITIONS`` consecutive served positions,
    over ``GAP_POSITIONS`` (a shorter last run counts the positions it
    lacks as 0: a handful of tokens is no sample).

    Why a mean and not each position's own gap, as the dense block's
    reference has it: this block's routing is discrete. The 8th and 9th
    largest of 128 router scores lie within bfloat16's rounding of each
    other in about one token in twenty a layer, and a token whose experts
    differ from the float32 reference's can put another candidate first:
    a SOUND bfloat16 run reads single positions 0.25-0.6 below the best
    (the top two logits lie 0.3 apart on average), which is what the
    float8 control reads too (0.55-1.5; my chip runs, PR 29). But it does
    so at one position in fifty against float8's one in three, so over 64
    positions the two lie 7 x apart (0.005-0.011 against 0.07-0.12), and a
    single foreign token (about 5 below the best) still reads 0.08."""
    gaps = token_gaps(ref_logits, tokens)
    runs = -(-len(gaps) // GAP_POSITIONS)
    padded = np.pad(gaps, (0, runs * GAP_POSITIONS - len(gaps)))
    return padded.reshape(runs, GAP_POSITIONS).sum(axis=1) / GAP_POSITIONS
