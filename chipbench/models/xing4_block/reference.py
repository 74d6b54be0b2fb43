"""The plain reference of the ``xing4_0`` block (Xing4.0-29B-A4B), given the
first stage of a pipeline of whole layers: straightforward ``jax.numpy`` in
float32 at ``highest``, no kernels, no cache, no batching; the sequence goes
through in blocks of tokens only so that a 35 k-token request fits beside
the weights.

It imports nothing of the program and takes nothing the program has made:
its weights come from ``make_weights`` below, from the seed, and the harness
hands THE SAME arrays to the program through the program's own init hook.
They are held in bfloat16, the type the configuration states, and are
raised to float32 at each product; every other value is float32.

The equations, each from the published ``config.json`` (per token; ``C``
hidden, ``n = hc_mult`` residual streams ``X`` of ``C`` each):

- ends: ``X_0 = [e, ..., e]`` (the embedding in every stream); after the
  last layer ``x = sum_i X[i]``, ``RMSNorm_f``, the untied head;
- hyper-connection around each sublayer ``F``: ``x^ = vec(X) /
  rms(vec(X))`` over ``n C`` lanes; ``h_pre = sigmoid(a_pre x^ phi_pre +
  b_pre)``, ``h_post = 2 sigmoid(a_post x^ phi_post + b_post)``, ``H~ =
  clamp(a_res mat(x^ phi_res) + b_res, mhc_h_res_clamp_min, _max)``;
  ``H_res``: ``exp(H~)``, then ``hc_sinkhorn_iters`` times each column
  divided by its sum + ``hc_eps``, then each row likewise; ``u = sum_i
  h_pre[i] X[i]``, ``f = F(RMSNorm_F(u))``, ``X[i] <- sum_j H_res[i, j]
  X[j] + h_post[i] f``;
- attention (MLA, the EXPANDED form): ``c_q = RMSNorm(u W_DQ)``, ``[q_nope |
  q_rope]_h = c_q W_UQ``; ``[c_kv | k_rope] = u W_DKV``, ``c_kv <-
  RMSNorm(c_kv)``; YaRN rotary on ``q_rope`` and the one shared
  ``k_rope``; ``[k_nope | v]_h = c_kv W_UKV``; ``score = (q_nope . k_nope +
  q_rope . k_rope) mscale^2 / sqrt(nope + rope)`` with ``mscale = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax; ``y = concat_h(o_h)
  W_O``;
- FFN: the first ``first_k_dense_replace`` layers a gated SiLU MLP of width
  ``intermediate_size``; then ``s = sigmoid(u W_r)`` over ALL
  ``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s +
  b_corr`` are chosen (``noaux_tc``; ``n_group`` = ``topk_group`` = 1: no
  group stage) and weighted ``s_k / sum s_k * routed_scaling_factor``;
  ``FFN = sum_k w_k E_k + sum E_shared``; every expert a gated SiLU of
  width ``moe_intermediate_size``.

ASSUMED (the configuration's file lists the same under ``assumed``): the two
ends; the column-then-row order of each Sinkhorn step; ``x^``'s norm has no
learned scale; the rotary turns pairs ``(x[2i], x[2i+1])``; the random
draws of ``b_corr``, the ``a``'s and the ``b``'s (``make_weights``).

THE SHARE: all 64 experts, all heads and the whole vocabulary are held;
``num_experts`` (``first_expert`` ..) names the routed experts held here,
the router keeps ``n_routed_experts``.

``matmul`` picks the precision of every matrix product's operands:
``"f32"`` (the reference proper), ``"bf16"`` (what the configuration
states) and ``"fp8"`` (per-tensor scaled float8_e4m3: the control, the step
below bfloat16).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 2048        # tokens a block; a test passes a smaller one
QUERY_BLOCK = 128   # queries a step of attention
KEY_STEP = 4096     # keys are cut to the block's causal reach, in these


def weight_spec(m: dict) -> dict:
    """Leaf name -> shape, for a model given as sizes (a configuration
    file's ``model.config``)."""
    C, V, H = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    n, R, qr = m["hc_mult"], m["kv_lora_rank"], m["q_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    F = m["moe_intermediate_size"]
    spec = {"token_embed": (V, C), "head/kernel": (C, V),
            "final_norm_scale": (C,)}
    for i in range(m["num_hidden_layers"]):
        p = f"layer_{i}/"
        for hc in ("attention_hc/", "ffn_hc/"):
            spec.update({p + hc + "phi_pre": (n * C, n),
                         p + hc + "phi_post": (n * C, n),
                         p + hc + "phi_res": (n * C, n * n),
                         p + hc + "b_pre": (n,), p + hc + "b_post": (n,),
                         p + hc + "b_res": (n, n), p + hc + "alpha": (3,)})
        spec[p + "attention_norm_scale"] = (C,)
        spec[p + "ffn_norm_scale"] = (C,)
        a = p + "attention/"
        spec.update({a + "q_down": (C, qr), a + "q_norm_scale": (qr,),
                     a + "q_up": (qr, H * (nope + rope)),
                     a + "kv_down": (C, R + rope), a + "kv_norm_scale": (R,),
                     a + "kv_up": (R, H * (nope + dv)), a + "out": (H * dv, C)})
        if i < m["first_k_dense_replace"]:
            D = m["intermediate_size"]
            spec.update({p + "mlp/gate": (1, C, D), p + "mlp/up": (1, C, D),
                         p + "mlp/down": (1, D, C)})
            continue
        spec[p + "router/kernel"] = (C, m["n_routed_experts"])
        spec[p + "router_bias"] = (m["n_routed_experts"],)
        for group, count in (("experts", m["num_experts"]),
                             ("shared_experts", m["n_shared_experts"])):
            spec[p + group + "/gate"] = (count, C, F)
            spec[p + group + "/up"] = (count, C, F)
            spec[p + group + "/down"] = (count, F, C)
    return spec


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (seeds may pass 2**31)."""
    seed = int(seed) % 2**64
    data = np.array([seed >> 32 ^ stream, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data))


def _kind(name: str) -> str:
    last = name.rsplit("/", 1)[-1]
    if last.endswith("scale"):
        return "scale"
    if name == "token_embed":
        return "embed"
    if last == "alpha":
        return "alpha"
    if last in ("b_pre", "b_post", "router_bias"):
        return "small_bias"
    if last == "b_res":
        return "bias"
    return "matrix"


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        z = 1.0 + 0.02 * z
    elif kind == "embed":
        z = 0.02 * z
    elif kind == "alpha":
        z = 0.3 + 0.05 * z
    elif kind == "small_bias":
        z = 0.1 * z
    elif kind == "matrix":  # a matrix (or a stack of them): variance 1 / fan_in
        z = z * shape[-2] ** -0.5
    return z.astype(jnp.bfloat16)


def make_weights(m: dict, seed: int) -> dict:
    """Every leaf from the seed, on the device, in bfloat16, one leaf at a
    time so that no more than one float32 draft of a leaf is alive.
    Matrices are normal with variance 1/fan_in, the embedding normal(0.02),
    RMSNorm scales 1 + normal(0.02); the hyper-connections' ``alpha``
    0.3 + normal(0.05), ``b_res`` normal(1), ``b_pre``, ``b_post`` and the
    router's correction bias normal(0.1)."""
    key = seed_key(seed)
    return {name: _leaf(jax.random.fold_in(key, i), shape, _kind(name))
            for i, (name, shape) in enumerate(sorted(weight_spec(m).items()))}


def _round_to(x, matmul: str):
    x = x.astype(jnp.float32)
    if matmul == "f32":
        return x
    if matmul == "bf16":
        # reduce_precision: a convert pair there and back is folded away
        # by the TPU compiler
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


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x if scale is None else x * scale.astype(jnp.float32)


def _inv_freq(m: dict) -> np.ndarray:
    """YaRN (``rope_scaling``): pair ``i``'s frequency ``theta ** (-2i /
    d)``, divided by ``factor`` for pairs that turn fewer than ``beta_slow``
    times over ``original_max_position_embeddings`` positions, kept for those
    that turn more than ``beta_fast`` times, a linear ramp between."""
    y, d, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]

    def pair(turns):
        return d * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair(y["beta_fast"])), 0)
    high = min(math.ceil(pair(y["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = base ** (-np.arange(0, d, 2) / d)
    return freq / y["factor"] * ramp + freq * (1 - ramp)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, positions, inv_freq, amp):
    """``x`` ``[S, heads, d]``: pairs ``(x[2i], x[2i+1])`` turned by
    ``positions * inv_freq[i]``, times ``amp``."""
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = amp * jnp.cos(angle), amp * jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps", "iters", "hc_eps", "clamp",
                                             "matmul"))
def _coefficients(X, phi_pre, phi_post, phi_res, b_pre, b_post, b_res, alpha,
                  eps, iters, hc_eps, clamp, matmul):
    """``X`` ``[S, n, C]`` -> ``(h_pre [S, n], h_post [S, n], H_res [S, n,
    n])``."""
    S, n, C = X.shape
    xh = _rms(X.reshape(S, n * C), None, eps)
    a = alpha.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(a[0] * _mm("sk,kn->sn", xh, phi_pre, matmul)
                           + b_pre.astype(jnp.float32))
    h_post = 2 * jax.nn.sigmoid(a[1] * _mm("sk,kn->sn", xh, phi_post, matmul)
                                + b_post.astype(jnp.float32))
    h = jnp.clip(a[2] * _mm("sk,kn->sn", xh, phi_res, matmul).reshape(S, n, n)
                 + b_res.astype(jnp.float32), *clamp)
    h = jnp.exp(h)
    for _ in range(iters):
        h = h / (jnp.sum(h, axis=1, keepdims=True) + hc_eps)  # columns
        h = h / (jnp.sum(h, axis=2, keepdims=True) + hc_eps)  # rows
    return h_pre, h_post, h


def _hc(w, p, X, m, matmul):
    return _coefficients(
        X, *(w[p + k] for k in ("phi_pre", "phi_post", "phi_res", "b_pre",
                                "b_post", "b_res", "alpha")),
        eps=float(m["rms_norm_eps"]), iters=int(m["hc_sinkhorn_iters"]),
        hc_eps=float(m["hc_eps"]),
        clamp=(float(m["mhc_h_res_clamp_min"]), float(m["mhc_h_res_clamp_max"])),
        matmul=matmul)


@functools.partial(jax.jit, static_argnames=("matmul",))
def _pre(X, h_pre, matmul):
    return _mm("sn,snc->sc", h_pre, X, matmul)


@functools.partial(jax.jit, static_argnames=("matmul",))
def _post(X, h_res, h_post, f, matmul):
    return _mm("sij,sjc->sic", h_res, X, matmul) + h_post[..., None] * f[:, None]


@functools.partial(jax.jit, static_argnames=("eps", "heads", "nope", "matmul"))
def _project(u, start, ln, q_down, q_norm, q_up, kv_down, kv_norm, inv_freq,
             amp, eps, heads, nope, matmul):
    """One block's ``q_nope``, turned ``q_rope``, ``c_kv`` and turned
    ``k_rope``."""
    S = u.shape[0]
    a = _rms(u, ln, eps)
    q = _mm("sc,cr->sr", _rms(_mm("sc,cq->sq", a, q_down, matmul), q_norm, eps),
            q_up, matmul).reshape(S, heads, -1)
    kv = _mm("sc,cr->sr", a, kv_down, matmul)
    R = kv_norm.shape[0]
    pos = start + jnp.arange(S)
    q_rope = _rope(q[..., nope:], pos, inv_freq, amp)
    k_rope = _rope(kv[:, None, R:], pos, inv_freq, amp)[:, 0]
    return q[..., :nope], q_rope, _rms(kv[:, :R], kv_norm, eps), k_rope


@functools.partial(jax.jit, static_argnames=("heads", "matmul"))
def _expand(c_kv, kv_up, heads, matmul):
    return _mm("lr,re->le", c_kv, kv_up, matmul).reshape(c_kv.shape[0], heads, -1)


@functools.partial(jax.jit, static_argnames=("reach", "nope", "scale",
                                             "matmul"))
def _attend(q_nope, q_rope, start, kv, k_rope, reach, nope, scale, matmul):
    """Queries (the first at ``start``) over the first ``reach`` keys of
    ``kv`` ``[L, H, nope + v]``, ``k_rope`` ``[L, rope]``, causal. Returns
    ``[S, H, v]``."""
    kv, k_rope = kv[:reach], k_rope[:reach]
    scores = (_mm("qhd,khd->hqk", q_nope, kv[..., :nope], matmul)
              + _mm("qhd,kd->hqk", q_rope, k_rope, matmul)) * scale
    i = (start + jnp.arange(q_nope.shape[0]))[:, None]
    j = jnp.arange(kv.shape[0])[None, :]
    scores = jnp.where((j <= i)[None], scores, -1e30)
    return _mm("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:],
               matmul)


@functools.partial(jax.jit, static_argnames=("matmul",))
def _out(o, w_out, matmul):
    return _mm("sd,dc->sc", o.reshape(o.shape[0], -1), w_out, matmul)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(u, scale, eps):
    return _rms(u, scale, eps)


@functools.partial(jax.jit, static_argnames=("matmul",))
def _mlp(n, wg, wu, wd, matmul):
    gate = jax.nn.silu(_mm("sc,cf->sf", n, wg, matmul))
    return _mm("sf,fc->sc", gate * _mm("sc,cf->sf", n, wu, matmul), wd, matmul)


@functools.partial(jax.jit, static_argnames=("top", "scaling", "matmul"))
def _route(n, wr, bias, top, scaling, matmul):
    """``(ids [S, top], weights [S, top])``: the ``top`` largest of
    ``sigmoid(n Wr) + bias``, weighted by the unbiased scores, normalised,
    times ``scaling``."""
    scores = jax.nn.sigmoid(_mm("sc,ce->se", n, wr, matmul))
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling


@functools.partial(jax.jit, static_argnames=("matmul",))
def _expert_add(f, n, rows, weights, wg, wu, wd, e, matmul):
    """``f[rows] += weights * E_e(n[rows])`` (a padded row has weight 0),
    ``E_e`` expert ``e`` of the stacks ``wg``, ``wu``, ``wd``."""
    return f.at[rows].add(weights[:, None] * _mlp(n[rows], wg[e], wu[e], wd[e],
                                                   matmul))


def _ffn(w, p, n, m, matmul):
    """The FFN of one block of tokens ``n`` ``[S, C]``: a dense layer's
    MLP, or the chosen experts held here (each over the tokens that chose
    it, gathered on the host) plus the shared experts."""
    if p + "mlp/gate" in w:
        return _mlp(n, *(w[p + "mlp/" + k][0] for k in ("gate", "up", "down")),
                    matmul)
    ids, weights = _route(n, w[p + "router/kernel"], w[p + "router_bias"],
                          int(m["num_experts_per_tok"]),
                          float(m["routed_scaling_factor"]), matmul)
    ids, weights = np.asarray(ids), np.asarray(weights)
    f = jnp.zeros_like(n)
    for j in range(m["n_shared_experts"]):
        f = f + _mlp(n, *(w[p + "shared_experts/" + k][j]
                          for k in ("gate", "up", "down")), matmul)
    first = m.get("first_expert", 0)
    for e in range(m["num_experts"]):
        rows, slot = np.nonzero(ids == first + e)
        if not len(rows):
            continue
        size = max(8, 1 << (len(rows) - 1).bit_length())  # bounded compiles
        pad = size - len(rows)
        f = _expert_add(
            f, n, jnp.asarray(np.pad(rows, (0, pad)), jnp.int32),
            jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
            *(w[p + "experts/" + k] for k in ("gate", "up", "down")),
            np.int32(e), matmul)
    return f


def hidden_states(w: dict, m: dict, ids, matmul: str = "f32",
                  block: int = BLOCK):
    """``x = sum_i X[i]`` after the last layer, ``[S, C]``, for token ids
    ``[S]``, ``S`` a multiple of ``block``."""
    ids = jnp.asarray(ids, jnp.int32)
    S = ids.shape[0]
    if S % block:
        raise ValueError(f"{S} tokens do not divide into blocks of {block}")
    n, H = m["hc_mult"], m["num_attention_heads"]
    nope = m["qk_nope_head_dim"]
    eps = float(m["rms_norm_eps"])
    y = m["rope_scaling"]
    inv_freq = jnp.asarray(_inv_freq(m), jnp.float32)
    amp = (_mscale(y["factor"], y["mscale"])
           / _mscale(y["factor"], y["mscale_all_dim"]))
    scale = (_mscale(y["factor"], y["mscale_all_dim"]) ** 2
             / math.sqrt(nope + m["qk_rope_head_dim"]))
    X = [jnp.repeat(w["token_embed"][ids[lo:lo + block]].astype(
        jnp.float32)[:, None], n, axis=1) for lo in range(0, S, block)]
    for i in range(m["num_hidden_layers"]):
        p = f"layer_{i}/"
        a = p + "attention/"
        weights = [w[a + k] for k in ("q_down", "q_norm_scale", "q_up",
                                      "kv_down", "kv_norm_scale")]

        def project(b):
            h_pre, h_post, h_res = _hc(w, p + "attention_hc/", X[b], m, matmul)
            u = _pre(X[b], h_pre, matmul)
            return (h_post, h_res) + _project(
                u, b * block, w[p + "attention_norm_scale"], *weights,
                inv_freq, amp, eps=eps, heads=H, nope=nope, matmul=matmul)

        # every block's latent first: a query sees the keys of all before it
        latents = [project(b)[4:] for b in range(len(X))]
        c_kv = jnp.concatenate([c for c, _ in latents])
        k_rope = jnp.concatenate([k for _, k in latents])
        kv = jnp.concatenate([_expand(c_kv[lo:lo + block], w[a + "kv_up"],
                                      heads=H, matmul=matmul)
                              for lo in range(0, S, block)])
        # keys past the sequence: rows no query sees, to whole key steps
        rows = -(-S // KEY_STEP) * KEY_STEP - S
        kv = jnp.pad(kv, ((0, rows), (0, 0), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, rows), (0, 0)))
        for b in range(len(X)):
            h_post, h_res, q_nope, q_rope, _, _ = project(b)
            outs = []
            for lo in range(0, block, QUERY_BLOCK):
                start = b * block + lo
                reach = -(-(start + QUERY_BLOCK) // KEY_STEP) * KEY_STEP
                outs.append(_attend(q_nope[lo:lo + QUERY_BLOCK],
                                    q_rope[lo:lo + QUERY_BLOCK], start, kv,
                                    k_rope, reach=reach, nope=nope,
                                    scale=scale, matmul=matmul))
            f = _out(jnp.concatenate(outs), w[a + "out"], matmul)
            X[b] = _post(X[b], h_res, h_post, f, matmul)
            h_pre, h_post, h_res = _hc(w, p + "ffn_hc/", X[b], m, matmul)
            u = _norm(_pre(X[b], h_pre, matmul), w[p + "ffn_norm_scale"], eps)
            X[b] = _post(X[b], h_res, h_post, _ffn(w, p, u, m, matmul), matmul)
    return jnp.concatenate([jnp.sum(x, axis=1) for x in X])


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _head(x, scale, w_head, eps, matmul):
    return _mm("sc,cv->sv", _rms(x, scale, eps), w_head, matmul)


def forward(w: dict, ids, m: dict, matmul: str = "f32", block: int = BLOCK):
    """Logits ``[S, V]`` in float32 for token ids ``[S]``."""
    x = hidden_states(w, m, ids, matmul, block)
    return _head(x, w["final_norm_scale"], w["head/kernel"],
                 eps=float(m["rms_norm_eps"]), matmul=matmul)


def position_logits(w: dict, m: dict, prompt, served, matmul: str,
                    pad_to: int, block: int = BLOCK):
    """One forward pass over ``prompt + served`` (padded on the right to
    whole blocks, at most ``pad_to`` rounded up to one; causal attention
    makes the padding invisible). Returns the logits ``[len(served), V]``
    at the positions that produced each served token."""
    seq = list(prompt) + list(served)
    padded = -(-len(seq) // block) * block
    if padded > -(-max(pad_to, block) // block) * block:
        raise ValueError(f"{len(seq)} tokens pass the context of {pad_to}")
    ids = np.zeros((padded,), np.int32)
    ids[:len(seq)] = seq
    x = hidden_states(w, m, ids, matmul, block)
    # position i predicts i + 1; a power-of-two count of rows bounds the
    # head's compiles
    rows = 1 << max(len(served) - 1, 0).bit_length()
    at = np.minimum(len(prompt) - 1 + np.arange(rows), len(seq) - 2)
    logits = _head(x[at], w["final_norm_scale"], w["head/kernel"],
                   eps=float(m["rms_norm_eps"]), matmul=matmul)
    return logits[:len(served)]


GAP_POSITIONS = 64  # positions a compared gap is the mean over


def token_gaps(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position (0 where the token IS the reference's best)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref_logits, axis=-1) - got)


def gaps_below_best(ref_logits, tokens) -> np.ndarray:
    """The gaps the harness takes the widest of: :func:`token_gaps` summed
    over each run of ``GAP_POSITIONS`` consecutive served positions, over
    ``GAP_POSITIONS`` (a shorter last run counts the positions it lacks as
    0: a handful of tokens is no sample).

    Why a mean and not each position's own gap: this block's routing is
    discrete, as the ``cohere2_moe`` block's is (its reference says more).
    The 4th and 5th of 64 biased router scores lie within bfloat16's
    rounding of each other in some tokens a layer, and a token whose
    experts differ from the float32 reference's can put another candidate
    first at a single position; a sound run does so rarely, a lower
    precision often, and the mean over 64 positions tells the two apart."""
    gaps = token_gaps(ref_logits, tokens)
    runs = -(-len(gaps) // GAP_POSITIONS)
    padded = np.pad(gaps, (0, runs * GAP_POSITIONS - len(gaps)))
    return padded.reshape(runs, GAP_POSITIONS).sum(axis=1) / GAP_POSITIONS
