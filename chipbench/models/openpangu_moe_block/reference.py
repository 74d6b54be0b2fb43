"""The plain reference of the ``pangu_ultra_moe`` block (openPangu-Ultra-MoE-
718B), given one chip's share of an expert-parallel stage: straightforward
``jax.numpy`` in float32 at ``highest``, no kernels, no batching; the
sequence goes through in blocks of tokens, and each block's attention in
groups of heads, only so that a 35 k-token request fits beside the weights.
Two things only save time, and change no number: ``position_logits`` does
not run again the blocks a request shares with one run before (the same
32,768-token document: their latent rows are kept, ``_hidden_rows``), nor
the last layer's blocks before the first position asked for; and a product
with a bfloat16 weight sums the three products ``highest`` makes that are
not zero (``_mmw``).

It imports nothing of the program and takes nothing the program has made:
its weights come from ``make_weights`` below, from the seed, and the harness
hands THE SAME arrays to the program through the program's own init hook.
They are held in bfloat16, the type the configuration states, and are
raised to float32 at each product; every other value is float32.

The equations, each from the published ``config.json`` (per token; ``x``
the residual stream of ``hidden_size`` ``C``):

- ends: ``x_0`` the embedding; after the last layer ``RMSNorm_f``, the
  untied head;
- sandwich norm (``sandwich_norm``): four RMSNorms a layer, eps
  ``rms_norm_eps``: ``a = RMSNorm_in(x)``, ``x <- x + RMSNorm_post_attn(
  MLA(a))``, ``m = RMSNorm_pre_mlp(x)``, ``x <- x + RMSNorm_post_mlp(
  FFN(m))``;
- attention (MLA, the EXPANDED form): ``c_q = RMSNorm(a W_DQ)``, ``[q_nope |
  q_rope]_h = c_q W_UQ``; ``[c_kv | k_rope] = a W_DKV``, ``c_kv <-
  RMSNorm(c_kv)``; RoPE at ``rope_theta`` on ``q_rope`` and the one shared
  ``k_rope``; ``[k_nope | v]_h = c_kv W_UKV``; ``score = (q_nope . k_nope +
  q_rope . k_rope) / sqrt(nope + rope)``; causal softmax; ``y =
  concat_h(o_h) W_O``;
- FFN: the first ``first_k_dense_replace`` layers a gated SiLU MLP of width
  ``intermediate_size``; then ``s = sigmoid(m W_r)`` over ALL
  ``num_router_outputs`` (the published ``n_routed_experts``); the
  ``num_experts_per_tok`` largest of ``s`` are chosen (no correction bias)
  and weighted ``s_k / sum s_k * routed_scaling_factor`` (``norm_topk_prob``);
  ``FFN = sum_k w_k E_k + sum E_shared``; every expert a gated SiLU of width
  ``moe_intermediate_size``.

ASSUMED (the configuration's file lists the same under ``assumed``): the
router scores by sigmoid (the config names no ``scoring_func``); the
rotary turns pairs ``(x[2i], x[2i+1])``; ``routed_scaling_factor``
multiplies the routed sum only; the post-norms' scales are drawn like the
other norms' (``make_weights``).

THE SHARE: all heads are held (attention is data-parallel: each chip
attends for its own rows); ``num_experts`` routed experts from
``first_expert`` are held here, the router keeps ``num_router_outputs``;
the vocabulary is the slice ``vocab_size``. What the absent experts would
add is left out.

``matmul`` picks the precision of every matrix product's operands:
``"f32"`` (the reference proper), ``"bf16"`` (what the configuration
states) and ``"fp8"`` (per-tensor scaled float8_e4m3: the control, the step
below bfloat16).
"""

from __future__ import annotations

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 2048        # tokens a block; a test passes a smaller one
QUERY_BLOCK = 128   # queries a step of attention
KEY_STEP = 4096     # keys are cut to the block's causal reach, in these
HEAD_GROUP = 16     # heads whose keys and values are built at a time
NORMS = ("attention_norm_scale", "post_attention_norm_scale",
         "ffn_norm_scale", "post_ffn_norm_scale")


def weight_spec(m: dict) -> dict:
    """Leaf name -> shape, for a model given as sizes (a configuration
    file's ``model.config``)."""
    C, V, H = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    R, qr = m["kv_lora_rank"], m["q_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    F = m["moe_intermediate_size"]
    spec = {"token_embed": (V, C), "head/kernel": (C, V),
            "final_norm_scale": (C,)}
    for i in range(m["num_hidden_layers"]):
        p = f"layer_{i}/"
        spec.update({p + norm: (C,) for norm in NORMS})
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
        spec[p + "router/kernel"] = (C, m["num_router_outputs"])
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
    if name.endswith("scale"):
        return "scale"
    return "embed" if name == "token_embed" else "matrix"


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
    """Every leaf from the seed, on the device, in bfloat16, one leaf at a
    time so that no more than one float32 draft of a leaf is alive.
    Matrices are normal with variance 1/fan_in, the embedding normal(0.02),
    every RMSNorm's scale (the post-norms' too) 1 + normal(0.02)."""
    key = seed_key(seed)
    leaves = sorted(weight_spec(m).items())
    compile_ahead([(_leaf, (key, shape, _kind(name)), {})
                   for name, shape in leaves])
    return {name: _leaf(jax.random.fold_in(key, i), shape, _kind(name))
            for i, (name, shape) in enumerate(leaves)}


_AHEAD: set = set()  # (function, arguments' shapes, statics) compiled


def compile_ahead(calls, threads: int | None = None) -> None:
    """Compile each ``(fn, args, statics)`` of ``calls`` (``fn`` jitted)
    not compiled before, side by side on ``threads`` threads (one each by
    default), before the calls come one after another; each is then found
    compiled, where ``args`` and ``statics`` are passed as the calls pass
    them (arrays or their shapes), in this process or, through the
    persistent compile cache, in the next. Only time: a cold start
    compiles ~20 leaves' programs here, and ~25 programs of a request,
    2-8 s each on a v5e's host. A program that fails to compile here is
    compiled when called."""
    todo = {}
    for fn, args, statics in calls:
        key = (fn, tuple((tuple(a.shape), str(a.dtype)) if hasattr(a, "shape")
                         else a for a in args), tuple(sorted(statics.items())))
        if key not in _AHEAD:
            todo[key] = (fn, args, statics)

    def compile_one(call):
        fn, args, statics = call
        try:
            fn.lower(*args, **statics).compile()
        except Exception:  # noqa: BLE001 (compiled when called)
            pass

    if todo:
        with concurrent.futures.ThreadPoolExecutor(
                threads or len(todo)) as pool:
            list(pool.map(compile_one, todo.values()))
        _AHEAD.update(todo)


def compile_requests_ahead(w: dict, m: dict, context: int,
                           threads: int | None = None) -> None:
    """``compile_ahead`` for every program ``position_logits`` calls in
    float32 on requests of up to ``context`` positions: each block's, the
    attention at every key step, an expert at up to 512 of a block's
    tokens, the head at 32-512 served positions. For a process that holds
    the chip before the reference's (the server's, which makes these
    weights too), so that the reference finds them in the compile cache."""
    inv_freq = jnp.asarray(_inv_freq(m), jnp.float32)
    keys = -(-max(context, BLOCK) // KEY_STEP) * KEY_STEP
    head = [(_head, (jax.ShapeDtypeStruct((rows, m["hidden_size"]),
                                          jnp.float32),
                     w["final_norm_scale"], w["head/kernel"]),
             {"eps": float(m["rms_norm_eps"]), "matmul": "f32"})
            for rows in (32, 64, 128, 256, 512)]
    compile_ahead(_block_calls(w, m, "f32", BLOCK, keys, inv_freq, range(
        KEY_STEP, keys + 1, KEY_STEP)) + head, threads)


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


def _mmw(spec: str, a, w, matmul: str, w_first: bool = False):
    """``_mm`` with ``w`` a weight as held, in bfloat16 (the second operand
    of ``spec``, or the first with ``w_first``): the same sum in fewer
    products. ``HIGHEST`` splits each float32 operand into three bfloat16
    parts and sums the products of the parts; a bfloat16 ``w`` is its own
    first part and its other two are zero, so the three products of
    ``a``'s parts with ``w`` itself, each exact and summed in float32, are
    all of it (``"bf16"``: ``a`` rounded is one part). ``"fp8"`` rescales
    ``w`` off bfloat16 and takes ``_mm``."""
    def product(x, y, mm, **kw):
        return mm(spec, y, x, **kw) if w_first else mm(spec, x, y, **kw)

    if matmul == "fp8" or w.dtype != jnp.bfloat16:
        return product(a, w, lambda *xs, **_: _mm(*xs, matmul))
    a = _round_to(a, matmul)
    out = 0.0
    for _ in range(3 if matmul == "f32" else 1):
        part = a.astype(jnp.bfloat16)
        out = out + product(part, w, jnp.einsum,
                            preferred_element_type=jnp.float32)
        a = a - part.astype(jnp.float32)
    return out


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _inv_freq(m: dict) -> np.ndarray:
    """Plain RoPE: pair ``i``'s frequency ``theta ** (-2i / d)``."""
    d = m["qk_rope_head_dim"]
    return float(m["rope_theta"]) ** (-np.arange(0, d, 2) / d)


def _rope(x, positions, inv_freq):
    """``x`` ``[S, heads, d]``: pairs ``(x[2i], x[2i+1])`` turned by
    ``positions * inv_freq[i]``."""
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _latent(x, start, ln, kv_down, kv_norm, inv_freq, eps, matmul):
    """One block's ``c_kv`` and turned ``k_rope``."""
    kv = _mmw("sc,cr->sr", _rms(x, ln, eps), kv_down, matmul)
    R = kv_norm.shape[0]
    pos = start + jnp.arange(x.shape[0])
    return (_rms(kv[:, :R], kv_norm, eps),
            _rope(kv[:, None, R:], pos, inv_freq)[:, 0])


@functools.partial(jax.jit, static_argnames=("eps", "heads", "nope",
                                             "matmul"))
def _queries(x, start, ln, q_down, q_norm, q_up, inv_freq, eps, heads, nope,
             matmul):
    """One block's ``q_nope`` and turned ``q_rope``."""
    q = _mmw("sc,cr->sr", _rms(_mmw("sc,cq->sq", _rms(x, ln, eps), q_down,
                                    matmul), q_norm, eps),
             q_up, matmul).reshape(x.shape[0], heads, -1)
    pos = start + jnp.arange(x.shape[0])
    return q[..., :nope], _rope(q[..., nope:], pos, inv_freq)


@functools.partial(jax.jit, static_argnames=("top", "group", "nope", "scale",
                                             "matmul"))
def _attend(q_nope, q_rope, start, c_kv, kv_up, k_rope, h, top, group, nope,
            scale, matmul):
    """A block's queries (the first at ``start``; ``q_nope``, ``q_rope``
    ``[S, H, ...]``) for the ``group`` heads from ``h``, over the first
    ``top`` keys of ``c_kv`` ``[L, R]``, ``k_rope`` ``[L, rope]`` (those
    that reach past the block's last query), causal: the group's keys and
    values ``[k_nope | v]_h = c_kv W_UKV`` (``kv_up`` ``[R, H, nope + v]``)
    built once, then ``QUERY_BLOCK`` queries at a time. Returns ``[S,
    group, v]``. The keys and values are built apart, before the loop over
    the queries (sliced from one array, each step of the loop copied
    them), with their positions last, ``[group, nope or v, top]``, the
    layout the products read them in (built ``[top, group, ...]``, the
    chip's compiler moved them into that layout once a call)."""
    def heads(x, axis):
        return jax.lax.dynamic_slice_in_dim(x, h, group, axis)

    q_nope, q_rope = heads(q_nope, 1), heads(q_rope, 1)
    kv_up = heads(kv_up, 1)
    k_nope, v = (_mmw("rhd,lr->hdl", c_kv[:top], w, matmul, w_first=True)
                 for w in (kv_up[..., :nope], kv_up[..., nope:]))
    k_rope = k_rope[:top].T  # [rope, top]
    S, G = q_nope.shape[:2]
    step = min(QUERY_BLOCK, S)
    j = jnp.arange(top)[None, :]

    def queries(args):
        qn, qr, lo = args
        scores = (_mm("qhd,hdk->hqk", qn, k_nope, matmul)
                  + _mm("qhd,dk->hqk", qr, k_rope, matmul)) * scale
        i = (start + lo + jnp.arange(step))[:, None]
        scores = jnp.where((j <= i)[None], scores, -1e30)
        # the softmax, its division after the sum over the keys
        e = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        return (_mm("hqk,hdk->qhd", e, v, matmul)
                / jnp.sum(e, axis=-1).T[..., None])

    out = jax.lax.map(queries, (q_nope.reshape(S // step, step, G, -1),
                                q_rope.reshape(S // step, step, G, -1),
                                jnp.arange(0, S, step)))
    return out.reshape(S, G, -1)


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _attention_out(x, o, w_out, post, eps, matmul):
    """``x + RMSNorm_post_attn(concat_h(o_h) W_O)``."""
    return x + _rms(_mmw("sd,dc->sc", o.reshape(o.shape[0], -1), w_out,
                         matmul), post, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, eps):
    return _rms(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _add_normed(x, f, scale, eps):
    return x + _rms(f, scale, eps)


@functools.partial(jax.jit, static_argnames=("matmul",))
def _mlp(n, wg, wu, wd, matmul):
    gate = jax.nn.silu(_mmw("sc,cf->sf", n, wg, matmul))
    return _mmw("sf,fc->sc", gate * _mmw("sc,cf->sf", n, wu, matmul), wd,
                matmul)


@functools.partial(jax.jit, static_argnames=("top", "scaling", "matmul"))
def _route(n, wr, top, scaling, matmul):
    """``(ids [S, top], weights [S, top])``: the ``top`` largest of
    ``sigmoid(n Wr)``, normalised, times ``scaling``."""
    scores = jax.nn.sigmoid(_mmw("sc,ce->se", n, wr, matmul))
    chosen, ids = jax.lax.top_k(scores, top)
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
    ids, weights = _route(n, w[p + "router/kernel"],
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


# The latent rows of sequences already run, kept on the host for
# ``position_logits``: each a layer's ``c_kv`` and ``k_rope`` of every
# whole block. A block's rows are a function of the tokens up to its end
# alone (causal), so a later sequence that starts with the same whole
# blocks (a request on a document already run) takes them as they are and
# runs its own blocks only. Valid for one set of weights, one precision
# and one block size at a time.
_SEEN: dict = {"key": None, "runs": []}
SEEN_RUNS = 8  # sequences kept (~425 MB each at 36,864 tokens)


def _seen_runs(w: dict, matmul: str, block: int) -> list:
    key = (w, matmul, block)
    held = _SEEN["key"]
    if held is None or held[0] is not w or held[1:] != key[1:]:
        _SEEN["key"], _SEEN["runs"] = key, []
    return _SEEN["runs"]


def _shared_blocks(a: np.ndarray, b: np.ndarray, block: int) -> int:
    """Whole blocks that ``a`` and ``b`` begin with alike."""
    n = min(len(a), len(b)) // block * block
    differ = np.flatnonzero(a[:n] != b[:n])
    return (int(differ[0]) if len(differ) else n) // block


def _block_calls(w: dict, m: dict, matmul: str, block: int, keys: int,
                 inv_freq, tops) -> list:
    """``(fn, args, statics)`` of every program the layer loop calls on a
    block, entered as the loop enters them (a layer's weights stand for
    every layer's), for ``compile_ahead``: the attention at each of
    ``tops``, an expert at 32-512 of the block's tokens (a count outside
    is compiled when it comes)."""
    sds = jax.ShapeDtypeStruct
    C, H, R = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    eps = {"eps": float(m["rms_norm_eps"])}
    mm = {"matmul": matmul}
    x = sds((block, C), jnp.float32)
    p = "layer_0/"
    a = p + "attention/"
    calls = [
        (_latent, (x, 0, w[p + "attention_norm_scale"], w[a + "kv_down"],
                   w[a + "kv_norm_scale"], inv_freq), {**eps, **mm}),
        (_queries, (x, 0, w[p + "attention_norm_scale"], w[a + "q_down"],
                    w[a + "q_norm_scale"], w[a + "q_up"], inv_freq),
         {**eps, "heads": H, "nope": nope, **mm}),
        (_attention_out, (x, sds((block, H, dv), jnp.float32), w[a + "out"],
                          w[p + "post_attention_norm_scale"]), {**eps, **mm}),
        (_norm, (x, w[p + "ffn_norm_scale"]), eps),
        (_add_normed, (x, x, w[p + "post_ffn_norm_scale"]), eps)]
    calls += [(_attend, (
        sds((block, H, nope), jnp.float32), sds((block, H, rope), jnp.float32),
        0, sds((keys, R), jnp.float32), sds((R, H, nope + dv), jnp.bfloat16),
        sds((keys, rope), jnp.float32), np.int32(0)), {
            "top": top, "group": min(HEAD_GROUP, H), "nope": nope,
            "scale": 1.0 / math.sqrt(nope + rope), **mm}) for top in sorted(tops)]

    def mlp(width):
        return (_mlp, (x, sds((C, width), jnp.bfloat16),
                       sds((C, width), jnp.bfloat16),
                       sds((width, C), jnp.bfloat16), matmul), {})

    if m["first_k_dense_replace"]:
        calls.append(mlp(m["intermediate_size"]))
    if m["first_k_dense_replace"] < m["num_hidden_layers"]:
        p = f"layer_{m['first_k_dense_replace']}/"
        calls += [mlp(m["moe_intermediate_size"]),
                  (_route, (x, w[p + "router/kernel"],
                            int(m["num_experts_per_tok"]),
                            float(m["routed_scaling_factor"]), matmul), {})]
        size = 32
        while size <= min(512, block):
            calls.append((_expert_add, (
                x, x, sds((size,), jnp.int32), sds((size,), jnp.float32),
                *(w[p + "experts/" + k] for k in ("gate", "up", "down")),
                np.int32(0), matmul), {}))
            size *= 2
    return calls


def _top(b: int, block: int) -> int:
    """The keys block ``b`` reads: past its last query, to whole key
    steps."""
    return -(-(b + 1) * block // KEY_STEP) * KEY_STEP


def _hidden_rows(w: dict, m: dict, ids, matmul: str, block: int,
                 rows_from: int, reuse: bool, context: int = 0,
                 ahead: tuple = ()):
    """``(first, x)``: ``x`` after the last layer at rows ``first`` to
    ``S`` (``first`` the start of the block that holds ``rows_from``, or
    earlier). A block before the first's runs through every layer but the
    last only for the latent rows the later blocks read. With ``reuse``
    the latent rows of blocks shared with a sequence run before are taken
    from it (``_SEEN``), and this sequence's are kept. The keys are
    padded to ``context`` (or ``S``), so that sequences of any length read
    keys of one shape; ``ahead``: more calls for ``compile_ahead``."""
    ids = np.asarray(ids, np.int32)
    S = ids.shape[0]
    if S % block:
        raise ValueError(f"{S} tokens do not divide into blocks of {block}")
    n = S // block
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    nope, dv = m["qk_nope_head_dim"], m["v_head_dim"]
    eps = float(m["rms_norm_eps"])
    inv_freq = jnp.asarray(_inv_freq(m), jnp.float32)
    scale = 1.0 / math.sqrt(nope + m["qk_rope_head_dim"])
    group = min(HEAD_GROUP, H)
    first = min(rows_from // block, n - 1)
    runs = _seen_runs(w, matmul, block) if reuse else []
    done, held = 0, None
    for run_ids, latents in runs:
        k = min(_shared_blocks(ids, run_ids, block), first)
        if k > done:
            done, held = k, latents
    kept = []
    x = {b: w["token_embed"][jnp.asarray(ids[b * block:(b + 1) * block])]
         .astype(jnp.float32) for b in range(done, n)}
    keys = -(-max(S, context) // KEY_STEP) * KEY_STEP
    compile_ahead(_block_calls(w, m, matmul, block, keys, inv_freq, {
        _top(b, block) for b in range(done, n)}) + list(ahead))
    layers = m["num_hidden_layers"]
    for i in range(layers):
        p = f"layer_{i}/"
        a = p + "attention/"
        ln = w[p + "attention_norm_scale"]
        # every block's latent first: a query sees the keys of all before it
        latents = [_latent(x[b], b * block, ln, w[a + "kv_down"],
                           w[a + "kv_norm_scale"], inv_freq, eps=eps,
                           matmul=matmul) for b in range(done, n)]
        c_kv = jnp.concatenate([c for c, _ in latents])
        k_rope = jnp.concatenate([k for _, k in latents])
        if done:
            c_kv = jnp.concatenate([jnp.asarray(held[i][0][:done * block]),
                                    c_kv])
            k_rope = jnp.concatenate([jnp.asarray(held[i][1][:done * block]),
                                      k_rope])
        if reuse:
            kept.append((np.asarray(c_kv), np.asarray(k_rope)))
        # keys past the sequence: rows no query sees, to whole key steps
        c_kv = jnp.pad(c_kv, ((0, keys - S), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, keys - S), (0, 0)))
        kv_up = w[a + "kv_up"].reshape(R, H, nope + dv)
        # the last layer's output is read at the rows asked for only
        for b in range(first if i == layers - 1 else done, n):
            q_nope, q_rope = _queries(
                x[b], b * block, ln, w[a + "q_down"], w[a + "q_norm_scale"],
                w[a + "q_up"], inv_freq, eps=eps, heads=H, nope=nope,
                matmul=matmul)
            heads = [_attend(q_nope, q_rope, b * block, c_kv, kv_up, k_rope,
                             np.int32(h), top=_top(b, block), group=group,
                             nope=nope, scale=scale, matmul=matmul)
                     for h in range(0, H, group)]
            x[b] = _attention_out(x[b], jnp.concatenate(heads, axis=1),
                                  w[a + "out"], w[p + "post_attention_norm_scale"],
                                  eps=eps, matmul=matmul)
            f = _ffn(w, p, _norm(x[b], w[p + "ffn_norm_scale"], eps=eps), m,
                     matmul)
            x[b] = _add_normed(x[b], f, w[p + "post_ffn_norm_scale"], eps=eps)
    if reuse:
        runs.append((ids, kept))
        del runs[:-SEEN_RUNS]
    return first * block, jnp.concatenate([x[b] for b in range(first, n)])


def hidden_states(w: dict, m: dict, ids, matmul: str = "f32",
                  block: int = BLOCK):
    """``x`` after the last layer, ``[S, C]``, for token ids ``[S]``,
    ``S`` a multiple of ``block``."""
    return _hidden_rows(w, m, ids, matmul, block, 0, reuse=False)[1]


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _head(x, scale, w_head, eps, matmul):
    return _mmw("sc,cv->sv", _rms(x, scale, eps), w_head, matmul)


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
    at the positions that produced each served token. The blocks that a
    sequence run before began with alike (a document asked about again)
    are not run again: their latent rows are taken as that run left them,
    the same numbers (``_hidden_rows``)."""
    seq = list(prompt) + list(served)
    padded = -(-len(seq) // block) * block
    if padded > -(-max(pad_to, block) // block) * block:
        raise ValueError(f"{len(seq)} tokens pass the context of {pad_to}")
    ids = np.zeros((padded,), np.int32)
    ids[:len(seq)] = seq
    # position i predicts i + 1; a power-of-two count of rows bounds the
    # head's compiles
    rows = 1 << max(len(served) - 1, 0).bit_length()
    eps = float(m["rms_norm_eps"])
    head = (_head, (jax.ShapeDtypeStruct((rows, m["hidden_size"]),
                                         jnp.float32),
                    w["final_norm_scale"], w["head/kernel"]),
            {"eps": eps, "matmul": matmul})
    first, x = _hidden_rows(w, m, ids, matmul, block, len(prompt) - 1,
                            reuse=True, context=pad_to, ahead=(head,))
    at = np.minimum(len(prompt) - 1 + np.arange(rows), len(seq) - 2)
    logits = _head(x[at - first], w["final_norm_scale"], w["head/kernel"],
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
    discrete, as the other expert blocks' is. The 8th and 9th of 256
    router scores lie within bfloat16's rounding of each other in some
    tokens a layer, and a token whose experts differ from the float32
    reference's can put another candidate first at a single position; a
    sound run does so rarely, a lower precision often, and the mean over
    64 positions tells the two apart."""
    gaps = token_gaps(ref_logits, tokens)
    runs = -(-len(gaps) // GAP_POSITIONS)
    padded = np.pad(gaps, (0, runs * GAP_POSITIONS - len(gaps)))
    return padded.reshape(runs, GAP_POSITIONS).sum(axis=1) / GAP_POSITIONS
