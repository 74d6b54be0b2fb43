"""Autoregressive generation with KV caches for the causal-LM family.

Beyond the reference (its predictors are batch-transform only —
``distkeras/predictors.py`` § ``ModelPredictor`` maps a fixed model over
rows); generation is table-stakes for the GPT models this framework adds,
so it is first-class here.

TPU-first shape discipline: everything is static. The KV caches are
``[B, max_seq_len, H, D]`` buffers written through ``dynamic_update_slice``
at a cache index; **prefill** runs the whole prompt in ONE forward (big
MXU matmuls, causal-masked, filling the caches), then the **decode loop**
is a single ``lax.scan`` of per-token steps — one compiled program for any
prompt, no per-step retracing, no growing shapes.

Sampling: greedy, temperature, and top-k (all inside the scan;
``jax.random.categorical`` over masked logits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["generate", "beam_search", "Generator", "accept_prefix_length",
           "cache_with_index", "greedy_accept_length", "greedy_ids"]


def _decode_module(model, slots: bool = False, **overrides):
    """Decode-mode twin of ``model``'s module (same params, KV-cache
    attention). ``slots=True`` selects the per-slot variant that the
    continuous-batching engine (serving/engine.py) steps; ``overrides``
    are replacements in the model's config (the engine's
    ``decode_cache_len`` cap, the ``paged_blocks``/``page_tokens``/
    ``page_table_blocks`` paged-KV geometry and ``tp_mesh`` —
    cache-variable shape knobs only, params stay layout-identical to the
    trained model).

    What a model has to bring (the decode surface; docs/serving.md): a
    ``config`` that is ``causal`` and has ``decode_module(slots,
    **overrides) -> (module, config)``; the twin config states
    ``vocab_size``, ``num_layers``, ``max_seq_len``, ``kv_token_bytes``
    and ``tp_split_sizes``, and the module takes ``positions`` /
    ``block_tables``. No model is known here by its class or name."""
    cfg = getattr(model, "config", None)
    if not callable(getattr(cfg, "decode_module", None)):
        raise ValueError(
            "generate() needs a causal LM whose config has a decode module "
            "(the distkeras_tpu.models.bert zoo: gpt_tiny/gpt_small/...; "
            "distkeras_tpu.models.cohere2_moe: command_a_plus_*; "
            "distkeras_tpu.models.xing4: xing4_*); got "
            f"{getattr(model, 'name', model)!r}"
        )
    if not cfg.causal:
        raise ValueError(
            f"model {model.name!r} is not causal ({type(cfg).__name__}."
            "causal is False); generation requires a decoder LM"
        )
    return cfg.decode_module(slots=slots, **overrides)


def _trained_len(model, dec_cfg) -> int:
    # `or` (not a getattr default): Model allows input_shape=None (e.g.
    # from_keras with no input shape) — falsy values fall back too.
    shape = getattr(model, "input_shape", None) or (dec_cfg.max_seq_len,)
    return shape[0]


def _context_limit(model, dec_cfg) -> int:
    """Decodable context bound: the TRAINED length, not cache capacity —
    positions past what training touched hold randomly-initialized
    positional embeddings. Shared with the serving engine's admission
    validation."""
    return min(dec_cfg.max_seq_len, _trained_len(model, dec_cfg))


def _check_context(model, dec_cfg, prompt, max_new_tokens: int):
    """Shared validation for generate()/beam_search(): bound decoding by
    the TRAINED context length — factory configs can have cache capacity
    (max_seq_len) beyond the seq_len training ever touched, and positions
    past it hold randomly-initialized positional embeddings."""
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be [B, S0]; got {prompt.shape}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    S0 = prompt.shape[1]
    trained_len = _trained_len(model, dec_cfg)
    limit = _context_limit(model, dec_cfg)
    if S0 + max_new_tokens > limit:
        raise ValueError(
            f"prompt ({S0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"{limit} (= min(max_seq_len {dec_cfg.max_seq_len}, trained "
            f"context {trained_len})); positions past the trained context "
            f"have untrained positional embeddings — build the model with a "
            f"larger seq_len to decode further"
        )


def _shard_prompt(mesh, prompt):
    """Batch-parallel decoding: shard the prompt over the mesh's ``dp``
    axis and let GSPMD propagate the sharding through the KV caches and
    the whole decode loop — each dp slice decodes its rows with no
    cross-slice communication. Shared by generate() and beam_search()
    (the beam-flattened ``B*K`` batch inherits the sharding through the
    ``jnp.repeat`` fan-out the same way)."""
    if mesh is None:
        return prompt
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
    if prompt.shape[0] % mesh.shape[axis]:
        raise ValueError(
            f"batch {prompt.shape[0]} not divisible by mesh "
            f"{axis}={mesh.shape[axis]}"
        )
    return jax.device_put(prompt, NamedSharding(mesh, P(axis)))


def _empty_cache(module, batch_size: int):
    """Cache PyTree of zeros, derived via eval_shape (never materializes a
    throwaway set of params)."""
    shapes = jax.eval_shape(
        lambda r: module.init(r, jnp.zeros((batch_size, 1), jnp.int32),
                              train=False),
        jax.random.PRNGKey(0),
    )["cache"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def cache_with_index(cache, index):
    """Return ``cache`` with every 1-D index leaf (the per-row cache and
    positional counters) set to ``index`` — the ONE way offsets move in a
    decode cache. Serving uses it to start a prefill chunk at a non-zero
    offset (after a prefix-cache splice or an earlier chunk) and to rewind
    a right-padded prefill from the padded length back to the true one;
    K/V leaves pass through untouched. ``index`` may be traced (safe
    inside jit)."""
    return jax.tree.map(
        lambda a: jnp.full_like(a, index) if a.ndim == 1 else a, cache)


def greedy_ids(logits):
    """THE greedy token selection, shared by every decode path: argmax
    over the logits quantized to bfloat16 (the model's compute dtype),
    lowest index winning ties.

    Why quantize: the float32 logits are accumulations of bfloat16
    products, so their sub-bf16-ULP structure is reduction-order noise —
    and different (all individually correct) lowerings of the same
    forward REORDER those reductions: a one-token decode step, a
    multi-token prefill/verify window, and a batched row of either can
    disagree by ~1 ULP of f32. On a near-tie that flips the raw argmax,
    which would let a speculative verify window "disagree" with the
    sequential decode it is provably equivalent to over the reals.
    Quantizing to the compute dtype before argmax makes greedy selection
    invariant to sub-bf16 noise, so every lowering picks the same token
    (ties resolve to the lowest id in all of them)."""
    return jnp.argmax(logits.astype(jnp.bfloat16), axis=-1).astype(jnp.int32)


def sample_rows(logits, temps, key, top_k):
    """Per-row sampling over ``[B, V]`` logits: rows with ``temps <= 0``
    take argmax (greedy, at bf16 resolution — :func:`greedy_ids`), the
    rest sample at their own temperature with optional top-k filtering.
    The ONE sampling implementation — shared by :func:`generate` and the
    serving engine's per-slot decode step so the two inference paths
    stay provably token-identical."""
    logits = logits.astype(jnp.float32)
    greedy = greedy_ids(logits)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k is not None:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def accept_prefix_length(match):
    """Length of each row's all-True prefix: ``match`` is bool
    ``[B, K]`` per-position accept verdicts; returns int32 ``[B]`` in
    ``[0, K]`` — acceptance stops at the FIRST False. The speculative-
    decoding commit rule's core: only a *prefix* of the drafts may
    commit, because draft ``j+1`` was generated conditioned on draft
    ``j`` being part of the sequence."""
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)


def greedy_accept_length(drafts, target_greedy):
    """Longest greedy-consistent prefix length per row: the strict
    token-equality form of the speculative accept rule (``drafts`` and
    ``target_greedy`` int32 ``[B, K]``). The serving engine's verify
    uses the ε-relaxed logit-gap form instead (see
    ``engine._spec_accept`` for why exact equality is too brittle
    across lowering widths); this exact form remains the reference
    semantics and the right tool when both sides come from the same
    lowering."""
    return accept_prefix_length(drafts == target_greedy)


@functools.partial(
    jax.jit,
    static_argnames=("module", "max_new_tokens", "top_k", "greedy"),
)
def _generate_jit(module, params, prompt, rng, max_new_tokens, temperature,
                  top_k, greedy):
    B = prompt.shape[0]
    cache = _empty_cache(module, B)

    def sample(logits, key):
        logits = logits.astype(jnp.float32)
        if greedy:
            # Static greedy skips the categorical entirely (no dead
            # sampling branch in the compiled program).
            return greedy_ids(logits)
        temps = jnp.broadcast_to(temperature, logits.shape[:1])
        return sample_rows(logits, temps, key, top_k)

    # Prefill: one big forward over the whole prompt fills every layer's
    # KV cache and yields the first next-token distribution.
    logits, mut = module.apply(
        {"params": params, "cache": cache}, prompt, train=False,
        mutable=["cache"],
    )
    cache = mut["cache"]
    rng, key = jax.random.split(rng)
    tok = sample(logits[:, -1], key)

    def step(carry, _):
        cache, tok, rng = carry
        logits, mut = module.apply(
            {"params": params, "cache": cache}, tok[:, None], train=False,
            mutable=["cache"],
        )
        rng, key = jax.random.split(rng)
        nxt = sample(logits[:, -1], key)
        return (mut["cache"], nxt, rng), nxt

    if max_new_tokens == 1:
        return tok[:, None]
    (_, _, _), rest = jax.lax.scan(
        step, (cache, tok, rng), None, length=max_new_tokens - 1
    )
    return jnp.concatenate([tok[:, None], rest.T], axis=1)


def generate(
    model,
    variables,
    prompt,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    greedy: bool = False,
    seed: int = 0,
    mesh=None,
):
    """Generate ``max_new_tokens`` continuations of ``prompt`` ``[B, S0]``.

    Returns an int32 ``[B, max_new_tokens]`` array of sampled token ids.
    One jitted program per (module, max_new_tokens, top_k, greedy) — reruns
    with different prompts/temperatures/seeds reuse the compilation.

    ``mesh``: batch-parallel decoding — the prompt shards over the mesh's
    ``dp`` axis (``B`` must divide it) and GSPMD propagates the sharding
    through the KV caches and the whole decode loop; each dp slice decodes
    its rows with no cross-slice communication.
    """
    module, dec_cfg = _decode_module(model)
    prompt = jnp.asarray(prompt, jnp.int32)
    _check_context(model, dec_cfg, prompt, max_new_tokens)
    prompt = _shard_prompt(mesh, prompt)
    if top_k is not None and not 1 <= top_k <= dec_cfg.vocab_size:
        raise ValueError(
            f"top_k={top_k} outside [1, vocab_size={dec_cfg.vocab_size}]"
        )
    out = _generate_jit(
        module, variables["params"], prompt, jax.random.PRNGKey(seed),
        max_new_tokens, jnp.float32(temperature), top_k, greedy,
    )
    return np.asarray(out)


@functools.partial(
    jax.jit, static_argnames=("module", "max_new_tokens", "num_beams")
)
def _beam_jit(module, params, prompt, max_new_tokens, num_beams):
    from jax import lax

    K = num_beams
    B = prompt.shape[0]
    N = max_new_tokens

    def apply(cache, tokens):
        logits, mut = module.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            mutable=["cache"],
        )
        return jax.nn.log_softmax(logits[:, -1].astype(jnp.float32)), mut["cache"]

    # Prefill on the un-replicated batch, then fan each item out to K beams
    # (cache leaves with a batch dim repeat; per-layer index scalars are
    # beam-invariant and stay shared).
    logp, cache = apply(_empty_cache(module, B), prompt)  # [B, V]
    V = logp.shape[-1]
    scores, toks = lax.top_k(logp, K)  # [B, K]
    rep = jnp.repeat(jnp.arange(B), K)
    cache = jax.tree.map(lambda c: c[rep] if c.ndim > 0 else c, cache)
    hist = jnp.zeros((B, K, N), jnp.int32).at[:, :, 0].set(toks)

    def step(carry, i):
        cache, tok, scores, hist = carry
        logp, cache = apply(cache, tok.reshape(B * K, 1))  # [B*K, V]
        cand = scores[:, :, None] + logp.reshape(B, K, V)
        new_scores, idx = lax.top_k(cand.reshape(B, K * V), K)  # [B, K]
        parent = idx // V
        new_tok = (idx % V).astype(jnp.int32)
        gather = (jnp.arange(B)[:, None] * K + parent).reshape(-1)  # [B*K]
        cache = jax.tree.map(lambda c: c[gather] if c.ndim > 0 else c, cache)
        hist = jnp.take_along_axis(hist, parent[:, :, None], axis=1)
        hist = hist.at[:, :, i].set(new_tok)
        return (cache, new_tok, new_scores, hist), None

    if N > 1:
        (cache, _, scores, hist), _ = lax.scan(
            step, (cache, toks, scores, hist), jnp.arange(1, N)
        )
    return hist, scores


def beam_search(
    model,
    variables,
    prompt,
    max_new_tokens: int,
    num_beams: int = 4,
    mesh=None,
):
    """Fixed-length beam search: decode ``max_new_tokens`` keeping the
    ``num_beams`` highest-total-log-probability continuations per batch
    item. Each beam carries its own KV cache; beam reordering gathers the
    caches along the (flattened) beam axis inside one ``lax.scan``.

    Returns ``(sequences, scores)``: ``[B, num_beams, max_new_tokens]``
    int32 tokens sorted by score, and ``[B, num_beams]`` float32 total
    log-probabilities. ``sequences[:, 0]`` is the best beam. No EOS
    handling (the model zoo has no reserved EOS semantics) — decode is
    fixed-length.

    ``mesh``: dp batch-parallel decoding, same contract as
    :func:`generate` (``B`` must divide the dp axis; per-item beams stay
    with their dp slice, so beam reordering is slice-local).
    """
    module, dec_cfg = _decode_module(model)
    prompt = jnp.asarray(prompt, jnp.int32)
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    _check_context(model, dec_cfg, prompt, max_new_tokens)
    prompt = _shard_prompt(mesh, prompt)
    seqs, scores = _beam_jit(
        module, variables["params"], prompt, max_new_tokens, num_beams
    )
    return np.asarray(seqs), np.asarray(scores)


class Generator:
    """Stateful convenience wrapper around :func:`generate` holding the
    model + trained variables (mirrors the Predictor surface)."""

    def __init__(self, model, variables):
        self.model = model
        self.variables = variables

    def __call__(self, prompt, max_new_tokens: int, **kw):
        return generate(self.model, self.variables, prompt, max_new_tokens,
                        **kw)

    def beam(self, prompt, max_new_tokens: int, num_beams: int = 4):
        return beam_search(self.model, self.variables, prompt,
                           max_new_tokens, num_beams=num_beams)
