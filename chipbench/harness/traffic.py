"""The one general traffic generator: a traffic file's parameters in, a
deterministic schedule out. Imports no JAX.

A traffic file (``chipbench/traffic/<name>.json``) names its ``kind``:

- ``train``  — rows of token ids for a trainer (``token_rows``);
- ``closed`` — a fixed number of clients, each sending its next request
  when the last one is done (``request_schedule``, no due times);
- ``open``   — requests due at Poisson arrival times at a fixed rate
  (``request_schedule``, with ``due_s``).

Every ``--seed`` gets the SAME multiset of lengths and the same arrival
times (drawn from the file's ``shape_seed``), in another order and with
other token ids: a seed must not change the amount of work.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

SEED_MOD = 2**63  # numpy takes any non-negative int; the driver's are > 2**31


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: tuple
    max_new_tokens: int
    due_s: float | None  # open loop: seconds after the window's start
    shared_prefix: int  # tokens of the prompt that other requests share


def _rng(*parts: int) -> np.random.Generator:
    return np.random.default_rng([int(p) % SEED_MOD for p in parts])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}`` or ``{"dist": "fixed", "value"}``, clipped to [min, max]."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(rate_per_s: float, horizon_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Poisson arrivals at ``rate_per_s`` over ``[0, horizon_s)``."""
    n = int(rate_per_s * horizon_s * 1.5) + 16
    t = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
    return t[t < horizon_s]


def request_schedule(traffic: dict, vocab: int, seed: int,
                     seconds: float) -> list[Request]:
    """The requests of one run, in the order they are sent.

    ``closed``: ``traffic["requests"]`` of them, a pool the clients draw
    from in order (more than a window can finish). ``open``: one per
    arrival in ``[0, seconds)``, where the caller counts the ramp before
    the window into ``seconds``."""
    shape = _rng(traffic["shape_seed"])
    order = _rng(seed, 1)
    tokens = _rng(seed, 2)
    if traffic["kind"] == "open":
        due = arrival_times(traffic["rate_per_s"], seconds, shape)
        n = len(due)
    elif traffic["kind"] == "closed":
        due, n = None, int(traffic["requests"])
    else:
        raise ValueError(f"no request schedule for kind {traffic['kind']!r}")
    prompt_len = draw_lengths(traffic["prompt_len"], n, shape)
    output_len = draw_lengths(traffic["output_len"], n, shape)
    shared = traffic.get("shared_prefix")
    which = np.full(n, -1)
    prefixes = []
    if shared:
        # Zipf popularity over a few system prompts: p(k) ~ 1 / (k+1)^a.
        p = 1.0 / np.arange(1, shared["count"] + 1) ** shared["zipf_exponent"]
        which = shape.choice(shared["count"], size=n, p=p / p.sum())
        prefixes = [tokens.integers(1, vocab, size=shared["tokens"])
                    for _ in range(shared["count"])]
    # The seed permutes which arrival gets which (lengths, prefix) triple.
    # A closed loop keeps the file's order: there the order decides how many
    # requests end inside the window, which is work (each admission stalls
    # every row for a prefill), and a seed must not change the work.
    perm = order.permutation(n) if due is not None else np.arange(n)
    out = []
    for i in range(n):
        j = perm[i]
        body = tokens.integers(1, vocab, size=int(prompt_len[j]))
        head = prefixes[which[j]] if which[j] >= 0 else body[:0]
        out.append(Request(
            index=i, prompt=tuple(int(t) for t in np.concatenate([head, body])),
            max_new_tokens=int(output_len[j]),
            due_s=None if due is None else float(due[i]),
            shared_prefix=len(head)))
    return out


def _buckets(lo: int, hi: int, min_bucket: int) -> list[int]:
    """The power-of-two pad lengths that lengths in [lo, hi] fall into."""
    size = min_bucket
    while size < lo:
        size *= 2
    out = [size]
    while size < hi:
        size *= 2
        out.append(size)
    return out


def warmup_prompts(traffic: dict, vocab: int, seed: int,
                   min_bucket: int = 16) -> list[tuple]:
    """Prompts that, sent one after another, compile every prefill shape
    the traffic's lengths can reach and no others: one cold prompt per
    power-of-two bucket of the whole prompt and, with shared prefixes, one
    per bucket of the tail that is prefilled after a prefix hit."""
    rng = _rng(seed, 3)
    spec = traffic["prompt_len"]
    lo, hi = ((spec["value"],) * 2 if spec["dist"] == "fixed"
              else (spec["min"], spec["max"]))
    shared = traffic.get("shared_prefix")
    head = shared["tokens"] if shared else 0

    def random_tokens(n):
        return tuple(int(t) for t in rng.integers(1, vocab, size=n))

    out = [random_tokens(min(b, head + hi))
           for b in _buckets(head + lo, head + hi, min_bucket)]
    if head:
        prefix = random_tokens(head)
        out.append(prefix + random_tokens(lo))  # cold: caches the prefix
        out += [prefix + random_tokens(min(b, hi))
                for b in _buckets(lo, hi, min_bucket)]
    return out


def token_rows(data: dict, rows: int, seq: int, vocab: int, seed: int):
    """Seed-made MLM rows (chip_smoke.token_dataset's recipe, copied):
    tokens Zipfian over ids 1..vocab-1, ``mask_rate`` of them replaced by
    ``mask_id`` in the features; the labels are the uncorrupted tokens.
    Returns int32 ``(features, labels)``, each ``[rows, seq]``."""
    if data["recipe"] != "zipf_mlm":
        raise ValueError(f"unknown data recipe {data['recipe']!r}")
    rng = _rng(seed, 4)
    p = 1.0 / np.arange(1, vocab)
    tokens = rng.choice(np.arange(1, vocab), size=(rows, seq), p=p / p.sum())
    corrupt = rng.random((rows, seq)) < data["mask_rate"]
    features = np.where(corrupt, data["mask_id"], tokens)
    return features.astype(np.int32), tokens.astype(np.int32)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values (no interpolation: a tail is
    a request that happened)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return float(xs[k])
