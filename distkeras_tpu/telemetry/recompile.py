"""Recompile auditor: make "compiled once" a runtime invariant.

The performance model of every hot path in this repo rests on compile
counts: the serving engine promises ONE decode executable for the
server's lifetime, prefill is bounded at one program per power-of-two
bucket, trainers compile one step per distinct batch geometry. A silent
retrace (a drifted dtype, a weak-type promotion, a shape that slipped
through bucketing) turns a microseconds dispatch into a seconds-long
compile — "it got slower and nobody noticed" until tail latency pages
someone.

:class:`RecompileAuditor` makes the check part of the runtime:

- :meth:`RecompileAuditor.wrap` wraps a jitted callable; each compile is
  detected (via the jit cache-size probe when available, else by tracking
  distinct abstract input signatures) and recorded with the triggering
  abstract shapes;
- :meth:`RecompileAuditor.arm` — after warmup — turns any FURTHER compile
  of the named (or all) wrapped callables into a loud
  :class:`RecompileError` at the exact call that triggered it, with the
  offending signature in the message.

Detection cost per call is one ``_cache_size()`` probe (an int read);
signatures are only materialized when a compile actually happened, so an
armed auditor is cheap enough to leave on in production serving.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable

__all__ = [
    "RecompileAuditor",
    "RecompileError",
    "CompileEvent",
    "abstract_signature",
    "named_program",
]


def named_program(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, for ``jax.jit``: the compiled program is then
    ``jit_<name>`` in a profiler trace and in the HLO. JAX names a program
    after its function's ``__name__``, and a ``functools.partial`` has
    none (``jit__unknown``): give a program the name it is wrapped with in
    :meth:`RecompileAuditor.wrap`, so the audit report and the trace agree.
    ``fn`` itself is left as it is (a module-level function is shared)."""
    fn = functools.partial(fn)
    fn.__name__ = name
    return fn


class RecompileError(RuntimeError):
    """An armed callable compiled again after warmup."""


def _leaf_sig(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            return f"{dtype}[{','.join(str(d) for d in shape)}]"
        except Exception:
            return f"{type(x).__name__}"
    # Non-array leaves retrace on VALUE (they are static or hashed into
    # weak-typed constants) — include the value, not just the type.
    return f"{type(x).__name__}={x!r}"


def abstract_signature(args: tuple, kwargs: dict) -> str:
    """Compact dtype[shape] signature of a call's abstract values — the
    identity jit traces on (up to weak types / static args)."""
    import jax

    leaves, treedef = jax.tree.flatten((args, kwargs))
    try:
        parts = [_leaf_sig(leaf) for leaf in leaves]
    except Exception:  # e.g. donated buffers in exotic backends
        return "<unavailable>"
    return f"({', '.join(parts)}) tree={treedef}"


@dataclasses.dataclass
class CompileEvent:
    """One observed compile: which callable, which call, what shapes."""

    name: str
    call_index: int
    signature: str
    armed: bool


class _AuditedFn:
    """Callable wrapper counting compiles of one jitted function.

    Transparent: ``__getattr__`` delegates to the wrapped callable, so
    probes like ``_cache_size`` (used by ``decode_compile_count``) and
    ``lower``/``compile`` still work through the wrapper.
    """

    def __init__(self, fn: Callable, name: str, auditor: "RecompileAuditor"):
        self._fn = fn
        self.name = name
        self._auditor = auditor
        self._lock = threading.Lock()
        self.calls = 0
        self.compiles = 0
        self.armed = False
        probe = getattr(fn, "_cache_size", None)
        self._probe = probe if callable(probe) else None
        self._seen_sigs: set[str] = set()
        self._max_size = self._cache_size() or 0

    def _cache_size(self) -> int | None:
        if self._probe is None:
            return None
        try:
            return int(self._probe())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        with self._lock:
            self.calls += 1
        size = self._cache_size()
        if size is None:
            # No probe (older/newer jax, or a plain callable): fall back to
            # signature-set tracking — a new abstract signature IS a trace.
            sig = abstract_signature(args, kwargs)
            with self._lock:
                fresh = sig not in self._seen_sigs
                self._seen_sigs.add(sig)
            out = self._fn(*args, **kwargs)
            if fresh:
                self._auditor._on_compile(self, sig)
            return out
        out = self._fn(*args, **kwargs)
        after = self._cache_size()
        grew = 0
        with self._lock:
            # Max-size tracking (not before/after around THIS call): with
            # concurrent callers (async trainer worker threads share one
            # window step) each cache-size increment is attributed exactly
            # once, by whichever caller observes it first.
            if after is not None and after > self._max_size:
                grew = after - self._max_size
                self._max_size = after
        if grew:
            # Signature materialized only on the (rare) compile; shape and
            # dtype are aval metadata, readable even off donated buffers.
            self._auditor._on_compile(
                self, abstract_signature(args, kwargs), n=grew)
        return out

    def __getattr__(self, name: str) -> Any:
        return getattr(self._fn, name)


class RecompileAuditor:
    """Audits a set of wrapped jitted callables.

    ``registry``: optional :class:`~distkeras_tpu.telemetry.registry.
    MetricsRegistry`; every observed compile increments
    ``recompile_auditor_compiles_total{fn=...}`` so the scrape endpoint
    shows compile counts live.
    """

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        self._fns: dict[str, _AuditedFn] = {}
        self.events: list[CompileEvent] = []
        self._registry = registry

    def wrap(self, fn: Callable, name: str) -> _AuditedFn:
        """Wrap ``fn`` (typically a ``jax.jit`` product) under ``name``;
        returns the transparent audited callable to use in its place."""
        wrapped = _AuditedFn(fn, name, self)
        with self._lock:
            if name in self._fns:
                raise ValueError(f"auditor already wraps a fn named {name!r}")
            self._fns[name] = wrapped
        return wrapped

    def _on_compile(self, fn: _AuditedFn, signature: str, n: int = 1) -> None:
        with fn._lock:  # compiles/calls share the wrapper's lock
            fn.compiles += n
            ev = CompileEvent(fn.name, fn.calls, signature, fn.armed)
        with self._lock:
            self.events.append(ev)
        if self._registry is not None:
            self._registry.counter(
                "recompile_auditor_compiles_total",
                help="compiles observed by the recompile auditor",
                fn=fn.name,
            ).inc(n)
        if fn.armed:
            raise RecompileError(
                f"{fn.name!r} recompiled after warmup (compile "
                f"#{fn.compiles}, call #{fn.calls}) — triggering abstract "
                f"signature: {signature}"
            )

    def arm(self, *names: str) -> None:
        """Fail loudly on any further compile of the named callables (all
        wrapped callables when no names given). Call after warmup — e.g.
        after the first decode iteration, or after the first train step."""
        with self._lock:
            targets = names or tuple(self._fns)
            for n in targets:
                if n not in self._fns:
                    raise KeyError(f"auditor wraps no fn named {n!r}")
                self._fns[n].armed = True

    def disarm(self, *names: str) -> None:
        with self._lock:
            for n in (names or tuple(self._fns)):
                self._fns[n].armed = False

    def compiles(self, name: str) -> int:
        return self._fns[name].compiles

    def total_compiles(self) -> int:
        return sum(f.compiles for f in self._fns.values())

    def report(self) -> dict:
        """Per-callable compile/call counts with triggering signatures —
        JSON-able, printed by ``run.py --audit-recompiles`` at exit."""
        with self._lock:
            events = list(self.events)
            fns = dict(self._fns)
        out = {}
        for name, fn in fns.items():
            out[name] = {
                "calls": fn.calls,
                "compiles": fn.compiles,
                "armed": fn.armed,
                "signatures": [e.signature for e in events if e.name == name],
            }
        return out
