"""Tracing, profiling, and structured metrics.

The reference's observability is wall-clock getters plus the Spark web UI
(SURVEY §5). Here:

- :class:`StepTimer` — per-step wall times with the derived metrics the
  BASELINE cares about (samples/sec/chip, step-time variance, tail
  percentiles, MFU);
- :class:`MetricStream` — structured per-step metric records with pluggable
  sinks (in-memory, JSONL file, stdout).

Spans, the recompile auditor, and the metrics registry live in
:mod:`distkeras_tpu.telemetry` — the unified observability layer this
module now publishes into. ``span`` / ``enable_tracing`` / ``Tracer``
— and now ``trace``, the ``jax.profiler`` capture promoted to
:func:`distkeras_tpu.telemetry.device.profile_trace` — remain
importable here as **deprecated shims** (a module ``__getattr__`` that
warns and forwards): they are pure re-exports, and new code should
import from ``distkeras_tpu.telemetry``.
"""

from __future__ import annotations

import json
import statistics
import time
import warnings
from typing import Any, Callable

import jax

from distkeras_tpu.telemetry.registry import percentile, sanitize_metric_name

# Names that moved to distkeras_tpu.telemetry; accessing them here still
# works but warns — the lazy __getattr__ keeps this module from paying
# (or masking) the telemetry.spans import on its own hot imports.
_TELEMETRY_SHIMS = frozenset(
    {"span", "enable_tracing", "disable_tracing", "active_tracer",
     "Tracer"})


def __getattr__(name: str):
    if name in _TELEMETRY_SHIMS:
        warnings.warn(
            f"distkeras_tpu.tracing.{name} is deprecated; import it from "
            f"distkeras_tpu.telemetry (it has been a pure re-export since "
            f"the telemetry unification)",
            DeprecationWarning, stacklevel=2)
        from distkeras_tpu.telemetry import spans as _spans

        return getattr(_spans, name)
    if name == "trace":
        # The jax.profiler start/stop pairing now lives in ONE place —
        # telemetry.device.profile_trace; this shim forwards rather than
        # keeping a second copy of the logic.
        warnings.warn(
            "distkeras_tpu.tracing.trace is deprecated; use "
            "distkeras_tpu.telemetry.profile_trace (the promoted "
            "jax.profiler helper)",
            DeprecationWarning, stacklevel=2)
        from distkeras_tpu.telemetry.device import profile_trace

        return profile_trace
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "StepTimer",
    "MetricStream",
    "device_peak_flops",
    "compiled_step_flops",
    # re-exported from distkeras_tpu.telemetry (canonical home):
    "trace",
    "span",
    "enable_tracing",
    "disable_tracing",
    "active_tracer",
    "Tracer",
]


# Peak bf16 FLOPs/s per chip by TPU generation (public figures).
_PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def device_peak_flops(device=None) -> float | None:
    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, flops in _PEAK_FLOPS.items():
        if key in kind:
            return flops
    return None


def compiled_step_flops(step_fn, *args) -> float | None:
    """FLOPs for ONE call of a jitted function, from XLA's own cost model
    (``Compiled.cost_analysis()``).

    This is the authoritative count for MFU: a hand-maintained
    ``Model.flops_per_example`` constant silently mis-reports the headline
    metric when the model changes; the compiled
    analysis counts what actually runs, including the backward pass and
    rematerialisation. With a persistent compile cache the extra
    ``lower().compile()`` is a cache hit, not a second real compile.
    Returns None when the backend offers no cost model.
    """
    try:
        compiled = step_fn.lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one dict per device
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", -1.0))
        return flops if flops > 0 else None
    except Exception:
        return None


class StepTimer:
    """Wall-clock per step; call ``tick()`` after each (blocked-on) step."""

    def __init__(self):
        self._times: list[float] = []
        self._last: float | None = None

    def start(self) -> None:
        self._last = time.perf_counter()

    def tick(self) -> float:
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            return 0.0
        dt = now - self._last
        self._last = now
        self._times.append(dt)
        return dt

    @property
    def step_times(self) -> list[float]:
        return self._times

    def summary(
        self,
        batch_size: int | None = None,
        flops_per_example: float | None = None,
        num_chips: int = 1,
        skip_warmup: int = 1,
        flops_per_step: float | None = None,
    ) -> dict[str, float]:
        """``flops_per_step`` (e.g. from :func:`compiled_step_flops`) is the
        exact per-step cost and takes precedence; ``flops_per_example``
        falls back to the 3x-forward heuristic (fwd + bwd)."""
        times = self._times[skip_warmup:] if len(self._times) > skip_warmup else self._times
        if not times:
            return {}
        mean = statistics.fmean(times)
        out = {
            "steps": float(len(times)),
            "step_time_mean_s": mean,
            "step_time_p50_s": statistics.median(times),
            # Tail percentiles: mean/p50 hide exactly the stragglers the
            # BASELINE's step-time-variance concern is about — one slow
            # step per N stalls every chip in a synchronous mesh.
            "step_time_p90_s": percentile(times, 90),
            "step_time_p99_s": percentile(times, 99),
            "step_time_var_s2": statistics.pvariance(times) if len(times) > 1 else 0.0,
            "step_time_min_s": min(times),
        }
        if batch_size:
            out["samples_per_sec"] = batch_size / mean
            out["samples_per_sec_per_chip"] = batch_size / mean / max(1, num_chips)
        step_flops = None
        if flops_per_step:
            step_flops = float(flops_per_step)
        elif batch_size and flops_per_example:
            # train step ≈ 3x forward FLOPs (fwd + bwd)
            step_flops = 3.0 * flops_per_example * batch_size
        if step_flops:
            achieved = step_flops / mean
            out["train_tflops_per_sec"] = achieved / 1e12
            peak = device_peak_flops()
            if peak:
                out["mfu"] = achieved / (peak * max(1, num_chips))
        return out


class MetricStream:
    """Structured metric records: ``emit(step, {...})`` fans out to sinks.

    ``registry``: optional :class:`~distkeras_tpu.telemetry.registry.
    MetricsRegistry`; every numeric metric emitted also sets a
    ``stream_<key>`` gauge (latest value) and bumps
    ``stream_records_total``, so a scrape of the registry shows the live
    tail of the step series without replaying the JSONL.

    Close when done: ``to_jsonl`` owns an open file handle. Use as a
    context manager, or call :meth:`close` — emitting after close raises.
    """

    def __init__(self, sinks: list[Callable[[dict], None]] | None = None,
                 registry=None):
        self.records: list[dict] = []
        self._sinks = sinks or []
        self._files: list[Any] = []  # handles owned by this stream
        self._closed = False
        self._registry = registry

    @classmethod
    def to_jsonl(cls, path: str, registry=None) -> "MetricStream":
        f = open(path, "a")

        def sink(rec: dict):
            f.write(json.dumps(rec) + "\n")
            f.flush()

        stream = cls([sink], registry=registry)
        stream._files.append(f)
        return stream

    def close(self) -> None:
        """Flush and close owned file handles; idempotent."""
        if self._closed:
            return
        self._closed = True
        for f in self._files:
            try:
                f.close()
            except OSError:
                pass
        self._files.clear()

    def __enter__(self) -> "MetricStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def emit(self, step: int, metrics: dict[str, Any]) -> None:
        if self._closed:
            raise ValueError("emit() on a closed MetricStream")
        rec = {"step": int(step), "ts": time.time(), **_floats(metrics)}
        self.records.append(rec)
        for sink in self._sinks:
            sink(rec)
        if self._registry is not None:
            self._registry.counter(
                "stream_records_total", help="MetricStream records emitted"
            ).inc()
            for k, v in rec.items():
                if k in ("step", "ts") or not isinstance(v, (int, float)):
                    continue
                self._registry.gauge(
                    "stream_" + sanitize_metric_name(k),
                    help="latest stream value").set(v)

    def last(self) -> dict | None:
        return self.records[-1] if self.records else None


def _floats(metrics: dict) -> dict:
    out = {}
    for k, v in metrics.items():
        try:
            out[k] = float(v)
        except (TypeError, ValueError):
            out[k] = v
    return out


