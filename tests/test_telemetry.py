"""Unified telemetry layer (distkeras_tpu.telemetry).

Covers the four pillars:
- spans: no-op when disabled, correct nesting across threads and asyncio
  tasks, Chrome-trace export is valid JSON with matched B/E per lane;
- recompile auditor: counts compiles with triggering shapes, flags an
  intentionally shape-unstable jit when armed, signature fallback when
  the jit cache probe is absent;
- registry: counter/gauge/histogram semantics, shared percentile edge
  cases (empty raises, single sample exact), Prometheus text exposition;
- streams/timers: MetricStream close + context manager, StepTimer tail
  percentiles.
"""

import asyncio
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry as T
from distkeras_tpu.telemetry import (
    MetricsRegistry,
    RecompileAuditor,
    RecompileError,
    Tracer,
    percentile,
)


@pytest.fixture(autouse=True)
def _tracing_disabled():
    """Every test starts and ends with tracing off (module-global state)."""
    T.disable_tracing()
    yield
    T.disable_tracing()


def _balanced_stacks(trace: dict) -> dict[int, list[str]]:
    """Walk traceEvents asserting every E matches the innermost B on its
    lane; returns the (empty) final per-lane stacks."""
    stacks: dict[int, list[str]] = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev["name"])
        elif ev["ph"] == "E":
            lane = stacks.get(ev["tid"])
            assert lane, f"E {ev['name']!r} without B on lane {ev['tid']}"
            assert lane.pop() == ev["name"]
    assert all(not s for s in stacks.values()), f"unclosed spans: {stacks}"
    return stacks


# -- spans --------------------------------------------------------------------

def test_span_disabled_is_noop_singleton():
    a = T.span("x")
    b = T.span("y", attr=1)
    assert a is b  # the shared null span: no allocation on the hot path
    with a:
        pass


def test_spans_nest_and_record_parents():
    tracer = T.enable_tracing()
    with T.span("outer", step=1):
        with T.span("inner"):
            pass
    with T.span("sibling"):
        pass
    T.disable_tracing()
    events = tracer.events()
    names = [(ph, name) for ph, name, *_ in events]
    assert names == [("B", "outer"), ("B", "inner"), ("E", "inner"),
                     ("E", "outer"), ("B", "sibling"), ("E", "sibling")]
    by_name = {name: parent for ph, name, t, lane, parent, attrs in events
               if ph == "B"}
    assert by_name["inner"] == "outer"
    assert by_name["outer"] is None and by_name["sibling"] is None


def test_chrome_trace_valid_json_matched_be(tmp_path):
    tracer = T.enable_tracing()
    with T.span("a"):
        with T.span("b", k=2):
            pass
    T.disable_tracing()
    path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    trace = json.loads(open(path).read())
    assert isinstance(trace["traceEvents"], list)
    bs = [e for e in trace["traceEvents"] if e["ph"] == "B"]
    es = [e for e in trace["traceEvents"] if e["ph"] == "E"]
    assert len(bs) == len(es) == 2
    for e in bs + es:
        assert set(e) >= {"name", "ph", "pid", "tid", "ts"}
    _balanced_stacks(trace)
    b_b = next(e for e in bs if e["name"] == "b")
    assert b_b["args"] == {"k": 2, "parent": "a"}


def test_spans_across_asyncio_tasks_get_own_lanes():
    """Two concurrent tasks interleave on one thread; each must land on
    its own lane with stack-balanced B/E, parented to the span that was
    active when the task was created."""
    tracer = T.enable_tracing()

    async def worker(tag):
        with T.span(f"task_{tag}"):
            await asyncio.sleep(0.01)
            with T.span(f"step_{tag}"):
                await asyncio.sleep(0.01)

    async def main():
        with T.span("root"):
            await asyncio.gather(worker("a"), worker("b"))

    asyncio.run(main())
    T.disable_tracing()
    events = tracer.events()
    parents = {name: parent for ph, name, t, lane, parent, _ in events
               if ph == "B"}
    assert parents["task_a"] == "root" and parents["task_b"] == "root"
    assert parents["step_a"] == "task_a" and parents["step_b"] == "task_b"
    lanes = {name: lane for ph, name, t, lane, parent, _ in events
             if ph == "B"}
    assert lanes["task_a"] != lanes["task_b"]  # separate swimlanes
    _balanced_stacks(tracer.chrome_trace())


def test_spans_across_threads_get_own_lanes():
    tracer = T.enable_tracing()

    def work(tag):
        with T.span(f"thread_{tag}"):
            pass

    ts = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    with T.span("main"):
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    T.disable_tracing()
    lanes = {name: lane for ph, name, t, lane, parent, _ in tracer.events()
             if ph == "B"}
    assert len({lanes["main"], lanes["thread_0"], lanes["thread_1"]}) == 3
    _balanced_stacks(tracer.chrome_trace())


def test_tracer_event_cap_keeps_matched_be():
    """A full tracer drops NEW spans whole (counted), while admitted and
    still-open spans keep their closing E — the recorded stream stays
    stack-balanced per lane."""
    tracer = T.enable_tracing(Tracer(max_events=4))
    with T.span("outer"):           # admitted (reserves its E)
        with T.span("kept"):        # admitted: 2 events used + reserve
            pass
        for _ in range(10):         # cap hit: all dropped
            with T.span("dropped"):
                pass
    T.disable_tracing()
    assert tracer.dropped_spans == 10
    names = [name for ph, name, *_ in tracer.events()]
    assert "dropped" not in names
    trace = tracer.chrome_trace()
    _balanced_stacks(trace)
    meta = [e for e in trace["traceEvents"] if e["name"] == "dropped_spans"]
    assert meta and meta[0]["args"]["count"] == 10


def _profiled_spans(tmp_path, body) -> dict[str, list[dict]]:
    """Run ``body()`` inside a jax.profiler session; the ``dk:`` events of
    the trace's ``/host:CPU`` plane, name -> [stats of each event]."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(T.spans.PREFIX):
                    assert plane.name == "/host:CPU"
                    found.setdefault(ev.name, []).append(
                        {"duration_ns": ev.duration_ns, **dict(ev.stats)})
    return found


def test_span_is_null_without_tracer_and_without_profiler_session(tmp_path):
    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled() and T.active_tracer() is None
    assert T.span("x") is T.spans._NULL_SPAN
    found = _profiled_spans(
        tmp_path, lambda: T.span("seen").__enter__().__exit__(None, None, None))
    assert list(found) == ["dk:seen"]
    # the session is over: the program was never told, it asks each time
    assert T.span("x", attr=1) is T.spans._NULL_SPAN


def test_span_reaches_a_live_profiler_session_with_its_attrs(tmp_path):
    import time

    def body():
        with T.span("decode_tick", active=64):
            time.sleep(0.005)
        with T.span("barrier", reason="paged_growth"):
            pass

    found = _profiled_spans(tmp_path, body)
    assert set(found) == {"dk:decode_tick", "dk:barrier"}
    (tick,) = found["dk:decode_tick"]
    assert tick["active"] == 64 and tick["duration_ns"] >= 5e6
    assert found["dk:barrier"][0]["reason"] == "paged_growth"


def test_tracer_and_profiler_session_together_record_both(tmp_path):
    tracer = T.enable_tracing()

    def body():
        with T.span("outer", step=3):
            with T.span("inner"):
                pass

    found = _profiled_spans(tmp_path, body)
    T.disable_tracing()
    assert found["dk:outer"][0]["step"] == 3 and "dk:inner" in found
    # the Chrome export keeps its own names (no prefix), parents and format
    trace = tracer.chrome_trace()
    _balanced_stacks(trace)
    begins = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "B"}
    assert set(begins) == {"outer", "inner"}
    assert begins["outer"]["args"] == {"step": 3}
    assert begins["inner"]["args"] == {"parent": "outer"}


def test_sanitize_metric_name():
    from distkeras_tpu.telemetry import sanitize_metric_name

    assert sanitize_metric_name("loss") == "loss"
    assert sanitize_metric_name("weird key!") == "weird_key_"
    assert sanitize_metric_name("1st") == "_1st"
    assert sanitize_metric_name("") == "_"


# -- recompile auditor --------------------------------------------------------

def test_auditor_flags_shape_unstable_jit_when_armed():
    auditor = RecompileAuditor()
    unstable = auditor.wrap(jax.jit(lambda x: x * 2), "unstable")
    unstable(jnp.ones((3,)))
    unstable(jnp.ones((3,)))  # cache hit
    assert auditor.compiles("unstable") == 1
    unstable(jnp.ones((4,)))  # retrace: new shape
    assert auditor.compiles("unstable") == 2
    auditor.arm("unstable")
    unstable(jnp.ones((4,)))  # still cached: fine while armed
    with pytest.raises(RecompileError, match="unstable"):
        unstable(jnp.ones((5,)))
    rep = auditor.report()["unstable"]
    assert rep["compiles"] == 3 and rep["armed"]
    # The triggering abstract shapes are in the record.
    assert any("5" in sig for sig in rep["signatures"])


def test_auditor_signature_fallback_without_probe():
    """A callable with no jit cache probe is audited by abstract input
    signature — distinct shapes count, repeats don't."""
    auditor = RecompileAuditor()
    fn = auditor.wrap(lambda x: np.asarray(x) * 2, "plain")
    fn(np.ones((3,)))
    fn(np.ones((3,)))
    fn(np.ones((2, 2)))
    assert auditor.compiles("plain") == 2


def test_auditor_registry_publishing_and_wrap_uniqueness():
    reg = MetricsRegistry()
    auditor = RecompileAuditor(registry=reg)
    f = auditor.wrap(jax.jit(lambda x: x + 1), "f")
    f(jnp.ones((2,)))
    snap = reg.snapshot()
    assert snap["recompile_auditor_compiles_total{fn=f}"]["value"] == 1.0
    with pytest.raises(ValueError, match="already wraps"):
        auditor.wrap(lambda x: x, "f")


# -- registry -----------------------------------------------------------------

def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", help="h")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("reqs_total") is c  # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")  # kind mismatch is loud
    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3


def test_histogram_and_shared_percentile_agree_on_edges():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    with pytest.raises(ValueError):
        h.percentile(50)  # empty
    with pytest.raises(ValueError):
        percentile([], 50)  # the exact helper agrees
    h.observe(0.42)
    assert h.percentile(1) == 0.42 == h.percentile(99)  # single: exact
    assert percentile([0.42], 1) == 0.42 == percentile([0.42], 99)
    for v in (0.02, 0.05, 0.2, 0.7):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(1.39)
    # Bucket estimate stays within the observed range and brackets p50.
    assert 0.02 <= h.percentile(50) <= 0.7
    # Exact percentile matches numpy's linear interpolation.
    xs = [3.0, 1.0, 2.0, 4.0]
    assert percentile(xs, 50) == pytest.approx(float(np.percentile(xs, 50)))
    assert percentile(xs, 90) == pytest.approx(float(np.percentile(xs, 90)))


def test_prometheus_text_format(tmp_path):
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests", code="ok").inc(5)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = T.prometheus_text(reg)
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{code="ok"} 5' in text
    assert "depth 2" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    # JSONL snapshot round-trips.
    path = tmp_path / "m.jsonl"
    T.write_snapshot_jsonl(reg, str(path))
    T.write_snapshot_jsonl(reg, str(path))
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    assert lines[0]["metrics"]["depth"]["value"] == 2


# -- stream close + timer tails ----------------------------------------------

def test_metric_stream_close_and_context_manager(tmp_path):
    from distkeras_tpu.tracing import MetricStream

    path = tmp_path / "m.jsonl"
    ms = MetricStream.to_jsonl(str(path))
    ms.emit(0, {"loss": 1.0})
    assert not ms._files[0].closed
    ms.close()
    ms.close()  # idempotent
    with pytest.raises(ValueError, match="closed"):
        ms.emit(1, {"loss": 0.5})
    with MetricStream.to_jsonl(str(path)) as ms2:
        ms2.emit(1, {"loss": 0.5})
        handle = ms2._files[0]
    assert handle.closed
    assert len([json.loads(l) for l in open(path)]) == 2


def test_metric_stream_publishes_to_registry(tmp_path):
    from distkeras_tpu.tracing import MetricStream

    reg = MetricsRegistry()
    ms = MetricStream(registry=reg)
    ms.emit(0, {"loss": 1.5, "weird key!": 2.0})
    ms.emit(1, {"loss": 1.2})
    snap = reg.snapshot()
    assert snap["stream_records_total"]["value"] == 2
    assert snap["stream_loss"]["value"] == 1.2  # latest value wins
    assert snap["stream_weird_key_"]["value"] == 2.0  # sanitized name


def test_step_timer_tail_percentiles():
    from distkeras_tpu.tracing import StepTimer

    t = StepTimer()
    t.start()
    t._times = [0.01] * 98 + [0.05, 0.1]  # deterministic synthetic tail
    s = t.summary(skip_warmup=0)
    assert s["step_time_p90_s"] == pytest.approx(0.01)
    assert s["step_time_p99_s"] > s["step_time_p90_s"]
    assert s["step_time_p99_s"] <= 0.1


def test_tracing_reexports_canonical_telemetry():
    """tracing.py stays a one-stop import for observability users."""
    from distkeras_tpu import tracing

    assert tracing.span is T.span
    assert tracing.enable_tracing is T.enable_tracing
    assert tracing.Tracer is T.Tracer
