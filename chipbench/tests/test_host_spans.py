"""The readers of the program's own spans and counters (PR 25), on the CPU.

``data/tiny_spans.xplane.pb`` is a recorded v5e trace with ``dk:`` spans:
6 steps of ``SingleTrainer`` on ``bert_tiny_mlm`` and the first 18 decode
ticks of a tiny paged engine (4 slots, 16-token blocks) in one profiler
session, cut to the device's ``XLA Modules`` / ``XLA Ops`` lines and the
host's ``dk:`` events (names shortened to 64 characters; my chip run, PR
25). ``expected_spans.json`` beside it was summed when the file was cut,
by other code: numpy over a raster of 10 ns cells, from the file's
picoseconds.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cell as cell_mod, host_spans, trace_reduce  # noqa: E402

SPANS_TRACE = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
PLAIN_TRACE = os.path.join(HERE, "data", "tiny.xplane.pb")  # PR 24's: no dk:
SPAN_READERS = ["tick_host_work_ms_p50", "idle_in_spans_share.serve",
                "feed_wait_ms_p50"]
COUNTER_READERS = ["pipeline_barriers_per_tick", "decode_occupancy",
                   "kv_pool_fill_share"]


@pytest.fixture(scope="module")
def want():
    with open(os.path.join(HERE, "data", "expected_spans.json")) as f:
        return json.load(f)


def _reader(name):
    return cell_mod._load_file(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "test_" + name)


def _ctx(path, monkeypatch, counters=None):
    """What a reader gets from the harness for a traced run whose trace
    file is ``path``."""
    monkeypatch.setattr(host_spans, "trace_file", lambda: path)
    return {"trace": trace_reduce.reduce(path, None, 1),
            "config": {"decode_program": {"name": "serving_decode"},
                       "serve_flags": ["--paged", "--slots", "64"]},
            "traffic": {"step_program": {"name": "^jit_step$"}},
            "counters": counters or {}}


def test_interval_arithmetic_by_hand():
    # work [0,4] and [3,7] (union 7), of which the wait [2,5] covers 3
    assert host_spans.uncovered([(0, 4), (3, 7)], [(2, 5)]) == 4
    assert host_spans.uncovered([(0, 4)], []) == 4
    assert host_spans.uncovered([], [(0, 1)]) == 0
    assert host_spans.clip([(0, 4), (5, 9), (10, 12)], 3, 10) == [(3, 4), (5, 9)]
    # two periods, [0,10) and [10,20): the work in each, less the wait
    assert host_spans.per_period(
        [0, 10, 20], [(1, 3), (8, 12), (15, 16)], [(2, 9)]) == [2, 3]
    spans = [("barrier", 0.0, 10.0), ("harvest", 1.0, 6.0), ("stream", 6.0, 9.0),
             ("decode_tick", 12.0, 14.0)]
    assert host_spans.named(spans, exclude=("harvest",)) == [
        (0.0, 10.0), (6.0, 9.0), (12.0, 14.0)]
    assert host_spans.named(spans, include=("harvest", "x")) == [(1.0, 6.0)]
    # nested spans: each stretch goes to the one that started last
    assert host_spans._innermost_segments(spans) == [
        (0.0, 1.0, "barrier"), (1.0, 6.0, "harvest"), (6.0, 9.0, "stream"),
        (9.0, 10.0, "barrier"), (12.0, 14.0, "decode_tick")]
    # operations [0,2] [5,8] [13,15]: idle 3 + 5 = 8, of which harvest has
    # [2,5], stream [8,9], barrier [9,10], decode_tick [12,13], none [10,12]
    idle, inside = host_spans.idle_by_span(
        {"spans": spans, "ops": [(0.0, 2.0), (5.0, 8.0), (13.0, 15.0)]})
    assert idle == 8
    assert inside == {"harvest": 3, "stream": 1, "barrier": 1, "decode_tick": 1}
    assert host_spans.idle_by_span({"spans": spans, "ops": [(0.0, 2.0)]}) is None


def test_recorded_trace_holds_the_spans_it_was_cut_with(want):
    parsed = host_spans.load(SPANS_TRACE)
    assert len(parsed["spans"]) == want["n_spans"]
    assert len(parsed["ops"]) == want["n_ops"]
    counts = {}
    for name, start, end in parsed["spans"]:
        counts[name] = counts.get(name, 0) + 1
        assert end >= start
    assert counts == want["span_counts"]
    assert {"harvest", "barrier", "loop_idle", "decode_tick", "stream",
            "data_next", "h2d_put", "train_step"} <= set(counts)
    assert host_spans.load(SPANS_TRACE) is parsed  # parsed once for a run


def test_tick_host_work_on_the_recorded_trace(want, monkeypatch):
    ctx = _ctx(SPANS_TRACE, monkeypatch)
    decode = trace_reduce.find_program(ctx["trace"], {"name": "serving_decode"})
    assert decode["count"] == want["decode_ticks"]
    assert all(n.startswith("jit_serving_decode(") for n in decode["names"])
    parsed = host_spans.load()
    waits = ("harvest", "loop_idle")
    got = host_spans.per_period(
        decode["starts_s"], host_spans.named(parsed["spans"], exclude=waits),
        host_spans.named(parsed["spans"], include=waits))
    assert [1e3 * g for g in got] == pytest.approx(
        want["tick_host_work_ms"], rel=1e-3, abs=1e-4)
    assert _reader("tick_host_work_ms_p50").read(ctx) == pytest.approx(
        want["tick_host_work_ms_p50"], rel=1e-3)


def test_feed_wait_on_the_recorded_trace(want, monkeypatch):
    ctx = _ctx(SPANS_TRACE, monkeypatch)
    step = trace_reduce.find_program(ctx["trace"], {"name": "^jit_step$"})
    assert step["count"] == want["steps"]
    assert _reader("feed_wait_ms_p50").read(ctx) == pytest.approx(
        want["feed_wait_ms_p50"], rel=1e-3)


def test_idle_in_spans_on_the_recorded_trace(want, monkeypatch, capsys):
    ctx = _ctx(SPANS_TRACE, monkeypatch)
    idle, inside = host_spans.idle_by_span(host_spans.load())
    assert idle == pytest.approx(want["idle_s"], rel=1e-3)
    assert set(inside) == set(want["idle_by_span_s"])
    for name, seconds in want["idle_by_span_s"].items():
        assert inside[name] == pytest.approx(seconds, rel=1e-3, abs=2e-6), name
    share = _reader("idle_in_spans_share.serve").read(ctx)
    assert share == pytest.approx(
        100 * want["idle_in_spans_s"] / want["idle_s"], rel=1e-3)
    assert 0 < share < 100
    assert "by span: decode_tick" in capsys.readouterr().err  # the largest


def test_counter_readers_by_hand():
    """137 ticks of 64 slots over a pool of 14,563 blocks."""
    delta = {"serving_decode_iterations_total": 137.0,
             "serving_slot_ticks_total": 8700.0,
             "kv_pool_block_ticks_total": 82200.0,
             "serving_pipeline_barriers_total{reason=paged_growth}": 130.0,
             "serving_pipeline_barriers_total{reason=admission}": 4.0,
             "serving_pipeline_barriers_total{reason=idle}": 0.0,
             "serving_tokens_out_total": 8765.0}
    ctx = {"config": {"serve_flags": ["--paged", "--slots", "64"]},
           "counters": {"metricsz_delta": delta,
                        "kv_pool": {"capacity_blocks": 14563}}}
    assert _reader("pipeline_barriers_per_tick").read(ctx) == pytest.approx(134 / 137)
    assert _reader("decode_occupancy").read(ctx) == pytest.approx(
        100 * 8700 / (137 * 64))                      # 99.22 %
    assert _reader("kv_pool_fill_share").read(ctx) == pytest.approx(
        100 * 600 / 14563)                            # 600 blocks a tick
    # a pool that is not paged has no capacity to set the blocks against
    ctx["counters"]["kv_pool"] = None
    assert _reader("kv_pool_fill_share").read(ctx) is None


@pytest.mark.parametrize("name", SPAN_READERS + COUNTER_READERS)
def test_a_program_without_the_spans_and_counters_reads_nothing(
        name, monkeypatch):
    """The parent of the PR that added them: its trace holds no ``dk:``
    event and its metricsz none of the three totals. A reader then returns
    None and does not raise."""
    assert host_spans.load(PLAIN_TRACE) is None
    parent = {"serving_decode_iterations_total": 137.0,
              "serving_tokens_out_total": 8765.0}
    ctx = _ctx(PLAIN_TRACE, monkeypatch, {
        "metricsz_delta": parent, "kv_pool": {"capacity_blocks": 14563}})
    ctx["config"]["decode_program"] = {"name": "^jit_step$"}  # it has 2 steps
    assert _reader(name).read(ctx) is None
    ctx["trace"] = None  # a CPU rehearsal: no device plane at all
    ctx["counters"] = {}
    assert _reader(name).read(ctx) is None


def test_new_readers_are_declared_beside_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_READERS + COUNTER_READERS:
        module = _reader(name)
        assert declared[name]["source"] == module.SOURCE
        assert declared[name]["unit"] == module.UNIT
        assert len(declared[name]["workloads"]) == 1
