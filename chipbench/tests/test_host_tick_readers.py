"""The three readers of the serving host's tick, on synthetic spans and
counter deltas: ``front_door_ms_per_tick`` (the union of ``front_read``
and ``front_write`` per decode period), ``loop_wait_ms_per_tick`` and
``harvest_ready_share`` (metricsz deltas), and what each reads from a
program that has none of them (the parent of the change that added
them)."""

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

from harness import cell as cell_mod, host_spans  # noqa: E402

READERS = ["front_door_ms_per_tick", "loop_wait_ms_per_tick",
           "harvest_ready_share"]
SERVING_CELLS = {"gpt2-small.gen_closed",
                 "command-a-plus-tp8ep8.mixed_len_closed",
                 "xing4-29b-a4b-pp.doc_qa_closed",
                 "openpangu-ultra-moe-ep16.doc_qa_64_closed"}


def _reader(name):
    return cell_mod._load_file(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "test_" + name)


def _ctx(monkeypatch, spans, starts, delta=None):
    """A traced serving run whose decode program started at ``starts``
    (seconds) and whose program wrote ``spans`` (name, start, end)."""
    parsed = {"spans": sorted(spans, key=lambda x: x[1]),
              "ops": [(s, s + 0.002) for s in starts]}
    monkeypatch.setattr(host_spans, "load",
                        lambda path=None: parsed if spans else None)
    program = {"count": len(starts), "total_s": 0.002 * len(starts),
               "starts_s": list(starts), "ops": ["fusion"]}
    return {"trace": {"devices": [{"programs": {
                "jit_serving_decode(7)": program}}]},
            "config": {"decode_program": {"name": "serving_decode$"}},
            "counters": {"metricsz_delta": delta or {}}}


def test_front_door_is_the_union_of_its_spans_per_tick(monkeypatch, capsys):
    # four decode starts, 10 ms apart: three periods
    starts = [0.0, 0.010, 0.020, 0.030]
    spans = [
        # period 1: a read [1, 2] ms and two writes [1.5, 3] and [5, 6]
        # ms, the read inside the first write's union: 2 + 1 = 3 ms
        ("front_read", 0.001, 0.002), ("front_write", 0.0015, 0.003),
        ("front_write", 0.005, 0.006),
        # period 2: one write of 2 ms, with other spans that do not count
        ("front_write", 0.012, 0.014), ("harvest", 0.010, 0.019),
        ("loop_yield", 0.014, 0.016), ("stream", 0.016, 0.018),
        # period 3: a write across its end is clipped to 1 ms
        ("front_write", 0.029, 0.031),
    ]
    ctx = _ctx(monkeypatch, spans, starts)
    got = _reader("front_door_ms_per_tick").read(ctx)
    # per period 3, 2 and 1 ms: the median is 2
    assert got == pytest.approx(2.0)
    err = capsys.readouterr().err
    assert "front_read" in err and "front_write" in err


def test_front_door_reads_nothing_without_its_spans(monkeypatch):
    starts = [0.0, 0.010, 0.020, 0.030]
    # the parent: other spans of the program, no front_*
    ctx = _ctx(monkeypatch, [("harvest", 0.001, 0.009),
                             ("stream", 0.011, 0.012)], starts)
    assert _reader("front_door_ms_per_tick").read(ctx) is None
    # too few decode executions to make periods of
    ctx = _ctx(monkeypatch, [("front_write", 0.001, 0.002)], starts[:2])
    assert _reader("front_door_ms_per_tick").read(ctx) is None
    # an untraced run
    ctx["trace"] = None
    assert _reader("front_door_ms_per_tick").read(ctx) is None


def test_loop_wait_per_iteration_sums_its_two_parts(monkeypatch, capsys):
    delta = {"serving_decode_iterations_total": 400.0,
             "serving_loop_wait_seconds_total{at=harvest}": 0.6,
             "serving_loop_wait_seconds_total{at=yield}": 1.0,
             "serving_tokens_out_total": 25000.0}
    ctx = _ctx(monkeypatch, [], [], delta)
    assert _reader("loop_wait_ms_per_tick").read(ctx) == pytest.approx(
        1e3 * 1.6 / 400)                               # 4 ms
    err = capsys.readouterr().err
    assert "harvest 1.500 ms" in err and "yield 2.500 ms" in err


def test_harvest_ready_share_is_ready_over_all(monkeypatch):
    delta = {"serving_harvests_total{ready=1}": 372.0,
             "serving_harvests_total{ready=0}": 28.0}
    ctx = _ctx(monkeypatch, [], [], delta)
    assert _reader("harvest_ready_share").read(ctx) == pytest.approx(93.0)
    delta["serving_harvests_total{ready=1}"] = 0.0
    assert _reader("harvest_ready_share").read(ctx) == 0.0
    # no harvest in the window reads nothing, not 0 %
    delta["serving_harvests_total{ready=0}"] = 0.0
    assert _reader("harvest_ready_share").read(ctx) is None


@pytest.mark.parametrize("name", READERS[1:])
def test_counter_readers_read_nothing_from_a_program_without_them(
        name, monkeypatch):
    parent = {"serving_decode_iterations_total": 137.0,
              "serving_tokens_out_total": 8765.0}
    assert _reader(name).read(_ctx(monkeypatch, [], [], parent)) is None
    assert _reader(name).read({"counters": {}}) is None


def test_the_three_are_declared_for_the_four_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        module = _reader(name)
        m = declared[name]
        assert (m["source"], m["unit"]) == (module.SOURCE, module.UNIT)
        assert set(m["workloads"]) == SERVING_CELLS
    assert declared["front_door_ms_per_tick"]["moves"] == "itl_p99_ms"
    assert declared["loop_wait_ms_per_tick"]["layer"] == \
        declared["harvest_ready_share"]["layer"] == "engine step loop"
