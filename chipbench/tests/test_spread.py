"""The spread reckoning (chipbench/spread.py) on a fixture of result
lines, and what a serving run sets in its own process and has to put back
(harness/serve_entry.py: the CPUs it keeps to). CPU only, tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q -p no:cacheprovider
"""

import gc
import io
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import spread  # noqa: E402
from harness import host_probe, serve_entry  # noqa: E402

LINES = os.path.join(HERE, "data", "spread_lines.jsonl")


# ------------------------------------------------------------ the formulas

# six runs of which one reads far off: 4600 4610 4620 4630 4640 | 4900
FAR_OFF = [4640, 4600, 4900, 4620, 4610, 4630]


@pytest.mark.parametrize("formula, want", [
    ("iqr", 97.5),       # quartiles at 4607.5 and 4705 (statistics, n=4)
    ("range", 300.0),
    ("range-1", 40.0),   # 4900 left out
    ("iqr-1", 30.0),     # quartiles of the five left: 4605 and 4635
])
def test_each_formula_on_a_set_with_one_run_far_off(formula, want):
    got = spread.spreads(FAR_OFF)
    assert got["median"] == 4625
    assert got[formula] == pytest.approx(want)


def test_leaving_out_the_farthest_run_never_widens_a_spread():
    # an even set: dropping an end run cannot narrow the quartiles' distance
    # by much, and may widen it (five values' quartiles sit further out)
    even = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    got = spread.spreads(even)
    assert got["iqr-1"] <= got["iqr"] and got["range-1"] <= got["range"]
    assert got["range-1"] == pytest.approx(4.0)
    # two runs: nothing is left out
    assert spread.without_farthest([1.0, 5.0]) == [1.0, 5.0]
    # the farthest is the one farthest from the MEDIAN, on either side
    assert sorted(spread.without_farthest([1.0, 9.0, 10.0, 11.0, 12.0])) == [
        9.0, 10.0, 11.0, 12.0]


def test_report_reads_the_fixture():
    sets = spread.read_sets([LINES])
    assert list(sets) == ["spread_lines.jsonl"]
    assert len(sets["spread_lines.jsonl"]) == 6    # the stray line is skipped
    out = io.StringIO()
    table = spread.report(sets, out)["spread_lines.jsonl"]
    tok, server = table["serve_tokens_per_s"], table["server.slot_ticks_per_s"]
    assert (tok["iqr"], tok["range"], tok["range-1"], tok["iqr-1"]) == (
        pytest.approx(97.5), 300, 40, pytest.approx(30.0))
    # the server's own count spreads as the clients' does in this fixture:
    # the noise is not the load generator's
    assert server["iqr"] == pytest.approx(tok["iqr"])
    assert table["itl_p99_ms"]["iqr"] == pytest.approx(0.7)
    assert table["setup_s"]["range"] == pytest.approx(5.0)
    text = out.getvalue()
    assert "all correct, none failed" in text
    assert "iqr-1 30 (0.649%)" in text
    whole, first, second = spread.halves_as_runs(
        sets["spread_lines.jsonl"])["serve_tokens_per_s"]
    # each run's halves read 5 under and 5 over its whole: the same spreads
    assert whole["iqr"] == first["iqr"] == second["iqr"] == pytest.approx(97.5)
    assert (first["median"], second["median"]) == (4620, 4630)
    assert "the second halves: 2.108%, 2.110%, 2.106%" in text
    few = spread.report({"two": sets["spread_lines.jsonl"][:2]}, out)
    assert few == {} and "too few for a spread" in out.getvalue()


def test_report_names_a_run_that_is_not_correct():
    lines = spread.read_sets([LINES])["spread_lines.jsonl"]
    lines[2] = {**lines[2], "correct": False}
    out = io.StringIO()
    spread.report({"s": lines}, out)
    assert "NOT correct or failed: [102]" in out.getvalue()


# ---------------------------------------------- what a run sets and restores

def test_split_cpus_parts_what_the_process_has_and_not_fixed_numbers():
    assert serve_entry.split_cpus({0, 1, 2}) is None       # too few to part
    gen, server = serve_entry.split_cpus(range(13))
    assert gen == {0, 1, 2} and server == set(range(3, 13))
    gen, server = serve_entry.split_cpus({5, 9, 17, 21})   # whatever it has
    assert gen == {5} and server == {9, 17, 21}
    assert not gen & server


@pytest.mark.parametrize("raises", [False, True])
def test_own_cpus_puts_the_process_back_where_it_was_found(raises):
    found = os.sched_getaffinity(0)
    cpus = {min(found)}
    try:
        with serve_entry.own_cpus(cpus):
            assert os.sched_getaffinity(0) == cpus
            if raises:
                raise RuntimeError("the window broke")
    except RuntimeError:
        assert raises
    assert os.sched_getaffinity(0) == found
    with serve_entry.own_cpus(None):               # too few to part: as found
        assert os.sched_getaffinity(0) == found


def test_the_server_starts_on_its_own_cpus_and_the_parent_keeps_all(
        monkeypatch, tmp_path):
    """The child inherits the set it was started from; the parent has its
    own back once the child is started."""
    import subprocess
    import types

    found = os.sched_getaffinity(0)
    cpus = {max(found)}
    started = {}

    def popen(argv, **kwargs):
        started["cpus"] = os.sched_getaffinity(0)  # what a child inherits
        return types.SimpleNamespace(pid=0)

    monkeypatch.setattr(subprocess, "Popen", popen)
    cell = types.SimpleNamespace(config_path="c.json", root=str(tmp_path))
    serve_entry.Server(cell, 1, None, cpus)
    assert started["cpus"] == cpus
    assert os.sched_getaffinity(0) == found


def test_gc_watch_times_this_process_collections_and_unhooks():
    with host_probe.GcWatch() as watch:
        junk = [[i] for i in range(1000)]
        gc.collect()
        del junk
    assert watch._callback not in gc.callbacks
    assert any(gen == 2 for gen, _, _ in watch.events)
    summary = watch.summary()
    assert summary["collections"]["2"]["n"] >= 1
    assert summary["longest_collections"][0][2] >= 0
    gc.collect()                       # outside the with: not counted
    assert sum(1 for g, _, _ in watch.events if g == 2) == \
        summary["collections"]["2"]["n"]
    with pytest.raises(RuntimeError):  # unhooked also when the body raises
        with host_probe.GcWatch() as broken:
            raise RuntimeError
    assert broken._callback not in gc.callbacks


def test_a_window_that_raises_leaves_the_process_as_it_found_it(
        tmp_path, monkeypatch):
    """One serving run on the CPU whose window breaks (the read of the
    server's counters at its close raises): the run raises, the server is
    stopped, and this process is back on the CPUs it was found on, with no
    callback of the window's left on its collector."""
    import test_chipbench as t
    from harness import main

    root = t._bench_dir(tmp_path, t.SERVE_CONFIG, t.SERVE_MIX, t.SERVE_LIMITS,
                        ["serve_tokens_per_s"], "requests", own_limits=True)
    real, calls, inside = serve_entry._counters, [], {}
    found = os.sched_getaffinity(0)
    parted = serve_entry.split_cpus(found)

    async def counters(host, port, since_wall=None):
        calls.append(since_wall)
        if len(calls) == 2:            # at the close of the window
            inside["cpus"] = os.sched_getaffinity(0)
            raise RuntimeError("the counters could not be read")
        return await real(host, port, since_wall)

    monkeypatch.setattr(serve_entry, "_counters", counters)
    with pytest.raises(RuntimeError, match="could not be read"):
        main.run_cell("tiny.mix", 2**31 + 13, 1.0, False, time.perf_counter(),
                      root=root, require_platform=None)
    if parted:                         # the window ran on the generator's
        assert inside["cpus"] == parted[0]
    assert os.sched_getaffinity(0) == found
    assert not [c for c in gc.callbacks if isinstance(
        getattr(c, "__self__", None), host_probe.GcWatch)]
