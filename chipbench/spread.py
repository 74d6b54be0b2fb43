#!/usr/bin/env python3
"""Reckons run-to-run spread from result lines, the way a bound is set
from it (PERF.md section 2), and splits a serving run's numbers by where
they were taken. Not a cell, and not run by the driver.

    python3 chipbench/spread.py chiprun_out/a.jsonl chiprun_out/b.jsonl
    python3 chipbench/spread.py --run --workload gpt2-small.gen_closed \
        --seeds 101,102,103 --seconds 30 --out chiprun_out/a.jsonl

Reading: each file is one set of runs, one result line of
``chipbench/run.py`` each. For each set and end-to-end metric it prints the median and the
spread by four formulas, as a number and as a share of the median:

- ``iqr``       third quartile less first, ``statistics.quantiles(n=4)``;
- ``range``     largest less smallest of all runs;
- ``range-1``   the range of the runs left when the one farthest from
                the median is left out;
- ``iqr-1``     the quartiles' distance of those same runs: the driver's
                words ("a spread leaves out the run farthest from its
                median where that narrows it") applied to its quartiles.

Where the lines carry a ``split`` (serving cells), the same spreads of
the server's own count over the same window (``slot_ticks_per_s``) stand
beside the clients': a server's count as unsteady as the clients' means
the noise is the server's or the machine's, a steadier one that it is
the load generator's. Then the halves of the runs, each reduced as a run
of half the length: where the halves spread as the wholes do, a run has
a state and a longer window narrows nothing; where the halves spread
wider (by the root of two, were every second its own draw), it would.

``--run`` makes the runs, one process each as the driver does, and
appends each result line (with its ``seed``) to ``--out``. Like the
benchmark's command it runs on the chip only.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def iqr(values) -> float:
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def full_range(values) -> float:
    return max(values) - min(values)


def without_farthest(values) -> list:
    """The runs left when the one farthest from the median goes."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))
    return kept[:-1] if len(kept) > 2 else kept


def spreads(values) -> dict:
    """Every formula's spread of one set, in the metric's own unit. The
    two that leave a run out never read wider than the two that do not."""
    values = [float(v) for v in values]
    kept = without_farthest(values)
    return {"median": statistics.median(values),
            "iqr": iqr(values), "range": full_range(values),
            "range-1": min(full_range(kept), full_range(values)),
            "iqr-1": min(iqr(kept), iqr(values))}


FORMULAS = ("iqr", "range", "range-1", "iqr-1")


def read_sets(paths) -> dict:
    """file name -> its result lines (dicts), in the order written."""
    sets = {}
    for path in paths:
        with open(path) as f:
            lines = [json.loads(s) for s in f if s.lstrip().startswith("{")]
        sets[os.path.basename(path)] = [r for r in lines if "metrics" in r]
    return sets


def series(lines) -> dict:
    """name -> one value a run: the end-to-end metrics, and from a split
    the server's own rate and the load generator's worst oversleep."""
    out = {}
    for r in lines:
        for name, m in r["metrics"].items():
            out.setdefault(name, []).append(m["value"])
        split = r.get("split") or {}
        if "server" in split:
            out.setdefault("server.slot_ticks_per_s", []).append(
                split["server"]["slot_ticks_per_s"])
        if "loadgen" in split:
            out.setdefault("loadgen.stall_max_ms", []).append(
                split["loadgen"]["stall_max_ms"])
    return {k: v for k, v in out.items() if len(v) == len(lines)}


def halves_as_runs(lines) -> dict:
    """metric -> the spreads of the wholes, of the first halves and of the
    second halves over the set, each half reduced as if it were a run of
    half the length."""
    out = {}
    for name in lines[0].get("split", {}).get("clients", {}):
        if name == "window_s":
            continue
        try:
            out[name] = [spreads(v) for v in (
                [r["split"]["clients"][name] for r in lines],
                [r["split"]["halves"][0][name] for r in lines],
                [r["split"]["halves"][1][name] for r in lines])]
        except KeyError:
            continue
    return out


def report(sets: dict, out=sys.stdout) -> dict:
    """Prints the tables; returns {set: {series: spreads}}."""
    table = {}
    for label, lines in sets.items():
        if len(lines) < 3:
            print(f"{label}: {len(lines)} runs, too few for a spread", file=out)
            continue
        wrong = [r.get("seed") for r in lines
                 if not r.get("correct") or r.get("failed")]
        print(f"\n{label}: {len(lines)} runs"
              + (f", NOT correct or failed: {wrong}" if wrong else
                 ", all correct, none failed"), file=out)
        table[label] = {}
        for name, values in series(lines).items():
            s = table[label][name] = spreads(values)
            med = s["median"]
            cells = "  ".join(
                f"{f} {s[f]:.6g} ({100 * s[f] / med:.3f}%)" if med else
                f"{f} {s[f]:.6g}" for f in FORMULAS)
            print(f"  {name:<28} median {med:<10.6g} {cells}", file=out)
        for name, parts in halves_as_runs(lines).items():
            print(f"  {name:<28} iqr of the wholes, the first halves, the "
                  "second halves: " + ", ".join(
                      f"{100 * p['iqr'] / p['median']:.3f}%" for p in parts),
                  file=out)
    return table


def make_runs(args) -> int:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        done = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds), "--trace", str(args.trace)],
            stdout=subprocess.PIPE, text=True, check=False)
        last = (done.stdout.strip().splitlines() or [""])[-1]
        try:
            line = {"seed": seed, **json.loads(last)}
        except ValueError:
            line = {"seed": seed, "rc": done.returncode, "no_result": last[-500:]}
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"spread: seed {seed} rc {done.returncode} "
              + json.dumps(line.get("metrics", line)), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="*", help="files of result lines")
    ap.add_argument("--run", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seeds", help="comma-separated")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.run:
        if not (args.workload and args.seeds and args.out):
            ap.error("--run needs --workload, --seeds and --out")
        return make_runs(args)
    if not args.files:
        ap.error("name the files of result lines to read")
    report(read_sets(args.files))
    return 0


if __name__ == "__main__":
    sys.exit(main())
