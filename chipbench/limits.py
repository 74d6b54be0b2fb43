#!/usr/bin/env python3
"""Reads what a cell's limits are set from: over the given seeds, the
numbers `correct` compares in sound runs of the program (the lower
reading) and, on the first ``--controls`` seeds, the same numbers of the
control — the reference in the precision below the configuration's — and of
the faults planted in the reference's place (the upper reading). Not a
cell, and not run by the driver; PERF.md section 2 holds what it printed.
Like the benchmark's command it runs on the chip only, and every line it
prints names the device.

    python3 chipbench/limits.py --workload <cell> --seeds 11,12,13 \
        --controls 3 --seconds 12
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import importlib

    from harness import cell as cell_mod, main as harness_main

    cell = cell_mod.load_cell(args.workload, ROOT)
    entry = importlib.import_module(
        "harness." + harness_main.ENTRIES[cell.config["entry"]])
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell.config["entry"] == "serve" and len(seeds) > 1:
        # One process per seed: after the server exits this process takes
        # the chip for the reference, and keeps it.
        import subprocess

        for i, seed in enumerate(seeds):
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 args.workload, "--seeds", str(seed), "--controls",
                 str(int(i < args.controls)), "--seconds", str(args.seconds)],
                check=False)
        return 0
    for i, seed in enumerate(seeds):
        got = entry.run(cell, seed, args.seconds, None, time.perf_counter(),
                        require_platform="tpu", control=i < args.controls)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "device": got["device"],
            "numbers": got["numbers"],
            "controls": got["controls"], "attempted": got["attempted"],
            "failed": got["failed"], "e2e": got["e2e"],
            "setup_s": got["setup_s"], "memory_peak_bytes": got["memory_peak_bytes"],
            "reference_s": got["counters"]["reference_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
