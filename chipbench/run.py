#!/usr/bin/env python3
"""chipbench — the benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up (set-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object as
the last line of standard output. There is no CPU mode and no fallback:
without a ``tpu`` device, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "distkeras_tpu")):
        sys.exit("chipbench: no distkeras_tpu/ beside chipbench/ — there is "
                 "no system to measure here")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    from harness import main as harness_main

    line = harness_main.run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), T_PROCESS_START, ROOT)
    harness_main.print_line(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
