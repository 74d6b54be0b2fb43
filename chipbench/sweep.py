#!/usr/bin/env python3
"""One-off rate sweep for an open-loop traffic file: finds the highest rate
the system sustains, so that the cell's fixed rate can be set to four
fifths of it. Not a cell, and not run by the driver.

    python3 chipbench/sweep.py --config gpt2-small --traffic chat_open \
        --rates 1,1.75,2.5,3.25,4 --seconds 30 --seed 1

One server, and one process, per rate (each run is the cell's own run at
another rate; the process that checks a run's answers keeps the chip), one
line per rate: offered and completed requests, tails, and whether the
backlog grew over the second half of the window. A rate is SUSTAINED when
every due request completed and the mean time-to-first-token of the second
half is under twice the first half's. It runs on the chip only, and every
line names the device.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="chipbench/configs/<name>.json")
    ap.add_argument("--traffic", required=True, help="chipbench/traffic/<name>.json")
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    from harness import cell as cell_mod, serve_entry
    from harness.traffic import percentile

    # The pair need not be a cell of BENCHMARK.json yet: the sweep comes first.
    config_path = os.path.join(HERE, "configs", args.config + ".json")
    with open(config_path) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", args.traffic + ".json")) as f:
        traffic = json.load(f)
    base = cell_mod.Cell(
        name=f"{args.config}.{args.traffic}", chips=1, config_name=args.config,
        config=config, config_path=config_path, traffic_name=args.traffic,
        traffic=traffic, end_to_end={}, per_layer=(), limits={},
        peaks_path=os.path.join(HERE, "peaks.json"), root=ROOT)
    if base.traffic["kind"] != "open":
        sys.exit("sweep.py: the cell's traffic is not open-loop")
    rates = [float(r) for r in args.rates.split(",")]
    if len(rates) > 1:
        import subprocess

        for rate in rates:
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--config",
                 args.config, "--traffic", args.traffic, "--rates", str(rate),
                 "--seconds", str(args.seconds), "--seed", str(args.seed)],
                check=False)
        return 0
    for rate in rates:
        cell = dataclasses.replace(
            base, traffic={**base.traffic, "rate_per_s": rate}, limits={})
        got = serve_entry.run(cell, args.seed, args.seconds, None,
                              time.perf_counter(), require_platform="tpu")
        ttft = got["spans"]["ttft_s"]
        half = len(ttft) // 2
        first, second = ttft[:half] or [0.0], ttft[half:] or [0.0]
        growing = sum(second) / len(second) > 2 * sum(first) / len(first)
        print(json.dumps({
            "rate_per_s": rate, "device": got["device"],
            "offered": got["attempted"],
            "failed": got["failed"],
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": got["e2e"].get("ttft_p95_ms"),
            "itl_p95_ms": got["e2e"].get("itl_p95_ms"),
            "tokens_per_s": got["e2e"]["serve_tokens_per_s"],
            "ttft_mean_first_half_ms": 1e3 * sum(first) / len(first),
            "ttft_mean_second_half_ms": 1e3 * sum(second) / len(second),
            "loadgen_lag_p95_ms": 1e3 * percentile(
                got["spans"]["loadgen_lag_s"], 95),
            "sustained": got["failed"] == 0 and not growing,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
