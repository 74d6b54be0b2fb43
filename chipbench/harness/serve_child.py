"""The server's process: the program's own ``serve`` entry
(``distkeras_tpu.run.serve_main``) with the configuration's flags, and two
things of the benchmark's beside it — the model's weights come from the
seed through the init hook, and a control thread on standard input starts
and stops the profiler and reads the device's memory counters, because
only the process that holds the chip can do either.

    python3 chipbench/harness/serve_child.py <config.json> <seed> <platform|-> <checkout>

Lines this process writes to standard output: the server's banner, control
replies ``{"chipbench": ...}``, and at SIGTERM the server's summary.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def control_loop() -> None:
    """``trace_start <dir>`` / ``trace_stop`` / ``memstats``, one per line."""
    import jax

    t_trace = None
    for raw in sys.stdin:
        words = raw.split()
        if not words:
            continue
        reply = {"cmd": words[0]}
        if words[0] == "trace_start":
            jax.profiler.start_trace(words[1])
            t_trace = time.perf_counter()
        elif words[0] == "trace_stop":
            reply["traced_s"] = time.perf_counter() - t_trace
            jax.profiler.stop_trace()
        elif words[0] == "memstats":
            reply["memory_stats"] = [d.memory_stats() or {}
                                     for d in jax.devices()]
        print(json.dumps({"chipbench": reply}), flush=True)


def main(argv) -> int:
    config_path, seed, platform, root = argv[0], int(argv[1]), argv[2], argv[3]
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.dirname(here))
    from harness.cell import ModelCode
    from harness.program import claim_devices

    with open(config_path) as f:
        config = json.load(f)
    claim_devices(1, None if platform == "-" else platform)
    code = ModelCode(config, root)

    from distkeras_tpu import run

    run.load_model = lambda _name, _kwargs: code.program.program_model(
        config, seed, code.reference)
    threading.Thread(target=control_loop, daemon=True).start()
    return run.serve_main([
        "--model", config["name"], "--port", "0",
        "--seed", str(seed % (2**31 - 1)), *config["serve_flags"]])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
