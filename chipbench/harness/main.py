"""One run of one cell: find its files, run its entry kind, reduce the
trace, read the per-layer metrics, decide ``correct``, and build the
contract's result line."""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys

from . import cell as cell_mod, compare

# A configuration's "entry" names one of these modules; each has
# run(cell, seed, seconds, trace_dir, t_process_start) -> dict.
ENTRIES = {"train": "train_entry", "serve": "serve_entry"}


def work_dir(root: str) -> str:
    """Scratch inside the checkout (git-ignored), at a fixed path."""
    path = os.path.join(root, "chipbench", ".work")
    os.makedirs(path, exist_ok=True)
    return path


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, root: str = cell_mod.ROOT,
             require_platform: str | None = "tpu") -> dict:
    """The result line of one run, as a dict. ``require_platform=None`` is
    for rehearsals on the CPU through this API; the command line always
    requires ``tpu``."""
    cell = cell_mod.load_cell(name, root)
    entry = importlib.import_module(
        "." + ENTRIES[cell.config["entry"]], __package__)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(work_dir(root), "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    got = entry.run(cell, seed, seconds, trace_dir, t_process_start,
                    require_platform=require_platform)
    device = got["device"]
    if require_platform and (device["platform"] != require_platform
                             or device["count"] < cell.chips):
        raise SystemExit(f"chipbench: cell {name} needs {cell.chips} "
                         f"{require_platform} device(s), found {device}")
    device = {**device, "memory_peak_bytes": got["memory_peak_bytes"]}
    correct, compared = compare.judge(got["numbers"], cell.limits)
    correct = correct and got["failed"] == 0
    e2e = {**got["e2e"], "setup_s": got["setup_s"]}
    line = {"correct": correct, "attempted": got["attempted"],
            "failed": got["failed"]}
    if not trace:
        missing = [k for k in cell.end_to_end if k not in e2e]
        if missing:
            raise SystemExit(f"chipbench: the window gave nothing to read "
                             f"{missing} from ({got['attempted']} attempted)")
        line["metrics"] = {k: {"value": e2e[k], "unit": unit}
                           for k, unit in cell.end_to_end.items()}
    else:
        from . import trace_reduce

        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        summary = trace_reduce.reduce(files[-1], got["traced_s"],
                                      cell.chips) if files else None
        ctx = {"cell": {"name": cell.name, "chips": cell.chips},
               "config": cell.config, "traffic": cell.traffic,
               "peaks": cell_mod.load_peaks(cell.peaks_path, device["kind"]),
               "window_s": got["window_s"], "e2e": e2e,
               "counters": got["counters"], "spans": got["spans"],
               "trace": summary,
               "math": cell_mod.ModelCode(cell.config, root).model_math}
        line["metrics"] = cell_mod.read_layer_metrics(cell, ctx)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = summary["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    line["device"] = device
    if got.get("split"):  # the run by where its numbers were taken
        line["split"] = got["split"]
    line["compared"] = compared  # last: each number beside its limit
    return line


def print_line(line: dict) -> None:
    for name, c in line["compared"].items():
        print(f"chipbench: compared {name} = {c['value']} (limit "
              f"{c['limit']})", file=sys.stderr)
    print(f"chipbench: correct = {line['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
