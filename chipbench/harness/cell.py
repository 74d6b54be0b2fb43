"""Finding a cell's files by the names in BENCHMARK.json. Nothing here
knows a cell, a configuration, a traffic mix or a metric by name: a later
PR adds files and entries, and edits none that are there. Imports no JAX."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict       # chipbench/configs/<config>.json, as it is run
    config_path: str
    traffic_name: str
    traffic: dict      # <bench_dir>/traffic/<traffic>.json
    end_to_end: dict   # name -> unit of the end-to-end metrics it reports
    per_layer: tuple   # (name, reader_path) of its per-layer metrics
    limits: dict       # name -> limit of each number `correct` compares
    peaks_path: str
    root: str


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_peaks(path: str, device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = _read_json(path)["device_kinds"]
    if device_kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{device_kind!r} in {path} (known: {sorted(table)})")
    return table[device_kind]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    try:
        work = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(
            f"chipbench: no workload {name!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in bench['workloads']]})") from None
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    config_path = os.path.join(root, conf["file"])
    config = _read_json(config_path)
    # A configuration's traffic, readers and peaks live beside its configs/.
    bench_dir = os.path.dirname(os.path.dirname(config_path))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      work["traffic"] + ".json"))

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = {m["name"]: m["unit"] for m in bench["end_to_end"] if reports(m)}
    layers = tuple(
        (m["name"], os.path.join(bench_dir, "layer_metrics", m["name"] + ".py"))
        for m in bench["per_layer"] if reports(m) and m["moves"] in e2e)
    # Limits of `correct`: the traffic file's, the configuration's for this
    # traffic, or a file of the cell's own (for a pair of files that are
    # both there already and may not be edited).
    own = os.path.join(bench_dir, "limits", name + ".json")
    limits = {**traffic.get("limits", {}),
              **config.get("limits", {}).get(work["traffic"], {}),
              **(_read_json(own) if os.path.isfile(own) else {})}
    return Cell(name=name, chips=int(work["chips"]), config_name=work["config"],
                config=config, config_path=config_path, traffic_name=work["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=layers, limits=limits,
                peaks_path=os.path.join(bench_dir, "peaks.json"), root=root)


def _load_file(path: str, label: str):
    """The module in the file at ``path``, loaded under a name of its own
    (never through ``sys.path``: two files may share a base name)."""
    name = "chipbench_" + "".join(c if c.isalnum() else "_" for c in label)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class ModelCode:
    """A configuration's model as code, found by the directory its file
    names under ``model.code`` (relative to the checkout): ``program.py``
    (``program_model(config, seed, reference)``: the program's own model
    with the seed's weights), ``reference.py`` (the plain reference and its
    control) and ``model_math.py`` (operations and bytes from shapes). Each
    is loaded when first asked for, so a process that must not import JAX
    yet does not."""

    PARTS = ("program", "reference", "model_math")

    def __init__(self, config: dict, root: str = ROOT):
        self.directory = os.path.join(root, config["model"]["code"])
        if not os.path.isdir(self.directory):
            raise SystemExit(f"chipbench: configuration {config.get('name')!r}"
                             f" names no model code at {self.directory}")

    def __getattr__(self, part: str):
        if part not in self.PARTS:
            raise AttributeError(part)
        module = _load_file(os.path.join(self.directory, part + ".py"),
                            self.directory + "_" + part)
        setattr(self, part, module)
        return module


def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Run each of the cell's per-layer readers (``read(ctx) -> number or
    None``). A reader that finds nothing to read returns None and its
    metric is left out of the line."""
    out = {}
    for name, path in cell.per_layer:
        module = _load_file(path, "layer_metric_" + name)
        value = module.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": module.UNIT}
    return out
