"""Resolution by name and the result line: everything about a cell is data.

A cell in BENCHMARK.json names its configuration, its traffic mix and its
chips. The configuration is `benchmark/configs/<config>.json`, the mix is
`benchmark/traffic/<traffic>.json`, the mix's `kind` is the runner module
`benchmark/runners/<kind>.py`, a per-layer metric is
`benchmark/layer_metrics/<name>.json` and its `reader` is the module
`benchmark/readers/<reader>.py`. Adding any of them is adding a file and an
entry; nothing here knows a cell, a model or a metric by name.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class Unresolved(FileNotFoundError):
    """A name in BENCHMARK.json that no file answers to."""


def _load_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise Unresolved(f"{what}: missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"), "the benchmark")


def _module(package: str, name: str, what: str):
    path = os.path.join(BENCH_DIR, package, f"{name}.py")
    if not os.path.exists(path):
        raise Unresolved(f"{what}: missing file "
                         f"{os.path.relpath(path, ROOT)}")
    return importlib.import_module(f"benchmark.{package}.{name}")


def resolve_cell(bench: dict, cell_name: str) -> dict:
    """The cell's entry with its configuration, traffic mix and runner
    module; Unresolved names the missing path."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise Unresolved(f"no workload {cell_name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise Unresolved(f"workload {cell_name!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")
    entry = configs[cell["config"]]
    config = _load_json(os.path.join(ROOT, entry["file"]),
                        f"configuration {entry['name']!r}")
    traffic = _load_json(
        os.path.join(BENCH_DIR, "traffic", f"{cell['traffic']}.json"),
        f"traffic mix {cell['traffic']!r}")
    runner = _module("runners", traffic["kind"],
                     f"runner kind {traffic['kind']!r} of mix "
                     f"{cell['traffic']!r}")
    return {"cell": cell, "config": config, "traffic": traffic,
            "runner": runner}


def metrics_of_cell(bench: dict, group: str, cell_name: str) -> list:
    """The entries of `end_to_end` or `per_layer` this cell reports: those
    without a `workloads` key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_layer_metric(name: str) -> tuple:
    """(definition, reader module) of one per-layer metric."""
    spec = _load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                   f"{name}.json"),
                      f"per-layer metric {name!r}")
    reader = _module("readers", spec["reader"],
                     f"reader {spec['reader']!r} of metric {name!r}")
    return spec, reader


def read_layer_metrics(bench: dict, cell_name: str, obs: dict) -> dict:
    """{name: {"value", "unit"}} for the per-layer metrics of this cell. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in metrics_of_cell(bench, "per_layer", cell_name):
        spec, reader = load_layer_metric(m["name"])
        value = reader.read(obs, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(bench: dict, cell_name: str, values: dict) -> dict:
    out = {}
    for m in metrics_of_cell(bench, "end_to_end", cell_name):
        if m["name"] not in values:
            raise KeyError(f"the runner of {cell_name!r} reported no "
                           f"{m['name']!r} (has: {sorted(values)})")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def work_dir(cell_name: str) -> str:
    """Where a run leaves what it generates (token files, the trainer's
    `runs/`, traces): inside the checkout, at a fixed path, gitignored."""
    d = os.path.join(ROOT, ".bench_work", cell_name)
    os.makedirs(d, exist_ok=True)
    return d


def seed31(seed: int) -> int:
    """`--seed` goes a little over 2**31; the program's keys and loaders
    take 31 bits."""
    return int(seed) % (2 ** 31 - 1)
