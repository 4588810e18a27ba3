"""A cell is resolved by name alone.

``BENCHMARK.json`` names the cell's configuration and traffic mix; everything
else follows from those names:

* ``configs/<config>.json``      — the sizes as run; its ``family`` picks
  ``families/<family>.py`` (the program's model) and that module's
  ``REFERENCE`` picks ``reference/<name>.py`` (the plain reference);
* ``traffic/<traffic>.json``     — the mix's parameters; its ``kind`` picks
  ``runners/<kind>.py``;
* ``limits/<cell>.json``         — the limits of the numbers ``correct`` compares;
* ``layer_metrics/<metric>.py``  — one reader per per-layer metric, ``read(ctx)``.

Adding a cell, a configuration, a mix or a per-layer metric is adding files
and appending entries; no file that is there needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = HERE):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the model's sizes as run
    mix: dict  # the traffic mix's parameters
    limits: dict  # {number compared: limit}
    end_to_end: list  # metric names this cell reports with --trace 0
    per_layer: list  # metric names this cell reports with --trace 1
    root: str = HERE

    @property
    def family(self):
        return load_module("families", self.config["family"], self.root)

    @property
    def reference(self):
        return load_module("reference", self.family.REFERENCE, self.root)

    @property
    def runner(self):
        return load_module("runners", self.mix["kind"], self.root)

    def layer_metric(self, name: str):
        return load_module("layer_metrics", name, self.root)


def _lists(metric: dict, cell: str, all_cells) -> bool:
    return cell in metric.get("workloads", all_cells)


def resolve(cell_name: str, repo_root: str = ROOT) -> Cell:
    """The cell's files, found from ``<repo_root>/BENCHMARK.json`` by name."""
    manifest = _json(repo_root, "BENCHMARK.json")
    root = os.path.join(repo_root, os.path.basename(HERE))
    entries = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in entries:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json ({sorted(entries)})")
    entry = entries[cell_name]
    config_files = {c["name"]: c["file"] for c in manifest["configs"]}
    return Cell(
        name=cell_name,
        chips=entry["chips"],
        config=_json(repo_root, config_files[entry["config"]]),
        mix=_json(root, "traffic", entry["traffic"] + ".json"),
        limits=_json(root, "limits", cell_name + ".json")["limits"],
        end_to_end=[m["name"] for m in manifest["end_to_end"] if _lists(m, cell_name, entries)],
        per_layer=[m["name"] for m in manifest["per_layer"] if _lists(m, cell_name, entries)],
        root=root,
    )
