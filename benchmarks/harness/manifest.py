"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A later PR adds a configuration, a traffic mix, a cell or a per-layer
metric with new files and a manifest entry; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration's file, as it is run
    traffic: dict           # the traffic mix's parameters
    end_to_end: tuple       # manifest entries reported by this cell
    per_layer: tuple


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest_file: str = "BENCHMARK.json",
              root: str = ROOT) -> Cell:
    manifest = load_json(os.path.join(root, manifest_file))
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in {manifest_file} "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        root, os.path.basename(BENCH_DIR), "traffic",
        w["traffic"] + ".json"))
    e2e = tuple(m for m in manifest["end_to_end"] if _in_cell(m, name))
    moved = {m["name"] for m in e2e}
    layer = tuple(m for m in manifest["per_layer"]
                  if _in_cell(m, name) and m["moves"] in moved)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)
