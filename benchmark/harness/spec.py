"""Finds what `BENCHMARK.json` names: a cell's configuration, traffic mix,
family and metric readers, each in a file of its own.

    benchmark/configs/<file named in BENCHMARK.json>   sizes and guarantee
    benchmark/traffic/<traffic>.json                   the mix's parameters
    benchmark/families/<config "family">.py            generator, system, reference
    benchmark/e2e/<metric>.py                          end-to-end readers
    benchmark/layers/<metric>.py                       per-layer readers

A later PR adds a cell, a mix or a metric by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file's contents
    traffic: dict       # the traffic file's contents
    family: object      # the family module
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, imported by path: names may hold
    dots and hyphens."""
    mod_name = f"benchmark.{kind}.{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(bench: dict, name: str, root: str = ROOT) -> Cell:
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _read_json(os.path.join(root, c["file"]))
    traffic = _read_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reported(m, name)]
    names = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if m["moves"] in names and _reported(m, name)]
    return Cell(name, w["chips"], config, traffic,
                load_module("families", config["family"]), e2e, layers)
