"""Find everything a cell needs by name: no registry, no import list.

``BENCHMARK.json`` names a cell's configuration and traffic mix; the
files of each live under ``benchmark/<kind>/<name>.<ext>`` and are
loaded by path, so a later PR adds a cell by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")


class BenchmarkError(RuntimeError):
    """The benchmark's own files disagree or name something missing."""


def read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchmarkError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[c['name'] for c in spec['workloads']]}")


def config_of(spec: dict, cell: dict) -> dict:
    """The configuration file of a cell, as it is run."""
    for entry in spec["configs"]:
        if entry["name"] == cell["config"]:
            with open(os.path.join(ROOT, entry["file"])) as f:
                return dict(json.load(f), name=entry["name"])
    raise BenchmarkError(f"cell {cell['name']!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")


def traffic_of(cell: dict) -> dict:
    return dict(read_json("traffic", cell["traffic"] + ".json"),
                name=cell["traffic"])


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, loaded by path (names
    carry ``-`` and ``.``, which an import statement cannot spell)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"{kind}/{name}.py does not exist")
    modname = "benchmark_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(spec: dict, section: str, cell_name: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it under ``workloads`` or list nothing."""
    return [m for m in spec[section]
            if cell_name in m.get("workloads", [cell_name])]


def sized(data: dict, rehearse: bool) -> dict:
    """A configuration or traffic file at the size it runs: as written,
    or with its ``rehearsal`` overrides for the CPU rehearsal."""
    out = {k: v for k, v in data.items() if k != "rehearsal"}
    if rehearse:
        out.update(data.get("rehearsal", {}))
    return out
