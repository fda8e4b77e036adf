"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's entry
gives its file; the mix is ``bench/traffic/<traffic>.json``; its ``kind``
names the runner ``bench/harness/kinds/<kind>.py``; the limits of the
correctness comparison are ``bench/limits/<cell>.json``; a per-layer metric
is read by ``bench/metrics/<metric>.py``; a kernel's operation and byte
counts are ``bench/kernels/<kernel>.py``.  Adding any of these is adding
files and entries: nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import NamedTuple

class Cell(NamedTuple):
    name: str
    chips: int
    config: dict          # the configuration file's contents
    mix: dict             # the traffic file's contents
    limits: dict          # name -> limit of each number compared
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list
    root: str


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", None) in e2e_names if "moves" in entry \
        else True


def find(root: str, *parts: str) -> str:
    return os.path.join(root, "bench", *parts)


def resolve(root: str, cell_name: str) -> Cell:
    """The cell ``cell_name`` of ``<root>/BENCHMARK.json``; configuration
    files are relative to ``root``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[cell_name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, cfgs[w["config"]]["file"]))
    mix = _read_json(find(root, "traffic", w["traffic"] + ".json"))
    limits = _read_json(find(root, "limits", cell_name + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell_name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, cell_name, e2e_names)]
    return Cell(cell_name, int(w["chips"]), config, mix, limits, e2e,
                per_layer, root)


def _module(root: str, kind: str, name: str):
    return load_module(find(root, *kind.split("/"), name + ".py"),
                       f"bench_{kind.replace('/', '_')}_"
                       + name.replace(".", "_").replace("-", "_"))


def runner(root: str, kind: str):
    return _module(root, "harness/kinds", kind)


def metric_reader(root: str, name: str):
    return _module(root, "metrics", name)


def kernel_count(root: str, name: str):
    return _module(root, "kernels", name)


def peaks(root: str, device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = _read_json(find(root, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
