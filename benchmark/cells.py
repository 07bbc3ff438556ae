"""BENCHMARK.json, and the files a cell's names lead to. Nothing here
knows any configuration, traffic mix, generator, frame builder,
reference or metric by name: a later PR adds a file and an entry, and
edits nothing that is there."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration and traffic files read."""
    cell = dict(_by_name(bench["workloads"], workload, "workload"))
    cfg_entry = _by_name(bench["configs"], cell["config"], "config")
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cell["config_data"] = json.load(f)
    with open(_find(bench, os.path.join("traffic", cell["traffic"] + ".json"), root)) as f:
        cell["traffic_data"] = json.load(f)
    return cell


def _find(bench: dict, relative: str, root: str) -> str:
    """The file `relative` under one of the benchmark's own directories."""
    for d in bench["paths"]:
        path = os.path.join(root, d, relative)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"{relative} under none of {bench['paths']}")


def load_scopes(bench: dict, config: str, root: str = ROOT):
    """The layer scopes that `scopes/<config>.json` declares for the
    configuration's step program, or None where it has no such file."""
    try:
        path = _find(bench, os.path.join("scopes", config + ".json"), root)
    except FileNotFoundError:
        return None
    with open(path) as f:
        return list(json.load(f)["scopes"])


def metrics_for(bench: dict, workload: str, group: str):
    """The metric entries of `group` (end_to_end | per_layer) that this
    cell reports: all without a `workloads` key, and those that list it."""
    return [m for m in bench[group] if "workloads" not in m or workload in m["workloads"]]


def load_module(bench: dict, kind: str, name: str, root: str = ROOT):
    """The module `<kind>/<name>.py` under one of the benchmark's
    directories: a metric's reader (`metrics`), a traffic mix's generator
    (`generators`) or frame builder (`wire`), a configuration's plain
    reference (`references`). Loaded once for each file."""
    path = _find(bench, os.path.join(kind, name + ".py"), root)
    if path not in _modules:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


_modules: dict = {}


def load_reader(bench: dict, name: str, root: str = ROOT):
    """A metric's own reader: `read(run) -> float | None`."""
    return load_module(bench, "metrics", name, root).read
