"""Finds what ``BENCHMARK.json`` names, by name, under a checkout's root:

* a configuration: the file its ``configs`` entry gives, and its plain
  reference ``bench/configs/<reference>.py``;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, a module with
  ``LAYER``, ``UNIT``, ``MOVES`` and ``read(run) -> float | None``.

Adding one of these is adding files and entries; no harness code changes.
"""
from __future__ import annotations

import importlib.util
import json
import os


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in man['workloads']]}")


def config(root: str, man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(root: str, conf: dict):
    name = conf["reference"]
    return _module(os.path.join(root, "bench", "configs", f"{name}.py"),
                   f"bench_reference_{name}")


def metric(root: str, name: str):
    return _module(os.path.join(root, "bench", "metrics", f"{name}.py"),
                   "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def end_to_end(man: dict, workload: str) -> list[dict]:
    """The cell's end-to-end metrics: those with no ``workloads`` key and
    those that list it."""
    return [m for m in man["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer(man: dict, workload: str) -> list[dict]:
    """The cell's per-layer metrics: those that list it, and those with no
    ``workloads`` key whose ``moves`` metric the cell reports."""
    reported = {m["name"] for m in end_to_end(man, workload)}
    return [m for m in man["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in reported)]
