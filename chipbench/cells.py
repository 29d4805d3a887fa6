"""Find a cell and everything it names, by name.

``BENCHMARK.json`` lists the cells; a cell names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
each per-layer metric has a reader (``metrics/<name>.py``, the part of
the name before the first dot).  A cell, a configuration, a mix or a
metric is added with new files and entries alone.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config(name: str, base: str = HERE) -> dict:
    return load_json(os.path.join(base, "configs", f"{name}.json"))


def mix(name: str, base: str = HERE) -> dict:
    return load_json(os.path.join(base, "traffic", f"{name}.json"))


def cell(name: str, bench: dict | None = None, base: str = HERE):
    """(cell entry, configuration, mix) of the cell called ``name``."""
    bench = benchmark() if bench is None else bench
    for c in bench["workloads"]:
        if c["name"] == name:
            return c, config(c["config"], base), mix(c["traffic"], base)
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def metrics_for(name: str, bench: dict) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries the cell ``name`` reports."""
    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return ([m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])


def reader(metric: str, base: str = HERE):
    """The ``read`` function serving ``metric``."""
    stem = metric.split(".", 1)[0]
    path = os.path.join(base, "metrics", f"{stem}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
