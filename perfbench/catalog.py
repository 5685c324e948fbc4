"""Discovery by name: every configuration, traffic mix, driver and
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it.

    configs/<name>.json     a deployment: sizes, guarantees, source
    traffic/<name>.json     a traffic mix: parameters for its driver
    drivers/<name>.py       the general generator a traffic mix names
    metrics/<name>.py       a per-layer metric: ``read(ctx)`` returns a
                            number, or None where it finds nothing

So a later change adds a cell, a configuration, a mix or a metric as new
files, and edits none.  An unknown name is an error.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict

ROOT = os.path.dirname(os.path.abspath(__file__))

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(LookupError):
    """No file for this name."""


def _path(root: str, kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise UnknownName(f"{name!r} is not a valid {kind} name")
    path = os.path.join(root, kind, name + ext)
    if not os.path.isfile(path):
        raise UnknownName(f"no {kind} named {name!r} ({path})")
    return path


def load_json(kind: str, name: str, root: str = ROOT) -> Dict[str, Any]:
    with open(_path(root, kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    path = _path(root, kind, name, ".py")
    mod_name = "perfbench_" + kind + "_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def benchmark(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise UnknownName(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict[str, Any], cell_name: str, group: str):
    """The metrics of ``group`` ("end_to_end" or "per_layer") that the
    cell reports: those that list it, and those that list no cells but
    move an end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
