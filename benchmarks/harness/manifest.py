"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one metric is a file found by name."""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def resolve_cell(manifest: Dict[str, Any], workload: str,
                 root: str = ROOT) -> Dict[str, Any]:
    cell = _by_name(manifest["workloads"], workload, "workload")
    config = _by_name(manifest["configs"], cell["config"], "config")
    base = os.path.join(root, manifest["paths"][0])
    return {
        "cell": cell,
        "config_entry": config,
        "config_file": os.path.join(root, config["file"]),
        "traffic_file": os.path.join(base, "traffic", cell["traffic"] + ".json"),
    }


def metrics_for(manifest: Dict[str, Any], workload: str, group: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e_here = set()
    for m in manifest["end_to_end"]:
        if "workloads" not in m or workload in m["workloads"]:
            e2e_here.add(m["name"])
    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e_here]
    out = []
    for m in manifest["per_layer"]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_here:
            out.append(m)
    return out


def metric_file(name: str, root: str = ROOT, manifest=None) -> Dict[str, Any]:
    manifest = manifest or load_manifest(root)
    path = os.path.join(root, manifest["paths"][0], "metrics", name + ".json")
    with open(path) as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """``generators/<name>.py``, ``readers/<name>.py``, ``runners/<name>.py``,
    ``families/<name>.py``: a new one is a new module in a directory the
    harness looks in."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def family_of(cfg: Dict[str, Any]):
    """The module of the configuration's model family: everything that depends
    on the family is asked of it (``families/__init__.py`` lists what)."""
    return load_plugin("families", cfg["family"])


def read_metrics(manifest, workload: str, group: str, ctx: Dict[str, Any],
                 root: str = ROOT) -> Dict[str, Dict[str, Any]]:
    """Run each metric's reader over the run's observations. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_for(manifest, workload, group):
        spec = metric_file(m["name"], root, manifest)
        reader = load_plugin("readers", spec["reader"])
        value = reader.read(ctx, spec.get("params", {}))
        if value is None:
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
