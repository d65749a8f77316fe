"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one metric is a file found by name."""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Dict, List, Optional

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


def resolve_params(params: Dict[str, Any],
                   cfg: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """One entry a MEANING, whatever the family: a parameter a family or a
    configuration decides is not written into the metric's file but named
    there, and a cell of a new family brings the value in its own new files.

        {"family": "NAME"}       the attribute NAME of the cell's family module
                                 (a program's name in the trace, the operation
                                 that tells a call's rows). The family HAS to
                                 state it (``AttributeError`` otherwise: a
                                 forgotten name may not read another
                                 quantity in silence); stated as None it
                                 leaves the parameter out
        {"config_span": "key"}   last minus first of the pair the cell's
                                 configuration file holds under that key
                                 (``held_experts`` [0, 64]: 64 experts held)
    """
    out = {}
    for key, value in params.items():
        if isinstance(value, dict) and len(value) == 1:
            (how, name), = value.items()
            if how in ("family", "config_span") and cfg is None:
                raise KeyError(f"parameter {key!r} is decided by the cell's "
                               f"configuration, and the caller gave none")
            if how == "family":
                family = family_of(cfg)
                if not hasattr(family, name):
                    raise AttributeError(
                        f"{family.__name__} states no {name} (parameter {key!r}; "
                        f"families/__init__.py lists what a family states)")
                value = getattr(family, name)
                if value is None:
                    continue
            elif how == "config_span":
                value = cfg[name][-1] - cfg[name][0]
        out[key] = value
    return out


def metric_params(name: str, cfg: Dict[str, Any], root: str = ROOT,
                  manifest=None) -> Dict[str, Any]:
    """The parameters of the metric's file, resolved for the cell's family
    and configuration: what its reader is given, and what a runner asks of
    to know which operations a trace summary has to keep."""
    return resolve_params(metric_file(name, root, manifest).get("params", {}), cfg)


def read_metric(name: str, ctx: Dict[str, Any], root: str = ROOT, manifest=None):
    """The metric's reader over the run's observations, its parameters
    resolved by ``ctx["cfg"]``, the cell's configuration (a ``KeyError`` where
    a parameter needs it and the caller left it out). None where the reader
    finds nothing to read."""
    spec = metric_file(name, root, manifest)
    params = resolve_params(spec.get("params", {}), ctx.get("cfg"))
    return load_plugin("readers", spec["reader"]).read(ctx, params)


def read_metrics(manifest, workload: str, group: str, ctx: Dict[str, Any],
                 root: str = ROOT) -> Dict[str, Dict[str, Any]]:
    """Run each metric's reader over the run's observations. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_for(manifest, workload, group):
        value = read_metric(m["name"], ctx, root, manifest)
        if value is None:
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
