"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a configuration, a mix or a metric: a cell names a
configuration and a traffic mix, and they are the files
``configs/<config>.json`` and ``traffic/<mix>.json`` under the benchmark's
directory; a metric ``<name>[.<split>]`` is read by ``metrics/<name>.py``
(the split suffix only says which end-to-end metric the reading moves).
A later cell, mix or metric is added as files and a ``workloads`` entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SETUP = "setup_s"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    cfg: dict
    mix: dict
    end_to_end: List[dict]          # this cell's end-to-end metric entries
    per_layer: List[dict]           # this cell's per-layer metric entries


def load_benchmark(repo: str = REPO) -> dict:
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1] if kind == 'configs' else kind}"
                                f" file {path} for {name!r}")
    with open(path) as f:
        return json.load(f)


def resolve(name: str, bench: dict, root: str = HERE) -> Cell:
    """The cell ``name`` of ``bench`` with its configuration, mix and
    metric entries, read from ``root``."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; known: {sorted(entries)}")
    w = entries[name]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"],
                cfg=load_json(root, "configs", w["config"]),
                mix=load_json(root, "traffic", w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def reader_path(metric: str, root: str = HERE) -> str:
    return os.path.join(root, "metrics", f"{metric.split('.', 1)[0]}.py")


def reader(metric: str, root: str = HERE) -> Callable:
    """``read(view) -> float | None`` of the metric's reader file."""
    path = reader_path(metric, root)
    base = metric.split(".", 1)[0]
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{base}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no reader {path} for metric {metric!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def readers(metrics: List[dict], root: str = HERE) -> Dict[str, Callable]:
    return {m["name"]: reader(m["name"], root) for m in metrics
            if m["name"] != SETUP}
