"""What a cell is, read from files found by name.

``BENCHMARK.json`` (at the checkout root) lists configurations, traffic
mixes, cells and metrics.  Everything that belongs to one of them sits in a
file of its own under ``bench/``:

  configs/<config>.json          the deployment: sizes, engine settings,
                                 corpus generator, reference, limits
  traffic/<mix>.json             the traffic mix, read by one generator
  traffic/<mix>.<config>.json    optional: what the mix sets for one
                                 configuration only (its offered rate)
  metrics/<metric>.py            one reader per metric: ``read(ctx)``; a
                                 metric split by mix (``dispatch_ms.poisson``)
                                 falls back to its quantity's reader
                                 (``metrics/dispatch_ms.py``)
  references/<name>.py           a configuration's plain reference
  peaks.json                     the chip's published peaks by device kind

So a later cell, configuration or metric is new files and new entries,
with no edit to a file that is already there.

Layout.  A configuration is served by one process on one chip, unless its
file has ``"replicas": N`` with N > 1: then N one-chip replicas, each
building the same engine from the same seed, serve behind the program's
router.  The run's own process is replica 0 and holds chip 0; replicas
1..N-1 are processes of their own, one chip each; the router is the
program's launcher (``repro.launch.serve --serve-http --role router``) in a
process that holds no chip.  Such a cell asks for N chips.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    run_seconds: int


def _applies(metric: Dict, cell: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(name: str, root: str = ROOT, bench: str = BENCH) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    config = _json(os.path.join(bench, "configs", f"{w['config']}.json"))
    n = int(config.get("replicas", 1))
    if n > 1 and int(w["chips"]) != n:
        raise ValueError(f"workload {name!r} asks for {w['chips']} chips, "
                         f"but its configuration {w['config']!r} is served "
                         f"by {n} one-chip replicas")
    traffic = _json(os.path.join(bench, "traffic", f"{w['traffic']}.json"))
    own = os.path.join(bench, "traffic", f"{w['traffic']}.{w['config']}.json")
    if os.path.isfile(own):
        traffic.update(_json(own))
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, [])]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer, run_seconds=int(spec["run_seconds"]))


def reader(metric: str, bench: str = BENCH) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``, or else of
    ``metrics/<quantity>.py``, the quantity being the name up to its first
    dot."""
    name = metric
    if not os.path.isfile(os.path.join(bench, "metrics", f"{name}.py")):
        name = metric.split(".")[0]
    path = os.path.join(bench, "metrics", f"{name}.py")
    return _module(path, "bench_metric_" + name.replace(".", "_")).read


def reference(name: str, bench: str = BENCH):
    """The module ``references/<name>.py``."""
    return _module(os.path.join(bench, "references", f"{name}.py"),
                   "bench_reference_" + name)


def peaks(device_kind: str, bench: str = BENCH) -> Dict:
    table = _json(os.path.join(bench, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
