"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration's file is the one its ``configs`` entry gives; the mix is
``bench/traffic/<traffic>.json``; each per-layer metric is read by
``bench/layer_metrics/<metric>.py``.  Adding a configuration, a mix, a
cell or a metric is adding files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    """One resolved cell: its configuration and traffic as data, and the
    metric entries of ``BENCHMARK.json`` that it reports."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{w['traffic']}.json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in reported and _reports(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def layer_reader(metric: str, bench_dir: str = BENCH_DIR
                 ) -> Callable[[dict], Optional[float]]:
    """``read(ctx)`` of ``bench/layer_metrics/<metric>.py``.

    ``ctx`` is what a traced run hands every reader: the program's tracer
    events of the window (``spans``), per-column iteration counts from the
    responses (``iters``), the window's change of the daemon's queue-wait
    histogram (``queue_wait``: sum, count), the recorded device solve calls
    (``calls``), per level of the hierarchy its vertex count and ELL
    blocks, ``(n, ((rows, width), ...))`` (``levels``), the plain device
    trace (``trace_plain``) and its summary (``trace``), the job count
    (``jobs``) and the chip's peaks (``peaks``).  A reader that finds
    nothing to read returns ``None`` and its metric is left out of the
    result.
    """
    path = os.path.join(bench_dir, "layer_metrics", f"{metric}.py")
    mod_name = "layer_metric_" + metric.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
