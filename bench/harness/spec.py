"""The benchmark's definition, resolved by name.

``BENCHMARK.json`` at the root of the checkout names every piece; each
lives in a file of its own under ``bench/``:

* a configuration: ``file`` of its entry (``bench/configs/<name>.json``);
* a traffic mix: ``bench/traffic/<mix>.json``, whose ``kind`` names the
  driver ``bench/harness/kinds/<kind>.py`` that reads it. The driver owns
  the kind's ``data(seed)``, its plain ``reference(mode)`` and the names
  of its ``CHECKS`` (:mod:`harness.mixes`); a new kind's data generator
  and reference are new modules beside ``bench/harness/reference.py``;
* a per-layer metric: ``bench/metrics/<metric>.py``, a module with one
  function ``read(ctx)`` that returns the reading or ``None``.

A later change adds a configuration, mix or metric by adding a file and
an entry, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every piece it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)
    readers: dict = field(default_factory=dict)      # name -> read(ctx)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(mix: str, bench: Path = BENCH) -> Path:
    return bench / "traffic" / f"{mix}.json"


def metric_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "metrics" / f"{name}.py"


def load_reader(name: str, bench: Path = BENCH):
    """The ``read`` function of metric ``name``'s reader module."""
    path = metric_path(name, bench)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``; raises KeyError or FileNotFoundError
    where an entry or a file is missing."""
    bm = benchmark(root)
    bench = root / "bench"
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    cell = Cell(name=workload, chips=int(w["chips"]), config=cfg,
                traffic=load_json(traffic_path(w["traffic"], bench)))
    cell.end_to_end = [m for m in bm["end_to_end"] if applies(m, workload)]
    cell.per_layer = [m for m in bm["per_layer"] if applies(m, workload)]
    cell.readers = {m["name"]: load_reader(m["name"], bench)
                    for m in cell.per_layer}
    return cell
