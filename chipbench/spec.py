"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything is found by name: a cell names its configuration and its
traffic mix, a configuration entry names its file, a traffic mix is
``<benchmark dir>/traffic/<name>.json`` and a per-layer metric is read by
``<benchmark dir>/metrics/<name>.py``.  Adding a configuration, a traffic
mix or a metric is adding files and entries; no code lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Callable, List

#: the benchmark's own directory, and the checkout root above it
BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file's contents
    traffic: dict       # the traffic file's contents
    end_to_end: List[dict]   # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    bench_dir: pathlib.Path


class SpecError(Exception):
    """BENCHMARK.json does not define what a run asks for."""


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files read."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = _by_name(bench["workloads"], name, "workload")
    entry = _by_name(bench["configs"], cell["config"], "configuration")
    bench_dir = root / BENCH_DIR.name
    traffic = bench_dir / "traffic" / f"{cell['traffic']}.json"
    if not traffic.is_file():
        raise SpecError(f"no traffic file {traffic}")
    mix = json.loads(traffic.read_text())
    if (mix.get("loop"), mix.get("clients")) != ("closed", 1):
        raise SpecError(f"{traffic}: the harness drives one closed-loop "
                        f"client, not loop={mix.get('loop')!r} "
                        f"clients={mix.get('clients')!r}")
    return Cell(
        name=name, chips=int(cell["chips"]),
        config=json.loads((root / entry["file"]).read_text()),
        traffic=mix,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir)


def metric_reader(bench_dir: pathlib.Path, name: str) -> Callable:
    """``read`` of ``<bench_dir>/metrics/<name>.py``: takes a
    ``harness.Readings`` and returns the metric's value, or None where
    the run has nothing for it to read."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_peaks(bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    return json.loads((pathlib.Path(bench_dir) / "peaks.json").read_text())
