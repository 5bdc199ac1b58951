"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix. Each
of those, each check and each metric lives in a file of its own:

* ``<configs[].file>``               the configuration as it is run (JSON);
* ``bench/models/<model>.py``        its plain reference and op/byte counts;
* ``bench/traffic/<traffic>.json``   the traffic mix's parameters;
* ``bench/checks/<cell>.json``       the limits of the cell's correctness check;
* ``bench/metrics/<metric>.py``      a reader, ``read(run) -> float | None``.

So a later cell, configuration, mix or metric is new files and new entries
in ``BENCHMARK.json``, never an edit.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic mix's parameters
    check: dict           # the correctness limits of this cell
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list
    root: Path


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    try:
        w = next(x for x in bench["workloads"] if x["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json") from None
    conf_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(root / conf_entry["file"]),
        traffic=_read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        check=_read_json(root / "bench" / "checks" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def metric_reader(name: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py`` (names may hold dots)."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_module(config: dict, serving: bool = False):
    """``bench.models.<model>`` (or its ``_serving`` adapter)."""
    name = config["model"] + ("_serving" if serving else "")
    return importlib.import_module(f"bench.models.{name}")
