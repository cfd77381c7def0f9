"""What a cell is, read from data: ``BENCHMARK.json`` at the repo root and
the files it names by name under ``chipbench/``.

- ``configs/<config>.json``: the configuration as it runs (the entry's
  ``file``), naming the program's architecture id (``arch``) and its plain
  reference (``reference`` -> ``reference/<reference>.py``);
- ``traffic/<traffic>.json``: the traffic mix's parameters;
- ``workloads/<cell>.json``: the cell's correctness check (sample size and
  the limit of each number compared, with the readings it was set from);
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``.

Adding a cell or a metric adds files and entries; no existing file
changes."""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # configuration file, as run
    traffic: dict       # traffic file
    check: dict         # workloads/<cell>.json


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _entry(items: list[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    w = _entry(bench["workloads"], name, "workload")
    c = _entry(bench["configs"], w["config"], "config")
    check = json.loads((HERE / "workloads" / f"{name}.json").read_text())
    if (check["config"], check["traffic"]) != (w["config"], w["traffic"]):
        raise ValueError(f"workloads/{name}.json names "
                         f"{check['config']}/{check['traffic']}, "
                         f"BENCHMARK.json {w['config']}/{w['traffic']}")
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((ROOT / c["file"]).read_text()),
        traffic=json.loads(
            (HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        check=check)


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; a metric with ``workloads`` only in those."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    return _load_module(path, f"chipbench_metric_{metric}").read


def reference(name: str):
    """The plain reference module ``reference/<name>.py``."""
    path = HERE / "reference" / f"{name}.py"
    return _load_module(path, f"chipbench_reference_{name}")
