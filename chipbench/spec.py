"""Find a cell's files by the names in ``BENCHMARK.json``.

Under the benchmark's root directory (this one, unless a caller names
another):

- ``configs/<config>.json``: a model configuration as it is run;
- ``traffic/<traffic>.json``: a traffic mix, whose ``job`` names the
  driver ``jobs/<job>.py`` and whose other keys that driver reads;
- ``workloads/<cell>.json``: the cell's limits for ``correct``;
- ``metrics/<metric>.py``: the reader of one per-layer metric, a function
  ``read(ctx)`` that returns a number or None; ``metrics/<base>.py`` serves
  each ``<base>.<kind>`` that has no file of its own.

Adding a cell, a configuration, a mix or a per-layer metric adds files and
entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in the benchmark; known: "
                       f"{sorted(w['name'] for w in bench['workloads'])}")
    w = found[0]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=w["chips"],
                config=load_json(root / "configs" / f"{w['config']}.json"),
                traffic=load_json(root / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(root / "workloads" / f"{name}.json")["limits"],
                end_to_end=e2e, per_layer=per_layer, root=root)


def job_module(cell: Cell):
    return _load(cell.root / "jobs" / f"{cell.traffic['job']}.py",
                 f"chipbench_job_{cell.traffic['job']}")


def reference_module(cell: Cell):
    ref = cell.config["reference"]
    return _load(cell.root / "reference" / f"{ref}.py",
                 f"chipbench_reference_{ref}")


def metric_path(root: Path, name: str) -> Path:
    """``metrics/<name>.py``; for a metric split by the end-to-end metric
    it moves (``device_idle_share.train``), the reader of the name before
    the last ``.`` where the split has no reader of its own."""
    path = root / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = root / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def metric_reader(root: Path, name: str):
    path = metric_path(root, name)
    return _load(path, "chipbench_metric_" + path.stem.replace(".", "_")).read


def _load(path: Path, module_name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
