"""Find a cell's pieces by name: ``BENCHMARK.json`` at the root, and under
``bench/`` the configuration file, its architecture module
``arch/<model_type>.py``, ``traffic/<traffic>.json``, ``limits/<cell>.json``
and one reader ``metrics/<metric>.py`` per metric.  A cell, a metric or an
architecture is added by adding such files; nothing here names one."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

from bench.model import load_config
from bench.traffic import load_traffic

REPO = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict            # the configuration file, with "name"
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list
    root: Path


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = REPO) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    conf = dict(load_config(root / c["file"]), name=c["name"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(
        name=name, chips=int(w["chips"]), conf=conf,
        traffic=load_traffic(root / "bench" / "traffic"
                             / f"{w['traffic']}.json"),
        limits=json.loads((root / "bench" / "limits"
                           / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer, root=root)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(cell: Cell, metric: str):
    """The reader module of ``metric``: ``bench/metrics/<metric>.py``."""
    return _load(cell.root / "bench" / "metrics" / f"{metric}.py",
                 f"bench_metric_{metric.replace('.', '_')}")


def arch_module(cell: Cell):
    """The architecture module of the cell's configuration:
    ``bench/arch/<model_type>.py`` under the cell's root, named by the
    file's published ``model_type``.  There is no fallback: a
    ``model_type`` without a module is an error that names the path."""
    kind = cell.conf.get("model_type")
    if not isinstance(kind, str) or not re.fullmatch(r"[A-Za-z0-9_]+", kind):
        raise ValueError(f"configuration {cell.conf.get('name')!r}: "
                         f"model_type {kind!r} names no architecture module")
    rel = f"bench/arch/{kind}.py"
    path = cell.root / rel
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {cell.conf.get('name')!r}: no architecture "
            f"module {rel} for model_type {kind!r} (looked for {path})")
    return _load(path, f"bench_arch_{kind}")
