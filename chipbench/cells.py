"""Finds everything a cell is made of, by the names in BENCHMARK.json.

A cell (`workloads` entry) names a configuration and a traffic mix:

  chipbench/configs/<config>.json   the deployment's sizes and settings
  chipbench/traffic/<traffic>.json  the mix, read by `traffic.py`
  chipbench/metrics/<metric>.py     the reader of a per-layer metric; a
                                    metric `<kind>.<suffix>` without a
                                    file of its own is read by
                                    metrics/<kind>.py
  chipbench/costs/<kernel>.py       operations and bytes of one kernel,
                                    for each kernel the configuration's
                                    `costs` lists
  chipbench/peaks.json              the chip's peaks, by device_kind

Adding a cell adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.relative_to(ROOT)} for "
                       f"{name!r}")
    return json.loads(path.read_text())


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def _module(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            return _module(path)
    raise KeyError(f"no reader chipbench/metrics/{name}.py or "
                   f"{name.split('.')[0]}.py for metric {name!r}")


def costs(kernels: List[str]) -> Dict[str, ModuleType]:
    """The cost module of each kernel named, by kernel name."""
    out = {}
    for k in kernels:
        path = HERE / "costs" / f"{k}.py"
        if not path.is_file():
            raise KeyError(f"no cost file {path.relative_to(ROOT)} for "
                           f"kernel {k!r}")
        out[k] = _module(path)
    return out


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table['devices'])})") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]        # this cell's end-to-end metrics
    per_layer: List[dict]         # this cell's per-layer metrics


def _applies(metric: dict, cell: str, reported: Optional[set]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = benchmark() if bench is None else bench
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {[w['name'] for w in bench['workloads']]})")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name, int(entry["chips"]), config(entry["config"]),
                traffic(entry["traffic"]), e2e, per_layer)
