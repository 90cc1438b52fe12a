"""Finding a cell's files by name.

``BENCHMARK.json`` at the checkout root lists the cells.  Everything
that belongs to one configuration, traffic mix, cell or per-layer metric
is a file of its own, found by its name:

    bench/configs/<config>.json     published config + program mapping
    bench/traffic/<traffic>.json    round parameters and data sizes
    bench/limits/<workload>.json    limits of the correctness comparison
    bench/metrics/<metric>.py       reader of one per-layer metric
    bench/frameworks/<name>.py      a framework's wrappers and reference
    bench/peaks.json                device peaks, keyed by device kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Callable, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"bad name {name!r}: 1-64 of A-Z a-z 0-9 _ . -, "
                         "not starting with . or -")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ValueError(f"bad unit {unit!r}: 1-16 of A-Z a-z 0-9 _ / % . -")
    return unit


def _read_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str = ""
    moves: str = ""


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files read."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json in {root}")
    return _read_json(path)


def _metrics(entries, workload: str = None) -> List[Metric]:
    """The metrics of ``workload``: those whose ``workloads`` key names
    it, and those with no such key (every cell)."""
    out = []
    for e in entries:
        if workload and workload not in e.get("workloads", [workload]):
            continue
        out.append(Metric(check_name(e["name"]), check_unit(e["unit"]),
                          e["better"], e["source"],
                          e.get("layer", ""), e.get("moves", "")))
    return out


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload`` with its config, traffic and limits."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=check_name(workload), chips=int(w["chips"]),
        config_name=check_name(w["config"]),
        traffic_name=check_name(w["traffic"]),
        config=_read_json(root / cfg_entry["file"]),
        traffic=read_traffic(w["traffic"], root),
        limits=read_limits(workload, root),
        end_to_end=_metrics(bench["end_to_end"], workload),
        per_layer=_metrics(bench["per_layer"], workload))


def bench_file(kind: str, name: str, suffix: str,
               root: pathlib.Path = ROOT) -> pathlib.Path:
    path = root / "bench" / kind / f"{check_name(name)}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return path


def read_traffic(name: str, root: pathlib.Path = ROOT) -> Dict:
    t = _read_json(bench_file("traffic", name, ".json", root))
    n_test, eval_batch = t["data"]["n_test"], t["eval_batch"]
    if n_test % eval_batch:
        # the program evaluates whole batches only; a remainder would
        # leave rows out that the reference counts
        raise ValueError(f"traffic {name!r}: n_test {n_test} is not a "
                         f"multiple of eval_batch {eval_batch}")
    return t


def read_limits(workload: str, root: pathlib.Path = ROOT) -> Dict:
    return _read_json(bench_file("limits", workload, ".json", root))


def read_peaks(device_kind: str, root: pathlib.Path = ROOT) -> Dict:
    peaks = _read_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(peaks)})")
    return peaks[device_kind]


def _module(kind: str, name: str, root: pathlib.Path):
    path = bench_file(kind, name, ".py", root)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``read(run) -> float | None`` from bench/metrics/<name>.py."""
    return _module("metrics", name, root).read


def framework(name: str, root: pathlib.Path = ROOT):
    """The module bench/frameworks/<name>.py: the traffic's framework's
    wrappers, operation count and reference (bench/frameworks/fedllm.py
    says what it gives).  A framework with no file is refused."""
    return _module("frameworks", name, root)
