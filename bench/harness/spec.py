"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found here by name:

* ``bench/configs/<config>.json``  sizes, formats, weight recipe;
* ``bench/configs/<config>_ref.py`` the plain reference of that design;
* ``bench/traffic/<traffic>.json``  the mix's parameters (its ``kind``
  picks the module ``bench/kinds/<kind>.py``);
* ``bench/metrics/<metric>.py``     one reader per per-layer metric;
* ``bench/peaks.json``              the chip's peaks, keyed by device kind.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """A cell, configuration, mix or metric the benchmark cannot resolve."""


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """Import one file by path (metric readers have dots in their names)."""
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod              # dataclasses look their module up
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    ref: ModuleType
    kind: ModuleType
    readers: Dict[str, ModuleType] = field(default_factory=dict)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve a workload and every file it names; raises SpecError."""
    doc = benchmark(root)
    cells = {w["name"]: w for w in doc["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in doc["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    entry = configs[w["config"]]
    config = read_json(root / entry["file"])
    bench = root / "bench"
    traffic = read_json(bench / "traffic" / f"{w['traffic']}.json")
    cfg_file = root / entry["file"]
    ref = load_module(cfg_file.with_name(cfg_file.stem + "_ref.py"),
                      f"bench_ref_{w['config']}")
    kind = load_module(bench / "kinds" / f"{traffic['kind']}.py",
                       f"bench_kind_{traffic['kind']}")
    e2e = [m for m in doc["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in doc["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(bench / "metrics" / f"{m['name']}.py",
                                      "bench_metric_"
                                      + m["name"].replace(".", "_"))
               for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                ref=ref, kind=kind, readers=readers)


def peaks_for(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = read_json(root / "bench" / "peaks.json")
    rows = {k: v for k, v in table.items() if not k.startswith("_")}
    if device_kind not in rows:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json; known: {sorted(rows)}")
    return rows[device_kind]
