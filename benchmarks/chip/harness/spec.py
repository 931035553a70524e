"""Finds a cell's files by name.

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration and traffic mix, and each per-layer metric.  Their files
live under ``benchmarks/chip``:

* ``configs/<config>.json``: the sizes as run, with their source;
  ``configs/<config>.py``: its plain reference and seeded weights;
* ``traffic/<traffic>.json``: the mix's parameters, for the one general
  generator in ``harness/traffic.py``;
* ``metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a mix or a metric adds files; nothing here names
one of them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    """``BENCHMARK.json`` at the root of the checkout."""
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    """The cell called ``name``."""
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{sorted(w['name'] for w in bench['workloads'])}")


def _module(path: pathlib.Path, tag: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    name = f"bench_{tag}_" + "".join(c if c.isalnum() else "_"
                                     for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, base: pathlib.Path = BENCH_DIR):
    """(sizes, reference module) of configuration ``name``."""
    sizes = json.loads((base / "configs" / f"{name}.json").read_text())
    if sizes.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself "
                         f"{sizes.get('name')!r}")
    return sizes, _module(base / "configs" / f"{name}.py", "config")


def load_traffic(name: str, base: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    """The parameters of traffic mix ``name``."""
    return json.loads((base / "traffic" / f"{name}.json").read_text())


def metric_reader(name: str, base: pathlib.Path = BENCH_DIR) -> ModuleType:
    """The reader of per-layer metric ``name``: a module with
    ``read(ctx) -> Optional[float]``."""
    return _module(base / "metrics" / f"{name}.py", "metric")


def per_layer_for(bench: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    own = {m["name"] for m in end_to_end_for(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        cells: Optional[List[str]] = m.get("workloads")
        if (cell in cells) if cells is not None else (m["moves"] in own):
            out.append(m)
    return out


def end_to_end_for(bench: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    """The end-to-end metrics a cell reports."""
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]
