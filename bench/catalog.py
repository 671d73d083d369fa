"""Finds every part of the benchmark by the name BENCHMARK.json gives it.

  cell            a ``workloads`` entry of BENCHMARK.json
  configuration   bench/configs/<config>.json (sizes, source, serving)
  traffic mix     bench/traffic/<traffic>.json
  reference       bench/references/<reference>.py, named by the config
  architecture    bench/architectures/<reference>.py: the config's layer
                  groups, leaves, served tree layout and work per call
  limits          bench/limits/<cell>.json (the numbers ``correct`` holds)
  per-layer       bench/layer_metrics/<metric>.py, one reader each
  kernel work     bench/work/<kernel>.py, ops and bytes from shapes

Later cells, mixes, metrics and kernels are added as files and entries;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _check(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _json(REPO / "BENCHMARK.json")


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json (cells: "
                   f"{[w['name'] for w in bench['workloads']]})")


def config(name: str) -> Dict:
    return _json(BENCH / "configs" / f"{_check(name)}.json")


def traffic(name: str) -> Dict:
    return _json(BENCH / "traffic" / f"{_check(name)}.json")


def limits(cell_name: str) -> Dict:
    path = BENCH / "limits" / f"{_check(cell_name)}.json"
    return _json(path) if path.exists() else {}


def _module(path: pathlib.Path, tag: str):
    if not path.exists():
        raise FileNotFoundError(f"missing {path.relative_to(REPO)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The per-layer reader of ``metric`` (its module's ``read``)."""
    return _module(BENCH / "layer_metrics" / f"{_check(metric)}.py",
                   "metric").read


def work(kernel: str):
    """The work function of ``kernel`` (its module)."""
    return _module(BENCH / "work" / f"{_check(kernel)}.py", "work")


def reference(name: str):
    return _module(BENCH / "references" / f"{_check(name)}.py", "ref")


def architecture(name: str):
    """What the benchmark knows of a model's shape, named by the config's
    ``reference`` key (its module)."""
    return _module(BENCH / "architectures" / f"{_check(name)}.py", "arch")


def metrics_of(bench: Dict, section: str, cell_name: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it under ``workloads``, and those with no such list."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]
