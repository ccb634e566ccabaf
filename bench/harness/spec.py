"""Find a cell and everything it names, by name, from files.

``BENCHMARK.json`` lists the cells (``workloads``), configurations and
metrics.  Each name leads to a file of its own:

* configuration ``<c>`` -> the ``file`` its entry names (``bench/configs``);
* traffic mix ``<t>`` -> ``bench/traffic/<t>.json``;
* the limits of cell ``<w>``'s comparison -> ``bench/checks/<w>.json``;
* metric ``<m>`` -> ``bench/metrics/<m>.py``, a module with ``read(rec)``.

Adding a cell, a configuration or a metric adds files and entries; no file
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    checks: Dict            # number -> {"limit": ...} (bench/checks/<name>)
    metrics_e2e: List[Dict]  # end_to_end entries this cell reports
    metrics_layer: List[Dict]  # per_layer entries this cell reports


def load_benchmark(path: Path = REPO / "BENCHMARK.json") -> Dict:
    return json.loads(Path(path).read_text())


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(name: str, bench: Optional[Dict] = None,
              root: Path = REPO) -> Cell:
    """The cell called ``name``; ``KeyError`` naming the known cells when
    there is none."""
    bench = load_benchmark(root / "BENCHMARK.json") if bench is None else bench
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    checks = json.loads(
        (root / "bench" / "checks" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, checks=checks,
                metrics_e2e=[m for m in bench["end_to_end"]
                             if _applies(m, name)],
                metrics_layer=[m for m in bench["per_layer"]
                               if _applies(m, name)])


def metric_reader(name: str, root: Path = BENCH) -> Callable:
    """``read(rec)`` of ``bench/metrics/<name>.py``."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
