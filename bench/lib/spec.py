"""What ``BENCHMARK.json`` and the files it names say about one cell.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.py``,
``models/<reference>.py``.  A new cell is new files and new entries."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    workload: Dict          # the workloads entry
    config: Dict            # configs/<name>.json
    traffic: Dict           # traffic/<name>.json
    end_to_end: List[Dict]  # the end_to_end entries this cell reports
    per_layer: List[Dict]   # the per_layer entries this cell reports


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    bench = _load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _load_json(ROOT / conf["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    # A per-layer metric without a workloads key is reported wherever the
    # end-to-end metric it moves is.
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in moved)]
    return Cell(w, config, traffic, e2e, per)


def load_module(path: Path):
    """Import a file by path (metric names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def reference_module(config: Dict):
    return load_module(BENCH_DIR / "models" / f"{config['reference']}.py")
