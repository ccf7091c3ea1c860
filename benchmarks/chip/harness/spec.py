"""Finding a cell's configuration, traffic and metrics by name.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

    configs/<config>.json     the configuration as it is run: the published
                              config's keys, and a "bench" group
    workloads/<traffic>.json  the traffic mix's parameters
    metrics/<metric>.py       a reader with ``read(obs) -> float | None``
    harness/<kind>.py         the runner the configuration's "kind" names

so a later cell or metric is added as files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: Callable[[dict], Optional[float]]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict                 # configs/<config>.json
    traffic: dict                # workloads/<traffic>.json
    end_to_end: List[Metric]     # reported with --trace 0
    per_layer: List[Metric]      # reported with --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_reader(name: str, metrics_dir: Path = BENCH_DIR / "metrics"):
    """``metrics/<name>.py``'s ``read`` function, loaded by path (metric
    names carry dots, so they are not importable module names)."""
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    # a metric without a cell list is reported wherever the end-to-end
    # metric it moves (or, for an end-to-end metric, every cell) is
    return entry.get("moves") is None or entry["moves"] in e2e_names


def find_cell(name: str, bench: Optional[dict] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    raw = json.loads((bench_dir.parents[1] / cfg_entry["file"]).read_text())
    # the file's top level is the published config (as run); the harness's
    # own settings sit in its "bench" group
    config = dict(raw["bench"],
                  model={k: v for k, v in raw.items() if k != "bench"})
    traffic = json.loads((bench_dir / "workloads" / f"{w['traffic']}.json")
                         .read_text())

    def metric(m):
        return Metric(m["name"], m["unit"], m["better"], m["source"],
                      load_reader(m["name"], bench_dir / "metrics"))

    e2e = [m for m in bench["end_to_end"] if _applies(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=[metric(m) for m in e2e],
                per_layer=[metric(m) for m in per_layer])


def load_runner(kind: str, harness_dir: Path = BENCH_DIR / "harness"):
    """The runner module ``harness/<kind>.py`` (its ``run`` function)."""
    path = harness_dir / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no runner for kind {kind!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"harness.{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(metrics: List[Metric], obs: dict) -> Dict[str, dict]:
    """Each metric's reader over the run's observations; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = m.reader(obs)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out
