"""Find the benchmark's parts by name: cells, configurations, traffic
mixes, correctness limits and per-layer metric readers.

A later cell, configuration, mix or metric is a new file next to the
others; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return _load_json(root / "BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "traffic" / f"{name}.json")


def limits(cell: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The correctness limits of one cell (``{number: limit}``)."""
    return _load_json(bench_dir / "limits" / f"{cell}.json")["limits"]


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with its files resolved."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple          # metric entries this cell reports untraced
    per_layer: tuple           # metric entries this cell reports traced


def _applies(metric: dict, cell: str, end_to_end_names: set) -> bool:
    """A per-layer metric is read in the cells it lists, or, listing none,
    in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in end_to_end_names


def cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve cell ``name`` of ``BENCHMARK.json`` into its files."""
    bench = benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    w = entries[0]
    bench_dir = root / "bench"
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _applies(m, name, e2e_names))
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config(w["config"], bench_dir),
                traffic=traffic(w["traffic"], bench_dir),
                end_to_end=e2e, per_layer=per_layer)
