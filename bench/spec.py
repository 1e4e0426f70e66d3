"""Finds a cell's configuration, traffic, metrics and kernels by name."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def load_benchmark() -> dict:
    return json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json`` (kind: configs, cells)."""
    return json.loads((BENCH_DIR / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (kind: traffic, metrics,
    roofline).  Names may hold dots, so the file is loaded by path."""
    key = f"bench.{kind}.{name.replace('.', '_')}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    with ``trace`` off, its per-layer metrics with ``trace`` on.  A
    per-layer metric without a ``workloads`` key goes to every cell
    that reports the end-to-end metric it moves."""
    e2e = [m for m in bm["end_to_end"] if _applies(m, cell)]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def peaks(kind: str) -> dict:
    """The published peaks of device kind ``kind``; an unknown kind is
    an error, never a default."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]
