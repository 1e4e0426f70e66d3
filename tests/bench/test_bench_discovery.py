"""``BENCHMARK.json`` against the files the harness finds by name, and
the shape ``BENCHMARK.json`` has to keep.  A new cell, configuration
or metric is new files and new entries only: the last test adds a cell
and two metrics to a copy and runs the new cell."""

from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness, spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(BM["paths"]) <= 16
    for path in BM["paths"]:
        assert (ROOT / path).is_dir() and not path.startswith("/")
        assert ".." not in path.split("/")
    assert (ROOT / "bench" / "run.py").is_file()
    rs = BM["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells, each run given run_seconds + 60 s and
    # each cell 2 x 90 s to compile, fits 43200 s with 1200 s to spare
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_bounds():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    names += CELLS + [c["name"] for c in BM["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])
    for m in BM["per_layer"]:
        assert "bound" not in m and m["layer"] and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in BM["configs"]]
    assert len(files) == len(set(files))
    for c in BM["configs"]:
        assert c["file"].startswith("bench/configs/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) and k in body for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BM["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files(cell):
    w = spec.workload(BM, cell)
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert NAME.match(w["traffic"])
    config = spec.load_json("configs", w["config"])
    body = spec.load_json("cells", cell)
    assert (body["config"], body["traffic"]) == (w["config"], w["traffic"])
    assert config["system"] == spec.load_module(
        "traffic", body["generator"]).Stream.kind
    assert body["limits"]["err_per_kappa"] > 0
    e2e = spec.cell_metrics(BM, cell, False)
    layer = spec.cell_metrics(BM, cell, True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert callable(spec.load_module("metrics", m["name"]).read)
    for m in layer:
        if m["name"].endswith("_roofline"):
            kernel = spec.load_module("metrics", m["name"]).KERNEL
            assert callable(spec.load_module("roofline", kernel).count)


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_per_layer_workloads_report_what_they_move():
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            reported = {x["name"] for x in spec.cell_metrics(BM, cell, False)}
            assert m["moves"] in reported, (m["name"], cell)


def test_a_new_cell_and_metrics_are_files_and_entries_only(tmp_path,
                                                           monkeypatch):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = "mv_n12_k9.b8.few"
    cell = spec.load_json("cells", "mv_n12_k9.b8")
    cell["traffic"] = "b8.few"
    cell["params"] = {**cell["params"], "patterns": 2, "sample": 1}
    (tmp_path / "bench" / "cells" / f"{new}.json").write_text(
        json.dumps(cell))
    (tmp_path / "bench" / "metrics" / "latency_p50_ms.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n"
        "    return float(np.percentile([c.done - c.issued"
        " for c in run.ok_calls], 50)) * 1e3\n")
    (tmp_path / "bench" / "metrics" / "window.calls.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    bm["workloads"].append({"name": new, "config": "mv_n12_k9",
                            "traffic": "b8.few", "chips": 1,
                            "why": "two masks only"})
    bm["end_to_end"].append({"name": "latency_p50_ms", "unit": "ms",
                             "better": "lower", "bound": 0.05,
                             "source": "host_clock", "workloads": [new]})
    bm["per_layer"].append({"name": "window.calls", "unit": "calls",
                            "better": "higher", "source": "host_clock",
                            "layer": "executor", "moves": "products_per_s",
                            "workloads": [new]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    monkeypatch.setattr(spec, "BENCH_DIR", tmp_path / "bench")

    loaded = spec.load_benchmark()
    assert [m["name"] for m in spec.cell_metrics(loaded, new, True)] == \
        ["window.calls"]
    config = {**spec.load_json("configs", "mv_n12_k9"), "A": [640, 480]}
    res = harness.run_cell(new, config, spec.load_json("cells", new),
                           seed=5, seconds=0.3, trace=False,
                           metrics=spec.cell_metrics(loaded, new, False),
                           backend="pallas-interpret")
    assert res["correct"], res["checks"]
    # latency_p95_ms lists its cells, and this one is not among them
    assert set(res["metrics"]) == {"products_per_s", "latency_p50_ms",
                                   "setup_s"}
    # one output of each of its three masks (two and the all-alive one)
    assert res["checks"]["outputs_compared"]["value"] <= 3
