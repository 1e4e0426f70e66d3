"""The four-chip cell ``mv_n12_k9.4chip.devworkers``: its phase cycle,
its co-tenant, what it counts, and one whole run of it at a size the
CPU holds, on four virtual devices in a subprocess (the kernels in
interpret mode; the look for a chip skipped)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spec

ROOT = Path(__file__).resolve().parents[2]
CELL = "mv_n12_k9.4chip.devworkers"
traffic = spec.load_module("traffic", "mv_devworkers")


def test_phase_cycle_is_seeded_rounds_of_permutations():
    def phases(seed, rounds=6, calls=3):
        cycle = traffic.PhaseCycle(4, calls)
        rng = np.random.default_rng(seed)
        drawn = [cycle.draw(rng) for _ in range(rounds * 5 * calls)]
        # each phase holds one slowed chip (or none) for ``calls`` calls
        assert all(len(set(drawn[i:i + calls])) == 1
                   for i in range(0, len(drawn), calls))
        return drawn[::calls]

    a = phases(2**33 + 7)
    for r in range(6):
        assert sorted(a[5 * r:5 * r + 5]) == [-1, 0, 1, 2, 3]
    assert a == phases(2**33 + 7)
    assert a != phases(2**33 + 8)


class Pending:
    def __init__(self):
        self.done = False

    def is_ready(self):
        return self.done


def test_cotenant_never_has_two_in_flight(monkeypatch):
    issued = []

    def fake_load(w, iters):
        issued.append(w)
        return Pending()

    monkeypatch.setattr(traffic, "cotenant_load", fake_load)
    co = traffic.Cotenant(["w0", "w1"], [5, 5])
    assert co.load(1) and issued == ["w1"]
    for _ in range(3):                      # still running: none issued
        assert not co.load(1)
    assert issued == ["w1"]
    assert co.load(0) and issued == ["w1", "w0"]
    co.inflight[1].done = True
    assert co.load(1) and issued == ["w1", "w0", "w1"]


def test_expected_decode_leaves_the_slowed_chip_out():
    assert traffic.expected_chips(-1, 4, 3) == (0, 1, 2)
    assert traffic.expected_chips(1, 4, 3) == (0, 2, 3)
    assert traffic.expected_chips(0, 1, 1) == (0,)


def test_a_program_that_cannot_place_fails_before_set_up(monkeypatch):
    import repro.api

    def compile_plan(A=None, *, scheme="proposed", n=None, k_A=None,
                     seed=0, backend="auto"):
        raise AssertionError("reached compile_plan")

    def make(*args, **kwargs):
        raise AssertionError("made operands")

    monkeypatch.setattr(repro.api, "compile_plan", compile_plan)
    monkeypatch.setattr(traffic.operands, "make", make)
    cell = spec.load_json("cells", CELL)
    with pytest.raises(TypeError, match="no devices="):
        traffic.Stream(spec.load_json("configs", cell["config"]),
                       cell["params"], 5, "pallas-interpret",
                       harness.Phases().phase)


def _stream():
    """A stream without its program: what the counts and metrics read."""
    s = object.__new__(traffic.Stream)
    s.n, s.k, s.per, s.batch = 12, 9, 3, 8
    s.chips, s.need = 4, 3
    s.supports = [(0, 1, 2)] * 12
    s.A = np.ones((16, 9))
    s.plan = object()
    return s


def test_describe_names_the_launched_workers():
    s = _stream()
    sp = traffic.Spec(0, 2, rows=tuple(range(6)) + (9, 10, 11),
                      launched=(0, 1, 3, 2))
    key = s.work_key(sp)
    assert key == ((0, 1, 3), (0, 1, 3, 2))
    desc = s.describe(key)
    assert desc["selected"] == [0, 1, 2, 3, 4, 5, 9, 10, 11, 6, 7, 8]
    assert desc["width"] == 8 and desc["k_A"] == 9


def test_new_metrics_read_the_calls_after_free_program():
    s = _stream()
    specs = [traffic.Spec(0, 2, rows=(0, 1, 2, 3, 4, 5, 9, 10, 11),
                          launched=(0, 1, 3), harvest_s=0.002),
             traffic.Spec(1, 2, rows=(0, 1, 2, 6, 7, 8, 9, 10, 11),
                          launched=(0, 1, 2, 3), harvest_s=0.004),
             traffic.Spec(2, -1, rows=(0, 1, 2, 3, 4, 5, 6, 7, 8),
                          launched=(0, 1, 2, 3), harvest_s=0.003)]
    calls = [harness.Call(sp, 0.0, 0.001, 0.005, True) for sp in specs]
    s.free_program()
    assert s.plan is None
    run = harness.Run(stream=s, calls=calls, window_s=1.0, setup_s=1.0,
                      phases={})
    harvest_ms = spec.load_module("metrics", "executor.harvest_ms")
    share = spec.load_module("metrics", "executor.slowed_chip_decode_share")
    assert harvest_ms.read(run) == pytest.approx(3.0)
    # of the two calls with chip 2 slowed, one decoded with its workers
    assert share.read(run) == pytest.approx(50.0)
    # a control run: no program reported a decode, nothing is read
    for sp in specs:
        sp.harvest_s = None
    assert harvest_ms.read(run) is None and share.read(run) is None


RUN = """
    import json, sys
    sys.path[:0] = [".", "src"]
    from bench import harness, spec
    cell = spec.load_json("cells", "%s")
    cell["params"] = {**cell["params"], "cotenant_size": 128,
                      "cotenant_ms": 20, "phase_calls": 2}
    config = {**spec.load_json("configs", cell["config"]), "A": [640, 540]}
    bm = spec.load_benchmark()
    metrics = spec.cell_metrics(bm, "%s", False) + [
        m for m in spec.cell_metrics(bm, "%s", True)
        if m["source"] != "device_trace"]
    res = harness.run_cell("%s", config, cell, seed=2**33 + 99,
                           seconds=2.0, trace=False, metrics=metrics,
                           backend="pallas-interpret")
    print("RESULT " + json.dumps(res))
""" % ((CELL,) * 4)


def test_a_whole_run_on_four_virtual_devices():
    prog = ("import os\nos.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n"
            + textwrap.dedent(RUN))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"})
    proc = subprocess.run([sys.executable, "-c", prog], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines()
                if x.startswith("RESULT "))
    res = json.loads(line[len("RESULT "):])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["checks"]["off_path_calls"]["value"] == 0
    m = res["metrics"]
    assert {"products_per_s", "setup_s", "executor.harvest_ms",
            "executor.slowed_chip_decode_share"} <= set(m)
    assert m["executor.harvest_ms"]["value"] > 0
    assert 0 <= m["executor.slowed_chip_decode_share"]["value"] <= 100
