"""Coded workers placed on several devices (``compile_plan(devices=)``,
``repro.runtime.executor`` over the policy of ``repro.runtime.placement``).

The policy (how many workers a device holds, which devices a product is
launched on, the harvest by completion) is tested here with scripted
readiness and no devices.  The placed plan itself runs in a subprocess
with four virtual CPU devices (``--xla_force_host_platform_device_count``,
as ``tests/test_multidevice.py`` does), the kernels in interpret mode,
and is compared with a plain float32 product and with the same plan
compiled unplaced.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.runtime.placement import (CallRecord, choose_launches,
                                     device_mask, harvest,
                                     workers_per_device)

ROOT = Path(__file__).resolve().parent.parent
EPS32 = float(np.finfo(np.float32).eps)


class Script:
    """Readiness by sweep: device ``c`` is ready from the sweep
    ``ready_at[c]`` on (never where absent); ``launch`` restarts the
    count for a device that joins."""

    def __init__(self, ready_at: dict, launch_takes: int = 1):
        self.ready_at = dict(ready_at)
        self.takes = launch_takes
        self.sweep = 0
        self.asked = set()
        self.launched = []

    def ready(self, c: int) -> bool:
        if c in self.asked:          # a device asked twice: a new sweep
            self.sweep += 1
            self.asked.clear()
        self.asked.add(c)
        return self.ready_at.get(c, float("inf")) <= self.sweep

    def launch(self, c: int) -> None:
        self.launched.append(c)
        self.ready_at[c] = self.sweep + self.takes


# -- the policy, no devices ---------------------------------------------------


@pytest.mark.parametrize("n,devices", [(10, 4), (12, 5), (9, 2), (12, 0)])
def test_uneven_placement_is_rejected(n, devices):
    with pytest.raises(ValueError, match=f"n={n} .* {devices} devices"):
        workers_per_device(n, devices)


def test_even_placement():
    assert workers_per_device(12, 4) == 3
    assert workers_per_device(12, 1) == 12


def test_busy_device_gets_no_launch():
    launch, skipped = choose_launches([False, False, True, False], 3,
                                      [4, 4, 2, 4])
    assert (launch, skipped) == ([0, 1, 3], [2])


def test_busy_device_launched_only_when_needed():
    # two busy, three needed: the one whose pending launch is oldest
    launch, skipped = choose_launches([False, True, True, False], 3,
                                      [9, 7, 3, 9])
    assert (launch, skipped) == ([0, 2, 3], [1])
    assert choose_launches([True] * 4, 3, [1, 2, 3, 4]) == ([0, 1, 2], [3])
    assert choose_launches([False] * 4, 3, [0] * 4) == ([0, 1, 2, 3], [])


def test_first_devices_to_finish_decode_in_their_order():
    s = Script({2: 1, 0: 2, 3: 2})              # device 1 never finishes
    order, joined, sweeps = harvest([0, 1, 2, 3], s.ready, 3)
    assert order == [2, 0, 3] and joined == []
    assert order[0] == 2                        # the decoding device
    assert sweeps == 3


def test_harvest_stops_at_count():
    s = Script({0: 0, 1: 0, 2: 0, 3: 0})
    order, _, sweeps = harvest([0, 1, 2, 3], s.ready, 3)
    assert order == [0, 1, 2] and sweeps == 1


def test_a_device_that_frees_up_joins_the_harvest():
    # 1 is slow and 3 was skipped as busy; 3 frees at sweep 2, is
    # launched, and finishes before 1 does
    s = Script({0: 0, 2: 0, 3: 2, 1: 50}, launch_takes=2)
    order, joined, _ = harvest([0, 1, 2], s.ready, 3, waiting=[3],
                               launch=s.launch)
    assert s.launched == [3] and joined == [3]
    assert order == [0, 2, 3]


def test_harvest_needs_enough_devices():
    with pytest.raises(ValueError, match="need 3 devices"):
        harvest([0, 1], lambda c: True, 3)


def test_device_mask():
    assert device_mask([0, 2], 12, 3).tolist() == \
        [True] * 3 + [False] * 3 + [True] * 3 + [False] * 3


# -- the placed plan on four virtual CPU devices ------------------------------

PROGRAM = """
    import json, os
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import compile_plan
    from repro.kernels.bcsr_matmul import bcsr_grid
    from repro.obs import default_tracer
    from repro.runtime.placement import RECORDS, device_mask

    HIGHEST = jax.lax.Precision.HIGHEST
    rng = np.random.default_rng(5)
    a = rng.standard_normal((640, 540)).astype(np.float32)
    a[rng.random(a.shape) < 0.9] = 0
    A = jnp.asarray(a)
    devs = jax.devices()[:4]
    kw = dict(scheme="proposed", n=12, k_A=9, seed=9,
              backend="pallas-interpret")
    plan = compile_plan(A, devices=devs, **kw)
    base = compile_plan(A, **kw)
    ex = plan.executor
    x = jnp.asarray(rng.standard_normal((8, 640)), jnp.float32)
    rep = jax.device_put(x, jax.sharding.NamedSharding(
        jax.sharding.Mesh(np.asarray(devs), ("d",)),
        jax.sharding.PartitionSpec()))
    G = np.asarray(plan.G, np.float64)
    out = {"describe": plan.describe(), "calls": [],
           "records_bound": ex.records.maxlen == RECORDS,
           "grid_steps": [int(np.prod(bcsr_grid(
               g.packed.a_data.shape, 3 * g.packed.mb, 8)))
               for g in ex.groups],
           # a launch over 1, 2 or 3 of a device's workers
           "row_grid_steps": [int(np.prod(bcsr_grid(
               ex.groups[0].packed.a_data.shape,
               m * ex.groups[0].packed.mb, 8))) for m in (1, 2, 3)],
           "base_grid_steps": int(np.prod(bcsr_grid(
               base.executor.packed.a_data.shape,
               9 * base.executor.packed.mb, 8)))}

    polls = [0]

    def report(name, y, done, xs=x):
        rec = ex.records[-1]
        now = default_tracer().counters().get("executor.harvest_polls", 0)
        for g in ex.groups:             # every device idle again
            g.pending.block_until_ready()
        mask = done if done is not None else device_mask(
            sorted({i // 3 for i in rec.rows}), 12, 3)
        yb = np.asarray(base.matvec(xs, mask))
        ref = np.asarray(jnp.dot(xs, A, precision=HIGHEST), np.float64)
        rows = np.flatnonzero(mask)[:9]
        out["calls"].append({
            "name": name, "launched": rec.launched,
            "explicit": done is not None, "polls": now - polls[0],
            "skipped": rec.skipped_busy, "rows": rec.rows,
            "decode": rec.decode_device, "harvest_s": rec.harvest_s,
            "on": [str(d) for d in y.devices()],
            "decode_on": str(devs[rec.decode_device]),
            "err": float(np.abs(np.asarray(y) - ref).max()
                         / np.abs(ref).max()),
            "kappa": float(np.linalg.cond(G[rows])),
            "same_as_unplaced": bool(np.array_equal(np.asarray(y), yb))})
        polls[0] = now

    ex.warm(rep)
    default_tracer().clear()
    before = default_tracer().counters()
    polls[0] = before.get("executor.harvest_polls", 0)
    report("none, replicated", plan.matvec(rep), None)
    report("none, on one device", plan.matvec(x), None)
    report("none, one row", plan.matvec(rep[0])[None], None, x[:1])
    for p in ex.patterns():
        m = device_mask(p, 12, 3)
        report(f"done {p}", plan.matvec(rep, m), m)
    scattered = np.ones(12, bool)
    scattered[[1, 5, 10]] = False          # three stragglers, three devices
    report("done scattered", plan.matvec(rep, scattered), scattered)
    # device 2 scripted slow from the next launch on: that product is
    # left out, then device 2 is skipped as busy
    real, slow_from = ex._ready, ex._count + 1
    ex._ready = lambda c: real(c) and not (
        c == 2 and ex.groups[2].launched_at >= slow_from)
    report("2 slow", plan.matvec(rep), None)
    report("2 still busy", plan.matvec(rep), None)
    ex._ready = real
    report("2 free again", plan.matvec(rep), None)
    tr = default_tracer()
    after = tr.counters()
    out["counters"] = {k: after.get(k, 0) - before.get(k, 0)
                       for k in after}
    out["spans"] = sorted({e["name"] for e in tr.events()})
    out["path_calls"] = dict(ex.calls)
    for name, call in [("mm", lambda: compile_plan(
                            A, scheme="proposed", n=8, k_A=2, k_B=2,
                            backend="pallas-interpret", devices=devs)),
                       ("uneven", lambda: compile_plan(
                            A, devices=jax.devices()[:5], **kw)),
                       ("traced", lambda: jax.jit(plan.matvec)(x))]:
        try:
            call()
            out[name] = "no error"
        except (NotImplementedError, ValueError) as e:
            out[name] = f"{type(e).__name__}: {e}"
    print("RESULT " + json.dumps(out))
"""


def run_py(code: str, devices: int, timeout: int = 560) -> str:
    prog = (f"import os\nos.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, cwd=ROOT,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "REPRO_TRACE": "1"})
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.fixture(scope="module")
def placed():
    out = run_py(PROGRAM, devices=5)
    line = next(x for x in out.splitlines() if x.startswith("RESULT "))
    res = json.loads(line[len("RESULT "):])
    res["by_name"] = {c["name"]: c for c in res["calls"]}
    return res


def test_placed_plan_agrees_with_float32_and_unplaced(placed):
    for c in placed["calls"]:
        # float32 sums in each worker (eps each) amplified by the
        # condition number of the decode's rows; 8 eps leaves room for
        # the sums' length (read: under 1 eps x kappa)
        assert c["err"] <= 8 * EPS32 * c["kappa"], c
        # the same tiles in the same slot order, the same inverse and
        # kernels: only where the workers live differs
        assert c["same_as_unplaced"], c["name"]


def test_first_devices_to_finish_decode_on_the_first(placed):
    for name in ("none, replicated", "none, on one device",
                 "none, one row"):
        c = placed["by_name"][name]
        assert c["launched"] == [0, 1, 2, 3] and c["skipped"] == []
        chips = sorted({i // 3 for i in c["rows"]})
        assert len(chips) == 3 and c["decode"] in chips
        assert c["on"] == [c["decode_on"]]


@pytest.mark.parametrize("pattern", [[0, 1, 2], [0, 1, 3], [0, 2, 3],
                                     [1, 2, 3]])
def test_each_device_pattern_is_honoured(placed, pattern):
    c = placed["by_name"][f"done {tuple(pattern)}"]
    assert c["launched"] == pattern
    assert sorted({i // 3 for i in c["rows"]}) == pattern
    assert c["decode"] in pattern and c["on"] == [c["decode_on"]]


def test_scattered_stragglers_launch_every_device_they_need(placed):
    c = placed["by_name"]["done scattered"]
    assert c["launched"] == [0, 1, 2, 3]
    assert c["rows"] == [0, 2, 3, 4, 6, 7, 8, 9, 11]
    assert c["on"] == [c["decode_on"]]


def test_slow_device_is_left_out_then_skipped_while_busy(placed):
    slow = placed["by_name"]["2 slow"]
    assert slow["launched"] == [0, 1, 2, 3]
    assert all(i // 3 != 2 for i in slow["rows"])
    busy = placed["by_name"]["2 still busy"]
    assert busy["launched"] == [0, 1, 3] and busy["skipped"] == [2]
    assert all(i // 3 != 2 for i in busy["rows"])
    free = placed["by_name"]["2 free again"]
    assert free["launched"] == [0, 1, 2, 3] and free["skipped"] == []


def test_record_spans_and_counters_fill_in(placed):
    calls = placed["calls"]
    assert placed["records_bound"]
    assert all(c["harvest_s"] >= 0 for c in calls)
    counters = placed["counters"]
    assert counters["executor.chip_launches"] == \
        sum(len(c["launched"]) for c in calls)
    assert counters["executor.chips_skipped_busy"] == 1
    # a harvest polls at least once; an explicit done knows its rows
    # before launch and polls nothing
    assert all(c["polls"] == 0 if c["explicit"] else c["polls"] >= 1
               for c in calls)
    assert counters["executor.harvest_polls"] == \
        sum(c["polls"] for c in calls)

    def steps(call, c):
        """A harvested launch reads all 3 of device c's workers; an
        explicit done's, only its decode rows there."""
        if not call["explicit"]:
            return placed["grid_steps"][c]
        return placed["row_grid_steps"][
            sum(i // 3 == c for i in call["rows"]) - 1]

    # each launch adds its grid (x of 8 or 1 rows: one N tile either
    # way), and so does each call's unplaced product it is compared with
    assert counters["kernel.bcsr_grid_steps"] == sum(
        steps(call, c) for call in calls
        for c in call["launched"]) + len(calls) * placed["base_grid_steps"]
    assert {"plan.matvec", "executor.launch", "executor.harvest",
            "executor.gather", "executor.decode",
            "executor.output"} <= set(placed["spans"])
    # every call on the kernel path: none off it
    assert placed["path_calls"] == {"pallas-interpret": len(calls)}


def test_describe_reports_the_placement(placed):
    p = placed["describe"]["placement"]
    assert p["workers_per_device"] == 3 and p["devices_to_decode"] == 3
    assert len(p["devices"]) == 4


def test_mm_and_uneven_placements_are_refused(placed):
    assert placed["mm"].startswith("NotImplementedError")
    assert placed["uneven"].startswith("ValueError")
    assert "n=12" in placed["uneven"] and "5 devices" in placed["uneven"]


def test_traced_input_is_refused(placed):
    # no reference fallback: the placed workers need concrete inputs
    assert placed["traced"] == \
        "ValueError: a placed plan needs concrete inputs"


def test_call_record_fields():
    assert CallRecord._fields == ("launched", "skipped_busy", "rows",
                                  "decode_device", "harvest_s")
