"""The reduction by the program's own spans (``bench.stages``):
device idle by the stage the host was in, device programs per product
and the device time of what ``executor.select`` launched.  Checked on a
synthetic trace, on two short windows recorded on a TPU v5e with the
program's spans and launch events (``trace_mv_spans``: 1 s of the MV
cell; ``trace_mm_spans``: 2 s of the MM cell, whose device clock offset
cannot be fixed), and on the older recording without them."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from bench import stages
from bench import trace as tracing
from bench.harness import SPANS
from repro.obs import PROGRAM_SPANS

DATA = Path(__file__).resolve().parent / "data"


def _load(name: str) -> dict:
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mv():
    return _load("trace_mv_spans.json.gz")


@pytest.fixture(scope="module")
def mm():
    return _load("trace_mm_spans.json.gz")


def _reduce(tr):
    summary = tracing.summarize(tr, SPANS)
    return summary, stages.stages(tr, SPANS, PROGRAM_SPANS,
                                   summary.offset_ns)


def test_recorded_traces_are_small():
    for name in ("trace_mv_spans.json.gz", "trace_mm_spans.json.gz"):
        assert (DATA / name).stat().st_size < 1 << 20


def test_innermost_gives_each_instant_to_the_deepest_open_span():
    spans = [("a", 0, 10), ("b", 2, 5), ("c", 3, 4), ("d", 6, 8),
             ("e", 12, 13)]
    assert stages.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 6, "a"),
        (6, 8, "d"), (8, 10, "a"), (12, 13, "e")]


def _synthetic(offset_ns: float = 0.0):
    """Three closed-loop MV calls.  Each launches 2 programs in
    ``executor.select`` (3 ms and 1 ms on the chip), then 1 in
    ``executor.worker`` (2 ms); the chip idles while the host is still
    selecting, until 1.12 ms into the call."""
    spans, launches, mods, ops, t = [], [], [], [], 0.0
    us = 1e3
    for _ in range(3):
        spans += [["bench.choose", t, 100 * us],
                  ["bench.call", t + 100 * us, 3000 * us],
                  ["plan.matvec", t + 110 * us, 2980 * us],
                  ["executor.select", t + 120 * us, 1900 * us],
                  ["executor.worker", t + 2100 * us, 500 * us],
                  ["bench.wait", t + 3100 * us, 4100 * us]]
        runs = [(t + 150 * us, t + 1120 * us, 3000 * us),
                (t + 1900 * us, t + 4120 * us, 1000 * us),
                (t + 2200 * us, t + 5120 * us, 2000 * us)]
        for i, (launch, start, dur) in enumerate(runs):
            launches.append([stages.LAUNCH, launch, 10 * us])
            mods.append([f"jit_m{i}", start - offset_ns, dur])
            ops.append([f"op{i}.1", start - offset_ns, dur])
        t += 7400 * us
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": spans},
            {"name": "main/1", "events": launches}]}]}


def test_synthetic_window_by_stage():
    tr = _synthetic()
    st = stages.stages(tr, SPANS, PROGRAM_SPANS, 0.0)
    assert st.products == 3 and st.programs_per_product == 3.0
    assert st.select_device_s == pytest.approx(4e-3)
    # the chip idles from the product's start (110 us) to its first run
    assert st.idle_in_program_s == pytest.approx(3 * 1.01e-3)
    # each gap, from the last run of the call before (or the window's
    # start) to the first run, lies mostly in executor.select; the last
    # run ends 80 us before the window's last wait does
    idle = st.idle_by_stage()
    assert idle == {"executor.select": pytest.approx(1.12e-3 + 2 * 1.4e-3),
                    "bench.wait": pytest.approx(80e-6)}
    assert st.breakdown() == [["executor.select", pytest.approx(1.4e-3)]] * 2 \
        + [["executor.select", pytest.approx(1.12e-3)],
           ["bench.wait", pytest.approx(80e-6)]]


@pytest.mark.parametrize("offset_ns", [0.0, 0.5e6])
def test_idle_by_stage_adds_up_to_the_summary(offset_ns):
    tr = _synthetic(offset_ns)
    summary = tracing.summarize(tr, SPANS)
    st = stages.stages(tr, SPANS, PROGRAM_SPANS, summary.offset_ns)
    assert sum(st.idle_by_stage().values()) == pytest.approx(
        summary.window_s - summary.busy_s)
    # pairing by launch order needs no clock offset
    assert st.programs_per_product == 3.0
    assert st.select_device_s == pytest.approx(4e-3)


def test_runs_launched_outside_products_are_not_counted():
    tr = _synthetic()
    host = tr["planes"][1]["lines"]
    # one more launch and run, from the wait of the last call
    last = max(e[1] for e in host[0]["events"] if e[0] == "bench.wait")
    host[1]["events"].append([stages.LAUNCH, last + 1e5, 1e4])
    for line in tr["planes"][0]["lines"]:
        line["events"].append(["x", last + 2e5, 1e5])
    st = stages.stages(tr, SPANS, PROGRAM_SPANS, 0.0)
    assert st.programs_per_product == 3.0


def test_unpaired_launches_leave_the_counts_out():
    tr = _synthetic()
    tr["planes"][1]["lines"][1]["events"].pop()
    assert stages.launched_runs(tr) is None
    st = stages.stages(tr, SPANS, PROGRAM_SPANS, 0.0)
    assert st.programs_per_product is None and st.select_device_s is None
    assert st.idle_in_program_s > 0


def test_no_product_span_reduces_to_nothing():
    assert stages.stages(_load("trace_mv8.json.gz"), SPANS, PROGRAM_SPANS,
                          None) is None


def test_older_recording_names_gaps_as_summarize_does():
    tr = _load("trace_mv8.json.gz")
    summary = tracing.summarize(tr, SPANS)
    st = stages.stages(tr, SPANS, SPANS, summary.offset_ns,
                        products=("bench.call",))
    assert st.gaps == summary.gaps
    assert st.products == 38 and st.programs_per_product is None


def test_recorded_mv_window(mv):
    summary, st = _reduce(mv)
    assert len(stages.launched_runs(mv)) == 988
    # every call runs the same 26 programs (988 module runs, 38 calls)
    assert st.products == 38 and st.programs_per_product == 26.0
    # the selected workers' copy: reshape, gather and broadcast copies
    assert 16e-3 < st.select_device_s < 18e-3
    idle = summary.window_s - summary.busy_s
    assert sum(st.idle_by_stage().values()) == pytest.approx(idle)
    assert 0 < st.idle_in_program_s < idle
    assert {n for n, _ in st.gaps} <= set(PROGRAM_SPANS) | set(SPANS) | {
        "untraced"}
    # the kernels' stable names reach the trace
    assert summary.kernel_s({"bcsr_matmul"}) > 0
    assert summary.kernel_s({"decode_matmul"}) > 0


def test_recorded_mm_window(mm):
    summary, st = _reduce(mm)
    assert summary.offset_ns is None
    assert st.products == 5 and st.programs_per_product == 266.0
    assert st.select_device_s > 0
    assert sum(st.idle_by_stage().values()) == pytest.approx(
        summary.window_s - summary.busy_s)
    assert summary.kernel_s({"bcsr_matmul"}) > 0
    assert summary.kernel_s({"cyclic_encode"}) > 0


def test_program_names_its_spans():
    """The reduction's products and select stage are spans the program
    records, and none of the program's spans is the benchmark's."""
    assert set(stages.PRODUCTS) | {stages.SELECT} <= set(PROGRAM_SPANS)
    assert not set(PROGRAM_SPANS) & set(SPANS)


def test_load_xspace_keeps_the_program_spans_when_named(tmp_path):
    """A profiler capture on the CPU at the harness's options: the
    program's spans reach the compact form, nested, only where named."""
    import jax

    from repro.obs import Tracer

    tr = Tracer(capacity=16)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.call"):
            with tr.span("plan.matvec"):
                with tr.span("executor.select"):
                    jax.numpy.arange(4.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tracing.find_xspace(str(tmp_path))
    names = SPANS + PROGRAM_SPANS
    call, prod, sel = (next(s for s in tracing.host_spans(
        tracing.load_xspace(path, names), names) if s[0] == n)
        for n in ("bench.call", "plan.matvec", "executor.select"))
    assert call[1] <= prod[1] <= sel[1] and sel[2] <= prod[2] <= call[2]
    assert [s[0] for s in tracing.host_spans(
        tracing.load_xspace(path, SPANS), names)] == ["bench.call"]
