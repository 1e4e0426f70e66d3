"""The reduction from a profiler trace to busy time, kernel time, idle
share and the breakdown, on a trace recorded on a TPU v5e (a 1 s window
of the matrix-vector cell, its zeros then in 8 x 8 tiles, kept in the
compact form)."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tracing
from bench.harness import SPANS

DATA = Path(__file__).resolve().parent / "data" / "trace_mv8.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def summary(recorded):
    return tracing.summarize(recorded, SPANS)


def test_recorded_trace_is_small():
    assert DATA.stat().st_size < 1 << 20


def test_union_merges_overlaps_and_touching():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [
        (0, 4), (5, 7), (8, 9)]


def test_short_names():
    assert tracing.short_name(
        "%worker_kernel.1 = f32[16128,8]{1,0} custom-call(s32[126,157] %copy)"
    ) == "worker_kernel.1"
    assert tracing.short_name("jit_worker_kernel(17707137995206951037)") \
        == "jit_worker_kernel"
    assert tracing.short_name("bench.call") == "bench.call"


def _synthetic(offset_ns: float):
    """Three closed-loop calls; the device's clock runs ``offset_ns``
    behind the host's."""
    spans, ops, mods, t = [], [], [], 0.0
    for _ in range(3):
        spans += [["bench.choose", t, 100e3], ["bench.call", t + 100e3, 1e6],
                  ["bench.wait", t + 1.1e6, 4e6]]
        start = t + 1.3e6 - offset_ns           # device clock
        mods.append(["jit_worker_kernel", start, 3e6])
        ops.append(["worker_kernel.1", start, 3e6])
        t += 5.4e6
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": spans}]}]}


@pytest.mark.parametrize("offset_ns", [0.0, 1.5e6, -1e6])
def test_clock_offset_recovers_a_known_shift(offset_ns):
    s = tracing.summarize(_synthetic(offset_ns), SPANS)
    # device work must end before its wait ends (shift <= +0.8 ms) and
    # start after its call starts (>= -1.2 ms): the middle is -0.2 ms
    assert s.offset_ns - offset_ns == pytest.approx(-0.2e6, abs=1e3)
    assert s.busy_s == pytest.approx(3 * 3e-3)
    assert s.window_s == pytest.approx((2 * 5.4e6 + 5.1e6) * 1e-9)
    assert s.kernel_s({"worker_kernel"}) == pytest.approx(9e-3)


def test_busy_is_the_union_of_device_ops(recorded, summary):
    ops = tracing.device_ops(recorded)["/device:TPU:0"]
    spans = tracing.host_spans(recorded, SPANS)
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    shift = summary.offset_ns
    iv = np.array(sorted((a + shift, b + shift) for _, a, b in ops))
    iv = np.clip(iv, lo, hi)
    covered, end = 0.0, lo
    for a, b in iv:                       # independent sweep
        if b > end:
            covered += b - max(a, end)
            end = b
    assert summary.busy_s == pytest.approx(covered * 1e-9, rel=1e-9)
    assert summary.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < summary.idle_share < 1


def test_recorded_window_reads_as_measured(summary):
    # 38 calls in 1.015 s; the chip was busy 96 % of it (one v5e chip)
    assert summary.window_s == pytest.approx(1.015, abs=0.005)
    assert 100 * summary.idle_share == pytest.approx(3.87, abs=0.05)


def test_offset_leaves_the_chip_idle_between_calls(recorded, summary):
    spans = tracing.host_spans(recorded, SPANS)
    quiet, since = [], None
    for name, start, end in spans:
        if name == "bench.call" and since is not None:
            quiet.append((since, start))
        since = end if name == "bench.wait" else since
    ops = tracing.device_ops(recorded)["/device:TPU:0"]
    shift = summary.offset_ns
    assert 0 < abs(shift) < tracing.MAX_OFFSET_NS and len(quiet) == 37
    for s, e in quiet:
        assert not any(a + shift < e and b + shift > s for _, a, b in ops)
    # unshifted, the device would have run while the host was between calls
    assert any(a < e and b > s for s, e in quiet for _, a, b in ops)


def test_kernel_time_sums_its_own_ops_only(recorded, summary):
    per_call = summary.kernel_s({"worker_kernel"}) / 38
    assert per_call == pytest.approx(8.5e-3, rel=0.01)
    # the copy that reads the kernel's output is not the kernel
    assert summary.kernel_s({"copy"}) > 0
    assert summary.kernel_s({"bcsr_matmul", "worker_kernel"}) == \
        summary.kernel_s({"worker_kernel"})
    assert summary.kernel_s({"cyclic_encode", "encode_kernel"}) == 0


def test_breakdown(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert "jit_worker_kernel/worker_kernel.1" in names
    assert all("/" in n for n in names)
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert {n for n, _ in b["idle_gaps"]} <= set(SPANS) | {"untraced"}
    idle = summary.idle_by_span()
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_no_device_plane_is_an_error():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.call", 0.0, 1e6]]}]}]}
    with pytest.raises(ValueError, match="no device plane"):
        tracing.summarize(host_only, SPANS)


def test_capture_keeps_only_the_named_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.call"):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load_xspace(tracing.find_xspace(str(tmp_path)),
                             ("bench.call",))
    spans = tracing.host_spans(tr, ("bench.call",))
    assert [s[0] for s in spans] == ["bench.call", "bench.call"]
    names = {ev[0] for p in tr["planes"] for line in p["lines"]
             for ev in line["events"]}
    assert names == {"bench.call"}


class _OneMaskStream:
    """Every call in the recorded window did the work of one product of
    a small operand (the reader's wiring, not the cell's count)."""

    def __init__(self):
        from bench import schemes

        a = np.zeros((64, 45), bool)
        a[::3] = True
        self.desc = {"A_nonzero": a, "k_A": 9,
                     "supports_A": schemes.alg1_supports(12, 9, 3),
                     "selected": list(range(9)), "width": 8}

    def work_key(self, spec):
        return 0

    def describe(self, key):
        return self.desc


def test_roofline_readers_on_the_recorded_window(summary):
    from bench import harness, spec

    calls = [harness.Call(0, 0.0, 0.001, 0.002, True)] * 38
    run = harness.Run(stream=_OneMaskStream(), calls=calls, window_s=4.0,
                      setup_s=1.0, phases={}, summary=summary,
                      peaks=spec.peaks("TPU v5 lite"))
    bcsr = spec.load_module("metrics", "bcsr_matmul_roofline").read(run)
    flops, moved = spec.load_module("roofline", "bcsr_matmul").count(
        _OneMaskStream().desc)
    least = max(flops / 197e12, moved / 819e9) * 38
    assert bcsr == pytest.approx(100 * least / summary.kernel_s(
        {"worker_kernel"}))
    assert 0 < bcsr <= 100
    # no encode ran in the window and a matrix-vector call encodes nothing
    assert spec.load_module("metrics", "cyclic_encode_roofline").read(
        run) is None
    idle = spec.load_module("metrics", "device.idle_share").read(run)
    assert idle == pytest.approx(100 * summary.idle_share)
