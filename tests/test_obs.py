"""Observability suite (repro.obs): tracing, wire v5, attribution.

Covers: the disabled representation (no tracer -> zero events AND zero
extra wire fields, so a tracerless v5 peer decodes traced-era frames),
the clock handshake + segment decomposition (traced matvec rounds on
memory/pipe/tcp yield a span tree whose critical-chain segment sum
telescopes to the measured round wall), straggler attribution naming a
seeded slow worker and feeding compute rates into
``worker_capacities(rates=...)``, ring-buffer bounding via
``REPRO_TRACE_BUF``, the ``REPRO_TRACE`` env enabling the process
default, Chrome-trace/Prometheus export validity, and the dual-clock
fleet/router log stamps.
"""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CodedFleet, compile_plan
from repro.cluster.faults import adversarial_faults
from repro.cluster.wire import Task, TaskResult, decode_event
from repro.obs import (
    Tracer,
    attribute,
    chrome_trace,
    default_tracer,
    prometheus_text,
    write_chrome_trace,
)


def block_sparse(rng, t, r, zeros, bs=8, dtype=np.float32):
    mask = rng.random((t // bs, r // bs)) >= zeros
    a = rng.standard_normal((t, r)).astype(dtype)
    return a * np.kron(mask, np.ones((bs, bs), dtype))


@pytest.fixture(scope="module")
def plan():
    rng = np.random.default_rng(5)
    A = jnp.asarray(block_sparse(rng, 128, 96, 0.9))
    return compile_plan(A, scheme="proposed", n=6, s=2, backend="packed")


@pytest.fixture(scope="module")
def xs():
    rng = np.random.default_rng(6)
    return [jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
            for _ in range(6)]


# ---------------------------------------------------------------------------
# disabled tracing: no events, no wire fields
# ---------------------------------------------------------------------------


class TestDisabled:
    def test_no_tracer_no_events(self, plan, xs, monkeypatch):
        monkeypatch.delenv("REPRO_TRACE", raising=False)
        assert default_tracer() is None
        with CodedFleet(6, transport="memory") as fleet:
            assert fleet._tracer is None
            h = fleet.attach(plan)
            for x in xs[:3]:
                h.matvec(x)
            for rnd_key in fleet._rounds:
                pytest.fail(f"round {rnd_key} still inflight")

    def test_untraced_frames_carry_no_trace_fields(self):
        t = Task(round=3, op="matvec", task_row=1, plan=2,
                 payload={"b": np.ones((4, 2), np.float32)})
        assert t.trace == 0
        assert Task.decode(t.encode()).trace == 0
        assert b"trace" not in t.encode()
        res = TaskResult(worker=1, round=3, task_row=1, plan=2,
                         arrays={"y": np.zeros(2, np.float32)})
        enc = res.encode()
        for fld in (b"trace", b"t_recv", b"t_start", b"t_finish"):
            assert fld not in enc
        back = decode_event(enc)
        assert back.trace == 0 and back.t_finish == 0.0

    def test_traced_frames_roundtrip(self):
        t = Task(round=3, op="matvec", task_row=1, plan=2, trace=77,
                 payload={"b": np.ones((4, 2), np.float32)})
        assert Task.decode(t.encode()).trace == 77
        res = TaskResult(worker=1, round=3, task_row=1, plan=2,
                         arrays={"y": np.zeros(2, np.float32)},
                         trace=77, t_recv=1.0, t_start=2.0,
                         t_finish=3.5)
        back = decode_event(res.encode())
        assert (back.trace, back.t_recv, back.t_start, back.t_finish) \
            == (77, 1.0, 2.0, 3.5)


# ---------------------------------------------------------------------------
# the tracer itself
# ---------------------------------------------------------------------------


class TestTracer:
    def test_ring_buffer_bounded(self):
        tr = Tracer(capacity=8)
        for i in range(50):
            tr.instant(f"e{i}")
        assert len(tr) == 8
        assert tr.events()[0]["name"] == "e42"      # oldest evicted

    def test_env_capacity(self, monkeypatch):
        monkeypatch.setenv("REPRO_TRACE_BUF", "16")
        assert Tracer().capacity == 16
        # garbage / nonpositive knobs fail loudly, naming the variable
        monkeypatch.setenv("REPRO_TRACE_BUF", "bogus")
        with pytest.raises(ValueError, match="REPRO_TRACE_BUF"):
            Tracer()
        monkeypatch.setenv("REPRO_TRACE_BUF", "0")
        with pytest.raises(ValueError, match="REPRO_TRACE_BUF"):
            Tracer()

    def test_env_enables_default(self, monkeypatch):
        import repro.obs.trace as trace_mod
        monkeypatch.setattr(trace_mod, "_GLOBAL", None)
        monkeypatch.delenv("REPRO_TRACE", raising=False)
        assert default_tracer() is None
        monkeypatch.setenv("REPRO_TRACE", "0")
        assert default_tracer() is None
        monkeypatch.setenv("REPRO_TRACE", "1")
        tr = default_tracer()
        assert tr is not None and default_tracer() is tr

    def test_span_and_wall_anchor(self):
        tr = Tracer(capacity=32)
        with tr.span("work", cat="test", meta=1):
            time.sleep(0.01)
        (e,) = tr.events()
        assert e["ph"] == "X" and e["dur"] >= 0.009
        assert e["args"] == {"meta": 1}
        wall = tr.wall_of(e["t"])
        assert abs(wall - time.time()) < 5.0


# ---------------------------------------------------------------------------
# traced rounds: span tree + segment telescoping on all transports
# ---------------------------------------------------------------------------


class TestTracedRounds:
    @pytest.mark.parametrize("transport", ["memory", "pipe", "tcp"])
    def test_segments_sum_to_round_wall(self, plan, xs, transport):
        if transport != "memory":
            pytest.importorskip("multiprocessing")
        tr = Tracer(capacity=4096)
        with CodedFleet(6, transport=transport, tracer=tr) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])                         # warm
            for x in xs:
                h.matvec(x)
        rounds = [e for e in tr.events() if e["cat"] == "round"]
        assert len(rounds) >= len(xs)
        devs = []
        for e in rounds[1:]:                        # skip the warm round
            segs = e["args"]["segments"]
            assert set(segs) == {"coord_queue", "wire_out",
                                 "worker_queue", "compute", "wire_back",
                                 "decode_wait", "decode"}
            wall = e["dur"]
            devs.append(abs(sum(segs.values()) - wall)
                        - max(0.10 * wall, 2e-3))
        assert len(devs) >= len(xs) - 1
        # clock-offset error (one-way hello latency) shows up in the
        # clamped segment sum; under parallel-suite load a single
        # round's offset can be noisy, so assert on the typical round
        # (the strict every-round 10% criterion runs in BENCH_obs)
        assert float(np.median(devs)) <= 0.0, devs
        # every traced round's spans share its trace id
        for e in rounds:
            tid = e["trace"]
            kin = [v for v in tr.events() if v["trace"] == tid]
            assert {v["name"] for v in kin} >= {"fleet.launch",
                                                "compute", "decode",
                                                "round"}

    def test_worker_spans_on_worker_tracks(self, plan, xs):
        tr = Tracer()
        with CodedFleet(6, tracer=tr) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
        tracks = {e["track"] for e in tr.events()
                  if e["name"] == "compute"}
        assert tracks and all(t.startswith("worker-") for t in tracks)


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


class TestAttribution:
    def test_names_seeded_slow_worker(self, plan, xs):
        slow = 3
        tr = Tracer()
        faults = adversarial_faults([slow], slowdown=60.0,
                                    time_scale=2e-3)
        with CodedFleet(6, transport="memory", faults=faults,
                        tracer=tr) as fleet:
            h = fleet.attach(plan)
            for x in xs * 2:
                h.matvec(x)
                # pacing: healthy workers drain their inboxes between
                # rounds, so only the injected straggler stays behind
                time.sleep(0.01)
            rep = attribute(tr.events())
            assert rep.rounds
            assert rep.suspects()[0] == slow
            s = rep.workers[slow]
            assert s.decoded_without + s.wasted_tasks > 0
            # attribution rates feed capacity quantization: the slow
            # worker must land on the lowest measured level
            rates = rep.compute_rates()
            if slow in rates:
                caps = fleet.worker_capacities(
                    workers=sorted(rep.workers), rates=rates)
                by_w = dict(zip(sorted(rep.workers), caps))
                assert by_w[slow] == min(caps)

    def test_wasted_and_decoded_without_accounting(self, plan, xs):
        tr = Tracer()
        with CodedFleet(6, tracer=tr) as fleet:
            h = fleet.attach(plan)
            for x in xs[:4]:
                h.matvec(x)
        rep = attribute(tr.events())
        # s=2 redundancy: every round decodes from k=4 of 6 workers, so
        # per round 2 workers are skipped or wasted
        assert sum(s.decoded_without + s.wasted_tasks
                   for s in rep.workers.values()) >= len(rep.rounds)
        assert rep.wasted_work() >= 0.0
        assert rep.table()      # renders without error

    def test_attribute_empty(self):
        rep = attribute([])
        assert rep.rounds == [] and rep.workers == {}
        assert rep.suspects() == []
        assert rep.compute_rates() == {}


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------


class TestExport:
    def test_chrome_trace_valid(self, plan, xs, tmp_path):
        tr = Tracer()
        with CodedFleet(6, tracer=tr) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
            fleet._log_event("probe")   # exercise the log-merge path
            path = tmp_path / "trace.json"
            n = write_chrome_trace(str(path), tr, fleet=fleet)
        doc = json.loads(path.read_text())
        assert len(doc["traceEvents"]) == n > 0
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases <= {"M", "X", "i"}
        for e in doc["traceEvents"]:
            assert "ts" in e or e["ph"] == "M"
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "fleet" in names and "fleet-log" in names

    def test_chrome_trace_empty(self):
        doc = chrome_trace([])
        assert json.loads(json.dumps(doc))["traceEvents"]

    def test_prometheus_text(self, plan, xs):
        tr = Tracer()
        with CodedFleet(6, tracer=tr) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
            text = prometheus_text(fleet=fleet, tracer=tr)
        assert "repro_fleet_n_live 6" in text
        assert "repro_trace_buffer_capacity" in text
        for line in text.strip().splitlines():
            name, val = line.rsplit(" ", 1)
            float(val)          # every exposition line is name value


# ---------------------------------------------------------------------------
# dual-clock log stamps (satellites a+b)
# ---------------------------------------------------------------------------


class TestDualClockLogs:
    def test_fleet_event_log_stamps_both_clocks(self, plan):
        with CodedFleet(6) as fleet:
            fleet.attach(plan)
            fleet._log_event("probe", detail=1)
            recs = [e for e in fleet.event_log if e["kind"] == "probe"]
        (e,) = recs
        assert abs(e["t"] - time.time()) < 5.0
        assert abs(e["t_mono"] - time.perf_counter()) < 5.0

    def test_router_dispatch_log_stamps_both_clocks(self, plan, xs):
        from repro.serve.router import Router
        router = Router()
        try:
            router.register("head", plan, replicas=1, n_workers=6)
            router.call("head", xs[0], tenant="t")
            log = router.dispatch_log("head")
        finally:
            router.close()
        assert log
        e = log[-1]
        assert {"t", "t_mono", "tenant", "cols", "calls", "width",
                "replica", "endpoint"} <= set(e)
        assert abs(e["t"] - time.time()) < 5.0
        assert abs(e["t_mono"] - time.perf_counter()) < 5.0


# ---------------------------------------------------------------------------
# the profiler sink, counters, and the coded plan path's own spans
# ---------------------------------------------------------------------------


def _profile(tmp_path, body):
    """Runs ``body()`` under the JAX profiler at the benchmark's options
    and returns the host plane's events as (name, start, end)."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def _count_annotations(monkeypatch) -> list[str]:
    """The names of the profiler annotations the tracer opens from now on."""
    import repro.obs.trace as trace_mod

    opened = []

    class Counting:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    return opened


class TestProfilerSink:
    def test_spans_land_in_the_profiler_trace_nested(self, tmp_path):
        tr = Tracer(capacity=32)

        def body():
            with tr.span("outer.span"):
                with tr.span("inner.span"):
                    time.sleep(0.002)

        events = _profile(tmp_path, body)
        (outer,) = [e for e in events if e[0] == "outer.span"]
        (inner,) = [e for e in events if e[0] == "inner.span"]
        assert _inside(inner, outer) and inner[2] - inner[1] >= 1.5e6
        # the ring keeps both as before
        assert [e["name"] for e in tr.events()] == ["inner.span",
                                                    "outer.span"]

    def test_complete_stays_in_the_ring(self, tmp_path):
        tr = Tracer(capacity=8)

        def body():
            t0 = time.perf_counter()
            tr.complete("past.span", t0 - 1.0, t0)

        events = _profile(tmp_path, body)
        assert not [e for e in events if e[0] == "past.span"]
        assert [e["name"] for e in tr.events()] == ["past.span"]

    def test_no_tracer_opens_no_annotation(self, monkeypatch):
        import repro.obs.trace as trace_mod

        opened = _count_annotations(monkeypatch)
        assert trace_mod.traced_call(None, "x.stage", lambda a: a + 1, 1) == 2
        with trace_mod.optional_span(None, "x.setup"):
            pass
        assert opened == []
        tr = Tracer(capacity=8)
        assert trace_mod.traced_call(tr, "x.stage", lambda a: a + 1, 1) == 2
        with trace_mod.optional_span(tr, "x.setup"):
            pass
        assert opened == ["x.stage", "x.setup"]


class TestCounters:
    def test_count_and_snapshot(self):
        tr = Tracer(capacity=8)
        tr.count("a.b")
        tr.count("a.b", 4)
        snap = tr.counters()
        tr.count("a.b")
        assert snap["a.b"] == 5 and tr.counters()["a.b"] == 6
        tr.clear()                          # the ring, not the counters
        assert tr.counters()["a.b"] == 6

    def test_lowerings_count_a_fresh_jit_once(self):
        import jax
        from repro.obs import LOWERINGS

        x = jnp.arange(7.0)
        x.block_until_ready()
        tr = Tracer(capacity=8)
        f = jax.jit(lambda v: v * 3.0 + 1.0)
        before = tr.counters().get(LOWERINGS, 0)
        f(x).block_until_ready()
        first = tr.counters()[LOWERINGS]
        f(x).block_until_ready()
        assert first - before == 1
        assert tr.counters()[LOWERINGS] == first     # the repeat lowers none


@pytest.fixture
def process_tracer(monkeypatch):
    """``REPRO_TRACE=1`` with a fresh process tracer."""
    import repro.obs.trace as trace_mod

    tr = Tracer(capacity=4096)
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setattr(trace_mod, "_GLOBAL", tr)
    return tr


def _by_name(events, name):
    return [(e["name"], e["t"], e["t"] + e["dur"]) for e in events
            if e["name"] == name]


class TestPlanSpans:
    """``compile_plan`` and the kernel path of one MV and one MM call
    record the program's spans (``PROGRAM_SPANS``), children inside their
    parents."""

    def test_matvec_spans(self, process_tracer):
        from repro.obs import PROGRAM_SPANS

        rng = np.random.default_rng(3)
        A = jnp.asarray(block_sparse(rng, 128, 96, 0.5))
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="pallas-interpret")
        setup = process_tracer.events()
        (compile_span,) = _by_name(setup, "plan.compile")
        for name in ("plan.encode", "plan.pack", "plan.prewarm"):
            (span,) = _by_name(setup, name)
            assert _inside(span, compile_span), name
        process_tracer.clear()
        done = np.ones(6, bool)
        done[[1, 4]] = False
        x = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
        for _ in range(2):
            plan.matvec(x, done).block_until_ready()
        events = process_tracer.events()
        calls = _by_name(events, "plan.matvec")
        assert len(calls) == 2
        for stage in ("executor.select", "executor.worker",
                      "executor.decode", "executor.output"):
            spans = _by_name(events, stage)
            assert len(spans) == 2, stage
            assert all(_inside(s, c) for s, c in zip(spans, calls)), stage
        assert {e["name"] for e in setup + events} <= set(PROGRAM_SPANS)

    def test_matmat_spans(self, process_tracer):
        rng = np.random.default_rng(4)
        A = jnp.asarray(block_sparse(rng, 96, 64, 0.5))
        plan = compile_plan(A, scheme="proposed", n=8, k_A=2, k_B=2,
                            backend="pallas-interpret")
        process_tracer.clear()
        B = jnp.asarray(rng.standard_normal((96, 40)), jnp.float32)
        done = np.ones(8, bool)
        done[[0, 5]] = False
        plan.matmat(B, done).block_until_ready()
        events = process_tracer.events()
        (call,) = _by_name(events, "plan.matmat")
        k = plan.k
        workers = _by_name(events, "executor.worker")
        assert len(workers) == k
        # the decode-cache lookup, then one per worker
        assert len(_by_name(events, "executor.select")) == k + 1
        for name in ("plan.encode_b", "executor.decode", "executor.select",
                     "executor.worker", "executor.output"):
            assert all(_inside(s, call) for s in _by_name(events, name)), name
        # the executor's output reshape and the plan's assembly
        assert len(_by_name(events, "executor.output")) == 2

    def test_untraced_plan_records_nothing(self, monkeypatch):
        import repro.obs.trace as trace_mod

        monkeypatch.delenv("REPRO_TRACE", raising=False)
        spy = Tracer(capacity=8)
        monkeypatch.setattr(trace_mod, "_GLOBAL", spy)
        opened = _count_annotations(monkeypatch)
        rng = np.random.default_rng(5)
        A = jnp.asarray(block_sparse(rng, 128, 96, 0.5))
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="pallas-interpret")
        assert plan._tracer is None and plan.executor._tracer is None
        plan.matvec(jnp.ones((2, 128), jnp.float32)).block_until_ready()
        assert len(spy) == 0 and opened == []
