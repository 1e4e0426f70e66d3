"""repro.scale: the autoscaling controller, policies and pools.

The controller tests are fully deterministic: a fake pool, a scripted
sensor and explicit ``step(now=...)`` ticks -- no threads, no sleeps,
no wall clock.  Hysteresis (cooldowns, watermark clamps, the
resilience-floor override), burst-up/gentle-down asymmetry and the
decision/trace logs are all asserted tick by tick.

Integration tests then close the real loop on live targets: a
``LocalPool`` growing a memory fleet (with ``grow_encodings`` the
re-encode turns new workers into capacity: ``k`` grows, ``s`` holds),
a ``ReplicaPool`` growing a router endpoint under a paused backlog,
and a ``RemotePool`` dialing standalone ``--connect`` workers into a
coordinator-mode tcp fleet.  Every value served across a scale event
is checked against the fault-free reference -- elasticity is not
allowed to cost correctness.
"""

import os
import socket
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CodedFleet, compile_plan
from repro.cluster import worker_env
from repro.obs import Tracer
from repro.scale import (
    Autoscaler,
    LatencySloPolicy,
    LocalPool,
    ProvisionError,
    QueueDepthPolicy,
    RemotePool,
    ReplicaPool,
    ScaleController,
    ScaleSnapshot,
    SchedulePolicy,
    WorkerPool,
)
from repro.scale.policy import (
    default_high_watermark,
    default_low_watermark,
    default_max_members,
    default_min_members,
)
from repro.serve import Router


def block_sparse(rng, t, r, zeros, bs=8, dtype=np.float32):
    mask = rng.random((t // bs, r // bs)) >= zeros
    a = rng.standard_normal((t, r)).astype(dtype)
    return a * np.kron(mask, np.ones((bs, bs), dtype))


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(11)
    t, r = 256, 144
    A = jnp.asarray(block_sparse(rng, t, r, 0.98))
    xs = [np.asarray(rng.standard_normal(t), np.float32)
          for _ in range(8)]
    return A, xs


def snap(t=0.0, size=1, backlog=0.0, inflight=0.0, lat=None, floor=1):
    return ScaleSnapshot(t=t, size=size, backlog=backlog,
                         inflight=inflight, lat_ewma_ms=lat, floor=floor)


# ---------------------------------------------------------------------------
# policies (pure: one snapshot in, a desired size out)
# ---------------------------------------------------------------------------


class TestPolicies:
    def test_queue_depth_scales_to_backlog(self):
        p = QueueDepthPolicy(high=8, low=1)
        # 40 queued over 1 member: jump straight to ceil(40/8) = 5
        assert p.target(snap(size=1, backlog=40)) == 5
        # between the watermarks: no opinion
        assert p.target(snap(size=5, backlog=20)) is None
        # idle: shrink one member at a time
        assert p.target(snap(size=5, backlog=0)) == 4
        # low backlog but work still in flight: hold
        assert p.target(snap(size=5, backlog=0, inflight=3)) is None

    def test_queue_depth_validates_watermarks(self):
        with pytest.raises(ValueError, match="below"):
            QueueDepthPolicy(high=4, low=4)

    def test_latency_slo(self):
        p = LatencySloPolicy(slo_ms=100.0, shrink_frac=0.5, low=1)
        assert p.target(snap(size=2, lat=250.0, backlog=9)) == 3
        # inside the SLO but not comfortably: hold
        assert p.target(snap(size=3, lat=80.0, backlog=0)) is None
        # comfortably inside + quiet queue: shrink
        assert p.target(snap(size=3, lat=20.0, backlog=0)) == 2
        # no latency measured yet, empty queue: shrink is still safe
        assert p.target(snap(size=3, lat=None, backlog=0)) == 2
        with pytest.raises(ValueError, match="slo_ms"):
            LatencySloPolicy(slo_ms=0)

    def test_schedule_policy_steps_on_snapshot_time(self):
        p = SchedulePolicy([(0, 2), (10, 6), (20, 3)])
        assert p.target(snap(t=100.0)) == 2        # t0 anchors here
        assert p.target(snap(t=105.0)) == 2
        assert p.target(snap(t=110.0)) == 6
        assert p.target(snap(t=125.0)) == 3
        with pytest.raises(ValueError):
            SchedulePolicy([])

    def test_env_knobs_strictly_parsed(self, monkeypatch):
        monkeypatch.setenv("REPRO_SCALE_HIGH", "12")
        assert default_high_watermark() == 12
        monkeypatch.setenv("REPRO_SCALE_LOW", "0")     # 0 is legitimate
        assert default_low_watermark() == 0
        monkeypatch.setenv("REPRO_SCALE_HIGH", "bogus")
        with pytest.raises(ValueError, match="REPRO_SCALE_HIGH"):
            default_high_watermark()
        monkeypatch.setenv("REPRO_SCALE_MAX_WORKERS", "-3")
        with pytest.raises(ValueError, match="REPRO_SCALE_MAX_WORKERS"):
            default_max_members()
        monkeypatch.setenv("REPRO_SCALE_MIN_WORKERS", "0")
        with pytest.raises(ValueError, match="REPRO_SCALE_MIN_WORKERS"):
            default_min_members()


# ---------------------------------------------------------------------------
# the controller, driven tick by tick with a fake clock + pool
# ---------------------------------------------------------------------------


class FakePool(WorkerPool):
    kind = "fake"

    def __init__(self, size=1, fail_provision=False):
        super().__init__()
        self._members = list(range(size))
        self._next = size
        self.fail_provision = fail_provision

    def members(self):
        return list(self._members)

    def provision(self):
        if self.fail_provision:
            self._count("provision_failures")
            raise ProvisionError("scripted provision failure")
        w, self._next = self._next, self._next + 1
        self._members.append(w)
        self._count("provisioned")
        return w

    def decommission(self, member):
        self._members.remove(member)
        self._count("decommissioned")


def make_controller(pool, policy, signal, **kw):
    """Controller whose sensor reads the mutable ``signal`` dict and
    whose clock would *fail* if consulted -- every test tick must pass
    ``now=`` explicitly (determinism is load-bearing)."""

    def sensor(now):
        return ScaleSnapshot(t=now, size=pool.size(), **signal)

    def no_clock():
        raise AssertionError("controller consulted the wall clock")

    kw.setdefault("cooldown_s", 1.0)
    return ScaleController(pool, policy, sensor, clock=no_clock, **kw)


class TestController:
    def test_burst_up_then_cooldown(self):
        pool = FakePool(size=1)
        sig = {"backlog": 40.0}
        c = make_controller(pool, QueueDepthPolicy(high=8, low=1), sig,
                            min_members=1, max_members=8, max_step_up=2)
        d = c.step(now=0.0)
        # wants ceil(40/8)=5 but the burst cap admits 2 per tick
        assert (d.action, d.target, d.applied) == ("up", 5, 2)
        assert pool.size() == 3
        # the next tick is inside the cooldown: blocked, logged as such
        d = c.step(now=0.5)
        assert (d.action, d.reason) == ("hold", "cooldown")
        assert pool.size() == 3
        d = c.step(now=1.5)                    # cooldown expired
        assert (d.action, d.applied) == ("up", 2)
        assert pool.size() == 5

    def test_scale_down_one_member_per_tick_newest_first(self):
        pool = FakePool(size=4)
        sig = {"backlog": 0.0}
        c = make_controller(pool, QueueDepthPolicy(high=8, low=1), sig,
                            min_members=1, max_members=8)
        d = c.step(now=0.0)
        assert (d.action, d.applied) == ("down", -1)
        assert pool.members() == [0, 1, 2]     # newest went first
        d = c.step(now=10.0)
        assert pool.members() == [0, 1]

    def test_clamps_to_min_and_max(self):
        pool = FakePool(size=2)
        sig = {"backlog": 10_000.0}
        c = make_controller(pool, QueueDepthPolicy(high=8, low=1), sig,
                            min_members=2, max_members=4, max_step_up=8)
        d = c.step(now=0.0)
        assert d.target == 4 and pool.size() == 4
        sig["backlog"] = 0.0
        c.step(now=10.0)
        c.step(now=20.0)
        d = c.step(now=30.0)
        # the floor: pool never shrinks below min_members
        assert pool.size() == 2
        assert (d.action, d.reason) == ("hold", "at-target")

    def test_floor_restore_outranks_policy_and_cooldown_reason(self):
        pool = FakePool(size=1)
        sig = {"backlog": 0.0, "floor": 3}     # fleet.min_workers = 3
        c = make_controller(pool, QueueDepthPolicy(high=8, low=1), sig,
                            min_members=1, max_members=8, max_step_up=4)
        d = c.step(now=0.0)
        # deaths dropped the roster below the resilience floor: the
        # controller restores it even though the load says shrink
        assert (d.action, d.reason, d.applied) == ("up", "floor", 2)
        assert pool.size() == 3

    def test_provision_failure_is_logged_not_fatal(self):
        pool = FakePool(size=1, fail_provision=True)
        sig = {"backlog": 100.0}
        c = make_controller(pool, QueueDepthPolicy(high=8, low=1), sig,
                            min_members=1, max_members=8)
        d = c.step(now=0.0)
        assert d.action == "up" and not d.ok
        assert "scripted provision failure" in d.error
        assert c.counters["errors"] == 1
        # the loop keeps going: the next post-cooldown tick retries
        pool.fail_provision = False
        d = c.step(now=5.0)
        assert d.ok and d.applied > 0

    def test_every_action_lands_in_tracer_and_decision_log(self):
        tr = Tracer(capacity=64)
        pool = FakePool(size=1)
        sig = {"backlog": 40.0}
        c = make_controller(pool, QueueDepthPolicy(high=8, low=1), sig,
                            min_members=1, max_members=8, max_step_up=8,
                            tracer=tr)
        c.step(now=0.0)
        sig["backlog"] = 0.0
        c.step(now=10.0)
        c.step(now=10.5)                       # cooldown hold
        log = c.decision_log()
        assert [d["action"] for d in log] == ["up", "down", "hold"]
        marks = [e for e in tr.events() if e["name"] == "scale.decision"]
        assert [m["args"]["action"] for m in marks] == ["up", "down"]
        assert marks[0]["args"]["applied"] == 4
        m = c.metrics()
        assert m["counters"]["ups"] == 1 and m["counters"]["downs"] == 1
        assert m["last_decision"]["reason"] == "cooldown"
        assert m["pool"]["kind"] == "fake"

    def test_schedule_policy_full_sequence(self):
        pool = FakePool(size=2)
        c = make_controller(pool, SchedulePolicy([(0, 2), (5, 6), (9, 4)]),
                            {}, min_members=1, max_members=8,
                            max_step_up=8, cooldown_s=0.0)
        assert c.step(now=0.0).action == "hold"
        assert c.step(now=5.0).applied == 4
        assert c.step(now=9.0).applied == -1
        assert c.step(now=9.1).applied == -1
        assert pool.size() == 4
        assert c.step(now=9.2).action == "hold"


# ---------------------------------------------------------------------------
# pools + Autoscaler against live targets
# ---------------------------------------------------------------------------


class TestLocalPoolAndFleet:
    def test_provision_decommission_roundtrip(self, operands):
        A, xs = operands
        plan = compile_plan(A, scheme="proposed", n=4, s=1,
                            backend="packed")
        with CodedFleet(4) as fleet:
            fleet.attach(plan)
            pool = LocalPool(fleet)
            w = pool.provision()
            assert w in fleet.live_workers() and pool.size() == 5
            pool.decommission(w)
            assert w not in fleet.live_workers() and pool.size() == 4
            m = pool.metrics()
            assert m["provisioned"] == 1 and m["decommissioned"] == 1

    def test_autoscaler_grows_encoding_into_capacity(self, operands):
        A, xs = operands
        plan = compile_plan(A, scheme="proposed", n=4, s=1,
                            backend="packed")
        with CodedFleet(4, grow_encodings=True) as fleet:
            h = fleet.attach(plan)
            ref = np.asarray(h.matvec(xs[0]))
            scaler = Autoscaler(fleet,
                                policy=SchedulePolicy([(0, 4), (1, 6)]),
                                min_members=2, max_members=8,
                                cooldown_s=0.0)
            assert scaler.step(now=0.0).action == "hold"
            d = scaler.step(now=2.0)
            assert (d.action, d.applied) == ("up", 2)
            assert len(fleet.live_workers()) == 6
            deadline = time.time() + 15
            while time.time() < deadline and h.plan.n <= plan.n:
                time.sleep(0.02)
            # growth preserved the absolute straggler budget and grew
            # k, shrinking each worker's omega/k share: capacity
            assert h.plan.n > plan.n
            assert h.plan.k > plan.k
            assert h.plan.s >= plan.s
            got = np.asarray(h.matvec(xs[0]))
            np.testing.assert_allclose(got, ref, atol=1e-3, rtol=1e-3)
            scaler.close()

    def test_autoscaler_start_close_lifecycle(self, operands):
        A, xs = operands
        plan = compile_plan(A, scheme="proposed", n=4, s=1,
                            backend="packed")
        with CodedFleet(4) as fleet:
            fleet.attach(plan)
            with Autoscaler(fleet, policy=QueueDepthPolicy(high=8, low=1),
                            interval_s=0.02) as scaler:
                deadline = time.time() + 10
                while time.time() < deadline \
                        and scaler.metrics()["counters"]["ticks"] < 3:
                    time.sleep(0.02)
                assert scaler.metrics()["counters"]["ticks"] >= 3
            with pytest.raises(RuntimeError, match="closed"):
                scaler.controller.start()

    def test_autoscaler_rejects_unknown_target(self):
        with pytest.raises(TypeError, match="autoscale"):
            Autoscaler(object())


class TestReplicaPoolAndRouter:
    def test_backlog_scales_replicas_up_and_down(self, operands):
        A, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with Router() as router:
            router.register("head", plan, replicas=1, n_workers=6)
            scaler = Autoscaler(router, endpoint="head",
                                policy=QueueDepthPolicy(high=8, low=1),
                                n_workers=6, min_members=1, max_members=3,
                                cooldown_s=0.0)
            router.pause()                     # build a visible backlog
            futs = [router.submit("head", xs[i % len(xs)])
                    for i in range(30)]
            d = scaler.step(now=0.0)
            assert d.action == "up" and scaler.pool.size() == 3
            router.resume()
            ref = np.asarray(plan.matvec(jnp.asarray(xs[0])))
            vals = [np.asarray(f.result(60)) for f in futs]
            np.testing.assert_allclose(vals[0], ref, atol=1e-3, rtol=1e-3)
            # drained: the scaler decommissions back to the floor, one
            # replica per tick, without failing a single future
            for i, now in enumerate((1.0, 2.0, 3.0)):
                scaler.step(now=now)
            assert scaler.pool.size() == 1
            assert all(f.done() for f in futs)
            scaler.close()

    def test_last_replica_is_protected(self, operands):
        A, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with Router() as router:
            router.register("head", plan, replicas=1, n_workers=6)
            pool = ReplicaPool(router, "head", n_workers=6)
            with pytest.raises(ProvisionError, match="last live replica"):
                pool.decommission(pool.members()[0])


class TestRemotePool:
    def test_dials_standalone_workers(self, operands):
        A, xs = operands
        plan = compile_plan(A, scheme="proposed", n=4, s=1,
                            backend="packed")
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = worker_env({
            **os.environ,
            "PYTHONPATH": os.pathsep.join(
                ["src"] + os.environ.get("PYTHONPATH", "").split(
                    os.pathsep)).rstrip(os.pathsep)})
        procs = []

        def launch(worker_id, port_):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.cluster.worker",
                 "--connect", f"127.0.0.1:{port_}", "--id",
                 str(worker_id)],
                env=env, cwd=root))

        for w in range(2):                     # the initial roster dials
            launch(w, port)
        try:
            with CodedFleet(2, transport="tcp",
                            transport_opts={"spawn": False,
                                            "port": port}) as fleet:
                h = fleet.attach(plan)
                ref = np.asarray(h.matvec(xs[0]))
                pool = RemotePool(fleet, launch)
                w = pool.provision()
                assert w == 2 and pool.size() == 3
                got = np.asarray(h.matvec(xs[1]))
                want = np.asarray(plan.matvec(jnp.asarray(xs[1])))
                np.testing.assert_allclose(got, want, atol=1e-3,
                                           rtol=1e-3)
                pool.decommission(w)
                assert pool.size() == 2
                np.testing.assert_allclose(
                    np.asarray(h.matvec(xs[0])), ref, atol=1e-3,
                    rtol=1e-3)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()

    def test_rejects_non_tcp_fleet(self):
        with CodedFleet(2) as fleet:
            with pytest.raises(ValueError, match="tcp"):
                RemotePool(fleet, lambda w, p: None)
