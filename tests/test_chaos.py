"""Chaos harness, retry policy, and wire-v4 membership codec tests.

The elastic-fleet PR's contract, pinned from three sides:

  * ``RetryPolicy`` -- deterministic backoff schedules (same seed, same
    sleeps), bounded attempts, wall budgets;
  * wire v4 -- join/leave/welcome/drop frames round-trip, and the
    capacity-proportional shard cut mirrors ``make_hetero_system``'s
    contiguous layout;
  * ``run_chaos`` -- scripted fault storms against a live fleet resolve
    every future (bitwise-verified within the resilience budget,
    degraded-but-correct or structured-failure past it), on every
    transport.
"""

import numpy as np
import pytest

from repro.cluster.chaos import (
    ChaosEvent,
    max_concurrent_failures,
    run_chaos,
    scripted_schedule,
)
from repro.cluster.retry import (
    ENV_RETRY_MAX_ATTEMPTS,
    RetryPolicy,
    default_max_attempts,
)
from repro.cluster.wire import (
    WorkerJoin,
    WorkerLeave,
    _host_virtuals,
    decode_event,
    drop_record,
    hello_record,
    welcome_record,
)


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_backoff_is_deterministic_and_capped(self):
        p = RetryPolicy(base_s=0.1, factor=2.0, max_backoff_s=0.5, seed=7)
        a = [p.backoff_s(i) for i in range(1, 8)]
        b = [p.backoff_s(i) for i in range(1, 8)]
        assert a == b                       # same (seed, attempt) replays
        assert all(x <= 0.5 * 1.25 for x in a)      # cap + jitter bound
        q = RetryPolicy(base_s=0.1, factor=2.0, max_backoff_s=0.5, seed=8)
        assert [q.backoff_s(i) for i in range(1, 8)] != a

    def test_call_retries_then_succeeds(self):
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise ConnectionError("not yet")
            return "ok"

        slept = []
        p = RetryPolicy(max_attempts=5, base_s=0.01, jitter=0.0)
        out = p.call(flaky, sleep=slept.append)
        assert out == "ok"
        assert len(attempts) == 3
        assert slept == [0.01, 0.02]        # exponential, no jitter

    def test_call_exhausts_attempts_and_reraises(self):
        p = RetryPolicy(max_attempts=3, base_s=0.0, jitter=0.0)
        with pytest.raises(ConnectionError, match="always"):
            p.call(lambda: (_ for _ in ()).throw(ConnectionError("always")),
                   sleep=lambda s: None)

    def test_non_retryable_error_propagates_immediately(self):
        calls = []

        def boom():
            calls.append(1)
            raise ValueError("not transient")

        p = RetryPolicy(max_attempts=5, base_s=0.0)
        with pytest.raises(ValueError):
            p.call(boom, sleep=lambda s: None)
        assert len(calls) == 1

    def test_total_timeout_bounds_the_wall_budget(self):
        now = [0.0]

        def clock():
            return now[0]

        def sleep(s):
            now[0] += s

        def always_fail():
            now[0] += 0.05
            raise TimeoutError("slow op")

        p = RetryPolicy(max_attempts=0, base_s=0.1, jitter=0.0,
                        total_timeout_s=1.0)
        with pytest.raises(TimeoutError):
            p.call(always_fail, clock=clock, sleep=sleep)
        assert now[0] <= 1.5                # stopped near the budget

    def test_env_var_sets_attempt_default(self, monkeypatch):
        monkeypatch.delenv(ENV_RETRY_MAX_ATTEMPTS, raising=False)
        assert default_max_attempts() == 5
        monkeypatch.setenv(ENV_RETRY_MAX_ATTEMPTS, "2")
        assert default_max_attempts() == 2
        attempts = []
        p = RetryPolicy(base_s=0.0)         # max_attempts=None -> env

        def fail():
            attempts.append(1)
            raise ConnectionError("x")

        with pytest.raises(ConnectionError):
            p.call(fail, sleep=lambda s: None)
        assert len(attempts) == 2

    def test_dial_retry_gives_up_at_max_dial_s(self):
        import time

        from repro.cluster.worker import run_remote_worker

        t0 = time.perf_counter()
        with pytest.raises((ConnectionError, OSError, TimeoutError)):
            # nothing listens on this port: the dial loop must retry
            # with backoff and give up at the wall cap, not instantly
            # and not forever
            run_remote_worker("127.0.0.1", 1, 0, max_dial_s=1.0)
        dt = time.perf_counter() - t0
        assert dt < 10.0


# ---------------------------------------------------------------------------
# Wire v4: membership records + capacity-proportional shard cut
# ---------------------------------------------------------------------------


class TestWireV4:
    def test_join_leave_records_roundtrip(self):
        j = decode_event(WorkerJoin(worker=7, capacity=3).encode())
        assert isinstance(j, WorkerJoin)
        assert (j.worker, j.capacity) == (7, 3)
        lv = decode_event(WorkerLeave(worker=2, reason="battery").encode())
        assert isinstance(lv, WorkerLeave)
        assert (lv.worker, lv.reason) == (2, "battery")

    def test_hello_welcome_drop_frames(self):
        h = decode_event(hello_record(4, join=True))
        assert h["record"] == "hello"
        assert h["worker"] == 4
        assert h["join"] is True
        w = decode_event(welcome_record(4, plans=2))
        assert (w["record"], w["plans"]) == ("welcome", 2)
        # drop is coordinator->worker: it decodes as a meta dict on the
        # worker side (the serve loop demuxes on record)
        from repro.cluster.wire import decode_record

        meta, _ = decode_record(drop_record(9))
        assert (meta["record"], meta["plan"]) == ("drop", 9)

    def test_host_virtuals_uniform_round_robin(self):
        cut = _host_virtuals(8, 4)
        assert cut == [[0, 4], [1, 5], [2, 6], [3, 7]]

    def test_host_virtuals_capacity_cut_matches_hetero_layout(self):
        from repro.core.assignment import make_hetero_system

        caps = [2, 1, 3]
        sys_ = make_hetero_system(caps)
        cut = _host_virtuals(sys_.n, len(caps), capacities=caps)
        # every virtual id owned exactly once, contiguously per host
        owned = sorted(v for vs in cut for v in vs)
        assert owned == list(range(sys_.n))
        for vs in cut:
            assert vs == list(range(vs[0], vs[0] + len(vs)))
        # the largest-capacity host owns the largest contiguous range,
        # mirroring make_hetero_system's descending-capacity order
        assert len(cut[2]) >= len(cut[0]) >= len(cut[1])

    def test_shard_plan_capacities_cut(self):
        import jax.numpy as jnp

        from repro.api import compile_plan
        from repro.cluster.wire import shard_plan

        rng = np.random.default_rng(0)
        A = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        shards = shard_plan(plan, 3, capacities=[1, 3, 2])
        rows = sorted(r for s_ in shards for r in s_.task_rows)
        assert rows == list(range(plan.n_tasks))    # exact partition
        sizes = {s_.worker: len(s_.task_rows) for s_ in shards}
        # capacity-proportional: host 1 (cap 3) gets the most rows,
        # host 0 (cap 1) the fewest
        assert sizes[1] >= sizes[2] >= sizes[0]


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------


class TestSchedules:
    def test_scripted_schedule_is_deterministic(self):
        a = scripted_schedule(seed=9, n=6, s=2, duration=3.0)
        b = scripted_schedule(seed=9, n=6, s=2, duration=3.0)
        assert [e.__dict__ for e in a] == [e.__dict__ for e in b]
        c = scripted_schedule(seed=10, n=6, s=2, duration=3.0)
        assert [e.__dict__ for e in a] != [e.__dict__ for e in c]

    def test_max_concurrent_failures_counts_overlap(self):
        sched = [
            ChaosEvent(kind="kill", t0=0.0, t1=2.0, worker=0),
            ChaosEvent(kind="hang", t0=1.0, t1=3.0, worker=1),
            ChaosEvent(kind="slow", t0=0.5, t1=2.5, worker=2),  # not a failure
            ChaosEvent(kind="kill", t0=4.0, t1=5.0, worker=3),
        ]
        assert max_concurrent_failures(sched) == 2

    def test_point_events_count_until_reconnect(self):
        sched = [
            ChaosEvent(kind="garble", t0=1.0, worker=0),
            ChaosEvent(kind="kill", t0=1.5, t1=2.0, worker=1),
            ChaosEvent(kind="reconnect", t0=1.2, worker=0),
        ]
        # the garble heals at 1.2, before the kill opens at 1.5
        assert max_concurrent_failures(sched) == 1

    def test_schedule_respects_failure_budget(self):
        for seed in range(5):
            sched = scripted_schedule(seed=seed, n=8, s=2, duration=4.0,
                                      n_events=10, budget=2)
            assert max_concurrent_failures(sched) <= 2


# ---------------------------------------------------------------------------
# Chaos runs
# ---------------------------------------------------------------------------


class TestChaosRuns:
    def test_memory_within_budget_all_resolve_bitwise(self):
        # one of everything, never more than s=2 concurrent failures;
        # run_chaos itself asserts every resolved value is bitwise the
        # local replay of its observed pattern and allclose to the
        # fault-free reference, and that zero futures failed
        storm = [
            ChaosEvent(kind="slow", t0=0.2, t1=1.0, worker=2, delay_s=0.1),
            ChaosEvent(kind="kill", t0=0.5, t1=1.2, worker=1),
            ChaosEvent(kind="join", t0=0.8),
            ChaosEvent(kind="leave", t0=1.1, worker=3),
            ChaosEvent(kind="reconnect", t0=1.6, worker=1),
        ]
        assert max_concurrent_failures(storm) <= 2
        res = run_chaos(storm, transport="memory", n=6, s=2, seed=0,
                        calls=16, spacing_s=0.1, warmup_s=3.0)
        counts = res.counts()
        assert counts["failed"] == 0
        assert counts["clean"] + counts["degraded"] == 16
        assert all(o.bitwise for o in res.outcomes)
        assert all(o.correct for o in res.outcomes)
        # the scripted joiner ended up serving the attached plan
        assert res.joiner_serving is True
        # kills re-homed / re-encoded: the journal shows recovery work
        kinds = {e["kind"] for e in res.events}
        assert "join" in kinds
        assert "death" in kinds or "suspect" in kinds

    def test_memory_past_budget_degrades_never_hangs(self):
        # three concurrent kills against s=2: past the budget.  The
        # fleet must re-encode at reduced resilience (degraded futures,
        # fresh plan id) or fail fast with FleetDegraded -- run_chaos
        # would raise AssertionError on any hang
        storm = [
            ChaosEvent(kind="kill", t0=0.4, t1=2.0, worker=1),
            ChaosEvent(kind="kill", t0=0.45, t1=2.0, worker=2),
            ChaosEvent(kind="kill", t0=0.5, t1=2.0, worker=3),
        ]
        assert max_concurrent_failures(storm) == 3
        res = run_chaos(storm, transport="memory", n=6, s=2, seed=1,
                        calls=16, spacing_s=0.1, warmup_s=3.0)
        counts = res.counts()
        assert sum(counts.values()) == 16
        # something actually happened: recovery work is visible
        assert counts["degraded"] > 0 or counts["failed"] > 0
        # and resolved values were still verified (bitwise + allclose)
        resolved = [o for o in res.outcomes if o.outcome != "failed"]
        assert resolved, "the fleet must keep answering past the budget"
        assert all(o.bitwise and o.correct for o in resolved)
        # the re-encode shrank the encoding to the survivors.  A kill
        # only fires when a task lands inside its window, so how many
        # of the three scripted kills actually fell their worker can
        # shift with scheduler noise -- assert the invariant instead:
        # resilience shrank below the configured s=2, and k follows
        # the policy k' = min(k, hosts') (availability goes last).  n'
        # can exceed the host count: measurably uneven worker rates
        # give the re-cut a capacity-weighted (hetero) layout
        final = [ev for ev in res.events if ev["kind"] == "reencode"
                 and ev["new_plan"] == res.final_plan["plan_id"]][-1]
        assert res.final_plan["n"] < 6
        assert res.final_plan["k"] == min(4, len(final["hosts"]))
        assert res.final_plan["s"] < 2

    def test_recovery_latency_is_reported_per_fault_kind(self):
        storm = [ChaosEvent(kind="kill", t0=0.3, t1=1.2, worker=0),
                 ChaosEvent(kind="reconnect", t0=1.5, worker=0)]
        res = run_chaos(storm, transport="memory", n=4, s=1, seed=2,
                        calls=10, spacing_s=0.1, warmup_s=3.0)
        lat = res.recovery_latency()
        assert "kill" in lat
        assert all(v >= 0 for v in lat["kill"])
        d = res.as_dict()
        assert "p50_s" in d["recovery_latency"]["kill"]
        assert "p99_s" in d["recovery_latency"]["kill"]

    def test_autoscaling_interleaves_with_faults(self):
        """Scripted faults and autoscaling decisions on the same fleet
        at the same time: a kill can land mid scale-up, a join mid
        drain.  run_chaos's invariants (no hang, bitwise parity of
        every resolved value, zero failures within the budget) must
        hold regardless, and the controller's decision log must show
        scaling actually happened in both directions."""
        from repro.scale import SchedulePolicy

        sched = scripted_schedule(seed=7, n=6, s=2, duration=2.0,
                                  n_events=5)
        res = run_chaos(
            sched, transport="memory", n=6, s=2, seed=7,
            calls=16, spacing_s=0.1, warmup_s=3.0,
            autoscale={"policy": SchedulePolicy([(0, 6), (0.5, 8),
                                                 (1.5, 6)]),
                       "min_members": 2, "max_members": 10,
                       "interval_s": 0.1, "cooldown_s": 0.2})
        counts = res.counts()
        assert sum(counts.values()) == 16
        if res.max_concurrent <= 2:
            assert counts["failed"] == 0
        resolved = [o for o in res.outcomes if o.outcome != "failed"]
        assert resolved
        assert all(o.bitwise and o.correct for o in resolved)
        actions = [d["action"] for d in res.autoscale]
        assert "up" in actions and "down" in actions
        # every non-hold decision carries its audit trail
        for d in res.autoscale:
            if d["action"] != "hold":
                assert d["reason"] and d["target"] >= 0

    @pytest.mark.slow
    @pytest.mark.parametrize("transport", ["pipe", "tcp", "shm"])
    def test_process_transports_survive_chaos(self, transport):
        sched = scripted_schedule(seed=3, n=4, s=1, duration=1.5,
                                  n_events=3)
        res = run_chaos(sched, transport=transport, n=4, s=1, seed=3,
                        calls=8, spacing_s=0.15, warmup_s=15.0,
                        suspect_after=1.0)
        counts = res.counts()
        assert sum(counts.values()) == 8
        resolved = [o for o in res.outcomes if o.outcome != "failed"]
        assert resolved
        assert all(o.bitwise and o.correct for o in resolved)
        if res.max_concurrent <= 1:
            assert counts["failed"] == 0
