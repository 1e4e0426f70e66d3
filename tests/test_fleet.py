"""CodedFleet session suite (repro.api.fleet / repro.cluster.fleet).

Covers: interleaved in-flight rounds across >= 2 attached plans on all
three transports with bitwise parity vs sequential execution, matvec ->
matmat microbatching (coalesced rounds decode each call's columns back
bitwise-identically to solo rounds), ``CodedFuture`` semantics
(``result`` / ``done`` / ``add_done_callback`` / cancellation of queued
calls), bounded-queue backpressure, per-call deadlines failing only the
affected future, ``fleet.close()`` fd/thread leak hygiene (alongside
the existing ServeEngine one), the ``REPRO_FLEET_MAX_INFLIGHT`` env
default, the standalone remote worker entry point
(``python -m repro.cluster.worker --connect``), and the consumer
surfaces sharing one fleet: serve-engine LM head via
``CodedConfig.fleet``, ``CodedMoE`` expert pipelining, and
``CodedAggregator.to_cluster(fleet=...)``.
"""

import concurrent.futures
import itertools
import os
import socket
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CodedFleet, compile_plan
from repro.api.fleet import FleetDegraded, default_max_inflight
from repro.cluster import ScriptedFaults, StragglerFaults, worker_env

TOL = dict(rtol=5e-3, atol=5e-3)


def block_sparse(rng, t, r, zeros, bs=8, dtype=np.float32):
    mask = rng.random((t // bs, r // bs)) >= zeros
    a = rng.standard_normal((t, r)).astype(dtype)
    return a * np.kron(mask, np.ones((bs, bs), dtype))


def all_straggler_masks(n, s):
    for pat in itertools.combinations(range(n), s):
        done = np.ones(n, bool)
        done[list(pat)] = False
        yield done


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(3)
    t, r = 256, 144
    A = jnp.asarray(block_sparse(rng, t, r, 0.98))
    A2 = jnp.asarray(block_sparse(rng, t, 96, 0.98))
    xs = jnp.asarray(rng.standard_normal((8, t)), jnp.float32)
    return A, A2, xs


# ---------------------------------------------------------------------------
# In-flight rounds across plans, all transports
# ---------------------------------------------------------------------------


class TestInterleavedRounds:
    @pytest.mark.parametrize("transport", ["memory", "pipe", "tcp"])
    def test_two_plans_interleaved_bitwise(self, operands, transport):
        if transport != "memory":
            pytest.importorskip("scipy")
        A, A2, xs = operands
        n, s = 6, 2
        p1 = compile_plan(A, scheme="proposed", n=n, s=s, backend="packed")
        p2 = compile_plan(A2, scheme="cyclic31", n=n, s=s, backend="packed")
        masks = list(all_straggler_masks(n, s))[:6]
        with CodedFleet(n, transport=transport, max_inflight=4) as fleet:
            h1 = fleet.attach(p1)
            h2 = fleet.attach(p2)
            # submit everything up front: rounds from both plans are in
            # flight simultaneously, demuxed by (plan, round) on one
            # uniform event stream
            futs = []
            for i, done in enumerate(masks):
                futs.append(("p1", i, done, h1.submit_matvec(xs[i], done)))
                futs.append(("p2", i, done, h2.submit_matvec(xs[i], done)))
            for which, i, done, fut in futs:
                plan = p1 if which == "p1" else p2
                want = np.asarray(plan.matvec(xs[i], jnp.asarray(done)))
                np.testing.assert_array_equal(np.asarray(fut.result()), want)
            assert len(h1.reports) == len(masks)
            assert len(h2.reports) == len(masks)

    def test_matmat_and_aggregate_futures(self):
        rng = np.random.default_rng(5)
        t = 144
        A = jnp.asarray(block_sparse(rng, t, 72, 0.95))
        B = jnp.asarray(block_sparse(rng, t, 48, 0.95))
        mm = compile_plan(A, scheme="proposed", n=12, k_A=3, k_B=3,
                          backend="packed")
        agg = compile_plan(scheme="proposed", n=6, s=2)
        payloads = [{"g": jnp.asarray(rng.standard_normal(16), jnp.float32)}
                    for _ in range(6)]
        with CodedFleet(12, max_inflight=4) as fleet:
            hm = fleet.attach(mm)
            ha = fleet.attach(agg)
            done_mm = np.ones(12, bool)
            done_ag = np.ones(6, bool)
            fm = hm.submit_matmat(B, done_mm)
            fa = ha.submit_aggregate(payloads, done_ag)
            np.testing.assert_array_equal(
                np.asarray(fm.result()),
                np.asarray(mm.matmat(B, jnp.asarray(done_mm))))
            np.testing.assert_allclose(
                np.asarray(fa.result()["g"]),
                np.asarray(agg.aggregate(payloads,
                                         jnp.asarray(done_ag))["g"]),
                rtol=1e-5, atol=1e-5)

    def test_race_mode_pattern_parity(self, operands):
        # race-mode decode must be bitwise the in-process plan under
        # the *observed* pattern the report records
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, max_inflight=2) as fleet:
            h = fleet.attach(plan)
            futs = [h.submit_matvec(xs[i]) for i in range(4)]
            outs = [np.asarray(f.result()) for f in futs]
        # rounds launch in submission order (round ids are monotonic),
        # so sorting reports by round maps each call to its pattern
        # even when completions interleave or calls coalesce
        reports = sorted(h.reports, key=lambda r: r.round)
        assert sum(r.calls for r in reports) == 4
        call_patterns = [r.pattern for r in reports for _ in range(r.calls)]
        for i, (out, pat) in enumerate(zip(outs, call_patterns)):
            want = np.asarray(plan.matvec(xs[i], jnp.asarray(pat)))
            np.testing.assert_array_equal(out, want)


# ---------------------------------------------------------------------------
# Microbatching
# ---------------------------------------------------------------------------


class TestMicrobatching:
    def test_queued_matvecs_coalesce_bitwise(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        # slow the workers so rounds 2..4 are provably queued while
        # round 1 is in flight -> they must coalesce into ONE round
        faults = StragglerFaults(time_scale=1.0, seed=1)
        with CodedFleet(6, max_inflight=1, microbatch=True,
                        faults=faults) as fleet:
            h = fleet.attach(plan)
            futs = [h.submit_matvec(xs[i]) for i in range(4)]
            outs = [np.asarray(f.result()) for f in futs]
        reports = list(h.reports)
        # the queued calls coalesced: strictly fewer rounds than calls
        assert len(reports) <= 2
        assert max(r.calls for r in reports) >= 3
        assert sum(r.calls for r in reports) == 4
        # every call decodes bitwise vs the in-process plan under its
        # round's observed pattern -- coalescing is invisible to values
        call_patterns = [r.pattern for r in reports for _ in range(r.calls)]
        for i, (out, pat) in enumerate(zip(outs, call_patterns)):
            want = np.asarray(plan.matvec(xs[i], jnp.asarray(pat)))
            np.testing.assert_array_equal(out, want)

    def test_microbatch_off_keeps_rounds_solo(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        faults = StragglerFaults(time_scale=1.0, seed=1)
        with CodedFleet(6, max_inflight=1, microbatch=False,
                        faults=faults) as fleet:
            h = fleet.attach(plan)
            futs = [h.submit_matvec(xs[i]) for i in range(3)]
            [f.result() for f in futs]
        assert [r.calls for r in h.reports] == [1, 1, 1]

    def test_column_cap_bounds_coalescing(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        faults = StragglerFaults(time_scale=1.0, seed=1)
        with CodedFleet(6, max_inflight=1, microbatch=True,
                        microbatch_cols=2, faults=faults) as fleet:
            h = fleet.attach(plan)
            futs = [h.submit_matvec(xs[i]) for i in range(5)]
            [f.result() for f in futs]
        # width cap 2: after the first solo round, coalesced rounds
        # stop growing once 2 columns are packed
        assert all(r.calls <= 2 for r in h.reports)
        assert sum(r.calls for r in h.reports) == 5


# ---------------------------------------------------------------------------
# Futures: callbacks, cancellation, deadlines, backpressure
# ---------------------------------------------------------------------------


class TestCodedFuture:
    def test_done_and_callback(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        fired = threading.Event()
        with CodedFleet(6) as fleet:
            h = fleet.attach(plan)
            fut = h.submit_matvec(xs[0])
            fut.add_done_callback(lambda f: fired.set())
            fut.result()
            assert fired.wait(timeout=5)
            assert fut.done() and not fut.cancelled()
            assert fut.exception() is None
            # callbacks added after resolution fire immediately
            late = threading.Event()
            fut.add_done_callback(lambda f: late.set())
            assert late.is_set()

    def test_cancel_queued_call(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        faults = StragglerFaults(time_scale=1.0, seed=1)
        with CodedFleet(6, max_inflight=1, microbatch=False,
                        faults=faults) as fleet:
            h = fleet.attach(plan)
            f1 = h.submit_matvec(xs[0])     # launches immediately
            f2 = h.submit_matvec(xs[1])     # queued behind it
            assert f2.cancel()
            assert f2.cancelled()
            with pytest.raises(concurrent.futures.CancelledError):
                f2.result()
            # the launched round is not cancellable and still resolves
            assert not f1.cancel()
            np.testing.assert_allclose(
                np.asarray(f1.result()), np.asarray(xs[0] @ A), **TOL)
        assert len(h.reports) == 1          # the cancelled call never ran

    def test_deadline_fails_only_its_future(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        slow = StragglerFaults(time_scale=30.0, seed=1)   # ~minutes/task
        with CodedFleet(6, max_inflight=2, faults=slow) as fleet:
            h = fleet.attach(plan)
            doomed = h.submit_matvec(xs[0], np.ones(6, bool), deadline=0.2)
            with pytest.raises(TimeoutError):
                doomed.result()
            assert isinstance(doomed.exception(), TimeoutError)
        # the fleet survives the expiry: nothing else was torn down

    def test_backpressure_bounds_queue(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        faults = StragglerFaults(time_scale=1.0, seed=1)
        with CodedFleet(6, max_inflight=1, microbatch=False, queue_cap=2,
                        faults=faults) as fleet:
            h = fleet.attach(plan)
            t0 = time.perf_counter()
            futs = [h.submit_matvec(xs[i % 8]) for i in range(6)]
            blocked_s = time.perf_counter() - t0
            [f.result() for f in futs]
        # with only 2 unresolved calls admitted at a time, the 6
        # submissions cannot all have been accepted instantly
        assert blocked_s > 0.05


# ---------------------------------------------------------------------------
# Session hygiene
# ---------------------------------------------------------------------------


class TestSessionLifecycle:
    def test_close_joins_fleet_threads(self, operands):
        A, A2, xs = operands
        p1 = compile_plan(A, scheme="proposed", n=6, s=2, backend="packed")
        p2 = compile_plan(A2, scheme="proposed", n=6, s=2, backend="packed")
        with CodedFleet(6) as fleet:
            h1, h2 = fleet.attach(p1), fleet.attach(p2)
            h1.matvec(xs[0])
            h2.matvec(xs[1])
        time.sleep(0.05)
        leftover = [t.name for t in threading.enumerate()
                    if t.name.startswith(("coded-fleet", "cluster-worker",
                                          "cluster-beat"))]
        assert leftover == []

    def test_tcp_close_releases_sockets_and_threads(self, operands):
        import gc
        import warnings

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with warnings.catch_warnings():
            warnings.simplefilter("error", ResourceWarning)
            with CodedFleet(6, transport="tcp") as fleet:
                h = fleet.attach(plan)
                h.matvec(xs[0])
            gc.collect()                # unclosed sockets would warn here
        for t in threading.enumerate():
            assert not t.name.startswith(("coded-fleet", "cluster-tcp",
                                          "cluster-beat", "cluster-worker"))

    def test_detach_keeps_fleet_serving_other_plans(self, operands):
        A, A2, xs = operands
        p1 = compile_plan(A, scheme="proposed", n=6, s=2, backend="packed")
        p2 = compile_plan(A2, scheme="proposed", n=6, s=2, backend="packed")
        with CodedFleet(6) as fleet:
            h1, h2 = fleet.attach(p1), fleet.attach(p2)
            h1.matvec(xs[0])
            h1.detach()
            with pytest.raises(RuntimeError, match="detached"):
                h1.submit_matvec(xs[0])
            np.testing.assert_allclose(np.asarray(h2.matvec(xs[1])),
                                       np.asarray(xs[1] @ A2), **TOL)

    def test_submit_after_close_raises(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        fleet = CodedFleet(6)
        h = fleet.attach(plan)
        fleet.close()
        with pytest.raises(RuntimeError, match="closed"):
            h.submit_matvec(xs[0])

    def test_env_var_sets_inflight_default(self, monkeypatch):
        monkeypatch.delenv("REPRO_FLEET_MAX_INFLIGHT", raising=False)
        assert default_max_inflight() == 8
        monkeypatch.setenv("REPRO_FLEET_MAX_INFLIGHT", "3")
        assert default_max_inflight() == 3
        fleet = CodedFleet(2)
        try:
            assert fleet.max_inflight == 3
        finally:
            fleet.close()

    def test_all_workers_dead_between_rounds_fails_fast(self, operands):
        from repro.cluster import FailStop

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        # every worker dies on its first served task: the round in
        # flight (or the ones after it) must surface the wipeout as a
        # RuntimeError on the future, and later submissions must
        # fail fast instead of hanging forever
        with CodedFleet(6, faults=FailStop(
                {w: 0 for w in range(6)})) as fleet:
            h = fleet.attach(plan)
            with pytest.raises(RuntimeError, match="dead"):
                h.matvec(xs[0], deadline=30.0)
            with pytest.raises(RuntimeError, match="dead"):
                h.submit_matvec(xs[1])

    def test_failstop_requeues_across_plans(self, operands):
        from repro.cluster import FailStop

        A, A2, xs = operands
        p1 = compile_plan(A, scheme="proposed", n=6, s=2, backend="packed")
        p2 = compile_plan(A2, scheme="proposed", n=6, s=2, backend="packed")
        with CodedFleet(6, faults=FailStop({0: 0})) as fleet:
            h1, h2 = fleet.attach(p1), fleet.attach(p2)
            # worker 0 dies serving its first task; BOTH plans' shards
            # held by it must re-home and both plans keep answering
            np.testing.assert_allclose(np.asarray(h1.matvec(xs[0])),
                                       np.asarray(xs[0] @ A), **TOL)
            np.testing.assert_allclose(np.asarray(h2.matvec(xs[1])),
                                       np.asarray(xs[1] @ A2), **TOL)
            np.testing.assert_allclose(np.asarray(h1.matvec(xs[2])),
                                       np.asarray(xs[2] @ A), **TOL)
            total_deaths = sum(r.deaths for r in
                               list(h1.reports) + list(h2.reports))
            assert total_deaths == 1


# ---------------------------------------------------------------------------
# Remote worker entry point (multi-host tcp)
# ---------------------------------------------------------------------------


class TestRemoteWorker:
    @pytest.mark.slow
    def test_remote_workers_join_tcp_fleet(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        # reserve a port for the coordinator so the "remote" workers
        # (separate python processes running the module entry point)
        # know where to dial before the fleet exists
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        env = worker_env({
            **os.environ,
            "PYTHONPATH": os.pathsep.join(
                ["src"] + os.environ.get("PYTHONPATH", "").split(
                    os.pathsep)).rstrip(os.pathsep)})
        procs = [subprocess.Popen(
            [sys.executable, "-m", "repro.cluster.worker",
             "--connect", f"127.0.0.1:{port}", "--id", str(w)],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            for w in range(2)]
        try:
            with CodedFleet(2, transport="tcp",
                            transport_opts={"spawn": False,
                                            "port": port}) as fleet:
                h = fleet.attach(plan)
                done = np.ones(6, bool)
                done[[2, 5]] = False
                got = np.asarray(h.matvec(xs[0], done))
                want = np.asarray(plan.matvec(xs[0], jnp.asarray(done)))
                np.testing.assert_array_equal(got, want)
            for p in procs:
                assert p.wait(timeout=30) == 0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()

    def test_cli_rejects_bad_address(self):
        from repro.cluster.worker import main

        with pytest.raises(SystemExit):
            main(["--connect", "no-port-here", "--id", "0"])


# ---------------------------------------------------------------------------
# Consumer surfaces sharing one fleet
# ---------------------------------------------------------------------------


class TestSharedConsumers:
    def test_engine_and_aggregator_share_one_fleet(self):
        import jax

        from repro.configs import get_smoke_config
        from repro.configs.base import CodedConfig
        from repro.models import build_model
        from repro.parallel.coded_grads import CodedAggregator
        from repro.serve import ServeEngine

        cfg = get_smoke_config("qwen3-14b")
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        with CodedFleet(6, max_inflight=4) as fleet:
            eng = ServeEngine(
                model, params, cfg, batch_size=2, max_len=32,
                coded=CodedConfig(enabled=True, n_workers=6, stragglers=2,
                                  fleet=fleet))
            agg = CodedAggregator.build(6, 2, seed=0)
            agg_handle = agg.to_cluster(fleet=fleet)
            assert agg_handle.fleet is fleet
            assert eng.coded_cluster.fleet is fleet

            hidden = jnp.asarray(rng.standard_normal(
                (2, cfg.d_model)), jnp.float32)
            head = params["embed"].T if cfg.tie_embeddings \
                else params["head"]
            out = eng.coded_logits(hidden)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(hidden @ head), **TOL)

            shard_grads = [
                {"g": jnp.asarray(rng.standard_normal(8), jnp.float32)}
                for _ in range(4)]
            payloads = [agg.worker_payload(w, shard_grads)
                        for w in range(6)]
            done = jnp.asarray(np.ones(6, bool))
            got = np.asarray(agg.aggregate(payloads, done,
                                           cluster=agg_handle)["g"])
            want = np.asarray(agg.aggregate(payloads, done)["g"])
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

            # engine close only DETACHES from the shared fleet; the
            # aggregator keeps serving on the same workers
            eng.close()
            assert eng.coded_cluster is None
            got2 = np.asarray(agg.aggregate(payloads, done,
                                            cluster=agg_handle)["g"])
            np.testing.assert_allclose(got2, want, rtol=1e-5, atol=1e-5)

    def test_coded_moe_pipelines_experts_on_fleet(self):
        import jax

        from repro.configs.base import MoEConfig
        from repro.models.moe import CodedMoE, init_moe_params

        moe = MoEConfig(n_experts=2, top_k=1, d_expert=48)
        p = init_moe_params(jax.random.key(0), 64, moe)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 4, 64)), jnp.float32)
        done = np.ones(6, bool)
        done[[1, 4]] = False
        local = CodedMoE(p, moe, n_workers=6, stragglers=2,
                         backend="packed")
        with CodedFleet(6, max_inflight=4) as fleet:
            dispatched = CodedMoE(p, moe, n_workers=6, stragglers=2,
                                  backend="packed", fleet=fleet)
            o_fleet, aux_f = dispatched(x, jnp.asarray(done))
            o_local, aux_l = local(x, jnp.asarray(done))
            np.testing.assert_array_equal(np.asarray(o_fleet),
                                          np.asarray(o_local))
            np.testing.assert_allclose(float(aux_f), float(aux_l))
            # 3 plans per expert attached and served
            assert len(dispatched.gate[0].reports) == 1
            dispatched.detach()

    def test_trainer_reships_through_fleet_handle(self):
        from repro.train.trainer import TrainConfig, Trainer

        rng = np.random.default_rng(0)
        t, r = 128, 72
        dense = jnp.asarray(rng.standard_normal((t, r)), jnp.float32)
        sparse = jnp.asarray(block_sparse(rng, t, r, 0.995))
        plan = compile_plan(sparse, scheme="proposed", n=6, s=2)
        assert plan.backend == "packed"

        class TinyModel:
            def init(self, key):
                return {"w": dense}

            def train_loss(self, params, batch):
                return jnp.mean(params["w"] ** 2)

        with CodedFleet(6) as fleet:
            handle = fleet.attach(plan)
            shards_before = handle.bytes_shards
            trainer = Trainer(
                TinyModel(),
                __import__("repro.optim.adamw",
                           fromlist=["AdamWConfig"]).AdamWConfig(lr=1e-3),
                TrainConfig(steps=1, retune_every=1, log_every=100),
                coded_plans=[(plan, lambda prm: prm["w"], handle)])
            trainer.fit(lambda start: iter(
                [{"x": np.zeros((1,), np.float32)}] * 4), resume=False)
            assert trainer.retunes and trainer.retunes[0]["changed"]
            assert trainer.retunes[0]["backend"] == "reference"
            assert trainer.retunes[0]["reshipped_bytes"] > 0
            assert handle.bytes_shards > shards_before


# ---------------------------------------------------------------------------
# Elastic membership: live join / graceful leave
# ---------------------------------------------------------------------------


def wait_until(pred, timeout=10.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


class TestElasticMembership:
    def test_add_worker_joins_and_serves(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
            joiner = fleet.add_worker()
            assert joiner in fleet.live_workers()
            assert "join" in [e["kind"] for e in fleet.event_log]
            # ownership rebalanced off the most-loaded hosts: the
            # newcomer actually serves the already-attached plan
            assert wait_until(lambda: any(
                o == joiner for ps in fleet._plans.values()
                for o in ps.owner.values()))
            # and parity survives the re-homed rows
            done = np.ones(6, bool)
            np.testing.assert_array_equal(
                np.asarray(h.matvec(xs[1], done)),
                np.asarray(plan.matvec(xs[1], jnp.asarray(done))))

    def test_remove_worker_drains_without_deaths(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
            fleet.remove_worker(5, drain=True)
            assert 5 not in fleet.live_workers()
            kinds = [e["kind"] for e in fleet.event_log]
            assert "leave" in kinds
            # drain-before-remove: no death notice, no suspicion
            assert "death" not in kinds and "suspect" not in kinds
            # resilience shrank before availability: k preserved
            assert wait_until(lambda: h.plan.n == 5)
            assert (h.plan.k, h.plan.s) == (4, 1)
            np.testing.assert_allclose(np.asarray(h.matvec(xs[1])),
                                       np.asarray(xs[1] @ A), **TOL)
            assert all(r.deaths == 0 for r in h.reports)

    def test_removing_last_worker_refuses(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(1) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
            with pytest.raises(FleetDegraded, match="add a worker"):
                fleet.remove_worker(0)
            # the refused leave left the fleet serving
            np.testing.assert_allclose(np.asarray(h.matvec(xs[1])),
                                       np.asarray(xs[1] @ A), **TOL)

    def test_join_restores_full_resilience_after_loss(self, operands):
        from repro.cluster import FailStop

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, faults=FailStop({0: 0})) as fleet:
            h = fleet.attach(plan)
            pid0 = h.plan_id
            # worker 0 dies serving its first task: the round still
            # answers, then the plan re-encodes for the 5 survivors
            np.testing.assert_allclose(np.asarray(h.matvec(xs[0])),
                                       np.asarray(xs[0] @ A), **TOL)
            assert wait_until(lambda: h.plan.n == 5)
            assert (h.plan.k, h.plan.s) == (4, 1)
            pid_shrunk = h.plan_id
            assert pid_shrunk != pid0
            # a replacement device joins: full strength restored
            fleet.add_worker()
            assert wait_until(lambda: h.plan.n == 6)
            assert (h.plan.k, h.plan.s) == (4, 2)
            assert h.plan_id != pid_shrunk
            np.testing.assert_allclose(np.asarray(h.matvec(xs[1])),
                                       np.asarray(xs[1] @ A), **TOL)

    def test_worker_capacities_quantize_throughput_ewmas(self, operands):
        with CodedFleet(4) as fleet:
            # no measurements yet: everyone is baseline
            assert fleet.worker_capacities([0, 1, 2, 3]) == [1, 1, 1, 1]
            # seeded EWMAs quantize to 1..levels, proportional to the
            # fastest; unmeasured workers get the median live rate
            fleet._rate.update({0: 4.0, 1: 1.0, 2: 2.0})
            fleet._rate_n.update({0: 1, 1: 1, 2: 1})
            # one result per worker is arrival-order noise, not a speed
            assert fleet.worker_capacities([0, 1, 2]) == [1, 1, 1]
            fleet._rate_n.update({0: 3, 1: 3, 2: 3})
            assert fleet.worker_capacities([0, 1, 2]) == [4, 1, 2]
            assert fleet.worker_capacities([0, 1, 2, 3]) == [4, 1, 2, 2]

    def test_reencode_gives_measured_slow_worker_fewer_tiles(
            self, operands):
        """Closing the observe->re-encode loop: with a tracer on the
        fleet, ``observed_rates()`` feeds the measured per-worker
        compute rates into the re-encode's capacity cut, so a worker
        that *measured* slow (not just configured slow) owns strictly
        fewer rows of the new hetero encoding."""
        from repro.cluster.faults import adversarial_faults
        from repro.obs import Tracer, attribute

        A, _, xs = operands
        slow = 0
        plan = compile_plan(A, scheme="proposed", n=12, s=4,
                            backend="packed")
        tr = Tracer(capacity=4096)
        faults = adversarial_faults([slow], slowdown=60.0,
                                    time_scale=2e-3)
        with CodedFleet(6, faults=faults, tracer=tr) as fleet:
            h = fleet.attach(plan)
            for x in list(xs) * 2:
                h.matvec(x)
                # pacing: healthy workers drain between rounds, so
                # only the injected straggler accumulates lag
                time.sleep(0.01)
            rates = fleet.observed_rates()
            assert rates and slow in rates
            assert rates[slow] == min(rates.values())
            # sanity: the rates come from the tracer's round records
            assert attribute(tr.events()).suspects()[0] == slow
            pid0 = h.plan_id
            fleet.remove_worker(5, drain=True)
            assert wait_until(lambda: h.plan_id != pid0)
            # the cut followed the measured speeds: hetero scheme,
            # and the slow worker owns strictly the fewest rows
            assert h.plan.scheme.name == "proposed-hetero"
            owned = {w: 0 for w in fleet.live_workers()}
            for o in h._ps.owner.values():
                owned[o] += 1
            assert all(owned[slow] < owned[w] for w in owned
                       if w != slow)
            np.testing.assert_allclose(np.asarray(h.matvec(xs[1])),
                                       np.asarray(xs[1] @ A), **TOL)

    def test_metrics_track_roster_across_add_remove(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])
            joiner = fleet.add_worker()
            m = fleet.metrics()
            assert m["n_live"] == 7 and joiner in m["live_workers"]
            assert len(m["worker_capacities"]) == 7
            fleet.remove_worker(joiner, drain=True)
            fleet.remove_worker(0, drain=True)
            m = fleet.metrics()
            assert m["n_live"] == 5
            assert joiner not in m["live_workers"]
            assert 0 not in m["live_workers"]
            assert len(m["worker_capacities"]) == 5
            np.testing.assert_allclose(np.asarray(h.matvec(xs[1])),
                                       np.asarray(xs[1] @ A), **TOL)


# ---------------------------------------------------------------------------
# Graceful degradation: floors, shedding, re-encode edges
# ---------------------------------------------------------------------------


class TestGracefulDegradation:
    def test_reencode_is_journaled_under_fresh_plan_id(self, operands):
        from repro.cluster import FailStop

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, faults=FailStop({2: 0})) as fleet:
            h = fleet.attach(plan)
            pid0 = h.plan_id
            np.testing.assert_allclose(np.asarray(h.matvec(xs[0])),
                                       np.asarray(xs[0] @ A), **TOL)
            assert wait_until(lambda: h.plan_id != pid0)
            kinds = [e["kind"] for e in fleet.event_log]
            assert "reencode" in kinds
            # the version that served round 1 stays replayable
            assert h.plan_version(pid0).n == 6

    def test_min_workers_floor_fails_fast(self, operands):
        from repro.cluster import FailStop

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, faults=FailStop({w: 0 for w in range(5)}),
                        min_workers=3) as fleet:
            h = fleet.attach(plan)
            with pytest.raises(FleetDegraded, match="min_workers"):
                h.matvec(xs[0], deadline=30.0)
            # below the floor every later submission fails fast too,
            # and the error names the recovery action
            with pytest.raises(FleetDegraded, match="add_worker"):
                h.submit_matvec(xs[1])
            assert "degraded-floor" in [e["kind"] for e in fleet.event_log]

    def test_shed_admission_rejects_when_saturated(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        slow = StragglerFaults(time_scale=30.0, seed=1)
        with CodedFleet(6, faults=slow, admission="shed", queue_cap=2,
                        max_inflight=1, microbatch=False) as fleet:
            h = fleet.attach(plan)
            f1 = h.submit_matvec(xs[0], np.ones(6, bool), deadline=0.5)
            f2 = h.submit_matvec(xs[1], np.ones(6, bool), deadline=0.5)
            with pytest.raises(FleetDegraded, match="queue_cap") as ei:
                h.submit_matvec(xs[2], np.ones(6, bool))
            assert ei.value.action == "shed"
            for f in (f1, f2):          # shed calls never wedge others
                with pytest.raises(TimeoutError):
                    f.result(timeout=30.0)

    def test_queued_explicit_mask_fails_structured_across_reencode(
            self, operands):
        from repro.cluster import FailStop

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, faults=FailStop({0: 0, 1: 0, 2: 0}),
                        max_inflight=1, microbatch=False) as fleet:
            h = fleet.attach(plan)
            # round 1 kills workers 0-2 -> the plan re-encodes once its
            # rounds drain; the queued explicit-mask call was built in
            # the old version's task coordinates and cannot be rebuilt.
            # The 3 survivors are fewer than k=4, so round 1 answers only
            # after the deaths are seen, and the queued call cannot
            # start on the old version first
            f1 = h.submit_matvec(xs[0])
            f2 = h.submit_matvec(xs[1], np.ones(6, bool))
            np.testing.assert_allclose(np.asarray(f1.result()),
                                       np.asarray(xs[0] @ A), **TOL)
            with pytest.raises(FleetDegraded, match="re-encode") as ei:
                f2.result(timeout=30.0)
            assert ei.value.action == "re-encode"
            # race-mode calls survive the same transition fine
            np.testing.assert_allclose(np.asarray(h.matvec(xs[2])),
                                       np.asarray(xs[2] @ A), **TOL)


# ---------------------------------------------------------------------------
# Two-phase suspicion edge cases, all transports
# ---------------------------------------------------------------------------


class TestSuspicionEdgeCases:
    @pytest.mark.parametrize("transport", ["memory", "pipe", "tcp"])
    def test_partitioned_worker_suspected_not_failed(self, operands,
                                                     transport):
        if transport != "memory":
            pytest.importorskip("scipy")
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        warm = 2.5 if transport == "memory" else 15.0
        epoch = time.time() + warm
        faults = ScriptedFaults(
            windows=[{"kind": "partition", "worker": 0,
                      "t0": 0.0, "t1": 2.0}],
            epoch=epoch)
        with CodedFleet(6, transport=transport, faults=faults,
                        heartbeat_s=0.1, suspect_after=0.4,
                        suspect_grace=10.0, microbatch=False) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])                     # warm before the window
            while time.time() < epoch + 0.6:
                time.sleep(0.02)
            # phase 1: silent but IDLE -- no outstanding rows, so the
            # two-phase rule must neither suspect nor re-home it
            assert 0 in fleet.live_workers()
            assert 0 not in fleet._suspected
            # phase 2: give it outstanding rows mid-partition; a
            # wait-all round cannot finish until the partition heals,
            # and the LONG grace means the late beat un-suspects the
            # worker instead of a spurious fail-stop + requeue
            done = np.ones(6, bool)
            out = np.asarray(h.matvec(xs[1], done, deadline=60.0))
            assert time.time() >= epoch + 1.8   # resolved post-heal
            rep = h.reports[-1]
            assert rep.suspected == 0
            assert rep.deaths == 0
            assert rep.requeues == 0
            np.testing.assert_array_equal(
                out, np.asarray(plan.matvec(xs[1], jnp.asarray(done))))
            assert 0 in fleet.live_workers()
            assert wait_until(lambda: 0 not in fleet._suspected, 5.0)
            assert "death" not in [e["kind"] for e in fleet.event_log]


# ---------------------------------------------------------------------------
# Close robustness: idempotence, mid-round teardown, leak checks
# ---------------------------------------------------------------------------


class TestCloseRobustness:
    @pytest.mark.parametrize("transport", ["memory", "tcp"])
    def test_double_close_is_idempotent(self, operands, transport):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        fleet = CodedFleet(6, transport=transport)
        h = fleet.attach(plan)
        h.matvec(xs[0])
        fleet.close()
        fleet.close()                           # second close is a no-op
        time.sleep(0.05)
        for t in threading.enumerate():
            assert not t.name.startswith(("coded-fleet", "cluster-tcp",
                                          "cluster-beat",
                                          "cluster-worker"))

    def test_close_mid_round_resolves_futures(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        slow = StragglerFaults(time_scale=30.0, seed=1)
        fleet = CodedFleet(6, faults=slow, microbatch=False)
        h = fleet.attach(plan)
        fut = h.submit_matvec(xs[0], np.ones(6, bool))
        time.sleep(0.2)
        fleet.close()                           # round still in flight
        with pytest.raises(RuntimeError, match="closed"):
            fut.result(timeout=10.0)            # resolved, never hangs
        assert fut.done()
        fleet.close()                           # idempotent afterwards
        time.sleep(0.05)
        leftover = [t.name for t in threading.enumerate()
                    if t.name.startswith(("coded-fleet", "cluster-worker",
                                          "cluster-beat"))]
        assert leftover == []

    def test_tcp_close_releases_fds(self, operands):
        import gc

        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")

        def run_once():
            with CodedFleet(4, transport="tcp") as fleet:
                h = fleet.attach(plan)
                h.matvec(xs[0])

        run_once()                              # warm lazy imports/caches
        gc.collect()
        time.sleep(0.2)
        before = len(os.listdir("/proc/self/fd"))
        run_once()
        gc.collect()
        time.sleep(0.2)
        after = len(os.listdir("/proc/self/fd"))
        assert after <= before + 2              # sockets + pipes released


# ---------------------------------------------------------------------------
# Observability + dynamic coalescing (metrics, group submits, idle pump)
# ---------------------------------------------------------------------------


class TestObservability:
    def test_fleet_and_handle_metrics_snapshot(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, max_inflight=4) as fleet:
            h = fleet.attach(plan)
            [h.submit_matvec(xs[i]).result() for i in range(3)]
            m = fleet.metrics()
            assert m["n_live"] == 6 and m["live_workers"] == list(range(6))
            assert m["inflight_rounds"] == 0 and m["queued_calls"] == 0
            assert len(m["worker_capacities"]) == 6
            pm = m["plans"][h.plan_id]
            assert pm["counters"]["submitted"] == 3
            assert pm["counters"]["resolved"] == 3
            assert pm["lat_ewma_ms"] > 0
            hm = h.metrics()                    # the per-handle slice
            assert hm["plan_id"] == h.plan_id
            assert hm["counters"] == pm["counters"]
            assert hm["fleet"]["n_live"] == 6

    def test_metrics_count_shed_and_deadline(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        slow = StragglerFaults(time_scale=20.0, seed=1)
        with CodedFleet(6, max_inflight=1, queue_cap=1, admission="shed",
                        microbatch=False, faults=slow) as fleet:
            h = fleet.attach(plan)
            futs = [h.submit_matvec(xs[0], deadline=0.05)]
            shed = 0
            for _ in range(8):                  # saturate the bounded queue
                try:
                    futs.append(h.submit_matvec(xs[0], deadline=0.05))
                except FleetDegraded as e:
                    assert e.action == "shed"
                    shed += 1
            for f in futs:
                with pytest.raises(TimeoutError):
                    f.result(timeout=20.0)
            hm = h.metrics()
            assert shed > 0 and hm["counters"]["shed"] == shed
            assert hm["counters"]["deadline_hit"] == len(futs)

    def test_metrics_after_close_direct_read(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        fleet = CodedFleet(6)
        h = fleet.attach(plan)
        h.matvec(xs[0])
        fleet.close()
        assert fleet.metrics()["plans"][h.plan_id][
            "counters"]["resolved"] == 1


class TestDynamicCoalescing:
    def test_set_microbatch_cols_retargets_live(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        faults = StragglerFaults(time_scale=1.0, seed=1)
        with CodedFleet(6, max_inflight=1, microbatch=True,
                        faults=faults) as fleet:
            h = fleet.attach(plan)
            h.set_microbatch_cols(2)            # per-plan cap, set live
            futs = [h.submit_matvec(xs[i]) for i in range(5)]
            [f.result() for f in futs]
            assert all(r.calls <= 2 for r in h.reports)
            assert h.metrics()["microbatch_cols"] == 2
            h.set_microbatch_cols(None)         # back to the fleet cap
            assert h.metrics()["microbatch_cols"] is None

    def test_submit_matvec_many_packs_one_round(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        # microbatch_cols=2 must NOT split an explicit group: the group
        # is cap-exempt, one round, per-call bitwise decode slices
        with CodedFleet(6, max_inflight=4, microbatch=True,
                        microbatch_cols=2) as fleet:
            h = fleet.attach(plan)
            futs = h.submit_matvec_many([xs[i] for i in range(5)])
            outs = [np.asarray(f.result()) for f in futs]
            reports = {id(f.report) for f in futs}
            assert len(reports) == 1            # exactly one round
            assert futs[0].report.calls == 5
            pat = futs[0].report.pattern
            for i, out in enumerate(outs):
                want = np.asarray(plan.matvec(xs[i], jnp.asarray(pat)))
                np.testing.assert_array_equal(out, want)

    def test_submit_group_wider_than_queue_cap_rejected(self, operands):
        # a group wider than the whole admission queue can never be
        # admitted, even against an idle fleet: blocking would self-
        # deadlock (only its own unsubmitted calls could free slots),
        # shedding would make every retry futile -- reject loudly
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, queue_cap=4) as fleet:
            h = fleet.attach(plan)
            with pytest.raises(ValueError, match="queue_cap"):
                h.submit_matvec_many([xs[i % 3] for i in range(5)])

    def test_group_nonblocking_shed_is_all_or_nothing(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")

        class FixedDelay:
            """Bounded 2s sleep per task: holds admission slots through
            the shed assertions without leaving workers asleep past
            fleet close (unlike an unbounded exponential tail)."""

            def delay(self, worker, task_row, work):
                return 2.0

            def should_fail(self, worker, tasks_done):
                return False

        with CodedFleet(6, faults=FixedDelay(), queue_cap=3,
                        max_inflight=1, microbatch=False) as fleet:
            h = fleet.attach(plan)
            f1 = h.submit_matvec(xs[0], np.ones(6, bool), deadline=0.5)
            with pytest.raises(FleetDegraded) as ei:  # 3 wanted, 2 free
                h.submit_matvec_many([xs[0], xs[1], xs[2]], block=False)
            assert ei.value.action == "shed"
            # all-or-nothing: the slots the shed group briefly held are
            # back, so a group that fits admits without blocking
            f2 = h.submit_matvec_many([xs[0], xs[1]], deadline=0.5,
                                      block=False)
            assert len(f2) == 2
            for f in (f1, *f2):         # slow workers: deadline fails
                with pytest.raises(TimeoutError):
                    f.result(timeout=30.0)

    def test_idle_fleet_pumps_immediately(self, operands):
        A, _, xs = operands
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with CodedFleet(6, max_inflight=1, microbatch=True) as fleet:
            h = fleet.attach(plan)
            h.matvec(xs[0])                     # warm
            t0 = time.perf_counter()
            for i in range(8):                  # closed loop, empty queue
                h.matvec(xs[i])
            closed = (time.perf_counter() - t0) / 8
        # an idle fleet must not defer the pump: closed-loop latency
        # stays near the round time, not the watchdog tick (the old
        # inflight=1 pathology was ~50x the sequential shim)
        assert closed < 0.2
        assert all(r.calls == 1 for r in h.reports)
