"""Cluster runtime suite (repro.cluster).

Covers: the wire codec (record framing, version gate, truncation /
garbling robustness, dtype fidelity for f32/bf16 operands -- the
serialization mirror of ``_match_dtype``), plan serialization
round-trips for every registered scheme, shard partitioning with input
column supports, dispatcher parity against the in-process plan under
all C(n, s) whole-worker patterns (bitwise on the packed backend, over
all three transports: memory, pipe, tcp) and under partial-straggler
task-level patterns, race-mode correctness with latency injection,
heartbeat-driven liveness (missed beats -> suspected -> requeue; a
worker killed mid-round over tcp), the tcp handshake's wire-version
gate, transport shutdown hygiene (no leaked fds/threads), worker
fail-stop with requeue, fault-injector determinism (including ``Hang``),
bytes-on-wire accounting, serve-engine mask routing, the scheme-registry
CLI, and online plan re-tuning (``plan.retune`` + shard re-shipping +
trainer integration).
"""

import itertools
import os
import signal
import socket
import struct
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import compile_plan, list_schemes, make_scheme
from repro.cluster import (
    ClusterPlan,
    FailStop,
    Hang,
    NoFaults,
    StragglerFaults,
    adversarial_faults,
    dumps_plan,
    loads_plan,
    resolve_transport,
    shard_plan,
    straggler_mask,
    worker_env,
)
from repro.cluster.faults import from_spec
from repro.cluster.transport.base import start_worker_process
from repro.cluster.wire import (
    WIRE_VERSION,
    Heartbeat,
    Task,
    TaskResult,
    decode_event,
    decode_record,
    decode_record_sg,
    encode_record,
    encode_record_sg,
    flatten,
    record_nbytes,
    scheme_from_meta,
    scheme_to_meta,
)
from repro.core.straggler import AdversarialSlow

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
def sparse_operand():
    rng = np.random.default_rng(0)
    t, r = 256, 144
    A = jnp.asarray(block_sparse(rng, t, r, 0.98))
    x = jnp.asarray(rng.standard_normal((3, t)), jnp.float32)
    return A, x


# ---------------------------------------------------------------------------
# Wire codec
# ---------------------------------------------------------------------------


class TestWireCodec:
    def test_record_roundtrip(self):
        meta = {"a": 1, "s": "x", "nested": {"b": [1, 2]}}
        arrays = {"f": np.arange(6, dtype=np.float32).reshape(2, 3),
                  "i": np.asarray([3, 1], np.int32),
                  "d": np.ones((2, 2), np.float64)}
        m2, a2 = decode_record(encode_record(meta, arrays))
        assert m2 == meta
        for k, v in arrays.items():
            assert a2[k].dtype == v.dtype
            np.testing.assert_array_equal(a2[k], v)

    def test_bad_magic_and_version_rejected(self):
        blob = bytearray(encode_record({"x": 1}, {}))
        bad = b"XXXX" + bytes(blob[4:])
        with pytest.raises(ValueError, match="not a repro"):
            decode_record(bad)
        blob[4] = 0xFF                      # version field
        with pytest.raises(ValueError, match="version"):
            decode_record(bytes(blob))

    def test_truncated_frames_rejected(self):
        blob = encode_record({"x": 1}, {"a": np.arange(8, dtype=np.float32)})
        with pytest.raises(ValueError, match="truncated"):
            decode_record(blob[:6])                 # short header
        with pytest.raises(ValueError, match="truncated"):
            decode_record(blob[:20])                # manifest cut off
        with pytest.raises(ValueError, match="truncated"):
            decode_record(blob[:-4])                # array payload cut off

    def test_garbled_manifest_rejected(self):
        blob = bytearray(encode_record({"x": 1}, {}))
        # flip bytes inside the json manifest
        blob[14 + 2: 14 + 8] = b"\xff\xfe\xfd\xfc\xfb\xfa"
        with pytest.raises(ValueError, match="garbled|truncated"):
            decode_record(bytes(blob))

    def test_sg_roundtrip_and_flatten_equivalence(self):
        # wire v6 scatter/gather: (header, buffers) framing must be
        # byte-equivalent to the flat encoding, and decoding the
        # buffer list must view, not copy, the source arrays
        meta = {"record": "task", "round": 9, "nested": {"b": [1, 2]}}
        arrays = {"f": np.arange(6, dtype=np.float32).reshape(2, 3),
                  "i": np.asarray([3, 1], np.int32)}
        header, bufs = encode_record_sg(meta, arrays)
        assert flatten(header, bufs) == encode_record(meta, arrays)
        assert flatten(header, bufs, prefix=b"LEN!") == \
            b"LEN!" + encode_record(meta, arrays)
        m2, a2 = decode_record_sg(header, bufs)
        assert m2 == meta
        for k, v in arrays.items():
            assert a2[k].dtype == v.dtype
            np.testing.assert_array_equal(a2[k], v)
        assert np.shares_memory(a2["f"], arrays["f"])   # zero-copy views
        # and the flat decoder accepts the gathered frame unchanged
        m3, a3 = decode_record(flatten(header, bufs))
        assert m3 == meta
        np.testing.assert_array_equal(a3["i"], arrays["i"])

    def test_sg_wrong_buffer_count_rejected(self):
        arrays = {"a": np.ones(4, np.float32), "b": np.arange(3, dtype=np.int64)}
        header, bufs = encode_record_sg({"x": 1}, arrays)
        with pytest.raises(ValueError, match="wrong buffer count"):
            decode_record_sg(header, bufs[:1])
        with pytest.raises(ValueError, match="wrong buffer count"):
            decode_record_sg(header, [*bufs, memoryview(b"extra")])
        with pytest.raises(ValueError, match="wrong buffer count"):
            decode_record_sg(header, [])

    def test_sg_truncated_buffers_rejected(self):
        arrays = {"a": np.ones(4, np.float32), "b": np.arange(3, dtype=np.int64)}
        header, bufs = encode_record_sg({"x": 1}, arrays)
        with pytest.raises(ValueError, match="truncated"):
            decode_record_sg(header, [bufs[0][:-2], bufs[1]])
        with pytest.raises(ValueError, match="truncated"):
            decode_record_sg(header, [bufs[0], bufs[1][:4]])
        # buffer lengths are checked per array, by name, in the error
        with pytest.raises(ValueError, match="'b'"):
            decode_record_sg(header, [bufs[0], bufs[1][:4]])

    def test_sg_garbled_header_rejected(self):
        header, bufs = encode_record_sg({"x": 1},
                                        {"a": np.ones(4, np.float32)})
        bad = bytearray(header)
        bad[0:4] = b"XXXX"
        with pytest.raises(ValueError, match="not a repro"):
            decode_record_sg(bytes(bad), bufs)
        bad = bytearray(header)
        bad[4] = WIRE_VERSION + 1               # wrong-wire-version peer
        with pytest.raises(ValueError, match="version"):
            decode_record_sg(bytes(bad), bufs)
        with pytest.raises(ValueError, match="truncated"):
            decode_record_sg(header[:6], bufs)  # short header
        bad = bytearray(header)
        bad[16:22] = b"\xff\xfe\xfd\xfc\xfb\xfa"    # inside the manifest
        with pytest.raises(ValueError, match="garbled|truncated"):
            decode_record_sg(bytes(bad), bufs)

    def test_sg_task_and_result_frames(self):
        t = Task(round=3, op="matvec", task_row=5,
                 payload={"b": np.ones((4, 2), np.float32)},
                 meta={"b": 2})
        header, bufs = t.encode_sg()
        assert flatten(header, bufs) == t.encode()
        t2 = Task.decode(flatten(header, bufs))
        np.testing.assert_array_equal(t2.payload["b"], t.payload["b"])
        r = TaskResult(worker=1, round=3, task_row=5, copied=123,
                       arrays={"y": np.zeros(3, np.float32)})
        header, bufs = r.encode_sg()
        assert flatten(header, bufs) == r.encode()
        r2 = TaskResult.decode(r.encode())
        assert r2.copied == 123                 # v6 copy accounting rides
        r0 = TaskResult(worker=1, round=3, task_row=5,
                        arrays={"y": np.zeros(3, np.float32)})
        assert b"copied" not in r0.encode()     # ...only when nonzero
        assert TaskResult.decode(r0.encode()).copied == 0

    def test_structurally_garbled_records_rejected(self):
        import json

        # manifest parses as json but the array specs are missing
        # fields: still ValueError, never a KeyError escaping handlers
        head = json.dumps({"meta": {}, "arrays": [{}]}).encode()
        blob = struct.pack("<4sHQ", b"RPRC", WIRE_VERSION, len(head)) + head
        with pytest.raises(ValueError, match="garbled"):
            decode_record(blob)
        # an event record that parses but lacks required fields
        with pytest.raises(ValueError, match="garbled"):
            decode_event(encode_record({"record": "result"}))
        with pytest.raises(ValueError, match="garbled"):
            decode_event(encode_record({"record": "beat"}))

    def test_record_nbytes_exact(self):
        meta = {"record": "task", "round": 2, "op": "matvec",
                "task_row": 7, "meta": {"b": 3}}
        arrays = {"bx": np.ones((16, 3), np.float32),
                  "bi": np.arange(2, dtype=np.int32)}
        assert record_nbytes(meta, arrays) == len(encode_record(meta, arrays))
        t = Task(round=2, op="matvec", task_row=7, payload=arrays,
                 meta={"b": 3})
        assert t.nbytes() == len(t.encode())

    def test_heartbeat_and_event_demux(self):
        hb = Heartbeat(worker=3, tick=17)
        back = decode_event(hb.encode())
        assert isinstance(back, Heartbeat)
        assert (back.worker, back.tick) == (3, 17)
        res = TaskResult(worker=1, round=2, task_row=4,
                         arrays={"y": np.ones(2, np.float32)})
        back = decode_event(res.encode())
        assert isinstance(back, TaskResult) and back.task_row == 4
        with pytest.raises(ValueError, match="unexpected event"):
            decode_event(encode_record({"record": "task"}))

    def test_task_result_roundtrip(self):
        t = Task(round=3, op="matvec", task_row=5,
                 payload={"b": np.ones((4, 2), np.float32)},
                 meta={"b": 2})
        t2 = Task.decode(t.encode())
        assert (t2.round, t2.op, t2.task_row, t2.meta) == (3, "matvec", 5,
                                                           {"b": 2})
        np.testing.assert_array_equal(t2.payload["b"], t.payload["b"])
        r = TaskResult(worker=1, round=3, task_row=5, work=0.25,
                       compute_s=1e-4, arrays={"y": np.zeros(3, np.float32)})
        r2 = TaskResult.decode(r.encode())
        assert r2.ok and r2.kind == "result" and r2.work == 0.25
        np.testing.assert_array_equal(r2.arrays["y"], r.arrays["y"])

    def test_scheme_meta_roundtrip_all_schemes(self):
        for info in list_schemes():
            if info.hetero:
                sch = make_scheme(info.name, capacities=[2, 2, 1, 1], k_A=4)
            elif info.kind == "mv":
                sch = make_scheme(info.name, n=6, k_A=4)
            else:
                sch = make_scheme(info.name, n=6, k_A=2, k_B=2)
            assert scheme_from_meta(scheme_to_meta(sch)) == sch


# ---------------------------------------------------------------------------
# Plan serialization
# ---------------------------------------------------------------------------


class TestPlanSerialization:
    @pytest.mark.parametrize("backend", ["packed", "reference"])
    def test_mv_roundtrip_every_scheme(self, backend):
        rng = np.random.default_rng(1)
        t, r = 128, 96
        A = jnp.asarray(block_sparse(rng, t, r, 0.9))
        x = jnp.asarray(rng.standard_normal(t), jnp.float32)
        for info in list_schemes("mv"):
            if info.hetero:
                plan = compile_plan(A, scheme=info.name,
                                    capacities=[2, 2, 1, 1], k_A=4,
                                    backend=backend)
            else:
                plan = compile_plan(A, scheme=info.name, n=6, k_A=4,
                                    backend=backend)
            plan2 = loads_plan(dumps_plan(plan))
            assert plan2.scheme == plan.scheme
            assert plan2.backend == plan.backend
            np.testing.assert_array_equal(np.asarray(plan2.G),
                                          np.asarray(plan.G))
            np.testing.assert_array_equal(np.asarray(plan2.executor.coded),
                                          np.asarray(plan.executor.coded))
            np.testing.assert_array_equal(np.asarray(plan2.matvec(x)),
                                          np.asarray(plan.matvec(x)))

    def test_mm_roundtrip(self):
        rng = np.random.default_rng(2)
        t, r = 128, 64
        A = jnp.asarray(block_sparse(rng, t, r, 0.9))
        B = jnp.asarray(rng.standard_normal((t, 24)), jnp.float32)
        for name in ("proposed", "poly"):
            plan = compile_plan(A, scheme=name, n=6, k_A=2, k_B=2,
                                backend="packed")
            plan2 = loads_plan(dumps_plan(plan))
            np.testing.assert_array_equal(np.asarray(plan2.matmat(B)),
                                          np.asarray(plan.matmat(B)))

    def test_aggregation_only_roundtrip(self):
        plan = compile_plan(scheme="proposed", n=6, s=2, seed=3)
        plan2 = loads_plan(dumps_plan(plan))
        rng = np.random.default_rng(3)
        payloads = [jnp.asarray(rng.standard_normal(5), jnp.float32)
                    for _ in range(6)]
        done = np.ones(6, bool)
        done[4] = False
        np.testing.assert_allclose(
            np.asarray(plan2.aggregate(payloads, jnp.asarray(done))),
            np.asarray(plan.aggregate(payloads, jnp.asarray(done))), **TOL)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_fidelity(self, dtype):
        # the wire mirror of api.plan._match_dtype: a bf16 operand's
        # coded shards must come back bf16, not silently doubled to f32
        rng = np.random.default_rng(4)
        A = jnp.asarray(block_sparse(rng, 64, 48, 0.9)).astype(dtype)
        for backend in ("packed", "reference"):
            plan = compile_plan(A, scheme="proposed", n=6, s=2,
                                backend=backend)
            assert plan.executor.coded.dtype == dtype
            plan2 = loads_plan(dumps_plan(plan))
            assert plan2.executor.coded.dtype == dtype
            np.testing.assert_array_equal(
                np.asarray(plan2.executor.coded, np.float32),
                np.asarray(plan.executor.coded, np.float32))

    def test_cache_patterns_shipped(self):
        rng = np.random.default_rng(5)
        A = jnp.asarray(block_sparse(rng, 64, 48, 0.98))
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        done = np.ones(6, bool)
        done[[0, 3]] = False
        plan.prewarm(jnp.asarray(done))
        plan2 = loads_plan(dumps_plan(plan))
        cache = plan2._decode_cache()
        hits0 = cache.hits
        plan2.matvec(jnp.ones(64, jnp.float32), jnp.asarray(done))
        assert cache.hits == hits0 + 1      # pattern arrived pre-warmed

    def test_shard_partition(self, sparse_operand):
        A, _ = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        for w in (6, 4, 3, 1):
            shards = shard_plan(plan, w)
            rows = sorted(r for s in shards for r in s.task_rows)
            assert rows == list(range(plan.n_tasks))
            assert all(s.work and min(s.work) > 0 for s in shards)
            assert len(shards) == w
        with pytest.raises(ValueError, match="n_workers"):
            shard_plan(plan, 0)


# ---------------------------------------------------------------------------
# Dispatcher parity vs the in-process plan
# ---------------------------------------------------------------------------


class TestDispatcherParity:
    @pytest.mark.parametrize("scheme", ["proposed", "cyclic31"])
    def test_whole_worker_patterns_bitwise(self, sparse_operand, scheme):
        A, x = sparse_operand
        n, s = 6, 2
        plan = compile_plan(A, scheme=scheme, n=n, s=s, backend="packed")
        with plan.to_cluster() as cl:
            assert cl.transport_name == "memory"
            for done in all_straggler_masks(n, s):
                want = np.asarray(plan.matvec(x, jnp.asarray(done)))
                got = np.asarray(cl.matvec(x, done))
                # same BSR products, same cached inverse: bitwise equal
                np.testing.assert_array_equal(got, want)

    @pytest.mark.slow
    @pytest.mark.parametrize("transport", ["pipe", "tcp", "shm"])
    def test_whole_worker_patterns_bitwise_socket_transports(
            self, sparse_operand, transport):
        # the same C(6, 2) sweep over real process/socket transports:
        # parity is a property of the stack, not of one byte carrier
        A, x = sparse_operand
        n, s = 6, 2
        plan = compile_plan(A, scheme="proposed", n=n, s=s, backend="packed")
        with plan.to_cluster(transport=transport) as cl:
            assert cl.transport_name == transport
            if transport == "tcp":
                # every worker digest-verified its shard and acked it
                import hashlib

                want_acks = {w: hashlib.sha256(blob).hexdigest()
                             for w, blob in enumerate(cl._shard_bytes)}
                deadline = time.time() + 10
                while (cl.transport.shard_acks != want_acks
                       and time.time() < deadline):
                    time.sleep(0.02)
                assert cl.transport.shard_acks == want_acks
            for done in all_straggler_masks(n, s):
                want = np.asarray(plan.matvec(x, jnp.asarray(done)))
                got = np.asarray(cl.matvec(x, done))
                np.testing.assert_array_equal(got, want)

    def test_reference_backend_tolerance(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="reference")
        done = np.ones(6, bool)
        done[[1, 4]] = False
        with plan.to_cluster() as cl:
            got = np.asarray(cl.matvec(x, done))
        np.testing.assert_allclose(
            got, np.asarray(plan.matvec(x, jnp.asarray(done))), **TOL)

    def test_partial_straggler_task_level_parity(self, sparse_operand):
        # scs36: 6 workers x 3 tasks, decode needs 12 of 18 task rows.
        # Worker 0 finishes 2/3, worker 1 finishes 1/3 -- strict subsets.
        A, x = sparse_operand
        plan = compile_plan(A, scheme="scs36", n=6, k_A=4, backend="packed")
        per = plan.tasks_per_worker
        assert per == 3
        task_done = np.ones(plan.n_tasks, bool)
        task_done[[2, 4, 5]] = False        # w0 loses row 2, w1 rows 4, 5
        want = np.asarray(plan.matvec(x, jnp.asarray(task_done)))
        with plan.to_cluster() as cl:
            got = np.asarray(cl.matvec(x, task_done))
            rep = cl.last_report
        np.testing.assert_array_equal(got, want)
        assert 0 in rep.partial_workers and 1 in rep.partial_workers
        # ground truth: still the exact matvec
        np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)

    def test_fewer_hosts_than_virtual_workers(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        done = np.ones(6, bool)
        done[[3, 4]] = False                # host 0 keeps row 0, loses 3
        with plan.to_cluster(3) as cl:      # hosts own {0,3}, {1,4}, {2,5}
            got = np.asarray(cl.matvec(x, done))
            rep = cl.last_report
        np.testing.assert_array_equal(
            got, np.asarray(plan.matvec(x, jnp.asarray(done))))
        assert rep.partial_workers == (0, 1)

    def test_matmat_patterns(self, sparse_operand):
        A, _ = sparse_operand
        rng = np.random.default_rng(6)
        B = jnp.asarray(rng.standard_normal((A.shape[0], 24)), jnp.float32)
        n, ka, kb = 6, 2, 2
        plan = compile_plan(A, scheme="proposed", n=n, k_A=ka, k_B=kb,
                            backend="packed")
        with plan.to_cluster() as cl:
            for done in itertools.islice(all_straggler_masks(n, 2), 6):
                want = np.asarray(plan.matmat(B, jnp.asarray(done)))
                got = np.asarray(cl.matmat(B, done))
                np.testing.assert_array_equal(got, want)
            got = np.asarray(cl.matmat(B))          # race mode
        np.testing.assert_allclose(got, np.asarray(A.T @ B), **TOL)

    def test_race_mode_with_faults(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        faults = StragglerFaults(time_scale=2e-3, seed=11)
        with plan.to_cluster(faults=faults) as cl:
            for _ in range(4):
                got = np.asarray(cl.matvec(x))
                np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)
                assert cl.last_report.n_done >= plan.k

    def test_matvec_1d_and_aggregation_only_errors(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with plan.to_cluster() as cl:
            got = np.asarray(cl.matvec(x[0]))
            assert got.shape == (plan.r,)
            with pytest.raises(ValueError, match="matmat needs an mm"):
                cl.matmat(x)
            with pytest.raises(ValueError, match="need at least k"):
                cl.matvec(x, np.zeros(6, bool))
        agg = compile_plan(scheme="proposed", n=6, s=2)
        with agg.to_cluster() as cl:
            with pytest.raises(ValueError, match="aggregation-only"):
                cl.matvec(x)

    def test_aggregate_parity_and_race(self):
        rng = np.random.default_rng(7)
        plan = compile_plan(scheme="proposed", n=6, s=2, seed=1)
        k = plan.k
        # consistent coded payloads (payload_i = sum_q G[i,q] g_q):
        # only then is the decode row-set independent, which is what
        # race mode exercises (arrival order picks the rows)
        G = np.asarray(plan.G, np.float32)
        grads = [rng.standard_normal((4, 3)).astype(np.float32)
                 for _ in range(k)]
        payloads = [{"g": jnp.asarray(
            sum(G[i, q] * grads[q] for q in range(k)))} for i in range(6)]
        total = np.sum(grads, axis=0)
        done = np.ones(6, bool)
        done[2] = False
        want = np.asarray(plan.aggregate(payloads, jnp.asarray(done))["g"])
        with plan.to_cluster() as cl:
            got = np.asarray(cl.aggregate(payloads, done)["g"])
            np.testing.assert_allclose(got, want, **TOL)
            raced = np.asarray(cl.aggregate(payloads)["g"])
        np.testing.assert_allclose(raced, total, **TOL)

    def test_coded_aggregator_cluster_mode(self):
        from repro.parallel.coded_grads import CodedAggregator

        rng = np.random.default_rng(8)
        agg = CodedAggregator.build(6, 2, seed=1)
        k = agg.scheme.k_A
        shard_grads = [{"w": jnp.asarray(rng.standard_normal((3, 2)),
                                         jnp.float32)} for _ in range(k)]
        payloads = [agg.worker_payload(i, shard_grads) for i in range(6)]
        done = np.ones(6, bool)
        done[5] = False
        want = np.asarray(agg.aggregate(payloads, jnp.asarray(done))["w"])
        with agg.to_cluster() as cl:
            got = np.asarray(agg.aggregate(payloads, done, cluster=cl)["w"])
        np.testing.assert_allclose(got, want, **TOL)
        total = np.sum([np.asarray(g["w"], np.float32)
                        for g in shard_grads], axis=0)
        np.testing.assert_allclose(got, total, **TOL)


# ---------------------------------------------------------------------------
# Fail-stop, requeue, deadlines, process backend
# ---------------------------------------------------------------------------


class TestFailStopAndTransports:
    def test_failstop_requeues_and_recovers(self, sparse_operand):
        A, x = sparse_operand
        n, k = 6, 5
        plan = compile_plan(A, scheme="proposed", n=n, s=n - k,
                            backend="packed")
        # two deaths leave 4 live hosts < k: decode NEEDS the requeue
        with plan.to_cluster(faults=FailStop({0: 0, 3: 0})) as cl:
            got = np.asarray(cl.matvec(x))
            rep = cl.last_report
            assert rep.deaths == 2
            assert rep.requeues >= 1
            np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)
            # the cluster keeps serving on the survivors
            got = np.asarray(cl.matvec(x))
            assert cl.last_report.deaths == 0
            np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)

    def test_sequential_deaths_reship_inherited_shards(self,
                                                       sparse_operand):
        # worker 0 dies first; its shard is inherited by some heir.  When
        # THAT heir later dies, its successor must receive both shards --
        # the inherited task rows must never be stranded.
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with plan.to_cluster(faults=FailStop({0: 0, 1: 1})) as cl:
            for i in range(4):          # worker 1 dies mid-sequence
                got = np.asarray(cl.matvec(x))
                np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)
            assert sum(r.deaths for r in cl.reports) == 2

    def test_all_workers_dead_raises(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=1,
                            backend="packed")
        with plan.to_cluster(faults=FailStop(
                {w: 0 for w in range(6)})) as cl:
            with pytest.raises(RuntimeError, match="dead"):
                cl.matvec(x)

    def test_deadline_timeout(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        slow = StragglerFaults(time_scale=30.0, seed=1)   # ~minutes/task
        with plan.to_cluster(faults=slow, deadline=0.3) as cl:
            with pytest.raises(TimeoutError, match="deadline"):
                cl.matvec(x)

    @pytest.mark.slow
    def test_process_backend_parity(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        done = np.ones(6, bool)
        done[[1, 4]] = False
        want = np.asarray(plan.matvec(x, jnp.asarray(done)))
        # backend="process" is the legacy spelling of transport="pipe"
        with plan.to_cluster(3, backend="process") as cl:
            assert cl.transport_name == "pipe"
            got = np.asarray(cl.matvec(x, done))
        # same f32 BSR math on the far side of the pipe
        np.testing.assert_array_equal(got, want)


def _put_jax_platforms(q):
    q.put(os.environ.get("JAX_PLATFORMS"))


class TestWorkerEnv:
    """Worker processes start with JAX held to the CPU, so none can reach
    for the accelerator its coordinator holds."""

    def test_spawned_worker_starts_cpu_only(self, monkeypatch):
        import multiprocessing as mp

        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        proc = ctx.Process(target=_put_jax_platforms, args=(q,))
        start_worker_process(proc)
        try:
            assert q.get(timeout=60) == "cpu"
        finally:
            proc.join(timeout=60)
        assert os.environ["JAX_PLATFORMS"] == "tpu"     # parent restored

    def test_worker_env_for_subprocesses(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert worker_env()["JAX_PLATFORMS"] == "cpu"
        assert worker_env({"PATH": "/bin"}) == {"PATH": "/bin",
                                                 "JAX_PLATFORMS": "cpu"}


# ---------------------------------------------------------------------------
# Heartbeat liveness, tcp handshake, transport hygiene
# ---------------------------------------------------------------------------


class TestLivenessAndTcp:
    def test_memory_hang_suspected_and_requeued(self, sparse_operand):
        # n=6, k=5: two silent workers leave 4 live -- decode NEEDS the
        # heartbeat timeout -> suspected -> requeue sequencing.  No
        # done= mask anywhere: liveness is measured, not injected.
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=1,
                            backend="packed")
        with plan.to_cluster(faults=Hang({0: 0, 3: 0}), heartbeat_s=0.05,
                             suspect_after=0.4) as cl:
            got = np.asarray(cl.matvec(x))
            rep = cl.last_report
            # one requeued row can complete the decode before the
            # second hung worker crosses the timeout: 1 or 2 suspected
            assert 1 <= rep.suspected <= 2
            assert rep.deaths == 0              # silent, not fail-stop
            assert rep.requeues >= 1
            np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)
            # the cluster keeps serving on the survivors
            got = np.asarray(cl.matvec(x))
            assert cl.last_report.suspected == 0
            np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)

    @pytest.mark.slow
    def test_tcp_hang_suspected_and_requeued(self, sparse_operand):
        # same sequencing over real sockets: the hung child keeps its
        # connection open, so ONLY the heartbeat timeout can catch it
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=1,
                            backend="packed")
        with plan.to_cluster(transport="tcp", faults=Hang({2: 0, 4: 0}),
                             heartbeat_s=0.05, suspect_after=0.4) as cl:
            got = np.asarray(cl.matvec(x))
            rep = cl.last_report
            assert 1 <= rep.suspected <= 2 and rep.requeues >= 1
            np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)

    @pytest.mark.slow
    def test_tcp_worker_killed_mid_round(self, sparse_operand):
        # a worker SIGKILLed between rounds: the dropped connection
        # surfaces as a death, its shard is re-shipped, the decode is
        # still correct -- no fault injection, no done= mask
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=1,
                            backend="packed")
        with plan.to_cluster(transport="tcp") as cl:
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A), **TOL)
            os.kill(cl.transport._procs[2].pid, signal.SIGKILL)
            time.sleep(0.3)
            got = np.asarray(cl.matvec(x))
            np.testing.assert_allclose(got, np.asarray(x @ A), **TOL)
            # the dropped connection surfaced as a death and its rows
            # were re-homed (shard re-shipped to the heir) -- the next
            # round decoded correctly without worker 2
            assert sum(r.deaths for r in cl.reports) == 1
            assert 2 not in cl.last_report.completed_per_worker

    @pytest.mark.slow
    def test_tcp_wrong_version_handshake_rejected(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with plan.to_cluster(transport="tcp") as cl:
            # a client speaking a future wire version is rejected at
            # the handshake: connection closed, nothing registered
            blob = bytearray(encode_record({"record": "hello", "worker": 0}))
            blob[4] = WIRE_VERSION + 1          # bump the header version
            with socket.create_connection(
                    ("127.0.0.1", cl.transport.port), timeout=5) as sock:
                sock.sendall(struct.pack("<I", len(blob)) + bytes(blob))
                sock.settimeout(5)
                assert sock.recv(1) == b""      # server closed on us
            # ... and the cluster is unharmed
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A), **TOL)

    @pytest.mark.slow
    def test_tcp_shutdown_releases_sockets_and_threads(self,
                                                       sparse_operand):
        import gc
        import warnings

        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with warnings.catch_warnings():
            warnings.simplefilter("error", ResourceWarning)
            with plan.to_cluster(transport="tcp") as cl:
                cl.matvec(x)
            gc.collect()                # unclosed sockets would warn here
        for t in threading.enumerate():
            assert not t.name.startswith(("cluster-tcp", "cluster-beat",
                                          "cluster-worker"))

    def test_memory_shutdown_joins_worker_threads(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with plan.to_cluster() as cl:
            cl.matvec(x)
        time.sleep(0.05)
        leftover = [t.name for t in threading.enumerate()
                    if t.name.startswith(("cluster-worker", "cluster-beat"))]
        assert leftover == []

    def test_env_var_selects_transport(self, sparse_operand, monkeypatch):
        assert resolve_transport(None) == "memory"
        monkeypatch.setenv("REPRO_CLUSTER_TRANSPORT", "tcp")
        assert resolve_transport(None) == "tcp"
        assert resolve_transport("memory") == "memory"   # explicit wins
        with pytest.raises(ValueError, match="transport"):
            resolve_transport("carrier-pigeon")

    def test_bytes_on_wire_accounting(self, sparse_operand):
        # support-restricted task payloads: measured task traffic must
        # be well under full-operand shipping on a 98%-sparse operand,
        # and the totals must accumulate across rounds
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with plan.to_cluster() as cl:
            assert cl.bytes_shards > 0
            cl.matvec(x)
            rep = cl.last_report
            assert 0 < rep.bytes_tasks < rep.bytes_tasks_dense
            assert rep.bytes_results > 0
            cl.matvec(x)
            totals = cl.wire_totals()
            assert totals["bytes_tasks_total"] == \
                rep.bytes_tasks + cl.last_report.bytes_tasks

    def test_shard_supports_cover_nonzero_tiles(self, sparse_operand):
        A, _ = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        for shard in shard_plan(plan, 3):
            assert len(shard.supports) == len(shard.task_rows)
            kb = shard.t_pad // shard.bk
            for sup, task in zip(shard.supports, shard.tasks):
                assert sorted(sup) == sorted(set(task["indices"].tolist()))
                assert all(0 <= j < kb for j in sup)


# ---------------------------------------------------------------------------
# Shared-memory transport: zero-copy accounting + segment lifecycle
# ---------------------------------------------------------------------------


def _own_shm_segments():
    """Names of /dev/shm entries created by this process's transports."""
    try:
        entries = os.listdir("/dev/shm")
    except FileNotFoundError:           # non-Linux: lifecycle untestable
        pytest.skip("/dev/shm not available")
    return {e for e in entries if e.startswith(f"repro{os.getpid()}x")}


@pytest.mark.slow
class TestShmTransport:
    def test_zero_copy_task_path(self, sparse_operand):
        # the tentpole claim, at test scale: shm task frames carry
        # segment references, so coordinator-side task copies are the
        # header frames alone and the worker materializes no operand
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        with plan.to_cluster(transport="shm") as cl:
            cl.matvec(x)
            rep = cl.last_report
            # every byte copied on the task path is a header frame byte
            assert 0 < rep.bytes_copied <= rep.bytes_tasks
            assert rep.bytes_copied < rep.bytes_tasks_dense
            totals = cl.fleet.wire_totals()
            assert totals["bytes_copied_total"] == rep.bytes_copied
            # transport-level counter additionally holds the one-time
            # shard staging copies
            assert totals["transport_bytes_copied"] >= \
                rep.bytes_copied + totals["bytes_shards"]

    def test_segments_released_on_close(self, sparse_operand):
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        before = _own_shm_segments()
        with plan.to_cluster(transport="shm") as cl:
            assert _own_shm_segments() - before     # shard segments live
            for _ in range(3):
                cl.matvec(x)
        assert _own_shm_segments() == before        # all unlinked

    def test_remove_worker_drain_releases_shard_segments(self,
                                                         sparse_operand):
        from repro.cluster.fleet import CodedFleet

        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        before = _own_shm_segments()
        with CodedFleet(6, transport="shm") as fleet:
            h = fleet.attach(plan)
            h.matvec(x)
            held = _own_shm_segments() - before
            assert held
            fleet.remove_worker(5, drain=True)
            # the leaver's shard segment was unlinked with it
            assert not any(key[0] == 5
                           for key in fleet.transport._shard_segs)
            np.testing.assert_allclose(np.asarray(h.matvec(x)),
                                       np.asarray(x @ A), **TOL)
        assert _own_shm_segments() == before

    def test_worker_crash_leaves_no_segments(self, sparse_operand):
        # SIGKILL mid-run: the coordinator owns every segment, so a
        # fail-stop child can leak nothing; recovery then close leaves
        # /dev/shm exactly as found
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=1,
                            backend="packed")
        before = _own_shm_segments()
        with plan.to_cluster(transport="shm") as cl:
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A), **TOL)
            os.kill(cl.transport._procs[2].pid, signal.SIGKILL)
            time.sleep(0.3)
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A), **TOL)
            assert sum(r.deaths for r in cl.reports) == 1
        assert _own_shm_segments() == before

    def test_garbled_and_wrong_version_frames_kill_worker(self,
                                                          sparse_operand):
        # a corrupt frame and a future-wire-version frame must both be
        # rejected with the codec's explicit error (the worker answers
        # with a death notice and the fleet re-homes its rows)
        A, x = sparse_operand
        plan = compile_plan(A, scheme="proposed", n=6, s=2,
                            backend="packed")
        before = _own_shm_segments()
        with plan.to_cluster(transport="shm") as cl:
            cl.transport.garble(1)
            bad = bytearray(
                Task(round=999, op="matvec", task_row=0,
                     payload={}, meta={}).encode())
            bad[4] = WIRE_VERSION + 1
            cl.transport._send(2, ("task", bytes(bad)))
            deadline = time.time() + 10
            while sum(1 for w in (1, 2)
                      if not cl.transport.alive(w)) < 2 \
                    and time.time() < deadline:
                time.sleep(0.02)
            assert not cl.transport.alive(1)
            assert not cl.transport.alive(2)
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A), **TOL)
        assert _own_shm_segments() == before


# ---------------------------------------------------------------------------
# Fault injectors
# ---------------------------------------------------------------------------


class TestFaults:
    def test_straggler_mask_matches_model(self):
        model = AdversarialSlow(stragglers=(1, 4), slowdown=50.0)
        done = straggler_mask(6, 2, np.random.default_rng(0), model)
        assert not done[1] and not done[4] and done.sum() == 4

    def test_per_worker_streams_deterministic(self):
        a = StragglerFaults(time_scale=1.0, seed=3)
        b = StragglerFaults(time_scale=1.0, seed=3)
        da = [a.delay(w, 0, 0.5) for w in (0, 1, 0, 2)]
        db = [b.delay(w, 0, 0.5) for w in (0, 1, 0, 2)]
        assert da == db
        assert all(d > 0 for d in da)

    def test_spec_roundtrip(self):
        for inj in (NoFaults(),
                    StragglerFaults(time_scale=2e-3, seed=5),
                    adversarial_faults([2], slowdown=7.0),
                    FailStop({1: 2}, base=StragglerFaults(seed=9)),
                    Hang({0: 1}, base=StragglerFaults(seed=4))):
            back = from_spec(inj.to_spec())
            assert type(back) is type(inj)
            assert back.to_spec() == inj.to_spec()
        assert isinstance(from_spec(None), NoFaults)
        with pytest.raises(ValueError, match="unknown fault spec"):
            from_spec({"kind": "nope"})

    def test_failstop_predicate(self):
        f = FailStop({0: 2})
        assert not f.should_fail(0, 1)
        assert f.should_fail(0, 2)
        assert not f.should_fail(1, 99)
        assert not f.mask(4, 1)[0]

    def test_hang_predicate(self):
        h = Hang({1: 1})
        assert not h.should_hang(1, 0)
        assert h.should_hang(1, 1)
        assert not h.should_hang(0, 99)
        assert not h.should_fail(1, 99)     # silent, never fail-stop
        assert not h.mask(4, 1)[1]


# ---------------------------------------------------------------------------
# Serve-engine routing + online re-tuning
# ---------------------------------------------------------------------------


class TestSurfaceIntegration:
    def test_engine_mask_routes_through_faults(self):
        from repro.configs import get_smoke_config
        from repro.configs.base import CodedConfig
        from repro.models import build_model
        from repro.serve import ServeEngine

        import jax

        cfg = get_smoke_config("qwen3-14b")
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        eng = ServeEngine(
            model, params, cfg, batch_size=2, max_len=32,
            coded=CodedConfig(enabled=True, n_workers=6, stragglers=2,
                              cluster=True, cluster_workers=3),
            faults=StragglerFaults(
                model=AdversarialSlow(stragglers=(0, 1), slowdown=50.0)))
        mask = np.asarray(eng._straggler_mask())
        assert not mask[0] and not mask[1]      # the injected model decides
        hidden = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, cfg.d_model)), jnp.float32)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        out = eng.coded_logits(hidden)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(hidden @ head), **TOL)
        assert eng.coded_cluster.last_report is not None
        eng.close()
        assert eng.coded_cluster is None

    def test_retune_follows_density(self):
        rng = np.random.default_rng(9)
        t, r = 256, 144
        A_sparse = jnp.asarray(block_sparse(rng, t, r, 0.99))
        A_dense = jnp.asarray(rng.standard_normal((t, r)), jnp.float32)
        x = jnp.asarray(rng.standard_normal(t), jnp.float32)
        plan = compile_plan(A_sparse, scheme="proposed", n=6, s=2)
        assert plan.backend == "packed"
        assert plan.retune() == "packed"              # no drift: no-op
        assert plan.retune(A_dense) == "reference"    # crossed down
        np.testing.assert_allclose(np.asarray(plan.matvec(x)),
                                   np.asarray(x @ A_dense), **TOL)
        assert plan.retune(A_sparse) == "packed"      # crossed back up
        np.testing.assert_allclose(np.asarray(plan.matvec(x)),
                                   np.asarray(x @ A_sparse), **TOL)
        agg = compile_plan(scheme="proposed", n=6, s=2)
        with pytest.raises(ValueError, match="no operand"):
            agg.retune()

    def test_reship_after_retune(self, sparse_operand):
        # plan.retune recompiles the packed shards; the cluster's
        # workers then hold stale BSR tables until reship() re-ships
        rng = np.random.default_rng(11)
        t, r = 256, 144
        A_sparse, x = sparse_operand
        A_dense = jnp.asarray(rng.standard_normal((t, r)), jnp.float32)
        plan = compile_plan(A_sparse, scheme="proposed", n=6, s=2)
        with plan.to_cluster() as cl:
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A_sparse), **TOL)
            shards_before = cl.bytes_shards
            assert plan.retune(A_dense) == "reference"
            sent = cl.reship()
            assert sent > 0
            assert cl.bytes_shards == shards_before + sent
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A_dense), **TOL)

    def test_list_schemes_cli(self, capsys):
        from repro.api.__main__ import format_scheme_table, main

        table = format_scheme_table()
        assert "proposed" in table and "weight law" in table
        assert format_scheme_table("mm").count("\n") < table.count("\n")
        assert main(["--list-schemes"]) == 0
        out = capsys.readouterr().out
        assert "repetition" in out and "NO" in out   # resilience column

    def test_trainer_retunes_every_n_steps(self, tmp_path):
        from repro.configs import get_smoke_config
        from repro.data.pipeline import DataConfig, make_pipeline
        from repro.models import build_model
        from repro.optim.adamw import AdamWConfig
        from repro.train import TrainConfig, Trainer

        rng = np.random.default_rng(10)
        A = jnp.asarray(block_sparse(rng, 128, 96, 0.99))
        plan = compile_plan(A, scheme="proposed", n=6, s=2)
        cfg = get_smoke_config("phi3-mini-3.8b")
        model = build_model(cfg, dtype=jnp.float32)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
        tr = Trainer(model, AdamWConfig(lr=1e-3, warmup_steps=2,
                                        total_steps=4),
                     TrainConfig(steps=4, ckpt_dir=None, retune_every=2),
                     coded_plans=[(plan, lambda params: A)])
        tr.fit(lambda s: make_pipeline(dcfg, s), resume=False)
        assert [r["step"] for r in tr.retunes] == [1, 3]
        assert all(r["backend"] == "packed" for r in tr.retunes)

    def test_trainer_reships_cluster_after_retune(self):
        from repro.configs import get_smoke_config
        from repro.data.pipeline import DataConfig, make_pipeline
        from repro.models import build_model
        from repro.optim.adamw import AdamWConfig
        from repro.train import TrainConfig, Trainer

        rng = np.random.default_rng(12)
        A_sparse = jnp.asarray(block_sparse(rng, 128, 96, 0.99))
        A_dense = jnp.asarray(rng.standard_normal((128, 96)), jnp.float32)
        plan = compile_plan(A_sparse, scheme="proposed", n=6, s=2)
        cfg = get_smoke_config("phi3-mini-3.8b")
        model = build_model(cfg, dtype=jnp.float32)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
        with plan.to_cluster() as cl:
            # the provider drifts the operand across the crossover:
            # the first retune recompiles and must re-ship the shards
            tr = Trainer(model, AdamWConfig(lr=1e-3, warmup_steps=2,
                                            total_steps=4),
                         TrainConfig(steps=4, ckpt_dir=None, retune_every=2),
                         coded_plans=[(plan, lambda params: A_dense, cl)])
            tr.fit(lambda s: make_pipeline(dcfg, s), resume=False)
            assert tr.retunes[0]["backend"] == "reference"
            assert tr.retunes[0]["reshipped_bytes"] > 0
            # second retune: same operand object, nothing recompiled
            assert "reshipped_bytes" not in tr.retunes[1]
            x = jnp.asarray(rng.standard_normal(128), jnp.float32)
            np.testing.assert_allclose(np.asarray(cl.matvec(x)),
                                       np.asarray(x @ A_dense), **TOL)
