"""Subprocess transport: wire bytes over ``multiprocessing`` pipes.

One spawned child per worker (spawn context, so children never inherit
jax state; their task path is pure numpy + scipy).  Everything crossing
the pipe is wire bytes inside ``(kind, bytes)`` tuples; the child runs
the shared ``serve_loop`` with a reader thread pumping the pipe into
its inbox and a heartbeat ticker beating on the same channel results
travel on.  A child that exits without a death notice (real fail-stop)
is detected by the parent pump's EOF -- and a child whose serve loop
*hangs* parks with the pipe open, invisible to everything except the
dispatcher's heartbeat timeout.

Membership is dynamic (wire v4): ``add_worker`` forks a fresh child
mid-run (reviving a dead id on reconnect) and pushes a ``WorkerJoin``;
``remove_worker`` reaps one child without a death notice (graceful
leave); ``garble`` sends a corrupt frame the child must answer with a
death notice.
"""

from __future__ import annotations

import os
import queue
import threading
import time

from ..faults import from_spec
from ..wire import Task, TaskResult, WorkerJoin, death_notice, decode_event
from ..worker import serve_loop, start_heartbeat
from .base import Transport, start_worker_process


def _pipe_worker_main(conn, worker_id: int, fault_spec, heartbeat_s: float
                      ) -> None:
    """Child entry point: pump pipe -> inbox, serve, beat."""
    faults = from_spec(fault_spec)
    inbox: queue.Queue = queue.Queue()
    send_lock = threading.Lock()
    parked = threading.Event()          # set when a stop/EOF reached the pump

    def emit(event) -> None:
        with send_lock:
            conn.send(("event", event.encode()))

    def pump() -> None:
        try:
            while True:
                msg = conn.recv()
                if msg[0] == "stop":
                    parked.set()
                inbox.put(msg)
        except (EOFError, OSError):     # dispatcher went away
            parked.set()
            inbox.put(("stop", None))

    with send_lock:                     # ready: imports are done, serve
        # loop is about to start; the perf_counter sample is the wire-v5
        # clock handshake (parent derives this child's clock offset)
        conn.send(("hello", (worker_id, time.perf_counter())))
    threading.Thread(target=pump, daemon=True).start()
    stop_beats = threading.Event()
    start_heartbeat(worker_id, emit, heartbeat_s, stop_beats,
                    mute=getattr(faults, "should_mute", None))
    try:
        status = serve_loop(worker_id, inbox, emit, faults,
                            stop_beats=stop_beats)
    except (BrokenPipeError, OSError):
        return
    if status == "hang":
        # mute with the pipe open: only the dispatcher's heartbeat
        # timeout can catch this worker -- but exit promptly once the
        # dispatcher says stop, so close() never waits out a join
        # timeout on a parked child
        parked.wait()
        os._exit(0)


class PipeTransport(Transport):
    name = "pipe"

    def __init__(self, n_workers: int, *, faults=None,
                 heartbeat_s: float = 0.25):
        super().__init__(n_workers, faults=faults, heartbeat_s=heartbeat_s)
        self._conns: dict = {}
        self._procs: dict = {}
        self._pumps: dict[int, threading.Thread] = {}
        self._ready: dict[int, threading.Event] = {}
        self._leaving: set[int] = set()

    def _spawn(self, w: int) -> None:
        import multiprocessing as mp  # noqa: PLC0415

        ctx = mp.get_context("spawn")
        conn, child = ctx.Pipe()
        proc = ctx.Process(
            target=_pipe_worker_main,
            args=(child, w, self.faults.to_spec(), self.heartbeat_s),
            daemon=True)
        start_worker_process(proc)
        child.close()
        self._conns[w] = conn
        self._procs[w] = proc
        self._ready[w] = threading.Event()
        pump = threading.Thread(target=self._pump, args=(w, conn),
                                daemon=True)
        pump.start()
        self._pumps[w] = pump

    def start(self, shard_blobs: list[bytes] | None = None) -> int:
        shipped = 0
        for w in sorted(self._known):
            self._spawn(w)
        for w, blob in enumerate(shard_blobs or []):
            shipped += self.ship_shard(w, blob)
        # don't hand the transport over until every child finished its
        # (slow: spawn + numpy/scipy import) startup -- otherwise the
        # liveness protocol would suspect workers that never got to beat
        for w, evt in self._ready.items():
            if not evt.wait(timeout=60):
                self.close()
                raise RuntimeError(f"pipe worker {w} never became ready")
        return shipped

    def _pump(self, worker: int, conn) -> None:
        try:
            while True:
                kind, data = conn.recv()
                if kind == "hello":
                    # wire v5 clock handshake: the child sampled its
                    # perf_counter at send; ours-at-receive minus that
                    # places its task timestamps on our timeline (error
                    # is the one-way hello latency)
                    if isinstance(data, tuple):
                        self.clock_offsets[worker] = \
                            time.perf_counter() - data[1]
                    self._ready[worker].set()
                    continue
                event = decode_event(data)
                if isinstance(event, TaskResult) and event.kind == "death":
                    self.mark_dead(worker)
                self.push_event(event)
        except (EOFError, OSError):
            if not self._closing and worker not in self._dead \
                    and worker not in self._leaving:
                # the process died without a notice: real fail-stop
                self.mark_dead(worker)
                self.push_event(death_notice(
                    worker, "worker process exited"))

    def _send(self, worker: int, msg) -> None:
        conn = self._conns.get(worker)
        if conn is None:
            return                      # left/removed: nothing to send to
        try:
            conn.send(msg)
        except (BrokenPipeError, OSError):
            pass                        # pump reports the death

    def ship_shard(self, worker: int, blob: bytes) -> int:
        self._send(worker, ("shard", blob))
        return len(blob)

    def submit(self, worker: int, task: Task) -> int:
        # encode() is single-copy since wire v6 (one gather join); the
        # pipe carries the flat frame, so that join is the task path's
        # only serialization memcpy -- recorded for the wire bench
        data = task.encode()
        self.bytes_copied += len(data)
        self._send(worker, ("task", data))
        return len(data)

    def cancel(self, worker: int, round_id: int) -> None:
        self._send(worker, ("cancel", round_id))

    def drop_plan(self, worker: int, plan_id: int) -> None:
        self._send(worker, ("drop", plan_id))

    def confirm_join(self, worker: int, plans: int = 0) -> None:
        self._send(worker, ("welcome", plans))

    # -- dynamic membership (wire v4) ---------------------------------------

    def add_worker(self, worker: int | None = None) -> int:
        w = self.next_worker_id() if worker is None else int(worker)
        if self.alive(w) and self._procs[w].is_alive():
            raise ValueError(f"worker {w} is already serving")
        self._reap(w)                   # a dead predecessor, if any
        self._leaving.discard(w)
        self._known.add(w)
        self.revive(w)
        self._spawn(w)
        if not self._ready[w].wait(timeout=60):
            self._reap(w)
            raise RuntimeError(f"pipe worker {w} never became ready")
        self.push_event(WorkerJoin(worker=w))
        return w

    def _reap(self, w: int, timeout: float = 2.0) -> None:
        proc = self._procs.pop(w, None)
        conn = self._conns.pop(w, None)
        if conn is not None:
            try:
                conn.send(("stop", None))
            except (BrokenPipeError, OSError):
                pass
        if proc is not None:
            proc.join(timeout=timeout)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=timeout)
        if conn is not None:
            conn.close()
        pump = self._pumps.pop(w, None)
        if pump is not None:
            pump.join(timeout=timeout)
        self._ready.pop(w, None)

    def remove_worker(self, worker: int) -> None:
        # the leaving mark silences the pump's EOF death notice -- a
        # graceful leave is not a fail-stop
        self._leaving.add(worker)
        self.mark_dead(worker)
        self._known.discard(worker)
        self._reap(worker)

    def garble(self, worker: int) -> int:
        blob = b"\x00garbled-frame"
        self._send(worker, ("task", blob))
        return len(blob)

    def close(self) -> None:
        if self._closing:
            return
        self._closing = True
        for w in list(self._conns):
            self._send(w, ("stop", None))
        for proc in self._procs.values():
            proc.join(timeout=2)
            if proc.is_alive():         # hung or stuck child
                proc.terminate()
                proc.join(timeout=2)
        for conn in self._conns.values():
            conn.close()
        for pump in self._pumps.values():
            pump.join(timeout=2)
