"""The ``Tracer``: spans and events in a bounded monotonic ring buffer.

Design constraints (ISSUE 8):

- **Near-zero cost when disabled.**  A disabled tracer is not a tracer
  with a flag -- it is ``None``.  Every instrumented hot path holds the
  tracer in a local and guards with ``if tr is not None``: one
  attribute load + one identity check, nothing else.  The ≤2 %
  closed-loop overhead criterion in ``BENCH_obs.json`` is measured
  against exactly that guard.
- **Monotonic timeline.**  All span endpoints are ``time.perf_counter``
  seconds; the tracer also records the ``(wall, mono)`` pair taken at
  construction so any record can be re-anchored to wall-clock time
  (``wall_of``) and joined with the fleet event log, which stamps both.
- **Bounded.**  Records land in a ``deque(maxlen=capacity)`` ring;
  capacity comes from ``REPRO_TRACE_BUF`` (default 4096).  Appends are
  GIL-atomic, so the fleet loop, the router scheduler thread, and
  in-process memory-transport workers can all write without a lock.

- **On the profiler's clock.**  ``span`` also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so while a
  profiler session runs every program span lands on the trace's host
  plane beside the device's operations.  Outside a session the
  annotation records nothing.  ``complete`` (past endpoints) and
  ``instant`` stay in the ring alone.
- **Counters.**  ``count(name, n)`` adds to a named counter and
  ``counters()`` snapshots them all.  Every tracer also counts
  ``jit.lowerings``: each program JAX lowers in the process, whether it
  is then compiled or loaded from the persistent cache (one listener on
  JAX's monitoring events, registered with the first tracer).

Record shape (a plain dict; ``export.chrome_trace`` maps it to the
Chrome trace-event format)::

    {"name": str, "cat": str, "ph": "X"|"i", "track": str,
     "t": float,            # perf_counter seconds (span start / instant)
     "dur": float,          # seconds; present on "X" (complete spans)
     "trace": int,          # 0 = unaffiliated, else a trace id
     "args": dict}          # structured payload; attribution reads it
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
import weakref
from collections import Counter, deque

from jax import monitoring
from jax.profiler import TraceAnnotation

from .._env import env_int

ENV_TRACE = "REPRO_TRACE"
ENV_TRACE_BUF = "REPRO_TRACE_BUF"
DEFAULT_BUF = 4096

# the spans the coded plan path records, set-up first, then per call
PROGRAM_SPANS = (
    "plan.compile",       # compile_plan, whole
    "plan.encode",        # encode of the operand A
    "plan.pack",          # coded shards to host, tile loop, upload
    "plan.prewarm",       # decode plan of the all-alive mask
    "plan.matvec",        # one product, from the plan's entry to return
    "plan.matmat",
    "plan.encode_b",      # matmat: split and encode of B
    "executor.select",    # decode-cache lookup (a new pattern: its plan
                          # and block-row table); matmat: one a worker,
                          # with its B shard
    "executor.worker",    # worker-kernel launch (matvec: x padded first)
    "executor.decode",    # decode-kernel launch and its operand
    "executor.output",    # reshapes and slices to the output's layout
    # a plan placed on devices (``repro.runtime.executor``)
    "executor.launch",    # one device: x padded there, its kernel launch
    "executor.harvest",   # last launch until k workers' results are ready
    "executor.gather",    # those results moved to the decoding device
)
LOWERINGS = "jit.lowerings"
# JAX's event around each lowering of a program to an MLIR module
# (``jax._src.dispatch.JAXPR_TO_MLIR_MODULE_EVENT``)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_LIVE: weakref.WeakSet = weakref.WeakSet()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == LOWERING_EVENT:
        for tr in list(_LIVE):
            tr.count(LOWERINGS)


def _listen_lowerings(tracer: "Tracer") -> None:
    """Counts lowerings into ``tracer`` from now on; the one listener
    is registered with JAX the first time."""
    global _LISTENING
    with _LISTEN_LOCK:
        _LIVE.add(tracer)
        if not _LISTENING:
            monitoring.register_event_duration_secs_listener(_on_duration)
            _LISTENING = True


def trace_buf_capacity() -> int:
    """Ring-buffer capacity: ``REPRO_TRACE_BUF`` or 4096."""
    return env_int(ENV_TRACE_BUF, DEFAULT_BUF)


class _Span:
    """Context manager recording one complete ("X") span on exit."""

    __slots__ = ("_tracer", "_name", "_cat", "_track", "_trace", "_args",
                 "_t0", "_note")

    def __init__(self, tracer, name, cat, track, trace, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._track = track
        self._trace = trace
        self._args = args

    def __enter__(self):
        self._note = TraceAnnotation(self._name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._note.__exit__(exc_type, exc, tb)
        self._tracer.complete(self._name, self._t0, t1, cat=self._cat,
                              track=self._track, trace=self._trace,
                              **self._args)
        return False


class Tracer:
    """Span/event sink over a bounded monotonic-clock ring buffer.

    An *instance* is always enabled -- "disabled" is represented by the
    absence of a tracer (``None``), so instrumented code pays only an
    identity check.  ``default_tracer()`` resolves the process-global
    instance when ``REPRO_TRACE=1`` and ``None`` otherwise.
    """

    def __init__(self, capacity: int | None = None):
        cap = capacity if capacity and capacity > 0 else trace_buf_capacity()
        self.capacity = cap
        self._buf: deque[dict] = deque(maxlen=cap)
        self._ids = itertools.count(1)
        # the (wall, mono) anchor pair: lets every perf_counter stamp in
        # the buffer be re-expressed as wall time, and joins span
        # timelines with event logs that stamp both clocks
        self.t0_wall = time.time()
        self.t0_mono = time.perf_counter()
        self._counts: Counter = Counter()
        self._counts_lock = threading.Lock()
        _listen_lowerings(self)

    # -- ids ---------------------------------------------------------------

    def new_trace_id(self) -> int:
        """A fresh nonzero id tying one logical request's records
        together across layers (router -> fleet -> worker)."""
        return next(self._ids)

    # -- recording ---------------------------------------------------------

    def instant(self, name: str, *, cat: str = "event",
                track: str = "main", trace: int = 0, **args) -> None:
        """Record a point-in-time event."""
        self._buf.append({"name": name, "cat": cat, "ph": "i",
                          "track": track, "t": time.perf_counter(),
                          "trace": trace, "args": args})

    def complete(self, name: str, t0: float, t1: float, *,
                 cat: str = "span", track: str = "main", trace: int = 0,
                 **args) -> None:
        """Record a complete span from explicit perf_counter endpoints
        (the fleet reconstructs worker-side spans coordinator-side from
        wire timestamps, so endpoints are often not "now")."""
        self._buf.append({"name": name, "cat": cat, "ph": "X",
                          "track": track, "t": t0,
                          "dur": max(0.0, t1 - t0), "trace": trace,
                          "args": args})

    def span(self, name: str, *, cat: str = "span", track: str = "main",
             trace: int = 0, **args) -> _Span:
        """``with tracer.span("plan.compile"): ...`` -- times the block
        and records one complete span on exit; inside a profiler
        session the block is also a host event of the same name."""
        return _Span(self, name, cat, track, trace, args)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._counts_lock:
            self._counts[name] += n

    # -- reading -----------------------------------------------------------

    def events(self) -> list[dict]:
        """Snapshot of the ring buffer, oldest first."""
        return list(self._buf)

    def counters(self) -> dict[str, int]:
        """Snapshot of every counter."""
        with self._counts_lock:
            return dict(self._counts)

    def clear(self) -> None:
        """Empty the ring; counters keep counting."""
        self._buf.clear()

    def __len__(self) -> int:
        return len(self._buf)

    def wall_of(self, t_mono: float) -> float:
        """Re-anchor a perf_counter stamp to wall-clock seconds."""
        return self.t0_wall + (t_mono - self.t0_mono)


_OFF = contextlib.nullcontext()


def optional_span(tracer: Tracer | None, name: str, **kw):
    """``tracer.span(name, **kw)``, or a context that does nothing
    where ``tracer`` is None.  For set-up code: a per-call path takes
    ``traced_call``, which pays no context where tracing is off."""
    return _OFF if tracer is None else tracer.span(name, **kw)


def traced_call(tracer: Tracer | None, name: str, fn, *args,
                cat: str = "span", track: str = "main"):
    """``fn(*args)``, inside the span ``name`` where ``tracer`` is not
    None: one identity check where tracing is off."""
    if tracer is None:
        return fn(*args)
    with tracer.span(name, cat=cat, track=track):
        return fn(*args)


_GLOBAL: Tracer | None = None


def default_tracer() -> Tracer | None:
    """The process-global tracer when ``REPRO_TRACE`` is truthy, else
    ``None`` (the disabled representation).  Instrumented constructors
    call this once; hot paths never re-read the environment."""
    global _GLOBAL
    if os.environ.get(ENV_TRACE, "") in ("", "0"):
        return None
    if _GLOBAL is None:
        _GLOBAL = Tracer()
    return _GLOBAL
