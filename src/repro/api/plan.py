"""Plan compilation: scheme + encoding + packed shards + backend, once.

``compile_plan`` is the repo's entry point for coded computation.  It
fuses everything that is per-*operator* rather than per-*call*:

  * the scheme (via the registry, ``repro.api.schemes``),
  * the encoding matrices (host numpy, seeded),
  * the encoded / packed shards (weight-omega encode + block-sparse
    packing on the sparse backends),
  * the backend choice (``backend="auto"`` measures the operand's block
    density and applies the BENCH_runtime.json crossover, see
    ``repro.api.backends``),
  * a pre-warmed decode cache (the all-alive pattern -- the common case
    on a healthy cluster -- never pays a solve).

The compiled ``CodedPlan`` then exposes the three per-call operations:

    plan = compile_plan(A, scheme="cyclic31", n=12, s=3, backend="auto")
    y = plan.matvec(x, done=mask)        # A^T x, straggler-resilient
    U = plan.matmat(B, done=mask)        # A^T B   (mm plans)
    g = plan.aggregate(payloads, done=mask)  # coded gradient sum

Plans compiled without an operand (``compile_plan(scheme=..., n=...)``)
are aggregation-only: they own the decode machinery (LRU per-pattern
inverse) but no shards -- that is what ``CodedAggregator`` rides on.

Why one object: it can be built once at init/checkpoint-load, cached on
the layer, shipped to the serving engine, and re-tuned (re-compiled)
when the operand's density drifts across the packed/reference crossover.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.assignment import MMScheme, MVScheme
from ..core.coded_matmul import fastest_k_rows, split_block_columns
from ..core.decoding import system_matrix
from ..core.encoding import mm_encoding_matrices, mv_encoding_matrix
from ..obs.trace import default_tracer, optional_span, traced_call
from ..runtime import (
    CodedExecutor,
    DecodeCache,
    encode_blocks,
    is_concrete as _is_concrete,
    support_tables,
)
from .backends import choose_backend
from .schemes import make_scheme


_PLAN = {"cat": "plan", "track": "plan"}


def _match_dtype(coded, A):
    """Keep the encoded shards in the operand dtype.

    The weight-omega encoders accumulate in f32; a bf16 operand (LM-head
    serving) must not silently double the coded shards' footprint --
    the n/k-redundant shards are the dominant memory cost.
    """
    if isinstance(coded, jax.core.Tracer) or coded.dtype == A.dtype:
        return coded
    return coded.astype(A.dtype)


@dataclass(eq=False)
class CodedPlan:
    """A precompiled coded operator (see module docstring).

    Public attributes are read-only by convention; per-call state lives
    entirely in the LRU decode cache (safe to share across steps).
    """

    scheme: MVScheme | MMScheme
    kind: str                       # "mv" | "mm"
    backend: str                    # concrete backend (auto already resolved)
    seed: int
    G: np.ndarray                   # (n_tasks, k) decode system matrix
    r: int | None = None            # logical output dim (None: aggregation-only)
    executor: CodedExecutor | None = field(default=None, repr=False)
    # mm-only: per-call B-side encoding state
    cache_size: int = 64
    _rb: np.ndarray | None = field(default=None, repr=False)
    _sup_b: np.ndarray | None = field(default=None, repr=False)
    _coef_b: np.ndarray | None = field(default=None, repr=False)
    _agg_cache: DecodeCache | None = field(default=None, repr=False)
    # operand reference kept for online re-tuning (``retune``); a jax
    # array reference, not a copy -- the caller's weights stay the
    # single allocation
    _A: object | None = field(default=None, repr=False)
    # devices the workers are placed on (``repro.runtime.executor``);
    # None: one device holds them all
    devices: tuple | None = field(default=None, repr=False)
    _tracer: object | None = field(default=None, init=False, repr=False)

    def __post_init__(self):
        self._tracer = default_tracer()

    # -- introspection ----------------------------------------------------

    @property
    def n(self) -> int:
        return self.scheme.n

    @property
    def k(self) -> int:
        return self.scheme.k

    @property
    def s(self) -> int:
        return self.scheme.s

    @property
    def tasks_per_worker(self) -> int:
        return getattr(self.scheme, "tasks_per_worker", 1)

    @property
    def n_tasks(self) -> int:
        return self.G.shape[0]

    def describe(self) -> dict:
        """Metadata for logs / benchmarks / schedulers."""
        d = {
            "scheme": self.scheme.name, "kind": self.kind,
            "backend": self.backend, "n": self.n, "k": self.k,
            "s": self.s, "weight": self.scheme.weight(), "seed": self.seed,
        }
        if self.executor is not None and self.executor.cache is not None:
            d["decode_cache"] = {"hits": self.executor.cache.hits,
                                 "misses": self.executor.cache.misses}
        if self.devices is not None and self.executor is not None:
            d["placement"] = self.executor.placement()
        return d

    def worker_tile_counts(self) -> np.ndarray:
        """Nonzero packed tiles per worker (the omega-scaling quantity)."""
        if self.executor is None:
            raise ValueError("aggregation-only plan holds no shards")
        return self.executor.worker_tile_counts()

    # -- done-mask plumbing ----------------------------------------------

    def _task_done(self, done):
        """Worker-level done mask -> task-row mask (Delta-partition
        baselines run ``tasks_per_worker`` tasks per worker).  A mask
        already at task granularity (length ``n_tasks``) passes through
        -- that is how partial stragglers are expressed: a slow worker
        whose mask covers only SOME of its task rows."""
        if done is None:
            return None
        per = self.tasks_per_worker
        if per == 1 or np.shape(done)[0] == self.n_tasks:
            return done
        if _is_concrete(done):
            return np.repeat(np.asarray(done, bool), per)
        return jnp.repeat(done, per)

    def _decode_cache(self) -> DecodeCache:
        if self.executor is not None and self.executor.cache is not None:
            return self.executor.cache
        if self._agg_cache is None:
            self._agg_cache = DecodeCache(self.G, self.k,
                                          maxsize=self.cache_size)
        return self._agg_cache

    # -- per-call operations ----------------------------------------------

    def matvec(self, x, done=None):
        """A^T x for x (t,) or (batch, t); tolerates any s stragglers.

        On a plan placed over devices, ``done=None`` decodes from the
        workers that finished first, and the result lies on the device
        that decoded it."""
        if self.kind != "mv":
            raise ValueError("matvec needs an mv plan; this plan is "
                             f"kind={self.kind!r}")
        if self.executor is None:
            raise ValueError("plan compiled without an operand; pass A to "
                             "compile_plan for matvec")
        return traced_call(self._tracer, "plan.matvec", self.executor.matvec,
                           x, self._task_done(done), **_PLAN)

    def matmat(self, B, done=None):
        """A^T B through the paired-encode pipeline; returns (r, w)."""
        if self.kind != "mm":
            raise ValueError("matmat needs an mm plan; this plan is "
                             f"kind={self.kind!r}")
        if self.executor is None:
            raise ValueError("plan compiled without an operand; pass A to "
                             "compile_plan for matmat")
        return traced_call(self._tracer, "plan.matmat", self._matmat, B,
                           done, **_PLAN)

    def _matmat(self, B, done):
        tr = self._tracer
        coded_b = traced_call(tr, "plan.encode_b", self._encode_b, B, done,
                              **_PLAN)
        u = self.executor.matmat(coded_b, done)      # (k, ca, cb)
        return traced_call(tr, "executor.output", self._assemble, u,
                           B.shape[1], **_PLAN)

    def _encode_b(self, B, done):
        """B's k_B block-columns, encoded into the n coded B shards."""
        blocks_b = split_block_columns(B, self.scheme.k_B)
        if self.backend == "reference" or not _is_concrete(B, done):
            return jnp.einsum("nk,ktc->ntc",
                              jnp.asarray(self._rb, B.dtype), blocks_b)
        return encode_blocks(blocks_b, self._sup_b, self._coef_b,
                             self.backend)

    def _assemble(self, u, w):
        """Decoded unknowns (k, ca, cb) -> A^T B (r, w)."""
        ka, kb = self.scheme.k_A, self.scheme.k_B
        ca, cb = u.shape[1], u.shape[2]
        out = u.reshape(ka, kb, ca, cb).transpose(0, 2, 1, 3)
        return out.reshape(ka * ca, kb * cb)[: self.r, : w]

    def aggregate(self, payloads, done=None):
        """Straggler-resilient sum of the k shard-gradients.

        ``payloads`` is the length-n list of worker payload pytrees
        (each ``sum_q R[i,q] g_q`` over the worker's support; straggler
        entries may hold garbage -- they are masked by ``done``).  The
        decode coefficient vector ``a`` (``a^T R[rows] = 1^T``) comes
        from the LRU-cached per-pattern inverse, so repeated steps under
        the same done mask never re-run a k x k solve.
        """
        if self.kind != "mv":
            raise ValueError("aggregate needs an mv plan; this plan is "
                             f"kind={self.kind!r}")
        k = self.k
        task_done = self._task_done(done)
        if _is_concrete(task_done):
            dplan = self._decode_cache().plan(task_done)
            # a^T G[rows] = 1^T  <=>  a = (G[rows]^{-1})^T 1 = colsums(hinv)
            a = jnp.asarray(dplan.hinv.sum(axis=0))
            rows = dplan.rows
        else:
            rows = fastest_k_rows(task_done, k)
            sub = jnp.asarray(self.G, jnp.float32)[rows]
            a = jnp.linalg.solve(sub.T, jnp.ones((k,), jnp.float32))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
        return jax.tree.map(
            lambda st: jnp.einsum("i,i...->...", a, st[rows]), stacked)

    # -- distribution ------------------------------------------------------

    def to_cluster(self, n_workers: int | None = None, *,
                   transport: str | None = None, backend: str | None = None,
                   faults=None, deadline: float | None = None, **kw):
        """Serve this plan from real workers (``repro.cluster``).

        Returns a ``ClusterPlan`` with the same ``matvec / matmat /
        aggregate`` signatures; per-worker ``PlanShard``s are shipped
        once at construction and every call dispatches tasks, collects
        results asynchronously and decodes at the fastest-k task set.
        ``transport`` picks the byte carrier (``memory`` | ``pipe`` |
        ``tcp``; default: the ``REPRO_CLUSTER_TRANSPORT`` env var, then
        ``memory``) -- ``backend=`` is the legacy worker-backend
        spelling (``thread``/``process``).  ``n_workers`` < n hosts
        several virtual workers per physical one (the partial-straggler
        setting).  Extra keywords (``heartbeat_s``, ``suspect_after``)
        tune the liveness protocol.  Shut the cluster down (``with``
        block or ``.shutdown()``) when done -- the transport owns real
        sockets/processes/threads.

        A ``ClusterPlan`` is a private single-plan session (one fleet,
        ``max_inflight=1``).  To share one worker set across several
        plans -- and get async futures, pipelined in-flight rounds and
        matvec microbatching -- build a ``repro.api.fleet.CodedFleet``
        and ``fleet.attach(plan)`` instead.
        """
        from ..cluster import ClusterPlan  # noqa: PLC0415 - optional layer

        return ClusterPlan(self, n_workers, transport=transport,
                           backend=backend, faults=faults,
                           deadline=deadline, **kw)

    # -- online re-tuning --------------------------------------------------

    def retune(self, A=None, *, crossover: float | None = None) -> str:
        """Re-measure sparsity and re-pick the backend (ROADMAP item).

        Training-time pruning (or densification) drifts the operand
        across the packed/reference crossover; ``retune`` re-runs the
        density pick on the current operand and recompiles the
        encoded/packed state when either the backend choice or the
        operand itself changed.  ``A=None`` re-measures the operand the
        plan was compiled with (cheap no-op when nothing moved).
        Returns the (possibly updated) backend name.
        """
        A = A if A is not None else self._A
        if A is None:
            raise ValueError("plan holds no operand; pass A= to retune")
        if not _is_concrete(A):
            raise ValueError("retune needs a concrete operand")
        new = choose_backend(A, "auto", crossover=crossover)
        if new != self.backend or A is not self._A:
            self.backend = new
            _attach_operand(self, A, new)
        return self.backend

    # -- cache management --------------------------------------------------

    def prewarm(self, done=None) -> "CodedPlan":
        """Precompute the decode plan for a pattern (default all-alive)."""
        if self.executor is not None and self.executor.cache is None:
            # reference executor: matvec/matmat solve per call and never
            # consult a cache -- warming one would be a wasted inversion
            return self
        task_done = self._task_done(done)
        if _is_concrete(task_done):
            self._decode_cache().plan(task_done)
        return self


def compile_plan(A=None, *, scheme="proposed", n=None, s=None,
                 k_A=None, k_B=None, capacities=None, seed: int = 0,
                 backend: str | None = "auto",
                 cache_size: int = 64, devices=None) -> CodedPlan:
    """Compile a ``CodedPlan`` (see module docstring).

    ``scheme`` is a registry name (``repro.api.list_schemes()``) or an
    already-built ``MVScheme`` / ``MMScheme`` descriptor.  ``backend=
    "auto"`` (the default) measures A's block density and applies the
    packed/reference crossover (``pallas`` on TPU); the
    ``REPRO_CODED_BACKEND`` env var overrides everything, including
    auto.  Without ``A`` the plan is aggregation-only.

    ``devices`` places an MV plan's workers over those devices, ``n``
    divided evenly among them (``repro.runtime.executor``); None keeps
    them all on one device.
    """
    tr = default_tracer()
    t0 = time.perf_counter() if tr is not None else 0.0
    if isinstance(scheme, (MVScheme, MMScheme)):
        sch = scheme
    else:
        sch = make_scheme(scheme, n=n, s=s, k_A=k_A, k_B=k_B,
                          capacities=capacities)
    kind = "mm" if isinstance(sch, MMScheme) else "mv"
    if devices is not None:
        if A is None:
            raise ValueError("a plan placed on devices needs its operand A")
        devices = tuple(devices)
    G = np.asarray(system_matrix(sch, seed))
    resolved = choose_backend(A, backend)

    plan = CodedPlan(scheme=sch, kind=kind, backend=resolved, seed=seed,
                     G=G, cache_size=cache_size, devices=devices)

    if A is not None:
        _attach_operand(plan, A, resolved)
    elif kind == "mv":
        plan.prewarm()      # aggregation-only: warm the all-alive pattern
    if tr is not None:
        tr.complete("plan.compile", t0, time.perf_counter(), cat="plan",
                    track="plan", kind=kind, backend=resolved,
                    n=sch.n, has_operand=A is not None)
    return plan


def _attach_operand(plan: CodedPlan, A, resolved: str) -> None:
    """(Re)build the per-operand state: encode, pack, prewarm.

    Shared by initial compilation and ``plan.retune`` -- re-tuning is
    literally re-running this attachment against the drifted operand.
    The executor packs the coded shards (``plan.pack``).
    """
    if A.ndim != 2:
        raise ValueError(f"operand must be 2-D (t, r), got {A.shape}")
    sch, tr = plan.scheme, plan._tracer
    with optional_span(tr, "plan.encode", cat="plan", track="plan",
                       kind=plan.kind, backend=resolved,
                       shape=list(A.shape)):
        if plan.kind == "mv":
            R = mv_encoding_matrix(sch, plan.seed)
            coded = _encode(A, sch.k_A, sch.supports, R, resolved)
            k = sch.k_A
        else:
            ra, rb = mm_encoding_matrices(sch, plan.seed)
            coded = _encode(A, sch.k_A, sch.supports_A, ra, resolved)
            k = sch.k
            plan._rb = rb
            plan._sup_b, plan._coef_b = (
                (None, None) if resolved == "reference"
                else support_tables(sch.supports_B, rb))
    plan.executor = CodedExecutor(
        _match_dtype(coded, A), jnp.asarray(plan.G, jnp.float32), k,
        A.shape[1], backend=resolved, devices=plan.devices,
        cache_size=plan.cache_size)
    plan.r = A.shape[1]
    if _is_concrete(A):
        plan._A = A
        with optional_span(tr, "plan.prewarm", cat="plan", track="plan"):
            plan.prewarm()


def _encode(A, k: int, supports, R, resolved: str):
    """A's k block-columns, encoded by R into the n coded shards."""
    blocks = split_block_columns(A, k)
    if resolved == "reference":
        return jnp.einsum("nk,ktc->ntc", jnp.asarray(R, A.dtype), blocks)
    sup, coef = support_tables(supports, R)
    return encode_blocks(blocks, sup, coef, resolved)
