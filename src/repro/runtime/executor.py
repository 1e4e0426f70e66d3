"""Coded executor: one API, pluggable sparsity-aware backends.

Why this exists: the paper's claim is that weight-omega encodings keep
the per-worker cost proportional to ``omega / k_A`` of the dense cost.
The backends realise that claim at different altitudes:

  * ``reference``        -- pure-jnp dense einsum over ALL n workers and a
    per-call ``jnp.linalg.solve`` (the original code path).  Fully
    traceable (jit / grad / shard_map) and the numerics baseline.
  * ``packed``           -- host **packed block-sparse** path: the packed
    tiles are exported as scipy BSR shards (the paper's CSR workers,
    block-adapted), only the fastest-k workers' shards are multiplied,
    and decode is a cached-inverse matmul.  Work scales with the
    nonzero-tile count, i.e. with omega.  The CPU fast path.
  * ``pallas``           -- the same packed layout dispatched to the Pallas
    TPU kernels (``bcsr_matmul``, ``cyclic_encode``, ``decode_matmul``).
  * ``pallas-interpret`` -- the Pallas kernels in interpreter mode; used to
    validate the kernel path on CPU.

Backend selection: the ``REPRO_CODED_BACKEND`` environment variable
overrides everything (how you force a backend); otherwise an explicit
``backend=`` argument wins; otherwise the platform default applies
(``pallas`` on TPU, ``reference`` elsewhere -- the reference path keeps
CPU tests on the original numerics).

The sparse backends need *concrete* inputs (the decode cache and the
fastest-k worker selection live on the host); when called under a
trace (jit/grad/vmap/shard_map) the executor transparently falls back
to the reference path, so a single call site serves both worlds.
"""

from __future__ import annotations

import functools
import math
import os
import threading
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.bcsr_matmul import bcsr_grid, bcsr_matmul
from ..kernels.cyclic_encode import cyclic_encode
from ..kernels.decode_matmul import decode_matmul
from ..kernels.ref import cyclic_encode_ref
from ..obs.trace import default_tracer, optional_span, traced_call
from .decode_cache import DecodeCache
from .pack import (BlockRows, PackedShards, _round_up, bsr_shards,
                   pack_coded_blocks)

ENV_BACKEND = "REPRO_CODED_BACKEND"

BACKENDS = ("reference", "packed", "pallas", "pallas-interpret")

# kernel-path backends; "packed" shares their layout but runs pure jnp
_KERNEL_BACKENDS = ("pallas", "pallas-interpret")


def resolve_backend(backend: str | None = None) -> str:
    """Env override > explicit argument > platform default.

    ``"auto"`` (and None) resolve to the platform default here; the
    density-aware auto pick lives in ``repro.api.backends.choose_backend``
    -- plan compilation resolves "auto" *before* reaching this layer, so
    an "auto" that arrives here simply means "no operand to measure".
    """
    env = os.environ.get(ENV_BACKEND)
    if env and env != "auto":
        backend = env       # a concrete env backend forces every call site
    if backend is None or backend == "auto":
        backend = ("pallas" if jax.devices()[0].platform == "tpu"
                   else "reference")
    if backend not in BACKENDS:
        raise ValueError(f"unknown coded backend {backend!r}; "
                         f"choose from {BACKENDS}")
    return backend


def is_concrete(*vals) -> bool:
    """True when no argument is a JAX tracer (None entries ignored).

    The sparse backends need concrete inputs (host-side packing, decode
    cache); every layer above uses this single check to decide between
    the fast path and the traceable reference fallback.
    """
    return not any(isinstance(v, jax.core.Tracer)
                   for v in vals if v is not None)


_is_concrete = is_concrete


def lane_tile(size: int, pref: int) -> tuple[int, int]:
    """(block, padded size) for one kernel dimension.

    A dimension that fits in one block stays whole; a longer one is
    padded up to a multiple of ``pref`` (itself a multiple of 128), so
    every block satisfies the TPU tiling rule: its last two dimensions
    are multiples of (8, 128) or the full array dimensions.
    """
    if size <= pref:
        return size, size
    return pref, _round_up(size, pref)


def _pad_to(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    if x.shape[axis] == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# Kernel calls: pad to the tiling, run, slice the padding off
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_kernel(blocks, sup, coef, *, interpret: bool = False):
    """``cyclic_encode`` of (k, T, C) block-columns.

    C stays whole; the row block is the largest power of two from 8 to
    128 that divides T rounded up to 8, so an aligned T is never copied.
    """
    t = blocks.shape[1]
    t_pad = _round_up(t, 8)
    bt = 128
    while t_pad % bt:
        bt //= 2
    out = cyclic_encode(_pad_to(blocks, 1, t_pad), sup, coef, bt=bt,
                        interpret=interpret)
    return out[:, :t]


@functools.partial(jax.jit, static_argnames=("interpret",))
def worker_kernel(a_data, a_idx, b, *, interpret: bool = False):
    """``bcsr_matmul`` C = A^T b for b (t_pad, N), N padded to its tile.

    ``a_idx`` is either the K-index table of every block-row of
    ``a_data``, or a ``BlockRows`` table naming the rows to read in
    place; the table is an argument, so a new one never recompiles.
    """
    blocks = None
    if isinstance(a_idx, BlockRows):
        blocks, a_idx = a_idx
    n = b.shape[1]
    bn, n_pad = lane_tile(n, 128)
    out = bcsr_matmul(a_data, a_idx, _pad_to(b, 1, n_pad), blocks=blocks,
                      bn=bn, interpret=interpret)
    return out[:, :n]


def worker_grid(a_data, table: BlockRows, n: int) -> tuple[int, int, int]:
    """The grid ``worker_kernel`` launches over for b of ``n`` columns."""
    bn, n_pad = lane_tile(n, 128)
    return bcsr_grid(a_data.shape, table.blocks.shape[0], n_pad, bn)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_kernel(hinv, y, *, interpret: bool = False):
    """``decode_matmul`` U = hinv @ y for y (k, P), P padded to its tile."""
    p = y.shape[1]
    bp, p_pad = lane_tile(p, 512)
    out = decode_matmul(hinv, _pad_to(y, 1, p_pad), bp=bp,
                        interpret=interpret)
    return out[:, :p]


def matvec_output(u, k: int, c: int, c_pad: int, r: int, b: int):
    """Decoded unknowns (k, c_pad * b) -> A^T x (b, r), eagerly."""
    u = u.reshape(k, c_pad, b)
    u = u[:, :c]                                    # drop padding
    out = jnp.moveaxis(u, 2, 0).reshape(b, -1)      # (b, k*c)
    return out[:, :r]


# ---------------------------------------------------------------------------
# Encoding (Alg. 1 / Alg. 2 line: coded_i = sum_j coef[i,j] * blocks[sup[i,j]])
# ---------------------------------------------------------------------------


def support_tables(supports, R) -> tuple[np.ndarray, np.ndarray]:
    """Padded (sup, coef) tables for the gather-style encoders.

    Rows are padded to the max support size with (index 0, coef 0.0)
    slots, which contribute nothing.
    """
    R = np.asarray(R)
    w = max(len(t) for t in supports)
    sup = np.zeros((len(supports), w), dtype=np.int32)
    coef = np.zeros((len(supports), w), dtype=np.float32)
    for i, t in enumerate(supports):
        idx = list(t)
        sup[i, : len(idx)] = idx
        coef[i, : len(idx)] = R[i, idx]
    return sup, coef


def encode_blocks(blocks, sup, coef, backend: str | None = None) -> jnp.ndarray:
    """Encode stacked block-columns (k, T, C) -> coded (n, T, C).

    O(omega) HBM reads per coded output on every backend except
    ``reference`` (which multiplies by the full n x k matrix the way
    the original code path did).
    """
    backend = resolve_backend(backend)
    blocks = jnp.asarray(blocks)
    sup = jnp.asarray(sup, jnp.int32)
    coef = jnp.asarray(coef, jnp.float32)
    if backend in _KERNEL_BACKENDS:
        return encode_kernel(blocks, sup, coef, interpret=backend != "pallas")
    # reference and packed: the jnp gather-einsum oracle is already the
    # weight-omega O(omega) encoder
    return cyclic_encode_ref(blocks, sup, coef)


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

# the per-call stages of the kernel path, each a span where tracing is on
SELECT, WORKER, DECODE, OUTPUT = ("executor.select", "executor.worker",
                                  "executor.decode", "executor.output")
# counters: worker launches that read the packed operand through a
# table, and the grid steps of those launches
TABLE_LAUNCHES = "executor.table_launches"
GRID_STEPS = "kernel.bcsr_grid_steps"
_STAGE = {"cat": "executor", "track": "executor"}


class CodedExecutor:
    """Backend-dispatched encode / worker-compute / decode engine.

    Bound to one pre-encoded operator: coded shards ``coded (n, t, c)``,
    system matrix ``G (n, k)`` and logical output width ``r``.  The
    public surface (``matvec``, ``matmat``, ``decode``) is what every
    call site in core/parallel/serve routes through.
    """

    def __init__(self, coded, G, k: int, r: int,
                 backend: str | None = None, *,
                 bk: int | None = None, bm: int | None = None,
                 cache_size: int = 64):
        self.backend = resolve_backend(backend)
        if not _is_concrete(coded, G):
            # a traced operand cannot be packed on the host; honour the
            # transparent-fallback contract instead of crashing
            self.backend = "reference"
        self.coded = jnp.asarray(coded)
        self.G = jnp.asarray(G, jnp.float32)
        self.k = k
        self.r = r
        self.n, self.t, self.c = self.coded.shape
        self.packed: PackedShards | None = None
        self.cache: DecodeCache | None = None
        self._bsr = None            # lazy scipy BSR shards ("packed")
        # calls per path taken: the backend name on the fast path,
        # "reference" where a traced input fell back
        self.calls: Counter = Counter()
        self._calls_lock = threading.Lock()
        self._tracer = default_tracer()
        if self.backend != "reference":
            tile = 128 if self.backend == "pallas" else 8
            with optional_span(self._tracer, "plan.pack", cat="plan",
                               track="plan"):
                self.packed = pack_coded_blocks(np.asarray(self.coded),
                                                bk or tile, bm or tile)
            kernel = self.backend in _KERNEL_BACKENDS
            # the kernel path reads the fastest-k workers' tiles through
            # a block-row table, built once a pattern with its plan
            self.cache = DecodeCache(
                np.asarray(self.G), k, maxsize=cache_size,
                tables=self.packed.block_rows if kernel else None)
            # matmat: one table a worker, each with its own B shard
            self._worker_tables = ([self.packed.block_rows([i])
                                    for i in range(self.n)] if kernel
                                   else None)

    def _bsr_shards(self):
        if self._bsr is None:
            self._bsr = bsr_shards(self.packed)
        return self._bsr

    # -- introspection ----------------------------------------------------

    def worker_tile_counts(self) -> np.ndarray:
        """Nonzero (bk x bm) tiles per worker -- the omega-scaling
        quantity (proportional to per-apply MXU work on this worker)."""
        if self.packed is None:
            packed = pack_coded_blocks(np.asarray(self.coded), 8, 8)
            return np.asarray(packed.tile_counts)
        return np.asarray(self.packed.tile_counts)

    def _interpret(self) -> bool:
        return self.backend != "pallas"

    def _fast_path(self, *vals) -> bool:
        fast = self.backend != "reference" and _is_concrete(*vals)
        with self._calls_lock:
            self.calls[self.backend if fast else "reference"] += 1
        return fast

    # -- matvec: A^T x ----------------------------------------------------

    def matvec(self, x: jnp.ndarray, done: jnp.ndarray | None = None
               ) -> jnp.ndarray:
        """A^T x for x (t,) or (batch, t); returns (r,) / (batch, r)."""
        squeeze = x.ndim == 1
        xb = x[None, :] if squeeze else x
        if self._fast_path(x, done):
            out = self._matvec_packed(xb, done)
        else:
            out = self._matvec_reference(xb, done)
        return out[0] if squeeze else out

    def _matvec_reference(self, xb, done):
        from ..core.coded_matmul import fastest_k_rows  # noqa: PLC0415
        if done is None:
            done = jnp.ones(self.n, dtype=bool)
        y = jnp.einsum("ntc,bt->nbc", self.coded, xb)
        rows = fastest_k_rows(done, self.k)
        sub = self.G[rows]
        ysub = y[rows].reshape(self.k, -1)
        u = jnp.linalg.solve(sub, ysub)
        b = xb.shape[0]
        u = u.reshape(self.k, b, -1).transpose(1, 0, 2).reshape(b, -1)
        return u[:, : self.r]

    def _matvec_packed(self, xb, done):
        if done is None:
            done = np.ones(self.n, dtype=bool)
        if self.backend in _KERNEL_BACKENDS:
            return self._matvec_kernel(xb, done)
        plan = self.cache.plan(done)
        packed = self.packed
        b = xb.shape[0]
        # scipy BSR shards: nnz-tile-proportional worker products,
        # stragglers (and zero tiles) never touched; stays host-side
        # numpy end-to-end to keep eager-dispatch overhead off the
        # hot path (one device transfer at the end)
        shards = self._bsr_shards()
        b_op = np.zeros((packed.t_pad, b), np.float32)
        b_op[: packed.t] = np.asarray(xb, np.float32).T[: packed.t]
        y = np.stack([shards[i] @ b_op for i in plan.rows])
        u = plan.hinv @ y.reshape(self.k, -1)
        u = u.reshape(self.k, packed.c_pad, b)[:, : packed.c]
        out = np.moveaxis(u, 2, 0).reshape(b, -1)[:, : self.r]
        return jnp.asarray(out)

    # -- the kernel path's stages ------------------------------------------

    def _matvec_kernel(self, xb, done):
        tr = self._tracer
        plan = traced_call(tr, SELECT, self.cache.plan, done, **_STAGE)
        if tr is not None:
            tr.count(TABLE_LAUNCHES)
            tr.count(GRID_STEPS, math.prod(worker_grid(
                self.packed.a_data, plan.tables, xb.shape[0])))
        y = traced_call(tr, WORKER, self._matvec_worker, plan.tables, xb,
                        **_STAGE)
        u = traced_call(tr, DECODE, self._decode, plan, y, **_STAGE)
        return traced_call(tr, OUTPUT, self._matvec_output, u, xb.shape[0],
                           **_STAGE)

    def _matvec_worker(self, tables, xb):
        """One launch over the fastest-k workers' tiles, read in place."""
        b_op = _pad_to(xb.T, 0, self.packed.t_pad)
        return worker_kernel(self.packed.a_data, tables, b_op,
                             interpret=self._interpret())

    def _decode(self, plan, y):
        return decode_kernel(plan.hinv_dev, y.reshape(self.k, -1),
                             interpret=self._interpret())

    def _matvec_output(self, u, b):
        packed = self.packed
        return matvec_output(u, self.k, packed.c, packed.c_pad, self.r, b)

    def _matmat_kernel(self, coded_b, done):
        tr = self._tracer
        interpret = self._interpret()
        plan = traced_call(tr, SELECT, self.cache.plan, done, **_STAGE)
        # stragglers' products are never computed: fastest-k only
        if tr is None:
            prods = [worker_kernel(*self._worker_operands(int(i), coded_b),
                                   interpret=interpret) for i in plan.rows]
        else:
            prods = []
            for i in plan.rows:
                with tr.span(SELECT, **_STAGE):
                    ops = self._worker_operands(int(i), coded_b)
                tr.count(TABLE_LAUNCHES)
                tr.count(GRID_STEPS, math.prod(worker_grid(
                    ops[0], ops[1], coded_b.shape[2])))
                with tr.span(WORKER, **_STAGE):
                    prods.append(worker_kernel(*ops, interpret=interpret))
        u = traced_call(tr, DECODE, self._matmat_decode, plan, prods,
                        **_STAGE)
        return traced_call(tr, OUTPUT, u.reshape,
                           (self.k, self.packed.c, coded_b.shape[2]),
                           **_STAGE)

    def _worker_operands(self, i: int, coded_b):
        """The packed operand, worker ``i``'s table into it, and its
        coded B shard, padded."""
        return (self.packed.a_data, self._worker_tables[i],
                _pad_to(coded_b[i], 0, self.packed.t_pad))

    def _matmat_decode(self, plan, prods):
        y = jnp.stack(prods)[:, : self.packed.c]        # (k, ca, cb)
        return self._decode(plan, y)

    # -- matmat: per-worker A_i^T B_i, decoded unknowns --------------------

    def matmat(self, coded_b: jnp.ndarray, done: jnp.ndarray | None = None
               ) -> jnp.ndarray:
        """Decoded unknowns U (k, ca, cb) from paired coded operands.

        ``self.coded`` holds the coded A shards, ``coded_b`` the coded B
        shards (n, t, cb); ``self.G`` must be the Khatri-Rao system over
        the k = k_A * k_B unknowns.
        """
        if self._fast_path(coded_b, done):
            return self._matmat_packed(coded_b, done)
        return self._matmat_reference(coded_b, done)

    def _matmat_reference(self, coded_b, done):
        from ..core.coded_matmul import fastest_k_rows  # noqa: PLC0415
        if done is None:
            done = jnp.ones(self.n, dtype=bool)
        p = jnp.einsum("ntc,ntd->ncd", self.coded, coded_b)
        rows = fastest_k_rows(done, self.k)
        sub = self.G[rows]
        ysub = p[rows].reshape(self.k, -1)
        u = jnp.linalg.solve(sub, ysub)
        return u.reshape((self.k,) + p.shape[1:])

    def _matmat_packed(self, coded_b, done):
        if done is None:
            done = np.ones(self.n, dtype=bool)
        if self.backend in _KERNEL_BACKENDS:
            return self._matmat_kernel(coded_b, done)
        plan = self.cache.plan(done)
        packed = self.packed
        cb = coded_b.shape[2]
        shards = self._bsr_shards()
        b_np = np.asarray(coded_b, np.float32)
        b_op = np.zeros((self.k, packed.t_pad, cb), np.float32)
        b_op[:, : packed.t] = b_np[plan.rows, : packed.t]
        y = np.stack([shards[i] @ b_op[j] for j, i in enumerate(plan.rows)])
        y = y[:, : packed.c]                            # (k, ca, cb)
        u = plan.hinv @ y.reshape(self.k, -1)
        return jnp.asarray(u.reshape((self.k,) + y.shape[1:]))

    # -- decode-only: worker results supplied by the caller ----------------

    def decode(self, y: jnp.ndarray, done: jnp.ndarray | None = None
               ) -> jnp.ndarray:
        """Worker results y (n, ..., c) -> decoded output (..., r)."""
        if self._fast_path(y, done):
            return self._decode_packed(y, done)
        return self._decode_reference(y, done)

    def _decode_reference(self, y, done):
        from ..core.coded_matmul import fastest_k_rows  # noqa: PLC0415
        if done is None:
            done = jnp.ones(self.n, dtype=bool)
        rows = fastest_k_rows(done, self.k)
        sub = self.G[rows]
        ysub = y[rows].astype(jnp.float32)
        u = jnp.linalg.solve(sub, ysub.reshape(self.k, -1))
        u = u.reshape((self.k,) + ysub.shape[1:])
        u = jnp.moveaxis(u, 0, -2)
        out = u.reshape(u.shape[:-2] + (self.k * u.shape[-1],))[..., : self.r]
        return out.astype(y.dtype)

    def _decode_packed(self, y, done):
        if done is None:
            done = np.ones(self.n, dtype=bool)
        plan = self.cache.plan(done)
        ysub = jnp.asarray(y)[plan.rows].astype(jnp.float32)
        flat = ysub.reshape(self.k, -1)
        if self.backend in _KERNEL_BACKENDS:
            u = decode_kernel(plan.hinv_dev, flat, interpret=self._interpret())
        else:
            u = plan.hinv_dev @ flat
        u = u.reshape((self.k,) + ysub.shape[1:])
        u = jnp.moveaxis(u, 0, -2)
        out = u.reshape(u.shape[:-2] + (self.k * u.shape[-1],))[..., : self.r]
        return out.astype(y.dtype)
