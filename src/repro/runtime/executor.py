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

The kernel path runs over device groups (``DeviceGroup``: one device
and its consecutive workers' packed tiles).  An unplaced operator is
one group on the default device; ``devices=`` places an MV operator's
workers over several, ``n / len(devices)`` each.  An MV product is
select -> launch -> [harvest] -> gather -> decode -> output.  Where its
decode rows are known at launch (``done`` given, or one group), each
group holding some launches once over exactly those rows and the first
decodes; otherwise every free device launches over all its workers and
the first devices to finish decode (``repro.runtime.placement``).  The
rows' results move once to the decoding device.  A placed operator
serves one caller at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import os
import threading
import time
from collections import Counter, deque

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
from .placement import (RECORDS, CallRecord, choose_launches, device_mask,
                        harvest, workers_per_device)

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

# the per-call stages of the kernel path, each a span where tracing is on;
# a placed product's launches are LAUNCH in place of WORKER
SELECT, WORKER, DECODE, OUTPUT = ("executor.select", "executor.worker",
                                  "executor.decode", "executor.output")
LAUNCH, HARVEST, GATHER = ("executor.launch", "executor.harvest",
                           "executor.gather")
# counters: launches (unplaced: through a table; placed: on a device),
# their grid steps, devices skipped as busy, readiness sweeps
TABLE_LAUNCHES = "executor.table_launches"
CHIP_LAUNCHES = "executor.chip_launches"
GRID_STEPS = "kernel.bcsr_grid_steps"
CHIPS_SKIPPED_BUSY = "executor.chips_skipped_busy"
HARVEST_POLLS = "executor.harvest_polls"
_STAGE = {"cat": "executor", "track": "executor"}
_PLACED_MM = ("placing an MM plan's workers on devices is not supported; "
              "compile it without devices=")


class DeviceGroup:
    """One device (None: the default one) and its consecutive workers:
    their packed tiles, a block-row table of each worker and one of all
    of them, and the group's latest launch."""

    def __init__(self, device, coded: np.ndarray, bk: int, bm: int):
        self.device = device
        # committed: every launch runs on this device (None leaves the
        # arrays uncommitted)
        with jax.default_device(device):
            packed = pack_coded_blocks(coded, bk, bm)
            self.workers = jax.device_put(
                [packed.block_rows([i]) for i in range(packed.n)], device)
            self.table = jax.device_put(packed.block_rows(range(packed.n)),
                                        device)
        self.packed = dataclasses.replace(
            packed, a_data=jax.device_put(packed.a_data, device),
            a_idx=jax.device_put(packed.a_idx, device))
        self.pending = None        # this group's latest output
        self.launched_at = -1      # the call that launched it

    def launch(self, x, table: BlockRows, interpret: bool):
        """x (batch, t) on this device, padded; one kernel launch over
        the rows of ``table``."""
        b_op = _pad_to(x.T, 0, self.packed.t_pad)
        self.pending = worker_kernel(self.packed.a_data, table, b_op,
                                     interpret=interpret)
        return self.pending


class CodedExecutor:
    """Backend-dispatched encode / worker-compute / decode engine.

    Bound to one pre-encoded operator: coded shards ``coded (n, t, c)``,
    system matrix ``G (n, k)`` and logical output width ``r``.  The
    public surface (``matvec``, ``matmat``, ``decode``) is what every
    call site in core/parallel/serve routes through.  ``devices``
    places an MV operator's workers over those devices (kernel backends
    only; see the module docstring); None keeps them on one.
    """

    def __init__(self, coded, G, k: int, r: int,
                 backend: str | None = None, *, devices=None,
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
        self.devices = None if devices is None else tuple(devices)
        kernel = self.backend in _KERNEL_BACKENDS
        placed = self.devices is not None
        # an MM operator's k unknowns are k_A x k_B products, not the
        # block-columns of A's r columns: only A^T x is placed
        if placed and self.c != -(-r // k):
            raise NotImplementedError(_PLACED_MM)
        self.per = workers_per_device(self.n,
                                      len(self.devices) if placed else 1)
        if placed and not kernel:
            raise ValueError(f"placing workers on devices needs a kernel "
                             f"backend {_KERNEL_BACKENDS}, not "
                             f"{self.backend!r}")
        self.need = math.ceil(k / self.per)     # devices a decode needs
        self.groups: list[DeviceGroup] = []
        self.cache: DecodeCache | None = None
        self._bsr = None            # lazy scipy BSR shards ("packed")
        # calls per path taken: the backend name on the fast path,
        # "reference" where a traced input fell back
        self.calls: Counter = Counter()
        self._calls_lock = threading.Lock()
        self._count = 0             # fast-path calls, numbering launches
        self.records: deque[CallRecord] = deque(maxlen=RECORDS)
        self._tracer = default_tracer()
        if self.backend != "reference":
            tile = 128 if self.backend == "pallas" else 8
            with optional_span(self._tracer, "plan.pack", cat="plan",
                               track="plan"):
                host = np.asarray(self.coded)
                self.groups = [
                    DeviceGroup(dev, host[c * self.per:(c + 1) * self.per],
                                bk or tile, bm or tile)
                    for c, dev in enumerate(self.devices or (None,))]
            # the kernel path reads the decode rows' tiles through
            # block-row tables, built once a pattern with its plan
            self.cache = DecodeCache(
                np.asarray(self.G), k, maxsize=cache_size,
                tables=self._pattern_tables if kernel else None)
        # the unplaced operator's packed form (the packed backend, wire)
        self.packed: PackedShards | None = (
            self.groups[0].packed if self.groups and not placed else None)
        if placed:
            self.coded = None   # packed a device at a time; the shards go

    def _pattern_tables(self, rows) -> dict:
        """{group: table of exactly its decode rows, on its device} for
        each group holding some of ``rows``, in device order."""
        tables = {}
        for c, g in enumerate(self.groups):
            mine = rows[rows // self.per == c] - c * self.per
            if mine.size:
                tables[c] = jax.device_put(g.packed.block_rows(mine),
                                           g.device)
        return tables

    def _bsr_shards(self):
        if self._bsr is None:
            self._bsr = bsr_shards(self.packed)
        return self._bsr

    # -- introspection ----------------------------------------------------

    def worker_tile_counts(self) -> np.ndarray:
        """Nonzero (bk x bm) tiles per worker -- the omega-scaling
        quantity (proportional to per-apply MXU work on this worker)."""
        if not self.groups:
            packed = pack_coded_blocks(np.asarray(self.coded), 8, 8)
            return np.asarray(packed.tile_counts)
        return np.concatenate([g.packed.tile_counts for g in self.groups])

    def patterns(self) -> list[tuple[int, ...]]:
        """The device-level patterns a harvest decodes: every set of
        ``need`` devices."""
        return list(itertools.combinations(range(len(self.groups)),
                                           self.need))

    def placement(self) -> dict:
        return {"devices": [str(d) for d in self.devices],
                "workers_per_device": self.per,
                "devices_to_decode": self.need}

    @property
    def c_pad(self) -> int:
        return self.groups[0].packed.c_pad

    def _interpret(self) -> bool:
        return self.backend != "pallas"

    def _fast_path(self, *vals) -> bool:
        fast = self.backend != "reference" and _is_concrete(*vals)
        if not fast and self.devices is not None:
            raise ValueError("a placed plan needs concrete inputs")
        with self._calls_lock:
            self.calls[self.backend if fast else "reference"] += 1
            self._count += 1
        return fast

    def _solve_reference(self, y, done):
        """(k, ...) unknowns from every worker's result y (n, ...): the
        fastest k rows of ``done`` solved on each call (traceable)."""
        from ..core.coded_matmul import fastest_k_rows  # noqa: PLC0415
        if done is None:
            done = jnp.ones(self.n, dtype=bool)
        rows = fastest_k_rows(done, self.k)
        ysub = y[rows]
        u = jnp.linalg.solve(self.G[rows], ysub.reshape(self.k, -1))
        return u.reshape(ysub.shape)

    # -- matvec: A^T x ----------------------------------------------------

    def matvec(self, x: jnp.ndarray, done: jnp.ndarray | None = None
               ) -> jnp.ndarray:
        """A^T x for x (t,) or (batch, t); returns (r,) / (batch, r).

        Placed, ``done=None`` decodes from the first devices to finish,
        and the result lies on the device that decoded it."""
        squeeze = x.ndim == 1
        xb = x[None, :] if squeeze else x
        if self._fast_path(x, done):
            out = self._matvec_packed(xb, done)
        else:
            out = self._matvec_reference(xb, done)
        return out[0] if squeeze else out

    def _matvec_reference(self, xb, done):
        u = self._solve_reference(
            jnp.einsum("ntc,bt->nbc", self.coded, xb), done)
        return u.transpose(1, 0, 2).reshape(xb.shape[0], -1)[:, : self.r]

    def _matvec_packed(self, xb, done):
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
        """select -> launch -> [harvest] -> gather -> decode -> output."""
        tr, b, call = self._tracer, xb.shape[0], self._count
        here = self._copies(xb)
        outs, tables = {}, {}

        def start(c: int, table: BlockRows) -> None:
            g = self.groups[c]
            tables[c] = table
            outs[c] = traced_call(tr, LAUNCH if self.devices else WORKER,
                                  g.launch, self._x_on(xb, here, c), table,
                                  self._interpret(), **_STAGE)
            g.launched_at = call

        skipped, sweeps, harvest_s = [], 0, 0.0
        if done is None and len(self.groups) > 1:
            # rows unknown: each free device reads all its workers
            launch, skipped = choose_launches(
                [g.pending is not None and not self._ready(c)
                 for c, g in enumerate(self.groups)], self.need,
                [g.launched_at for g in self.groups])
            for c in launch:
                start(c, self.groups[c].table)
            t0 = time.perf_counter()
            order, joined, sweeps = traced_call(
                tr, HARVEST, harvest, launch, self._ready, self.need,
                skipped, lambda c: start(c, self.groups[c].table),
                **_STAGE)
            harvest_s = time.perf_counter() - t0
            skipped = [c for c in skipped if c not in joined]
            plan = traced_call(tr, SELECT, self.cache.plan,
                               device_mask(order, self.n, self.per),
                               **_STAGE)
            dec = order[0]
        else:
            plan = traced_call(tr, SELECT, self.cache.plan, done, **_STAGE)
            for c, table in plan.tables.items():
                start(c, table)
            dec = next(iter(plan.tables))
        if tr is not None:
            self._count_launches(tables.items(), b, skipped, sweeps)
        out = self._finish(plan, outs, dec, b)
        self.records.append(CallRecord(
            tuple(outs), tuple(skipped), tuple(plan.rows.tolist()), dec,
            harvest_s))
        return out

    def _count_launches(self, launches, width: int, skipped=(),
                        sweeps: int = 0) -> None:
        """Counters of one product: its (group, table) launches over b
        of ``width`` columns and their grid steps, the devices skipped
        as busy, the readiness sweeps."""
        tr, launches = self._tracer, list(launches)
        bn, n_pad = lane_tile(width, 128)       # as ``worker_kernel``
        tr.count(CHIP_LAUNCHES if self.devices else TABLE_LAUNCHES,
                 len(launches))
        tr.count(GRID_STEPS, sum(math.prod(bcsr_grid(
            self.groups[c].packed.a_data.shape, t.blocks.shape[0], n_pad,
            bn)) for c, t in launches))
        tr.count(CHIPS_SKIPPED_BUSY, len(skipped))
        tr.count(HARVEST_POLLS, sweeps)

    def _ready(self, c: int) -> bool:
        """Group ``c``'s latest launch is done: the one readiness test of
        the busy rule and the harvest."""
        return self.groups[c].pending.is_ready()

    def _copies(self, xb) -> dict:
        """xb's copies by device: an unplaced group (device None) takes
        xb as it is; placed, each copy of a replicated array (a
        committed one is replicated on its one device)."""
        if self.devices is None:
            return {None: xb}
        if isinstance(xb, jax.Array) and xb.sharding.is_fully_replicated:
            return {s.device: s.data for s in xb.addressable_shards}
        return {}

    def _x_on(self, xb, here: dict, c: int):
        """xb on group ``c``'s device: its own copy there, else a
        transfer."""
        dev = self.groups[c].device
        return here[dev] if dev in here else jax.device_put(xb, dev)

    def _finish(self, plan, outs: dict, dec: int, b: int):
        """Gather the decode rows' results on group ``dec``'s device,
        decode and lay out the output there."""
        tr = self._tracer
        dev = self.groups[dec].device
        y = outs[dec]
        if len(plan.tables) > 1 or y.shape[0] > self.k * self.c_pad:
            y = traced_call(tr, GATHER, self._gather, plan, outs, dev,
                            **_STAGE)
        u = traced_call(tr, DECODE, self._decode, plan.hinv_on(dev), y,
                        **_STAGE)
        return traced_call(tr, OUTPUT, matvec_output, u, self.k, self.c,
                           self.c_pad, self.r, b, **_STAGE)

    def _gather(self, plan, outs: dict, dev):
        """(k * c_pad, batch) on ``dev``: the results of the devices
        holding the decode rows, each moved once, in device order."""
        ys = [jax.device_put(outs[c], dev) for c in plan.tables]
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
        return y[: self.k * self.c_pad]     # a launch read all its workers

    def _decode(self, hinv, y):
        return decode_kernel(hinv, y.reshape(self.k, -1),
                             interpret=self._interpret())

    def warm(self, x) -> None:
        """Runs once every program a product of ``x``'s shape with
        ``done=None`` runs: its launches, and each device-level
        pattern's decode on each device of it."""
        xb = x[None, :] if x.ndim == 1 else x
        here = self._copies(xb)

        def launch(tables: dict) -> dict:
            return {c: self.groups[c].launch(self._x_on(xb, here, c), t,
                                             self._interpret())
                    for c, t in tables.items()}

        # rows unknown: every device launched over all its workers once
        every = (launch({c: g.table for c, g in enumerate(self.groups)})
                 if len(self.groups) > 1 else None)
        for p in self.patterns():
            plan = self.cache.plan(device_mask(p, self.n, self.per))
            outs = every or launch(plan.tables)
            for dec in plan.tables:
                jax.block_until_ready(
                    self._finish(plan, outs, dec, xb.shape[0]))

    def _matmat_kernel(self, coded_b, done):
        tr, g = self._tracer, self.groups[0]    # MM is never placed
        kernel = functools.partial(worker_kernel,
                                   interpret=self._interpret())
        plan = traced_call(tr, SELECT, self.cache.plan, done, **_STAGE)
        # stragglers' products are never computed: fastest-k only
        prods = []
        for i in plan.rows:
            ops = traced_call(tr, SELECT, self._worker_operands, int(i),
                              coded_b, **_STAGE)
            prods.append(traced_call(tr, WORKER, kernel, *ops, **_STAGE))
        if tr is not None:
            self._count_launches([(0, g.workers[i]) for i in plan.rows],
                                 coded_b.shape[2])
        u = traced_call(tr, DECODE, self._matmat_decode, plan, prods,
                        **_STAGE)
        return traced_call(tr, OUTPUT, u.reshape,
                           (self.k, self.c, coded_b.shape[2]), **_STAGE)

    def _worker_operands(self, i: int, coded_b):
        """The packed operand, worker ``i``'s table into it, and its
        coded B shard, padded."""
        g = self.groups[0]
        return (g.packed.a_data, g.workers[i],
                _pad_to(coded_b[i], 0, g.packed.t_pad))

    def _matmat_decode(self, plan, prods):
        y = jnp.stack(prods)[:, : self.c]               # (k, ca, cb)
        return self._decode(plan.hinv_on(), y)

    # -- matmat: per-worker A_i^T B_i, decoded unknowns --------------------

    def matmat(self, coded_b: jnp.ndarray, done: jnp.ndarray | None = None
               ) -> jnp.ndarray:
        """Decoded unknowns U (k, ca, cb) from paired coded operands.

        ``self.coded`` holds the coded A shards, ``coded_b`` the coded B
        shards (n, t, cb); ``self.G`` must be the Khatri-Rao system over
        the k = k_A * k_B unknowns.
        """
        if self.devices is not None:    # k_B = 1 passes the shape test
            raise NotImplementedError(_PLACED_MM)
        if self._fast_path(coded_b, done):
            return self._matmat_packed(coded_b, done)
        return self._solve_reference(
            jnp.einsum("ntc,ntd->ncd", self.coded, coded_b), done)

    def _matmat_packed(self, coded_b, done):
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
            u = self._decode_packed(y, done)
        else:
            u = self._solve_reference(y.astype(jnp.float32), done)
        u = jnp.moveaxis(u, 0, -2)
        out = u.reshape(u.shape[:-2] + (self.k * u.shape[-1],))[..., : self.r]
        return out.astype(y.dtype)

    def _decode_packed(self, y, done):
        plan = self.cache.plan(done)
        ysub = jnp.asarray(y)[plan.rows].astype(jnp.float32)
        if self.backend in _KERNEL_BACKENDS:
            u = self._decode(plan.hinv_on(), ysub)
        else:
            u = plan.hinv_on() @ ysub.reshape(self.k, -1)
        return u.reshape(ysub.shape)
