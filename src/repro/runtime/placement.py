"""Coded workers placed on devices, harvested by completion.

On one chip the fastest-k workers are rows that one kernel launch
reads, and a straggler is a mask the caller hands in.  Placed over
several devices, each device holds ``n / devices`` consecutive workers
(device ``c`` holds workers ``c*per .. c*per + per - 1``) and a product
runs as:

  * one ``worker_kernel`` launch a free device, over its own workers'
    packed tiles, with no collective: no device waits for another;
  * a harvest: readiness of the launched outputs is polled
    (``jax.Array.is_ready``) until enough devices have finished for
    ``k`` workers, in the order they finished;
  * the finished devices' results move to the device that finished
    first, whose queue is empty, and the decode of the observed
    pattern runs there.

A device still busy with this plan's earlier product gets no launch,
and its workers count as stragglers, so a persistent straggler's queue
stays at one product.  It is launched on only when fewer free devices
than a decode needs are left, or when it frees up while the harvest
still waits.  A placed plan serves one caller at a time.

The policy (``workers_per_device``, ``choose_launches``, ``harvest``)
is plain Python over device indices and a readiness callable, so it is
tested with scripted readiness and no devices.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from collections import Counter, deque
from collections.abc import Callable, Sequence
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import default_tracer, traced_call
from .decode_cache import DecodeCache
from .executor import (_KERNEL_BACKENDS, DECODE, GRID_STEPS, OUTPUT,
                       SELECT, _pad_to, decode_kernel, is_concrete,
                       matvec_output, worker_grid, worker_kernel)
from .pack import pack_coded_blocks

# the per-call stages of a placed product, each a span where tracing is on
LAUNCH, HARVEST, GATHER = ("executor.launch", "executor.harvest",
                           "executor.gather")
# counters: launches, devices skipped as busy, readiness sweeps
CHIP_LAUNCHES = "executor.chip_launches"
CHIPS_SKIPPED_BUSY = "executor.chips_skipped_busy"
HARVEST_POLLS = "executor.harvest_polls"
# calls kept in ``PlacedExecutor.records``
RECORDS = 1 << 16
_STAGE = {"cat": "executor", "track": "executor"}


# ---------------------------------------------------------------------------
# The policy: device indices and readiness only
# ---------------------------------------------------------------------------


def workers_per_device(n: int, devices: int) -> int:
    """Workers each device holds; ``n`` must divide evenly."""
    if devices < 1 or n % devices:
        raise ValueError(f"n={n} workers do not divide evenly over "
                         f"{devices} devices")
    return n // devices


def choose_launches(busy: Sequence[bool], need: int,
                    launched_at: Sequence[int]) -> tuple[list[int], list[int]]:
    """(devices to launch on, devices skipped as busy).

    Every free device is launched on.  A busy device is launched on
    only while fewer than ``need`` are free, the one whose pending
    launch is oldest (``launched_at``, a call count) first.
    """
    free = [c for c, b in enumerate(busy) if not b]
    waiting = sorted((c for c, b in enumerate(busy) if b),
                     key=lambda c: launched_at[c])
    extra = waiting[: max(need - len(free), 0)]
    return sorted(free + extra), sorted(waiting[len(extra):])


def harvest(launched: Sequence[int], ready: Callable[[int], bool],
            count: int, waiting: Sequence[int] = (),
            launch: Callable[[int], None] | None = None
            ) -> tuple[list[int], list[int], int]:
    """(the first ``count`` devices to be ``ready``, in the order they
    were seen ready; the devices of ``waiting`` that joined; the sweeps
    that took).

    Each sweep asks every pending device once, in order.  A device of
    ``waiting`` (busy with earlier work, so not launched on) that
    ``ready`` finds free while the harvest still waits is launched on
    with ``launch`` and joins it: a device that frees up is used, and
    none is ever given a second product to queue.
    """
    if count > len(launched) + len(waiting):
        raise ValueError(f"need {count} devices, have "
                         f"{len(launched) + len(waiting)}")
    pending, waiting = list(launched), list(waiting)
    order, joined, sweeps = [], [], 0
    while True:
        sweeps += 1
        for c in list(pending):
            if ready(c):
                order.append(c)
                pending.remove(c)
                if len(order) == count:
                    return order, joined, sweeps
        for c in list(waiting):
            if ready(c):
                launch(c)
                waiting.remove(c)
                joined.append(c)
                pending.append(c)


def device_mask(devices: Sequence[int], n: int, per: int) -> np.ndarray:
    """The worker-level done mask of the given devices' workers."""
    mask = np.zeros(n, bool)
    for c in devices:
        mask[c * per:(c + 1) * per] = True
    return mask


class CallRecord(NamedTuple):
    """What one placed product did."""

    launched: tuple[int, ...]       # devices launched on
    skipped_busy: tuple[int, ...]   # devices skipped: still busy
    rows: tuple[int, ...]           # the workers whose results decoded
    decode_device: int              # index of the device that decoded
    harvest_s: float                # host seconds, last launch to the
                                    # k-th worker's result ready


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


class _Group:
    """One device's workers: packed tiles and their block-row table."""

    def __init__(self, coded: np.ndarray, device, tile: int):
        with jax.default_device(device):
            packed = pack_coded_blocks(coded, tile, tile)
            table = packed.block_rows(range(packed.n))
        # committed: every launch runs on this device
        self.packed = dataclasses.replace(
            packed, a_data=jax.device_put(packed.a_data, device),
            a_idx=jax.device_put(packed.a_idx, device))
        self.table = jax.device_put(table, device)
        self.pending = None        # this plan's latest output here
        self.launched_at = -1      # the call that launched it

    def launch(self, x, interpret: bool):
        """x (batch, t) on this device, padded; one kernel launch."""
        b_op = _pad_to(x.T, 0, self.packed.t_pad)
        self.pending = worker_kernel(self.packed.a_data, self.table, b_op,
                                     interpret=interpret)
        return self.pending

    def grid_steps(self, batch: int) -> int:
        """Grid steps of one launch over x of ``batch`` rows."""
        return math.prod(worker_grid(self.packed.a_data, self.table, batch))


class PlacedExecutor:
    """An MV plan's coded workers placed over ``devices`` (see the
    module docstring).  ``coded (n, t, c)`` is packed a device's
    workers at a time and not kept."""

    def __init__(self, coded, G, k: int, r: int, devices, backend: str, *,
                 cache_size: int = 64):
        if backend not in _KERNEL_BACKENDS:
            raise ValueError(f"placing workers on devices needs a kernel "
                             f"backend {_KERNEL_BACKENDS}, not {backend!r}")
        self.backend = backend
        self.devices = tuple(devices)
        self.G = np.asarray(G, np.float32)
        self.k, self.r = k, r
        self.n = self.G.shape[0]
        self.per = workers_per_device(self.n, len(self.devices))
        self.need = math.ceil(k / self.per)     # devices a decode needs
        tile = 128 if backend == "pallas" else 8
        host = np.asarray(coded)
        self.groups = [_Group(host[c * self.per:(c + 1) * self.per], dev,
                              tile)
                       for c, dev in enumerate(self.devices)]
        del host
        first = self.groups[0].packed
        self.c, self.c_pad = first.c, first.c_pad
        self.cache = DecodeCache(self.G, k, maxsize=cache_size)
        self.calls: Counter = Counter()
        self._lock = threading.Lock()
        self._count = 0
        self.records: deque[CallRecord] = deque(maxlen=RECORDS)
        self._tracer = default_tracer()
        for chips in self.patterns():
            dplan = self.cache.plan(device_mask(chips, self.n, self.per))
            for c in self._devices_of(dplan.rows):
                dplan.hinv_on(self.devices[c])

    # -- introspection -----------------------------------------------------

    def patterns(self) -> list[tuple[int, ...]]:
        """The device-level patterns a harvest decodes: every set of
        ``need`` devices."""
        return list(itertools.combinations(range(len(self.devices)),
                                           self.need))

    def placement(self) -> dict:
        return {"devices": [str(d) for d in self.devices],
                "workers_per_device": self.per,
                "devices_to_decode": self.need}

    def worker_tile_counts(self) -> np.ndarray:
        return np.concatenate([g.packed.tile_counts for g in self.groups])

    def _devices_of(self, rows) -> list[int]:
        return sorted({int(i) // self.per for i in rows})

    def _interpret(self) -> bool:
        return self.backend != "pallas"

    # -- the product -------------------------------------------------------

    def matvec(self, x, done=None):
        """A^T x for x (t,) or (batch, t), on the device that decoded.

        With ``done`` None the decode takes the first devices to finish;
        an explicit ``done`` launches the devices that hold its decode
        rows and decodes that mask."""
        if not is_concrete(x, done):
            raise ValueError("a placed plan needs concrete inputs")
        with self._lock:
            self.calls[self.backend] += 1
            self._count += 1
            call = self._count
        squeeze = x.ndim == 1
        xb = x[None, :] if squeeze else x
        tr = self._tracer
        if done is None:
            launch, skipped = choose_launches(
                [g.pending is not None and not self._ready(c)
                 for c, g in enumerate(self.groups)], self.need,
                [g.launched_at for g in self.groups])
            count = self.need
        else:
            dplan = traced_call(tr, SELECT, self.cache.plan, done, **_STAGE)
            launch, skipped = self._devices_of(dplan.rows), []
            count = len(launch)
        here = self._copies(xb)
        interpret = self._interpret()
        outs = {}

        def start(c: int) -> None:
            g = self.groups[c]
            outs[c] = traced_call(tr, LAUNCH, g.launch,
                                  self._x_on(xb, here, c), interpret,
                                  **_STAGE)
            g.launched_at = call

        for c in launch:
            start(c)
        t0 = time.perf_counter()
        order, joined, sweeps = traced_call(
            tr, HARVEST, harvest, launch, self._ready, count, skipped, start,
            **_STAGE)
        harvest_s = time.perf_counter() - t0
        if done is None:
            dplan = traced_call(tr, SELECT, self.cache.plan,
                                device_mask(order, self.n, self.per),
                                **_STAGE)
        skipped = [c for c in skipped if c not in joined]
        if tr is not None:
            tr.count(CHIP_LAUNCHES, len(outs))
            tr.count(CHIPS_SKIPPED_BUSY, len(skipped))
            tr.count(HARVEST_POLLS, sweeps)
            tr.count(GRID_STEPS, sum(self.groups[c].grid_steps(xb.shape[0])
                                     for c in outs))
        out = self._finish(dplan, outs, order[0], xb.shape[0])
        self.records.append(CallRecord(
            tuple(outs), tuple(skipped), tuple(int(i) for i in dplan.rows),
            order[0], harvest_s))
        return out[0] if squeeze else out

    def _ready(self, c: int) -> bool:
        """Device ``c``'s latest product from this plan is done: the one
        readiness test of the busy rule and the harvest."""
        return self.groups[c].pending.is_ready()

    def _copies(self, xb) -> dict:
        """The devices that already hold ``xb``: each copy of a
        replicated array (a committed one is replicated on its one
        device)."""
        if isinstance(xb, jax.Array) and xb.sharding.is_fully_replicated:
            return {s.device: s.data for s in xb.addressable_shards}
        return {}

    def _x_on(self, xb, here: dict, c: int):
        """xb on device ``c``: its own copy there, else a transfer."""
        dev = self.devices[c]
        return here[dev] if dev in here else jax.device_put(xb, dev)

    def _finish(self, dplan, outs: dict, dec: int, b: int):
        """Gather the decode rows' results on device ``dec``, decode
        and lay out the output there."""
        tr = self._tracer
        dev = self.devices[dec]
        y = traced_call(tr, GATHER, self._gather, dplan.rows, outs, dev,
                        **_STAGE)
        u = traced_call(tr, DECODE, self._decode, dplan.hinv_on(dev), y,
                        **_STAGE)
        return traced_call(tr, OUTPUT, matvec_output, u, self.k, self.c,
                           self.c_pad, self.r, b, **_STAGE)

    def _decode(self, hinv, y):
        return decode_kernel(hinv, y.reshape(self.k, -1),
                             interpret=self._interpret())

    def _gather(self, rows, outs: dict, dev):
        """(k * c_pad, batch) on ``dev``: the results of ``rows``, each
        device's block of workers moved once."""
        chips = self._devices_of(rows)
        ys = [jax.device_put(outs[c], dev) for c in chips]
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
        pos = np.asarray([chips.index(int(i) // self.per) * self.per
                          + int(i) % self.per for i in rows])
        if len(chips) * self.per == self.k and \
                np.array_equal(pos, np.arange(self.k)):
            return y
        y = y.reshape(len(chips) * self.per, self.c_pad, -1)
        return y[jax.device_put(pos, dev)].reshape(self.k * self.c_pad, -1)

    # -- set-up ------------------------------------------------------------

    def warm(self, x) -> None:
        """Runs once every program a product of ``x``'s shape runs:
        each device's launch, and each device-level pattern's decode on
        each device of it."""
        xb = x[None, :] if x.ndim == 1 else x
        here = self._copies(xb)
        outs = {c: g.launch(self._x_on(xb, here, c), self._interpret())
                for c, g in enumerate(self.groups)}
        jax.block_until_ready(outs)
        masks = [device_mask(p, self.n, self.per) for p in self.patterns()]
        for mask in masks + [np.ones(self.n, bool)]:
            dplan = self.cache.plan(mask)
            for dec in self._devices_of(dplan.rows):
                jax.block_until_ready(
                    self._finish(dplan, outs, dec, xb.shape[0]))
