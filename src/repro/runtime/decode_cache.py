"""Decode planner: per-straggler-pattern decode plans, LRU-cached.

The server-side decode solves ``G[rows] @ U = Y[rows]`` for the k
unknowns, where ``rows`` are the fastest-k completed tasks.  The k x k
factorisation depends *only* on the straggler pattern -- on a real
cluster the same handful of patterns recurs step after step (usually
the all-alive pattern), yet the dense reference path re-runs
``jnp.linalg.solve`` on every single apply.

``DecodeCache`` keys the precomputed inverse on the ``done`` mask
bytes: a hit costs a dict lookup, a miss costs one host-side k x k
inversion (k is at most a few dozen).  The hot loop then reduces to a
skinny matmul ``U = Hinv @ Y`` dispatched to the ``decode_matmul``
Pallas kernel (or its jnp oracle), never a per-call solve.

Plans require a *concrete* mask (the cache lives on the host); traced
masks fall back to the reference solve path in the executor.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from collections.abc import Callable
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class DecodePlan:
    """Precomputed decode for one straggler pattern."""

    key: bytes                 # canonical done-mask bytes
    rows: np.ndarray           # (k,) fastest-k task rows (host ints)
    hinv: np.ndarray           # (k, k) f32 inverse of G[rows] (host)
    tables: Any = None         # the owner's per-pattern operands (the
                               # executor's block-row tables), if any
    # hinv on each device that decodes this pattern
    copies: dict = field(default_factory=dict, compare=False, repr=False)

    def hinv_on(self, device=None) -> jnp.ndarray:
        """``hinv`` on ``device`` (None: the default device, left
        uncommitted): uploaded there on first use, kept and evicted
        with the plan."""
        copy = self.copies.get(device)
        if copy is None:
            copy = self.copies[device] = jax.device_put(self.hinv, device)
        return copy


class DecodeCache:
    """LRU cache of ``DecodePlan`` keyed on the done mask."""

    def __init__(self, G, k: int, maxsize: int = 64,
                 tables: Callable[[np.ndarray], Any] | None = None):
        self._G = np.asarray(G, dtype=np.float64)
        if self._G.shape[1] != k:
            raise ValueError(f"G has {self._G.shape[1]} unknowns, expected {k}")
        self.k = k
        self.maxsize = maxsize
        # built from a plan's rows on its miss, kept and evicted with it
        self._tables = tables
        self._plans: OrderedDict[bytes, DecodePlan] = OrderedDict()
        self.hits = 0
        self.misses = 0   # == number of host-side k x k inversions run
        # a plan shared with a fleet session is consulted from the
        # fleet's loop thread while the owner may use it in-process
        # (or retune it) concurrently -- the LRU bookkeeping must not
        # corrupt under that interleaving
        self._lock = threading.Lock()

    def plan(self, done=None) -> DecodePlan:
        """The plan of the done mask (None: every task done)."""
        mask = (np.ones(self._G.shape[0], bool) if done is None
                else np.asarray(done, dtype=bool))
        if mask.ndim != 1 or mask.shape[0] != self._G.shape[0]:
            raise ValueError(
                f"done mask shape {mask.shape} incompatible with "
                f"{self._G.shape[0]} tasks")
        key = np.packbits(mask).tobytes()
        with self._lock:
            cached = self._plans.get(key)
            if cached is not None:
                self._plans.move_to_end(key)
                self.hits += 1
                return cached

        rows = np.flatnonzero(mask)[: self.k]
        if rows.shape[0] < self.k:
            raise ValueError(
                f"only {rows.shape[0]} tasks done, need k={self.k}")
        hinv = np.linalg.inv(self._G[rows]).astype(np.float32)
        plan = DecodePlan(key=key, rows=rows, hinv=hinv,
                          tables=None if self._tables is None
                          else self._tables(rows))
        with self._lock:
            self._plans[key] = plan
            self.misses += 1
            if len(self._plans) > self.maxsize:
                self._plans.popitem(last=False)
        return plan

    def patterns(self) -> np.ndarray:
        """(P, n_tasks) bool -- the cached straggler patterns, LRU order.

        This is what plan serialization ships (``repro.cluster.wire``):
        patterns are tiny and the receiving side re-derives bitwise the
        same inverses from its copy of G, so the shipped plan arrives
        pre-warmed without shipping the factorisations themselves.
        """
        n = self._G.shape[0]
        with self._lock:
            keys = list(self._plans)
        if not keys:
            return np.zeros((0, n), bool)
        rows = [np.unpackbits(np.frombuffer(key, np.uint8))[:n]
                for key in keys]
        return np.asarray(rows, bool)

    def __len__(self) -> int:
        return len(self._plans)

    def clear(self) -> None:
        self._plans.clear()
        self.hits = self.misses = 0
