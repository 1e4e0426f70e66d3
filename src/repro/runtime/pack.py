"""Packing layer: coded block-columns -> packed block-sparse operands.

The paper's worker-cost argument (Sec. IV-C) is that a weight-omega
coded submatrix inherits the union of its omega source block-columns'
sparsity, so per-worker work is ~ omega/k_A of the dense cost.  The
Pallas worker kernel (``repro.kernels.bcsr_matmul``) consumes that
structure as a *packed* form: per output block-column, only the nonzero
(bk x bm) K-tiles are stored, together with their K-block indices.

This module converts a stack of coded shards ``coded (n, t, c)`` into
one packed operand shared by every backend of the executor:

  * all workers are packed to a **common slot count J** (the max
    nonzero-tile count over workers, rounded up to the worker kernel's
    slot groups by ``padded_slots``) and concatenated along the
    output-block axis, so a single kernel launch computes every
    worker's product ``coded_i^T @ B`` when B is shared (matvec);
  * a block-row table (``block_rows``) names the workers a launch
    computes -- the fastest k of a straggler pattern (matvec), or one
    worker with its own B shard (matmat) -- and the kernel reads their
    tiles in place;
  * ``tile_counts`` records the true nonzero-tile count per worker --
    the quantity that scales with omega (asserted in tests, reported
    by the benchmarks).

Packing happens once at operator build time (host-side numpy), exactly
like the edge server dispatching coded tasks; the hot loop only ever
sees the packed arrays.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..kernels.bcsr_matmul import _round_up, padded_slots


@dataclass(frozen=True)
class PackedShards:
    """Packed block-sparse form of n coded shards (see module docstring).

    a_data : (n * Mb, J, bk, bm)  nonzero tiles, zero-padded slots
    a_idx  : (n * Mb, J) int32    K-block index per slot (pad slots -> 0)
    """

    a_data: jnp.ndarray
    a_idx: jnp.ndarray
    n: int                 # workers
    mb: int                # output block-columns per worker (c_pad / bm)
    bk: int
    bm: int
    t: int                 # logical K dim (rows of each shard)
    c: int                 # logical M dim (cols of each shard)
    t_pad: int
    c_pad: int
    tile_counts: tuple[int, ...]   # nonzero (bk x bm) tiles per worker
    # real (un-padded) slots per (worker, output block-column); the
    # BSR export needs these to drop the zero pad tiles
    slot_counts: tuple[tuple[int, ...], ...]

    @property
    def slots(self) -> int:
        return int(self.a_idx.shape[1])

    def worker_view(self, i: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(a_data, a_idx) slice for worker i."""
        lo, hi = i * self.mb, (i + 1) * self.mb
        return self.a_data[lo:hi], self.a_idx[lo:hi]

    def block_rows(self, rows) -> "BlockRows":
        """Block-row table of the given workers, in order: the worker
        kernel reads their tiles where they lie (fastest-k compute:
        stragglers' tiles are never touched, and none are copied)."""
        rows = np.asarray(rows)
        blocks = (rows[:, None] * self.mb + np.arange(self.mb)).ravel()
        blocks = jnp.asarray(blocks, jnp.int32)
        return BlockRows(blocks, self.a_idx[blocks])


class BlockRows(NamedTuple):
    """Rows of a packed operand for the worker kernel: output
    block-column m reads block-row ``blocks[m]``, whose K-block indices
    are ``idx[m]``."""

    blocks: jnp.ndarray    # (Mb,) int32
    idx: jnp.ndarray       # (Mb, J) int32


def pack_coded_blocks(coded, bk: int = 8, bm: int = 8) -> PackedShards:
    """Pack coded shards (n, t, c) into the kernel's block-sparse form.

    Pads t and c up to multiples of (bk, bm); a tile is stored iff it
    has any nonzero entry.  All workers share the max slot count J so
    they stack into one operand (padding slots are zero tiles pointing
    at K-block 0 -- they contribute nothing in both the kernel and the
    jnp gather-einsum path).
    """
    a = np.asarray(coded)
    if a.ndim != 3:
        raise ValueError(f"coded must be (n, t, c), got {a.shape}")
    n, t, c = a.shape
    t_pad, c_pad = _round_up(t, bk), _round_up(c, bm)
    if (t_pad, c_pad) != (t, c):
        a = np.pad(a, ((0, 0), (0, t_pad - t), (0, c_pad - c)))
    kb, mb = t_pad // bk, c_pad // bm

    # (n, kb, bk, mb, bm) -> (n, mb, kb, bk, bm)
    blocks = a.reshape(n, kb, bk, mb, bm).transpose(0, 3, 1, 2, 4)
    nz = np.abs(blocks).max(axis=(3, 4)) > 0           # (n, mb, kb)
    tile_counts = tuple(int(x) for x in nz.sum(axis=(1, 2)))
    slot_counts = tuple(tuple(int(x) for x in row) for row in nz.sum(axis=2))
    j = padded_slots(max(int(nz.sum(axis=2).max()), 1))

    a_data = np.zeros((n, mb, j, bk, bm), dtype=a.dtype)
    a_idx = np.zeros((n, mb, j), dtype=np.int32)
    for i in range(n):
        for m in range(mb):
            ks = np.flatnonzero(nz[i, m])
            a_data[i, m, : len(ks)] = blocks[i, m, ks]
            a_idx[i, m, : len(ks)] = ks
    return PackedShards(
        a_data=jnp.asarray(a_data.reshape(n * mb, j, bk, bm)),
        a_idx=jnp.asarray(a_idx.reshape(n * mb, j)),
        n=n, mb=mb, bk=bk, bm=bm, t=t, c=c, t_pad=t_pad, c_pad=c_pad,
        tile_counts=tile_counts, slot_counts=slot_counts,
    )


def bsr_shards(packed: PackedShards):
    """Export each worker's *transposed* shard A_i^T as a scipy BSR
    matrix (c_pad x t_pad), blocksize (bm, bk).

    This is the CPU analogue of the Pallas kernel: scipy's block-CSR
    matmul walks exactly the nonzero tiles the packer kept, so worker
    cost is nnz-tile proportional (the paper's CSR workers, block-
    adapted).  Pad slots are dropped via ``slot_counts``.
    """
    from scipy import sparse  # noqa: PLC0415 - optional heavy dep

    n, mb, bk, bm = packed.n, packed.mb, packed.bk, packed.bm
    a_data = np.asarray(packed.a_data, dtype=np.float32)
    a_data = a_data.reshape(n, mb, -1, bk, bm)
    a_idx = np.asarray(packed.a_idx).reshape(n, mb, -1)
    shards = []
    for i in range(n):
        counts = packed.slot_counts[i]
        indptr = np.zeros(mb + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        data = np.concatenate(
            [a_data[i, m, : counts[m]] for m in range(mb)], axis=0)
        # BSR blocks of A^T are the transposed tiles
        data = np.ascontiguousarray(data.transpose(0, 2, 1))
        indices = np.concatenate(
            [a_idx[i, m, : counts[m]] for m in range(mb)])
        shards.append(sparse.bsr_matrix(
            (data, indices, indptr),
            shape=(packed.c_pad, packed.t_pad), blocksize=(bm, bk)))
    return shards


def unpack_coded_blocks(packed: PackedShards) -> np.ndarray:
    """Inverse of ``pack_coded_blocks``: reconstruct dense (n, t, c).

    Round-trip identity holds because pad slots carry zero tiles; used
    by tests and by any consumer that needs the dense shards back
    (e.g. checkpoint export).
    """
    n, mb, bk, bm = packed.n, packed.mb, packed.bk, packed.bm
    kb = packed.t_pad // bk
    a_data = np.asarray(packed.a_data).reshape(n, mb, -1, bk, bm)
    a_idx = np.asarray(packed.a_idx).reshape(n, mb, -1)
    dense = np.zeros((n, mb, kb, bk, bm), dtype=a_data.dtype)
    for i in range(n):
        for m in range(mb):
            # pad slots are zero tiles; += keeps them harmless even if
            # a real tile also lives at K-block 0
            np.add.at(dense[i, m], a_idx[i, m], a_data[i, m])
    out = dense.transpose(0, 2, 3, 1, 4).reshape(n, packed.t_pad, packed.c_pad)
    return out[:, : packed.t, : packed.c]
