"""Work of the encode kernel ``cyclic_encode`` when a call encodes B.

Each of the n coded B shards is a weighted sum of its omega_B source
block-columns.  Counted from the operand's nonzeros: a scaled add per
nonzero source entry, the nonzero sources read, and the coded shard
written whole, since the worker kernel takes it as a dense operand.

  operations  2 x (nonzeros of the shard's sources)
  bytes       (nonzeros of the sources + t x cb) x 4 (float32)

A call that encodes nothing (a matrix-vector call) counts no work.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.roofline.bcsr_matmul import F32, block_columns


def count(desc: dict) -> tuple[int, int] | None:
    if "B_nonzero" not in desc:
        return None
    nz = desc["B_nonzero"]
    t, r = nz.shape
    k = desc["k_B"]
    cb = -(-r // k)
    per_block = [int(x) for x in block_columns(nz, k).sum(axis=(1, 2))]
    src = [sum(per_block[p] for p in sup) for sup in desc["supports_B"]]
    flops = sum(2 * s for s in src)
    moved = sum(F32 * (s + t * cb) for s in src)
    return flops, moved
