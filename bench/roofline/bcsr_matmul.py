"""Work of the worker kernel ``bcsr_matmul`` in one coded product.

Counted from the operand and the support sets, over the k selected
workers only: a selected worker multiplies its coded block, whose
nonzeros are the union of its omega source block-columns' nonzeros,
by a dense right operand of ``width`` columns.

  operations  2 x nonzeros x width, per selected worker
  bytes       (nonzeros + dense operand + output) x 4 (float32)

The packing's tiles, slots and padding are not counted, so the count
does not move with the tile size or the layout.  A kernel that used the
right operand's sparsity would need a new count.
"""

from __future__ import annotations

import jax.numpy as jnp

F32 = 4


def block_columns(nonzero, k: int):
    """(t, r) bool -> (k, t, ceil(r/k)) bool, padded with False."""
    t, r = nonzero.shape
    c = -(-r // k)
    nz = jnp.pad(jnp.asarray(nonzero), ((0, 0), (0, k * c - r)))
    return jnp.moveaxis(nz.reshape(t, k, c), 1, 0)


def coded_nnz(nonzero, k: int, supports, workers) -> list[int]:
    """Nonzeros of each listed worker's coded block: the union of its
    sources' block-columns."""
    blocks = block_columns(nonzero, k)
    return [int(jnp.any(blocks[jnp.asarray(supports[i])], axis=0).sum())
            for i in workers]


def count(desc: dict) -> tuple[int, int]:
    """(operations, bytes) of one product described by ``desc``."""
    nz = desc["A_nonzero"]
    t, r = nz.shape
    k = desc["k_A"]
    c, width = -(-r // k), desc["width"]
    nnz = coded_nnz(nz, k, desc["supports_A"], desc["selected"])
    flops = sum(2 * m * width for m in nnz)
    moved = sum(F32 * (m + t * width + c * width) for m in nnz)
    return flops, moved
