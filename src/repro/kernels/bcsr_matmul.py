"""Block-sparse worker matmul kernel: C = A^T @ B, A block-sparse.

This is the compute hot-spot of the paper: an edge worker multiplying
its *sparsity-preserved* coded submatrix.  The paper's AWS workers use
scalar CSR sparsity on CPUs; the TPU-native adaptation is
**block**-sparsity: the MXU consumes
(bk x bm) tiles, so the unit of skippable work is a tile, and the
low-weight encoding guarantees each coded block-column touches at most
``omega`` source columns' tiles -> the nonzero-tile count (and hence
MXU work) scales with omega/k_A exactly like the paper's nnz argument.

Mechanism: per output block-column ``m`` we pre-gather the nonzero
K-tiles of A into a packed array with their K-block indices.  The
kernel walks grid (Mb, Nb, J); the B tile for slot j is selected with a
*scalar-prefetched* index (``PrefetchScalarGridSpec``), i.e. a
block-table indirection in the same spirit as paged attention -- the
TPU analogue of the CSR pointer chase.  A second scalar-prefetched
table picks, per output block-column, the block-row of A it reads, so
a caller computes any subset of a resident packed operand's rows (the
fastest-k workers') in place, with no gathered copy.  Accumulation
happens in the f32 output tile in VMEM across the innermost grid
dimension.

VMEM budget per step (defaults bk=bm=bn=128, f32):
  A tile 64 KiB + B tile 64 KiB + C tile 64 KiB << 16 MiB VMEM.
MXU alignment: all three tile dims default to 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bcsr_matmul_kernel(blocks_ref, idx_ref, a_ref, b_ref, c_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    a = a_ref[0, 0]            # (bk, bm) tile of A for slot j
    b = b_ref[...]             # (bk, bn) tile of B at K-block idx[m, j]
    c_ref[...] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,    # f32 operands stay f32
        preferred_element_type=jnp.float32,
    )


def bcsr_matmul(a_data: jnp.ndarray, a_idx: jnp.ndarray, b: jnp.ndarray,
                *, blocks: jnp.ndarray | None = None, bn: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """C = A^T @ B from packed block-sparse A, read through a block-row
    table.

    a_data : (Mb_in, J, bk, bm)  packed nonzero tiles (zero-padded slots)
    a_idx  : (Mb, J) int32       K-block index per slot of each row read
    b      : (K, N)              dense right operand
    blocks : (Mb,) int32         output block-column m reads a_data's
                                 block-row blocks[m]; None is the
                                 identity (Mb = Mb_in)
    Returns C : (Mb*bm, N) float32.
    """
    _, j, bk, bm = a_data.shape
    mb = a_idx.shape[0]
    if blocks is None:
        blocks = jnp.arange(a_data.shape[0], dtype=jnp.int32)
    if blocks.shape != (mb,) or a_idx.shape[1] != j:
        raise ValueError(f"blocks {blocks.shape} and a_idx {a_idx.shape} "
                         f"do not fit a_data {a_data.shape}")
    k, n = b.shape
    if k % bk:
        raise ValueError(f"K={k} not a multiple of bk={bk}")
    bn = min(bn, n)
    if n % bn:
        raise ValueError(f"N={n} not a multiple of bn={bn}")
    nb = n // bn

    grid = (mb, nb, j)
    kernel = pl.pallas_call(
        _bcsr_matmul_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bk, bm),
                             lambda m, nn, jj, blk, idx: (blk[m], jj, 0, 0)),
                pl.BlockSpec((bk, bn),
                             lambda m, nn, jj, blk, idx: (idx[m, jj], nn)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda m, nn, jj, blk, idx: (m, nn)),
        ),
        out_shape=jax.ShapeDtypeStruct((mb * bm, n), jnp.float32),
        interpret=interpret,
        name="bcsr_matmul",
    )
    return kernel(blocks, a_idx, a_data, b)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def bcsr_matmul_jit(a_data, a_idx, b, *, bn: int = 128, interpret: bool = False):
    return bcsr_matmul(a_data, a_idx, b, bn=bn, interpret=interpret)
