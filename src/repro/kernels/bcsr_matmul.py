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
K-tiles of A into a packed array with their K-block indices.  Each grid
step walks a *slot group*: ``T`` consecutive K-slots of one output
block-column, so the kernel walks grid (Mb, Nb, J / T).  A group's A
tiles are one contiguous block, fetched with one DMA.  The B tile of
each slot is selected with a *scalar-prefetched* index
(``PrefetchScalarGridSpec``), i.e. a block-table indirection in the
same spirit as paged attention -- the TPU analogue of the CSR pointer
chase.  B is passed ``T`` times, input ``t`` reading the tile of slot
``g*T + t``, so the pipeline double-buffers each slot's B tile and
nothing is copied.  A second scalar-prefetched table picks, per output
block-column, the block-row of A it reads, so a caller computes any
subset of a resident packed operand's rows (the fastest-k workers') in
place, with no gathered copy.

The slot products are added into the f32 output tile one slot after
another, in slot order, across the group and then across the innermost
grid dimension: the sums, and so the results, are those of a walk of
one slot a step, bit for bit.  Groups exist because a grid step has a
fixed cost (a few tenths of a microsecond on a TPU v5e) that set the
pace at one 64 KiB tile a step.  ``T`` (``slot_group``) is the largest divisor of J up to
``SLOT_GROUP`` whose tiles fit ``VMEM_BUDGET``; a packer rounds J up
with ``padded_slots`` so that it has a large one (pad slots are zero
tiles at K-block 0).  On a v5e at the paper's MV shapes a launch took
8.8, 3.4, 3.0 and 2.9 ms at T = 1, 8, 16 and 32.

VMEM per step (defaults bk=bm=bn=128, f32, T=32), every block double-
buffered: A 32 x 64 KiB + B 32 x 64 KiB + C 64 KiB, x2 = 8.1 MiB of the
16 MiB scoped default (a B tile narrower than 128 lanes still takes 128
in VMEM).  MXU alignment: all three tile dims default to 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# K-slots a grid step walks at most, and the VMEM its double-buffered
# A and B tiles may take
SLOT_GROUP = 32
VMEM_BUDGET = 8 << 20


def _round_up(x: int, m: int) -> int:
    return x + (-x) % m


def slot_group(j: int, bk: int, bm: int, bn: int) -> int:
    """K-slots a grid step walks: the largest divisor of ``j`` up to
    ``SLOT_GROUP`` whose A and B tiles, double-buffered and laid out in
    (8, 128) tiles of 4-byte items, fit ``VMEM_BUDGET``."""
    per_slot = 2 * 4 * _round_up(bk, 8) * (_round_up(bm, 128)
                                           + _round_up(bn, 128))
    most = min(SLOT_GROUP, max(VMEM_BUDGET // per_slot, 1), j)
    return max(t for t in range(1, most + 1) if j % t == 0)


def padded_slots(j: int) -> int:
    """``j`` slots rounded up to the fewest equal groups of at most
    ``SLOT_GROUP``: fewer pad slots than groups."""
    groups = -(-j // SLOT_GROUP)
    return groups * -(-j // groups)


def bcsr_grid(a_shape, mb: int, n: int, bn: int = 128
              ) -> tuple[int, int, int]:
    """The grid ``bcsr_matmul`` launches over for packed A of shape
    ``a_shape``, ``mb`` output block-columns and B ``n`` wide: (block-
    columns, N tiles, slot groups)."""
    _, j, bk, bm = a_shape
    bn = min(bn, n)
    return mb, n // bn, j // slot_group(j, bk, bm, bn)


def _bcsr_matmul_kernel(blocks_ref, idx_ref, a_ref, *refs):
    *b_refs, c_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    acc = c_ref[...]
    for t, b_ref in enumerate(b_refs):     # slot order, one at a time
        acc += jax.lax.dot_general(
            a_ref[0, t],       # (bk, bm) tile of A for slot g*T + t
            b_ref[...],        # (bk, bn) tile of B at its K-block
            (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,    # f32 operands stay f32
            preferred_element_type=jnp.float32,
        )
    c_ref[...] = acc


def _b_spec(bk: int, bn: int, group: int, t: int) -> pl.BlockSpec:
    """B's tile for slot ``t`` of each group: K-block idx[m, g*group + t]."""
    return pl.BlockSpec(
        (bk, bn), lambda m, nn, g, blk, idx: (idx[m, g * group + t], nn))


def bcsr_matmul(a_data: jnp.ndarray, a_idx: jnp.ndarray, b: jnp.ndarray,
                *, blocks: jnp.ndarray | None = None, bn: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """C = A^T @ B from packed block-sparse A, read through a block-row
    table.

    a_data : (Mb_in, J, bk, bm)  packed nonzero tiles (zero-padded slots;
                                 ``padded_slots`` gives J its groups)
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
    grid = bcsr_grid(a_data.shape, mb, n, bn)
    group = j // grid[2]
    kernel = pl.pallas_call(
        _bcsr_matmul_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, group, bk, bm),
                             lambda m, nn, g, blk, idx: (blk[m], g, 0, 0)),
            ] + [_b_spec(bk, bn, group, t) for t in range(group)],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda m, nn, g, blk, idx: (m, nn)),
        ),
        out_shape=jax.ShapeDtypeStruct((mb * bm, n), jnp.float32),
        interpret=interpret,
        name="bcsr_matmul",
    )
    return kernel(blocks, a_idx, a_data, *[b] * group)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def bcsr_matmul_jit(a_data, a_idx, b, *, bn: int = 128, interpret: bool = False):
    return bcsr_matmul(a_data, a_idx, b, bn=bn, interpret=interpret)
