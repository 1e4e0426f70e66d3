"""Cyclic low-weight encoding kernel: coded_i = sum_j r[i,j] * A_{sup[i,j]}.

The edge server's encoding step (Alg. 1 line 10 / Alg. 2 lines 13-14).
Dense MDS encoders need a full (n x k) mixing matmul over every block;
the paper's point is that only ``omega`` source block-columns feed each
coded output.  The TPU kernel therefore *gathers* exactly omega source
tiles per output tile (scalar-prefetched support table) and accumulates
the scaled sum in VMEM -- O(omega) HBM reads per output instead of O(k).

Grid (n, Tb, omega): worker x row-tile x support-slot, accumulating over
the innermost slot dimension.  Coefficients ride in SMEM next to the
support indices.  Tile (bt x C) with bt=128 default rows; the full
block-column width C stays resident since coded layers use C = d/k_A
(a few hundred) -- recorded in the BlockSpec so VMEM stays bounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cyclic_encode_kernel(sup_ref, coef_ref, blocks_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    r = coef_ref[i, j]
    out_ref[...] += (r * blocks_ref[0].astype(jnp.float32)).astype(out_ref.dtype)


def cyclic_encode(blocks: jnp.ndarray, sup: jnp.ndarray, coef: jnp.ndarray,
                  *, bt: int = 128, interpret: bool = False) -> jnp.ndarray:
    """Encode stacked block-columns.

    blocks : (k, T, C)   source block-columns
    sup    : (n, w) int32  support table (Alg. 1 / Alg. 2)
    coef   : (n, w) f32    random coefficients on the support
    Returns coded : (n, T, C) float32.
    """
    k, t, c = blocks.shape
    n, w = sup.shape
    bt = min(bt, t)
    if t % bt:
        raise ValueError(f"T={t} not a multiple of bt={bt}")
    tb = t // bt

    grid = (n, tb, w)
    kernel = pl.pallas_call(
        _cyclic_encode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bt, c), lambda i, tt, jj, sup, coef: (sup[i, jj], tt, 0)),
            ],
            out_specs=pl.BlockSpec((1, bt, c), lambda i, tt, jj, sup, coef: (i, tt, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, t, c), jnp.float32),
        interpret=interpret,
        name="cyclic_encode",
    )
    return kernel(sup, coef, blocks)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def cyclic_encode_jit(blocks, sup, coef, *, bt: int = 128, interpret: bool = False):
    return cyclic_encode(blocks, sup, coef, bt=bt, interpret=interpret)
