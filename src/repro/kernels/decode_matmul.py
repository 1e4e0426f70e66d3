"""Fastest-k decode kernel: U = Hinv @ Y.

The server-side decode is a small (k x k) solve applied to a wide
result matrix Y (k x P) where P = per-unknown payload (r/k_A columns x
batch for matrix-vector, (r/k_A)(w/k_B) for matrix-matrix).  For fixed
straggler pattern the inverse Hinv is precomputed on host (k <= a few
dozen), so the hot loop is a skinny-matmul broadcast of Hinv over P.

Grid (Pb,): Hinv stays fully VMEM-resident ((k x k) -- at k=64 that is
16 KiB); each step streams one (k x bp) panel of Y through the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _decode_kernel(h_ref, y_ref, u_ref):
    u_ref[...] = jnp.dot(h_ref[...], y_ref[...],
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def decode_matmul(hinv: jnp.ndarray, y: jnp.ndarray, *, bp: int = 512,
                  interpret: bool = False) -> jnp.ndarray:
    """hinv (k, k) f32, y (k, P) -> U (k, P) f32."""
    k, p = y.shape
    if hinv.shape != (k, k):
        raise ValueError(f"hinv {hinv.shape} incompatible with y {y.shape}")
    bp = min(bp, p)
    if p % bp:
        raise ValueError(f"P={p} not a multiple of bp={bp}")
    pb = p // bp

    kernel = pl.pallas_call(
        _decode_kernel,
        grid=(pb,),
        in_specs=[
            pl.BlockSpec((k, k), lambda pp: (0, 0)),
            pl.BlockSpec((k, bp), lambda pp: (0, pp)),
        ],
        out_specs=pl.BlockSpec((k, bp), lambda pp: (0, pp)),
        out_shape=jax.ShapeDtypeStruct((k, p), jnp.float32),
        interpret=interpret,
        name="decode_matmul",
    )
    return kernel(hinv.astype(jnp.float32), y.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def decode_matmul_jit(hinv, y, *, bp: int = 512, interpret: bool = False):
    return decode_matmul(hinv, y, bp=bp, interpret=interpret)
