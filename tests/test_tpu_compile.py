"""Compile-only checks of the coded path's kernels for a TPU v5e.

Each test lowers one of the executor's kernel calls (``encode_kernel``,
``worker_kernel``, ``decode_kernel``, which pick and pad the Pallas
blocks) at the shapes ``chip_smoke.py`` runs, and compiles it for a
described ``v5e:2x2`` chip.  No chip is needed and nothing runs: the
TPU compiler only accepts or refuses the program, which is what
interpret mode cannot show (a block whose last two dimensions break the
(8, 128) tiling, or too much scoped VMEM).

The shapes are the paper's: the matrix-vector system A 20000 x 15000,
n=12, k_A=9 at batches 8 and 136, and the matrix-matrix system
A 20000 x 15000, B 20000 x 12000, n=42, k_A=k_B=6, at full size and at
the 0.7 scale ``chip_smoke.py`` runs by default, the largest that fits
one chip's memory.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.runtime.executor import (
    decode_kernel,
    encode_kernel,
    worker_kernel,
)
from repro.runtime.pack import _round_up

TILE = 128
# one entry per system chip_smoke.py runs (c: block-column widths)
MV = dict(t=20000, c=-(-15000 // 9), n=12, k=9, omega=3)
MM_FULL = dict(t=20000, ca=2500, cb=2000, n=42, k_a=6, k_b=6, omega=6)
MM_CHIP = dict(t=14000, ca=1750, cb=1400, n=42, k_a=6, k_b=6, omega=6)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies  # noqa: PLC0415

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    return make


def _packed(spec, workers, t, c):
    """Packed operand of ``workers`` coded shards (t, c) at 128 tiles,
    every K-tile kept (the densest packing the operand can give)."""
    kb = _round_up(t, TILE) // TILE
    mb = _round_up(c, TILE) // TILE
    return (spec((workers * mb, kb, TILE, TILE)),
            spec((workers * mb, kb), jnp.int32))


def _compile(fn, *args):
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("cfg,k,c", [
    (MV, MV["k"], MV["c"]),
    (MM_FULL, MM_FULL["k_a"], MM_FULL["ca"]),
    (MM_FULL, MM_FULL["k_b"], MM_FULL["cb"]),
    (MM_CHIP, MM_CHIP["k_a"], MM_CHIP["ca"]),
    (MM_CHIP, MM_CHIP["k_b"], MM_CHIP["cb"]),
], ids=["mv", "mm-a", "mm-b", "mm-a-chip", "mm-b-chip"])
def test_encode_compiles(spec, cfg, k, c):
    n, omega = cfg["n"], cfg["omega"]
    _compile(encode_kernel, spec((k, cfg["t"], c)),
             spec((n, omega), jnp.int32), spec((n, omega)))


@pytest.mark.parametrize("batch", [8, 136])
def test_mv_worker_compiles(spec, batch):
    # one fused launch over the fastest k workers' shards
    a_data, a_idx = _packed(spec, MV["k"], MV["t"], MV["c"])
    b = spec((_round_up(MV["t"], TILE), batch))
    _compile(worker_kernel, a_data, a_idx, b)


@pytest.mark.parametrize("batch", [8, 136])
def test_mv_decode_compiles(spec, batch):
    p = _round_up(MV["c"], TILE) * batch
    _compile(decode_kernel, spec((MV["k"], MV["k"])), spec((MV["k"], p)))


@pytest.mark.parametrize("cfg", [MM_FULL, MM_CHIP], ids=["full", "chip"])
def test_mm_worker_compiles(spec, cfg):
    # one launch per fastest-k worker, each on its own coded-B shard
    a_data, a_idx = _packed(spec, 1, cfg["t"], cfg["ca"])
    b = spec((_round_up(cfg["t"], TILE), cfg["cb"]))
    _compile(worker_kernel, a_data, a_idx, b)


@pytest.mark.parametrize("cfg", [MM_FULL, MM_CHIP], ids=["full", "chip"])
def test_mm_decode_compiles(spec, cfg):
    k = cfg["k_a"] * cfg["k_b"]
    p = cfg["ca"] * cfg["cb"]          # 5,000,000 at full size
    _compile(decode_kernel, spec((k, k)), spec((k, p)))
