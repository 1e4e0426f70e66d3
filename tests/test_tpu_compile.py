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

from repro.kernels.bcsr_matmul import bcsr_grid, padded_slots
from repro.runtime.executor import (
    decode_kernel,
    encode_kernel,
    worker_kernel,
)
from repro.runtime.pack import BlockRows, _round_up

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
    every K-tile kept (the densest packing the operand can give), its
    slots rounded up to the kernel's slot groups as the packer does."""
    slots = padded_slots(_round_up(t, TILE) // TILE)
    mb = _round_up(c, TILE) // TILE
    return (spec((workers * mb, slots, TILE, TILE)),
            spec((workers * mb, slots), jnp.int32))


def _table(spec, workers, c, slots):
    """Block-row table of ``workers`` workers' (c-wide) block-columns."""
    rows = workers * (_round_up(c, TILE) // TILE)
    return BlockRows(spec((rows,), jnp.int32), spec((rows, slots), jnp.int32))


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
    # one launch over the fastest k workers' tiles, read in place from
    # the whole n-worker operand through a k-worker table
    a_data, _ = _packed(spec, MV["n"], MV["t"], MV["c"])
    tables = _table(spec, MV["k"], MV["c"], a_data.shape[1])
    b = spec((_round_up(MV["t"], TILE), batch))
    _compile(worker_kernel, a_data, tables, b)


@pytest.mark.parametrize("batch", [8, 136])
def test_mv_decode_compiles(spec, batch):
    p = _round_up(MV["c"], TILE) * batch
    _compile(decode_kernel, spec((MV["k"], MV["k"])), spec((MV["k"], p)))


@pytest.mark.parametrize("cfg", [MM_FULL, MM_CHIP], ids=["full", "chip"])
def test_mm_worker_compiles(spec, cfg):
    # one launch per fastest-k worker, each on its own coded-B shard,
    # reading that worker's tiles from the whole operand through its table
    a_data, _ = _packed(spec, cfg["n"], cfg["t"], cfg["ca"])
    tables = _table(spec, 1, cfg["ca"], a_data.shape[1])
    b = spec((_round_up(cfg["t"], TILE), cfg["cb"]))
    _compile(worker_kernel, a_data, tables, b)


@pytest.mark.parametrize("cfg", [MM_FULL, MM_CHIP], ids=["full", "chip"])
def test_mm_decode_compiles(spec, cfg):
    k = cfg["k_a"] * cfg["k_b"]
    p = cfg["ca"] * cfg["cb"]          # 5,000,000 at full size
    _compile(decode_kernel, spec((k, k)), spec((k, p)))


@pytest.mark.parametrize("chip", range(4))
def test_mv_placed_compiles_on_each_chip(topo, chip):
    # a plan placed over the 2x2 host (repro.runtime.executor): chip c
    # launches over its own n/4 workers' tiles, and decodes the pattern
    # when it is the first to finish
    on = SingleDeviceSharding(topo.devices[chip])

    def spec_on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=on)

    per = MV["n"] // 4
    a_data, _ = _packed(spec_on, per, MV["t"], MV["c"])
    tables = _table(spec_on, per, MV["c"], a_data.shape[1])
    _compile(worker_kernel, a_data, tables,
             spec_on((_round_up(MV["t"], TILE), 8)))
    p = _round_up(MV["c"], TILE) * 8
    _compile(decode_kernel, spec_on((MV["k"], MV["k"])),
             spec_on((MV["k"], p)))


@pytest.mark.parametrize("t,workers,rows,c,n,bn,grid", [
    # one MV launch: 9 of 12 workers' 14 block-columns, x of 8 rows
    (MV["t"], MV["n"], MV["k"], MV["c"], 8, 8, (126, 1, 5)),
    # one MM launch: one worker's 14 block-columns, B 1400 wide padded
    # to 11 tiles of 128
    (MM_CHIP["t"], MM_CHIP["n"], 1, MM_CHIP["ca"], 1408, 128, (14, 11, 4)),
    # one chip's launch of a placed MV plan: its own 3 workers
    (MV["t"], MV["n"] // 4, MV["n"] // 4, MV["c"], 8, 8, (42, 1, 5)),
], ids=["mv", "mm-chip", "mv-placed"])
def test_worker_grid_at_the_papers_shapes(t, workers, rows, c, n, bn, grid):
    """The 157 (MV) and 110 (MM) K-tiles of a block-column are packed
    to 160 and 112 slots and walked 32 and 28 a grid step: 5 and 4
    steps a block-column (one slot a step: 157 and 110)."""
    mb = _round_up(c, TILE) // TILE
    slots = padded_slots(_round_up(t, TILE) // TILE)
    assert bcsr_grid((workers * mb, slots, TILE, TILE), rows * mb, n,
                     bn) == grid
