"""The kernels' work counts: exact, repeatable, independent of how the
coded blocks are packed into tiles, and never above the work the packed
kernel really does."""

from __future__ import annotations

import numpy as np
import pytest

from bench import schemes, spec

N, K, OMEGA = 12, 9, 3


def _operand(seed: int, t: int = 200, r: int = 150, tile: int = 8,
             zeros: float = 0.8) -> np.ndarray:
    rng = np.random.default_rng(seed)
    keep = rng.random((-(-t // tile), -(-r // tile))) >= zeros
    mask = np.kron(keep, np.ones((tile, tile), bool))[:t, :r]
    return np.where(mask, rng.standard_normal((t, r)), 0.0).astype(np.float32)


def _brute_nnz(a: np.ndarray, k: int, support) -> int:
    t, r = a.shape
    c = -(-r // k)
    a = np.pad(a, ((0, 0), (0, k * c - r)))
    hit = np.zeros((t, c), bool)
    for q in support:
        hit |= a[:, q * c:(q + 1) * c] != 0
    return int(hit.sum())


def _mv_desc(a, done, width=8):
    return {"A_nonzero": a != 0, "k_A": K,
            "supports_A": schemes.alg1_supports(N, K, OMEGA),
            "selected": schemes.fastest_k(done, K), "width": width}


@pytest.fixture(scope="module")
def bcsr():
    return spec.load_module("roofline", "bcsr_matmul")


@pytest.fixture(scope="module")
def encode():
    return spec.load_module("roofline", "cyclic_encode")


@pytest.mark.parametrize("seed", [0, 1])
def test_bcsr_count_is_exact(bcsr, seed):
    a = _operand(seed)
    done = np.ones(N, bool)
    done[[1, 5, 9]] = False
    desc = _mv_desc(a, done)
    sup = desc["supports_A"]
    nnz = [_brute_nnz(a, K, sup[i]) for i in desc["selected"]]
    t, r = a.shape
    c = -(-r // K)
    flops, moved = bcsr.count(desc)
    assert flops == sum(2 * m * 8 for m in nnz)
    assert moved == sum(4 * (m + t * 8 + c * 8) for m in nnz)
    assert bcsr.count(desc) == (flops, moved)          # repeatable


def test_bcsr_count_reads_only_the_selected_workers(bcsr):
    a = _operand(2)
    alive = _mv_desc(a, np.ones(N, bool))
    done = np.ones(N, bool)
    done[:3] = False
    late = _mv_desc(a, done)
    assert alive["selected"] == list(range(9))
    assert late["selected"] == list(range(3, 12))
    assert bcsr.count(alive) != bcsr.count(late)


@pytest.fixture(scope="module")
def coded():
    """The coded blocks the program builds from one operand."""
    from repro.api import compile_plan

    a = _operand(3, t=300, r=270, zeros=0.9)
    plan = compile_plan(a, scheme="proposed", n=N, k_A=K, seed=9,
                        backend="packed")
    return a, np.asarray(plan.executor.coded)


@pytest.mark.parametrize("tile", [8, 128])
def test_count_does_not_move_with_the_tile_size(bcsr, coded, tile):
    from repro.runtime.pack import pack_coded_blocks, unpack_coded_blocks

    a, shards = coded
    packed = pack_coded_blocks(shards, tile, tile)
    back = unpack_coded_blocks(packed)
    done = np.ones(N, bool)
    done[[0, 4, 8]] = False
    desc = _mv_desc(a, done, width=8)
    sel = desc["selected"]
    width = desc["width"]
    flops, moved = bcsr.count(desc)
    # the count is the nonzeros of what the program multiplies ...
    assert flops == sum(2 * int((back[i] != 0).sum()) * width for i in sel)
    # ... never more than the packed kernel's tiles, slots included
    tiles = sum(packed.tile_counts[i] for i in sel) * tile * tile
    slots = len(sel) * packed.mb * packed.slots * tile * tile
    assert flops <= 2 * tiles * width <= 2 * slots * width
    tile_bytes = 4 * (tiles + len(sel) * (packed.t_pad + packed.c_pad)
                      * width)
    assert moved <= tile_bytes


def test_tile_sizes_store_different_work_for_one_count(coded):
    from repro.runtime.pack import pack_coded_blocks

    _, shards = coded
    small, large = (pack_coded_blocks(shards, b, b) for b in (8, 128))
    stored = [sum(p.tile_counts) * p.bk * p.bm for p in (small, large)]
    assert stored[0] < stored[1]


def test_mm_count_uses_the_coded_b_width(bcsr):
    a = _operand(4, t=96, r=60)
    sup_a, _ = schemes.alg2_supports(42, 6, 6, 2, 3)
    done = np.ones(42, bool)
    done[[3, 9, 14, 20, 30, 41]] = False
    desc = {"A_nonzero": a != 0, "k_A": 6, "supports_A": sup_a,
            "selected": schemes.fastest_k(done, 36), "width": 7}
    flops, moved = bcsr.count(desc)
    nnz = [_brute_nnz(a, 6, sup_a[i]) for i in desc["selected"]]
    assert len(nnz) == 36
    assert flops == sum(2 * m * 7 for m in nnz)
    assert moved == sum(4 * (m + 96 * 7 + 10 * 7) for m in nnz)


def test_encode_count_is_exact(encode):
    b = _operand(5, t=96, r=48)
    _, sup_b = schemes.alg2_supports(42, 6, 6, 2, 3)
    desc = {"B_nonzero": b != 0, "k_B": 6, "supports_B": sup_b}
    per_block = [int((b[:, p * 8:(p + 1) * 8] != 0).sum()) for p in range(6)]
    src = [sum(per_block[p] for p in s) for s in sup_b]
    assert encode.count(desc) == (sum(2 * x for x in src),
                                  sum(4 * (x + 96 * 8) for x in src))


def test_encode_counts_nothing_for_a_matrix_vector_call(encode):
    assert encode.count(_mv_desc(_operand(6), np.ones(N, bool))) is None
