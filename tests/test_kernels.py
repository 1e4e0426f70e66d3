"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Sweeps shapes, block sizes, densities and dtypes per the kernel contract;
plus hypothesis property tests tying the kernels back to the coded-
computation semantics (encode kernel == encoding matrix product).
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mv_encoding_matrix, proposed_mv
from repro.kernels.bcsr_matmul import bcsr_grid, bcsr_matmul, padded_slots
from repro.kernels.cyclic_encode import cyclic_encode
from repro.kernels.decode_matmul import decode_matmul
from repro.kernels.ops import coded_worker_matmul, decode_unknowns, encode_submatrices
from repro.kernels.ref import (
    bcsr_matmul_packed_ref,
    bcsr_matmul_ref,
    cyclic_encode_ref,
    decode_matmul_ref,
    pack_bcsr,
)
from repro.runtime import pack_coded_blocks

# the module, which the package's ``bcsr_matmul`` function shadows
bcsr_module = importlib.import_module("repro.kernels.bcsr_matmul")
TOL = dict(rtol=2e-5, atol=2e-5)
TOL_BF16 = dict(rtol=2e-2, atol=2e-2)


def make_block_sparse(rng, K, M, bk, bm, density, dtype=np.float32):
    mask = rng.random((K // bk, M // bm)) < density
    if not mask.any():
        mask[0, 0] = True
    a = rng.standard_normal((K, M)).astype(dtype)
    return a * np.kron(mask, np.ones((bk, bm))).astype(dtype)


class TestBcsrMatmul:
    @pytest.mark.parametrize("K,M,N,bk,bm,bn", [
        (64, 32, 48, 8, 8, 16),
        (128, 128, 128, 16, 16, 128),
        (256, 64, 96, 32, 16, 32),
        (32, 32, 32, 32, 32, 32),   # single block
        (64, 16, 8, 8, 8, 8),
    ])
    @pytest.mark.parametrize("density", [0.15, 0.5, 1.0])
    def test_shape_density_sweep(self, K, M, N, bk, bm, bn, density):
        rng = np.random.default_rng(hash((K, M, N, bk, density)) % 2**31)
        a = make_block_sparse(rng, K, M, bk, bm, density)
        b = rng.standard_normal((K, N)).astype(np.float32)
        a_data, a_idx, _ = pack_bcsr(a, bk, bm)
        out = bcsr_matmul(jnp.asarray(a_data), jnp.asarray(a_idx),
                          jnp.asarray(b), bn=bn, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(bcsr_matmul_ref(a, b)), **TOL)

    @pytest.mark.parametrize("dtype,tol", [(np.float32, TOL),
                                           (jnp.bfloat16, TOL_BF16)])
    def test_dtype_sweep(self, dtype, tol):
        rng = np.random.default_rng(0)
        a = make_block_sparse(rng, 64, 32, 8, 8, 0.4).astype(dtype)
        b = rng.standard_normal((64, 32)).astype(dtype)
        a_data, a_idx, _ = pack_bcsr(np.asarray(a, dtype=np.float32), 8, 8)
        out = bcsr_matmul(jnp.asarray(a_data).astype(dtype), jnp.asarray(a_idx),
                          jnp.asarray(b), bn=16, interpret=True)
        assert out.dtype == jnp.float32  # f32 accumulation contract
        ref = bcsr_matmul_ref(jnp.asarray(a, jnp.float32),
                              jnp.asarray(b, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)

    @pytest.mark.parametrize("rows", [None, [4, 1, 3], [2], [3, 0, 3]],
                             ids=["identity", "unsorted", "one", "repeated"])
    def test_block_table_matches_gathered_operand(self, rows):
        """Rows read through a table equal the kernel on the explicitly
        gathered rows, bit for bit: the same tiles in the same order."""
        rng = np.random.default_rng(6)
        workers, mb = 5, 2
        packed = pack_coded_blocks(np.stack([
            make_block_sparse(rng, 64, mb * 8, 8, 8, 0.5)
            for _ in range(workers)]), 8, 8)
        a_data, a_idx = np.asarray(packed.a_data), np.asarray(packed.a_idx)
        b = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
        if rows is None:
            blocks = np.arange(workers * mb, dtype=np.int32)
            out = bcsr_matmul(jnp.asarray(a_data), jnp.asarray(a_idx), b,
                              bn=16, interpret=True)
        else:
            blocks = (np.asarray(rows)[:, None] * mb
                      + np.arange(mb)).ravel().astype(np.int32)
            out = bcsr_matmul(jnp.asarray(a_data), jnp.asarray(a_idx[blocks]),
                              b, blocks=jnp.asarray(blocks), bn=16,
                              interpret=True)
        gathered = bcsr_matmul(jnp.asarray(a_data[blocks]),
                               jnp.asarray(a_idx[blocks]), b, bn=16,
                               interpret=True)
        assert out.shape == (len(blocks) * 8, 16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(gathered))

    @pytest.mark.parametrize("tiles,rounded,group", [
        (64, True, 32),     # J a multiple of the slot group
        (37, True, 19),     # the packer rounds 37 slots to 2 groups of 19
        (5, True, 5),       # J below SLOT_GROUP: one group of J slots
        (1, True, 1),       # one slot a step
        (37, False, 1),     # an unrounded prime J: one slot a step
    ], ids=["multiple", "rounded", "below", "one", "unrounded"])
    @pytest.mark.parametrize("rows", [None, [3, 0, 3, 1]],
                             ids=["identity", "repeated-unsorted"])
    def test_slot_groups_match_one_slot_a_step(self, monkeypatch, tiles,
                                               rounded, group, rows):
        """A walk of several K-slots a grid step gives the walk of one
        slot a step bit for bit: the same tiles added in the same order,
        pad slots (zero tiles at K-block 0) included."""
        rng = np.random.default_rng(tiles)
        workers, mb, kb = 4, 2, 64
        coded = rng.standard_normal((workers, kb * 8, mb * 8))
        for i in range(workers):
            for m in range(mb):       # `tiles` K-tiles kept, K-block 0
                order = rng.permutation(kb)     # among them in every other
                if (i + m) % 2:
                    order = np.r_[0, order[order != 0]]
                coded[i].reshape(kb, 8, mb, 8)[order[tiles:], :, m] = 0
        packed = pack_coded_blocks(coded.astype(np.float32), 8, 8)
        a_data, a_idx = np.asarray(packed.a_data), np.asarray(packed.a_idx)
        assert packed.slot_counts == ((tiles,) * mb,) * workers
        assert packed.slots == padded_slots(tiles)
        if not rounded:
            a_data, a_idx = a_data[:, :tiles], a_idx[:, :tiles]
        np.testing.assert_array_equal(a_data[:, tiles:], 0)
        np.testing.assert_array_equal(a_idx[:, tiles:], 0)
        blocks = np.arange(workers * mb) if rows is None else \
            (np.asarray(rows)[:, None] * mb + np.arange(mb)).ravel()
        blocks = blocks.astype(np.int32)
        b = jnp.asarray(rng.standard_normal((kb * 8, 16)), jnp.float32)
        j = a_data.shape[1]
        assert bcsr_grid(a_data.shape, len(blocks), 16) == \
            (len(blocks), 1, j // group)

        def run():
            return np.asarray(bcsr_matmul(
                jnp.asarray(a_data), jnp.asarray(a_idx[blocks]), b,
                blocks=jnp.asarray(blocks), bn=16, interpret=True))

        out = run()
        monkeypatch.setattr(bcsr_module, "SLOT_GROUP", 1)
        assert bcsr_grid(a_data.shape, len(blocks), 16)[2] == j
        np.testing.assert_array_equal(out, run())
        np.testing.assert_allclose(out, np.asarray(bcsr_matmul_packed_ref(
            jnp.asarray(a_data[blocks]), jnp.asarray(a_idx[blocks]), b)),
            **TOL)

    def test_block_table_must_fit_the_operand(self):
        a_data = jnp.zeros((4, 3, 8, 8), jnp.float32)
        b = jnp.zeros((32, 8), jnp.float32)
        with pytest.raises(ValueError, match="do not fit"):
            bcsr_matmul(a_data, jnp.zeros((2, 3), jnp.int32), b,
                        blocks=jnp.zeros((3,), jnp.int32), bn=8,
                        interpret=True)

    def test_packed_ref_matches_dense_ref(self):
        rng = np.random.default_rng(3)
        a = make_block_sparse(rng, 96, 48, 8, 16, 0.3)
        b = rng.standard_normal((96, 24)).astype(np.float32)
        a_data, a_idx, _ = pack_bcsr(a, 8, 16)
        np.testing.assert_allclose(
            np.asarray(bcsr_matmul_packed_ref(jnp.asarray(a_data),
                                              jnp.asarray(a_idx), jnp.asarray(b))),
            np.asarray(bcsr_matmul_ref(a, b)), **TOL)

    def test_flop_saving_structure(self):
        """The packed representation's slot count scales with block
        density -- the structural source of the paper's speedup."""
        rng = np.random.default_rng(4)
        a_sparse = make_block_sparse(rng, 128, 64, 8, 8, 0.2)
        a_dense = make_block_sparse(rng, 128, 64, 8, 8, 1.0)
        _, _, j_sparse = pack_bcsr(a_sparse, 8, 8)
        _, _, j_dense = pack_bcsr(a_dense, 8, 8)
        assert j_sparse < j_dense / 2

    def test_ops_wrapper(self):
        rng = np.random.default_rng(5)
        a = make_block_sparse(rng, 64, 32, 8, 8, 0.4)
        b = rng.standard_normal((64, 16)).astype(np.float32)
        out = coded_worker_matmul(a, b, bk=8, bm=8, bn=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(bcsr_matmul_ref(a, b)), **TOL)


class TestCyclicEncode:
    @pytest.mark.parametrize("k,T,C,n,w,bt", [
        (4, 32, 8, 6, 2, 16),
        (9, 64, 16, 12, 3, 32),
        (6, 128, 4, 10, 4, 128),
        (3, 16, 32, 5, 2, 16),
    ])
    def test_shape_sweep(self, k, T, C, n, w, bt):
        rng = np.random.default_rng(hash((k, T, C, n, w)) % 2**31)
        blocks = rng.standard_normal((k, T, C)).astype(np.float32)
        sup = rng.integers(0, k, size=(n, w)).astype(np.int32)
        coef = rng.standard_normal((n, w)).astype(np.float32)
        out = cyclic_encode(jnp.asarray(blocks), jnp.asarray(sup),
                            jnp.asarray(coef), bt=bt, interpret=True)
        ref = cyclic_encode_ref(jnp.asarray(blocks), jnp.asarray(sup),
                                jnp.asarray(coef))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    def test_bf16_blocks(self):
        rng = np.random.default_rng(1)
        blocks = jnp.asarray(rng.standard_normal((4, 32, 8)), jnp.bfloat16)
        sup = jnp.asarray(rng.integers(0, 4, size=(6, 2)), jnp.int32)
        coef = jnp.asarray(rng.standard_normal((6, 2)), jnp.float32)
        out = cyclic_encode(blocks, sup, coef, bt=16, interpret=True)
        ref = cyclic_encode_ref(blocks, sup, coef)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL_BF16)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_matches_encoding_matrix_semantics(self, seed):
        """Property: kernel encode == R @ blocks for the Alg. 1 scheme."""
        rng = np.random.default_rng(seed)
        sch = proposed_mv(6, 4)
        R = mv_encoding_matrix(sch, seed=seed % 101)
        sup = np.array([list(t) for t in sch.supports], dtype=np.int32)
        coef = np.take_along_axis(R, sup, axis=1).astype(np.float32)
        blocks = rng.standard_normal((4, 32, 8)).astype(np.float32)
        out = encode_submatrices(blocks, sup, coef, bt=16, interpret=True)
        ref = np.einsum("nk,ktc->ntc", R, blocks)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


class TestDecodeMatmul:
    @pytest.mark.parametrize("k,P,bp", [(4, 64, 16), (9, 512, 512),
                                        (16, 256, 64), (36, 72, 36)])
    def test_shape_sweep(self, k, P, bp):
        rng = np.random.default_rng(hash((k, P)) % 2**31)
        h = rng.standard_normal((k, k)).astype(np.float32)
        y = rng.standard_normal((k, P)).astype(np.float32)
        out = decode_matmul(jnp.asarray(h), jnp.asarray(y), bp=bp, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(decode_matmul_ref(h, y)), **TOL)

    def test_end_to_end_decode(self):
        """Hinv from a real scheme pattern: kernel decode reproduces the
        uncoded blocks."""
        rng = np.random.default_rng(7)
        sch = proposed_mv(6, 4)
        R = mv_encoding_matrix(sch, seed=3)
        alive = [0, 2, 3, 5]
        hinv = np.linalg.inv(R[alive]).astype(np.float32)
        u_true = rng.standard_normal((4, 64)).astype(np.float32)
        y = (R[alive] @ u_true).astype(np.float32)
        u = decode_unknowns(hinv, y, bp=32, interpret=True)
        np.testing.assert_allclose(np.asarray(u), u_true, rtol=1e-4, atol=1e-4)
