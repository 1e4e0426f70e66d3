"""Runtime executor suite: packing round-trips, decode-plan caching,
backend parity (reference vs packed vs pallas-interpret), and the
omega/k_A work-scaling structure that is the paper's whole point."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CodedOperator,
    coded_matmat,
    coded_matvec,
    mv_encoding_matrix,
    poly_mv,
    proposed_mm,
    proposed_mv,
    system_matrix,
)
from repro.core.coded_matmul import split_block_columns
from repro.core.weights import mv_weight
from repro.parallel.coded_layer import CodedLinear
from repro.runtime import (
    BACKENDS,
    CodedExecutor,
    DecodeCache,
    encode_blocks,
    pack_coded_blocks,
    resolve_backend,
    support_tables,
    unpack_coded_blocks,
)

TOL = dict(rtol=2e-4, atol=2e-4)
CPU_BACKENDS = ("reference", "packed", "pallas-interpret")


def build_coded(rng, n, k, t, r, seed=0):
    sch = proposed_mv(n, k)
    A = rng.standard_normal((t, r)).astype(np.float32)
    R = mv_encoding_matrix(sch, seed)
    blocks = np.asarray(split_block_columns(jnp.asarray(A), k))
    coded = np.einsum("nk,ktc->ntc", R, blocks)
    G = np.asarray(system_matrix(sch, seed))
    return sch, A, coded, G


# ---------------------------------------------------------------------------
# Packing layer
# ---------------------------------------------------------------------------


class TestPacking:
    @pytest.mark.parametrize("t,c,bk,bm", [
        (32, 16, 8, 8),       # exact multiples
        (20, 9, 8, 8),        # both dims need padding
        (64, 8, 16, 8),       # rectangular tiles
    ])
    def test_round_trip(self, t, c, bk, bm):
        rng = np.random.default_rng(hash((t, c, bk)) % 2**31)
        coded = rng.standard_normal((5, t, c)).astype(np.float32)
        # block-structured zeros so slots are actually skipped
        coded[:, : t // 2] *= rng.random((5, 1, 1)) > 0.5
        packed = pack_coded_blocks(coded, bk, bm)
        np.testing.assert_array_equal(unpack_coded_blocks(packed), coded)

    def test_tile_counts_reflect_sparsity(self):
        rng = np.random.default_rng(0)
        dense = rng.standard_normal((2, 64, 32)).astype(np.float32)
        sparse = dense.copy()
        sparse[:, 16:] = 0.0              # 3/4 of the row-tiles vanish
        pd = pack_coded_blocks(dense, 8, 8)
        ps = pack_coded_blocks(sparse, 8, 8)
        assert sum(ps.tile_counts) == sum(pd.tile_counts) // 4
        assert ps.slots < pd.slots

    def test_select_workers_matches_views(self):
        rng = np.random.default_rng(1)
        coded = rng.standard_normal((6, 16, 8)).astype(np.float32)
        packed = pack_coded_blocks(coded, 8, 8)
        rows = np.array([4, 1, 3])
        sel_d, sel_i = packed.select_workers(rows)
        for j, i in enumerate(rows):
            vd, vi = packed.worker_view(int(i))
            lo, hi = j * packed.mb, (j + 1) * packed.mb
            np.testing.assert_array_equal(np.asarray(sel_d[lo:hi]),
                                          np.asarray(vd))
            np.testing.assert_array_equal(np.asarray(sel_i[lo:hi]),
                                          np.asarray(vi))


# ---------------------------------------------------------------------------
# Decode planner
# ---------------------------------------------------------------------------


class TestDecodeCache:
    def test_hit_miss_across_patterns(self):
        rng = np.random.default_rng(2)
        G = rng.standard_normal((6, 4))
        cache = DecodeCache(G, 4)
        m1 = np.array([1, 1, 0, 1, 1, 0], bool)
        m2 = np.array([0, 1, 1, 1, 1, 0], bool)
        p1 = cache.plan(m1)
        assert (cache.hits, cache.misses) == (0, 1)
        assert cache.plan(m1) is p1
        assert (cache.hits, cache.misses) == (1, 1)
        p2 = cache.plan(m2)
        assert p2 is not p1
        assert (cache.hits, cache.misses) == (1, 2)
        # plans are correct inverses of the fastest-k subsystem
        np.testing.assert_allclose(
            np.asarray(p2.hinv) @ G[p2.rows].astype(np.float32),
            np.eye(4), atol=1e-4)
        np.testing.assert_array_equal(p2.rows, [1, 2, 3, 4])

    def test_lru_eviction(self):
        rng = np.random.default_rng(3)
        cache = DecodeCache(rng.standard_normal((6, 4)), 4, maxsize=2)
        masks = [np.ones(6, bool) for _ in range(3)]
        for i, m in enumerate(masks):
            m[i] = False
            cache.plan(m)
        assert len(cache) == 2
        cache.plan(masks[0])              # evicted -> re-inverted
        assert cache.misses == 4

    def test_insufficient_workers_raises(self):
        cache = DecodeCache(np.eye(4), 4)
        with pytest.raises(ValueError, match="need k"):
            cache.plan(np.array([1, 0, 1, 0], bool))


class TestNoRepeatedSolves:
    def test_repeated_apply_zero_additional_solves(self, monkeypatch):
        """Same done mask twice -> exactly one host inversion, and the
        hot path never calls jnp.linalg.solve at all."""
        rng = np.random.default_rng(4)
        A = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
        op = CodedOperator.build(A, proposed_mv(6, 4), seed=1,
                                 backend="packed")
        x = jnp.asarray(rng.standard_normal((3, 32)), jnp.float32)
        done = jnp.asarray([True, False, True, True, False, True])

        inv_calls = {"n": 0}
        real_inv = np.linalg.inv

        def counting_inv(a):
            inv_calls["n"] += 1
            return real_inv(a)

        def forbidden_solve(*a, **kw):  # pragma: no cover - must not run
            raise AssertionError("packed path called jnp.linalg.solve")

        monkeypatch.setattr(np.linalg, "inv", counting_inv)
        monkeypatch.setattr(jnp.linalg, "solve", forbidden_solve)

        first = op.apply(x, done)
        for _ in range(5):
            out = op.apply(x, done)
        np.testing.assert_allclose(np.asarray(out), np.asarray(first),
                                   rtol=0, atol=0)
        assert inv_calls["n"] == 1
        ex = op.executor()
        # misses == 2: plan compilation pre-warms the all-alive pattern
        # (one upfront inversion), the straggler mask costs the second;
        # every repeat under the same mask is a pure cache hit.
        assert (ex.cache.hits, ex.cache.misses) == (5, 2)


# ---------------------------------------------------------------------------
# Backend parity
# ---------------------------------------------------------------------------


class TestBackendParity:
    @pytest.mark.parametrize("backend", CPU_BACKENDS[1:])
    @pytest.mark.parametrize("n,k,t,r,b", [
        (6, 4, 32, 24, 3),
        (12, 9, 40, 30, 1),    # t, r and batch all need padding
        (12, 9, 40, 30, 136),  # batch over one lane tile: padded to 256
    ])
    def test_matvec_parity(self, backend, n, k, t, r, b):
        # a fixed seed (str hashes change per process): some random
        # straggler masks leave an ill-conditioned k x k decode
        rng = np.random.default_rng((n, t, b))
        sch, A, coded, G = build_coded(rng, n, k, t, r)
        x = jnp.asarray(rng.standard_normal((b, t)), jnp.float32)
        done = np.ones(n, bool)
        done[rng.choice(n, n - k, replace=False)] = False
        ref = CodedExecutor(coded, G, k, r, backend="reference")
        ex = CodedExecutor(coded, G, k, r, backend=backend)
        np.testing.assert_allclose(
            np.asarray(ex.matvec(x, jnp.asarray(done))),
            np.asarray(ref.matvec(x, jnp.asarray(done))), **TOL)
        # 1-d x and default (all-alive) mask
        np.testing.assert_allclose(
            np.asarray(ex.matvec(x[0])), np.asarray(ref.matvec(x[0])), **TOL)

    @pytest.mark.parametrize("backend", CPU_BACKENDS[1:])
    @pytest.mark.parametrize("w", [
        18,
        3000,       # B block-column width cb=1000: padded to 1024 lanes
    ])
    def test_functional_matmat_parity(self, backend, w):
        rng = np.random.default_rng(7)
        sch = proposed_mm(12, 3, 3)
        A = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((32, w)), jnp.float32)
        done = np.ones(12, bool)
        done[[2, 8, 11]] = False
        ref = coded_matmat(A, B, sch, done=jnp.asarray(done),
                           backend="reference")
        out = coded_matmat(A, B, sch, done=jnp.asarray(done), backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(A.T @ B), rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("backend", CPU_BACKENDS[1:])
    def test_functional_matvec_parity(self, backend):
        rng = np.random.default_rng(8)
        sch = proposed_mv(10, 8)
        A = jnp.asarray(rng.standard_normal((40, 30)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((40,)), jnp.float32)
        done = np.ones(10, bool)
        done[[0, 5]] = False
        ref = coded_matvec(A, x, sch, done=jnp.asarray(done),
                           backend="reference")
        out = coded_matvec(A, x, sch, done=jnp.asarray(done), backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    @pytest.mark.parametrize("backend", CPU_BACKENDS[1:])
    def test_decode_parity(self, backend):
        rng = np.random.default_rng(9)
        sch, A, coded, G = build_coded(rng, 6, 4, 32, 24)
        layer = CodedLinear(scheme=sch, coded=jnp.asarray(coded),
                            G=jnp.asarray(G, jnp.float32), d_out=24,
                            backend=backend)
        ref = CodedLinear(scheme=sch, coded=jnp.asarray(coded),
                          G=jnp.asarray(G, jnp.float32), d_out=24)
        x = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
        y = layer.worker_compute(x)
        done = jnp.asarray([True, True, False, True, False, True])
        np.testing.assert_allclose(np.asarray(layer.decode(y, done)),
                                   np.asarray(ref.decode(y, done)), **TOL)

    def test_encode_backend_parity(self):
        rng = np.random.default_rng(10)
        sch = proposed_mv(12, 9)
        R = mv_encoding_matrix(sch, 5)
        blocks = rng.standard_normal((9, 40, 8)).astype(np.float32)
        sup, coef = support_tables(sch.supports, R)
        outs = [np.asarray(encode_blocks(blocks, sup, coef, b))
                for b in CPU_BACKENDS]
        for out in outs[1:]:
            np.testing.assert_allclose(out, outs[0], **TOL)
        np.testing.assert_allclose(
            outs[0], np.einsum("nk,ktc->ntc", R, blocks), rtol=1e-4, atol=1e-4)

    def test_jit_and_grad_fall_back_to_reference(self):
        """Traced callers must keep working on a sparse backend (the
        executor switches to the traceable reference path)."""
        rng = np.random.default_rng(11)
        w = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
        layer = CodedLinear.build(w, 6, 2, seed=0, backend="packed")
        x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
        done = jnp.asarray([True, True, False, True, True, False])
        jit_out = jax.jit(layer.apply)(x, done)
        np.testing.assert_allclose(np.asarray(jit_out), np.asarray(x @ w),
                                   **TOL)
        # the executor counts which path each call took
        ex = layer.executor()
        assert ex.calls == {"reference": 1}
        layer.apply(x, done)
        assert ex.calls == {"reference": 1, "packed": 1}
        g = jax.grad(lambda x: layer.apply(x, done).sum())(x[0])
        g_ref = jax.grad(lambda x: (x @ w).sum())(x[0])
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-3, atol=1e-3)

    def test_jit_functional_api_with_forced_sparse_backend(self, monkeypatch):
        """Even with a sparse backend forced process-wide, tracing the
        functional API (A itself a tracer) must not crash -- it degrades
        to the reference path (host packing needs concrete data)."""
        monkeypatch.setenv("REPRO_CODED_BACKEND", "packed")
        rng = np.random.default_rng(21)
        sch = proposed_mv(6, 4)
        A = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((32,)), jnp.float32)
        out = jax.jit(lambda a, v: coded_matvec(a, v, sch))(A, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(A.T @ x),
                                   **TOL)
        # operator built inside a trace: throwaway reference executor
        out2 = jax.jit(
            lambda a, v: CodedOperator.build(a, sch).apply(v))(A, x)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(A.T @ x),
                                   **TOL)

    def test_backend_registry_and_env_override(self, monkeypatch):
        assert set(CPU_BACKENDS) <= set(BACKENDS)
        monkeypatch.delenv("REPRO_CODED_BACKEND", raising=False)
        assert resolve_backend("packed") == "packed"
        monkeypatch.setenv("REPRO_CODED_BACKEND", "pallas-interpret")
        assert resolve_backend("packed") == "pallas-interpret"
        assert resolve_backend() == "pallas-interpret"
        monkeypatch.setenv("REPRO_CODED_BACKEND", "nope")
        with pytest.raises(ValueError, match="unknown coded backend"):
            resolve_backend()


# ---------------------------------------------------------------------------
# The omega / k_A work-scaling structure
# ---------------------------------------------------------------------------


class TestOmegaScaling:
    def test_tile_count_scales_with_omega_not_k(self):
        """Banded A: each source block-column occupies its own row band,
        so a weight-omega shard touches omega bands while a dense-coded
        shard touches all k -- per-worker tile counts (== MXU work)
        must show exactly that omega/k ratio."""
        n, k, t, r = 6, 4, 64, 32
        rng = np.random.default_rng(12)
        A = np.zeros((t, r), np.float32)
        band = t // k
        c = r // k
        for q in range(k):
            A[q * band:(q + 1) * band, q * c:(q + 1) * c] = (
                rng.standard_normal((band, c)))
        omega = mv_weight(n, k)
        assert omega < k

        prop = CodedOperator.build(jnp.asarray(A), proposed_mv(n, k),
                                   seed=1, backend="packed")
        dense = CodedOperator.build(jnp.asarray(A), poly_mv(n, k),
                                    seed=1, backend="packed")
        tiles_prop = prop.worker_tile_counts()
        tiles_dense = dense.worker_tile_counts()
        band_tiles = (band // 8) * (c // 8)
        np.testing.assert_array_equal(tiles_prop, omega * band_tiles)
        np.testing.assert_array_equal(tiles_dense, k * band_tiles)
        assert tiles_prop.max() / tiles_dense.max() == omega / k

        # and the coded output is still exact under max stragglers
        x = jnp.asarray(rng.standard_normal((t,)), jnp.float32)
        done = jnp.asarray([True, False, True, True, False, True])
        np.testing.assert_allclose(np.asarray(prop.apply(x, done)),
                                   np.asarray(x @ jnp.asarray(A)), **TOL)

    def test_worker_nnz_matches_packed_tiles_structure(self):
        rng = np.random.default_rng(13)
        sch, A, coded, G = build_coded(rng, 6, 4, 32, 24)
        op = CodedOperator(scheme=sch, coded=jnp.asarray(coded),
                           G=jnp.asarray(G), r=24, backend="packed")
        nnz = op.worker_nnz()
        tiles = op.worker_tile_counts()
        assert nnz.shape == tiles.shape == (6,)
        assert (tiles >= 1).all()
