"""Integration tests: data pipeline, optimizer, trainer loop,
checkpoint/restart, coded layer, serving engine, compile cache."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticTokens, make_pipeline
from repro.launch.cache import CHECKOUT_CACHE_DIR, init_compile_cache
from repro.models import build_model
from repro.optim import AdamWConfig, CompressionConfig, apply_updates, init_state
from repro.parallel.coded_layer import CodedLinear
from repro.serve import Request, ServeEngine
from repro.train import TrainConfig, Trainer, checkpoint


class TestData:
    def test_deterministic_and_seekable(self):
        cfg = DataConfig(vocab=128, seq_len=32, global_batch=4)
        src = SyntheticTokens(cfg)
        b0a, b0b = src.batch_at(0), src.batch_at(0)
        np.testing.assert_array_equal(b0a["tokens"], b0b["tokens"])
        assert not np.array_equal(src.batch_at(1)["tokens"], b0a["tokens"])
        # labels are next-token shifted
        np.testing.assert_array_equal(b0a["tokens"][:, 1:], b0a["labels"][:, :-1])

    def test_host_sharding_partitions(self):
        cfg = DataConfig(vocab=128, seq_len=16, global_batch=4)
        full = SyntheticTokens(cfg).batch_at(3)["tokens"]
        parts = [SyntheticTokens(
            DataConfig(vocab=128, seq_len=16, global_batch=4,
                       host_count=2, host_index=h)).batch_at(3)["tokens"]
            for h in range(2)]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_prefetch(self):
        it = make_pipeline(DataConfig(vocab=64, seq_len=8, global_batch=2))
        b = next(it)
        assert b["tokens"].shape == (2, 8)
        it.close()


class TestOptimizer:
    def test_adamw_descends_quadratic(self):
        cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                          weight_decay=0.0)
        params = {"w": jnp.ones((4,)) * 5.0}
        state = init_state(cfg, params)
        for _ in range(60):
            grads = {"w": 2 * params["w"]}          # d/dw ||w||^2
            params, state, m = apply_updates(cfg, params, grads, state)
        assert float(jnp.abs(params["w"]).max()) < 1.0

    def test_bf16_moments(self):
        cfg = AdamWConfig(moment_dtype="bfloat16")
        state = init_state(cfg, {"w": jnp.ones((3,))})
        assert state["m"]["w"].dtype == jnp.bfloat16

    def test_clip(self):
        cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=0)
        params = {"w": jnp.zeros((2,))}
        state = init_state(cfg, params)
        _, _, m = apply_updates(cfg, params, {"w": jnp.ones((2,)) * 1e6}, state)
        assert float(m["grad_norm"]) > 1e5  # norm reported pre-clip


class TestTrainerLoop:
    def _setup(self, tmp_path, steps=6, schedule_total=None):
        cfg = get_smoke_config("phi3-mini-3.8b")
        model = build_model(cfg, dtype=jnp.float32)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
        tcfg = TrainConfig(steps=steps, ckpt_every=3, log_every=100,
                           ckpt_dir=str(tmp_path / "ckpt"))
        # the LR-schedule horizon must be the FULL run length even when a
        # phase stops early (otherwise resume sees a different schedule)
        tr = Trainer(model, AdamWConfig(lr=1e-3, warmup_steps=2,
                                        total_steps=schedule_total or steps),
                     tcfg)
        factory = lambda start: make_pipeline(dcfg, start)  # noqa: E731
        return tr, factory

    def test_loss_decreases(self, tmp_path):
        tr, factory = self._setup(tmp_path, steps=20)
        _, _, hist = tr.fit(factory, resume=False)
        first = np.mean([h["loss"] for h in hist[:4]])
        last = np.mean([h["loss"] for h in hist[-4:]])
        assert last < first, (first, last)

    def test_checkpoint_restart_exact(self, tmp_path):
        tr, factory = self._setup(tmp_path, steps=6)
        p1, o1, hist1 = tr.fit(factory)
        # "crash" after completion; a fresh trainer resumes from step 6
        tr2, factory2 = self._setup(tmp_path, steps=6)
        p2, o2, hist2 = tr2.fit(factory2)
        assert hist2 == []  # nothing left to do
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_mid_run_resume_matches_uninterrupted(self, tmp_path):
        # uninterrupted 6-step run
        tr_a, factory_a = self._setup(tmp_path / "a", steps=6)
        pa, _, _ = tr_a.fit(factory_a)
        # interrupted: 3 steps (ckpt at 3), then resume to 6
        tr_b1, factory_b = self._setup(tmp_path / "b", steps=3,
                                       schedule_total=6)
        tr_b1.fit(factory_b)
        tr_b2, factory_b2 = self._setup(tmp_path / "b", steps=6)
        pb, _, hist = tr_b2.fit(factory_b2)
        assert hist[0]["step"] == 3
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_compression_still_learns(self, tmp_path):
        cfg = get_smoke_config("phi3-mini-3.8b")
        model = build_model(cfg, dtype=jnp.float32)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
        tcfg = TrainConfig(steps=16, ckpt_dir=None,
                           compression=CompressionConfig(mode="int8"))
        tr = Trainer(model, AdamWConfig(lr=1e-3, warmup_steps=2,
                                        total_steps=16), tcfg)
        _, _, hist = tr.fit(lambda s: make_pipeline(dcfg, s), resume=False)
        assert np.mean([h["loss"] for h in hist[-3:]]) < \
            np.mean([h["loss"] for h in hist[:3]])


class TestCheckpoint:
    def test_atomic_roundtrip(self, tmp_path):
        state = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
                 "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        checkpoint.save(tmp_path, 7, state)
        assert checkpoint.latest_step(tmp_path) == 7
        out = checkpoint.restore(tmp_path, 7, state)
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(state["a"]))
        assert out["b"]["c"].dtype == jnp.bfloat16

    def test_keep_last(self, tmp_path):
        state = {"x": jnp.zeros((1,))}
        for s in range(5):
            checkpoint.save(tmp_path, s, state, keep_last=2)
        steps = sorted(int(p.name[5:13]) for p in tmp_path.glob("ckpt_*.npz"))
        assert steps == [3, 4]

    def test_shape_mismatch_raises(self, tmp_path):
        checkpoint.save(tmp_path, 0, {"x": jnp.zeros((2,))})
        with pytest.raises(ValueError):
            checkpoint.restore(tmp_path, 0, {"x": jnp.zeros((3,))})


class TestCodedLinear:
    def test_matches_uncoded_any_pattern(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((24, 36)), jnp.float32)
        layer = CodedLinear.build(w, n_workers=6, stragglers=2, seed=1)
        x = jnp.asarray(rng.standard_normal((5, 24)), jnp.float32)
        ref = np.asarray(x @ w)
        import itertools
        for pat in itertools.combinations(range(6), 2):
            done = np.ones(6, bool)
            done[list(pat)] = False
            out = layer.apply(x, jnp.asarray(done))
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=2e-4, atol=2e-4)

    def test_storage_overhead_is_omega_over_k(self):
        w = jnp.ones((16, 32))
        layer = CodedLinear.build(w, n_workers=6, stragglers=2)
        # n shards of width d_out/k: total = (n/k) * logical size
        assert layer.coded.shape == (6, 16, 8)

    def test_differentiable(self):
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
        layer = CodedLinear.build(w, n_workers=4, stragglers=1, seed=0)
        x = jnp.asarray(rng.standard_normal((8,)), jnp.float32)

        def f(x):
            return layer.apply(x).sum()

        g = jax.grad(f)(x)
        ref = jax.grad(lambda x: (x @ w).sum())(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


class TestServeEngine:
    def test_batched_generation(self):
        cfg = get_smoke_config("phi3-mini-3.8b")
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        eng = ServeEngine(model, params, cfg, batch_size=2, max_len=64)
        reqs = [Request(prompt=[1, 5, 9], max_new=4),
                Request(prompt=[1, 7], max_new=4),
                Request(prompt=[1, 2, 3, 4], max_new=4)]
        out = eng.run(reqs)
        assert all(len(r.output) == 4 for r in out)

    def test_coded_head_resilient(self):
        cfg = get_smoke_config("qwen3-14b")
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        from repro.configs.base import CodedConfig
        eng = ServeEngine(model, params, cfg, batch_size=2, max_len=32,
                          coded=CodedConfig(enabled=True, n_workers=6,
                                            stragglers=2))
        rng = np.random.default_rng(0)
        hidden = jnp.asarray(rng.standard_normal((2, cfg.d_model)),
                             jnp.float32)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        ref = np.asarray(hidden @ head)
        for _ in range(5):  # random straggler masks each step
            out = eng.coded_logits(hidden)
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=5e-3, atol=5e-3)


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield before
        jax.config.update("jax_compilation_cache_dir", before)

    def test_outside_directory_left_alone(self, monkeypatch,
                                          restore_cache_dir):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert init_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == restore_cache_dir

    def test_default_is_the_checkout(self, monkeypatch, restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert init_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == (
            Path(__file__).resolve().parents[1] / ".jax_cache")
