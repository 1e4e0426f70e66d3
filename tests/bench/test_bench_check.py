"""The comparison that decides ``correct``, driven through a whole run of
each cell at a size the CPU holds (the kernels in interpret mode; the
look for a chip skipped): sound runs pass, the controls fail, and runs
with the timed path broken underneath fail."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec

# a size the CPU holds in interpret mode
SMALL = {"mv_n12_k9.b8": {"A": [640, 480]},
         "mm_n42_k6.s6": {"A": [256, 216], "B": [256, 180]}}
CELLS = ["mv_n12_k9.b8", "mm_n42_k6.s6"]
SEED = 2**33 + 12345       # wider than 32 bits, as a check's seeds may be


def run(cell_name: str, product: str = harness.PROGRAM, seed: int = SEED):
    cell = spec.load_json("cells", cell_name)
    config = {**spec.load_json("configs", cell["config"]), **SMALL[cell_name]}
    return harness.run_cell(cell_name, config, cell, seed=seed, seconds=0.3,
                            trace=False, metrics=metrics(cell_name),
                            backend="pallas-interpret", product=product)


def metrics(cell_name: str) -> list[dict]:
    return spec.cell_metrics(spec.load_benchmark(), cell_name, False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in metrics(cell)}
    assert {"products_per_s", "setup_s"} <= set(res["metrics"])


def _stream(cell_name: str, seed: int = SEED):
    cell = spec.load_json("cells", cell_name)
    config = {**spec.load_json("configs", cell["config"]), **SMALL[cell_name]}
    return spec.load_module("traffic", cell["generator"]).Stream(
        config, cell["params"], seed, "pallas-interpret",
        harness.Phases().phase)


def test_same_seed_same_work():
    a, b = (_stream("mv_n12_k9.b8") for _ in range(2))
    assert bool(jnp.array_equal(a.A, b.A))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a.xs, b.xs))
    assert [m.tolist() for m in a.masks] == [m.tolist() for m in b.masks]


@pytest.mark.parametrize("cell", CELLS)
def test_zeros_fall_scalar_by_scalar(cell):
    """The configurations state the paper's scalar-random zeros: no
    8 x 8 tile of the operand is all zero or all nonzero as a rule."""
    stream = _stream(cell)
    nz = np.asarray(stream.A) != 0
    assert abs(nz.mean() - 0.02) < 0.01
    t, r = nz.shape
    tiles = nz[:t // 8 * 8, :r // 8 * 8].reshape(t // 8, 8, r // 8, 8)
    per_tile = tiles.sum(axis=(1, 3))
    assert (per_tile == 1).sum() > (per_tile == 64).sum()


def test_masks_come_in_rounds():
    from bench.schemes import MaskCycle

    cycle, rng = MaskCycle(9), np.random.default_rng(SEED)
    rounds = [[cycle.draw(rng) for _ in range(9)] for _ in range(4)]
    assert all(sorted(r) == list(range(9)) for r in rounds)
    assert len({tuple(r) for r in rounds}) > 1


def test_sample_keeps_some_outputs_of_every_mask():
    keep = harness.Sample(2, np.random.default_rng(SEED))
    made = []
    for i in range(60):
        keep.offer(i % 3, lambda i=i: made.append(i) or i)
    items = keep.take()
    assert len(items) == 6 and sorted(i % 3 for i in items) == [0, 0, 1, 1,
                                                               2, 2]
    assert len(made) < 60          # an output not kept is never sliced
    assert keep.take() == []


def test_mm_keeps_columns_of_every_block():
    stream = _stream("mm_n42_k6.s6")
    cb = -(-stream.rb // stream.k_b)
    per_block = np.bincount(stream.cols // cb, minlength=stream.k_b)
    params = spec.load_json("cells", "mm_n42_k6.s6")["params"]
    assert per_block.tolist() == [params["columns"]] * stream.k_b
    assert len(set(stream.cols.tolist())) == len(stream.cols)


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(cell):
    res = run(cell, "bf16")
    check = res["checks"]["err_per_kappa"]
    assert not res["correct"] and check["value"] > check["limit"]


def test_high_control_asks_for_three_passes():
    """On the TPU ``Precision.HIGH`` is three bfloat16 passes; the CPU
    computes it exactly, so its readings come from the chip (PERF.md)."""
    import jax

    from bench.reference import lower_precision_dot

    a = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    text = jax.jit(lambda x, y: lower_precision_dot(x, y, "high")).lower(
        a, b).as_text()
    assert "precision = [HIGH, HIGH]" in text, text


def _altered_answer(kernel):
    def broken(a_data, a_idx, b, *, interpret=False):
        return kernel(a_data, a_idx, b, interpret=interpret).at[0, 0].add(1.0)
    return broken


def _half_batch(kernel):
    def broken(a_data, a_idx, b, *, interpret=False):
        half = b.shape[1] // 2
        return kernel(a_data, a_idx, b.at[:, half:].set(0.0),
                      interpret=interpret)
    return broken


@pytest.mark.parametrize("fault", [_altered_answer, _half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    from repro.runtime import executor

    monkeypatch.setattr(executor, "worker_kernel",
                        fault(executor.worker_kernel))
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["err_per_kappa"]["value"] > \
        res["checks"]["err_per_kappa"]["limit"]


def test_a_call_off_the_kernel_path_is_not_correct(monkeypatch):
    monkeypatch.setenv("REPRO_CODED_BACKEND", "packed")
    res = run("mv_n12_k9.b8")
    assert not res["correct"]
    assert res["checks"]["off_path_calls"]["value"] > 0
