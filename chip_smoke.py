#!/usr/bin/env python3
"""Run the coded plan path once on a TPU, at the paper's sizes.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # the sharded coded layer only

One process drives the chip.  With no options it runs, in this order:

  device  the first JAX device must be a TPU; otherwise the script exits
          non-zero without printing a result.  It never goes on on a CPU.
  mv      ``compile_plan(A, scheme="proposed", n=12, k_A=9,
          backend="pallas")`` on A 20000 x 15000 f32 at 98% zeros, then
          ``plan.matvec`` at batches 8 and 136, all workers alive and
          with workers {1, 5, 9} straggling.
  fleet   the mv plan attached to a ``CodedFleet`` on the ``pipe``
          transport: host worker processes serve a few matvecs.  It runs
          before mm so the mv plan is freed before the largest
          allocation.
  mm      the paper's matrix-matrix system (n=42, k_A=k_B=6, six
          stragglers) on A 20000 x 15000 and B 20000 x 12000, each side
          scaled by ``--mm-scale`` (default 0.7): at full size the coded
          shards alone (8.4 GB of A, 6.7 GB of B in f32) exceed one
          v5e's 16 GB, and 0.75 no longer fits.

Every result is checked against a float64 product on the host; the
largest relative error ``max|y - y_ref| / max|y_ref|`` must be at most
1e-3.  A phase also fails if a call left the compiled Pallas path (a
backend override, the CPU default, a traced-input fallback to the
reference path, or interpret mode).

``--chips 4`` runs only ``CodedLinear.apply_sharded`` (n=4, s=1, one
worker per device, one device masked as a straggler) on a 20000 x 15000
weight, against the same product run uncoded over the same mesh at
``precision=HIGHEST`` and against float64.

Each phase prints one JSON line; set-up and call wall seconds in it are
set-up information, not metrics.  The last line is
``{"ok": true, "device": {...}}`` only when every phase passed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-3
ZEROS = 0.98                   # the paper's sparsity level
MV_SHAPE = (20000, 15000)      # A: t x r
MM_SHAPES = ((20000, 15000), (20000, 12000))


def block_sparse(rng, t: int, r: int, zeros: float = ZEROS, bs: int = 8):
    """(t, r) f32 with whole (bs x bs) tiles zeroed at rate ``zeros`` and
    standard-normal values elsewhere (the runtime benchmark's operand)."""
    tb, rb = -(-t // bs), -(-r // bs)
    mask = rng.random((tb, rb)) >= zeros
    a = np.zeros((tb, bs, rb, bs), np.float32)
    a.transpose(0, 2, 1, 3)[mask] = rng.standard_normal(
        (int(mask.sum()), bs, bs), dtype=np.float32)
    return a.reshape(tb * bs, rb * bs)[:t, :r]


def rel_err(y, ref) -> float:
    y = np.asarray(y, np.float64)
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def sparse64(a):
    from scipy import sparse  # noqa: PLC0415

    return sparse.csr_matrix(a, dtype=np.float64)


def check_path(plan) -> None:
    """Every executor call of ``plan`` ran the compiled Pallas kernels."""
    ex = plan.executor
    if plan.backend != "pallas" or ex.backend != "pallas":
        raise RuntimeError(f"plan resolved to {plan.backend!r} "
                           f"(executor {ex.backend!r}), not 'pallas'")
    if set(ex.calls) != {"pallas"}:
        raise RuntimeError(f"calls left the pallas path: {dict(ex.calls)}")


class Phase:
    """Collects one phase's line; a raised error fails the phase."""

    def __init__(self, name: str, results: list, **shapes):
        self.results = results
        self.line = {"phase": name, "ok": False, "shapes": shapes,
                     "backend": None, "compiled": None, "max_rel_err": {},
                     "info_not_metrics": {}, "error": None}

    def info(self, **kw) -> None:
        self.line["info_not_metrics"].update(kw)

    def check(self, **pairs) -> None:
        """Records every ``label=(y, ref)`` error, then fails on any
        above the tolerance."""
        errs = {label: rel_err(y, ref) for label, (y, ref) in pairs.items()}
        self.line["max_rel_err"].update(errs)
        bad = {label: e for label, e in errs.items() if not e <= TOL}
        if bad:
            raise AssertionError(f"max rel err above {TOL}: {bad}")

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.info(phase_wall_s=time.perf_counter() - self.t0)
        if exc is not None:
            self.line["error"] = "".join(
                traceback.format_exception_only(exc_type, exc)).strip()
            traceback.print_exception(exc_type, exc, tb, file=sys.stderr)
        else:
            self.line["ok"] = True
        self.results.append(self.line)
        print(json.dumps(self.line), flush=True)
        return exc is None or isinstance(exc, Exception)


def timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def mv_phase(rng, results: list):
    import jax.numpy as jnp  # noqa: PLC0415

    from repro.api import compile_plan  # noqa: PLC0415

    t, r = MV_SHAPE
    n, k_a = 12, 9
    plan = a_sp = None
    with Phase("mv", results, A=[t, r], n=n, k_A=k_a, batches=[8, 136],
               stragglers=[1, 5, 9]) as ph:
        a_np = block_sparse(rng, t, r)
        a_sp = sparse64(a_np)
        A = jnp.asarray(a_np)
        plan, setup_s = timed(compile_plan, A, scheme="proposed", n=n,
                              k_A=k_a, backend="pallas")
        ph.line["backend"] = plan.backend
        ph.info(compile_plan_s=setup_s)
        slow = np.ones(n, bool)
        slow[[1, 5, 9]] = False
        calls = {}
        for batch in (8, 136):
            x = rng.standard_normal((batch, t), dtype=np.float32)
            ref = (a_sp.T @ x.T.astype(np.float64)).T
            for label, done in (("alive", None), ("stragglers", slow)):
                y, call_s = timed(plan.matvec, jnp.asarray(x), done)
                calls[f"b{batch}_{label}"] = call_s
                ph.check(**{f"b{batch}_{label}": (y, ref)})
        check_path(plan)
        ph.line["compiled"] = True
        ph.info(call_s_first_includes_compile=calls)
    return (plan, a_sp) if ph.line["ok"] else (None, None)


def fleet_phase(rng, plan, a_sp, results: list):
    from repro.api.fleet import CodedFleet  # noqa: PLC0415

    t = MV_SHAPE[0]
    workers, calls = 4, 4
    with Phase("fleet", results, transport="pipe", workers=workers,
               calls=calls, x=[t]) as ph:
        if plan is None:
            raise RuntimeError("no mv plan to serve (the mv phase failed)")
        ph.line["backend"] = plan.backend
        t0 = time.perf_counter()
        with CodedFleet(workers, transport="pipe") as fleet:
            handle = fleet.attach(plan)
            ph.info(attach_s=time.perf_counter() - t0)
            xs = rng.standard_normal((calls, t), dtype=np.float32)
            t1 = time.perf_counter()
            futs = [handle.submit_matvec(x) for x in xs]
            ys = [f.result(timeout=600) for f in futs]
            ph.info(serve_s=time.perf_counter() - t1)
        ref = (a_sp.T @ xs.T.astype(np.float64)).T
        ph.check(**{f"call{i}": (y, ref[i]) for i, y in enumerate(ys)})
        check_path(plan)
        ph.line["compiled"] = True


def mm_phase(rng, scale: float, results: list):
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from repro.api import compile_plan  # noqa: PLC0415

    (t, ra), (_, rb) = MM_SHAPES
    t, ra, rb = (int(round(d * scale)) for d in (t, ra, rb))
    n, k_a, k_b = 42, 6, 6
    with Phase("mm", results, A=[t, ra], B=[t, rb], n=n, k_A=k_a, k_B=k_b,
               mm_scale=scale, stragglers=6) as ph:
        a_np = block_sparse(rng, t, ra)
        b_np = block_sparse(rng, t, rb)
        ref = sparse64(a_np).T @ b_np.astype(np.float64)
        A, B = jnp.asarray(a_np), jnp.asarray(b_np)
        del a_np, b_np
        plan, setup_s = timed(compile_plan, A, scheme="proposed", n=n,
                              k_A=k_a, k_B=k_b, backend="pallas")
        ph.line["backend"] = plan.backend
        ph.info(compile_plan_s=setup_s)
        done = np.ones(n, bool)
        done[rng.choice(n, 6, replace=False)] = False
        ph.line["shapes"]["straggler_ids"] = np.flatnonzero(~done).tolist()
        y, call_s = timed(plan.matmat, B, done)
        ph.info(matmat_s_includes_compile=call_s)
        ph.check(stragglers=(y, ref))
        check_path(plan)
        ph.line["compiled"] = True
        stats = jax.devices()[0].memory_stats() or {}
        ph.info(**{key: stats[key] for key in
                   ("peak_bytes_in_use", "bytes_limit") if key in stats})


# ---------------------------------------------------------------------------
# Four chips: the sharded coded layer
# ---------------------------------------------------------------------------


def sharded_phase(rng, chips: int, results: list):
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415
    from jax.sharding import NamedSharding  # noqa: PLC0415
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    from repro.parallel import CodedLinear  # noqa: PLC0415

    t, r = MV_SHAPE
    batch = 8
    with Phase("sharded", results, W=[t, r], n=chips, s=1, batch=batch,
               straggler=[1]) as ph:
        mesh = jax.make_mesh((chips,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        w_np = block_sparse(rng, t, r)
        x_np = rng.standard_normal((batch, t), dtype=np.float32)
        ref = (sparse64(w_np).T @ x_np.T.astype(np.float64)).T
        W, x = jnp.asarray(w_np), jnp.asarray(x_np)
        layer, setup_s = timed(CodedLinear.build, W, n_workers=chips,
                               stragglers=1, backend="pallas")
        ph.line["backend"] = layer.backend
        if layer.backend != "pallas":
            raise RuntimeError(f"layer built on {layer.backend!r}, "
                               "not 'pallas'")
        ph.info(build_s=setup_s)
        done = jnp.asarray(np.arange(chips) != 1)
        y, call_s = timed(layer.apply_sharded, mesh, "model", x, done)
        ph.info(apply_sharded_s_includes_compile=call_s)
        w_sharded = jax.device_put(W, NamedSharding(mesh, P(None, "model")))
        dot = jax.jit(lambda a, b: jnp.dot(
            a, b, precision=jax.lax.Precision.HIGHEST))
        y_dense, dense_s = timed(dot, x, w_sharded)
        ph.info(uncoded_dot_s_includes_compile=dense_s)
        ph.line["max_rel_err"]["coded_vs_uncoded"] = rel_err(
            y, np.asarray(y_dense, np.float64))
        ph.check(uncoded_vs_float64=(y_dense, ref), coded_vs_float64=(y, ref))
        ph.line["compiled"] = True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded coded layer phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mm-scale", type=float, default=0.7,
                    help="scale of the mm system's sides (1.0: paper size; "
                         "0.7 is the largest that fits one v5e's 16 GB)")
    args = ap.parse_args()

    try:
        from repro.launch.cache import init_compile_cache  # noqa: PLC0415
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: "
              f"{e}", file=sys.stderr)
        return 2
    init_compile_cache()
    import jax  # noqa: PLC0415

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    rng = np.random.default_rng(args.seed)
    results: list[dict] = []
    if args.chips == 1:
        plan, a_sp = mv_phase(rng, results)
        fleet_phase(rng, plan, a_sp, results)
        del plan, a_sp
        gc.collect()
        mm_phase(rng, args.mm_scale, results)
    else:
        sharded_phase(rng, args.chips, results)

    if not results or not all(line["ok"] for line in results):
        failed = [line["phase"] for line in results if not line["ok"]]
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
