"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
        --requests 8 --max-new 16 --coded --stragglers 2

Boots a model (smoke config on CPU; full config under a mesh on real
hardware), runs a wave of synthetic requests through the batched engine,
and optionally routes the LM head through the straggler-resilient coded
path, reporting per-step resilience checks.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..api.schemes import scheme_names
from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..configs.base import CodedConfig
from ..models import build_model
from ..runtime import BACKENDS
from ..serve import Request, ServeEngine
from .cache import init_compile_cache


def main() -> None:
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--coded", action="store_true",
                    help="serve logits through the coded LM head")
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--stragglers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheme",
                    choices=scheme_names("mv", resilient_only=True),
                    default="proposed",
                    help="registered coded scheme for the LM head "
                         "(repro.api.list_schemes; non-resilient and "
                         "capacity-based schemes are excluded)")
    ap.add_argument("--coded-backend", choices=BACKENDS + ("auto",),
                    default="auto",
                    help="coded-execution backend for the LM head "
                         "(auto = density + platform pick at plan "
                         "compile time, see repro.api.backends)")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "audio":
        raise SystemExit("audio serving needs frames; see tests/examples")
    model = build_model(cfg, dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    params = model.init(jax.random.key(args.seed))
    coded = CodedConfig(enabled=True, n_workers=args.workers,
                        stragglers=args.stragglers, scheme=args.scheme,
                        backend=args.coded_backend) if args.coded else None
    engine = ServeEngine(model, params, cfg, batch_size=args.batch,
                         max_len=args.max_len, coded=coded)
    if engine.coded is not None:
        print(f"coded LM head plan: {engine.coded.describe()}")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=[1] + rng.integers(2, cfg.vocab,
                                              rng.integers(2, 9)).tolist(),
                    max_new=args.max_new)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    out = engine.run(reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in out)
    print(f"served {len(out)} requests, {tokens} tokens "
          f"in {dt:.2f}s ({tokens / dt:.1f} tok/s incl. compile)")
    for i, r in enumerate(out[: min(4, len(out))]):
        print(f"  req {i}: {r.prompt[:6]}... -> {r.output}")

    if args.coded:
        hidden = jnp.asarray(rng.standard_normal((2, cfg.d_model)),
                             jnp.float32)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        ref = np.asarray(hidden @ head)
        worst = 0.0
        for _ in range(5):
            logits = engine.coded_logits(hidden)
            worst = max(worst, float(np.max(np.abs(np.asarray(logits) - ref))
                                     / (np.max(np.abs(ref)) + 1e-9)))
        print(f"coded head: 5 random straggler patterns, "
              f"worst rel err {worst:.2e} "
              f"(resilient to any {args.stragglers}/{args.workers} lost)")


if __name__ == "__main__":
    main()
