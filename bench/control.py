#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 \\
        --products program,bf16,high --seconds 3

Runs the cell once per seed and product in one process, each with a
short window at the cell's own load, and prints one JSON line per run
with the numbers its check compared.  ``program`` is the coded plan, as
``run.py`` drives it; ``bf16`` and ``high`` are the controls: the
float64 reference's product computed on the chip in its place, with one
bfloat16 pass or with three.  The benchmark's own runs never run them.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv) -> int:
    import argparse  # noqa: PLC0415
    import gc  # noqa: PLC0415
    import json  # noqa: PLC0415

    from bench import harness, spec  # noqa: PLC0415

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--products", default="program,bf16")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from repro.launch.cache import init_compile_cache  # noqa: PLC0415

    init_compile_cache()
    import jax  # noqa: PLC0415

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found; nothing was run", file=sys.stderr)
        return 1
    bm = spec.load_benchmark()
    w = spec.workload(bm, args.workload)
    config = spec.load_json("configs", w["config"])
    cell = spec.load_json("cells", w["name"])
    for product in args.products.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            res = harness.run_cell(w["name"], config, cell, seed=seed,
                                   seconds=args.seconds, trace=False,
                                   metrics=[], product=product)
            print(json.dumps({"workload": w["name"], "product": product,
                              "seed": seed, "correct": res["correct"],
                              "attempted": res["attempted"],
                              "checks": res["checks"],
                              "memory_peak_bytes":
                                  res["device"]["memory_peak_bytes"]}),
                  flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
