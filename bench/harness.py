"""One run of one cell: set-up, the measured window, the check, the line.

Set-up makes the operands from the seed on the device, compiles the
plan through ``compile_plan`` and calls every shape and straggler mask
the traffic will use once.  The window is one caller in a closed loop:
draw the next call, issue it, wait for it with ``block_until_ready``,
until ``--seconds`` have passed.  Nothing compiles inside it.  After it
the run reads the device's peak memory, checks that every call stayed
on the compiled kernel path, frees the program's state, and compares
the outputs it kept (a sample of each straggler mask's, drawn from the
seed) with the float64 reference.  The number compared is each output's
error, ``max|y - ref| / max|ref|``, over the condition number of its
straggler mask's decode: a sound float32 decode loses digits in
proportion to it, so the raw error swings with the mask drawn (by three
orders of magnitude between the masks of ``mm_n42_k6``).

With ``--trace 1`` the window runs under the JAX profiler, with the
benchmark's own host spans around each call (``bench.choose``,
``bench.call``, ``bench.wait``), and the run reports its per-layer
metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from bench import reference, spec
from bench import trace as tracing

SPANS = ("bench.choose", "bench.call", "bench.wait")
PROGRAM = "program"
CONTROLS = ("bf16", "high")


def info(**kw) -> None:
    """A line of information on standard error; never a metric."""
    print(json.dumps({"info": kw}), file=sys.stderr, flush=True)


class Phases:
    """Host seconds of each named set-up phase."""

    def __init__(self):
        self.s: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - t


class Sample:
    """A uniform sample of at most ``size`` of the outputs of each key
    (a straggler mask), drawn with ``rng``.  ``offer`` takes a function
    that makes the item, and calls it only where the item is kept."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = size, rng
        self.items: dict = {}
        self.seen: Counter = Counter()

    def offer(self, key, make) -> None:
        self.seen[key] += 1
        kept = self.items.setdefault(key, [])
        if len(kept) < self.size:
            kept.append(make())
            return
        j = int(self.rng.integers(self.seen[key]))
        if j < self.size:
            kept[j] = make()

    def take(self) -> list:
        """Every kept item, key by key; the sample is emptied."""
        items = [x for key in sorted(self.items) for x in self.items[key]]
        self.items = {}
        return items


@dataclass
class Call:
    spec: object
    issued: float          # host clock, seconds
    returned: float        # the call handed back its (pending) result
    done: float            # block_until_ready returned
    ok: bool


@dataclass
class Run:
    """What a metric reader sees of one run."""

    stream: object
    calls: list[Call]
    window_s: float
    setup_s: float
    phases: dict[str, float]
    peaks: dict | None = None
    summary: tracing.Summary | None = None
    _work: dict = field(default_factory=dict)

    @property
    def ok_calls(self) -> list[Call]:
        return [c for c in self.calls if c.ok]

    def work(self, kernel: str) -> tuple[int, int] | None:
        """(operations, bytes) of ``kernel`` over every call in the
        window, from ``roofline/<kernel>.py``; None where the calls do
        no such work."""
        count = spec.load_module("roofline", kernel).count
        keys = Counter(self.stream.work_key(c.spec) for c in self.ok_calls)
        flops = moved = 0
        for key, times in keys.items():
            if (kernel, key) not in self._work:
                self._work[kernel, key] = count(self.stream.describe(key))
            one = self._work[kernel, key]
            if one is None:
                return None
            flops += times * one[0]
            moved += times * one[1]
        return flops, moved

    def roofline_share(self, kernel: str, match) -> float | None:
        """The least time the chip needs for the kernel's work over its
        device time in the trace, in %; None where the trace holds no
        such kernel or the calls do no such work."""
        if self.summary is None or self.peaks is None:
            return None
        seconds = self.summary.kernel_s(match)
        work = self.work(kernel)
        if seconds <= 0 or work is None:
            return None
        t_ops = work[0] / self.peaks["flops_per_s"]
        t_mem = work[1] / self.peaks["bytes_per_s"]
        info(roofline={kernel: {
            "bound": "compute" if t_ops >= t_mem else "memory",
            "operations": work[0], "bytes": work[1],
            "least_s": max(t_ops, t_mem), "device_s": seconds}})
        return 100.0 * max(t_ops, t_mem) / seconds


def closed_loop(stream, call, seconds: float, rng: np.random.Generator,
                annotate, keep: Sample) -> tuple[list[Call], float]:
    """One caller, one product in flight, for ``seconds``."""
    calls = []
    start = time.perf_counter()
    end = start + seconds
    while True:
        with annotate("bench.choose"):
            sp = stream.draw(rng)
        ok, y = True, None
        with annotate("bench.call"):
            t1 = time.perf_counter()
            try:
                y = call(sp)
            except Exception as exc:  # noqa: BLE001 - a failed call is counted
                ok = False
                info(call_failed=repr(exc))
            t2 = time.perf_counter()
        with annotate("bench.wait"):
            if ok:
                try:
                    y.block_until_ready()
                except Exception as exc:  # noqa: BLE001
                    ok = False
                    info(call_failed=repr(exc))
            t3 = time.perf_counter()
        calls.append(Call(sp, t1, t2, t3, ok))
        if ok:
            keep.offer(stream.work_key(sp),
                       lambda sp=sp, y=y: (sp, stream.kept(sp, y)))
        if t3 >= end:
            return calls, t3 - start


def _no_span(name):
    return contextlib.nullcontext()


def peak_bytes() -> int | None:
    import jax  # noqa: PLC0415

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def run_cell(name: str, config: dict, cell: dict, *, seed: int,
             seconds: float, trace: bool, metrics: list[dict],
             backend: str = "pallas", product: str = PROGRAM,
             t0: float | None = None, peaks: dict | None = None) -> dict:
    """Runs ``name`` once and returns its result line as a dict.

    ``product`` is ``program`` (the coded plan) or a control: the
    reference in the program's place at ``bf16`` or ``high`` precision
    (``reference.lower_precision_dot``).
    """
    import jax  # noqa: PLC0415

    t0 = time.perf_counter() if t0 is None else t0
    phases = Phases()
    gen = spec.load_module("traffic", cell["generator"])
    stream = gen.Stream(config, cell["params"], seed, backend, phases.phase)
    if product == PROGRAM:
        call = stream.call
    elif product in CONTROLS:
        stream.free_program()
        def call(sp):  # noqa: E306
            return stream.reference_call(sp, product)
    else:
        raise ValueError(f"unknown product {product!r}")
    with phases.phase("warm"):
        for sp in stream.warm_specs():
            jax.block_until_ready(stream.kept(sp, call(sp)))
    rng = np.random.default_rng([seed, 2])
    keep = Sample(stream.sample_size, np.random.default_rng([seed, 3]))
    setup_s = time.perf_counter() - t0
    info(setup_s=setup_s, phases=phases.s)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # Python calls: cost, no use
        options.host_tracer_level = 1        # the benchmark's spans only
        jax.profiler.start_trace(log_dir, profiler_options=options)
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = _no_span
    try:
        calls, window_s = closed_loop(stream, call, seconds, rng, annotate,
                                      keep)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = peak_bytes()

    run = Run(stream=stream, calls=calls, window_s=window_s, setup_s=setup_s,
              phases=phases.s, peaks=peaks)
    if trace:
        compact = tracing.load_xspace(tracing.find_xspace(log_dir), SPANS)
        shutil.rmtree(log_dir, ignore_errors=True)
        run.summary = tracing.summarize(compact, SPANS)
        del compact
        info(trace={"device_clock_offset_ns": run.summary.offset_ns,
                    "idle_s_by_host_span": run.summary.idle_by_span()})

    checks = {}
    if product == PROGRAM:
        checks["off_path_calls"] = {
            "value": reference.off_path_calls(stream.plan, backend),
            "limit": 0}
        checks["scheme_differs"] = {"value": int(not stream.scheme_matches()),
                                    "limit": 0}
        stream.free_program()
    values = {}
    for m in metrics:
        v = spec.load_module("metrics", m["name"]).read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    samples = keep.take()
    t_ref = time.perf_counter()
    errs = stream.errors(samples)           # (mask, error, decode kappa)
    info(reference_s=time.perf_counter() - t_ref)
    per_kappa = [e / kappa for _, e, kappa in errs]
    worst: dict[str, dict] = {}
    for mask, e, kappa in errs:
        w = worst.setdefault(str(mask), {"kappa": kappa, "max_rel_err": 0.0})
        w["max_rel_err"] = max(w["max_rel_err"], e)
    info(errors_by_mask=worst)
    limit = cell["limits"]["err_per_kappa"]
    failed_calls = sum(not c.ok for c in calls)
    checks["calls_failed"] = {"value": failed_calls, "limit": 0}
    checks["outputs_compared"] = {"value": len(errs), "limit": 1,
                                  "at_least": True}
    checks["err_per_kappa"] = {"value": max(per_kappa, default=float("inf")),
                               "limit": limit}
    correct = all((c["value"] >= c["limit"]) if c.get("at_least")
                  else (c["value"] <= c["limit"]) for c in checks.values())

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": failed_calls + sum(e > limit for e in per_kappa),
              "metrics": values, "device": device}
    if run.summary is not None:
        device["busy_s"] = run.summary.busy_s
        device["window_s"] = run.summary.window_s
        result["breakdown"] = run.summary.breakdown()
    result["checks"] = checks
    return result


def print_checks(checks: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines
    on standard error."""
    for key, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {key} = {c['value']!r} (limit {rel} {c['limit']!r})",
              file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t0: float) -> int:
    args = parse(argv)
    bm = spec.load_benchmark()
    w = spec.workload(bm, args.workload)
    config = spec.load_json("configs", w["config"])
    cell = spec.load_json("cells", w["name"])
    try:
        from repro.launch.cache import init_compile_cache  # noqa: PLC0415
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    init_compile_cache()
    import jax  # noqa: PLC0415

    # small programs too: every later run then finds all of set-up cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX's first device is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < w["chips"]:
        print(f"bench: {args.workload} needs {w['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    try:
        peaks = spec.peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return 1

    result = run_cell(args.workload, config, cell, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      metrics=spec.cell_metrics(bm, args.workload,
                                                bool(args.trace)),
                      t0=t0, peaks=peaks)
    info(memory_peak_bytes=result["device"]["memory_peak_bytes"])
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0
