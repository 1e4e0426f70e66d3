"""A traced window reduced by the program's own spans.

The program records its stages as host spans (``repro.obs.PROGRAM_SPANS``:
``plan.matvec``, ``executor.select``, ...) while ``REPRO_TRACE=1`` and a
profiler session run, on the clock of the benchmark's spans.  The host
plane also holds one launch event per program run (``LAUNCH``).  The
chip runs one stream in launch order, so the n-th launch and the n-th
module run are one program, and the program's spans around a launch say
which stage ran it.

The trace is in ``bench.trace``'s compact form, loaded with the
program's span names and ``LAUNCH`` kept beside the benchmark's spans.
``stages`` gives the window's device idle time by the stage the host
was in, the device programs per product and the device time of what
``executor.select`` launched.  The harness does not call it yet.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from bench.trace import (DEVICE_PREFIX, MODULES_LINE, _line, device_ops,
                         host_spans, union)

PRODUCTS = ("plan.matvec", "plan.matmat")
SELECT = "executor.select"
# the host event of one program launch through the PJRT C API (one per
# module run on the chip; the runtime's own ``...::Execute`` events nest
# two deep)
LAUNCH = "PJRT_LoadedExecutable_Execute"


@dataclass
class Stages:
    """The window's device idle time and device programs by the program
    stage the host was in (times in seconds)."""

    products: int                 # product spans in the window
    idle_in_program_s: float      # device idle while a product span is open
    gaps: list[tuple[str, float]] = field(default_factory=list)
    # where each module run pairs with its launch on the host, else None
    programs_per_product: float | None = None
    select_device_s: float | None = None   # per product

    def idle_by_stage(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for name, s in self.gaps:
            out[name] = out.get(name, 0.0) + s
        return out

    def breakdown(self, top: int = 10) -> list:
        return [[n, s] for n, s in sorted(self.gaps, key=lambda g: -g[1])[:top]]


def idle_intervals(tr: dict, span_names, offset_ns: float | None
                   ) -> tuple[float, float, list[tuple[float, float]]]:
    """The window (host clock, ns) and the first chip's idle intervals in
    it, exactly as ``bench.trace.summarize`` finds them under the same
    offset."""
    spans = host_spans(tr, span_names)
    lo, hi = spans[0][1], max(end for _, _, end in spans)
    shift = offset_ns or 0.0
    ops = next(iter(device_ops(tr).values()))
    busy = union((max(a + shift, lo), min(b + shift, hi))
                 for _, a, b in ops if b + shift > lo and a + shift < hi)
    edges = [lo] + [x for a, b in busy for x in (a, b)] + [hi]
    return lo, hi, [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def innermost(spans) -> list[tuple[float, float, str]]:
    """The host timeline as (start, end, name) segments, each instant
    given to the innermost of the nested ``spans`` open at it."""
    segs, stack, t = [], [], None
    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= start:
            if stack[-1][2] > t:
                segs.append((t, stack[-1][2], stack[-1][0]))
            t = max(t, stack.pop()[2])
        if stack and start > t:
            segs.append((t, start, stack[-1][0]))
        t = start if t is None else max(t, start)
        stack.append((name, start, end))
    while stack:
        if stack[-1][2] > t:
            segs.append((t, stack[-1][2], stack[-1][0]))
        t = max(t, stack.pop()[2])
    return segs


def _owner(segs, starts, a: float, b: float) -> str:
    """The segment name holding most of [a, b] ("untraced" where none)."""
    share: dict[str, float] = {}
    for s, e, name in segs[max(bisect.bisect_right(starts, a) - 1, 0):]:
        if s >= b:
            break
        overlap = min(e, b) - max(s, a)
        if overlap > 0:
            share[name] = share.get(name, 0.0) + overlap
    return max(share, key=share.get) if share else "untraced"


def _within(intervals, starts, a: float, b: float) -> float:
    """How much of [a, b] the sorted, disjoint ``intervals`` cover."""
    covered = 0.0
    for s, e in intervals[max(bisect.bisect_right(starts, a) - 1, 0):]:
        if s >= b:
            break
        covered += max(0.0, min(e, b) - max(s, a))
    return covered


def launched_runs(tr: dict) -> list[tuple[float, float]] | None:
    """(launch on the host clock, device duration) of each module run of
    the first chip, pairing the host's launch events (``LAUNCH``) with
    the chip's module runs in order: one stream runs its programs in the
    order they were launched.  None where the two counts differ."""
    plane = next(p for p in tr["planes"] if p["name"].startswith(DEVICE_PREFIX))
    runs = sorted((start, dur) for _, start, dur in _line(plane, MODULES_LINE))
    launches = [start for _, start, _ in host_spans(tr, (LAUNCH,))]
    if not runs or len(runs) != len(launches):
        return None
    return [(t, dur) for t, (_, dur) in zip(launches, runs)]


def stages(tr: dict, span_names, program_spans, offset_ns: float | None,
           products=PRODUCTS) -> Stages | None:
    """Reduce the window that ``span_names`` cover by the program's own
    spans ``program_spans``; None where the trace holds no product span
    (``products``) in the window.

    Each idle interval of the first chip, found as ``summarize`` finds
    it, goes to the innermost span, of the program's or the benchmark's,
    that holds most of it.  Where every module run pairs with its launch
    (``launched_runs``), a product's programs are the runs launched
    inside its span, and each run's device time goes to the stage whose
    span holds its launch."""
    lo, hi, idle = idle_intervals(tr, span_names, offset_ns)
    prog = [s for s in host_spans(tr, program_spans) if lo <= s[1] < hi]
    prods = [(s, e) for name, s, e in prog if name in set(products)]
    if not prods:
        return None
    segs = innermost(prog + host_spans(tr, span_names))
    starts = [s for s, _, _ in segs]
    idle_starts = [a for a, _ in idle]
    out = Stages(
        products=len(prods),
        idle_in_program_s=1e-9 * sum(_within(idle, idle_starts, s, e)
                                     for s, e in prods),
        gaps=[(_owner(segs, starts, a, b), (b - a) * 1e-9) for a, b in idle])
    runs = launched_runs(tr)
    if runs is None:
        return out
    prod_starts = [s for s, _ in prods]
    programs, select_ns = 0, 0.0
    for t, dur in runs:
        i = bisect.bisect_right(prod_starts, t) - 1
        if i < 0 or t > prods[i][1]:
            continue                    # launched outside every product
        programs += 1
        j = bisect.bisect_right(starts, t) - 1
        if j >= 0 and t < segs[j][1] and segs[j][2] == SELECT:
            select_ns += dur
    out.programs_per_product = programs / len(prods)
    out.select_device_s = select_ns * 1e-9 / len(prods)
    return out
