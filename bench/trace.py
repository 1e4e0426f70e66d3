"""From a profiler trace to busy time, kernel time and idle gaps.

A trace is kept in a compact form, the same for a fresh capture and for
the recorded one the tests read:

    {"planes": [{"name": ..., "lines": [{"name": ...,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are ``/device:TPU:<i>``.  Their ``XLA Modules`` line holds
one event per program run (``jit_worker_kernel``) and their ``XLA Ops``
line one event per operation in it, named by its HLO instruction
(``worker_kernel.1``, ``copy.1``).  Of the host (``/host:CPU``) only the
benchmark's own spans (``jax.profiler.TraceAnnotation``) are kept.

The device's timestamps reach the host's clock with an offset of a
millisecond or two.  In a closed loop the chip is idle while the host
is between two calls, so the offset is taken as the middle of the range
of shifts under which no device operation overlaps such a host interval.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MAX_OFFSET_NS = 5e6


def short_name(name: str) -> str:
    """``%worker_kernel.1 = f32[..] custom-call(..)`` -> ``worker_kernel.1``;
    ``jit_worker_kernel(1770..)`` -> ``jit_worker_kernel``."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    if name.endswith(")") and "(" in name:
        return name[:name.index("(")]
    return name


def load_xspace(path: str, host_spans=()) -> dict:
    """The compact form of one ``.xplane.pb`` file: the device planes'
    modules and operations, and the host spans named in ``host_spans``."""
    from jax.profiler import ProfileData  # noqa: PLC0415

    keep = set(host_spans)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [{"name": line.name,
                      "events": [[short_name(ev.name), ev.start_ns,
                                  ev.duration_ns] for ev in line.events]}
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)]
        elif plane.name == HOST_PLANE:
            lines = [{"name": line.name,
                      "events": [[ev.name, ev.start_ns, ev.duration_ns]
                                 for ev in line.events if ev.name in keep]}
                     for line in plane.lines]
            lines = [line for line in lines if line["events"]]
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xspace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {found}")
    return found[0]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def _line(plane: dict, name: str) -> list:
    return [ev for line in plane["lines"] if line["name"] == name
            for ev in line["events"]]


def device_ops(tr: dict) -> dict[str, list[tuple[str, float, float]]]:
    """Per device plane, its operations as (``module/op``, start, end),
    each named after the module run that holds its start."""
    out = {}
    for plane in tr["planes"]:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        mods = sorted(_line(plane, MODULES_LINE), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        ops = []
        for name, start, dur in _line(plane, OPS_LINE):
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and start < mods[i][1] + mods[i][2]
            ops.append((f"{mods[i][0] if inside else '?'}/{name}", start,
                        start + dur))
        out[plane["name"]] = ops
    return out


def host_spans(tr: dict, names) -> list[tuple[str, float, float]]:
    """The host events named in ``names``, as (name, start, end), in order."""
    names = set(names)
    return sorted(((name, start, start + dur)
                   for plane in tr["planes"] if plane["name"] == HOST_PLANE
                   for line in plane["lines"]
                   for name, start, dur in line["events"] if name in names),
                  key=lambda s: s[1])


def clock_offset(busy, quiet, limit: float = MAX_OFFSET_NS) -> float | None:
    """The shift (ns) to add to device times: the middle of the feasible
    range nearest 0 of shifts under which no busy interval overlaps a
    ``quiet`` host interval.  None where no shift within ``limit`` is
    feasible."""
    starts = [a for a, _ in busy]
    forbidden = []
    for s, e in quiet:
        i = max(bisect.bisect_left(starts, s - limit) - 1, 0)
        for a, b in busy[i:]:
            if a >= e + limit:
                break
            forbidden.append((s - b, e - a))  # open: overlap iff inside
    feasible, lo = [], -limit
    for a, b in union(forbidden) + [(limit, limit)]:
        if a > lo:
            feasible.append((lo, min(a, limit)))
        lo = max(lo, b)
        if lo >= limit:
            break
    if not feasible:
        return None
    a, b = min(feasible, key=lambda r: abs(r[0] + r[1]))
    return (a + b) / 2


@dataclass
class Summary:
    """One traced window, reduced (times in seconds)."""

    window_s: float
    busy_s: float                 # mean over the chips used
    op_s: dict[str, float]        # device time per ``module/op``
    offset_ns: float | None       # device clock shift applied
    gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, names) -> float:
        """Device time of the operations whose HLO instruction, without
        its ``.N`` suffix, is one of ``names``."""
        names = set(names)
        return sum(s for key, s in self.op_s.items()
                   if key.rsplit("/", 1)[-1].rsplit(".", 1)[0] in names)

    def idle_by_span(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for name, s in self.gaps:
            out[name] = out.get(name, 0.0) + s
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def summarize(tr: dict, span_names, quiet=("bench.wait", "bench.call")
              ) -> Summary:
    """Reduce a trace over the window its host spans ``span_names``
    cover, from the first one's start to the last one's end.

    The chip is taken to be idle from the end of each ``quiet[0]`` span
    to the start of the next ``quiet[1]`` span; that fixes the device
    clock's offset.  Busy time is the union of each chip's operation
    intervals inside the window, averaged over the chips.  Each idle gap
    of the first chip is named after the host span that overlaps it
    most ("untraced" where none does)."""
    spans = host_spans(tr, span_names)
    if not spans:
        raise ValueError(f"no host spans {sorted(span_names)} in the trace")
    lo, hi = spans[0][1], max(end for _, _, end in spans)
    per_device = device_ops(tr)
    if not per_device:
        raise ValueError("no device plane in the trace")
    first = next(iter(per_device.values()))
    quiet_iv, since = [], None
    for name, start, end in spans:
        if name == quiet[1] and since is not None:
            quiet_iv.append((since, start))
        since = end if name == quiet[0] else since
    offset = clock_offset(union((a, b) for _, a, b in first), quiet_iv)
    shift = offset or 0.0
    op_s: dict[str, float] = {}
    busy = []
    for ops in per_device.values():
        inside = [(name, max(a + shift, lo), min(b + shift, hi))
                  for name, a, b in ops if b + shift > lo and a + shift < hi]
        for name, a, b in inside:
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
        busy.append(union((a, b) for _, a, b in inside))
    busy_s = sum(b - a for iv in busy for a, b in iv) * 1e-9 / len(busy)
    starts = [s for _, s, _ in spans]
    edges = [lo] + [x for a, b in busy[0] for x in (a, b)] + [hi]
    gaps = [(_cover(spans, starts, a, b), (b - a) * 1e-9)
            for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_s, op_s=op_s,
                   offset_ns=offset, gaps=gaps)


def _cover(spans, starts, a: float, b: float) -> str:
    """The span overlapping [a, b] most; spans follow one another, so
    only those from the one open at ``a`` on can overlap."""
    best, best_len = "untraced", 0.0
    for name, s, e in spans[max(bisect.bisect_right(starts, a) - 1, 0):]:
        if s >= b:
            break
        overlap = min(e, b) - max(s, a)
        if overlap > best_len:
            best, best_len = name, overlap
    return best
