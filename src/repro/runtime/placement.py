"""Placement policy: the device-free part of the coded executor.

``CodedExecutor`` runs a product over device groups, group ``c`` one
device and workers ``c*per .. c*per + per - 1``.  Where the decode
rows are not known at launch (``done=None`` on several devices), every
free device is launched on; one still busy with the plan's earlier
product is skipped and its workers count as stragglers (so a persistent
straggler's queue stays at one product), unless fewer free devices
than a decode needs are left or it frees up while the harvest waits.
The harvest polls readiness until enough devices have finished for
``k`` workers; the first to finish decodes.  Plain Python over device
indices and a readiness callable, tested with scripted readiness.
"""

from __future__ import annotations

from collections.abc import Callable, Sequence
from typing import NamedTuple

import numpy as np

# calls kept in ``CodedExecutor.records``
RECORDS = 1 << 16


def workers_per_device(n: int, devices: int) -> int:
    """Workers each device holds; ``n`` must divide evenly."""
    if devices < 1 or n % devices:
        raise ValueError(f"n={n} workers do not divide evenly over "
                         f"{devices} devices")
    return n // devices


def choose_launches(busy: Sequence[bool], need: int,
                    launched_at: Sequence[int]) -> tuple[list[int], list[int]]:
    """(devices to launch on, devices skipped as busy).

    Every free device is launched on.  A busy device is launched on
    only while fewer than ``need`` are free, the one whose pending
    launch is oldest (``launched_at``, a call count) first.
    """
    free = [c for c, b in enumerate(busy) if not b]
    waiting = sorted((c for c, b in enumerate(busy) if b),
                     key=lambda c: launched_at[c])
    extra = waiting[: max(need - len(free), 0)]
    return sorted(free + extra), sorted(waiting[len(extra):])


def harvest(launched: Sequence[int], ready: Callable[[int], bool],
            count: int, waiting: Sequence[int] = (),
            launch: Callable[[int], None] | None = None
            ) -> tuple[list[int], list[int], int]:
    """(the first ``count`` devices to be ``ready``, in the order they
    were seen ready; the devices of ``waiting`` that joined; the sweeps
    that took).

    Each sweep asks every pending device once, in order.  A device of
    ``waiting`` (busy with earlier work, so not launched on) that
    ``ready`` finds free while the harvest still waits is launched on
    with ``launch`` and joins it: a device that frees up is used, and
    none is ever given a second product to queue.
    """
    if count > len(launched) + len(waiting):
        raise ValueError(f"need {count} devices, have "
                         f"{len(launched) + len(waiting)}")
    pending, waiting = list(launched), list(waiting)
    order, joined, sweeps = [], [], 0
    while True:
        sweeps += 1
        for c in list(pending):
            if ready(c):
                order.append(c)
                pending.remove(c)
                if len(order) == count:
                    return order, joined, sweeps
        for c in list(waiting):
            if ready(c):
                launch(c)
                waiting.remove(c)
                joined.append(c)
                pending.append(c)


def device_mask(devices: Sequence[int], n: int, per: int) -> np.ndarray:
    """The worker-level done mask of the given devices' workers."""
    mask = np.zeros(n, bool)
    for c in devices:
        mask[c * per:(c + 1) * per] = True
    return mask


class CallRecord(NamedTuple):
    """What one MV product on the kernel path did."""

    launched: tuple[int, ...]       # devices launched on
    skipped_busy: tuple[int, ...]   # devices skipped: still busy
    rows: tuple[int, ...]           # the workers whose results decoded
    decode_device: int              # index of the device that decoded
    harvest_s: float                # host seconds, last launch to the
                                    # k-th worker's result ready (0 where
                                    # the rows were known at launch)
