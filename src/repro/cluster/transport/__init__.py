"""Pluggable cluster transports: memory | pipe | tcp | shm.

One ``Transport`` interface (``base.Transport``: start / ship-shard /
submit / cancel / uniform result+heartbeat stream / close), four
implementations:

  * ``memory`` -- in-process serve threads (deterministic default; the
    old ``thread`` worker backend);
  * ``pipe``   -- spawned subprocesses over ``multiprocessing`` pipes
    (the old ``process`` backend, now heartbeat-capable);
  * ``tcp``    -- asyncio localhost sockets speaking length-prefixed
    frames of the versioned wire format, with a hello handshake (wire
    version + worker id) and sha256-verified shard shipping;
  * ``shm``    -- the pipe transport's control plane with payloads in
    ``multiprocessing.shared_memory`` segments (wire v6): shards land
    once, tasks ship segment references instead of bytes, results
    write into a per-round slab the coordinator decodes in place --
    the zero-copy path for co-located workers.

``make_transport(None, ...)`` resolves the default from the
``REPRO_CLUSTER_TRANSPORT`` env var (falling back to ``memory``), so a
deployment can flip the whole stack onto sockets without touching
call sites -- mirroring how ``REPRO_CODED_BACKEND`` picks the compute
backend.
"""

from __future__ import annotations

import os

from .base import Transport, worker_env  # noqa: F401
from .memory import MemoryTransport
from .pipe import PipeTransport
from .shm import ShmTransport
from .tcp import TcpTransport

TRANSPORTS: dict[str, type] = {
    "memory": MemoryTransport,
    "pipe": PipeTransport,
    "tcp": TcpTransport,
    "shm": ShmTransport,
}

# legacy worker-backend names (PR 3's ClusterPlan(backend=...))
_ALIASES = {"thread": "memory", "process": "pipe"}

ENV_TRANSPORT = "REPRO_CLUSTER_TRANSPORT"


def resolve_transport(name: str | None) -> str:
    """Explicit name > ``REPRO_CLUSTER_TRANSPORT`` env var > ``memory``."""
    name = name or os.environ.get(ENV_TRANSPORT) or "memory"
    name = _ALIASES.get(name, name)
    if name not in TRANSPORTS:
        raise ValueError(f"cluster transport must be one of "
                         f"{sorted(TRANSPORTS)}, got {name!r}")
    return name


def make_transport(name: str | None, n_workers: int, **kw) -> Transport:
    return TRANSPORTS[resolve_transport(name)](n_workers, **kw)
