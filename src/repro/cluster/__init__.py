"""Cluster runtime: shared-worker fleet sessions over pluggable
transports, measuring real straggler mitigation.

The simulator (`repro.core.straggler`) predicts coded-job wall-clock;
this package *produces* it.  ``compile_plan(...).to_cluster()`` turns a
precompiled ``CodedPlan`` into a ``ClusterPlan`` with the same
``matvec / matmat / aggregate`` surface, backed by real workers:

  * ``wire``       -- versioned plan / shard / task / result / heartbeat
    serialization (dtype-faithful, pickle-free), with support-restricted
    task payloads so per-task traffic is omega/k-proportional;
  * ``worker``     -- the transport-agnostic worker core: one serve loop
    + heartbeat ticker shared by every transport, BSR compute at
    nnz-proportional cost;
  * ``transport``  -- the pluggable byte carriers: ``memory`` (in-process
    threads), ``pipe`` (spawned subprocesses), ``tcp`` (asyncio localhost
    sockets with a version/digest handshake); pick via
    ``to_cluster(transport=...)``, ``CodedConfig.transport``, or the
    ``REPRO_CLUSTER_TRANSPORT`` env var;
  * ``fleet``      -- the session spine: ``CodedFleet`` owns one persistent
    worker set + one long-lived dispatcher loop; ``attach(plan)`` ships
    shards once and returns a ``PlanHandle`` whose ``submit_*`` calls
    return ``CodedFuture``s -- multiple rounds in flight, queued
    matvecs microbatched into wider rounds, heartbeat-derived liveness
    (missed beats => suspected => shard re-ship + requeue across every
    live round), partial-straggler credit, deadlines;
  * ``dispatcher`` -- ``ClusterPlan``, the blocking back-compat shim: a
    private single-plan fleet with ``max_inflight=1``;
  * ``faults``     -- deterministic latency / death / hang injection as a
    decorator around any transport's serve path (it *causes* behaviour
    the protocol then *measures*; liveness never reads it), including
    wall-clock-scripted fault windows (``ScriptedFaults``);
  * ``chaos``      -- the deterministic chaos harness: seeded fault
    schedules (kill / hang / slow / partition / garble / leave / join /
    reconnect) driven against a live fleet with bitwise-parity and
    no-hang assertions (``run_chaos``);
  * ``retry``      -- ``RetryPolicy``: bounded exponential backoff with
    deterministic jitter, shared by worker dialing and transport ops.

The fleet is *elastic* (wire v4): ``fleet.add_worker()`` admits a
device into the running session (shard catch-up + welcome),
``fleet.remove_worker()`` drains before removing, and worker loss
degrades gracefully -- shards re-home, plans re-encode at reduced
resilience (``k`` preserved), and below ``min_workers`` futures fail
fast with a structured ``FleetDegraded`` instead of hanging.

``python benchmarks/run.py --only cluster`` runs the paper-shaped
experiment over this stack and writes ``BENCH_cluster.json`` --
including measured bytes-on-wire per scheme.
"""

from .chaos import (  # noqa: F401
    ChaosEvent,
    ChaosResult,
    max_concurrent_failures,
    run_chaos,
    scripted_schedule,
)
from .dispatcher import ClusterPlan, ClusterReport  # noqa: F401
from .fleet import (  # noqa: F401
    CodedFleet,
    CodedFuture,
    FleetDegraded,
    PlanHandle,
    default_max_inflight,
    default_min_workers,
)
from .faults import (  # noqa: F401
    FailStop,
    Hang,
    NoFaults,
    ScriptedFaults,
    StragglerFaults,
    WorkerFailure,
    WorkerHang,
    adversarial_faults,
    faulty,
    straggler_mask,
)
from .retry import RetryPolicy  # noqa: F401
from .transport import (  # noqa: F401
    TRANSPORTS,
    Transport,
    make_transport,
    resolve_transport,
    worker_env,
)
from .wire import (  # noqa: F401
    Heartbeat,
    PlanShard,
    Task,
    TaskResult,
    WorkerJoin,
    WorkerLeave,
    dumps_plan,
    loads_plan,
    shard_plan,
)
from .worker import ShardRuntime, serve_loop, start_heartbeat  # noqa: F401
