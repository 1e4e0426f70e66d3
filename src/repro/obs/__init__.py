"""``repro.obs`` -- end-to-end round tracing and straggler attribution.

The paper's claim is about *time*: straggler-optimal wall-clock under
sparsity-preserving encodings.  ``fleet.metrics()`` (PR 7) summarizes
it with EWMAs; this package shows where each round's milliseconds
actually go and which device straggled in which phase.

- ``trace``  -- ``Tracer``: spans/events into a bounded monotonic-clock
  ring buffer; near-zero cost when disabled (a ``None`` check on the
  hot path).  Enable with ``REPRO_TRACE=1`` or pass
  ``CodedFleet(tracer=)`` / ``Router(tracer=)`` explicitly.  Its
  spans are also ``jax.profiler`` host events, so a profiler trace
  shows them on the device trace's clock; ``PROGRAM_SPANS`` names the
  ones the coded plan path records (``compile_plan``, ``CodedPlan``,
  ``CodedExecutor``).  Named counters (``count``, ``counters``) include
  ``jit.lowerings``, every program JAX lowers in the process.
- ``export`` -- Chrome trace-event JSON (Perfetto-loadable) and
  Prometheus text exposition of the fleet/router counters.
- ``attrib`` -- straggler attribution: per-worker per-round latency
  breakdown (queue / wire / worker-queue / compute / decode), which
  rounds decoded *without* which workers, wasted work from cancelled
  and late tasks, and measured compute rates that feed
  ``fleet.worker_capacities(rates=...)``.

``python -m repro.obs`` runs a small traced demo round and writes both
export formats.
"""

from .attrib import Attribution, RoundBreakdown, WorkerStats, attribute
from .export import chrome_trace, prometheus_text, write_chrome_trace
from .trace import (DEFAULT_BUF, ENV_TRACE, ENV_TRACE_BUF, LOWERINGS,
                    PROGRAM_SPANS, Tracer, default_tracer, optional_span,
                    traced_call)

__all__ = [
    "Attribution",
    "DEFAULT_BUF",
    "ENV_TRACE",
    "ENV_TRACE_BUF",
    "LOWERINGS",
    "PROGRAM_SPANS",
    "RoundBreakdown",
    "Tracer",
    "WorkerStats",
    "attribute",
    "chrome_trace",
    "default_tracer",
    "optional_span",
    "prometheus_text",
    "traced_call",
    "write_chrome_trace",
]
