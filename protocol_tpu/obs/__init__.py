"""Node-wide observability: trace spans, metrics, exporters, the
flight recorder, and runtime invariant watchers.

The node's one instrumentation layer, threaded through ingest,
convergence, proving, checkpointing, and serving.  Five pieces:

- :mod:`~protocol_tpu.obs.trace` — hierarchical spans (context
  managers, monotonic timing, contextvar nesting) collected into a
  per-epoch span tree the node serves as ``GET /trace/<epoch>``;
  ``Tracer.attach_closed`` bridges out-of-band attributions (the
  native prover's phase-timer table) into the same tree;
- :mod:`~protocol_tpu.obs.metrics` — a thread-safe registry of
  counters/gauges/histograms (ingest accept/reject by reason,
  sig-verify throughput, iterations-to-convergence, the per-iteration
  residual trajectory, dropped epoch ticks, checkpoint and
  window-plan events, jit recompiles, score drift, journal volume)
  served as ``GET /metrics``;
- :mod:`~protocol_tpu.obs.export` — Prometheus text + JSON renderers
  and the opt-in ``jax.profiler`` session hook;
- :mod:`~protocol_tpu.obs.journal` — the flight recorder: a bounded
  JSONL event journal (ring + batched writer) every closed span,
  ingest rejection, plan outcome, coalesced tick, and anomaly writes
  through; served as ``GET /debug/flight``, dumped on crash/SIGTERM;
- :mod:`~protocol_tpu.obs.watchers` — runtime invariant watchers:
  jit recompile tracking around the converge entry points, per-span
  device-memory watermarks, and the score-integrity/drift monitor
  behind ``GET /scores/drift``;
- :mod:`~protocol_tpu.obs.lineage` — attestation lineage sampling: a
  configurable fraction of submissions carry a flat int lineage ID
  through intake → admission → verify → apply → included-in-epoch →
  converged → proof-landed, feeding the per-stage
  ``eigentrust_freshness_seconds`` histograms (end-to-end freshness);
- :mod:`~protocol_tpu.obs.timeline` — the epoch timeline registry:
  one joined record per epoch (ingest watermarks, phase durations,
  proof lifecycle, freshness) served as ``GET /timeline/<epoch>``;
- :mod:`~protocol_tpu.obs.fleet` — cross-process metric aggregation:
  worker registries shipped back across the spawn boundary and
  multi-process snapshot exchange, merged into one ``process``-labeled
  exposition at ``GET /metrics/fleet``;
- :mod:`~protocol_tpu.obs.slo` — the declarative SLO engine behind
  ``GET /slo``: objectives over the registry (freshness p99, proof-lag
  p99, epoch cadence, shed rate, residual stalls) with burn-rate
  state, journaled transitions, and CI enforcement.

Doctrine (enforced by graftlint passes 3 and 5,
``analysis/ast_rules.py``): spans, metrics, and journal writes live
at *host boundaries only*.  Nothing here may be called from inside a
jit-traced function, and the per-iteration residual trajectory is
captured device-side in the ``lax.while_loop`` carry
(``ops.sparse.run_power_iteration``) and fetched ONCE after
convergence — the hot loop never syncs, logs, or reads a clock.

This package imports only the standard library at import time (the
watchers reach jax lazily, inside method calls), so instrumenting a
module costs nothing at import time.
"""

from __future__ import annotations

import time as _time

from . import metrics as _metrics
from .export import metrics_json, profile_session, prometheus_text
from .fleet import FLEET, FleetAggregator, fleet_prometheus_text, registry_snapshot
from .journal import JOURNAL, FlightRecorder
from .lineage import LINEAGE, LineageTracker
from .metrics import METRICS, MetricsRegistry
from .podtrace import (
    POD_TRACES,
    PodTraceStore,
    publish_epoch_trace,
    stitch_epoch,
)
from .slo import (
    SLO_ENGINE,
    SLOEngine,
    SLObjective,
    install_pod_defaults,
    pod_objectives,
)
from .timeline import TIMELINE, TimelineRegistry
from .trace import (
    TRACER,
    Span,
    SpanContextFilter,
    Tracer,
    configure_logging,
)
from .watchers import (
    DRIFT,
    MEMORY_WATERMARKS,
    RECOMPILES,
    STRAGGLERS,
    MemoryWatermarkWatcher,
    RecompileTracker,
    ScoreDriftMonitor,
    StragglerWatcher,
)


def _span_closed(span: Span) -> None:
    # Memory watermark first so the delta lands in the span's attrs
    # before the event is journaled.
    MEMORY_WATERMARKS.on_close(span)
    # Every closed span feeds the phase-seconds histogram, so span
    # timings (plan, converge, prove, checkpoint, sig_verify, ...) are
    # scrapeable without separate timer plumbing at each site.
    _metrics.PHASE_SECONDS.observe(span.duration_s or 0.0, phase=span.name)
    # An epoch root closing is the timeline's phase-join moment: the
    # tick wall-clock and the per-phase durations land on the epoch's
    # record in one write (children with repeated names last-win —
    # the phases here mirror /trace exactly).
    if span.name == "epoch_tick" and "epoch" in span.attrs:
        TIMELINE.record(
            span.attrs["epoch"],
            tick_seconds=round(span.duration_s or 0.0, 6),
            tick_ended_unix=round(_time.time(), 3),
            phases={
                c.name: round(c.duration_s or 0.0, 6)
                for c in span.children
                if c.duration_s is not None
            },
            error=bool(span.attrs.get("error", False)),
        )
    # ... and the flight recorder, so a post-mortem replays the span
    # sequence without the trace ring having kept the epoch.
    fields = {"name": span.name, "duration_s": round(span.duration_s or 0.0, 6)}
    for k, v in span.attrs.items():
        if k not in fields and k not in ("ts", "seq", "kind") and isinstance(
            v, (str, int, float, bool)
        ):
            fields[k] = v
    JOURNAL.record("span", **fields)


TRACER.on_span_close = _span_closed
TRACER.on_span_open = MEMORY_WATERMARKS.on_open

__all__ = [
    "DRIFT",
    "FLEET",
    "JOURNAL",
    "LINEAGE",
    "METRICS",
    "MEMORY_WATERMARKS",
    "POD_TRACES",
    "RECOMPILES",
    "SLO_ENGINE",
    "STRAGGLERS",
    "TIMELINE",
    "FleetAggregator",
    "FlightRecorder",
    "LineageTracker",
    "MemoryWatermarkWatcher",
    "MetricsRegistry",
    "PodTraceStore",
    "RecompileTracker",
    "SLOEngine",
    "SLObjective",
    "ScoreDriftMonitor",
    "Span",
    "SpanContextFilter",
    "StragglerWatcher",
    "TRACER",
    "TimelineRegistry",
    "Tracer",
    "configure_logging",
    "fleet_prometheus_text",
    "install_pod_defaults",
    "metrics_json",
    "pod_objectives",
    "profile_session",
    "prometheus_text",
    "publish_epoch_trace",
    "registry_snapshot",
    "stitch_epoch",
]
