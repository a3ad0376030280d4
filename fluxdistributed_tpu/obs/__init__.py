"""Unified observability layer (ROADMAP: the instrumentation substrate
every perf PR reports through).

One registry, four producers, three consumers:

* :mod:`.metrics` — process-wide Counter/Gauge/Histogram registry with
  Prometheus text exposition and a JSONL snapshot sink;
* :mod:`.spans` — the step timeline: one process-wide, always-on,
  bounded span tracer (every loader item's wait, dispatch and completion
  under one id, also as ``jax.profiler`` annotations), exporting
  Chrome/Perfetto trace-event JSON;
* :mod:`.jaxmon` — ``jax.monitoring`` listeners: compile counts/seconds
  and steady-state recompile flagging;
* :mod:`.watchdog` — rolling-median heartbeat stall detection (+ the
  OOM-skip counter and the HBM low-headroom alert);
* :mod:`.memstats` — static per-program memory model
  (``memory_analysis`` through the compat shim) + live per-device HBM
  gauges (``fdtpu_hbm_*``, None-safe on CPU);
* :mod:`.comms` — the collective-traffic ledger (jaxpr + compiled-HLO
  collective counts/bytes per step per mesh axis);
* :mod:`.server` — stdlib-HTTP ``/metrics`` + ``/healthz`` (the
  training-side analog of the LM server's endpoints);
* :mod:`.flight` — the black-box flight recorder: a bounded ring of
  per-step records flushed append-only with atomic checkpoints, so a
  SIGKILL loses at most one flush interval of history;
* :mod:`.runs` — the cross-run ledger (``runs.jsonl``): one record per
  run/round/episode keyed by topology fingerprint, with regression
  gating and the merged postmortem (``bin/trends.py``).

:class:`Observation` bundles the per-run pieces for the trainer:
``train(task, observation=Observation.full(trace_path="run.trace.json"))``
adds a stall watchdog and a trace file; the default (``None``) already
feeds step counters, phase histograms and compile counts into the
process registry and every loader item's spans into the process tracer
(:func:`get_tracer`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from . import comms, jaxmon, memstats, runs
from .flight import FlightRecorder, read_flight
from .memstats import HbmGauges
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    Registry,
    bucket_percentile,
    get_registry,
)
from .profile import Profile, ProfileMismatch, collect_profile
from .reqtrace import RequestTracer
from .server import MetricsServer, start_metrics_server
from .spans import (
    CompletionWatcher,
    SpanTracer,
    current_span,
    get_tracer,
    innermost_active,
)
from .watchdog import StepWatchdog

__all__ = [
    "CompletionWatcher",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "HbmGauges",
    "Histogram",
    "JsonlSink",
    "MetricsServer",
    "Observation",
    "Profile",
    "ProfileMismatch",
    "Registry",
    "RequestTracer",
    "SpanTracer",
    "StepWatchdog",
    "bucket_percentile",
    "collect_profile",
    "comms",
    "current_span",
    "get_registry",
    "get_tracer",
    "innermost_active",
    "jaxmon",
    "memstats",
    "read_flight",
    "runs",
    "start_metrics_server",
]


@dataclasses.dataclass
class Observation:
    """What the training loop should instrument, bundled.

    Attributes
    ----------
    registry: where counters/histograms live (default: process registry)
    watchdog: stall watchdog, or None; ``train`` starts/stops it
    trace_path: write the process tracer's ring (:func:`get_tracer`) as
        Chrome trace JSON here when training ends.  The spans are
        recorded either way; this only exports them.
    steady_after: after this many loader items, declare
        :func:`jaxmon.mark_steady` — any later XLA compile is flagged as
        a steady-state recompile.  None (default) = never; eval or
        remainder batches legitimately compile late in short runs.
    """

    registry: Registry = dataclasses.field(default_factory=get_registry)
    watchdog: Optional[StepWatchdog] = None
    trace_path: Optional[str] = None
    steady_after: Optional[int] = None
    # append a registry snapshot line here at the print cadence and at
    # exit (offline run diffing — no Prometheus server required)
    jsonl_path: Optional[str] = None
    # write a versioned cost-profile artifact (obs.profile.Profile:
    # static per-layer/step costs + the run's measured phase data) here
    # when training ends — the planner-facing output of a profiled run
    profile_path: Optional[str] = None
    # black-box flight recorder: either pass a live FlightRecorder
    # (``flight``) or a path (``flight_path``) and ``train`` constructs
    # one; the dump survives any exit including SIGKILL (minus at most
    # one flush interval)
    flight: Optional[FlightRecorder] = None
    flight_path: Optional[str] = None

    @classmethod
    def default(cls) -> "Observation":
        """Counters and phase histograms in the process registry, spans
        in the process tracer; no watchdog thread, no files."""
        return cls()

    @classmethod
    def full(
        cls,
        trace_path: Optional[str] = None,
        registry: Optional[Registry] = None,
        watchdog_factor: float = 5.0,
        steady_after: Optional[int] = None,
        jsonl_path: Optional[str] = None,
        profile_path: Optional[str] = None,
        flight_path: Optional[str] = None,
    ) -> "Observation":
        """The default plus a stall watchdog and the files asked for.
        The loop observed is the loop every user runs."""
        registry = registry or get_registry()
        return cls(
            registry=registry,
            watchdog=StepWatchdog(factor=watchdog_factor, registry=registry),
            trace_path=trace_path,
            steady_after=steady_after,
            jsonl_path=jsonl_path,
            profile_path=profile_path,
            flight_path=flight_path,
        )
