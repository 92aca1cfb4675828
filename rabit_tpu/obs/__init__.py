"""rabit_tpu.obs — the telemetry subsystem.

The pieces (doc/observability.md):

* :mod:`rabit_tpu.obs.metrics` — counters, gauges and log2-bucket
  latency histograms behind a thread-safe :class:`Metrics` registry;
* :mod:`rabit_tpu.obs.trace` — a bounded ring-buffer
  :class:`EventTrace` of structured events (op spans, link errors,
  recovery phases, checkpoint commits) dumpable as JSON lines and
  Chrome-trace format;
* :mod:`rabit_tpu.obs.log` — the rank/role/seqno-prefixed structured
  logger (``rabit_debug``-gated);
* :mod:`rabit_tpu.obs.export` — the **live telemetry plane**: delta
  frame export over the heartbeat channel, the tracker's per-job fold,
  and the Prometheus text exposition for ``GET /metrics``;
* :mod:`rabit_tpu.obs.span` — cross-rank collective spans, per-op skew
  merging and rolling straggler scores (doc/observability.md "Live
  telemetry");
* :mod:`rabit_tpu.obs.program` — **program spans and counters**: the
  device-plane path's layer boundaries timed where the work happens
  (always-on table = ``Engine.path_stats``; the profiler's trace while
  a session records; events + histograms when telemetry is on —
  doc/observability.md "Program spans");
* :mod:`rabit_tpu.obs.adapt` — the **adaptive controller** closing the
  loop: live span folds re-score the schedule choice online, push
  schedule-switch epochs, demote persistent stragglers out of
  hierarchical leadership and warm the TuningCache
  (doc/performance.md "Online adaptation").

Engines expose their instruments through ``Engine.stats()`` /
``Engine.events()``; at shutdown each worker ships its rank-local
summary over the tracker's print channel (:data:`OBS_SUMMARY_PREFIX`)
and the tracker aggregates min/mean/max across ranks into a per-job
report under ``--obs-dir`` (rendered by ``tools/obs_report.py``).

Telemetry is **off by default**: :func:`configure` enables it when
``rabit_obs`` is truthy or ``rabit_obs_dir`` is set, and the engines
gate every call site on that single bool, so the disabled cost is one
attribute check per collective.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

from rabit_tpu.obs import log
from rabit_tpu.obs.adapt import (AdaptiveController, Decision,
                                 ScheduleScorer, candidate_schedules)
from rabit_tpu.obs.export import (DeltaExporter, LiveTable, prom_name,
                                  prometheus_text, serve_slo,
                                  serve_straggler_scores)
from rabit_tpu.obs.log import _truthy
from rabit_tpu.obs.metrics import (Counter, Gauge, Histogram, Metrics,
                                   aggregate_snapshots, flatten_snapshot)
from rabit_tpu.obs.span import (SpanBuffer, SpanMerger, merge_group,
                                payload_bucket)
from rabit_tpu.obs.trace import (DEFAULT_FLIGHT_EVENTS,
                                 DEFAULT_TRACE_SAMPLE, HOP_FIELDS,
                                 EventTrace, FlightRecorder, HopBuffer,
                                 TraceAssembler, chrome_trace,
                                 load_flight_records, trace_sampled)

# Print-channel extension marker: a tracker print message starting with
# this is a rank-local telemetry summary (JSON), ingested by the tracker
# instead of echoed (tracker/tracker.py).
OBS_SUMMARY_PREFIX = "\x01rabit-obs1\x01"

DEFAULT_TRACE_CAPACITY = 4096
# Streaming export cadence (rabit_obs_flush_sec): how often a worker
# ships one delta frame + its buffered spans over the heartbeat channel
# while telemetry is on.  0 disables streaming (shutdown-only shipping,
# the PR-2 behaviour).
DEFAULT_FLUSH_SEC = 2.0


@dataclass
class ObsConfig:
    """Resolved telemetry settings for one engine instance."""

    enabled: bool = False
    obs_dir: str | None = None
    trace_capacity: int = DEFAULT_TRACE_CAPACITY
    flush_sec: float = DEFAULT_FLUSH_SEC
    # Causal hop tracing (rabit_trace_sample): trace every Nth op; 0 =
    # off — and the engines keep the entire arm/emit path behind one
    # attribute check, so the disabled cost is zero on the hot path.
    trace_sample: int = 0
    # Flight recorder: ring capacity and the persistence directory
    # (records only land on disk when rabit_trace_dir is set).
    flight_events: int = DEFAULT_FLIGHT_EVENTS
    trace_dir: str | None = None


def configure(params: dict | None = None) -> ObsConfig:
    """Resolve telemetry settings from engine params / environment and
    apply the log level (``rabit_debug``).  Called from every engine's
    ``init()``; see doc/parameters.md "Observability"."""
    params = params or {}
    log.configure(params)
    obs_dir = params.get("rabit_obs_dir") or os.environ.get("RABIT_OBS_DIR")
    obs_dir = str(obs_dir) if obs_dir else None
    raw = params.get("rabit_obs")
    if raw is None:
        raw = os.environ.get("RABIT_OBS", "")
    enabled = _truthy(raw) or obs_dir is not None
    cap = params.get("rabit_obs_events")
    if cap is None:
        cap = os.environ.get("RABIT_OBS_EVENTS", DEFAULT_TRACE_CAPACITY)
    try:
        cap = int(cap)
    except (TypeError, ValueError):
        cap = DEFAULT_TRACE_CAPACITY
    flush = params.get("rabit_obs_flush_sec")
    if flush is None:
        flush = os.environ.get("RABIT_OBS_FLUSH_SEC", DEFAULT_FLUSH_SEC)
    try:
        flush = max(float(flush), 0.0)
    except (TypeError, ValueError):
        flush = DEFAULT_FLUSH_SEC
    sample = params.get("rabit_trace_sample")
    if sample is None:
        sample = os.environ.get("RABIT_TRACE_SAMPLE", 0)
    try:
        sample = max(int(sample), 0)
    except (TypeError, ValueError):
        sample = 0
    flight = params.get("rabit_flight_events")
    if flight is None:
        flight = os.environ.get("RABIT_FLIGHT_EVENTS",
                                DEFAULT_FLIGHT_EVENTS)
    try:
        flight = max(int(flight), 8)
    except (TypeError, ValueError):
        flight = DEFAULT_FLIGHT_EVENTS
    trace_dir = (params.get("rabit_trace_dir")
                 or os.environ.get("RABIT_TRACE_DIR"))
    trace_dir = str(trace_dir) if trace_dir else None
    return ObsConfig(enabled=enabled, obs_dir=obs_dir, trace_capacity=cap,
                     flush_sec=flush, trace_sample=sample,
                     flight_events=flight, trace_dir=trace_dir)


def record_op(metrics: Metrics, trace: EventTrace, kind: str, nbytes: int,
              dt: float, rank: int, seqno: int | None = None,
              version: int | None = None, replayed: bool = False) -> None:
    """Record one completed collective — the per-op metric/event scheme
    shared by every instrumented engine (doc/observability.md), so the
    emitted names can never drift between backends."""
    metrics.counter(f"op.{kind}.count").inc()
    metrics.counter(f"op.{kind}.bytes").inc(nbytes)
    metrics.histogram(f"op.{kind}.seconds").observe(dt)
    if replayed:
        metrics.counter(f"op.{kind}.replayed").inc()
    trace.emit("op", kind=kind, nbytes=nbytes, dur=dt, seqno=seqno,
               version=version, rank=rank, replayed=replayed or None)


def ship_summary(print_fn, logger, engine_name: str, rank: int, world: int,
                 metrics_snapshot: dict, recovery_events: list[dict],
                 job: str | None = None) -> None:
    """Ship one rank-local summary over the tracker print channel
    (``print_fn`` is the engine's ``tracker_print``).  Shared by every
    instrumented engine; the tracker merges multiple summaries for the
    same rank section-wise, so a layered engine (XLA over a host inner)
    ships its own instruments without clobbering the inner's.  ``job``
    names the tenant on a multi-tenant tracker so merged reports stay
    attributable (None/"default" = the implicit single job)."""
    payload = {"rank": rank, "world": world, "engine": engine_name,
               "metrics": metrics_snapshot, "recovery": recovery_events}
    if job and job != "default":
        payload["job"] = job
    try:
        print_fn(OBS_SUMMARY_PREFIX + json.dumps(payload))
    except Exception as e:  # noqa: BLE001 — teardown path, best effort
        logger.debug("obs summary ship failed: %s", e)


def note_drops(metrics: Metrics, trace: EventTrace) -> None:
    """Sync the ``obs.events_dropped`` counter to the trace's eviction
    count — called at every streaming flush and at shutdown shipping,
    so silent ring-buffer eviction always surfaces in the shipped
    summaries (and the obs_report warning that renders it)."""
    dropped = trace.dropped
    c = metrics.counter("obs.events_dropped")
    behind = dropped - c.value
    if behind > 0:
        c.inc(behind)


def dump_events(logger, obs_dir: str, rank: int, events: list[dict]) -> None:
    """Write one rank's event trace to ``<obs_dir>/events.rank<N>.jsonl``
    (the format tools/obs_report.py consumes)."""
    try:
        os.makedirs(obs_dir, exist_ok=True)
        path = os.path.join(obs_dir, f"events.rank{rank}.jsonl")
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps(ev) + "\n")
    except OSError as e:
        logger.warn("obs event dump failed: %s", e)


__all__ = [
    "Counter", "Gauge", "Histogram", "Metrics", "EventTrace",
    "aggregate_snapshots", "flatten_snapshot", "chrome_trace",
    "ObsConfig", "configure", "log", "OBS_SUMMARY_PREFIX",
    "DEFAULT_TRACE_CAPACITY", "DEFAULT_FLUSH_SEC", "record_op",
    "ship_summary", "dump_events", "note_drops",
    "DeltaExporter", "LiveTable", "prom_name", "prometheus_text",
    "serve_slo",
    "serve_straggler_scores",
    "SpanBuffer", "SpanMerger", "merge_group", "payload_bucket",
    "AdaptiveController", "ScheduleScorer", "Decision",
    "candidate_schedules",
    "HOP_FIELDS", "DEFAULT_TRACE_SAMPLE", "DEFAULT_FLIGHT_EVENTS",
    "HopBuffer", "TraceAssembler", "FlightRecorder", "trace_sampled",
    "load_flight_records",
]
