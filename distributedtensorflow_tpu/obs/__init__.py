"""Unified telemetry: metrics registry, span tracing, cross-host
aggregation, anomaly detection.

The reference harness's observability floor is ``tf.summary`` scalars plus
chief-only logging (``utils.metrics.MetricWriter`` keeps that floor: the
same event files, written without TensorFlow); this subsystem answers the
questions that floor cannot:
*where did the step time go* (span tracing → per-step breakdown), *which
host is slow* (cross-host gauge aggregation), *is the run healthy*
(streaming anomaly detection), and *what is every layer doing* (the
process-local registry any module writes to without plumbing a writer).

Surfaces:

- ``counter/gauge/histogram`` — process-local registry metrics, exported
  into ``metrics.jsonl`` rows and a Prometheus text snapshot
  (``metrics.prom``);
- ``span("name")`` — wall-time tree tracing into ``trace.jsonl`` plus the
  per-step breakdown fields (``t_data``/``t_step``/``f_data``/...);
- ``host_aggregate`` — per-host gauge allgather → min/median/max/straggler;
- ``AnomalyDetector`` — NaN/Inf loss, loss z-spike, step-time regression,
  raising through the Watchdog-style callback convention;
- ``FlightRecorder`` — bounded ring of structured events, dumped to
  ``flight.jsonl`` on watchdog timeout / crash / anomaly / preemption so a
  dying job always leaves a last-minutes forensic record;
- ``StatusServer`` — per-host stdlib HTTP thread serving ``/healthz``,
  ``/statusz``, ``/varz``, ``/threadz``, ``/memz``, ``/flightz`` — the
  live half: point ``curl`` at a run while it is wedged;
- ``memory`` — per-device HBM, host RSS, and ``jax.live_arrays()`` census
  feeding the registry, the per-step record, and ``/memz``;
- ``GoodputLedger`` — end-to-end wall-time accounting into exclusive
  buckets (init/compile/train/data/checkpoint/eval/lost-work/...),
  persisted to ``goodput.json`` and merged across restarts — the
  cost-of-training verdict (``goodput_fraction``, ``/goodputz``);
- ``CaptureEngine`` — reactive profiling: anomaly-/straggler-triggered
  and on-demand (``POST /profilez``) ``jax.profiler`` windows with a
  per-run budget, a ``captures.jsonl`` manifest, and
  ``capture_begin``/``capture_end`` flight events — the layer that turns
  the telemetry above into an actionable debugging loop;
- ``FleetAggregator`` — the fleet observability plane: a chief-side
  scraper over peer StatusServers' ``/varz`` (trainer hosts, data-service
  workers, the serve server, coordinator subprocess workers) merging
  samples into one min/median/max/sum view with per-peer up/stale/down
  liveness and ``spread_ratio`` straggler detection, served at
  ``/fleetz`` and persisted to ``fleet.json``;
- ``SLOMonitor`` — declarative SLO rules (JSON) evaluated over registry
  histograms/counters as multi-window burn rates
  (``slo_burn_rate{slo=,window=}``), raising ``slo_violation`` flight
  events, serving ``/sloz``, and optionally arming the CaptureEngine on
  a fast-burn trip;
- ``AlertManager`` — declarative alert rules (JSON) over registry
  scalars, history series, and fleet-merged samples — ``threshold`` /
  ``burn`` / ``absence`` / ``anomaly`` kinds, edge-triggered with
  cooldowns, dedup, and silences — fanning out to log/webhook/capture
  sinks, appending ``alerts.jsonl``, snapshotting per-firing incident
  evidence bundles (``incidents/<id>/``), and serving ``GET /alertz``;
  ``obs.alerts.recompute_from_history`` replays the rules offline;
- ``DynamicsMonitor`` — training-dynamics observability (``obs.dynamics``):
  in-graph per-module grad/param/update statistics on a ``lax.cond``
  cadence riding the train step's metrics, flushed at log boundaries
  into ``dynamics.jsonl`` + the ``dynamics_*`` registry families +
  ``GET /dynamicz``, with a NaN-provenance pass (activation taps,
  parameter census, gradient binary search) that names the first
  module to go non-finite as a ``nan_provenance`` flight event and
  incident bundle;
- ``MetricsHistory`` — the embedded metrics history store (``obs.tsdb``):
  fixed-memory downsampling rings over registry samples (plus fleet
  merges and per-SLO good/total snapshots when attached), answering
  windowed queries at ``GET /histz`` and persisting ``history.jsonl``
  ticks that ``obs.slo.recompute_from_history`` replays into offline
  burn rates;
- ``remote_span`` / ``record_remote_span`` — cross-process request
  tracing: a trace context (trace_id, parent span_id) propagated over
  RPC frames so spans in every process's ``trace.jsonl`` stitch into one
  timeline (``tools/timeline.py --fleet``);
- ``tools/run_report.py`` — renders a logdir's streams into one
  human-readable run report; ``tools/timeline.py`` merges them into a
  single Chrome-trace/Perfetto timeline (restarts included).
"""

from . import alerts, capture, dynamics, fleet, flight_recorder, goodput, memory, slo, tsdb  # noqa: F401
from .alerts import AlertManager, AlertRule  # noqa: F401
from .aggregate import (  # noqa: F401
    host_aggregate,
    spread_ratio,
    straggler_summary,
)
from .anomaly import Anomaly, AnomalyDetector  # noqa: F401
from .capture import CaptureEngine  # noqa: F401
from .fleet import FleetAggregator  # noqa: F401
from .flight_recorder import (  # noqa: F401
    FlightRecorder,
    default_recorder,
    install_recorder,
    record_event,
)
from .goodput import GoodputLedger  # noqa: F401
from .mfu import mfu_record_fields, peak_flops  # noqa: F401
from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    default_registry,
    gauge,
    histogram,
    set_default_registry,
)
from .server import StatusServer  # noqa: F401
from .slo import SLOMonitor, SLORule  # noqa: F401
from .tsdb import MetricsHistory  # noqa: F401
from .tracing import (  # noqa: F401
    PhaseTrace,
    Span,
    TraceRecorder,
    active_recorder,
    current_context,
    install_compile_log,
    new_trace_id,
    record_remote_span,
    remote_span,
    span,
    take_compiled,
)
