#!/usr/bin/env python
"""Validate ``metrics.jsonl`` / ``flight.jsonl`` / ``goodput.json`` /
``captures.jsonl`` / ``faults.jsonl`` / ``requests.jsonl`` files against
the documented schemas.

Usage::

    python tools/check_metrics_schema.py                # all ARTIFACTS runs
    python tools/check_metrics_schema.py path/a.jsonl [path/b.jsonl ...]

Files whose basename starts with ``flight`` are validated against the
flight-recorder event schema; basenames starting with ``goodput`` against
the goodput-ledger document schema; basenames starting with ``captures``
against the reactive-profiler manifest schema; basenames starting with
``faults`` against the chaos fault-log schema; basenames starting with
``requests`` against the serving per-request log schema (ok rows also
carry the ISSUE-14 prefix-cache split when present:
``cached_prefix_tokens >= 0``, ``prefill_tokens >= 0``, the two summing
exactly to ``prompt_tokens``, plus a non-negative ``itl_max_s``, the
ISSUE-16 ``spec_drafted``/``spec_accepted`` mirror pair, and the
exclusive ``attr_*`` tail-latency components whose sum must stay within
5% of ``e2e_s``, and the ISSUE-19 identifier-style ``tenant`` identity);
basenames starting with ``steps`` against the engine
step-log schema (serve/engine.py: strictly-increasing ``step`` ids,
non-decreasing ``t``, known phase tokens, non-negative counts, phase
wall split tiling ``step_s``, plus — when present — the ISSUE-19
non-negative ``kv_blocks_billed`` census and an ``admitted_tenants``
breakdown summing to ``admitted``); basenames starting with ``trace`` and ending
``.jsonl`` against the span-trace schema (obs/tracing.py: step rows of
span trees, anomaly events, ``kind: "span"`` rows, and the ``startup.*``
phases sharing ``trace_id`` ``"startup"`` and tiling time in order);
basenames starting with ``history``
against the metrics-history tick schema (obs/tsdb.py: non-decreasing
``t``, well-formed metric names mapping to finite numbers, cardinality
bounded by :data:`HISTORY_MAX_SERIES`); basenames starting with
``alerts`` against the alert-stream schema (``obs/alerts.py``:
non-decreasing ``t``, known kinds/severities/phases, every ``resolved``
row pairing an earlier ``fired`` id of the same rule, and the dedup
invariant — never two open alerts per (rule, labels)); files named
``manifest.json`` under an ``incidents/`` directory against the
incident evidence-bundle manifest schema (required keys, known
severity/kind, every listed evidence file present in the bundle);
basenames
starting with ``flash_blocks`` against the flash-attention autotune cache
schema (ops/flash_tuning.py: version 1, entries with platform/dtype/
shape, blocks dividing seq, known sources); basenames starting with
``slo`` and ending ``.json`` against the SLO rule-file schema
(``obs/slo.py``: known rule kinds, objective in [0, 1), positive windows
with fast <= slow, positive burn-rate thresholds, unique names);
basenames starting with ``fleet`` and ending ``.json`` against the fleet
aggregator snapshot schema (``obs/fleet.py``: peer states from
:data:`FLEET_PEER_STATES`, non-negative counts/ages, a non-negative
``worst_spread`` ratio); basenames starting with ``timeline`` and ending
``.json`` against the Chrome-trace document shape (a ``traceEvents``
list of objects with a ``ph`` phase and finite ``ts``/non-negative
``dur`` where present — the fleet-mode stitcher's output rides the
default sweep); files ending in ``.prom`` against the Prometheus
exposition snapshot (well-formed samples;
``collective_dispatch_seconds`` ``op`` labels restricted to the known
collective set — see :data:`COLLECTIVE_OPS` — ``overlapped`` labels to
"0"/"1", the input-plane ``data_prefetch_depth`` /
``data_prefetch_resizes_total`` ``component``/``direction`` labels to
:data:`PREFETCH_COMPONENTS` / :data:`PREFETCH_DIRECTIONS`, the fleet
``fleet_peers`` ``state`` label to :data:`FLEET_PEER_STATES`, and
``slo_burn_rate`` samples to a known ``window`` label with a
non-negative value, the serving prefix-cache families
(``serve_prefix_*`` / ``serve_kv_*``) to non-negative values with the
ratio gauges in [0, 1], and the resilient-transport ``rpc_*`` /
``breaker_*`` families to known endpoint prefixes / retry outcomes /
breaker-state encodings); basenames starting with ``dispatcher`` and
ending ``.journal`` against the dispatcher durability-journal schema
(``data/service.py``: strictly-increasing ``seq``, known record kinds,
per-epoch monotonic generations, replay-safe ordering, a torn final
line tolerated); basenames starting with ``dynamics`` against the
training-dynamics cadence-row schema (``obs/dynamics.py``:
non-decreasing ``t``, a constant positive ``every`` dividing every
``step`` (step rewinds allowed — supervised restarts — but never two
rows for the same step in a row), per-module stats under identifier
module names with finite-or-sentinel values and non-negative integer
``nonfinite_grads`` counts consistent with ``nonfinite_total``);
everything else against the metric-row schema
(where ``quant_mode`` is the one string-typed field, from
:data:`QUANT_MODES`; the input-plane/fleet/slo label checks apply to the
jsonl-flattened field names too).

The metric schema (docs/API.md "Telemetry"): every row of a *training-run*
``metrics.jsonl`` is one JSON object with

- ``step``: a non-negative integer (integral floats accepted — JSON has one
  number type);
- every other entry: a finite number, or one of the non-finite sentinel
  strings ``"NaN"`` / ``"Infinity"`` / ``"-Infinity"`` the writer emits to
  keep lines strict JSON (reported as a warning, not an error — a NaN loss
  is exactly what the stream must be able to record), with a non-empty key
  free of control characters.

The flight schema (docs/API.md "Live introspection"): every event of a
``flight.jsonl`` dump is one JSON object with ``t`` (finite unix seconds),
``kind`` (non-empty string), optional ``step`` (non-negative integer), and
free-form event fields (JSON scalars; non-finite numbers use the same
sentinel strings); event timestamps must be non-decreasing (ring order).

The captures schema (docs/API.md "Reactive profiling"): every row of a
``captures.jsonl`` manifest is one JSON object with a non-negative
integer ``id`` (strictly increasing across the file), a ``trigger`` from
the known set (``static`` / ``manual`` / ``step_time_regression`` /
``straggler_spread`` / ``slo_burn``), integer ``step_begin < step_end``
(``<=`` allowed
for ``aborted`` rows), finite ``t_begin <= t_end``, non-negative
``wall_s`` / ``overhead_s``, and a ``dir`` that exists on disk (resolved
against the manifest's directory when relative).

The faults schema (docs/API.md "Self-healing & fault injection"): every
row of a ``faults.jsonl`` chaos log is one JSON object with finite
non-decreasing ``t``, non-negative integer ``id`` and ``step``, ``kind``
from the known fault set (``nan_loss`` / ``checkpoint_truncate`` /
``worker_kill`` / ``data_stall`` / ``preemption`` plus the
transport-recovered ``net_delay`` / ``net_drop`` / ``net_sever`` /
``dispatcher_kill``), and ``phase``
``injected`` or ``recovered``; injected ``id``s strictly increase with
non-decreasing ``step``s, every recovered row must reference an earlier
injected ``id`` of the same kind, and every injected fault must be paired
with a recovered row by end of file (an unpaired injection = the run did
not self-heal).

The goodput schema (docs/API.md "Goodput"): ``goodput.json`` is ONE JSON
object with a ``generations`` list (each: finite ``start_t <= last_t``,
``buckets`` mapping bucket name → non-negative finite seconds) and a
``merged`` object whose exclusive buckets are non-negative, drawn from the
documented bucket set (unknown names warn), and sum to ``wall_s`` within
1% (+ a small absolute epsilon for sub-second runs); ``goodput_fraction``
must lie in [0, 1].

Rows written by the async-PS role (keyed by ``time``/``global_version``
instead of ``step``, nested ``staleness_hist``) are a different stream and
out of scope here; this tool targets the convergence/training artifacts.

Exit status: 0 = every file valid, 1 = any violation (CI gate).
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import sys

#: jsonl-flattened label suffix of the collective histogram (.op_<op>);
#: label suffixes sort alphabetically, so an ``overlapped`` label can
#: follow the op one — match mid-key, not just at end of field name.
_FLAT_OP_RE = re.compile(r"\.op_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``overlapped`` label (parallel/overlap.py wrappers).
_FLAT_OVERLAPPED_RE = re.compile(r"\.overlapped_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``component`` label of the input-plane depth metrics
#: (data/adaptive.py controller).
_FLAT_COMPONENT_RE = re.compile(r"\.component_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``direction`` label of the resize-decision counter.
_FLAT_DIRECTION_RE = re.compile(r"\.direction_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``state`` label of the ``fleet_peers`` gauge.
_FLAT_STATE_RE = re.compile(r"\.state_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``window`` label of the ``slo_burn_rate`` gauge.
_FLAT_WINDOW_RE = re.compile(r"\.window_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``stage`` label of the pipeline handoff/stall
#: histograms (parallel/pipeline_mpmd.py).
_FLAT_STAGE_RE = re.compile(r"\.stage_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``endpoint`` label of the ``rpc_*`` / ``breaker_*``
#: families (net/rpc.py, net/breaker.py).  Endpoint identities embed
#: addresses, so ``:`` is a legal value character.
_FLAT_ENDPOINT_RE = re.compile(r"\.endpoint_([A-Za-z0-9_:]+?)(?=\.|$)")
#: jsonl-flattened ``outcome`` label of ``rpc_retries_total``.
_FLAT_OUTCOME_RE = re.compile(r"\.outcome_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``to`` label of ``breaker_transitions_total``.
_FLAT_TO_RE = re.compile(r"\.to_([A-Za-z0-9_]+?)(?=\.|$)")
#: jsonl-flattened ``module`` label of the ``dynamics_*`` families
#: (obs/dynamics.py).
_FLAT_MODULE_RE = re.compile(r"\.module_([A-Za-z0-9_]+?)(?=\.|$)")
#: Dynamics module names: sanitized first parameter-path components
#: (obs/dynamics.py _sanitize) — identifier grammar.
_MODULE_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")

#: One Prometheus exposition sample: name, optional {labels}, value.
_PROM_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$"
)
_PROM_LABEL_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="([^"]*)"')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_GLOB = os.path.join(REPO, "ARTIFACTS", "convergence_*", "metrics.jsonl")
DEFAULT_FLIGHT_GLOB = os.path.join(
    REPO, "ARTIFACTS", "convergence_*", "flight*.jsonl"
)
DEFAULT_GOODPUT_GLOB = os.path.join(
    REPO, "ARTIFACTS", "convergence_*", "goodput*.json"
)
DEFAULT_CAPTURES_GLOB = os.path.join(
    REPO, "ARTIFACTS", "convergence_*", "captures*.jsonl"
)
DEFAULT_FAULTS_GLOB = os.path.join(
    REPO, "ARTIFACTS", "convergence_*", "faults*.jsonl"
)
DEFAULT_REQUESTS_GLOB = os.path.join(
    REPO, "ARTIFACTS", "serve_*", "requests*.jsonl"
)
DEFAULT_STEPS_GLOB = os.path.join(
    REPO, "ARTIFACTS", "serve_*", "steps*.jsonl"
)
DEFAULT_HISTORY_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "history*.jsonl"
)
DEFAULT_PROM_GLOB = os.path.join(
    REPO, "ARTIFACTS", "convergence_*", "metrics.prom"
)
DEFAULT_FLASH_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "flash_blocks*.json"
)
DEFAULT_SLO_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "slo*.json"
)
DEFAULT_FLEET_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "fleet*.json"
)
DEFAULT_TIMELINE_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "timeline*.json"
)
DEFAULT_JOURNAL_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "dispatcher*.journal"
)
DEFAULT_ALERTS_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "alerts*.jsonl"
)
DEFAULT_INCIDENT_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "incidents", "*", "manifest.json"
)
DEFAULT_DYNAMICS_GLOB = os.path.join(
    REPO, "ARTIFACTS", "*", "dynamics*.jsonl"
)

#: The documented exclusive wall-time buckets (obs/goodput.py BUCKETS —
#: duplicated: this tool is stdlib-only and must run anywhere logs land).
GOODPUT_BUCKETS = (
    "init", "compile", "train_step", "data_wait", "checkpoint_save",
    "checkpoint_restore", "eval", "preemption_drain", "profile_capture",
    "resize", "lost_work", "badput_restart", "other",
)

#: The known capture trigger kinds (obs/capture.py TRIGGERS — duplicated
#: for the same stdlib-only reason).
CAPTURE_TRIGGERS = (
    "static", "manual", "step_time_regression", "straggler_spread",
    "slo_burn", "alert",
)

#: The known chaos fault kinds (resilience/chaos.py FAULT_KINDS —
#: duplicated for the same stdlib-only reason; the ``net_*`` /
#: ``dispatcher_kill`` kinds are transport-recovered, ISSUE 13).
FAULT_KINDS = (
    "nan_loss", "checkpoint_truncate", "worker_kill", "data_stall",
    "preemption", "resize",
    "net_delay", "net_drop", "net_sever", "dispatcher_kill",
)
FAULT_PHASES = ("injected", "recovered")

#: ``elastic_resizes_total`` outcome label values
#: (resilience/elastic.py RESIZE_OUTCOMES — duplicated for the same
#: stdlib-only reason).
ELASTIC_RESIZE_OUTCOMES = ("completed", "failed", "rejected")

#: Resilient-transport label sets (net/rpc.py, net/breaker.py —
#: duplicated for the same stdlib-only reason).  Endpoint identities are
#: "<prefix>" or "<prefix>:<detail>"; the prefix names the transport.
RPC_ENDPOINT_PREFIXES = (
    "dispatcher", "data_worker", "mpmd_link", "fleet_peer", "serve",
    "peer", "webhook",
)
RPC_RETRY_OUTCOMES = ("ok", "error")
BREAKER_TO_STATES = ("closed", "half_open", "open")

#: Alert-stream vocabularies (obs/alerts.py — duplicated for the same
#: stdlib-only reason).
ALERT_KINDS = ("threshold", "burn", "absence", "anomaly")
ALERT_SEVERITIES = ("info", "warn", "page")
ALERT_PHASES = ("fired", "resolved")

#: Dispatcher journal record kinds (data/service.py JOURNAL_KINDS —
#: duplicated for the same stdlib-only reason).
JOURNAL_KINDS = (
    "open", "replay", "worker_register", "worker_deregister",
    "epoch_start", "reshard", "client_progress",
)


def _check_endpoint_value(value: str) -> str | None:
    """None when ``value`` is a well-formed endpoint identity, else the
    complaint."""
    if not value:
        return "is empty"
    prefix = value.split(":", 1)[0]
    if prefix not in RPC_ENDPOINT_PREFIXES:
        return (f"has unknown endpoint prefix {prefix!r} "
                f"(known: {RPC_ENDPOINT_PREFIXES})")
    return None

#: Terminal request states + finish reasons (serve/engine.py — duplicated
#: for the same stdlib-only reason).
REQUEST_STATES = ("ok", "rejected", "error")
FINISH_REASONS = ("eos", "length")

#: Exclusive tail-latency attribution fields stamped on ok requests.jsonl
#: rows (serve/engine.py, ISSUE 16).  Together with ``attr_queue_s`` they
#: tile ``e2e_s``: each non-negative finite, the sum within 5% of e2e.
REQUEST_ATTR_FIELDS = (
    "attr_queue_s", "attr_prefill_s", "attr_stall_s", "attr_decode_s",
    "attr_spec_s", "attr_gap_s",
)

#: Engine step-log schema (serve/engine.py ``_log_step``, ISSUE 16):
#: phase tokens of the per-iteration ``phase`` field, the non-negative
#: integer count fields, and the non-negative finite wall-split fields
#: (``admit_s + prefill_s + decode_s <= step_s`` up to rounding: the
#: durations of the iteration's ``engine.*`` span tree).
STEP_PHASE_TOKENS = ("admit", "prefill", "decode")
STEP_COUNT_FIELDS = (
    "occupancy", "active_slots", "filling_slots", "queue_depth",
    "admitted", "evicted", "prefill_chunks", "budget_stall",
    "tokens_committed", "spec_drafted", "spec_accepted",
)
STEP_WALL_FIELDS = (
    "admit_s", "prefill_s", "decode_s", "step_s",
)
#: The iteration's leaves and the engine thread's account (ISSUE 36;
#: validated where present, so older logs stay green): seconds, all of
#: them — ``dispatch_s + prelaunch_s + fetch_s + commit_s <= decode_s``
#: (``prelaunch_s``, ISSUE 60: the next iteration's prefill chunks launched
#: under this one's decode step, whose count ``prefill_prelaunched`` of the
#: NEXT record's ``prefill_chunks`` is) and the leaves leave ``unnamed_s <=
#: 2 %`` of ``step_s`` (up to rounding).
STEP_LEAF_FIELDS = (
    "dispatch_s", "prelaunch_s", "fetch_s", "commit_s", "first_token_s",
    "log_prev_s",
    "between_s", "wait_s", "offcpu_s", "commit_cpu_s", "gc_s", "unnamed_s",
    "stream_lag_max_s", "compile_s",
)
#: What JAX compiled or loaded inside the iteration, or the log row's
#: steps (ISSUE 50, the compile log of obs/tracing.py): ``compile_s`` its
#: seconds (a step-log leaf field above, a metric-row field too), and,
#: only beside a ``compile_s`` above 0, ``compiled``: the programs' names,
#: joined by commas.
COMPILED_FIELD = "compiled"


def _check_compiled(row: dict, where: str) -> list[str]:
    """``compiled`` is a non-empty string, there exactly where
    ``compile_s`` is above 0 (both absent in older logs)."""
    names, secs = row.get(COMPILED_FIELD), row.get("compile_s")
    if names is None:
        if _nonneg_finite(secs) and secs > 0:
            return [f"{where}: 'compile_s' {secs!r} names no 'compiled' "
                    "program"]
        return []
    if not isinstance(names, str) or not names:
        return [f"{where}: 'compiled' {names!r} is not a non-empty string"]
    if not (_nonneg_finite(secs) and secs > 0):
        return [f"{where}: 'compiled' {names!r} beside 'compile_s' "
                f"{secs!r}: nothing took any time"]
    return []

#: Tenant identities are identifier-style (serve/engine.py ``TENANT_RE``
#: — duplicated, stdlib-only): the ``tenant`` of a requests.jsonl row and
#: the keys of a step row's ``admitted_tenants``.
_TENANT_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")

#: Series cap of the embedded metrics history store (obs/tsdb.py
#: ``MetricsHistory`` default ``max_series`` — duplicated, stdlib-only).
#: A ``history.jsonl`` row carrying more names than this means the
#: writer's cardinality bound is broken.
HISTORY_MAX_SERIES = 512
#: A history metric name: the registry's flattened spelling (dots join
#: label suffixes; ``fleet.<key>.<stat>`` / ``slo_good.<rule>`` ride the
#: same namespace).  No whitespace, no control characters.
_HISTORY_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_.:/\-]*$")

#: Serving prefix-cache metric families (serve/engine.py, ISSUE 14).
#: The monotonic counters must be non-negative; the ratio gauges live in
#: [0, 1].  Checked both as .prom samples and as jsonl-flattened /
#: engine-metrics-row field names.
SERVE_PREFIX_COUNTERS = (
    "serve_prefix_hits_total", "serve_prefix_cached_tokens_total",
    "serve_prefill_tokens_total", "serve_prefix_evictions_total",
    "serve_kv_cow_copies_total", "serve_kv_block_refs",
    "serve_kv_blocks_cached",
)
SERVE_PREFIX_RATIOS = (
    "serve_prefix_hit_rate", "serve_prefix_cache_occupancy",
    "serve_kv_fragmentation",
)
#: Decode-fast-path metric families (serve/engine.py, ISSUE 15): the
#: speculative counters are monotonic non-negative and the acceptance
#: invariant ``accepted <= drafted`` must hold wherever both appear
#: (one .prom page, one metrics row, one requests.jsonl row).
SERVE_SPEC_COUNTERS = (
    "serve_spec_drafted_total", "serve_spec_accepted_total",
)
#: Their spellings inside the serving engine's own metrics.jsonl rows.
SERVE_ROW_COUNTERS = (
    "prefix_hits_total", "prefix_lookups_total",
    "prefix_cached_tokens_total", "prefill_tokens_total",
    "prefix_evictions_total", "cow_copies_total", "blocks_cached",
    "block_refs", "prefill_iters", "prefill_chunks", "prefill_prelaunched",
    "prefill_budget",
    "spec_drafted_total", "spec_accepted_total", "decode_tokens_total",
    "decode_dispatches_total", "host_sample_rounds_total", "speculate",
    "fused_sampling", "tokens_per_step",
)
SERVE_ROW_RATIOS = (
    "prefix_hit_rate", "prefix_occupancy", "kv_fragmentation",
    "spec_acceptance_rate",
)

#: The known ``op`` labels of the ``collective_dispatch_seconds``
#: histogram (parallel/collectives.py wrappers — duplicated for the same
#: stdlib-only reason).  ``reduce_scatter`` / ``all_gather`` cover both
#: the shard_map primitives and the GSPMD-constraint wrappers the ZeRO
#: weight-update sharding path dispatches through.
COLLECTIVE_OPS = (
    "all_reduce", "all_gather", "reduce_scatter", "broadcast", "permute",
    "shift", "all_to_all",
)

#: Values of the ``overlapped`` histogram label (parallel/overlap.py —
#: "1" = issued by the backward-pass bucketed gradient sync).
OVERLAPPED_VALUES = ("0", "1")

#: Allowed values of the string-typed ``quant_mode`` metric-row field
#: (ops/quant.py QUANT_MODES minus the unstamped "none" — duplicated for
#: the same stdlib-only reason).
QUANT_MODES = ("none", "int8", "int8_stochastic", "fp8")

#: Provenance tags of a flash-blocks autotune cache entry
#: (ops/flash_tuning.py SOURCES — duplicated, stdlib-only).
FLASH_SOURCES = ("sweep", "xplane")

#: ``component`` labels of the adaptive input-plane depth metrics
#: (``data_prefetch_depth`` gauge / ``data_prefetch_resizes_total``
#: counter — data/adaptive.py, duplicated for the same stdlib-only
#: reason).  "prefetcher" = the host->device Prefetcher buffer,
#: "client" = the data-service credit window.
PREFETCH_COMPONENTS = ("prefetcher", "client")
#: ``direction`` labels of the resize-decision counter.
PREFETCH_DIRECTIONS = ("grow", "shrink")

#: Peer states of the fleet aggregator (obs/fleet.py PEER_STATES —
#: duplicated for the same stdlib-only reason).
FLEET_PEER_STATES = ("up", "stale", "down")
#: ``window`` labels of the SLO burn-rate gauge (obs/slo.py SLO_WINDOWS).
SLO_WINDOWS = ("fast", "slow")
#: SLO rule kinds (obs/slo.py RULE_KINDS — duplicated, stdlib-only).
SLO_RULE_KINDS = (
    "histogram_under", "gauge_good_fraction", "gauge_bad_fraction",
)

#: Values of the string-typed ``pipeline_schedule`` metric-row field
#: (parallel/pipeline.py SCHEDULES + the MPMD stage-per-process variant
#: — duplicated for the same stdlib-only reason).
PIPELINE_SCHEDULES = ("gpipe", "1f1b", "interleaved", "mpmd")


def check_row(row, lineno: int) -> tuple[list[str], list[str]]:
    """Returns (errors, warnings) for one parsed row."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(row, dict):
        return [f"line {lineno}: row is {type(row).__name__}, not an object"], []
    step = row.get("step")
    if step is None:
        errors.append(f"line {lineno}: missing 'step'")
    elif not isinstance(step, (int, float)) or isinstance(step, bool) \
            or float(step) != int(step) or step < 0:
        errors.append(f"line {lineno}: 'step' {step!r} is not a "
                      "non-negative integer")
    for k, v in row.items():
        if k == "step":
            continue
        if not isinstance(k, str) or not k or any(ord(c) < 32 for c in k):
            errors.append(f"line {lineno}: bad field name {k!r}")
            continue
        if k.startswith("collective_dispatch_seconds"):
            # flattened label suffix: ..._count.op_<op> (registry.scalars)
            m = _FLAT_OP_RE.search(k)
            if m and m.group(1) not in COLLECTIVE_OPS:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown collective "
                    f"op {m.group(1)!r} (known: {COLLECTIVE_OPS})"
                )
            m = _FLAT_OVERLAPPED_RE.search(k)
            if m and m.group(1) not in OVERLAPPED_VALUES:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown "
                    f"overlapped value {m.group(1)!r} "
                    f"(known: {OVERLAPPED_VALUES})"
                )
        if k.startswith(("data_prefetch_depth", "data_prefetch_resizes")):
            # input-plane depth telemetry: a typo'd component/direction
            # label silently forks the adaptive controller's time series
            m = _FLAT_COMPONENT_RE.search(k)
            if m and m.group(1) not in PREFETCH_COMPONENTS:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown prefetch "
                    f"component {m.group(1)!r} "
                    f"(known: {PREFETCH_COMPONENTS})"
                )
            m = _FLAT_DIRECTION_RE.search(k)
            if m and m.group(1) not in PREFETCH_DIRECTIONS:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown resize "
                    f"direction {m.group(1)!r} "
                    f"(known: {PREFETCH_DIRECTIONS})"
                )
        if k.startswith("elastic_resizes_total"):
            # flattened ``outcome`` label of the elastic-resize counter:
            # an unknown outcome forks the resize success-rate series
            m = _FLAT_OUTCOME_RE.search(k)
            if m and m.group(1) not in ELASTIC_RESIZE_OUTCOMES:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown resize "
                    f"outcome {m.group(1)!r} "
                    f"(known: {ELASTIC_RESIZE_OUTCOMES})"
                )
        if k.startswith("fleet_peers"):
            m = _FLAT_STATE_RE.search(k)
            if m and m.group(1) not in FLEET_PEER_STATES:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown fleet "
                    f"peer state {m.group(1)!r} "
                    f"(known: {FLEET_PEER_STATES})"
                )
        if k.startswith(("rpc_retries_total", "rpc_deadline_exceeded_total",
                         "rpc_attempt_seconds", "breaker_state",
                         "breaker_transitions_total")):
            m = _FLAT_ENDPOINT_RE.search(k)
            if m:
                bad = _check_endpoint_value(m.group(1))
                if bad:
                    errors.append(f"line {lineno}: field {k!r} {bad}")
            m = _FLAT_OUTCOME_RE.search(k)
            if m and m.group(1) not in RPC_RETRY_OUTCOMES:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown rpc "
                    f"retry outcome {m.group(1)!r} "
                    f"(known: {RPC_RETRY_OUTCOMES})"
                )
            m = _FLAT_TO_RE.search(k)
            if m and m.group(1) not in BREAKER_TO_STATES:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown breaker "
                    f"state {m.group(1)!r} (known: {BREAKER_TO_STATES})"
                )
            if k.startswith("breaker_state") and isinstance(v, (int, float)) \
                    and not isinstance(v, bool) and v not in (0, 1, 2):
                errors.append(
                    f"line {lineno}: field {k!r} value {v!r} is not a "
                    "breaker state encoding (0=closed, 1=half_open, 2=open)"
                )
        if k.startswith("dynamics_"):
            # flattened ``module`` label of the training-dynamics
            # families: a malformed module name forks the per-layer
            # divergence series (obs/dynamics.py sanitizes to
            # identifier grammar)
            m = _FLAT_MODULE_RE.search(k)
            if m and not _MODULE_NAME_RE.match(m.group(1)):
                errors.append(
                    f"line {lineno}: field {k!r} carries malformed "
                    f"dynamics module name {m.group(1)!r}"
                )
            if k.startswith(("dynamics_nonfinite_grads_total",
                             "dynamics_provenance_total")) \
                    and isinstance(v, (int, float)) \
                    and not isinstance(v, bool) \
                    and math.isfinite(v) and v < 0:
                errors.append(
                    f"line {lineno}: field {k!r} is negative ({v}) — the "
                    "dynamics counters are monotonic"
                )
        if k.startswith("slo_burn_rate"):
            m = _FLAT_WINDOW_RE.search(k)
            if m and m.group(1) not in SLO_WINDOWS:
                errors.append(
                    f"line {lineno}: field {k!r} carries unknown slo "
                    f"window {m.group(1)!r} (known: {SLO_WINDOWS})"
                )
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and math.isfinite(v) and v < 0:
                errors.append(
                    f"line {lineno}: field {k!r} is negative ({v}) — burn "
                    "rates are non-negative by construction"
                )
        if k == "quant_mode":
            # the one STRING-typed metric-row field: the quantized-compute
            # mode stamp (TrainerConfig.quant)
            if v not in QUANT_MODES:
                errors.append(
                    f"line {lineno}: 'quant_mode' {v!r} not in "
                    f"{QUANT_MODES}"
                )
            continue
        if k == COMPILED_FIELD:
            errors.extend(_check_compiled(row, f"line {lineno}"))
            continue
        if k == "pipeline_schedule":
            # the pipeline-schedule stamp (TrainerConfig.pipeline_schedule
            # / MPMD stage rows) — string-typed like quant_mode
            if v not in PIPELINE_SCHEDULES:
                errors.append(
                    f"line {lineno}: 'pipeline_schedule' {v!r} not in "
                    f"{PIPELINE_SCHEDULES}"
                )
            continue
        if k in ("pipeline_stages", "pipeline_microbatches",
                 "pipeline_virtual"):
            if not _nonneg_int(v):
                errors.append(
                    f"line {lineno}: {k!r} {v!r} is not a non-negative "
                    "integer"
                )
            continue
        if k == "pipeline_bubble":
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not math.isfinite(v) or not 0.0 <= v < 1.0:
                errors.append(
                    f"line {lineno}: 'pipeline_bubble' {v!r} is not in "
                    "[0, 1)"
                )
            continue
        if k.startswith(("pipeline_handoff_seconds",
                         "pipeline_mpmd_stall_seconds")):
            m = _FLAT_STAGE_RE.search(k)
            if m and not m.group(1).isdigit():
                errors.append(
                    f"line {lineno}: field {k!r} carries non-numeric "
                    f"pipeline stage label {m.group(1)!r}"
                )
        if (k.startswith(SERVE_PREFIX_COUNTERS) or k in SERVE_ROW_COUNTERS) \
                and isinstance(v, (int, float)) \
                and not isinstance(v, bool) and math.isfinite(v) and v < 0:
            errors.append(
                f"line {lineno}: field {k!r} is negative ({v}) — the "
                "serving prefix-cache counters are monotonic"
            )
        if (k in SERVE_ROW_RATIOS or k.startswith(SERVE_PREFIX_RATIOS)) \
                and isinstance(v, (int, float)) \
                and not isinstance(v, bool) and math.isfinite(v) \
                and not 0.0 <= v <= 1.0:
            errors.append(
                f"line {lineno}: field {k!r} {v!r} is not in [0, 1]"
            )
        if v in ("NaN", "Infinity", "-Infinity"):
            warnings.append(f"line {lineno}: field {k!r} is non-finite ({v})")
        elif isinstance(v, bool) or not isinstance(v, (int, float)):
            errors.append(
                f"line {lineno}: field {k!r} is {type(v).__name__}, "
                "not a number"
            )
        elif not math.isfinite(v):
            # pre-sentinel writers emitted bare NaN tokens; python json
            # still parses them, so keep flagging rather than erroring
            warnings.append(f"line {lineno}: field {k!r} is non-finite ({v})")
    drafted = row.get("spec_drafted_total")
    accepted = row.get("spec_accepted_total")
    if _nonneg_int(drafted) and _nonneg_int(accepted) \
            and accepted > drafted:
        errors.append(
            f"line {lineno}: spec_accepted_total {accepted} exceeds "
            f"spec_drafted_total {drafted} — the verifier cannot accept "
            "more drafts than were proposed"
        )
    return errors, warnings


def check_flight_row(row, lineno: int,
                     prev_t: float | None) -> tuple[list[str], list[str], float | None]:
    """Returns (errors, warnings, timestamp) for one flight event."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(row, dict):
        return ([f"line {lineno}: event is {type(row).__name__}, "
                 "not an object"], [], prev_t)
    t = row.get("t")
    if not isinstance(t, (int, float)) or isinstance(t, bool) \
            or not math.isfinite(t):
        errors.append(f"line {lineno}: 't' {t!r} is not a finite number")
        t = None
    elif prev_t is not None and t < prev_t:
        errors.append(
            f"line {lineno}: 't' {t} decreases (ring order violated)"
        )
    kind = row.get("kind")
    if not isinstance(kind, str) or not kind:
        errors.append(f"line {lineno}: 'kind' {kind!r} is not a "
                      "non-empty string")
    step = row.get("step")
    if step is not None and (
        not isinstance(step, (int, float)) or isinstance(step, bool)
        or float(step) != int(step) or step < 0
    ):
        errors.append(f"line {lineno}: 'step' {step!r} is not a "
                      "non-negative integer")
    for k, v in row.items():
        if not isinstance(k, str) or not k or any(ord(c) < 32 for c in k):
            errors.append(f"line {lineno}: bad field name {k!r}")
            continue
        if k in ("t", "kind", "step"):
            continue
        if isinstance(v, float) and not math.isfinite(v):
            warnings.append(f"line {lineno}: field {k!r} is a bare "
                            f"non-finite ({v}); writer emits sentinels")
        elif not isinstance(v, (int, float, str, bool)) and v is not None:
            errors.append(
                f"line {lineno}: field {k!r} is {type(v).__name__}, "
                "not a JSON scalar"
            )
    return errors, warnings, (t if t is not None else prev_t)


def _nonneg_int(v) -> bool:
    """True when ``v`` is a non-negative integral JSON number.  The
    finiteness check comes FIRST: ``json.loads`` parses bare ``NaN`` /
    ``Infinity`` tokens, and ``int(nan)`` raises — a malformed row must
    become a reported error, never a checker traceback."""
    return (
        isinstance(v, (int, float)) and not isinstance(v, bool)
        and math.isfinite(v) and float(v) == int(v) and v >= 0
    )


def check_capture_row(
    row, lineno: int, prev_id: int | None, manifest_dir: str,
) -> tuple[list[str], list[str], int | None]:
    """Returns (errors, warnings, id) for one captures.jsonl manifest row."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(row, dict):
        return ([f"line {lineno}: row is {type(row).__name__}, "
                 "not an object"], [], prev_id)
    cap_id = row.get("id")
    if not _nonneg_int(cap_id):
        errors.append(f"line {lineno}: 'id' {cap_id!r} is not a "
                      "non-negative integer")
        cap_id = None
    elif prev_id is not None and int(cap_id) <= prev_id:
        errors.append(
            f"line {lineno}: 'id' {int(cap_id)} does not increase "
            f"(previous {prev_id})"
        )
    trigger = row.get("trigger")
    if trigger not in CAPTURE_TRIGGERS:
        errors.append(
            f"line {lineno}: 'trigger' {trigger!r} not in "
            f"{CAPTURE_TRIGGERS}"
        )
    aborted = bool(row.get("aborted"))
    steps = {}
    for name in ("step_begin", "step_end"):
        v = row.get(name)
        if not _nonneg_int(v):
            errors.append(f"line {lineno}: {name!r} {v!r} is not a "
                          "non-negative integer")
        else:
            steps[name] = int(v)
    if len(steps) == 2:
        if aborted:
            if steps["step_end"] < steps["step_begin"]:
                errors.append(
                    f"line {lineno}: step_end {steps['step_end']} precedes "
                    f"step_begin {steps['step_begin']}"
                )
        elif steps["step_end"] <= steps["step_begin"]:
            errors.append(
                f"line {lineno}: step_end {steps['step_end']} must exceed "
                f"step_begin {steps['step_begin']} (window covered no step)"
            )
    times = {}
    for name in ("t_begin", "t_end"):
        v = row.get(name)
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not math.isfinite(v):
            errors.append(f"line {lineno}: {name!r} {v!r} is not a "
                          "finite number")
        else:
            times[name] = float(v)
    if len(times) == 2 and times["t_end"] < times["t_begin"]:
        errors.append(
            f"line {lineno}: t_end {times['t_end']} precedes t_begin "
            f"{times['t_begin']}"
        )
    for name in ("wall_s", "overhead_s"):
        v = row.get(name)
        if v is None:
            continue
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not math.isfinite(v) or v < 0:
            errors.append(f"line {lineno}: {name!r} {v!r} is not a "
                          "non-negative finite number")
    cap_dir = row.get("dir")
    if not isinstance(cap_dir, str) or not cap_dir:
        errors.append(f"line {lineno}: 'dir' {cap_dir!r} is not a "
                      "non-empty string")
    else:
        resolved = (cap_dir if os.path.isabs(cap_dir)
                    else os.path.join(manifest_dir, cap_dir))
        if not os.path.isdir(resolved):
            errors.append(
                f"line {lineno}: capture dir {resolved} does not exist"
            )
    return (errors, warnings,
            int(cap_id) if cap_id is not None else prev_id)


def check_faults_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one ``faults.jsonl`` chaos log (see module docstring):
    per-row shape, time/id/step ordering, and injected/recovered pairing."""
    errors: list[str] = []
    warnings: list[str] = []
    prev_t: float | None = None
    prev_injected_id: int | None = None
    prev_injected_step: int | None = None
    injected_kinds: dict[int, str] = {}
    recovered_ids: set[int] = set()
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: row is {type(row).__name__}, "
                              "not an object")
                continue
            t = row.get("t")
            if isinstance(t, bool) or not isinstance(t, (int, float)) \
                    or not math.isfinite(t):
                errors.append(f"line {i}: 't' {t!r} is not a finite number")
            else:
                if prev_t is not None and t < prev_t:
                    errors.append(f"line {i}: 't' {t} decreases")
                prev_t = float(t)
            kind = row.get("kind")
            if kind not in FAULT_KINDS:
                errors.append(
                    f"line {i}: 'kind' {kind!r} not in {FAULT_KINDS}"
                )
            phase = row.get("phase")
            if phase not in FAULT_PHASES:
                errors.append(
                    f"line {i}: 'phase' {phase!r} not in {FAULT_PHASES}"
                )
            fid = row.get("id")
            if not _nonneg_int(fid):
                errors.append(f"line {i}: 'id' {fid!r} is not a "
                              "non-negative integer")
                continue
            fid = int(fid)
            step = row.get("step")
            if not _nonneg_int(step):
                errors.append(f"line {i}: 'step' {step!r} is not a "
                              "non-negative integer")
                step = None
            if phase == "injected":
                if fid in injected_kinds:
                    errors.append(f"line {i}: fault id {fid} injected twice")
                elif prev_injected_id is not None \
                        and fid <= prev_injected_id:
                    errors.append(
                        f"line {i}: injected id {fid} does not increase "
                        f"(previous {prev_injected_id})"
                    )
                prev_injected_id = (
                    fid if prev_injected_id is None
                    else max(prev_injected_id, fid)
                )
                if step is not None:
                    if prev_injected_step is not None \
                            and int(step) < prev_injected_step:
                        errors.append(
                            f"line {i}: injected step {int(step)} decreases "
                            f"(previous {prev_injected_step})"
                        )
                    prev_injected_step = (
                        int(step) if prev_injected_step is None
                        else max(prev_injected_step, int(step))
                    )
                injected_kinds[fid] = kind
            elif phase == "recovered":
                if fid not in injected_kinds:
                    errors.append(
                        f"line {i}: recovered id {fid} was never injected"
                    )
                elif kind != injected_kinds[fid]:
                    errors.append(
                        f"line {i}: recovered id {fid} kind {kind!r} != "
                        f"injected kind {injected_kinds[fid]!r}"
                    )
                recovered_ids.add(fid)
    unpaired = sorted(set(injected_kinds) - recovered_ids)
    for fid in unpaired:
        errors.append(
            f"fault id {fid} ({injected_kinds[fid]}) was injected but "
            "never recovered — the run did not self-heal"
        )
    return errors, warnings


def check_journal_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one ``dispatcher.journal`` durability log
    (``data/service.py`` DispatcherJournal): every line one JSON object
    with a strictly-increasing integer ``seq``, non-decreasing finite
    ``t``, a ``kind`` from :data:`JOURNAL_KINDS`, and replay-safe
    ordering — an epoch's ``epoch_start`` (gen 0) precedes any of its
    ``reshard`` / ``client_progress`` records, reshard generations
    strictly increase per epoch, and worker registrations carry an
    address + non-negative shard.  A torn FINAL line is tolerated (the
    one legal partial append); torn lines elsewhere are errors."""
    errors: list[str] = []
    warnings: list[str] = []
    prev_seq: int | None = None
    prev_t: float | None = None
    epoch_gens: dict[str, int] = {}
    with open(path) as f:
        lines = f.read().split("\n")
    n_lines = len([ln for ln in lines if ln.strip()])
    seen = 0
    for i, line in enumerate(lines, start=1):
        line = line.strip()
        if not line:
            continue
        seen += 1
        try:
            row = json.loads(line)
        except json.JSONDecodeError as e:
            if seen == n_lines:
                warnings.append(f"line {i}: torn final line dropped "
                                "(interrupted append)")
            else:
                errors.append(f"line {i}: invalid JSON ({e})")
            continue
        if not isinstance(row, dict):
            errors.append(f"line {i}: record is {type(row).__name__}, "
                          "not an object")
            continue
        seq = row.get("seq")
        if not _nonneg_int(seq):
            errors.append(f"line {i}: 'seq' {seq!r} is not a non-negative "
                          "integer")
        else:
            seq = int(seq)
            if prev_seq is not None and seq <= prev_seq:
                errors.append(f"line {i}: 'seq' {seq} does not increase "
                              f"(previous {prev_seq})")
            prev_seq = seq if prev_seq is None else max(prev_seq, seq)
        t = row.get("t")
        if isinstance(t, bool) or not isinstance(t, (int, float)) \
                or not math.isfinite(t):
            errors.append(f"line {i}: 't' {t!r} is not a finite number")
        else:
            if prev_t is not None and t < prev_t:
                errors.append(f"line {i}: 't' {t} decreases")
            prev_t = float(t)
        kind = row.get("kind")
        if kind not in JOURNAL_KINDS:
            errors.append(f"line {i}: 'kind' {kind!r} not in "
                          f"{JOURNAL_KINDS}")
            continue
        if kind == "worker_register":
            if not isinstance(row.get("addr"), str) or not row["addr"]:
                errors.append(f"line {i}: worker_register 'addr' "
                              f"{row.get('addr')!r} is not a non-empty "
                              "string")
            if not _nonneg_int(row.get("shard")):
                errors.append(f"line {i}: worker_register 'shard' "
                              f"{row.get('shard')!r} is not a "
                              "non-negative integer")
        elif kind == "epoch_start":
            epoch = str(row.get("epoch"))
            if row.get("gen") != 0:
                errors.append(f"line {i}: epoch_start 'gen' "
                              f"{row.get('gen')!r} must be 0")
            if not isinstance(row.get("splits"), dict):
                errors.append(f"line {i}: epoch_start 'splits' is not an "
                              "object")
            if epoch in epoch_gens:
                errors.append(f"line {i}: epoch {epoch!r} started twice")
            epoch_gens[epoch] = 0
        elif kind == "reshard":
            epoch = str(row.get("epoch"))
            gen = row.get("gen")
            if epoch not in epoch_gens:
                errors.append(f"line {i}: reshard for epoch {epoch!r} "
                              "precedes its epoch_start (replay-unsafe "
                              "ordering)")
            elif not _nonneg_int(gen):
                errors.append(f"line {i}: reshard 'gen' {gen!r} is not a "
                              "non-negative integer")
            elif int(gen) <= epoch_gens[epoch]:
                errors.append(
                    f"line {i}: reshard gen {int(gen)} does not increase "
                    f"for epoch {epoch!r} (previous {epoch_gens[epoch]})"
                )
            else:
                epoch_gens[epoch] = int(gen)
            if not isinstance(row.get("splits"), dict):
                errors.append(f"line {i}: reshard 'splits' is not an "
                              "object")
        elif kind == "client_progress":
            epoch = str(row.get("epoch"))
            if epoch not in epoch_gens:
                errors.append(f"line {i}: client_progress for epoch "
                              f"{epoch!r} precedes its epoch_start")
            received = row.get("received")
            if not isinstance(received, dict):
                errors.append(f"line {i}: client_progress 'received' is "
                              "not an object")
            else:
                for s, n in received.items():
                    if not _nonneg_int(n):
                        errors.append(
                            f"line {i}: client_progress received[{s!r}] "
                            f"{n!r} is not a non-negative integer"
                        )
    return errors, warnings


def check_requests_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one serving ``requests.jsonl`` log (docs/API.md
    "Serving"): every row is one JSON object with finite non-decreasing
    ``t``, a non-empty string ``id``, ``status`` from the terminal set,
    and non-negative integer ``prompt_tokens`` / ``new_tokens``.  ``ok``
    rows must additionally carry ``finish_reason`` from the known set,
    ``new_tokens > 0`` / ``prompt_tokens > 0``, latencies satisfying
    ``0 <= ttft_s <= e2e_s`` (plus non-negative ``tpot_s`` /
    ``queue_s``), occupancy fields (``occ_mean`` non-negative finite,
    ``occ_max`` non-negative integer), and an integer ``slot >= -1``."""
    errors: list[str] = []
    warnings: list[str] = []
    prev_t: float | None = None
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: row is {type(row).__name__}, "
                              "not an object")
                continue
            t = row.get("t")
            if isinstance(t, bool) or not isinstance(t, (int, float)) \
                    or not math.isfinite(t):
                errors.append(f"line {i}: 't' {t!r} is not a finite number")
            else:
                if prev_t is not None and t < prev_t:
                    errors.append(f"line {i}: 't' {t} decreases")
                prev_t = float(t)
            rid = row.get("id")
            if not isinstance(rid, str) or not rid:
                errors.append(f"line {i}: 'id' {rid!r} is not a non-empty "
                              "string")
            status = row.get("status")
            if status not in REQUEST_STATES:
                errors.append(
                    f"line {i}: 'status' {status!r} not in {REQUEST_STATES}"
                )
                continue
            for name in ("prompt_tokens", "new_tokens"):
                if not _nonneg_int(row.get(name)):
                    errors.append(f"line {i}: {name!r} {row.get(name)!r} is "
                                  "not a non-negative integer")
            # the request's identity (ISSUE 19; validated when present
            # so pre-ISSUE-19 logs stay green): identifier-style tenant.
            tenant = row.get("tenant")
            if tenant is not None and (
                not isinstance(tenant, str) or not _TENANT_RE.match(tenant)
            ):
                errors.append(f"line {i}: 'tenant' {tenant!r} does not "
                              f"match {_TENANT_RE.pattern}")
            if status != "ok":
                continue
            if not (_nonneg_int(row.get("prompt_tokens"))
                    and row.get("prompt_tokens", 0) > 0):
                errors.append(f"line {i}: ok row has no prompt tokens")
            if not (_nonneg_int(row.get("new_tokens"))
                    and row.get("new_tokens", 0) > 0):
                errors.append(f"line {i}: ok row generated no tokens")
            if row.get("finish_reason") not in FINISH_REASONS:
                errors.append(
                    f"line {i}: 'finish_reason' {row.get('finish_reason')!r} "
                    f"not in {FINISH_REASONS}"
                )
            lat = {}
            for name in ("ttft_s", "tpot_s", "e2e_s"):
                v = row.get(name)
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or not math.isfinite(v) or v < 0:
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative finite number")
                else:
                    lat[name] = float(v)
            if "ttft_s" in lat and "e2e_s" in lat \
                    and lat["ttft_s"] > lat["e2e_s"]:
                errors.append(
                    f"line {i}: ttft_s {lat['ttft_s']} exceeds e2e_s "
                    f"{lat['e2e_s']}"
                )
            for name in ("queue_s", "occ_mean"):
                v = row.get(name)
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or not math.isfinite(v) or v < 0:
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative finite number")
            if not _nonneg_int(row.get("occ_max")):
                errors.append(f"line {i}: 'occ_max' {row.get('occ_max')!r} "
                              "is not a non-negative integer")
            slot = row.get("slot")
            if isinstance(slot, bool) or not isinstance(slot, (int, float)) \
                    or not math.isfinite(slot) or float(slot) != int(slot) \
                    or slot < -1:
                errors.append(f"line {i}: 'slot' {slot!r} is not an "
                              "integer >= -1")
            # prefix-cache accounting (ISSUE 14; present on engines built
            # since then — validated when present so pre-ISSUE-14 logs in
            # ARTIFACTS stay green): the cached/prefilled split must tile
            # the prompt exactly.
            split = {}
            for name in ("cached_prefix_tokens", "prefill_tokens"):
                v = row.get(name)
                if v is None:
                    continue
                if not _nonneg_int(v):
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative integer")
                else:
                    split[name] = int(v)
            if len(split) == 2 and _nonneg_int(row.get("prompt_tokens")) \
                    and sum(split.values()) != int(row["prompt_tokens"]):
                errors.append(
                    f"line {i}: cached_prefix_tokens "
                    f"{split['cached_prefix_tokens']} + prefill_tokens "
                    f"{split['prefill_tokens']} != prompt_tokens "
                    f"{int(row['prompt_tokens'])}"
                )
            itl = row.get("itl_max_s")
            if itl is not None and (
                isinstance(itl, bool) or not isinstance(itl, (int, float))
                or not math.isfinite(itl) or itl < 0
            ):
                errors.append(f"line {i}: 'itl_max_s' {itl!r} is not a "
                              "non-negative finite number")
            # speculative-decoding accounting (ISSUE 15; present on
            # engines built since then — validated when present): both
            # non-negative ints, and a request can never have more
            # drafts accepted than proposed.
            spec = {}
            for name in ("drafted", "accepted"):
                v = row.get(name)
                if v is None:
                    continue
                if not _nonneg_int(v):
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative integer")
                else:
                    spec[name] = int(v)
            if len(spec) == 2 and spec["accepted"] > spec["drafted"]:
                errors.append(
                    f"line {i}: 'accepted' {spec['accepted']} exceeds "
                    f"'drafted' {spec['drafted']}"
                )
            # spec_* mirror fields (ISSUE 16): the fleet-wide spelling of
            # the same per-request draft accounting.
            mirror = {}
            for name in ("spec_drafted", "spec_accepted"):
                v = row.get(name)
                if v is None:
                    continue
                if not _nonneg_int(v):
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative integer")
                else:
                    mirror[name] = int(v)
            if len(mirror) == 2 \
                    and mirror["spec_accepted"] > mirror["spec_drafted"]:
                errors.append(
                    f"line {i}: 'spec_accepted' {mirror['spec_accepted']} "
                    f"exceeds 'spec_drafted' {mirror['spec_drafted']}"
                )
            # exclusive tail-latency attribution (ISSUE 16; validated
            # when present so pre-ISSUE-16 logs stay green): each
            # component non-negative finite, and the sum must not exceed
            # e2e by more than the documented 5% (+ rounding epsilon) —
            # the components are exclusive, never overlapping.
            attr = {}
            for name in REQUEST_ATTR_FIELDS:
                v = row.get(name)
                if v is None:
                    continue
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or not math.isfinite(v) or v < 0:
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative finite number")
                else:
                    attr[name] = float(v)
            if len(attr) == len(REQUEST_ATTR_FIELDS) and "e2e_s" in lat:
                total = sum(attr.values())
                if total > lat["e2e_s"] * 1.05 + 1e-4:
                    errors.append(
                        f"line {i}: attribution sum {total:.6f} exceeds "
                        f"e2e_s {lat['e2e_s']:.6f} by more than 5% — the "
                        "components are not exclusive"
                    )
    return errors, warnings


def _nonneg_finite(v) -> bool:
    return not isinstance(v, bool) and isinstance(v, (int, float)) \
        and math.isfinite(v) and v >= 0


def _check_trace_span(row: dict, i: int) -> list[str]:
    errors = []
    for key in ("name", "trace_id", "span_id"):
        if not isinstance(row.get(key), str) or not row[key]:
            errors.append(f"line {i}: span {key!r} {row.get(key)!r} is "
                          "not a non-empty string")
    if "parent_id" in row and not isinstance(row["parent_id"], str):
        errors.append(f"line {i}: span 'parent_id' is not a string")
    for key in ("t0", "dur_s"):
        if not _nonneg_finite(row.get(key)):
            errors.append(f"line {i}: span {key!r} {row.get(key)!r} is "
                          "not a non-negative finite number")
    if isinstance(row.get("proc"), bool) \
            or not isinstance(row.get("proc"), int):
        errors.append(f"line {i}: span 'proc' {row.get('proc')!r} is not "
                      "an integer")
    return errors


#: The compile log's rows (obs/tracing.py ``install_compile_log``, ISSUE
#: 50 — duplicated, stdlib-only) and what ``cache`` says on a
#: ``compile.backend`` row.
COMPILE_SPAN_NAMES = ("compile.trace", "compile.lower", "compile.backend",
                      "compile.cache_load")
COMPILE_CACHE_STATES = ("hit", "miss", "off")
#: The sums a start-up phase row carries of the compile roots that ended
#: inside it, and ``startup.ready`` of the whole trace beside its own.
COMPILE_SUM_FIELDS = ("trace_s", "lower_s", "backend_s", "cache_load_s",
                      "programs")
STARTUP_READY_FIELDS = COMPILE_SUM_FIELDS + (
    "total_s", "cache_hits", "cache_misses", "unnamed_s")


def _check_compile_span(row: dict, i: int) -> list[str]:
    """A ``compile.*`` row names its ``program``; it is a child (of the
    start-up phase it ended in, or of the compile event it began in) or
    carries ``trace_id`` ``"compile"``; ``compile.backend`` says what the
    persistent cache said."""
    name, errors = row["name"], []
    if name not in COMPILE_SPAN_NAMES:
        errors.append(f"line {i}: unknown compile row {name!r} (known: "
                      f"{COMPILE_SPAN_NAMES})")
    if not isinstance(row.get("program"), str):
        errors.append(f"line {i}: {name!r} names no 'program'")
    if row["trace_id"] not in ("startup", "compile"):
        errors.append(f"line {i}: {name!r} has trace_id "
                      f"{row['trace_id']!r}, neither 'startup' nor "
                      "'compile'")
    elif "parent_id" not in row and row["trace_id"] != "compile":
        errors.append(f"line {i}: {name!r} under trace_id 'startup' is "
                      "no phase's child")
    if name == "compile.backend":
        if row.get("cache") not in COMPILE_CACHE_STATES:
            errors.append(f"line {i}: 'cache' {row.get('cache')!r} not in "
                          f"{COMPILE_CACHE_STATES}")
        if not _nonneg_finite(row.get("cache_load_s")) \
                or row["cache_load_s"] > row["dur_s"] + 1e-5:
            errors.append(f"line {i}: 'cache_load_s' "
                          f"{row.get('cache_load_s')!r} is not a part of "
                          f"dur_s {row['dur_s']!r}")
    return errors


def _check_startup_ready(row: dict, i: int, top: dict) -> list[str]:
    """``startup.ready`` against ``top``, the sums of the top-level phases
    before it: every field there and a non-negative finite number,
    ``total_s`` their seconds with ``unnamed_s`` between them, the
    compile sums theirs (each row rounds to a microsecond)."""
    errors = []
    for key in STARTUP_READY_FIELDS:
        if not _nonneg_finite(row.get(key)):
            errors.append(f"line {i}: 'startup.ready' {key!r} "
                          f"{row.get(key)!r} is not a non-negative finite "
                          "number")
    if errors:
        return errors
    if row["unnamed_s"] > 1e-3:
        errors.append(f"line {i}: 'startup.ready' leaves unnamed_s "
                      f"{row['unnamed_s']!r} of start-up to no phase")
    if abs(row["total_s"] - row["unnamed_s"] - top.get("dur_s", 0.0)) > 1e-3:
        errors.append(
            f"line {i}: 'startup.ready' total_s {row['total_s']!r} less "
            f"unnamed_s is not the top-level phases' "
            f"{top.get('dur_s', 0.0):.6f}")
    for key in COMPILE_SUM_FIELDS:
        if abs(row[key] - top.get(key, 0.0)) > 1e-3:
            errors.append(
                f"line {i}: 'startup.ready' {key!r} {row[key]!r} is not "
                f"the top-level phases' {top.get(key, 0.0):.6f}")
    return errors


def check_trace_file(path: str) -> tuple[list[str], list[str]]:
    """Validate a ``trace.jsonl`` (obs/tracing.py): per-step span-tree
    rows (``step`` an integer or null, ``spans`` a list of
    ``{"name", "dur_s", "children"?}`` trees, ``k`` and ``t_wall`` on
    anchored rows), ``kind: "anomaly"`` events, and ``kind: "span"``
    cross-process rows (string ``name``/``trace_id``/``span_id``,
    absolute ``t0``, ``dur_s >= 0``, integer ``proc``).  The start-up
    phases (``startup.*`` spans, one process's ``PhaseTrace``) all carry
    ``trace_id`` ``"startup"``, and those with no ``parent_id`` tile time
    in file order: none starts before the one before it ends
    (``startup.first_request`` after ``startup.listen`` like any other).
    ``startup.ready`` is the summary and no tile: its ``total_s`` is the
    top-level phases' seconds (``unnamed_s`` what they leave: rounding)
    and its compile sums are theirs.  ``compile.*`` rows are
    :func:`_check_compile_span`'s."""
    errors: list[str] = []

    def tree_errors(node, i) -> list[str]:
        if not isinstance(node, dict) or not isinstance(
                node.get("name"), str):
            return [f"line {i}: span tree node {node!r} has no name"]
        out = []
        if not _nonneg_finite(node.get("dur_s")):
            out.append(f"line {i}: span {node['name']!r} dur_s "
                       f"{node.get('dur_s')!r} is not a non-negative "
                       "finite number")
        for c in node.get("children", []):
            out.extend(tree_errors(c, i))
        return out

    startup_end: dict[int, float] = {}   # proc -> end of its last phase
    # proc -> what its top-level phases sum to, for startup.ready
    startup_sums: dict[int, dict[str, float]] = {}
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: not a JSON object")
                continue
            kind = row.get("kind")
            if kind == "span":
                errs = _check_trace_span(row, i)
                errors.extend(errs)
                name = row.get("name")
                if not errs and name.startswith("compile."):
                    errors.extend(_check_compile_span(row, i))
                if errs or not name.startswith("startup."):
                    continue
                if row["trace_id"] != "startup":
                    errors.append(f"line {i}: {name!r} has trace_id "
                                  f"{row['trace_id']!r}, not 'startup'")
                for key in COMPILE_SUM_FIELDS:
                    if key in row and not _nonneg_finite(row[key]):
                        errors.append(f"line {i}: {name!r} {key!r} "
                                      f"{row[key]!r} is not a non-negative "
                                      "finite number")
                if name == "startup.ready":
                    # (a restarted run appends its own start-up)
                    errors.extend(_check_startup_ready(
                        row, i, startup_sums.pop(row["proc"], {})))
                elif "parent_id" not in row:
                    sums = startup_sums.setdefault(row["proc"], {})
                    for key in ("dur_s",) + COMPILE_SUM_FIELDS:
                        if _nonneg_finite(row.get(key)):
                            sums[key] = sums.get(key, 0.0) + row[key]
                    end = startup_end.get(row["proc"])
                    if end is not None and row["t0"] < end - 1e-5:
                        errors.append(
                            f"line {i}: {name!r} starts at {row['t0']:.6f}"
                            f", before the previous start-up phase ends "
                            f"({end:.6f})")
                    startup_end[row["proc"]] = row["t0"] + row["dur_s"]
            elif kind == "anomaly":
                if not isinstance(row.get("anomaly"), str):
                    errors.append(f"line {i}: anomaly row has no kind name")
            elif kind is not None:
                errors.append(f"line {i}: unknown trace row kind {kind!r}")
            else:
                step = row.get("step", "missing")
                if step is not None and (isinstance(step, bool)
                                         or not isinstance(step, int)):
                    errors.append(f"line {i}: 'step' {step!r} is neither "
                                  "an integer nor null")
                if not isinstance(row.get("spans"), list):
                    errors.append(f"line {i}: step row has no 'spans' list")
                    continue
                for node in row["spans"]:
                    errors.extend(tree_errors(node, i))
    return errors, []


def check_steps_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one engine step log ``steps.jsonl`` (serve/engine.py
    ``_log_step``; docs/API.md "Serving observability"): every row one
    JSON object with finite non-decreasing ``t``, a positive integer
    ``step`` strictly increasing across the file, a ``phase`` of
    ``"idle"`` or "+"-joined tokens from :data:`STEP_PHASE_TOKENS`,
    non-negative integer count fields (:data:`STEP_COUNT_FIELDS`, with
    ``budget_stall`` in {0, 1} and ``spec_accepted <= spec_drafted``;
    where present, ``device_sampled <= occupancy`` and ``logits_fetched``
    in {0, 1}),
    and non-negative finite wall fields whose phase split tiles the
    iteration: ``admit_s + prefill_s + decode_s <= step_s`` (up to
    rounding); where present, the leaves and the engine thread's account
    (:data:`STEP_LEAF_FIELDS`), with ``dispatch_s + prelaunch_s + fetch_s +
    commit_s <= decode_s`` and ``unnamed_s`` within 2 % of ``step_s``, and
    ``prefill_prelaunched <= prefill_chunks``."""
    errors: list[str] = []
    warnings: list[str] = []
    prev_t: float | None = None
    prev_step: int | None = None
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: row is {type(row).__name__}, "
                              "not an object")
                continue
            t = row.get("t")
            if isinstance(t, bool) or not isinstance(t, (int, float)) \
                    or not math.isfinite(t):
                errors.append(f"line {i}: 't' {t!r} is not a finite number")
            else:
                if prev_t is not None and t < prev_t:
                    errors.append(f"line {i}: 't' {t} decreases")
                prev_t = float(t)
            step = row.get("step")
            if not _nonneg_int(step) or int(step) < 1:
                errors.append(f"line {i}: 'step' {step!r} is not a "
                              "positive integer")
            else:
                step = int(step)
                if prev_step is not None and step <= prev_step:
                    errors.append(f"line {i}: 'step' {step} does not "
                                  f"increase (previous {prev_step})")
                prev_step = step if prev_step is None \
                    else max(prev_step, step)
            phase = row.get("phase")
            if not isinstance(phase, str) or not phase:
                errors.append(f"line {i}: 'phase' {phase!r} is not a "
                              "non-empty string")
            elif phase != "idle":
                for tok in phase.split("+"):
                    if tok not in STEP_PHASE_TOKENS:
                        errors.append(
                            f"line {i}: phase token {tok!r} not in "
                            f"{STEP_PHASE_TOKENS}"
                        )
            counts = {}
            for name in STEP_COUNT_FIELDS:
                v = row.get(name)
                if not _nonneg_int(v):
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative integer")
                else:
                    counts[name] = int(v)
            if counts.get("budget_stall", 0) > 1:
                errors.append(f"line {i}: 'budget_stall' "
                              f"{counts['budget_stall']} is not 0/1")
            ahead = row.get("prefill_prelaunched")
            if ahead is not None and (
                    not _nonneg_int(ahead)
                    or ahead > counts.get("prefill_chunks", ahead)):
                errors.append(
                    f"line {i}: 'prefill_prelaunched' {ahead!r} is not a "
                    "count of this record's 'prefill_chunks' "
                    f"{row.get('prefill_chunks')!r}")
            if "spec_drafted" in counts and "spec_accepted" in counts \
                    and counts["spec_accepted"] > counts["spec_drafted"]:
                errors.append(
                    f"line {i}: 'spec_accepted' {counts['spec_accepted']} "
                    f"exceeds 'spec_drafted' {counts['spec_drafted']}"
                )
            walls = {}
            for name in STEP_WALL_FIELDS:
                v = row.get(name)
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or not math.isfinite(v) or v < 0:
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative finite number")
                else:
                    walls[name] = float(v)
            if all(k in walls for k in ("admit_s", "prefill_s", "decode_s",
                                        "step_s")):
                parts = (walls["admit_s"] + walls["prefill_s"]
                         + walls["decode_s"])
                if parts > walls["step_s"] + 1e-5:
                    errors.append(
                        f"line {i}: admit_s+prefill_s+decode_s "
                        f"{parts:.6f} exceeds step_s "
                        f"{walls['step_s']:.6f}"
                    )
            for name in STEP_LEAF_FIELDS:
                v = row.get(name)
                if v is None:
                    continue
                if not _nonneg_finite(v):
                    errors.append(f"line {i}: {name!r} {v!r} is not a "
                                  "non-negative finite number")
                else:
                    walls[name] = float(v)
            if all(k in walls for k in ("dispatch_s", "fetch_s", "commit_s",
                                        "decode_s")):
                parts = (walls["dispatch_s"] + walls.get("prelaunch_s", 0.0)
                         + walls["fetch_s"] + walls["commit_s"])
                if parts > walls["decode_s"] + 1e-5:
                    errors.append(
                        f"line {i}: dispatch_s+fetch_s+commit_s "
                        f"{parts:.6f} exceeds decode_s "
                        f"{walls['decode_s']:.6f}")
            errors.extend(_check_compiled(row, f"line {i}"))
            if "unnamed_s" in walls and "step_s" in walls \
                    and walls["unnamed_s"] > 0.02 * walls["step_s"] + 1e-4:
                errors.append(
                    f"line {i}: unnamed_s {walls['unnamed_s']:.6f} is more "
                    f"than 2 % of step_s {walls['step_s']:.6f}: the "
                    "iteration's leaves do not tile it")
            lines = row.get("stream_lines")
            if lines is not None and not _nonneg_int(lines):
                errors.append(f"line {i}: 'stream_lines' {lines!r} is not "
                              "a non-negative integer")
            # ISSUE 19's step fields (validated when present so
            # pre-ISSUE-19 logs stay green): the pool's refcount-weighted
            # block census at the iteration boundary, and the admission
            # count broken down by tenant.
            billed = row.get("kv_blocks_billed")
            if billed is not None and (
                isinstance(billed, bool)
                or not isinstance(billed, (int, float))
                or not math.isfinite(billed) or billed < 0
            ):
                errors.append(f"line {i}: 'kv_blocks_billed' {billed!r} is "
                              "not a non-negative finite number")
            # where an iteration's tokens came from (PR 31; validated when
            # present so older logs stay green): the slots whose token came
            # off the device with the step, of the slots decoding, and
            # whether the logits were fetched for the rest.
            sampled = row.get("device_sampled")
            if sampled is not None and (
                not _nonneg_int(sampled)
                or int(sampled) > counts.get("occupancy", int(sampled))
            ):
                errors.append(
                    f"line {i}: 'device_sampled' {sampled!r} is not an "
                    f"integer in [0, occupancy {counts.get('occupancy')}]")
            fetched = row.get("logits_fetched")
            if fetched is not None and (
                    not _nonneg_int(fetched) or int(fetched) > 1):
                errors.append(
                    f"line {i}: 'logits_fetched' {fetched!r} is not 0/1")
            adm_t = row.get("admitted_tenants")
            if adm_t is not None:
                if not isinstance(adm_t, dict) or not adm_t:
                    errors.append(f"line {i}: 'admitted_tenants' {adm_t!r} "
                                  "is not a non-empty object")
                else:
                    ok_counts = True
                    for tenant, n in adm_t.items():
                        if not isinstance(tenant, str) \
                                or not _TENANT_RE.match(tenant):
                            errors.append(
                                f"line {i}: admitted_tenants key "
                                f"{tenant!r} is not a valid tenant"
                            )
                        if not _nonneg_int(n) or int(n) < 1:
                            errors.append(
                                f"line {i}: admitted_tenants[{tenant!r}] "
                                f"{n!r} is not a positive integer"
                            )
                            ok_counts = False
                    if ok_counts and "admitted" in counts \
                            and sum(adm_t.values()) != counts["admitted"]:
                        errors.append(
                            f"line {i}: admitted_tenants sum "
                            f"{sum(adm_t.values())} != 'admitted' "
                            f"{counts['admitted']}"
                        )
    return errors, warnings


def check_history_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one metrics-history tick log ``history.jsonl``
    (obs/tsdb.py ``MetricsHistory``; docs/API.md "Serving
    observability"): every row one JSON object with finite
    non-decreasing ``t`` and a ``values`` object mapping well-formed
    metric names (:data:`_HISTORY_NAME_RE`) to finite numbers — the
    writer filters non-finite samples, so a sentinel string here is a
    corruption — with per-row and whole-file name cardinality bounded by
    :data:`HISTORY_MAX_SERIES` (the store's fixed-memory contract)."""
    errors: list[str] = []
    warnings: list[str] = []
    prev_t: float | None = None
    all_names: set[str] = set()
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: row is {type(row).__name__}, "
                              "not an object")
                continue
            t = row.get("t")
            if isinstance(t, bool) or not isinstance(t, (int, float)) \
                    or not math.isfinite(t):
                errors.append(f"line {i}: 't' {t!r} is not a finite number")
            else:
                if prev_t is not None and t < prev_t:
                    errors.append(f"line {i}: 't' {t} decreases")
                prev_t = float(t)
            values = row.get("values")
            if not isinstance(values, dict):
                errors.append(f"line {i}: 'values' is "
                              f"{type(values).__name__}, not an object")
                continue
            if len(values) > HISTORY_MAX_SERIES:
                errors.append(
                    f"line {i}: {len(values)} series in one tick exceeds "
                    f"the {HISTORY_MAX_SERIES}-series cardinality bound"
                )
            for name, v in values.items():
                if not isinstance(name, str) \
                        or not _HISTORY_NAME_RE.match(name):
                    errors.append(f"line {i}: metric name {name!r} is "
                                  "malformed")
                    continue
                all_names.add(name)
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or not math.isfinite(v):
                    errors.append(f"line {i}: values[{name!r}] {v!r} is "
                                  "not a finite number")
    if len(all_names) > HISTORY_MAX_SERIES:
        errors.append(
            f"{len(all_names)} distinct series across the file exceeds "
            f"the {HISTORY_MAX_SERIES}-series cardinality bound"
        )
    return errors, warnings


def check_flash_cache_doc(doc) -> tuple[list[str], list[str]]:
    """Validate one parsed flash-blocks autotune cache
    (``ops/flash_tuning.py`` format): version 1, an ``entries`` list
    whose rows carry non-empty ``platform``/``dtype`` strings, positive
    int ``seq``/``depth``/``block_q``/``block_k`` with both blocks
    dividing ``seq`` (a non-dividing entry can never be consulted — it
    is a corrupt or hand-mangled cache), a known ``source``, and a
    non-negative finite ``ms`` when present."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"], []
    if doc.get("version") != 1:
        errors.append(f"'version' {doc.get('version')!r} != 1")
    entries = doc.get("entries")
    if not isinstance(entries, list):
        errors.append("'entries' is missing or not a list")
        return errors, warnings
    for i, e in enumerate(entries):
        where = f"entries[{i}]"
        if not isinstance(e, dict):
            errors.append(f"{where}: not an object")
            continue
        for k in ("platform", "dtype"):
            if not isinstance(e.get(k), str) or not e.get(k):
                errors.append(f"{where}: {k!r} {e.get(k)!r} is not a "
                              "non-empty string")
        ints = {}
        for k in ("seq", "depth", "block_q", "block_k"):
            v = e.get(k)
            if isinstance(v, bool) or not isinstance(v, int) or v <= 0:
                errors.append(f"{where}: {k!r} {v!r} is not a positive "
                              "integer")
            else:
                ints[k] = v
        if "seq" in ints:
            for k in ("block_q", "block_k"):
                if k in ints and ints["seq"] % ints[k]:
                    errors.append(
                        f"{where}: {k} {ints[k]} does not divide seq "
                        f"{ints['seq']}"
                    )
        for k in ("batch", "heads"):
            v = e.get(k)
            if v is not None and (
                isinstance(v, bool) or not isinstance(v, int) or v <= 0
            ):
                errors.append(f"{where}: {k!r} {v!r} is not a positive "
                              "integer")
        src = e.get("source")
        if src is not None and src not in FLASH_SOURCES:
            errors.append(f"{where}: 'source' {src!r} not in "
                          f"{FLASH_SOURCES}")
        ms = e.get("ms")
        if ms is not None and (
            isinstance(ms, bool) or not isinstance(ms, (int, float))
            or not math.isfinite(ms) or ms < 0
        ):
            errors.append(f"{where}: 'ms' {ms!r} is not a non-negative "
                          "finite number")
    return errors, warnings


def check_prom_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one ``metrics.prom`` snapshot (obs registry text
    exposition): every non-comment line must be a well-formed sample with
    a parseable value, and every ``collective_dispatch_seconds*`` sample
    carrying an ``op`` label must use a KNOWN collective op
    (:data:`COLLECTIVE_OPS`) — a typo'd or unregistered op label would
    silently fork the histogram's time series."""
    errors: list[str] = []
    warnings: list[str] = []
    spec_totals: dict[str, float] = {}
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = _PROM_SAMPLE_RE.match(line)
            if not m:
                errors.append(f"line {i}: not a prometheus sample: {line!r}")
                continue
            name, labelstr, value = m.groups()
            try:
                float(value)  # accepts nan/+Inf/-Inf spellings
            except ValueError:
                errors.append(
                    f"line {i}: sample {name} value {value!r} is not a number"
                )
            if name.startswith("collective_dispatch_seconds") and labelstr:
                labels = dict(_PROM_LABEL_RE.findall(labelstr))
                op = labels.get("op")
                if op is not None and op not in COLLECTIVE_OPS:
                    errors.append(
                        f"line {i}: {name} carries unknown collective op "
                        f"{op!r} (known: {COLLECTIVE_OPS})"
                    )
                ov = labels.get("overlapped")
                if ov is not None and ov not in OVERLAPPED_VALUES:
                    errors.append(
                        f"line {i}: {name} carries unknown overlapped "
                        f"value {ov!r} (known: {OVERLAPPED_VALUES})"
                    )
            if name.startswith(
                ("data_prefetch_depth", "data_prefetch_resizes")
            ) and labelstr:
                labels = dict(_PROM_LABEL_RE.findall(labelstr))
                comp = labels.get("component")
                if comp is not None and comp not in PREFETCH_COMPONENTS:
                    errors.append(
                        f"line {i}: {name} carries unknown prefetch "
                        f"component {comp!r} (known: {PREFETCH_COMPONENTS})"
                    )
                direction = labels.get("direction")
                if direction is not None \
                        and direction not in PREFETCH_DIRECTIONS:
                    errors.append(
                        f"line {i}: {name} carries unknown resize "
                        f"direction {direction!r} "
                        f"(known: {PREFETCH_DIRECTIONS})"
                    )
            if name.startswith("fleet_peers") and labelstr:
                labels = dict(_PROM_LABEL_RE.findall(labelstr))
                state = labels.get("state")
                if state is not None and state not in FLEET_PEER_STATES:
                    errors.append(
                        f"line {i}: {name} carries unknown fleet peer "
                        f"state {state!r} (known: {FLEET_PEER_STATES})"
                    )
            if name in SERVE_PREFIX_COUNTERS or name in SERVE_PREFIX_RATIOS \
                    or name in SERVE_SPEC_COUNTERS:
                try:
                    v = float(value)
                except ValueError:
                    v = None  # already reported above
                if v is not None and math.isfinite(v):
                    if v < 0:
                        errors.append(
                            f"line {i}: {name} is negative ({value}) — "
                            "serving prefix-cache/speculation samples are "
                            "non-negative"
                        )
                    elif name in SERVE_PREFIX_RATIOS and v > 1.0:
                        errors.append(
                            f"line {i}: {name} {value} is not in [0, 1]"
                        )
                    if name in SERVE_SPEC_COUNTERS:
                        if labelstr:
                            errors.append(
                                f"line {i}: {name} carries unexpected "
                                f"labels {labelstr!r} (the speculation "
                                "counters are unlabeled)"
                            )
                        spec_totals[name] = v
            if name.startswith(
                ("pipeline_handoff_seconds", "pipeline_mpmd_stall_seconds")
            ):
                labels = dict(_PROM_LABEL_RE.findall(labelstr or ""))
                stage = labels.get("stage")
                if stage is None:
                    errors.append(
                        f"line {i}: {name} sample is missing the 'stage' "
                        "label"
                    )
                elif not stage.isdigit():
                    errors.append(
                        f"line {i}: {name} carries non-numeric stage "
                        f"label {stage!r}"
                    )
            if name.startswith(("rpc_retries_total",
                                "rpc_deadline_exceeded_total",
                                "rpc_attempt_seconds", "breaker_state",
                                "breaker_transitions_total")):
                labels = dict(_PROM_LABEL_RE.findall(labelstr or ""))
                ep = labels.get("endpoint")
                if ep is None:
                    errors.append(
                        f"line {i}: {name} sample is missing the "
                        "'endpoint' label"
                    )
                else:
                    bad = _check_endpoint_value(ep)
                    if bad:
                        errors.append(f"line {i}: {name} endpoint {bad}")
                outcome = labels.get("outcome")
                if name.startswith("rpc_retries_total") \
                        and outcome not in RPC_RETRY_OUTCOMES:
                    errors.append(
                        f"line {i}: {name} carries unknown retry outcome "
                        f"{outcome!r} (known: {RPC_RETRY_OUTCOMES})"
                    )
                to = labels.get("to")
                if name.startswith("breaker_transitions_total") \
                        and to not in BREAKER_TO_STATES:
                    errors.append(
                        f"line {i}: {name} carries unknown breaker state "
                        f"{to!r} (known: {BREAKER_TO_STATES})"
                    )
                if name == "breaker_state":
                    try:
                        if float(value) not in (0.0, 1.0, 2.0):
                            errors.append(
                                f"line {i}: breaker_state value {value!r} "
                                "is not a state encoding (0=closed, "
                                "1=half_open, 2=open)"
                            )
                    except ValueError:
                        pass  # already reported above
            if name.startswith("elastic_resizes_total"):
                labels = dict(_PROM_LABEL_RE.findall(labelstr or ""))
                outcome = labels.get("outcome")
                if outcome not in ELASTIC_RESIZE_OUTCOMES:
                    errors.append(
                        f"line {i}: {name} carries unknown resize outcome "
                        f"{outcome!r} (known: {ELASTIC_RESIZE_OUTCOMES})"
                    )
            if name.startswith("dynamics_"):
                labels = dict(_PROM_LABEL_RE.findall(labelstr or ""))
                module = labels.get("module")
                if module is not None and not _MODULE_NAME_RE.match(module):
                    errors.append(
                        f"line {i}: {name} carries malformed dynamics "
                        f"module name {module!r}"
                    )
                if name in ("dynamics_nonfinite_grads_total",
                            "dynamics_provenance_total"):
                    try:
                        if float(value) < 0:
                            errors.append(
                                f"line {i}: {name} is negative ({value}) — "
                                "the dynamics counters are monotonic"
                            )
                    except ValueError:
                        pass  # already reported above
            if name == "slo_burn_rate":
                labels = dict(_PROM_LABEL_RE.findall(labelstr or ""))
                window = labels.get("window")
                if window not in SLO_WINDOWS:
                    errors.append(
                        f"line {i}: {name} carries unknown slo window "
                        f"{window!r} (known: {SLO_WINDOWS})"
                    )
                if not labels.get("slo"):
                    errors.append(
                        f"line {i}: {name} sample is missing the 'slo' "
                        "label"
                    )
                try:
                    if float(value) < 0:
                        errors.append(
                            f"line {i}: {name} value {value!r} is "
                            "negative — burn rates are non-negative by "
                            "construction"
                        )
                except ValueError:
                    pass  # already reported above
    if len(spec_totals) == 2 and (
        spec_totals["serve_spec_accepted_total"]
        > spec_totals["serve_spec_drafted_total"]
    ):
        errors.append(
            f"serve_spec_accepted_total "
            f"{spec_totals['serve_spec_accepted_total']:g} exceeds "
            f"serve_spec_drafted_total "
            f"{spec_totals['serve_spec_drafted_total']:g} — the verifier "
            "cannot accept more drafts than were proposed"
        )
    return errors, warnings


def check_slo_rules_doc(doc) -> tuple[list[str], list[str]]:
    """Validate one parsed SLO rule file (``obs/slo.py`` schema: a
    ``{"slos": [...]}`` object or bare rule list — see the module
    docstring for the per-rule constraints)."""
    errors: list[str] = []
    warnings: list[str] = []
    if isinstance(doc, dict):
        rules = doc.get("slos")
        if not isinstance(rules, list):
            return ["'slos' is missing or not a list"], []
    elif isinstance(doc, list):
        rules = doc
    else:
        return [f"document is {type(doc).__name__}, not an object or "
                "list"], []
    seen: set[str] = set()
    for i, rule in enumerate(rules):
        where = f"slos[{i}]"
        if not isinstance(rule, dict):
            errors.append(f"{where}: not an object")
            continue
        name = rule.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: 'name' {name!r} is not a non-empty "
                          "string")
        elif name in seen:
            errors.append(f"{where}: duplicate rule name {name!r}")
        else:
            seen.add(name)
        kind = rule.get("kind")
        if kind not in SLO_RULE_KINDS:
            errors.append(f"{where}: 'kind' {kind!r} not in "
                          f"{SLO_RULE_KINDS}")
        metric = rule.get("metric")
        if not isinstance(metric, str) or not metric:
            errors.append(f"{where}: 'metric' {metric!r} is not a "
                          "non-empty string")
        obj = rule.get("objective")
        if isinstance(obj, bool) or not isinstance(obj, (int, float)) \
                or not math.isfinite(obj) or not 0.0 <= obj < 1.0:
            errors.append(f"{where}: 'objective' {obj!r} must be a finite "
                          "number in [0, 1)")
        thr = rule.get("threshold")
        if kind == "histogram_under":
            if isinstance(thr, bool) or not isinstance(thr, (int, float)) \
                    or not math.isfinite(thr) or thr <= 0:
                errors.append(f"{where}: 'threshold' {thr!r} must be a "
                              "positive finite number for histogram_under")
        elif thr is not None:
            errors.append(f"{where}: 'threshold' is only valid for "
                          "histogram_under rules")
        windows = {}
        for key in ("fast_window_s", "slow_window_s"):
            v = rule.get(key, 60.0 if key.startswith("fast") else 600.0)
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or not math.isfinite(v) or v <= 0:
                errors.append(f"{where}: {key!r} {v!r} must be a positive "
                              "finite number")
            else:
                windows[key] = float(v)
        if len(windows) == 2 \
                and windows["fast_window_s"] > windows["slow_window_s"]:
            errors.append(
                f"{where}: fast_window_s {windows['fast_window_s']} "
                f"exceeds slow_window_s {windows['slow_window_s']}"
            )
        for key in ("fast_burn", "slow_burn"):
            v = rule.get(key, 1.0)
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or not math.isfinite(v) or v <= 0:
                errors.append(f"{where}: {key!r} {v!r} must be a positive "
                              "finite number (burn-rate threshold)")
    return errors, warnings


def check_fleet_doc(doc) -> tuple[list[str], list[str]]:
    """Validate one parsed fleet aggregator snapshot (``obs/fleet.py``
    ``fleet.json``): peer states from the known set, non-negative
    scrape/age counts, a non-negative worst-spread ratio."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"], []
    peers = doc.get("peers")
    if not isinstance(peers, dict):
        errors.append("'peers' is missing or not an object")
        peers = {}
    for name, p in peers.items():
        where = f"peers[{name!r}]"
        if not isinstance(p, dict):
            errors.append(f"{where}: not an object")
            continue
        state = p.get("state")
        if state not in FLEET_PEER_STATES:
            errors.append(f"{where}: 'state' {state!r} not in "
                          f"{FLEET_PEER_STATES}")
        addr = p.get("addr")
        if not isinstance(addr, str) or not addr:
            errors.append(f"{where}: 'addr' {addr!r} is not a non-empty "
                          "string")
        age = p.get("age_s")
        if age is not None and (
            isinstance(age, bool) or not isinstance(age, (int, float))
            or not math.isfinite(age) or age < 0
        ):
            errors.append(f"{where}: 'age_s' {age!r} is not a "
                          "non-negative finite number or null")
        for key in ("ok", "errors"):
            if not _nonneg_int(p.get(key)):
                errors.append(f"{where}: {key!r} {p.get(key)!r} is not a "
                              "non-negative integer")
    states = doc.get("states")
    if states is not None:
        if not isinstance(states, dict):
            errors.append("'states' is not an object")
        else:
            for s, n in states.items():
                if s not in FLEET_PEER_STATES:
                    errors.append(f"states: unknown state {s!r} "
                                  f"(known: {FLEET_PEER_STATES})")
                if not _nonneg_int(n):
                    errors.append(f"states[{s!r}]: {n!r} is not a "
                                  "non-negative integer")
    worst = doc.get("worst_spread")
    if worst is not None:
        if not isinstance(worst, dict):
            errors.append("'worst_spread' is not an object or null")
        else:
            ratio = worst.get("ratio")
            if isinstance(ratio, bool) \
                    or not isinstance(ratio, (int, float)) \
                    or not math.isfinite(ratio) or ratio < 0:
                errors.append(f"worst_spread: 'ratio' {ratio!r} is not a "
                              "non-negative finite number")
    for key in ("scrape_rounds", "metrics_merged"):
        v = doc.get(key)
        if v is not None and not _nonneg_int(v):
            errors.append(f"{key!r} {v!r} is not a non-negative integer")
    return errors, warnings


def check_timeline_doc(doc) -> tuple[list[str], list[str]]:
    """Validate one Chrome-trace timeline document (``tools/timeline.py``
    output, fleet mode included): a ``traceEvents`` list of objects, each
    with a non-empty ``ph`` phase string, finite ``ts`` and non-negative
    finite ``dur`` where present."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"], []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["'traceEvents' is missing or not a list"], []
    for i, e in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(e, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = e.get("ph")
        if not isinstance(ph, str) or not ph:
            errors.append(f"{where}: 'ph' {ph!r} is not a non-empty string")
        ts = e.get("ts")
        if ts is not None and (
            isinstance(ts, bool) or not isinstance(ts, (int, float))
            or not math.isfinite(ts)
        ):
            errors.append(f"{where}: 'ts' {ts!r} is not a finite number")
        dur = e.get("dur")
        if dur is not None and (
            isinstance(dur, bool) or not isinstance(dur, (int, float))
            or not math.isfinite(dur) or dur < 0
        ):
            errors.append(f"{where}: 'dur' {dur!r} is not a non-negative "
                          "finite number")
    return errors, warnings


def _check_bucket_map(buckets, where: str) -> tuple[list[str], list[str]]:
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(buckets, dict):
        return [f"{where}: 'buckets' is "
                f"{type(buckets).__name__}, not an object"], []
    for k, v in buckets.items():
        if not isinstance(k, str) or not k:
            errors.append(f"{where}: bad bucket name {k!r}")
            continue
        if k not in GOODPUT_BUCKETS:
            warnings.append(f"{where}: unknown bucket {k!r}")
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not math.isfinite(v):
            errors.append(f"{where}: bucket {k!r} value {v!r} is not a "
                          "finite number")
        elif v < 0:
            errors.append(f"{where}: bucket {k!r} is negative ({v})")
    return errors, warnings


def check_goodput_doc(doc) -> tuple[list[str], list[str]]:
    """Validate one parsed ``goodput.json`` document (buckets exclusive by
    construction of a JSON object; non-negative; sum ≈ wall time)."""
    errors: list[str] = []
    warnings: list[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"], []
    gens = doc.get("generations")
    if not isinstance(gens, list) or not gens:
        errors.append("'generations' is missing or not a non-empty list")
        gens = []
    for i, g in enumerate(gens):
        where = f"generations[{i}]"
        if not isinstance(g, dict):
            errors.append(f"{where}: not an object")
            continue
        start = g.get("start_t")
        last = g.get("last_t")
        for name, v in (("start_t", start), ("last_t", last)):
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or not math.isfinite(v):
                errors.append(f"{where}: {name!r} {v!r} is not a "
                              "finite number")
        if isinstance(start, (int, float)) and isinstance(last, (int, float)) \
                and math.isfinite(start) and math.isfinite(last) \
                and last < start:
            errors.append(f"{where}: last_t {last} precedes start_t {start}")
        e, w = _check_bucket_map(g.get("buckets"), where)
        errors.extend(e)
        warnings.extend(w)
    merged = doc.get("merged")
    if not isinstance(merged, dict):
        errors.append("'merged' is missing or not an object")
        return errors, warnings
    e, w = _check_bucket_map(merged.get("buckets"), "merged")
    errors.extend(e)
    warnings.extend(w)
    wall = merged.get("wall_s")
    if isinstance(wall, bool) or not isinstance(wall, (int, float)) \
            or not math.isfinite(wall) or wall < 0:
        errors.append(f"merged: 'wall_s' {wall!r} is not a non-negative "
                      "finite number")
    elif not e and isinstance(merged.get("buckets"), dict):
        total = sum(
            v for v in merged["buckets"].values()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        )
        # 1% relative + a small absolute epsilon: per-bucket rounding to
        # 1 ms dominates on sub-second runs.
        tol = max(0.01 * wall, 0.05)
        if abs(total - wall) > tol:
            errors.append(
                f"merged: buckets sum to {total:.3f}s but wall_s is "
                f"{wall:.3f}s (tolerance {tol:.3f}s)"
            )
    frac = merged.get("goodput_fraction")
    if frac is not None and (
        isinstance(frac, bool) or not isinstance(frac, (int, float))
        or not math.isfinite(frac) or not 0.0 <= frac <= 1.0
    ):
        errors.append(f"merged: 'goodput_fraction' {frac!r} outside [0, 1]")
    return errors, warnings


def check_alerts_file(path: str) -> tuple[list[str], list[str]]:
    """Validate an ``alerts.jsonl`` stream (obs/alerts.py AlertManager):
    rows t-ordered, known kinds/severities/phases, every ``resolved`` row
    pairing an earlier ``fired`` id of the same rule, and the dedup
    invariant — never two OPEN alerts for one (rule, labels) key."""
    errors: list[str] = []
    warnings: list[str] = []
    prev_t: float | None = None
    prev_fired_id: int | None = None
    # alert id -> (rule, labels_key) for open (fired, unresolved) alerts
    open_by_id: dict = {}
    open_keys: set = set()
    required = ("t", "id", "rule", "kind", "severity", "phase", "labels")
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: row is not an object")
                continue
            missing = [k for k in required if k not in row]
            if missing:
                errors.append(f"line {i}: missing keys {missing}")
                continue
            t = row["t"]
            if not isinstance(t, (int, float)) or isinstance(t, bool) \
                    or not math.isfinite(t):
                errors.append(f"line {i}: 't' {t!r} is not a finite number")
            elif prev_t is not None and t < prev_t:
                errors.append(
                    f"line {i}: 't' went backwards ({t} < {prev_t})")
            else:
                prev_t = float(t)
            aid = row["id"]
            if isinstance(aid, bool) or not isinstance(aid, int) or aid < 0:
                errors.append(f"line {i}: 'id' {aid!r} is not a "
                              "non-negative int")
                continue
            if row["kind"] not in ALERT_KINDS:
                errors.append(f"line {i}: unknown kind {row['kind']!r} "
                              f"(known: {ALERT_KINDS})")
            if row["severity"] not in ALERT_SEVERITIES:
                errors.append(
                    f"line {i}: unknown severity {row['severity']!r} "
                    f"(known: {ALERT_SEVERITIES})")
            labels = row["labels"]
            if not isinstance(labels, dict):
                errors.append(f"line {i}: 'labels' is not an object")
                labels = {}
            key = (str(row["rule"]),
                   tuple(sorted((str(k), str(v))
                                for k, v in labels.items())))
            phase = row["phase"]
            if phase == "fired":
                if prev_fired_id is not None and aid <= prev_fired_id:
                    errors.append(
                        f"line {i}: fired id {aid} not increasing "
                        f"(previous fired id {prev_fired_id})")
                prev_fired_id = aid
                if key in open_keys:
                    errors.append(
                        f"line {i}: duplicate OPEN alert for rule "
                        f"{row['rule']!r} labels {dict(labels)!r} "
                        "(dedup invariant)")
                else:
                    open_keys.add(key)
                    open_by_id[aid] = key
            elif phase == "resolved":
                if aid not in open_by_id:
                    errors.append(
                        f"line {i}: resolved id {aid} has no earlier "
                        "unresolved 'fired' row")
                else:
                    fired_key = open_by_id.pop(aid)
                    open_keys.discard(fired_key)
                    if fired_key[0] != str(row["rule"]):
                        errors.append(
                            f"line {i}: resolved id {aid} names rule "
                            f"{row['rule']!r} but fired under "
                            f"{fired_key[0]!r}")
            else:
                errors.append(f"line {i}: unknown phase {phase!r} "
                              f"(known: {ALERT_PHASES})")
    if open_by_id:
        warnings.append(
            f"{len(open_by_id)} alert(s) still open at end of stream "
            f"(ids {sorted(open_by_id)}) — fine for a live file, "
            "suspicious for a finished run")
    return errors, warnings


def check_incident_manifest(path: str) -> tuple[list[str], list[str]]:
    """Validate an incident evidence-bundle ``manifest.json``
    (obs/alerts.py ``_write_incident``): required keys, known
    severity/kind, and every listed evidence file present next to it."""
    errors: list[str] = []
    warnings: list[str] = []
    try:
        doc = _load_json_doc(path)
    except (OSError, json.JSONDecodeError) as e:
        return [f"invalid JSON ({e})"], []
    if not isinstance(doc, dict):
        return ["manifest is not an object"], []
    required = ("id", "t", "rule", "kind", "severity", "labels", "files")
    missing = [k for k in required if k not in doc]
    if missing:
        return [f"missing keys {missing}"], []
    aid = doc["id"]
    if isinstance(aid, bool) or not isinstance(aid, int) or aid < 0:
        errors.append(f"'id' {aid!r} is not a non-negative int")
    t = doc["t"]
    if not isinstance(t, (int, float)) or isinstance(t, bool) \
            or not math.isfinite(t):
        errors.append(f"'t' {t!r} is not a finite number")
    if doc["kind"] not in ALERT_KINDS:
        errors.append(f"unknown kind {doc['kind']!r} (known: {ALERT_KINDS})")
    if doc["severity"] not in ALERT_SEVERITIES:
        errors.append(f"unknown severity {doc['severity']!r} "
                      f"(known: {ALERT_SEVERITIES})")
    if not isinstance(doc["labels"], dict):
        errors.append("'labels' is not an object")
    files = doc["files"]
    if not isinstance(files, list) or not all(
            isinstance(f, str) for f in files):
        errors.append("'files' is not a list of file names")
    else:
        bundle_dir = os.path.dirname(os.path.abspath(path))
        for name in files:
            if os.path.basename(name) != name:
                errors.append(f"evidence file {name!r} is not a bare "
                              "file name")
            elif not os.path.exists(os.path.join(bundle_dir, name)):
                errors.append(f"evidence file {name!r} listed in the "
                              "manifest is missing from the bundle")
        if not files:
            warnings.append("bundle lists no evidence files")
    return errors, warnings


def _num_or_sentinel(v) -> bool:
    """A dynamics stat value: a number, or a writer sentinel string."""
    if v in ("NaN", "Infinity", "-Infinity"):
        return True
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def check_dynamics_file(path: str) -> tuple[list[str], list[str]]:
    """Validate one ``dynamics.jsonl`` training-dynamics stream
    (obs/dynamics.py): non-decreasing ``t``; a constant positive
    ``every`` dividing every ``step`` (the in-graph ``lax.cond`` cadence
    contract — an off-cadence row means the gate is broken); step
    rewinds allowed (supervised restart replays the window) but never
    two consecutive rows for the same step; identifier-grammar module
    names; per-module stats finite or sentinel-flagged with
    non-negative integer ``nonfinite_grads`` counts summing to the
    row's ``nonfinite_total``."""
    errors: list[str] = []
    warnings: list[str] = []
    required = ("t", "step", "every", "global_grad_norm",
                "nonfinite_total", "modules")
    stats_known = ("grad_norm", "param_norm", "update_ratio",
                   "nonfinite_grads")
    prev_t: float | None = None
    prev_step: int | None = None
    file_every: int | None = None
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if not isinstance(row, dict):
                errors.append(f"line {i}: row is {type(row).__name__}, "
                              "not an object")
                continue
            missing = [k for k in required if k not in row]
            if missing:
                errors.append(f"line {i}: missing keys {missing}")
                continue
            t = row["t"]
            if not isinstance(t, (int, float)) or isinstance(t, bool) \
                    or not math.isfinite(t):
                errors.append(f"line {i}: 't' {t!r} is not a finite number")
            else:
                if prev_t is not None and t < prev_t:
                    errors.append(
                        f"line {i}: 't' went backwards "
                        f"({prev_t} -> {t})"
                    )
                prev_t = float(t)
            every = row["every"]
            if isinstance(every, bool) or not isinstance(every, int) \
                    or every <= 0:
                errors.append(f"line {i}: 'every' {every!r} is not a "
                              "positive integer")
                every = None
            elif file_every is None:
                file_every = every
            elif every != file_every:
                errors.append(
                    f"line {i}: 'every' changed mid-stream "
                    f"({file_every} -> {every}) — the cadence is fixed "
                    "at monitor construction"
                )
            step = row["step"]
            if isinstance(step, bool) or not isinstance(step, int) \
                    or step < 0:
                errors.append(f"line {i}: 'step' {step!r} is not a "
                              "non-negative integer")
            else:
                if every and step % every != 0:
                    errors.append(
                        f"line {i}: step {step} is not a multiple of the "
                        f"cadence ({every}) — the lax.cond gate booked an "
                        "off-cadence row"
                    )
                if prev_step is not None and step == prev_step:
                    errors.append(
                        f"line {i}: step {step} repeats the previous row "
                        "(rewinds after a restart are fine; an exact "
                        "repeat means double-booking)"
                    )
                elif prev_step is not None and step < prev_step:
                    warnings.append(
                        f"line {i}: step went backwards "
                        f"({prev_step} -> {step}) — supervised restart "
                        "replay"
                    )
                prev_step = step
            if not _num_or_sentinel(row["global_grad_norm"]):
                errors.append(
                    f"line {i}: 'global_grad_norm' "
                    f"{row['global_grad_norm']!r} is neither a number nor "
                    "a non-finite sentinel"
                )
            nft = row["nonfinite_total"]
            if isinstance(nft, bool) or not isinstance(nft, int) or nft < 0:
                errors.append(f"line {i}: 'nonfinite_total' {nft!r} is not "
                              "a non-negative integer")
                nft = None
            modules = row["modules"]
            if not isinstance(modules, dict):
                errors.append(f"line {i}: 'modules' is not an object")
                continue
            counted = 0
            for mname, stats in modules.items():
                if not isinstance(mname, str) \
                        or not _MODULE_NAME_RE.match(mname):
                    errors.append(f"line {i}: malformed module name "
                                  f"{mname!r}")
                if not isinstance(stats, dict):
                    errors.append(f"line {i}: module {mname!r} stats is "
                                  f"{type(stats).__name__}, not an object")
                    continue
                for sk, sv in stats.items():
                    if sk not in stats_known:
                        warnings.append(
                            f"line {i}: module {mname!r} carries unknown "
                            f"stat {sk!r} (known: {stats_known})"
                        )
                    elif sk == "nonfinite_grads":
                        if isinstance(sv, bool) or not isinstance(sv, int) \
                                or sv < 0:
                            errors.append(
                                f"line {i}: module {mname!r} "
                                f"'nonfinite_grads' {sv!r} is not a "
                                "non-negative integer"
                            )
                        else:
                            counted += sv
                    elif not _num_or_sentinel(sv):
                        errors.append(
                            f"line {i}: module {mname!r} stat {sk!r} "
                            f"{sv!r} is neither a number nor a non-finite "
                            "sentinel"
                        )
            if nft is not None and counted != nft:
                errors.append(
                    f"line {i}: 'nonfinite_total' {nft} != sum of module "
                    f"'nonfinite_grads' ({counted})"
                )
    return errors, warnings


def _load_json_doc(path: str):
    with open(path) as f:
        return json.load(f)


def check_file(path: str) -> tuple[list[str], list[str]]:
    base = os.path.basename(path)
    if base.endswith(".json") and base.startswith(("slo", "fleet",
                                                   "timeline")):
        try:
            doc = _load_json_doc(path)
        except (OSError, json.JSONDecodeError) as e:
            return [f"invalid JSON ({e})"], []
        if base.startswith("slo"):
            return check_slo_rules_doc(doc)
        if base.startswith("fleet"):
            return check_fleet_doc(doc)
        return check_timeline_doc(doc)
    if os.path.basename(path).startswith("goodput"):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return [f"invalid JSON ({e})"], []
        return check_goodput_doc(doc)
    if os.path.basename(path).startswith("flash_blocks"):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return [f"invalid JSON ({e})"], []
        return check_flash_cache_doc(doc)
    if os.path.basename(path).startswith("faults"):
        return check_faults_file(path)
    if os.path.basename(path).startswith("dispatcher") \
            and path.endswith(".journal"):
        return check_journal_file(path)
    if path.endswith(".prom"):
        return check_prom_file(path)
    if os.path.basename(path).startswith("requests"):
        return check_requests_file(path)
    if os.path.basename(path).startswith("steps"):
        return check_steps_file(path)
    if os.path.basename(path).startswith("trace") \
            and path.endswith(".jsonl"):
        return check_trace_file(path)
    if os.path.basename(path).startswith("history"):
        return check_history_file(path)
    if os.path.basename(path).startswith("alerts"):
        return check_alerts_file(path)
    if os.path.basename(path).startswith("dynamics"):
        return check_dynamics_file(path)
    if os.path.basename(path) == "manifest.json" \
            and "incidents" in os.path.abspath(path).split(os.sep):
        return check_incident_manifest(path)
    flight = os.path.basename(path).startswith("flight")
    captures = os.path.basename(path).startswith("captures")
    manifest_dir = os.path.dirname(os.path.abspath(path))
    errors: list[str] = []
    warnings: list[str] = []
    prev_t: float | None = None
    prev_id: int | None = None
    resize_events: list[tuple[int, dict]] = []
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {i}: invalid JSON ({e})")
                continue
            if flight:
                e, w, prev_t = check_flight_row(row, i, prev_t)
                if isinstance(row, dict) and row.get("kind") in (
                    "resize_begin", "resize_end"
                ):
                    resize_events.append((i, row))
            elif captures:
                e, w, prev_id = check_capture_row(row, i, prev_id,
                                                  manifest_dir)
            else:
                e, w = check_row(row, i)
            errors.extend(e)
            warnings.extend(w)
    if resize_events:
        e, w = _check_resize_pairing(resize_events)
        errors.extend(e)
        warnings.extend(w)
    return errors, warnings


def _check_resize_pairing(
    events: list[tuple[int, dict]],
) -> tuple[list[str], list[str]]:
    """Elastic-resize window invariants over one flight dump:
    ``resize_begin``/``resize_end`` strictly alternate (every window
    closes, none nests), device counts are positive and actually change,
    and ``resize_end`` carries a known ``outcome``.  The flight ring is
    bounded, so a dump whose FIRST resize event is an ``end`` merely lost
    its ``begin`` to rotation — warned, not an error."""
    errors: list[str] = []
    warnings: list[str] = []
    open_line: int | None = None

    def _devices(lineno: int, row: dict) -> None:
        frm, to = row.get("from_devices"), row.get("to_devices")
        for name, v in (("from_devices", frm), ("to_devices", to)):
            if not _nonneg_int(v) or int(v) <= 0:
                errors.append(
                    f"line {lineno}: {row.get('kind')} {name!r} {v!r} is "
                    "not a positive integer"
                )
                return
        if int(frm) == int(to):
            errors.append(
                f"line {lineno}: {row.get('kind')} from_devices == "
                f"to_devices ({int(frm)}) — a resize must change the "
                "device count"
            )

    for idx, (lineno, row) in enumerate(events):
        kind = row.get("kind")
        _devices(lineno, row)
        if kind == "resize_begin":
            if open_line is not None:
                errors.append(
                    f"line {lineno}: resize_begin while the window from "
                    f"line {open_line} is still open (windows must not "
                    "nest)"
                )
            open_line = lineno
        else:  # resize_end
            if open_line is None:
                if idx == 0:
                    warnings.append(
                        f"line {lineno}: resize_end without a begin — "
                        "its resize_begin rotated out of the bounded ring"
                    )
                else:
                    errors.append(
                        f"line {lineno}: resize_end without an open "
                        "resize_begin"
                    )
            open_line = None
            outcome = row.get("outcome")
            if outcome not in ELASTIC_RESIZE_OUTCOMES:
                errors.append(
                    f"line {lineno}: resize_end 'outcome' {outcome!r} not "
                    f"in {ELASTIC_RESIZE_OUTCOMES}"
                )
    if open_line is not None:
        errors.append(
            f"line {open_line}: resize_begin never closed by a "
            "resize_end (the resize window leaked)"
        )
    return errors, warnings


def main(argv: list[str] | None = None) -> int:
    paths = list(argv) if argv else sorted(
        glob.glob(DEFAULT_GLOB) + glob.glob(DEFAULT_FLIGHT_GLOB)
        + glob.glob(DEFAULT_GOODPUT_GLOB) + glob.glob(DEFAULT_CAPTURES_GLOB)
        + glob.glob(DEFAULT_FAULTS_GLOB) + glob.glob(DEFAULT_REQUESTS_GLOB)
        + glob.glob(DEFAULT_STEPS_GLOB) + glob.glob(DEFAULT_HISTORY_GLOB)
        + glob.glob(DEFAULT_PROM_GLOB) + glob.glob(DEFAULT_FLASH_GLOB)
        + glob.glob(DEFAULT_SLO_GLOB) + glob.glob(DEFAULT_FLEET_GLOB)
        + glob.glob(DEFAULT_TIMELINE_GLOB)
        + glob.glob(DEFAULT_JOURNAL_GLOB)
        + glob.glob(DEFAULT_ALERTS_GLOB)
        + glob.glob(DEFAULT_INCIDENT_GLOB)
        + glob.glob(DEFAULT_DYNAMICS_GLOB)
    )
    if not paths:
        print(f"no metrics.jsonl found under {DEFAULT_GLOB}", file=sys.stderr)
        return 1
    failed = False
    for path in paths:
        errors, warnings = check_file(path)
        for w in warnings:
            print(f"WARN  {path}: {w}")
        if errors:
            failed = True
            for e in errors:
                print(f"ERROR {path}: {e}")
        else:
            print(f"OK    {path}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
