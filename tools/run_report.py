#!/usr/bin/env python
"""Render a logdir's telemetry streams into one human-readable run report.

Usage::

    python tools/run_report.py <logdir> [--json]

Reads ``<logdir>/metrics.jsonl`` (required) plus ``<logdir>/trace.jsonl``
and ``<logdir>/flight.jsonl`` (optional) — the streams the obs subsystem
writes — and prints:

- run summary (rows, step range, final/best metrics);
- step-time percentiles (p50/p90/p99/max), from the per-record ``t_step``
  breakdown fields when present, else from per-step trace rows, else from
  ``steps_per_sec``;
- the step-time breakdown table (mean data-wait / dispatch / host-block /
  eval / checkpoint fractions);
- anomalies: events recorded in ``trace.jsonl`` by the live detector, plus
  an offline re-scan of the metric rows (so pre-obs logs still get a
  verdict);
- straggler summary when the run was multi-host (``*_host_min/median/max``
  fields);
- flight recorder: the last events before exit from ``flight.jsonl`` —
  the first thing to read on a crashed or hung run (a last event that is
  not ``fit_end`` means the process died mid-flight);
- captures: the reactive profiler's manifest from ``captures.jsonl``
  (count, per-trigger breakdown, step ranges, per-capture wall cost);
- goodput: the merged cross-restart wall-time ledger from ``goodput.json``
  (``--goodput`` runs) — productive fraction, per-bucket seconds,
  generation/restart counts;
- resilience: the self-healing story — chaos faults from ``faults.jsonl``
  (injected/recovered pairing by kind, unpaired injections called out),
  supervised restarts and rejected-checkpoint fallbacks from the flight
  events, worker respawns, and the ``badput_restart`` seconds the
  restarts cost;
- serving: the request-level story from ``requests.jsonl`` (serve.py
  logdirs) — terminal-state counts, TTFT/TPOT/e2e p50+p99, batch
  occupancy, rejects, delivered tokens/sec, plus the ISSUE-14
  prefix-cache story (hit rate, cached-token share, prefill-vs-decode
  token split), the per-iteration prefill-budget utilization from
  the engine's metrics rows, and the ISSUE-16 tail attribution — the
  p50-vs-p99 breakdown of the exclusive ``attr_*`` latency components
  (queue/prefill/stall/decode/spec/gap) with the dominant tail
  component called out, plus the ``steps.jsonl`` step-log digest
  (``tools/tail_report.py`` renders the same split with step-log
  evidence);
- input plane: data-wait share of step time, live adaptive prefetch
  depth / data-service credit window, per-worker fetch throughput,
  dropped workers, and elastic ``data_reshard`` events;
- fleet: the fleet observability plane — peer states (up/stale/down)
  and the worst straggler spread from ``fleet.json`` (the aggregator's
  snapshot), the SLO burn-rate summary (last-record ``slo_burn_rate``
  fields + ``slo_violation`` flight events), and the cross-process trace
  count (distinct ``trace_id``s among the ``kind: "span"`` rows of
  ``trace.jsonl``).

``--json`` emits the same content as one machine-readable JSON object.
Pure stdlib + numpy-free on purpose: must run anywhere the logs land.

Exit status: 0 = report rendered from a healthy stream; 1 = the metric
stream had unparseable lines or no valid rows (CI gates on this —
``trace.jsonl``, ``captures.jsonl``, ``faults.jsonl``,
``requests.jsonl``, ``steps.jsonl``, ``dynamics.jsonl``,
``goodput.json``, and ``fleet.json`` parse errors gate it too, matching
the stream-gating convention); missing ``metrics.jsonl`` is a hard
SystemExit.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import statistics
import sys


_NONFINITE = {"NaN": float("nan"), "Infinity": float("inf"),
              "-Infinity": float("-inf")}


def _load_jsonl(path: str) -> tuple[list[dict], int]:
    """Parsed rows plus the count of unparseable lines (the CI gate:
    ``main`` exits non-zero when the metric stream had any)."""
    rows = []
    bad = 0
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                print(f"{path}:{i + 1}: skipping bad row ({e})",
                      file=sys.stderr)
                bad += 1
                continue
            if isinstance(row, dict):
                # decode the writer's strict-JSON non-finite sentinels
                rows.append({
                    k: _NONFINITE.get(v, v) if isinstance(v, str) else v
                    for k, v in row.items()
                })
            else:
                print(f"{path}:{i + 1}: skipping non-object row",
                      file=sys.stderr)
                bad += 1
    return rows, bad


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile (no interpolation; stdlib-only)."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, max(0, math.ceil(q * len(sorted_vals)) - 1))
    return sorted_vals[idx]


def split_rows(rows: list[dict]) -> tuple[list[dict], list[dict]]:
    """(train records, eval records) — eval rows carry only eval-prefixed
    scalars: ``eval_*`` from the Trainer, ``eval/*`` from the sidecar
    evaluator."""
    train, evals = [], []
    for r in rows:
        keys = set(r) - {"step"}
        if keys and all(k.startswith(("eval_", "eval/")) for k in keys):
            evals.append(r)
        else:
            train.append(r)
    return train, evals


def step_times(train: list[dict], trace: list[dict]) -> tuple[list[float], str]:
    """Per-step wall seconds and which source supplied them."""
    vals = [r["t_step"] for r in train
            if isinstance(r.get("t_step"), (int, float))]
    if vals:
        return vals, "t_step breakdown fields"
    vals = [r["t_wall"] / max(int(r.get("k", 1)), 1) for r in trace
            if isinstance(r.get("t_wall"), (int, float))]
    if vals:
        return vals, "trace.jsonl step rows"
    vals = [1.0 / r["steps_per_sec"] for r in train
            if r.get("steps_per_sec")]
    return vals, "1/steps_per_sec"


def breakdown_table(train: list[dict]) -> list[tuple[str, float, float]]:
    """[(part, mean_seconds_per_step, mean_fraction)] from breakdown fields."""
    parts = [
        ("data_wait", "t_data"),
        ("dispatch", "t_dispatch"),
        ("host_block", "t_host"),
        ("eval", "t_eval"),
        ("checkpoint", "t_ckpt"),
    ]
    rows_with = [r for r in train if isinstance(r.get("t_step"), (int, float))]
    if not rows_with:
        return []
    mean_t_step = statistics.fmean(r["t_step"] for r in rows_with)
    out = []
    for label, key in parts:
        vals = [r[key] for r in rows_with
                if isinstance(r.get(key), (int, float))]
        if not vals:
            continue
        # absent key in a row = 0 contribution in that window
        mean_s = sum(vals) / len(rows_with)
        out.append((label, mean_s, mean_s / mean_t_step if mean_t_step else 0.0))
    return out


def collect_anomalies(trace: list[dict], train: list[dict]) -> list[dict]:
    recorded = [r for r in trace if r.get("kind") == "anomaly"]
    # Offline re-scan with the same detector the Trainer runs live, so a
    # logdir written before obs (or with detection off) still gets checked.
    # Exception, not ImportError: the package import chain pulls in jax,
    # and on an analysis box with a different jax this must degrade to
    # recorded-only, never crash the report (the tool's portability
    # contract).
    try:
        from distributedtensorflow_tpu.obs import AnomalyDetector
    except Exception as e:
        print(f"offline anomaly re-scan unavailable ({e})", file=sys.stderr)
        return recorded
    det = AnomalyDetector(on_anomaly=lambda a: None)
    seen = {(r.get("anomaly"), r.get("step")) for r in recorded}
    for r in train:
        for a in det.observe_record(r):
            if (a.kind, a.step) not in seen:
                recorded.append({
                    "kind": "anomaly", "step": a.step, "anomaly": a.kind,
                    "message": a.message, "value": a.value,
                    "source": "offline_rescan",
                })
    return recorded


def flight_summary(flight: list[dict], last_n: int = 10) -> dict:
    """Flight-recorder digest: event count by kind, the last ``last_n``
    events (what the process was doing before exit), and whether the dump
    ends in a clean ``fit_end`` or mid-flight (crash/hang signature)."""
    if not flight:
        return {}
    kinds: dict[str, int] = {}
    for e in flight:
        k = e.get("kind", "?")
        kinds[k] = kinds.get(k, 0) + 1
    return {
        "events": len(flight),
        "kinds": dict(sorted(kinds.items(), key=lambda kv: -kv[1])),
        "clean_exit": flight[-1].get("kind") == "fit_end",
        "last": flight[-last_n:],
    }


def capture_summary(rows: list[dict]) -> dict:
    """Reactive-profiler digest from ``captures.jsonl``: capture count,
    per-trigger counts, and per-capture windows (step range + wall cost)."""
    if not rows:
        return {}
    triggers: dict[str, int] = {}
    windows = []
    for r in rows:
        t = str(r.get("trigger", "?"))
        triggers[t] = triggers.get(t, 0) + 1
        w = {
            "id": r.get("id"),
            "trigger": t,
            "step_begin": r.get("step_begin"),
            "step_end": r.get("step_end"),
            "wall_s": r.get("wall_s"),
            "overhead_s": r.get("overhead_s"),
            "dir": r.get("dir"),
        }
        if r.get("aborted"):
            w["aborted"] = True
        windows.append(w)
    return {
        "count": len(rows),
        "triggers": dict(sorted(triggers.items(), key=lambda kv: -kv[1])),
        "windows": windows,
    }


def resilience_summary(faults: list[dict], flight: list[dict],
                       goodput: dict) -> dict:
    """The self-healing digest: fault injection/recovery pairing
    (``faults.jsonl``), supervised restarts + checkpoint fallbacks +
    worker respawns (flight events), and what the restarts cost
    (``badput_restart``).  Empty when the run had none of it."""
    injected = [r for r in faults if r.get("phase") == "injected"]
    recovered_ids = {r.get("id") for r in faults
                     if r.get("phase") == "recovered"}
    restarts = [e for e in flight if e.get("kind") == "restart"]
    gave_up = [e for e in flight if e.get("kind") == "supervisor_giving_up"]
    corrupt = [e for e in flight if e.get("kind") == "checkpoint_corrupt"]
    respawns = [e for e in flight if e.get("kind") == "worker_respawn"]
    if not (injected or restarts or corrupt or respawns or gave_up):
        return {}
    by_kind: dict[str, dict[str, int]] = {}
    unpaired = []
    for r in injected:
        k = str(r.get("kind", "?"))
        d = by_kind.setdefault(k, {"injected": 0, "recovered": 0})
        d["injected"] += 1
        if r.get("id") in recovered_ids:
            d["recovered"] += 1
        else:
            unpaired.append({"id": r.get("id"), "kind": k,
                             "step": r.get("step")})
    restart_kinds: dict[str, int] = {}
    for e in restarts:
        k = str(e.get("failure", "?"))
        restart_kinds[k] = restart_kinds.get(k, 0) + 1
    out = {
        "faults_injected": len(injected),
        "faults_recovered": len(injected) - len(unpaired),
        "unpaired": unpaired,
        "faults_by_kind": by_kind,
        "restarts": len(restarts),
        "restarts_by_failure": dict(
            sorted(restart_kinds.items(), key=lambda kv: -kv[1])
        ),
        "restart_events": [
            {k: e.get(k) for k in ("step", "failure", "attempt",
                                   "backoff_s", "rejected_checkpoints")}
            for e in restarts
        ],
        "gave_up": bool(gave_up),
        "fallback_restores": len(corrupt),
        "rejected_checkpoint_steps": [e.get("step") for e in corrupt],
        "worker_respawns": len(respawns),
    }
    badput = (goodput.get("buckets") or {}).get("badput_restart")
    if isinstance(badput, (int, float)):
        out["badput_restart_s"] = badput
    return out


def elasticity_summary(flight: list[dict], goodput: dict) -> dict:
    """The elastic-training digest: paired ``resize_begin``/``resize_end``
    windows (count, outcomes, per-resize wall cost) plus the ``resize``
    goodput bucket's share of run wall.  Empty when the run never
    resized."""
    windows: list[dict] = []
    t0 = None
    for e in flight:
        kind = e.get("kind")
        if kind == "resize_begin":
            t0 = e.get("t")
        elif kind == "resize_end":
            dur = e.get("duration_s")
            if not isinstance(dur, (int, float)) and \
                    isinstance(t0, (int, float)) and \
                    isinstance(e.get("t"), (int, float)):
                dur = round(float(e["t"]) - float(t0), 3)
            windows.append({
                "from_devices": e.get("from_devices"),
                "to_devices": e.get("to_devices"),
                "outcome": e.get("outcome"),
                "step": e.get("step"),
                "resumed_step": e.get("resumed_step"),
                "duration_s": dur,
                "source": e.get("source"),
            })
            t0 = None
    if not windows:
        return {}
    costs = [w["duration_s"] for w in windows
             if isinstance(w["duration_s"], (int, float))]
    out = {
        "resizes": len(windows),
        "completed": sum(1 for w in windows
                         if w.get("outcome") == "completed"),
        "failed": sum(1 for w in windows if w.get("outcome") == "failed"),
        "resize_wall_s": round(sum(costs), 3),
        "windows": windows,
    }
    bucket = (goodput.get("buckets") or {}).get("resize")
    wall = goodput.get("wall_s")
    if isinstance(bucket, (int, float)):
        out["resize_bucket_s"] = bucket
        if isinstance(wall, (int, float)) and wall > 0:
            out["goodput_share"] = round(float(bucket) / float(wall), 4)
    return out


_ATTR_COMPONENTS = (
    ("queue", "attr_queue_s"),
    ("prefill", "attr_prefill_s"),
    ("stall", "attr_stall_s"),
    ("decode", "attr_decode_s"),
    ("spec", "attr_spec_s"),
    ("gap", "attr_gap_s"),
)


def tail_attribution(ok: list[dict]) -> dict:
    """The p50-vs-p99 component breakdown from the engine's exclusive
    attribution fields on ok requests (``attr_*_s``; they tile e2e).
    The dominant component is the one whose tail-cohort mean grew the
    most over the p50 cohort — ``tools/tail_report.py`` renders the
    same split with step-log evidence attached."""
    rows = [
        r for r in ok
        if isinstance(r.get("e2e_s"), (int, float))
        and math.isfinite(r["e2e_s"])
        and all(isinstance(r.get(f), (int, float))
                and math.isfinite(r[f]) for _, f in _ATTR_COMPONENTS)
    ]
    if not rows:
        return {}
    e2es = sorted(r["e2e_s"] for r in rows)
    p50 = _percentile(e2es, 0.50)
    p99 = _percentile(e2es, 0.99)
    p50_rows = [r for r in rows if r["e2e_s"] <= p50]
    tail_rows = ([r for r in rows if r["e2e_s"] >= p99]
                 or [max(rows, key=lambda r: r["e2e_s"])])
    comps = {}
    for label, field in _ATTR_COMPONENTS:
        m50 = sum(r[field] for r in p50_rows) / len(p50_rows)
        mtail = sum(r[field] for r in tail_rows) / len(tail_rows)
        comps[label] = {"p50_mean_s": m50, "tail_mean_s": mtail,
                        "growth_s": mtail - m50}
    dominant = max(comps, key=lambda k: comps[k]["growth_s"])
    covered = sum(
        1 for r in rows
        if abs(sum(r[f] for _, f in _ATTR_COMPONENTS) - r["e2e_s"])
        <= 0.05 * r["e2e_s"] + 1e-4
    )
    return {
        "requests": len(rows),
        "e2e_p50_s": p50,
        "e2e_p99_s": p99,
        "components": comps,
        "dominant": dominant,
        "dominant_growth_s": comps[dominant]["growth_s"],
        "covered_share": covered / len(rows),
    }


def serving_summary(rows: list[dict], metrics_rows: list[dict] | None
                    = None, steps_rows: list[dict] | None = None) -> dict:
    """The serving digest from ``requests.jsonl`` (serve.py logdirs):
    terminal-state counts, SLO percentiles (TTFT / TPOT / e2e p50+p99),
    batch occupancy (per-request mean/max fields written by the engine),
    and delivered token throughput over the log's time span.  With the
    engine's ``metrics.jsonl`` rows (ISSUE 14), also the prefix-cache
    story — hit rate, cached-token share, prefill-vs-decode token split —
    and the per-iteration prefill-budget utilization; with the ISSUE 15
    fast path, the speculation digest (draft acceptance rate, tokens per
    decode step, per-step dispatch count)."""
    if not rows:
        return {}
    by_status: dict[str, int] = {}
    for r in rows:
        s = str(r.get("status", "?"))
        by_status[s] = by_status.get(s, 0) + 1
    ok = [r for r in rows if r.get("status") == "ok"]

    def pcts(name):
        rows_for = ok
        if name == "tpot_s":
            # single-token completions have no per-output-token interval
            # (the engine writes tpot_s=0.0) — including them would
            # deflate the tail.
            rows_for = [r for r in ok if r.get("new_tokens", 0) > 1]
        vals = sorted(
            r[name] for r in rows_for
            if isinstance(r.get(name), (int, float))
        )
        if not vals:
            return {}
        return {"p50": _percentile(vals, 0.50),
                "p99": _percentile(vals, 0.99)}

    tokens = sum(
        r.get("new_tokens", 0) for r in ok
        if isinstance(r.get("new_tokens"), (int, float))
    )
    ts = [r["t"] for r in rows if isinstance(r.get("t"), (int, float))]
    span = max(ts) - min(ts) if len(ts) > 1 else 0.0
    occ_max = [r["occ_max"] for r in ok
               if isinstance(r.get("occ_max"), (int, float))]
    occ_mean = [r["occ_mean"] for r in ok
                if isinstance(r.get("occ_mean"), (int, float))]
    reasons: dict[str, int] = {}
    for r in ok:
        fr = str(r.get("finish_reason", "?"))
        reasons[fr] = reasons.get(fr, 0) + 1
    out = {
        "requests": len(rows),
        "by_status": dict(sorted(by_status.items(), key=lambda kv: -kv[1])),
        "rejected": by_status.get("rejected", 0),
        "finish_reasons": reasons,
        "tokens_generated": tokens,
        "tokens_per_sec": tokens / span if span else 0.0,
        "ttft_s": pcts("ttft_s"),
        "tpot_s": pcts("tpot_s"),
        "e2e_s": pcts("e2e_s"),
        "occupancy_max": max(occ_max, default=0),
        "occupancy_mean": (sum(occ_mean) / len(occ_mean)
                           if occ_mean else 0.0),
    }
    # prefix-cache accounting (per-request split fields, ISSUE 14):
    # cached_prefix_tokens + prefill_tokens tile each ok row's prompt.
    split_rows_ = [
        r for r in ok
        if isinstance(r.get("cached_prefix_tokens"), (int, float))
        and isinstance(r.get("prefill_tokens"), (int, float))
    ]
    if split_rows_:
        cached = sum(r["cached_prefix_tokens"] for r in split_rows_)
        prefilled = sum(r["prefill_tokens"] for r in split_rows_)
        prompt_total = cached + prefilled
        out["prefix_cache"] = {
            "requests_with_hits": sum(
                1 for r in split_rows_ if r["cached_prefix_tokens"] > 0
            ),
            "hit_rate": (sum(
                1 for r in split_rows_ if r["cached_prefix_tokens"] > 0
            ) / len(split_rows_)),
            "cached_tokens": cached,
            "cached_token_share": (cached / prompt_total
                                   if prompt_total else 0.0),
        }
        out["token_split"] = {
            "prompt_cached": cached,
            "prompt_prefilled": prefilled,
            "decode": tokens,
        }
    # decode fast path (ISSUE 15): per-request draft accounting from the
    # requests rows, tokens-per-step / dispatch telemetry from the
    # engine's last metrics.jsonl row.
    last = {}
    for r in metrics_rows or []:
        if "prefill_iters" in r:
            last = r
    spec_rows = [
        r for r in ok
        if isinstance(r.get("drafted"), (int, float))
        and isinstance(r.get("accepted"), (int, float))
    ]
    drafted = sum(int(r["drafted"]) for r in spec_rows)
    accepted = sum(int(r["accepted"]) for r in spec_rows)
    if drafted or last.get("fused_sampling") or last.get("speculate"):
        fast: dict = {
            "fused_sampling": bool(last.get("fused_sampling", drafted > 0)),
            "speculate": int(last.get("speculate", 0)),
            "drafted": drafted,
            "accepted": accepted,
        }
        if drafted:
            fast["acceptance_rate"] = accepted / drafted
        if isinstance(last.get("tokens_per_step"), (int, float)):
            fast["tokens_per_step"] = last["tokens_per_step"]
        steps = last.get("step")
        disp = last.get("decode_dispatches_total")
        # iterations that fetched the logits for the host sampler (a
        # request with temperature > 0 was decoding): 0 in greedy traffic
        rounds = last.get("host_sample_rounds_total")
        if isinstance(steps, (int, float)) and steps \
                and isinstance(disp, (int, float)) \
                and isinstance(rounds, (int, float)):
            fast["dispatches_per_step"] = (disp + rounds) / steps
        out["decode_fast_path"] = fast
    iters = last.get("prefill_iters")
    chunk = last.get("prefill_chunk")
    budget = last.get("prefill_budget")
    if isinstance(iters, (int, float)) and iters \
            and isinstance(chunk, (int, float)):
        per_iter = last.get("prefill_chunks", 0) * chunk / iters
        bu = {"prefill_iters": int(iters),
              "tokens_per_iter": per_iter,
              "budget_tokens": int(budget or 0)}
        if budget:
            bu["utilization"] = min(per_iter / budget, 1.0)
        out["prefill_budget"] = bu
    # tail attribution (ISSUE 16): which exclusive component (queue /
    # prefill / stall / decode / spec / gap) explains p99 vs p50.
    ta = tail_attribution(ok)
    if ta:
        out["tail_attribution"] = ta
    if steps_rows:
        out["step_log"] = {
            "records": len(steps_rows),
            "budget_stalls": sum(
                int(r.get("budget_stall", 0)) for r in steps_rows
                if isinstance(r.get("budget_stall"), (int, float))
            ),
            "tokens_committed": sum(
                int(r.get("tokens_committed", 0)) for r in steps_rows
                if isinstance(r.get("tokens_committed"), (int, float))
            ),
        }
    return out


def step_time_opt_summary(train: list[dict], logdir: str) -> dict:
    """The step-time-attack digest: quantized-compute mode
    (``quant_mode`` row stamp), collective-matmul overlap (bucket count +
    coverage stamps, plus the overlapped share of collective dispatches
    from the flattened histogram fields), and the flash-attention
    autotuner's block choices (``<logdir>/flash_blocks.json`` when the
    run's sweep landed its cache there).  Empty when the run used none
    of the three."""
    last: dict = {}
    for r in train:
        if "quant_mode" in r or "overlap_buckets" in r:
            last = r
    out: dict = {}
    if isinstance(last.get("quant_mode"), str):
        out["quant_mode"] = last["quant_mode"]
    if isinstance(last.get("overlap_buckets"), (int, float)) \
            and last["overlap_buckets"]:
        overlap: dict = {"buckets": int(last["overlap_buckets"])}
        if isinstance(last.get("overlap_coverage"), (int, float)):
            overlap["coverage"] = last["overlap_coverage"]
        # Overlapped share of collective dispatches, from the flattened
        # histogram counts in the same record.
        overlapped = 0.0
        total = 0.0
        for k, v in last.items():
            if not k.startswith("collective_dispatch_seconds_count"):
                continue
            if not isinstance(v, (int, float)):
                continue
            total += v
            if ".overlapped_1" in k:
                overlapped += v
        if total:
            overlap["dispatch_share"] = overlapped / total
        out["overlap"] = overlap
    cache_path = os.path.join(logdir, "flash_blocks.json")
    if os.path.exists(cache_path):
        try:
            with open(cache_path) as f:
                doc = json.load(f)
            entries = doc.get("entries") if isinstance(doc, dict) else None
        except (OSError, json.JSONDecodeError) as e:
            print(f"{cache_path}: unreadable ({e})", file=sys.stderr)
            entries = None
        if isinstance(entries, list) and entries:
            out["autotuned_blocks"] = [
                {k: e.get(k) for k in ("platform", "dtype", "seq", "depth",
                                       "block_q", "block_k", "ms",
                                       "source")}
                for e in entries if isinstance(e, dict)
            ]
    return out


_RPC_RETRY_RE = re.compile(
    r"^rpc_retries_total\.endpoint_(?P<ep>[A-Za-z0-9_:]+)"
    r"\.outcome_(?P<outcome>[a-z_]+)$"
)
_RPC_DEADLINE_RE = re.compile(
    r"^rpc_deadline_exceeded_total\.endpoint_(?P<ep>[A-Za-z0-9_:]+)$"
)
_RPC_ATTEMPT_COUNT_RE = re.compile(
    r"^rpc_attempt_seconds_count\.endpoint_(?P<ep>[A-Za-z0-9_:]+)$"
)
_BREAKER_STATE_RE = re.compile(
    r"^breaker_state\.endpoint_(?P<ep>[A-Za-z0-9_:]+)$"
)
_BREAKER_TRANS_RE = re.compile(
    r"^breaker_transitions_total\.endpoint_(?P<ep>[A-Za-z0-9_:]+)"
    r"\.to_(?P<to>[a-z_]+)$"
)
_BREAKER_STATE_NAMES = {0.0: "closed", 1.0: "half_open", 2.0: "open"}


def rpc_summary(train: list[dict], logdir: str) -> tuple[dict, int]:
    """``(rpc digest, parse errors)``: resilient-transport behavior from
    the last metric record's flattened ``rpc_*`` / ``breaker_*`` fields
    (retries + deadline misses + attempts by endpoint, breaker states
    and trip counts, same-worker stream resumes) plus a replay summary
    of ``<logdir>/dispatcher.journal`` when one exists — journal parse
    errors gate the exit code like every other stream."""
    last: dict = {}
    for r in train:
        if any(k.startswith(("rpc_", "breaker_")) for k in r):
            last = r
    out: dict = {}
    bad = 0
    endpoints: dict[str, dict] = {}
    for k, v in last.items():
        if not isinstance(v, (int, float)):
            continue
        m = _RPC_RETRY_RE.match(k)
        if m:
            d = endpoints.setdefault(m.group("ep"), {})
            d[f"retries_{m.group('outcome')}"] = int(v)
        m = _RPC_DEADLINE_RE.match(k)
        if m:
            endpoints.setdefault(m.group("ep"), {})["deadline_misses"] = \
                int(v)
        m = _RPC_ATTEMPT_COUNT_RE.match(k)
        if m:
            endpoints.setdefault(m.group("ep"), {})["attempts"] = int(v)
        m = _BREAKER_STATE_RE.match(k)
        if m:
            endpoints.setdefault(m.group("ep"), {})["breaker"] = \
                _BREAKER_STATE_NAMES.get(float(v), f"?{v}")
        m = _BREAKER_TRANS_RE.match(k)
        if m:
            d = endpoints.setdefault(m.group("ep"), {})
            d[f"breaker_to_{m.group('to')}"] = int(v)
    if endpoints:
        out["endpoints"] = dict(sorted(endpoints.items()))
        out["retries_total"] = sum(
            d.get("retries_ok", 0) + d.get("retries_error", 0)
            for d in endpoints.values()
        )
        out["deadline_misses_total"] = sum(
            d.get("deadline_misses", 0) for d in endpoints.values()
        )
        out["breaker_trips_total"] = sum(
            d.get("breaker_to_open", 0) for d in endpoints.values()
        )
    if isinstance(last.get("data_service_stream_resumes_total"),
                  (int, float)):
        out["stream_resumes"] = int(
            last["data_service_stream_resumes_total"]
        )
    journal_path = os.path.join(logdir, "dispatcher.journal")
    if os.path.exists(journal_path):
        by_kind: dict[str, int] = {}
        epochs: dict[str, int] = {}
        replays = 0
        lines = open(journal_path).read().split("\n")
        n_lines = len([ln for ln in lines if ln.strip()])
        seen = 0
        for ln in lines:
            ln = ln.strip()
            if not ln:
                continue
            seen += 1
            try:
                row = json.loads(ln)
            except json.JSONDecodeError:
                if seen == n_lines:
                    continue  # torn final line: the one legal tear
                print(f"{journal_path}: corrupt journal line",
                      file=sys.stderr)
                bad += 1
                continue
            if not isinstance(row, dict):
                bad += 1
                continue
            kind = str(row.get("kind", "?"))
            by_kind[kind] = by_kind.get(kind, 0) + 1
            if kind == "replay":
                replays += 1
            elif kind in ("epoch_start", "reshard"):
                epochs[str(row.get("epoch"))] = int(row.get("gen", 0))
        out["journal"] = {
            "records": sum(by_kind.values()),
            "by_kind": dict(sorted(by_kind.items())),
            "replays": replays,
            "epochs": epochs,
        }
    return out, bad


_WORKER_COUNT_RE = re.compile(
    r"^data_service_fetch_seconds_count\.worker_(.+)$"
)
_WORKER_SUM_RE = re.compile(r"^data_service_fetch_seconds_sum\.worker_(.+)$")


def input_plane_summary(train: list[dict], flight: list[dict]) -> dict:
    """The input-plane digest: what the data path cost and how the
    adaptive machinery behaved — data-wait share of step time, the live
    prefetch depth / data-service credit window (per-record fields from
    the adaptive controller), per-worker fetch counts + mean wire time
    (flattened ``data_service_fetch_seconds{worker=}`` fields), dropped
    workers, and elastic re-shard events (``data_reshard`` flights).
    Empty when the run carried no input-plane telemetry."""
    last: dict = {}
    for r in train:  # last row carrying any input-plane field wins
        if any(k.startswith("data_") for k in r):
            last = r
    reshards = [e for e in flight if e.get("kind") == "data_reshard"]
    if not last and not reshards:
        return {}
    out: dict = {}
    rows_with = [
        r for r in train
        if isinstance(r.get("t_step"), (int, float)) and r["t_step"] > 0
    ]
    if rows_with:
        t_step = sum(r["t_step"] for r in rows_with)
        t_data = sum(
            r["t_data"] for r in rows_with
            if isinstance(r.get("t_data"), (int, float))
        )
        out["data_wait_share"] = t_data / t_step if t_step else 0.0
    for field in ("data_prefetch_depth", "data_client_window",
                  "data_batches_total",
                  "data_service_workers_dropped_total",
                  "data_service_resharded_splits_total"):
        if isinstance(last.get(field), (int, float)):
            out[field] = last[field]
    workers: dict[str, dict] = {}
    for k, v in last.items():
        if not isinstance(v, (int, float)):
            continue
        m = _WORKER_COUNT_RE.match(k)
        if m:
            workers.setdefault(m.group(1), {})["batches"] = v
        m = _WORKER_SUM_RE.match(k)
        if m:
            workers.setdefault(m.group(1), {})["fetch_s"] = v
    for d in workers.values():
        n = d.get("batches", 0)
        d["mean_fetch_ms"] = 1e3 * d.get("fetch_s", 0.0) / n if n else 0.0
    if workers:
        out["workers"] = dict(sorted(workers.items()))
    if reshards:
        out["reshard_events"] = [
            {k: e.get(k) for k in ("t", "worker", "splits", "gen", "epoch")}
            for e in reshards
        ]
    return out


def sharding_summary(train: list[dict]) -> dict:
    """The weight-update-sharding digest from the per-record state-bytes
    fields (written once per log boundary from the fit's static
    accounting): per-device params / optimizer-state bytes and the ZeRO
    mode that produced them — the number ``--zero`` exists to shrink.
    Empty when the run predates the fields."""
    last = {}
    for r in train:  # last row carrying the fields wins
        if isinstance(r.get("opt_state_bytes_per_device"), (int, float)) \
                or isinstance(r.get("params_bytes_per_device"), (int, float)):
            last = r
    if not last:
        return {}
    out: dict = {}
    for key in ("params_bytes_per_device", "opt_state_bytes_per_device"):
        if isinstance(last.get(key), (int, float)):
            out[key] = last[key]
    out["zero_stage"] = int(last.get("zero_stage", 0) or 0)
    if isinstance(last.get("zero_degree"), (int, float)):
        out["zero_degree"] = int(last["zero_degree"])
    return out


_STALL_FIELD_RE = re.compile(
    r"^pipeline_mpmd_stall_seconds_(count|sum)\.stage_(\d+)$"
)


def pipeline_summary(train: list[dict], trace: list[dict]) -> dict:
    """Pipeline-parallelism digest: the schedule stamps from the last
    record carrying them (trainer SPMD runs and MPMD stage dirs both
    write the ``pipeline_*`` fields), the stage-handoff span latencies
    from the trace stream (MPMD ``pipeline.handoff`` rows), and the
    credit-window stall accounting from the flattened stall-histogram
    fields.  Empty when the run is unpipelined."""
    last = {}
    for r in train:
        if r.get("pipeline_schedule"):
            last = r
    out: dict = {}
    if last:
        out["schedule"] = last.get("pipeline_schedule")
        for k in ("pipeline_stages", "pipeline_microbatches",
                  "pipeline_virtual"):
            if isinstance(last.get(k), (int, float)):
                out[k.replace("pipeline_", "")] = int(last[k])
        if isinstance(last.get("pipeline_bubble"), (int, float)):
            out["predicted_bubble"] = float(last["pipeline_bubble"])
    durs = sorted(
        float(r.get("dur_s", 0.0)) for r in trace
        if isinstance(r, dict) and r.get("kind") == "span"
        and r.get("name") == "pipeline.handoff"
    )
    if durs:
        out["handoff"] = {
            "count": len(durs),
            "p50_s": _percentile(durs, 0.50),
            "p99_s": _percentile(durs, 0.99),
        }
    stalls: dict[str, dict[str, float]] = {}
    for r in train:
        for k, v in r.items():
            m = _STALL_FIELD_RE.match(k)
            if m and isinstance(v, (int, float)):
                stalls.setdefault(m.group(2), {})[m.group(1)] = float(v)
    if stalls:
        out["link_stalls"] = {
            f"stage{sid}": {
                "count": int(d.get("count", 0)),
                "total_s": d.get("sum", 0.0),
            }
            for sid, d in sorted(stalls.items())
        }
    return out


def straggler_fields(train: list[dict]) -> dict[str, dict[str, float]]:
    """Last-row host-spread fields, grouped by base key."""
    out: dict[str, dict[str, float]] = {}
    for r in train:
        for k, v in r.items():
            for suffix in ("_host_min", "_host_median", "_host_max",
                           "_straggler"):
                if k.endswith(suffix):
                    base = k[: -len(suffix)]
                    out.setdefault(base, {})[suffix.lstrip("_")] = v
    return out


_SLO_FIELD_RE = re.compile(
    r"^slo_burn_rate\.slo_(?P<slo>.+)\.window_(?P<window>[A-Za-z0-9_]+)$"
)


def fleet_summary(logdir: str, train: list[dict], trace: list[dict],
                  flight: list[dict]) -> tuple[dict, int]:
    """``(fleet digest, parse errors)``: peer states + worst straggler
    spread from ``<logdir>/fleet.json``, SLO burn rates from the last
    metric record's flattened ``slo_burn_rate`` fields + ``slo_violation``
    flight events, and the cross-process trace census from the
    ``kind: "span"`` rows of ``trace.jsonl``.  Empty when the run carried
    none of it."""
    out: dict = {}
    bad = 0
    path = os.path.join(logdir, "fleet.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError) as e:
            print(f"{path}: unreadable ({e})", file=sys.stderr)
            doc, bad = None, 1
        if isinstance(doc, dict):
            peers = doc.get("peers") or {}
            states: dict[str, int] = {}
            for p in peers.values():
                s = str(p.get("state", "?")) if isinstance(p, dict) else "?"
                states[s] = states.get(s, 0) + 1
            out["peers"] = {
                name: {k: p.get(k) for k in ("addr", "state", "age_s",
                                             "ok", "errors")}
                for name, p in peers.items() if isinstance(p, dict)
            }
            out["peer_states"] = states
            if isinstance(doc.get("worst_spread"), dict):
                out["worst_spread"] = doc["worst_spread"]
            if isinstance(doc.get("scrape_rounds"), (int, float)):
                out["scrape_rounds"] = doc["scrape_rounds"]
    # SLO burn: the last record carrying any slo_burn_rate field wins.
    last: dict = {}
    for r in train:
        if any(k.startswith("slo_burn_rate") for k in r):
            last = r
    burns: dict[str, dict[str, float]] = {}
    for k, v in last.items():
        m = _SLO_FIELD_RE.match(k)
        if m and isinstance(v, (int, float)):
            burns.setdefault(m.group("slo"), {})[m.group("window")] = v
    if burns:
        out["slo_burn_rates"] = {k: burns[k] for k in sorted(burns)}
    violations = [e for e in flight if e.get("kind") == "slo_violation"]
    if violations:
        out["slo_violations"] = [
            {k: e.get(k) for k in ("t", "slo", "window", "burn", "limit",
                                   "metric")}
            for e in violations
        ]
    spans = [r for r in trace if r.get("kind") == "span"]
    if spans:
        trace_ids = {r.get("trace_id") for r in spans
                     if isinstance(r.get("trace_id"), str)}
        out["cross_process_traces"] = len(trace_ids)
        out["cross_process_spans"] = len(spans)
    return out, bad


def alerts_summary(logdir: str) -> tuple[dict, int]:
    """``(alerts digest, parse errors)`` from ``<logdir>/alerts.jsonl``
    plus the ``incidents/`` evidence bundles: firing counts by rule and
    severity, the still-open set, the last firings, and per-bundle
    manifest summaries.  Empty when the run carried no alerting."""
    out: dict = {}
    bad = 0
    path = os.path.join(logdir, "alerts.jsonl")
    if os.path.exists(path):
        rows, bad = _load_jsonl(path)
        fired = [r for r in rows if r.get("phase") == "fired"]
        resolved_ids = {r.get("id") for r in rows
                        if r.get("phase") == "resolved"}
        by_rule: dict[str, int] = {}
        by_severity: dict[str, int] = {}
        for r in fired:
            by_rule[str(r.get("rule"))] = by_rule.get(
                str(r.get("rule")), 0) + 1
            by_severity[str(r.get("severity"))] = by_severity.get(
                str(r.get("severity")), 0) + 1
        out = {
            "fired": len(fired),
            "by_rule": {k: by_rule[k] for k in sorted(by_rule)},
            "by_severity": {k: by_severity[k] for k in sorted(by_severity)},
            "open": [
                {k: r.get(k) for k in ("id", "rule", "severity", "t")}
                for r in fired if r.get("id") not in resolved_ids
            ],
            "last": [
                {k: r.get(k) for k in ("t", "id", "rule", "kind",
                                       "severity", "phase", "value",
                                       "reason")}
                for r in rows[-10:]
            ],
        }
    incidents_dir = os.path.join(logdir, "incidents")
    if os.path.isdir(incidents_dir):
        bundles = []
        for name in sorted(os.listdir(incidents_dir)):
            manifest = os.path.join(incidents_dir, name, "manifest.json")
            if not os.path.exists(manifest):
                continue
            try:
                with open(manifest) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError, ValueError) as e:
                print(f"{manifest}: unreadable ({e})", file=sys.stderr)
                bad += 1
                continue
            if isinstance(doc, dict):
                bundles.append({
                    "dir": name,
                    **{k: doc.get(k) for k in ("id", "rule", "severity",
                                               "t")},
                    "files": len(doc.get("files") or []),
                })
        if bundles:
            out["incidents"] = bundles
    return out, bad


def dynamics_summary(logdir: str, flight: list[dict]) -> tuple[dict, int]:
    """``(training-dynamics digest, parse errors)`` from
    ``<logdir>/dynamics.jsonl`` (obs/dynamics.py cadence rows): cadence
    coverage, global-grad-norm envelope, per-module last/peak stats,
    non-finite rows, and the flight stream's last ``nan_provenance``
    verdict.  Empty when the run carried no ``--dynamics-every``
    telemetry."""
    path = os.path.join(logdir, "dynamics.jsonl")
    if not os.path.exists(path):
        return {}, 0
    rows, bad = _load_jsonl(path)
    rows = [r for r in rows if isinstance(r.get("step"), int)]
    if not rows:
        return ({"rows": 0} if not bad else {}), bad
    gnorms = [r["global_grad_norm"] for r in rows
              if isinstance(r.get("global_grad_norm"), (int, float))
              and math.isfinite(r["global_grad_norm"])]
    modules: dict[str, dict] = {}
    for r in rows:
        for m, stats in (r.get("modules") or {}).items():
            if not isinstance(stats, dict):
                continue
            d = modules.setdefault(m, {"nonfinite_grads": 0})
            for k in ("grad_norm", "param_norm", "update_ratio"):
                v = stats.get(k)
                v = _NONFINITE.get(v, v) if isinstance(v, str) else v
                if isinstance(v, (int, float)) and math.isfinite(v):
                    d[k] = v  # last finite value wins
                    if k == "update_ratio":
                        d["update_ratio_max"] = max(
                            d.get("update_ratio_max", 0.0), v)
            nf = stats.get("nonfinite_grads")
            if isinstance(nf, int) and not isinstance(nf, bool):
                d["nonfinite_grads"] += nf
    out = {
        "rows": len(rows),
        "every": rows[-1].get("every"),
        "steps": {"first": rows[0]["step"], "last": rows[-1]["step"]},
        "global_grad_norm": {
            "last": gnorms[-1] if gnorms else None,
            "max": max(gnorms) if gnorms else None,
        },
        "nonfinite_steps": [r["step"] for r in rows
                            if r.get("nonfinite_total")],
        "modules": {m: modules[m] for m in sorted(modules)},
    }
    prov = [e for e in flight if e.get("kind") == "nan_provenance"]
    if prov:
        out["provenance"] = {
            k: prov[-1].get(k)
            for k in ("step", "module", "reason", "method")
        }
    return out, bad


def load_goodput(logdir: str) -> tuple[dict, int]:
    """``(goodput summary, parse errors)`` from ``<logdir>/goodput.json``
    (the GoodputLedger document; empty summary when absent)."""
    path = os.path.join(logdir, "goodput.json")
    if not os.path.exists(path):
        return {}, 0
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError, ValueError) as e:
        print(f"{path}: unreadable ({e})", file=sys.stderr)
        return {}, 1
    merged = doc.get("merged") if isinstance(doc, dict) else None
    if not isinstance(merged, dict):
        print(f"{path}: no 'merged' section", file=sys.stderr)
        return {}, 1
    gens = doc.get("generations") or []
    out = dict(merged)
    out.setdefault("generations", len(gens))
    out["ended"] = [g.get("ended") for g in gens if isinstance(g, dict)]
    return out, 0


def build_report(logdir: str) -> dict:
    metrics_path = os.path.join(logdir, "metrics.jsonl")
    if not os.path.exists(metrics_path):
        raise SystemExit(f"{metrics_path}: not found (is this a logdir?)")
    rows, bad_metrics = _load_jsonl(metrics_path)
    trace_path = os.path.join(logdir, "trace.jsonl")
    # trace.jsonl parse errors gate the exit code like every other stream
    # (a truncated/corrupt trace used to pass silently).
    trace, bad_trace = (_load_jsonl(trace_path) if os.path.exists(trace_path)
                        else ([], 0))
    flight_path = os.path.join(logdir, "flight.jsonl")
    flight, _ = (_load_jsonl(flight_path) if os.path.exists(flight_path)
                 else ([], 0))
    captures_path = os.path.join(logdir, "captures.jsonl")
    captures, bad_captures = (
        _load_jsonl(captures_path) if os.path.exists(captures_path)
        else ([], 0)
    )
    faults_path = os.path.join(logdir, "faults.jsonl")
    faults, bad_faults = (
        _load_jsonl(faults_path) if os.path.exists(faults_path)
        else ([], 0)
    )
    requests_path = os.path.join(logdir, "requests.jsonl")
    requests, bad_requests = (
        _load_jsonl(requests_path) if os.path.exists(requests_path)
        else ([], 0)
    )
    steps_path = os.path.join(logdir, "steps.jsonl")
    steps_rows, bad_steps = (
        _load_jsonl(steps_path) if os.path.exists(steps_path)
        else ([], 0)
    )
    goodput, bad_goodput = load_goodput(logdir)
    train, evals = split_rows(rows)
    fleet, bad_fleet = fleet_summary(logdir, train, trace, flight)
    rpc, bad_journal = rpc_summary(train, logdir)
    alerts, bad_alerts = alerts_summary(logdir)
    dynamics, bad_dynamics = dynamics_summary(logdir, flight)

    times, source = step_times(train, trace)
    times_sorted = sorted(times)
    percentiles = {
        "p50": _percentile(times_sorted, 0.50),
        "p90": _percentile(times_sorted, 0.90),
        "p99": _percentile(times_sorted, 0.99),
        "max": times_sorted[-1] if times_sorted else float("nan"),
    } if times_sorted else {}

    steps = [int(r["step"]) for r in rows if "step" in r]
    final_train = train[-1] if train else {}
    final_eval = evals[-1] if evals else {}
    report = {
        "logdir": logdir,
        "rows": {"train": len(train), "eval": len(evals),
                 "trace": len(trace)},
        "steps": {"first": min(steps), "last": max(steps)} if steps else {},
        "step_time": {"source": source, "unit": "s/step", **percentiles},
        "breakdown": [
            {"part": p, "s_per_step": s, "fraction": f}
            for p, s, f in breakdown_table(train)
        ],
        "anomalies": collect_anomalies(trace, train),
        "sharding": sharding_summary(train),
        "pipeline": pipeline_summary(train, trace),
        "input_plane": input_plane_summary(train, flight),
        "step_time_opt": step_time_opt_summary(train, logdir),
        "stragglers": straggler_fields(train),
        "flight": flight_summary(flight),
        "captures": capture_summary(captures),
        "goodput": goodput,
        "resilience": resilience_summary(faults, flight, goodput),
        "elasticity": elasticity_summary(flight, goodput),
        "serving": serving_summary(requests, train, steps_rows),
        "fleet": fleet,
        "rpc": rpc,
        "alerts": alerts,
        "dynamics": dynamics,
        # metric-stream health: any unparseable metrics.jsonl / trace /
        # captures / faults / requests line (or an unreadable
        # goodput.json / fleet.json / dispatcher.journal) makes main()
        # exit non-zero (CI gate)
        "parse_errors": (bad_metrics + bad_trace + bad_goodput
                         + bad_captures + bad_faults + bad_requests
                         + bad_steps + bad_fleet + bad_journal
                         + bad_alerts + bad_dynamics),
        "final_metrics": {
            k: v for k, v in final_train.items()
            if k in ("step", "loss", "accuracy", "steps_per_sec",
                     "examples_per_sec_per_chip", "mfu", "mfu_analytic",
                     "mfu_xla_cost")
        },
        "final_eval": final_eval,
    }
    return report


def render(report: dict) -> str:
    lines = [
        f"RUN REPORT — {report['logdir']}",
        "=" * 72,
        (
            f"rows: {report['rows']['train']} train, "
            f"{report['rows']['eval']} eval, {report['rows']['trace']} trace"
        ),
    ]
    if report["steps"]:
        lines.append(
            f"steps: {report['steps']['first']} .. {report['steps']['last']}"
        )
    st = report["step_time"]
    if "p50" in st:
        lines += [
            "",
            f"step time ({st['source']}):",
            (
                f"  p50 {st['p50']:.4g}s   p90 {st['p90']:.4g}s   "
                f"p99 {st['p99']:.4g}s   max {st['max']:.4g}s"
            ),
        ]
    if report["breakdown"]:
        lines += ["", "step-time breakdown (mean per optimizer step):"]
        for b in report["breakdown"]:
            lines.append(
                f"  {b['part']:<12} {b['s_per_step'] * 1e3:9.3f} ms  "
                f"{b['fraction'] * 100:6.2f}%"
            )
    lines += ["", f"anomalies: {len(report['anomalies'])}"]
    for a in report["anomalies"][:20]:
        src = " [offline]" if a.get("source") == "offline_rescan" else ""
        lines.append(f"  step {a.get('step')}: {a.get('anomaly')} — "
                     f"{a.get('message', '')}{src}")
    if len(report["anomalies"]) > 20:
        lines.append(f"  ... {len(report['anomalies']) - 20} more")
    fl = report.get("flight")
    if fl:
        exit_note = ("clean exit" if fl["clean_exit"]
                     else "NOT a clean exit — died mid-flight")
        lines += [
            "",
            f"flight recorder: {fl['events']} events ({exit_note})",
        ]
        t_last = None
        for e in fl["last"]:
            if isinstance(e.get("t"), (int, float)):
                t_last = e["t"]
        for e in fl["last"]:
            t = e.get("t")
            rel = (f"{t - t_last:+9.2f}s"
                   if isinstance(t, (int, float)) and t_last is not None
                   else " " * 10)
            extra = " ".join(
                f"{k}={v}" for k, v in e.items()
                if k not in ("t", "kind", "stacks", "message")
            )
            lines.append(f"  {rel}  {e.get('kind', '?'):<18} {extra}".rstrip())
    cap = report.get("captures")
    if cap:
        trig = ", ".join(f"{k} x{v}" for k, v in cap["triggers"].items())
        lines += [
            "",
            f"captures: {cap['count']} profiler window(s) ({trig})",
        ]
        for w in cap["windows"]:
            wall = w.get("wall_s")
            over = w.get("overhead_s")
            note = "  ABORTED" if w.get("aborted") else ""
            line = (
                f"  #{w.get('id')} {w['trigger']:<22} steps "
                f"{w.get('step_begin')}..{w.get('step_end')}"
            )
            if isinstance(wall, (int, float)):
                line += f"  wall {wall:.3g}s"
            if isinstance(over, (int, float)):
                line += f"  overhead {over:.3g}s"
            lines.append(line + f"  {w.get('dir')}{note}")
    gp = report.get("goodput")
    if gp:
        wall = gp.get("wall_s", 0.0) or 0.0
        frac = gp.get("goodput_fraction", 0.0) or 0.0
        gens = gp.get("generations", 1)
        restarts = gp.get("restarts", max(gens - 1, 0))
        lines += [
            "",
            (
                f"goodput: {frac * 100:.1f}% productive (train_step) of "
                f"{wall:.1f}s wall — {gens} generation(s), "
                f"{restarts} restart(s)"
            ),
        ]
        buckets = gp.get("buckets") or {}
        for name, secs in sorted(buckets.items(), key=lambda kv: -kv[1]):
            pct = 100.0 * secs / wall if wall else 0.0
            lines.append(f"  {name:<18} {secs:10.2f} s  {pct:6.2f}%")
    res = report.get("resilience")
    if res:
        healed = (
            "all recovered" if not res["unpaired"]
            else f"{len(res['unpaired'])} UNRECOVERED"
        )
        lines += [
            "",
            (
                f"resilience: {res['faults_injected']} fault(s) injected "
                f"({healed}), {res['restarts']} supervised restart(s), "
                f"{res['fallback_restores']} checkpoint fallback(s), "
                f"{res['worker_respawns']} worker respawn(s)"
            ),
        ]
        for kind, d in sorted(res["faults_by_kind"].items()):
            lines.append(
                f"  fault {kind:<20} injected {d['injected']}  "
                f"recovered {d['recovered']}"
            )
        for e in res["restart_events"]:
            extra = ""
            if e.get("rejected_checkpoints"):
                extra = (f"  (fell back past "
                         f"{e['rejected_checkpoints']} corrupt ckpt)")
            lines.append(
                f"  restart #{e.get('attempt')}: {e.get('failure')} -> "
                f"resumed step {e.get('step')} after "
                f"{e.get('backoff_s')}s backoff{extra}"
            )
        if res.get("rejected_checkpoint_steps"):
            lines.append(
                "  rejected checkpoint step(s): "
                f"{res['rejected_checkpoint_steps']}"
            )
        if "badput_restart_s" in res:
            lines.append(
                f"  restart cost (badput_restart): "
                f"{res['badput_restart_s']:.2f} s"
            )
        if res.get("gave_up"):
            lines.append("  SUPERVISOR GAVE UP — retry budget exhausted")
        for u in res["unpaired"]:
            lines.append(
                f"  UNRECOVERED fault #{u['id']} {u['kind']} "
                f"(step {u['step']})"
            )
    el = report.get("elasticity")
    if el:
        share = ""
        if "goodput_share" in el:
            share = f", {el['goodput_share'] * 100:.1f}% of run wall"
        lines += [
            "",
            (
                f"elasticity: {el['resizes']} resize(s) "
                f"({el['completed']} completed, {el['failed']} failed), "
                f"{el['resize_wall_s']:.2f} s total resize wall{share}"
            ),
        ]
        for w in el["windows"]:
            dur = w.get("duration_s")
            cost = (f"{dur:.2f} s"
                    if isinstance(dur, (int, float)) else "? s")
            lines.append(
                f"  {w.get('from_devices')} -> {w.get('to_devices')} "
                f"devices at step {w.get('step')}: {w.get('outcome')} "
                f"in {cost} (source {w.get('source')})"
            )
    srv = report.get("serving")
    if srv:
        stat = ", ".join(f"{k} x{v}" for k, v in srv["by_status"].items())
        lines += [
            "",
            (
                f"serving: {srv['requests']} request(s) ({stat}) — "
                f"{srv['tokens_generated']} tokens at "
                f"{srv['tokens_per_sec']:.1f} tok/s, peak batch occupancy "
                f"{srv['occupancy_max']}"
            ),
        ]
        for name, label in (("ttft_s", "ttft"), ("tpot_s", "tpot"),
                            ("e2e_s", "e2e")):
            d = srv.get(name) or {}
            if d:
                lines.append(
                    f"  {label:<5} p50 {d['p50']:.4g}s   p99 {d['p99']:.4g}s"
                )
        if srv.get("finish_reasons"):
            fr = ", ".join(f"{k} x{v}"
                           for k, v in sorted(srv["finish_reasons"].items()))
            lines.append(f"  finish: {fr}")
        pc = srv.get("prefix_cache")
        if pc:
            lines.append(
                f"  prefix cache: hit rate {pc['hit_rate']:.0%} "
                f"({pc['requests_with_hits']} request(s)), "
                f"{pc['cached_tokens']} cached tokens "
                f"({pc['cached_token_share']:.0%} of prompt tokens)"
            )
        ts = srv.get("token_split")
        if ts:
            lines.append(
                f"  tokens: {ts['prompt_prefilled']} prefilled + "
                f"{ts['prompt_cached']} cache-mapped prompt, "
                f"{ts['decode']} decoded"
            )
        fp = srv.get("decode_fast_path")
        if fp:
            bits = [f"fused_sampling={'on' if fp['fused_sampling'] else 'off'}"]
            if fp.get("speculate"):
                bits.append(f"speculate={fp['speculate']}")
            if "acceptance_rate" in fp:
                bits.append(
                    f"{fp['acceptance_rate']:.0%} acceptance "
                    f"({fp['accepted']}/{fp['drafted']} drafts)"
                )
            if "tokens_per_step" in fp:
                bits.append(f"{fp['tokens_per_step']:.2f} tokens/step")
            if "dispatches_per_step" in fp:
                bits.append(
                    f"{fp['dispatches_per_step']:.1f} dispatches/step"
                )
            lines.append("  decode fast path: " + ", ".join(bits))
        bu = srv.get("prefill_budget")
        if bu:
            util = (f", {bu['utilization']:.0%} of the "
                    f"{bu['budget_tokens']}-token budget"
                    if "utilization" in bu else " (unbudgeted)")
            lines.append(
                f"  prefill: {bu['tokens_per_iter']:.1f} tokens/iteration "
                f"over {bu['prefill_iters']} iteration(s){util}"
            )
        ta = srv.get("tail_attribution")
        if ta:
            lines.append(
                f"  tail attribution ({ta['requests']} request(s), "
                f"{ta['covered_share']:.0%} within 5% of e2e):"
            )
            for label, _ in _ATTR_COMPONENTS:
                c = ta["components"][label]
                mark = "  << dominant" if label == ta["dominant"] else ""
                lines.append(
                    f"    {label:<8} p50 {c['p50_mean_s'] * 1e3:9.3f} ms"
                    f"   p99 {c['tail_mean_s'] * 1e3:9.3f} ms"
                    f"   growth {c['growth_s'] * 1e3:+9.3f} ms{mark}"
                )
        sl = srv.get("step_log")
        if sl:
            lines.append(
                f"  step log: {sl['records']} iteration record(s), "
                f"{sl['tokens_committed']} decode tokens committed, "
                f"{sl['budget_stalls']} prefill budget stall(s)"
            )
        if srv.get("rejected"):
            lines.append(f"  REJECTED {srv['rejected']} request(s) "
                         "(queue backpressure)")
    flt = report.get("fleet")
    if flt:
        parts = []
        ps = flt.get("peer_states")
        if ps:
            parts.append(
                f"{sum(ps.values())} peer(s) — "
                + ", ".join(f"{ps.get(s, 0)} {s}"
                            for s in ("up", "stale", "down"))
            )
        if "cross_process_traces" in flt:
            parts.append(
                f"{flt['cross_process_traces']} cross-process trace(s) "
                f"({flt['cross_process_spans']} spans)"
            )
        lines += ["", "fleet: " + (", ".join(parts) or "telemetry only")]
        for name, p in sorted((flt.get("peers") or {}).items()):
            lines.append(
                f"  peer {name}: {p.get('addr')}  {p.get('state')}  "
                f"ok {p.get('ok')} err {p.get('errors')}"
            )
        ws = flt.get("worst_spread")
        if ws:
            flag = "  ** STRAGGLER **" if ws.get("straggling") else ""
            lines.append(
                f"  worst straggler spread: {ws.get('ratio', 0.0):.2f}x "
                f"on {ws.get('key')} (peer {ws.get('peer')}){flag}"
            )
        for slo, windows in (flt.get("slo_burn_rates") or {}).items():
            lines.append(
                "  slo " + slo + ": "
                + ", ".join(f"{w} burn {windows[w]:.2f}x"
                            for w in sorted(windows))
            )
        if flt.get("slo_violations"):
            lines.append(
                f"  SLO VIOLATIONS: {len(flt['slo_violations'])} "
                "flight event(s)"
            )
            for v in flt["slo_violations"][:10]:
                lines.append(
                    f"    {v.get('slo')} {v.get('window')}-window burn "
                    f"{v.get('burn')}x (limit {v.get('limit')}x, "
                    f"{v.get('metric')})"
                )
    rpc = report.get("rpc")
    if rpc:
        parts = []
        if "retries_total" in rpc:
            parts.append(f"{rpc['retries_total']} retried attempt(s)")
        if rpc.get("deadline_misses_total"):
            parts.append(f"{rpc['deadline_misses_total']} deadline "
                         "miss(es)")
        if rpc.get("breaker_trips_total"):
            parts.append(f"{rpc['breaker_trips_total']} breaker trip(s)")
        if rpc.get("stream_resumes"):
            parts.append(f"{rpc['stream_resumes']} stream resume(s)")
        lines += ["", "rpc: " + (", ".join(parts) or "telemetry only")]
        for ep, d in (rpc.get("endpoints") or {}).items():
            bits = [f"attempts {d.get('attempts', 0)}"]
            retries = d.get("retries_ok", 0) + d.get("retries_error", 0)
            if retries:
                bits.append(f"retries {retries} "
                            f"(ok {d.get('retries_ok', 0)} / err "
                            f"{d.get('retries_error', 0)})")
            if d.get("deadline_misses"):
                bits.append(f"deadline misses {d['deadline_misses']}")
            if "breaker" in d:
                cyc = "".join(
                    f" {to}x{d[f'breaker_to_{to}']}"
                    for to in ("open", "half_open", "closed")
                    if d.get(f"breaker_to_{to}")
                )
                bits.append(f"breaker {d['breaker']}"
                            + (f" (transitions:{cyc})" if cyc else ""))
            lines.append(f"  {ep}: " + "  ".join(bits))
        j = rpc.get("journal")
        if j:
            kinds = ", ".join(f"{k} x{v}" for k, v in j["by_kind"].items())
            lines.append(
                f"  dispatcher journal: {j['records']} record(s) "
                f"({kinds}), {j['replays']} replay(s)"
            )
            for epoch, gen in sorted(j["epochs"].items()):
                lines.append(f"    epoch {epoch}: generation {gen}")
    al = report.get("alerts")
    if al:
        sev = ", ".join(f"{k} x{v}" for k, v in
                        (al.get("by_severity") or {}).items())
        lines += ["", f"alerts: {al.get('fired', 0)} firing(s)"
                  + (f" ({sev})" if sev else "")
                  + (f", {len(al['open'])} still open"
                     if al.get("open") else "")]
        for rule, n in (al.get("by_rule") or {}).items():
            lines.append(f"  {rule}: fired x{n}")
        for o in al.get("open", []):
            lines.append(f"  OPEN: {o.get('rule')} "
                         f"[{o.get('severity')}] id {o.get('id')}")
        for b in al.get("incidents", []):
            lines.append(
                f"  incident {b.get('dir')}: rule {b.get('rule')} "
                f"[{b.get('severity')}], {b.get('files', 0)} evidence "
                "file(s)")
    dyn = report.get("dynamics")
    if dyn and dyn.get("rows"):
        st = dyn.get("steps") or {}
        lines += [
            "",
            (
                f"training dynamics: {dyn['rows']} cadence row(s) "
                f"(every {dyn.get('every')}, steps "
                f"{st.get('first')}..{st.get('last')})"
            ),
        ]
        gg = dyn.get("global_grad_norm") or {}
        if isinstance(gg.get("last"), (int, float)):
            lines.append(
                f"  global grad norm: last {gg['last']:.4g}, "
                f"max {gg.get('max', float('nan')):.4g}"
            )
        for m, d in (dyn.get("modules") or {}).items():
            bits = []
            for key, label in (("grad_norm", "grad"),
                               ("param_norm", "param"),
                               ("update_ratio", "upd")):
                if isinstance(d.get(key), (int, float)):
                    bits.append(f"{label} {d[key]:.4g}")
            if d.get("nonfinite_grads"):
                bits.append(f"NONFINITE x{d['nonfinite_grads']}")
            lines.append(f"  module {m:<12} " + "  ".join(bits))
        if dyn.get("nonfinite_steps"):
            lines.append(
                "  NON-FINITE gradient row(s) at step(s): "
                f"{dyn['nonfinite_steps']}"
            )
        prov = dyn.get("provenance")
        if prov:
            lines.append(
                f"  nan provenance: module '{prov.get('module') or '?'}' "
                f"first non-finite at step {prov.get('step')} "
                f"({prov.get('reason')}, via {prov.get('method')})"
            )
    sto = report.get("step_time_opt")
    if sto:
        parts = []
        if "quant_mode" in sto:
            parts.append(f"quant={sto['quant_mode']}")
        ov = sto.get("overlap")
        if ov:
            cov = ov.get("coverage")
            parts.append(
                f"overlap {ov['buckets']} bucket(s)"
                + (f", {cov * 100:.0f}% coverage"
                   if isinstance(cov, (int, float)) else "")
            )
        if sto.get("autotuned_blocks"):
            parts.append(f"{len(sto['autotuned_blocks'])} autotuned "
                         "flash tiling(s)")
        lines += ["", "step-time attack: " + (", ".join(parts) or "none")]
        if ov and isinstance(ov.get("dispatch_share"), (int, float)):
            lines.append(
                f"  overlapped collective dispatches: "
                f"{ov['dispatch_share'] * 100:.1f}%"
            )
        for b in sto.get("autotuned_blocks", []):
            lines.append(
                f"  flash {b.get('platform')}/{b.get('dtype')} "
                f"seq {b.get('seq')} d {b.get('depth')}: "
                f"block_q {b.get('block_q')} block_k {b.get('block_k')}"
                + (f"  ({b.get('ms'):.3g} ms, {b.get('source')})"
                   if isinstance(b.get("ms"), (int, float)) else
                   f"  ({b.get('source')})")
            )
    ip = report.get("input_plane")
    if ip:
        parts = []
        if isinstance(ip.get("data_wait_share"), (int, float)):
            parts.append(
                f"data-wait {ip['data_wait_share'] * 100:.1f}% of step time"
            )
        if "data_prefetch_depth" in ip:
            parts.append(f"prefetch depth {int(ip['data_prefetch_depth'])}")
        if "data_client_window" in ip:
            parts.append(f"credit window {int(ip['data_client_window'])}")
        if "data_batches_total" in ip:
            parts.append(f"{int(ip['data_batches_total'])} batches")
        lines += ["", "input plane: " + (", ".join(parts) or "telemetry only")]
        for addr, d in (ip.get("workers") or {}).items():
            lines.append(
                f"  worker {addr}: {int(d.get('batches', 0))} batches, "
                f"mean fetch {d.get('mean_fetch_ms', 0.0):.2f} ms"
            )
        dropped = ip.get("data_service_workers_dropped_total")
        if dropped:
            lines.append(f"  workers dropped: {int(dropped)}")
        moved = ip.get("data_service_resharded_splits_total")
        if moved:
            lines.append(
                f"  elastically re-assigned splits: {int(moved)}"
            )
        for e in ip.get("reshard_events", []):
            lines.append(
                f"  RESHARD: worker {e.get('worker')} died, "
                f"{e.get('splits')} split(s) re-assigned at gen "
                f"{e.get('gen')} (epoch {e.get('epoch')})"
            )
    sh = report.get("sharding")
    if sh:
        mode = (
            f"ZeRO stage {sh['zero_stage']}"
            + (f" (degree {sh['zero_degree']})" if "zero_degree" in sh
               else "")
            if sh.get("zero_stage") else "replicated"
        )
        lines += ["", f"weight-update sharding: {mode}"]
        for key, label in (
            ("params_bytes_per_device", "params"),
            ("opt_state_bytes_per_device", "optimizer state"),
        ):
            if key in sh:
                lines.append(
                    f"  {label:<16} {sh[key] / (1 << 20):10.2f} MiB/device"
                )
    pp = report.get("pipeline")
    if pp:
        lines += ["", "pipeline:"]
        if "schedule" in pp:
            lines.append(
                f"  schedule {pp['schedule']}  stages "
                f"{pp.get('stages', '?')}  microbatches "
                f"{pp.get('microbatches', '?')}  virtual "
                f"{pp.get('virtual', 1)}  predicted bubble "
                f"{pp.get('predicted_bubble', 0.0):.1%}"
            )
        if "handoff" in pp:
            h = pp["handoff"]
            lines.append(
                f"  stage handoffs: {h['count']}  "
                f"p50 {h['p50_s'] * 1e3:.3g}ms  "
                f"p99 {h['p99_s'] * 1e3:.3g}ms"
            )
        for stage, d in (pp.get("link_stalls") or {}).items():
            lines.append(
                f"  link stalls {stage}: {d['count']} "
                f"({d['total_s']:.3g}s blocked on the credit window)"
            )
    if report["stragglers"]:
        lines += ["", "straggler summary (last record):"]
        for base, d in report["stragglers"].items():
            lines.append(
                f"  {base}: min/median/max = "
                f"{d.get('host_min', float('nan')):.4g}/"
                f"{d.get('host_median', float('nan')):.4g}/"
                f"{d.get('host_max', float('nan')):.4g}s  "
                f"straggler host {int(d.get('straggler', -1))}"
            )
    if report["final_metrics"]:
        lines += ["", "final train record: " + " ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in report["final_metrics"].items()
        )]
    if report["final_eval"]:
        lines.append("final eval record:  " + " ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in report["final_eval"].items()
        ))
    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("logdir", help="directory holding metrics.jsonl "
                                  "(+ optional trace.jsonl)")
    p.add_argument("--json", action="store_true",
                   help="emit the report as one JSON object")
    args = p.parse_args(argv)
    report = build_report(args.logdir)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(render(report), end="")
    # CI gate: a metric stream that is missing rows or had unparseable
    # lines must fail the report, not silently render a partial one.
    if report.get("parse_errors"):
        print(
            f"run_report: {report['parse_errors']} unparseable telemetry "
            "entries (metrics/trace/captures/faults/requests/steps/"
            "goodput/fleet/dispatcher-journal)", file=sys.stderr,
        )
        return 1
    if not (report["rows"]["train"] or report["rows"]["eval"]):
        print("run_report: metrics.jsonl contains no valid rows",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    raise SystemExit(main())
