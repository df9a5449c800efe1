"""tools/run_report.py + tools/check_metrics_schema.py against a synthetic
logdir — the tier-1 exercise of the reporting path (no training needed)."""

import json

import pytest

from tools import check_metrics_schema, run_report


def _write_jsonl(path, rows):
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))


@pytest.fixture
def logdir(tmp_path):
    rows = []
    for i, step in enumerate(range(10, 101, 10)):
        rows.append({
            "step": step, "loss": 2.0 - 0.01 * i, "accuracy": 0.1 + 0.05 * i,
            "steps_per_sec": 10.0,
            "t_step": 0.1 if step < 100 else 0.4,  # final window regresses
            "t_data": 0.01, "t_dispatch": 0.08, "t_host": 0.001,
            "f_data": 0.1, "f_dispatch": 0.8, "f_host": 0.01,
            "t_step_host_min": 0.09, "t_step_host_median": 0.1,
            "t_step_host_max": 0.12, "t_step_straggler": 3,
        })
        if step % 50 == 0:
            rows.append({"step": step, "eval_loss": 1.5, "eval_accuracy": 0.5})
    _write_jsonl(tmp_path / "metrics.jsonl", rows)
    trace = [
        {"step": s, "k": 1, "t_wall": 0.1,
         "spans": [{"name": "data_wait", "dur_s": 0.01},
                   {"name": "train_step", "dur_s": 0.08}]}
        for s in range(1, 6)
    ]
    trace.append({"kind": "anomaly", "step": 100,
                  "anomaly": "step_time_regression",
                  "message": "step time 0.4s is 4.0x the trailing median",
                  "value": 0.4})
    _write_jsonl(tmp_path / "trace.jsonl", trace)
    return tmp_path


def test_build_report_sections(logdir):
    report = run_report.build_report(str(logdir))
    assert report["rows"] == {"train": 10, "eval": 2, "trace": 6}
    assert report["steps"] == {"first": 10, "last": 100}
    st = report["step_time"]
    assert st["source"] == "t_step breakdown fields"
    assert st["p50"] == pytest.approx(0.1)
    assert st["max"] == pytest.approx(0.4)
    parts = {b["part"]: b for b in report["breakdown"]}
    assert parts["data_wait"]["s_per_step"] == pytest.approx(0.01)
    assert 0 < parts["dispatch"]["fraction"] < 1
    # recorded anomaly survives; step-time regression at step 100
    kinds = {a["anomaly"] for a in report["anomalies"]}
    assert "step_time_regression" in kinds
    assert report["stragglers"]["t_step"]["straggler"] == 3
    assert report["final_eval"]["eval_accuracy"] == 0.5


def test_render_contains_tables(logdir, capsys):
    assert run_report.main([str(logdir)]) == 0
    out = capsys.readouterr().out
    assert "RUN REPORT" in out
    assert "p50 0.1s" in out
    assert "data_wait" in out and "dispatch" in out
    assert "step_time_regression" in out
    assert "straggler host 3" in out


def test_report_json_mode(logdir, capsys):
    assert run_report.main([str(logdir), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["rows"]["train"] == 10


def test_report_offline_rescan_finds_nan(tmp_path):
    rows = [{"step": s, "loss": 1.0} for s in range(1, 5)]
    # the writer records NaN as the strict-JSON sentinel string
    rows.append({"step": 5, "loss": "NaN"})
    _write_jsonl(tmp_path / "metrics.jsonl", rows)  # no trace.jsonl at all
    report = run_report.build_report(str(tmp_path))
    assert any(
        a["anomaly"] == "non_finite_loss" and a.get("source") == "offline_rescan"
        for a in report["anomalies"]
    )


def test_report_missing_logdir():
    with pytest.raises(SystemExit):
        run_report.build_report("/nonexistent/logdir")


def test_report_missing_metrics_exits_nonzero(tmp_path):
    """CI gate: main() must not exit 0 when metrics.jsonl is absent."""
    with pytest.raises(SystemExit) as exc:
        run_report.main([str(tmp_path)])
    assert exc.value.code not in (0, None)


def test_report_unparseable_rows_exit_nonzero(tmp_path, capsys):
    """A metric stream with broken lines still renders from the good rows
    but exits 1 so CI can gate on it."""
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "loss": 1.0}) + "\n" + "{broken json\n"
    )
    assert run_report.main([str(tmp_path)]) == 1
    assert "RUN REPORT" in capsys.readouterr().out


def test_report_empty_metrics_exit_nonzero(tmp_path):
    (tmp_path / "metrics.jsonl").write_text("not json at all\n")
    assert run_report.main([str(tmp_path)]) == 1


# --- goodput section ---------------------------------------------------------


_GOODPUT = {
    "version": 1,
    "generations": [
        {"gen": 0, "start_t": 0.0, "last_t": 100.0, "ended": "preempted",
         "resumed_step": None, "ckpts": [[4, 60.0]],
         "buckets": {"init": 10.0, "train_step": 80.0, "other": 10.0}},
        {"gen": 1, "start_t": 110.0, "last_t": 160.0, "ended": "clean",
         "resumed_step": 4, "ckpts": [],
         "buckets": {"init": 5.0, "train_step": 45.0}},
    ],
    "merged": {
        "wall_s": 160.0,
        "buckets": {"init": 9.0, "train_step": 93.0, "other": 6.0,
                    "lost_work": 40.0, "badput_restart": 10.0,
                    "checkpoint_save": 2.0},
        "goodput_fraction": 0.5813,
        "generations": 2, "restarts": 1,
    },
}


def test_report_goodput_section(logdir, capsys):
    (logdir / "goodput.json").write_text(json.dumps(_GOODPUT))
    report = run_report.build_report(str(logdir))
    gp = report["goodput"]
    assert gp["goodput_fraction"] == 0.5813
    assert gp["buckets"]["lost_work"] == 40.0
    assert gp["ended"] == ["preempted", "clean"]
    assert run_report.main([str(logdir)]) == 0
    out = capsys.readouterr().out
    assert "goodput: 58.1% productive" in out
    assert "lost_work" in out and "badput_restart" in out
    # --json mode carries the same merged ledger
    assert run_report.main([str(logdir), "--json"]) == 0
    as_json = json.loads(capsys.readouterr().out)
    assert as_json["goodput"]["buckets"] == gp["buckets"]


def test_report_unreadable_goodput_exits_nonzero(logdir):
    (logdir / "goodput.json").write_text("{broken")
    assert run_report.main([str(logdir)]) == 1


def test_report_without_goodput_has_empty_section(logdir):
    assert run_report.build_report(str(logdir))["goodput"] == {}


# --- flight recorder section -------------------------------------------------


_FLIGHT = [
    {"t": 100.0, "kind": "fit_begin", "step": 0, "total_steps": 3},
    {"t": 100.5, "kind": "compile", "label": "train_step", "seconds": 0.5},
    {"t": 101.0, "kind": "step", "step": 1, "k": 1},
    {"t": 101.2, "kind": "log", "step": 1, "loss": 2.1},
    {"t": 101.9, "kind": "watchdog_timeout", "idle_s": 0.7,
     "timeout_s": 0.5, "stacks": "--- thread MainThread ---"},
]


def test_report_flight_section(logdir, capsys):
    _write_jsonl(logdir / "flight.jsonl", _FLIGHT)
    report = run_report.build_report(str(logdir))
    fl = report["flight"]
    assert fl["events"] == 5
    assert fl["clean_exit"] is False  # died mid-flight: no fit_end
    assert fl["kinds"]["fit_begin"] == 1
    assert fl["last"][-1]["kind"] == "watchdog_timeout"
    assert run_report.main([str(logdir)]) == 0
    out = capsys.readouterr().out
    assert "flight recorder: 5 events" in out
    assert "NOT a clean exit" in out
    assert "watchdog_timeout" in out
    assert "--- thread" not in out  # stacks stay out of the one-liner


def test_report_flight_clean_exit(logdir, capsys):
    _write_jsonl(logdir / "flight.jsonl",
                 _FLIGHT[:4] + [{"t": 102.0, "kind": "fit_end", "step": 3}])
    report = run_report.build_report(str(logdir))
    assert report["flight"]["clean_exit"] is True
    assert run_report.main([str(logdir)]) == 0
    assert "clean exit" in capsys.readouterr().out


def test_report_without_flight_has_empty_section(logdir):
    report = run_report.build_report(str(logdir))
    assert report["flight"] == {}


# --- schema checker ---------------------------------------------------------


def test_schema_accepts_valid_rows(tmp_path):
    p = tmp_path / "metrics.jsonl"
    _write_jsonl(p, [
        {"step": 0, "loss": 1.0},
        {"step": 100, "eval_accuracy": 0.99, "hbm_in_use_gib": 1.25},
    ])
    errors, warnings = check_metrics_schema.check_file(str(p))
    assert errors == [] and warnings == []
    assert check_metrics_schema.main([str(p)]) == 0


def test_schema_rejects_bad_rows(tmp_path, capsys):
    p = tmp_path / "metrics.jsonl"
    p.write_text(
        json.dumps({"loss": 1.0}) + "\n"  # missing step
        + json.dumps({"step": -1, "loss": 1.0}) + "\n"  # negative step
        + json.dumps({"step": 2, "note": "a string"}) + "\n"  # non-numeric
        + "{broken json\n"
    )
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 4
    assert check_metrics_schema.main([str(p)]) == 1


def test_schema_warns_on_non_finite(tmp_path):
    p = tmp_path / "metrics.jsonl"
    # both spellings: the sentinel string the current writer emits, and a
    # bare NaN token from a pre-sentinel log (python json still parses it)
    _write_jsonl(p, [{"step": 1, "loss": "NaN"}])
    with open(p, "a") as f:
        f.write('{"step": 2, "loss": NaN}\n')
    errors, warnings = check_metrics_schema.check_file(str(p))
    assert errors == []
    assert len(warnings) == 2  # NaN loss is recordable, flagged not fatal


def test_schema_default_glob_covers_artifacts():
    # the repo's own convergence artifacts must satisfy the documented schema
    assert check_metrics_schema.main([]) == 0


def test_flight_schema_accepts_valid_events(tmp_path):
    p = tmp_path / "flight.jsonl"
    _write_jsonl(p, [
        {"t": 100.0, "kind": "fit_begin", "step": 0},
        {"t": 100.5, "kind": "anomaly", "step": 2, "value": "NaN",
         "message": "loss is nan"},
        {"t": 100.5, "kind": "fit_end", "step": 3, "preempted": False},
    ])
    errors, warnings = check_metrics_schema.check_file(str(p))
    assert errors == [] and warnings == []
    assert check_metrics_schema.main([str(p)]) == 0


def test_flight_schema_rejects_bad_events(tmp_path):
    p = tmp_path / "flight.jsonl"
    _write_jsonl(p, [
        {"kind": "step", "step": 1},                 # missing t
        {"t": 100.0, "step": 1},                     # missing kind
        {"t": 99.0, "kind": "step", "step": -1},     # t decreases + bad step
        {"t": 101.0, "kind": "log", "nested": {"a": 1}},  # non-scalar field
    ])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 5
    assert check_metrics_schema.main([str(p)]) == 1


def test_flight_schema_selected_by_basename(tmp_path):
    # the same rows validate as metrics, not flight, under another name
    p = tmp_path / "metrics.jsonl"
    _write_jsonl(p, [{"t": 100.0, "kind": "step"}])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert any("missing 'step'" in e for e in errors)
    p2 = tmp_path / "flight.3.jsonl"  # non-chief hosts' dumps also match
    _write_jsonl(p2, [{"t": 100.0, "kind": "step"}])
    assert check_metrics_schema.check_file(str(p2)) == ([], [])


def test_report_sharding_section(tmp_path, capsys):
    """The weight-update-sharding digest: per-device params/opt-state
    bytes + the ZeRO mode, from the per-record state-bytes fields."""
    p = tmp_path / "metrics.jsonl"
    _write_jsonl(p, [
        {"step": 10, "loss": 1.0, "t_step": 0.1,
         "params_bytes_per_device": 8 << 20,
         "opt_state_bytes_per_device": 2 << 20,
         "zero_stage": 1, "zero_degree": 8},
    ])
    report = run_report.build_report(str(tmp_path))
    assert report["sharding"] == {
        "params_bytes_per_device": 8 << 20,
        "opt_state_bytes_per_device": 2 << 20,
        "zero_stage": 1, "zero_degree": 8,
    }
    out = run_report.render(report)
    assert "weight-update sharding: ZeRO stage 1 (degree 8)" in out
    assert "optimizer state" in out

    # replicated run: fields present, zero_stage absent -> "replicated"
    _write_jsonl(p, [
        {"step": 10, "loss": 1.0,
         "params_bytes_per_device": 8 << 20,
         "opt_state_bytes_per_device": 16 << 20},
    ])
    out = run_report.render(run_report.build_report(str(tmp_path)))
    assert "weight-update sharding: replicated" in out


def test_report_without_state_bytes_has_empty_sharding(logdir):
    report = run_report.build_report(str(logdir))
    assert report["sharding"] == {}
    assert "weight-update sharding" not in run_report.render(report)


def test_prom_schema_validates_collective_op_labels(tmp_path):
    """metrics.prom validation: well-formed samples pass; an unknown
    collective_dispatch_seconds op label is an error (a typo'd op would
    silently fork the histogram's time series)."""
    p = tmp_path / "metrics.prom"
    p.write_text(
        "# snapshot_unix_time 1.0\n"
        "# TYPE collective_dispatch_seconds histogram\n"
        'collective_dispatch_seconds_bucket{le="0.001",op="reduce_scatter"} 2\n'
        'collective_dispatch_seconds_bucket{le="+Inf",op="all_gather"} 3\n'
        'collective_dispatch_seconds_count{op="all_reduce"} 3\n'
        'collective_dispatch_seconds_sum{op="all_to_all"} 0.004\n'
        "steps_per_sec 10.0\n"
    )
    assert check_metrics_schema.check_file(str(p)) == ([], [])
    assert check_metrics_schema.main([str(p)]) == 0

    p.write_text(
        'collective_dispatch_seconds_count{op="not_a_collective"} 1\n'
        "not a sample line\n"
        "steps_per_sec oops\n"
    )
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 3
    assert any("not_a_collective" in e for e in errors)
    assert check_metrics_schema.main([str(p)]) == 1


def test_metrics_rows_validate_flattened_collective_ops(tmp_path):
    """The jsonl-flattened registry scalars carry the same known-op rule
    (collective_dispatch_seconds_count.op_<op>)."""
    p = tmp_path / "metrics.jsonl"
    _write_jsonl(p, [
        {"step": 1, "collective_dispatch_seconds_count.op_reduce_scatter": 2,
         "collective_dispatch_seconds_avg.op_all_gather": 0.001},
    ])
    assert check_metrics_schema.check_file(str(p)) == ([], [])
    _write_jsonl(p, [
        {"step": 1, "collective_dispatch_seconds_count.op_bogus": 2},
    ])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 1 and "bogus" in errors[0]


def test_report_input_plane_section(tmp_path, capsys):
    """The input-plane digest: data-wait share, live adaptive depths,
    per-worker fetch throughput, dropped workers, and elastic RESHARD
    events (data_reshard flights)."""
    _write_jsonl(tmp_path / "metrics.jsonl", [
        {"step": 10, "loss": 1.0, "t_step": 0.1, "t_data": 0.025,
         "data_prefetch_depth": 4, "data_client_window": 3,
         "data_batches_total": 40,
         "data_service_workers_dropped_total": 1,
         "data_service_resharded_splits_total": 1,
         "data_service_fetch_seconds_count.worker_127_0_0_1:9001": 25,
         "data_service_fetch_seconds_sum.worker_127_0_0_1:9001": 0.5,
         "data_service_fetch_seconds_count.worker_127_0_0_1:9002": 15,
         "data_service_fetch_seconds_sum.worker_127_0_0_1:9002": 0.6},
    ])
    _write_jsonl(tmp_path / "flight.jsonl", [
        {"t": 100.0, "kind": "fit_begin"},
        {"t": 101.0, "kind": "data_reshard", "worker": "127.0.0.1:9001",
         "splits": 1, "gen": 1, "epoch": "0"},
        {"t": 102.0, "kind": "fit_end"},
    ])
    report = run_report.build_report(str(tmp_path))
    ip = report["input_plane"]
    assert ip["data_wait_share"] == pytest.approx(0.25)
    assert ip["data_prefetch_depth"] == 4
    assert ip["data_client_window"] == 3
    assert ip["workers"]["127_0_0_1:9001"]["batches"] == 25
    assert ip["workers"]["127_0_0_1:9001"]["mean_fetch_ms"] == pytest.approx(20.0)
    assert len(ip["reshard_events"]) == 1
    out = run_report.render(report)
    assert "input plane: data-wait 25.0% of step time" in out
    assert "prefetch depth 4" in out
    assert "credit window 3" in out
    assert "worker 127_0_0_1:9001: 25 batches, mean fetch 20.00 ms" in out
    assert "workers dropped: 1" in out
    assert "elastically re-assigned splits: 1" in out
    assert ("RESHARD: worker 127.0.0.1:9001 died, 1 split(s) "
            "re-assigned at gen 1") in out


def test_report_without_input_fields_has_empty_input_plane(tmp_path):
    _write_jsonl(tmp_path / "metrics.jsonl", [
        {"step": 10, "loss": 1.0, "t_step": 0.1, "t_data": 0.01},
    ])
    report = run_report.build_report(str(tmp_path))
    assert report["input_plane"] == {}
    assert "input plane" not in run_report.render(report)


def test_metrics_rows_validate_prefetch_component_labels(tmp_path):
    """Flattened data_prefetch_depth/resizes fields: known component and
    direction labels pass; typos are errors (a forked time series)."""
    p = tmp_path / "metrics.jsonl"
    _write_jsonl(p, [{
        "step": 1,
        "data_prefetch_depth.component_prefetcher": 4,
        "data_prefetch_depth.component_client": 2,
        "data_prefetch_resizes_total.component_client.direction_grow": 1,
    }])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert errors == []
    _write_jsonl(p, [{
        "step": 1,
        "data_prefetch_depth.component_sidecar": 4,
    }])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 1 and "component" in errors[0]
    _write_jsonl(p, [{
        "step": 1,
        "data_prefetch_resizes_total.component_client.direction_explode": 1,
    }])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 1 and "direction" in errors[0]


def test_prom_schema_validates_prefetch_labels(tmp_path):
    p = tmp_path / "metrics.prom"
    p.write_text(
        'data_prefetch_depth{component="prefetcher"} 4\n'
        'data_prefetch_depth{component="client"} 2\n'
        'data_prefetch_resizes_total{component="client",direction="grow"} 1\n'
    )
    errors, _ = check_metrics_schema.check_file(str(p))
    assert errors == []
    p.write_text('data_prefetch_depth{component="mystery"} 4\n')
    errors, _ = check_metrics_schema.check_file(str(p))
    assert len(errors) == 1 and "component" in errors[0]


def test_report_fleet_section(logdir, capsys):
    """ISSUE 11: fleet.json peers + worst spread, last-record SLO burn
    fields, slo_violation flight events, and the cross-process trace
    census render in text and --json."""
    (logdir / "fleet.json").write_text(json.dumps({
        "t": 1.0, "interval_s": 0.5, "scrape_rounds": 4,
        "peers": {
            "chief": {"addr": "127.0.0.1:1", "state": "up", "age_s": 0.1,
                      "ok": 4, "errors": 0},
            "data_worker0": {"addr": "127.0.0.1:2", "state": "down",
                             "age_s": 3.0, "ok": 2, "errors": 2},
        },
        "states": {"up": 1, "stale": 0, "down": 1},
        "worst_spread": {"key": "data_service_batches_served_total",
                         "ratio": 2.5, "peer": "data_worker0",
                         "straggling": True},
        "metrics_merged": 12,
    }))
    # burn fields ride the last metric record (registry flattening)
    rows, _ = run_report._load_jsonl(str(logdir / "metrics.jsonl"))
    rows[-1]["slo_burn_rate.slo_e2e_p99.window_fast"] = 3.5
    rows[-1]["slo_burn_rate.slo_e2e_p99.window_slow"] = 1.2
    _write_jsonl(logdir / "metrics.jsonl", rows)
    _write_jsonl(logdir / "flight.jsonl", [
        {"t": 1.0, "kind": "fit_begin", "step": 0},
        {"t": 2.0, "kind": "slo_violation", "slo": "e2e_p99",
         "window": "fast", "burn": 3.5, "limit": 2.0,
         "metric": "serve_e2e_seconds"},
        {"t": 3.0, "kind": "fit_end", "step": 100},
    ])
    # cross-process span rows in the trace stream
    trace, _ = run_report._load_jsonl(str(logdir / "trace.jsonl"))
    trace += [
        {"kind": "span", "name": "data_service.start_epoch",
         "trace_id": "aaaa", "span_id": "1", "t0": 1.0, "dur_s": 0.5},
        {"kind": "span", "name": "data_worker.get_next",
         "trace_id": "aaaa", "span_id": "2", "parent_id": "1",
         "t0": 1.1, "dur_s": 0.1},
        {"kind": "span", "name": "serve.request", "trace_id": "bbbb",
         "span_id": "3", "t0": 2.0, "dur_s": 0.2},
    ]
    _write_jsonl(logdir / "trace.jsonl", trace)

    report = run_report.build_report(str(logdir))
    flt = report["fleet"]
    assert flt["peer_states"] == {"up": 1, "down": 1}
    assert flt["worst_spread"]["ratio"] == 2.5
    assert flt["slo_burn_rates"]["e2e_p99"]["fast"] == 3.5
    assert len(flt["slo_violations"]) == 1
    assert flt["cross_process_traces"] == 2
    assert flt["cross_process_spans"] == 3
    text = run_report.render(report)
    assert "fleet: 2 peer(s) — 1 up, 0 stale, 1 down" in text
    assert "worst straggler spread: 2.50x" in text
    assert "slo e2e_p99: fast burn 3.50x" in text
    assert "SLO VIOLATIONS: 1" in text
    assert "2 cross-process trace(s) (3 spans)" in text
    assert run_report.main([str(logdir)]) == 0


def test_report_unparseable_trace_exits_nonzero(logdir, capsys):
    """The satellite: a corrupt trace.jsonl gates the exit code with a
    one-line diagnostic (the stream-gating convention)."""
    with open(logdir / "trace.jsonl", "a") as f:
        f.write("{this is not json\n")
    assert run_report.main([str(logdir)]) == 1
    err = capsys.readouterr().err
    assert "unparseable telemetry entries" in err


def test_report_unreadable_fleet_json_exits_nonzero(logdir, capsys):
    (logdir / "fleet.json").write_text("{truncated")
    assert run_report.main([str(logdir)]) == 1
    assert "fleet.json: unreadable" in capsys.readouterr().err


# --- serving tail attribution + step log (ISSUE 16) --------------------------


def _ok_request_row(t, e2e, *, queue=0.0, prefill=0.0, stall=0.0,
                    decode=0.0, spec=0.0, gap=0.0, rid="r"):
    """One schema-valid ok row whose attribution components tile e2e by
    construction (callers pass components summing to e2e)."""
    return {
        "t": t, "id": rid, "status": "ok", "prompt_tokens": 8,
        "new_tokens": 4, "finish_reason": "length",
        "ttft_s": queue + prefill + stall, "tpot_s": decode / 3,
        "e2e_s": e2e, "queue_s": queue, "occ_mean": 1.0, "occ_max": 2,
        "slot": 0, "drafted": 0, "accepted": 0,
        "spec_drafted": 0, "spec_accepted": 0,
        "attr_queue_s": queue, "attr_prefill_s": prefill,
        "attr_stall_s": stall, "attr_decode_s": decode,
        "attr_spec_s": spec, "attr_gap_s": gap,
    }


def _step_row(t, step, **kw):
    row = {
        "t": t, "step": step, "phase": "decode", "occupancy": 1,
        "active_slots": 1, "filling_slots": 0, "queue_depth": 0,
        "admitted": 0, "evicted": 0, "prefill_chunks": 0,
        "budget_stall": 0, "tokens_committed": 2, "spec_drafted": 0,
        "spec_accepted": 0, "admit_s": 0.0, "prefill_s": 0.0,
        "decode_s": 0.004, "step_s": 0.005,
    }
    row.update(kw)
    return row


def _serving_logdir(logdir):
    """requests.jsonl where the p99 tail is dominated by prefill-
    interference stall, plus a matching steps.jsonl."""
    reqs = [
        _ok_request_row(100.0 + i, 0.05, queue=0.01, prefill=0.01,
                        decode=0.03, rid=f"fast{i}")
        for i in range(9)
    ]
    reqs.append(_ok_request_row(110.0, 0.55, queue=0.01, prefill=0.01,
                                stall=0.50, decode=0.03, rid="slow"))
    _write_jsonl(logdir / "requests.jsonl", reqs)
    _write_jsonl(logdir / "steps.jsonl", [
        _step_row(100.0, 1, phase="admit+prefill", admitted=1,
                  prefill_chunks=2, tokens_committed=0),
        _step_row(100.1, 2, budget_stall=1),
        _step_row(100.2, 3, tokens_committed=5),
    ])


def test_report_serving_tail_attribution(logdir, capsys):
    _serving_logdir(logdir)
    report = run_report.build_report(str(logdir))
    srv = report["serving"]
    ta = srv["tail_attribution"]
    assert ta["requests"] == 10
    assert ta["dominant"] == "stall"
    assert ta["dominant_growth_s"] == pytest.approx(0.5)
    assert ta["covered_share"] == 1.0  # components tile e2e exactly
    assert srv["step_log"] == {
        "records": 3, "budget_stalls": 1, "tokens_committed": 7,
    }
    assert run_report.main([str(logdir)]) == 0
    text = capsys.readouterr().out
    assert "tail attribution (10 request(s)" in text
    assert "<< dominant" in text
    assert "step log: 3 iteration record(s)" in text


def test_report_corrupt_steps_exits_nonzero(logdir, capsys):
    _serving_logdir(logdir)
    with open(logdir / "steps.jsonl", "a") as f:
        f.write("{not json\n")
    assert run_report.main([str(logdir)]) == 1
    assert "unparseable telemetry entries" in capsys.readouterr().err


def test_steps_schema_accepts_valid_rows(tmp_path):
    p = tmp_path / "steps.jsonl"
    _write_jsonl(p, [
        _step_row(100.0, 1, phase="admit+prefill+decode", admitted=1,
                  prefill_chunks=1),
        _step_row(100.1, 2),
        _step_row(100.2, 5, phase="idle", occupancy=0, active_slots=0,
                  tokens_committed=0),  # gaps in step ids are fine
    ])
    errors, warnings = check_metrics_schema.check_file(str(p))
    assert errors == [] and warnings == []
    assert check_metrics_schema.main([str(p)]) == 0


def test_steps_schema_rejects_bad_rows(tmp_path):
    p = tmp_path / "steps.jsonl"
    _write_jsonl(p, [
        _step_row(100.0, 2),
        _step_row(99.0, 2, phase="warmup"),  # t rewinds, id repeats, phase
        _step_row(100.2, 3, budget_stall=2),  # not a 0/1 flag
        _step_row(100.3, 4, spec_drafted=1, spec_accepted=2),
        _step_row(100.4, 5, admit_s=0.004, prefill_s=0.004,
                  decode_s=0.004, step_s=0.005),  # phases exceed the step
        _step_row(100.5, 6, decode_s=-0.001),  # a negative wall
    ])
    errors, _ = check_metrics_schema.check_file(str(p))
    joined = "\n".join(errors)
    assert "'t' 99.0 decreases" in joined
    assert "does not increase" in joined
    assert "phase" in joined
    assert "budget_stall" in joined
    assert "spec_accepted" in joined
    assert "exceeds step_s" in joined and "'decode_s' -0.001" in joined
    assert check_metrics_schema.main([str(p)]) == 1


def test_requests_schema_validates_attribution_fields(tmp_path):
    p = tmp_path / "requests.jsonl"
    good = _ok_request_row(100.0, 0.05, queue=0.01, decode=0.04)
    neg = dict(_ok_request_row(100.1, 0.05, decode=0.05),
               attr_queue_s=-0.01)
    # components summing way past e2e: not exclusive
    overlap = dict(_ok_request_row(100.2, 0.05, decode=0.05),
                   attr_decode_s=0.05, attr_prefill_s=0.05)
    bad_mirror = dict(_ok_request_row(100.3, 0.05, decode=0.05),
                      spec_drafted=1, spec_accepted=3)
    _write_jsonl(p, [good, neg, overlap, bad_mirror])
    errors, _ = check_metrics_schema.check_file(str(p))
    joined = "\n".join(errors)
    assert not any("line 1" in e for e in errors)
    assert "'attr_queue_s' -0.01" in joined
    assert "not exclusive" in joined
    assert "'spec_accepted' 3 exceeds 'spec_drafted' 1" in joined


def test_history_schema_accepts_valid_rows(tmp_path):
    p = tmp_path / "history.jsonl"
    _write_jsonl(p, [
        {"t": 100.0, "values": {"queue_depth": 3.0, "slo_good.e2e": 0.9}},
        {"t": 102.0, "values": {}},
        {"t": 104.0, "values": {"fleet.loss.median": 1.5}},
    ])
    errors, warnings = check_metrics_schema.check_file(str(p))
    assert errors == [] and warnings == []
    assert check_metrics_schema.main([str(p)]) == 0


def test_history_schema_rejects_bad_rows(tmp_path):
    p = tmp_path / "history.jsonl"
    over = {f"m{i}": 1.0 for i in range(
        check_metrics_schema.HISTORY_MAX_SERIES + 1)}
    _write_jsonl(p, [
        {"t": 100.0, "values": {"ok": 1.0}},
        {"t": 99.0},  # t rewinds, no values
        {"t": 101.0, "values": {"bad name!": 1.0}},
        {"t": 102.0, "values": {"x": "NaN"}},  # writer filters non-finite
        {"t": 103.0, "values": over},
    ])
    errors, _ = check_metrics_schema.check_file(str(p))
    joined = "\n".join(errors)
    assert "'t' 99.0 decreases" in joined
    assert "values" in joined
    assert "bad name!" in joined
    assert check_metrics_schema.main([str(p)]) == 1
