"""Profile-tool hardening: profile_summary exits non-zero with a one-line
diagnostic on a missing or empty profile dir, and the captures.jsonl schema
gate in check_metrics_schema."""

import json

from tools import check_metrics_schema, profile_summary


# -- profile_summary ---------------------------------------------------------

def test_profile_summary_missing_dir_exits_1(tmp_path, capsys):
    assert profile_summary.main([str(tmp_path / "nope")]) == 1
    assert "no such profile dir" in capsys.readouterr().err


def test_profile_summary_empty_dir_exits_1(tmp_path, capsys):
    assert profile_summary.main([str(tmp_path)]) == 1
    assert "no *.xplane.pb" in capsys.readouterr().err


# -- captures.jsonl schema gate ----------------------------------------------

def _write_manifest(tmp_path, rows, name="captures.jsonl"):
    p = tmp_path / name
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return p


def _row(tmp_path, **over):
    (tmp_path / "captures" / "0").mkdir(parents=True, exist_ok=True)
    row = {
        "id": 0, "trigger": "step_time_regression", "reason": "slow",
        "step_begin": 10, "step_end": 15, "t_begin": 100.0, "t_end": 101.5,
        "wall_s": 1.5, "overhead_s": 0.1, "dir": "captures/0",
    }
    row.update(over)
    return row


def test_captures_schema_valid(tmp_path):
    (tmp_path / "captures" / "1").mkdir(parents=True)
    p = _write_manifest(tmp_path, [
        _row(tmp_path),
        _row(tmp_path, id=1, trigger="manual", step_begin=20, step_end=25,
             dir="captures/1"),
    ])
    errors, warnings = check_metrics_schema.check_file(str(p))
    assert errors == []
    assert check_metrics_schema.main([str(p)]) == 0


def test_captures_schema_violations(tmp_path):
    p = _write_manifest(tmp_path, [
        _row(tmp_path, id=1),
        _row(tmp_path, id=1),                      # non-monotonic id
        _row(tmp_path, id=2, trigger="vibes"),     # unknown trigger
        _row(tmp_path, id=3, step_end=10),         # begin == end, not aborted
        _row(tmp_path, id=4, t_end=99.0),          # t_end < t_begin
        _row(tmp_path, id=5, dir="captures/nope"),  # dir missing on disk
        _row(tmp_path, id=6, wall_s=-1.0),         # negative wall
    ])
    errors, _ = check_metrics_schema.check_file(str(p))
    text = "\n".join(errors)
    assert "does not increase" in text
    assert "'trigger' 'vibes'" in text
    assert "must exceed" in text
    assert "precedes t_begin" in text
    assert "does not exist" in text
    assert "'wall_s'" in text
    assert check_metrics_schema.main([str(p)]) == 1


def test_captures_schema_nonfinite_numbers_error_not_crash(tmp_path):
    """json.loads parses bare NaN/Infinity tokens; the checker must turn
    them into reported errors, not an int(nan) traceback."""
    p = tmp_path / "captures.jsonl"
    row = _row(tmp_path)
    text = json.dumps(row).replace('"id": 0', '"id": NaN').replace(
        '"step_end": 15', '"step_end": Infinity'
    )
    p.write_text(text + "\n")
    errors, _ = check_metrics_schema.check_file(str(p))
    text = "\n".join(errors)
    assert "'id' nan" in text
    assert "'step_end' inf" in text


def test_captures_schema_aborted_allows_equal_steps(tmp_path):
    p = _write_manifest(tmp_path, [
        _row(tmp_path, step_end=10, aborted=True),
    ])
    errors, _ = check_metrics_schema.check_file(str(p))
    assert errors == []


def test_goodput_bucket_set_includes_profile_capture():
    """The schema tool's duplicated bucket list stays in sync with
    obs.goodput.BUCKETS (the new profile_capture bucket included)."""
    from distributedtensorflow_tpu.obs.goodput import BUCKETS

    assert set(check_metrics_schema.GOODPUT_BUCKETS) == set(BUCKETS)
    assert "profile_capture" in check_metrics_schema.GOODPUT_BUCKETS


def test_capture_trigger_set_in_sync():
    from distributedtensorflow_tpu.obs.capture import TRIGGERS

    assert set(check_metrics_schema.CAPTURE_TRIGGERS) == set(TRIGGERS)
