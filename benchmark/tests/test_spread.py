"""``tools/spread.py`` reads two sets of runs as the driver's check does."""

import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "spread", os.path.join(BENCH, "tools", "spread.py"))
spread = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(spread)

# PR 26's refusal (ledger): itl_mean_ms of gpt2m-serve-chat-steady, median
# 57.161 ms, bound 1.3 % = 0.743 ms, "the spread is 0.366908 and 0.616076".
# Each set's farthest run is left out; what is left spans those ranges.
SET_A = [57.161 - 0.20, 57.161 - 0.05, 57.161, 57.161, 57.161 + 0.166908,
         57.161 + 0.9]
SET_B = [57.30 - 0.316076, 57.30 - 0.1, 57.30, 57.30, 57.30 + 0.3,
         57.30 - 1.4]


def test_trimmed_range_leaves_out_the_farthest_run_only_if_that_narrows():
    assert spread.trimmed_range(SET_A) == pytest.approx(0.366908, abs=1e-6)
    assert spread.trimmed_range(SET_B) == pytest.approx(0.616076, abs=1e-6)
    assert spread.whole_range(SET_A) == pytest.approx(1.1, abs=1e-6)
    # two far-off runs: only one is left out
    assert spread.trimmed_range([1.0, 1.0, 1.0, 1.0, 2.0, 2.0]) == 1.0
    assert spread.trimmed_range([5.0, 5.0]) == 0.0


def test_pr26_case_fails_at_66_percent_of_its_bound():
    j = spread.judge([SET_A, SET_B], 0.013)
    assert j["room"] == pytest.approx(0.743093, abs=1e-4)
    assert j["share_of_bound"] == pytest.approx(0.66, abs=0.005)
    assert not j["tight_ok"] and not j["within_margin"]
    # the bound that the same runs would have needed, with margin
    assert spread.least_bound([SET_A, SET_B]) == 0.025
    ok = spread.judge([SET_A, SET_B], 0.025)
    assert ok["tight_ok"] and ok["within_margin"] and ok["loose_ok"]


def test_too_loose_and_drifting_sets_fail():
    steady = [[100.0, 100.1, 100.0, 99.9, 100.0, 100.05]] * 2
    assert spread.judge(steady, 0.01)["loose_ok"]      # 1 % never too loose
    assert not spread.judge(steady, 0.05)["loose_ok"]  # 5 > 8 x 0.2
    drift = [steady[0], [v + 1.5 for v in steady[0]]]
    assert not spread.judge(drift, 0.01)["drift_ok"]
    assert spread.judge(drift, 0.02)["drift_ok"]
