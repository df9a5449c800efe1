import pytest

import window


def _req(due, times, sent=None, status=200, error=None, counts=None):
    return {"id": "x", "due": due, "sent": due if sent is None else sent,
            "status": status, "error": error, "prompt_tokens": 4,
            "max_new_tokens": 8, "token_times": times,
            "token_counts": counts or [1] * len(times)}


def test_gaps_count_when_the_later_token_is_inside_the_window():
    logs = [
        _req(-3.0, [-1.0, -0.5, 0.25, 1.0]),     # warm-in request
        _req(8.0, [9.0, 9.5, 10.5]),             # last token after T = 10
    ]
    s = window.account(logs, seconds=10.0, guard_s=2.0)
    # gaps: 0.75 (straddles the opening), 0.75, 0.5; not -0.5 -> 9.0's own
    # first token, not the one ending at 10.5
    assert s["n_gaps"] == 3
    assert s["itl_mean_ms"] == pytest.approx(1e3 * (0.75 + 0.75 + 0.5) / 3)
    # tokens inside [0, 10]: 0.25, 1.0, 9.0, 9.5
    assert s["tokens"] == 4 and s["serve_tok_per_s"] == pytest.approx(0.4)


def test_guard_and_failure_accounting():
    logs = [
        _req(1.0, [1.5, 2.0]),                  # ttft 0.5
        _req(7.9, [9.9]),                       # ttft 2.0, before the guard
        _req(8.5, [8.6]),                       # inside the guard: no ttft
        _req(3.0, []),                          # no first token by T: failed
        _req(-1.0, [-0.5]),                     # the period starts at -guard
        _req(-3.0, [], status=429),             # refused in the warm-in
    ]
    s = window.account(logs, seconds=10.0, guard_s=2.0)
    assert s["n_ttft"] == 4 and s["attempted"] == 5
    assert s["failed"] == 2 and s["refused"] == 1
    assert s["ttft_mean_ms"] == pytest.approx(
        1e3 * (0.5 + 2.0 + 7.0 + 0.5) / 4)
    # above capacity a request still queued at T is no failure
    s = window.account(logs, seconds=10.0, guard_s=0.0, judge_ttft=False)
    assert s["failed"] == 1 and s["n_ttft"] == 3


def test_lateness_is_send_minus_due_in_the_window():
    logs = [_req(1.0, [2.0], sent=1.004), _req(2.0, [3.0], sent=2.001),
            _req(-1.0, [0.5], sent=-0.5)]
    s = window.account(logs, seconds=10.0, guard_s=0.0)
    assert 1.0 < s["loadgen_late_p95_ms"] < 4.0


def test_percentile_interpolates():
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile(list(range(101)), 95) == 95
    assert window.percentile([7.0], 95) == 7.0
