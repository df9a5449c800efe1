"""The controls of the correctness comparisons, at a size a test run can
hold (``gpt_tiny`` on the CPU): the system's own int8 path fails the
on-chip check's single-position comparison on every seed while bf16
passes it, and the reference with fp8 weights in the server's place fails the serving
comparison while the float32 reference passes it.
The readings at the cells' own size are in PERF.md section 2."""

import os

import pytest

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
control = harness.load_module(os.path.join(BENCH, "tools", "control.py"))
kind = harness.load_module(os.path.join(
    BENCH, "traffic_kinds", "open-loop-stratified.py"))
TINY = os.path.join(BENCH, "tests", "rehearsal", "configs")


@pytest.mark.parametrize("seed", [11, 12, 2700000013])
def test_quantised_control_fails_the_train_check_and_bf16_passes(seed):
    config = harness.load_json(os.path.join(TINY, "gpt-tiny-train.json"))
    row = control.train(config, [BENCH], seed)
    limit = row["limits"]["token_rms_diff"]
    assert row["sound_ok"] and row["sound"]["token_rms_diff"] < limit
    assert not row["control_ok"]
    assert row["control"]["token_rms_diff"] > limit
    # the mean loss alone would have let the control through
    assert row["control"]["abs_diff"] < row["limits"]["abs_diff"]


@pytest.mark.parametrize("seed", [1, 2, 2700000013])
def test_fp8_control_fails_the_serving_check_and_float32_passes(seed):
    config = harness.load_json(os.path.join(TINY, "gpt-tiny-serve.json"))
    row = control.serve(config, [BENCH], seed)
    assert row["float32_ok"] and row["float32"]["mean_regret"] == 0.0
    assert not row["control_ok"]
    assert row["control"]["mean_regret"] > 2 * row["limit"]
    assert row["control"]["positions_checked"] == 64


def test_compare_takes_the_mean_regret_of_every_served_token():
    check = {"mean_regret_limit": 0.01, "min_positions": 6}
    served = [{"tokens": [5, 7, 9], "max_new_tokens": 3},
              {"tokens": [2, 4, 6], "max_new_tokens": 3}]
    agree = [[[5, 0.9, 0.0], [7, 0.1, 0.0], [9, 0.6, 0.0]],
             [[2, 0.4, 0.0], [4, 0.3, 0.0], [6, 0.2, 0.0]]]
    v = kind._compare(served, agree, check)
    assert v["ok"] and v["mean_regret"] == 0.0
    assert v["positions_checked"] == 6 and v["positions_differing"] == 0
    # a near-tie lost costs little; what follows it still counts
    near = [[[5, 0.9, 0.0], [8, 0.03, 0.03], [9, 0.6, 0.0]], agree[1]]
    v = kind._compare(served, near, check)
    assert v["ok"] and v["mean_regret"] == pytest.approx(0.005)
    assert v["positions_differing"] == 1 and v["largest_regret"] == 0.03
    # a dear one does not pass
    far = [agree[0], [[2, 0.4, 0.0], [5, 0.3, 0.3], [6, 0.2, 0.0]]]
    v = kind._compare(served, far, check)
    assert not v["ok"] and v["mean_regret"] == pytest.approx(0.05)
    # nor a request that came back short, nor too few positions
    short = [served[0], {"tokens": [2, 4], "max_new_tokens": 3}]
    v = kind._compare(short, [agree[0], agree[1][:2]], check)
    assert not v["ok"] and v["requests_short_of_tokens"] == 1
    assert not kind._compare(served, agree, {**check, "min_positions": 7})[
        "ok"]


def test_rounding_keeps_255_levels_a_column_and_leaves_vectors():
    import numpy as np

    rng = np.random.default_rng(0)
    params = {"kernel": rng.normal(size=(64, 8)).astype(np.float32),
              "scale": rng.normal(size=(8,)).astype(np.float32)}
    out = control.round_matrices(params, "int8")
    assert np.array_equal(out["scale"], params["scale"])
    for j in range(8):
        col, src = np.asarray(out["kernel"][:, j]), params["kernel"][:, j]
        step = np.abs(src).max() / 127
        assert len(np.unique(np.round(col / step))) <= 255
        assert np.abs(col - src).max() <= step / 2 + 1e-6
    coarse = control.round_matrices(params, "fp8")["kernel"]
    assert np.abs(np.asarray(coarse) - params["kernel"]).max() > step
    with pytest.raises(ValueError):
        control.round_matrices(params, "int4")
