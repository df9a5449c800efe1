import json
import os

import pytest

import flops as peaks_and_roofline
import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the hand sums below are those of PR 23, on the module they moved to
flops = harness.load_module(os.path.join(BENCH, "counts", "gpt2.py"))


def _model():
    with open(os.path.join(BENCH, "configs", "gpt2-medium-train.json")) as f:
        return json.load(f)


def test_gpt2_medium_hand_sums():
    m = _model()
    # per layer: 3 d^2 (qkv) + d^2 (proj) + 8 d^2 (MLP) = 12 d^2
    assert flops.matmul_params(m) == 24 * 12 * 1024 ** 2 + 50257 * 1024
    assert flops.matmul_params(m) == 353_453_056
    assert 6 * flops.matmul_params(m) == pytest.approx(2.1207e9, rel=1e-4)
    # attention: 12 L d S = 0.302 GFLOP/token in full at 1024, half causally
    full = flops.attention_flops_per_token(m, 1024, causal=False)
    assert full == 12 * 24 * 1024 * 1024 == 301_989_888
    assert flops.attention_flops_per_token(m, 1024) == full / 2
    # the benchmark counts causally: 2.1207 + 0.1510 = 2.2717 GFLOP/token
    assert flops.train_flops_per_token(m, 1024) == pytest.approx(
        2.27171e9, rel=1e-5)


def test_kernel_requirements_agree_with_the_per_token_count():
    m = _model()
    f = flops.flash_flops(m, batch=64, seq_len=1024)
    per_token = (f["fwd"] + f["bwd"]) / (64 * 1024)
    assert per_token == flops.attention_flops_per_token(m, 1024)
    assert flops.xent_flops(m, 1000) == 6 * 1000 * 1024 * 50257
    assert flops.kv_bytes_per_token(m) == 98304            # 98 KB a token
    assert flops.weight_bytes(m, 2) == pytest.approx(0.7069e9, rel=1e-3)
    b = flops.decode_iter_bytes(m, live_kv_tokens=1000, weight_dtype_bytes=2)
    assert b == flops.weight_bytes(m, 2) + 1000 * 98304


def test_step_kernel_is_what_the_roofline_reader_summed():
    m = _model()
    f, b = flops.flash_flops(m, 64, 1024), flops.flash_bytes(m, 64, 1024)
    assert flops.step_kernel(m, "flash") == {
        "flops": f["fwd"] + f["bwd"], "bytes": b["fwd"] + b["bwd"]}
    tokens = 64 * 1023
    assert flops.step_kernel(m, "xent") == {
        "flops": flops.xent_flops(m, tokens),
        "bytes": flops.xent_bytes(m, tokens)}
    assert flops.train_flops_per_token(m, 1024) == 2271713280.0
    with pytest.raises(KeyError):
        flops.step_kernel(m, "conv")


def test_peaks_table_has_no_default():
    flops = peaks_and_roofline
    assert flops.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for unknown in ("cpu", "source", "TPU v9"):
        with pytest.raises(KeyError):
            flops.peaks(unknown)
    r = flops.roofline_seconds(197e12, 819e9 / 2, "TPU v5 lite")
    assert r == {"seconds": 1.0, "bound": "compute"}
