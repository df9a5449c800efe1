"""MFU estimator reconciliation (ISSUE 7 satellite).

The analytic and xla-cost MFU paths once disagreed 2x on ResNet-50: the
analytic constant passed a MAC count where a MACs x 2 FLOP count was owed.
These tests PIN both estimator paths to the same convention on a known
matmul — XLA's ``cost_analysis()`` counts an ``(M,K) @ (K,N)`` matmul as
exactly ``2*M*N*K`` FLOPs, and the analytic side
(:func:`obs.mfu.matmul_flops`) must use the same arithmetic — so the two
numbers can only diverge for the documented structural reason (scan bodies
counted once; ``xla_flops_scale``), never by a units mismatch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.obs import mfu as mfu_lib

M, K, N = 128, 96, 64


@pytest.fixture(scope="module")
def compiled_matmul():
    a = jnp.zeros((M, K), jnp.float32)
    b = jnp.zeros((K, N), jnp.float32)
    return jax.jit(lambda x, y: x @ y).lower(a, b).compile()


def test_analytic_matmul_convention():
    assert mfu_lib.matmul_flops(M, N, K) == 2 * M * N * K


def test_xla_cost_matches_analytic_on_known_matmul(compiled_matmul):
    """The pin: XLA's cost analysis and the analytic MACs x 2 convention
    agree exactly on a bare matmul (no fusion freedom, no scan)."""
    xla = mfu_lib.xla_cost_flops(compiled_matmul)
    if xla is None:
        pytest.skip("backend reports no cost-analysis flops")
    assert xla == pytest.approx(mfu_lib.matmul_flops(M, N, K), rel=0.01)


def test_mfu_fields_agree_on_known_matmul(compiled_matmul):
    """mfu_fields emits mfu_analytic == mfu_xla_cost when fed the
    convention-correct analytic count — the end-to-end reconciliation
    (the 2x ResNet-50 disagreement was exactly this pair diverging)."""
    analytic = mfu_lib.matmul_flops(M, N, K)
    fields = mfu_lib.mfu_fields(
        compiled_matmul, dt=1e-9, n_steps=1, device_kind="TPU v5 lite",
        analytic_flops_per_step=analytic,
        analytic_source="matmul_2mnk",
    )
    assert fields["mfu"] == fields["mfu_analytic"]
    if fields["mfu_xla_cost"] is None:
        pytest.skip("backend reports no cost-analysis flops")
    assert fields["mfu_xla_cost"] == pytest.approx(
        fields["mfu_analytic"], rel=0.02, abs=1e-6
    )


def test_mfu_xla_cost_scales_with_steps_per_call():
    """XLA cost analysis counts a lax.scan body once, so a k-steps-per-
    dispatch executable under-reports executed FLOPs by ~k.  mfu_fields
    must honour xla_flops_scale=k."""

    class FakeCompiled:
        def cost_analysis(self):
            return {"flops": 1e12}

    base = mfu_lib.mfu_fields(FakeCompiled(), dt=1.0, n_steps=10,
                              device_kind="TPU v5 lite",
                              analytic_flops_per_step=2e12,
                              analytic_source="test")
    scaled = mfu_lib.mfu_fields(FakeCompiled(), dt=1.0, n_steps=10,
                                device_kind="TPU v5 lite",
                                analytic_flops_per_step=2e12,
                                analytic_source="test", xla_flops_scale=20.0)
    assert scaled["mfu_xla_cost"] == pytest.approx(
        20.0 * base["mfu_xla_cost"], rel=1e-2)  # fields round to 4 places
    assert scaled["mfu_analytic"] == base["mfu_analytic"]


def test_unknown_device_kind_has_no_peak():
    """No default chip: the accounting raises on a kind without
    published peaks, and the Trainer's record carries no mfu field."""
    for kind in ("cpu", "TPU v9 imaginary", ""):
        with pytest.raises(KeyError, match="no published peaks"):
            mfu_lib.peak_flops(kind)
        with pytest.raises(KeyError, match="no published peaks"):
            mfu_lib.mfu_fields(None, 1.0, 1, kind, 1e12, "test", cost={})
        assert mfu_lib.mfu_record_fields(1e12, 0.1, device_kind=kind) == {}
    # default device = this process's (CPU) device: same answer
    assert mfu_lib.mfu_record_fields(1e12, 0.1) == {}
    assert mfu_lib.peak_flops("TPU v5 lite") == 197e12
    assert mfu_lib.peak_hbm_bytes_per_s("TPU v5 lite") == 819e9
