"""The bench scripts measure the TPU or nothing.

Each ``bench*.py`` that reports a device metric starts with
``bench_common.start``: no probe process, no cached row, no CPU
fallback.  Without a chip the script must exit non-zero, quickly, name
what it found, and print no result line.  (Their measurement code runs
on the chip; ``chip_smoke.py`` is the quickest proof that the system
starts there.)
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEVICE_BENCHES = [
    "bench.py", "bench_lm.py", "bench_bert.py", "bench_attn.py",
    "bench_generate.py", "bench_serve.py", "tools/sweep_flash_blocks.py",
]


def test_mfu_xla_cost_scales_with_steps_per_call():
    """XLA cost analysis counts a lax.scan body once, so a k-steps-per-
    dispatch executable under-reports executed FLOPs by ~k.  mfu_fields
    must honour xla_flops_scale=k."""
    from distributedtensorflow_tpu.obs.mfu import mfu_fields

    class FakeCompiled:
        def cost_analysis(self):
            return {"flops": 1e12}

    base = mfu_fields(FakeCompiled(), dt=1.0, n_steps=10,
                      device_kind="TPU v5 lite",
                      analytic_flops_per_step=2e12,
                      analytic_source="test")
    scaled = mfu_fields(FakeCompiled(), dt=1.0, n_steps=10,
                        device_kind="TPU v5 lite",
                        analytic_flops_per_step=2e12,
                        analytic_source="test", xla_flops_scale=20.0)
    assert scaled["mfu_xla_cost"] == pytest.approx(
        20.0 * base["mfu_xla_cost"], rel=1e-2)  # fields round to 4 places
    assert scaled["mfu_analytic"] == base["mfu_analytic"]


@pytest.mark.parametrize("script", DEVICE_BENCHES)
def test_device_bench_refuses_to_run_without_a_chip(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, script)], cwd=REPO,
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert res.returncode != 0, res.stdout[-500:]
    assert "requires a tpu device" in res.stderr, res.stderr[-1500:]
    assert "platform='cpu'" in res.stderr
    assert not res.stdout.strip(), res.stdout[-500:]
