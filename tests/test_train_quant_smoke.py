"""PR 8 acceptance smoke (slow lane): ``train.py --quant int8`` on
gpt_tiny converges within 2% of the bf16 run over 120 steps with
``quant_mode`` stamped in the metric rows; the autotuner persists a cache
the kernel can consult; run_report's step-time section reports quant +
overlap + autotuned blocks; and the schema gates stay green.

(The bucketed-vs-unbucketed gradient parity half of the acceptance — DP
and ``--zero`` on the 8-device CPU mesh — is pinned bit-tolerant in the
fast lane, tests/test_overlap.py.)
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 120


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    # keep the kernel's tiling resolution hermetic for the train runs
    env["DTFT_FLASH_TUNE_CACHE"] = "off"
    return env


def _train(logdir, *extra):
    cmd = [
        sys.executable, os.path.join(REPO, "train.py"),
        "--workload", "gpt_lm", "--test-size", "--device", "cpu",
        "--steps", str(STEPS), "--log-every", "20", "--seed", "0",
        "--logdir", logdir, *extra,
    ]
    out = subprocess.run(cmd, capture_output=True, text=True, env=_env(),
                         timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    rows = []
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        for line in f:
            if line.strip():
                rows.append(json.loads(line))
    train_rows = [r for r in rows if "loss" in r]
    assert train_rows, rows
    return train_rows


def test_quant_int8_convergence_and_reporting(tmp_path):
    bf16_dir = str(tmp_path / "bf16")
    int8_dir = str(tmp_path / "int8")

    bf16_rows = _train(bf16_dir)
    int8_rows = _train(int8_dir, "--quant", "int8", "--overlap")

    # --- final loss within 2% of the full-width run over >= 100 steps ---
    assert bf16_rows[-1]["step"] == STEPS
    assert int8_rows[-1]["step"] == STEPS
    bf16_loss = bf16_rows[-1]["loss"]
    int8_loss = int8_rows[-1]["loss"]
    assert abs(int8_loss - bf16_loss) / bf16_loss < 0.02, (
        bf16_loss, int8_loss,
    )
    # and the loss actually fell (this is a training run, not a no-op)
    assert int8_loss < int8_rows[0]["loss"]

    # --- mode stamps in every quantized train row ---
    for r in int8_rows:
        assert r.get("quant_mode") == "int8", r
        assert r.get("overlap_buckets", 0) >= 1, r
        assert r.get("overlap_coverage") == 1.0, r
    assert all("quant_mode" not in r for r in bf16_rows)
    # the overlapped dispatch label reached the metric stream
    assert any(
        ".overlapped_1" in k
        for r in int8_rows for k in r
        if k.startswith("collective_dispatch_seconds_count")
    )

    # --- autotuner persists a cache the kernel consults ---
    cache = os.path.join(int8_dir, "flash_blocks.json")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "autotune_flash.py"),
         "--shape", "2,4,64,32", "--dtype", "bfloat16",
         "--blocks", "32,64", "--steps", "1", "--cache", cache],
        capture_output=True, text=True,
        env=_env(),
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    picked = json.loads(out.stdout.strip().splitlines()[-1])
    from distributedtensorflow_tpu.ops import flash_tuning

    assert flash_tuning.lookup(
        platform="cpu", dtype="bfloat16", seq=64, depth=32,
        batch=2, heads=4, path=cache,
    ) == (picked["block_q"], picked["block_k"])

    # --- run_report's step-time section reports all three ---
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         int8_dir, "--json"],
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    assert rep.returncode == 0, (rep.stdout[-2000:], rep.stderr[-2000:])
    sto = json.loads(rep.stdout)["step_time_opt"]
    assert sto["quant_mode"] == "int8"
    assert sto["overlap"]["buckets"] >= 1
    assert sto["overlap"]["coverage"] == 1.0
    assert sto["autotuned_blocks"], sto
    text = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         int8_dir],
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    assert "step-time attack" in text.stdout

    # --- schema gates green on everything the run produced ---
    targets = [os.path.join(int8_dir, "metrics.jsonl"), cache]
    prom = os.path.join(int8_dir, "metrics.prom")
    if os.path.exists(prom):
        targets.append(prom)
    gate = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "check_metrics_schema.py"), *targets],
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    assert gate.returncode == 0, gate.stdout
