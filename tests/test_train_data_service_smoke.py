"""train.py --data-service end to end in a subprocess (ISSUE 9).

The acceptance command: ``python train.py --workload mnist_lenet
--test-size --steps 24 --data-service 2 --adaptive-prefetch`` must train
green on CPU through the full disaggregated input plane — loopback
dispatcher + 2 in-process data workers, streaming client (pipelined
credit window, raw tensor wire), adaptive prefetch — with the input-plane
telemetry riding every record (``data_prefetch_depth`` /
``data_client_window`` fields, per-worker fetch histograms), the schema
gates green, and run_report rendering an "input plane" section.

Process-spawning, so slow-laned wholesale via conftest's
_PROCESS_TEST_FILES.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_data_service_end_to_end(tmp_path):
    logdir = tmp_path / "logs"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [
            sys.executable, "train.py",
            "--workload", "mnist_lenet", "--test-size", "--device", "cpu",
            "--steps", "24", "--log-every", "6",
            "--data-service", "2",
            "--adaptive-prefetch",
            "--logdir", str(logdir),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    log = res.stderr + res.stdout
    assert "data service: dispatcher" in log
    assert "done at step 24" in log

    rows = [
        json.loads(line)
        for line in (logdir / "metrics.jsonl").read_text().splitlines()
        if line.strip()
    ]
    train_rows = [r for r in rows if "loss" in r]
    assert train_rows, rows
    last = train_rows[-1]
    # the adaptive controllers stamped their live depths into the record
    assert last.get("data_prefetch_depth", 0) >= 1
    assert last.get("data_client_window", 0) >= 1
    # batches flowed through the service and were counted
    assert last.get("data_batches_total", 0) >= 24
    # per-worker fetch histograms rode the registry flattening (2 workers)
    fetch_fields = [
        k for k in last
        if k.startswith("data_service_fetch_seconds_count.worker_")
    ]
    assert len(fetch_fields) == 2, sorted(last)

    # schema gates green on the metric stream and prom snapshot
    check = subprocess.run(
        [
            sys.executable, "tools/check_metrics_schema.py",
            str(logdir / "metrics.jsonl"), str(logdir / "metrics.prom"),
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert check.returncode == 0, check.stdout + check.stderr

    # run_report renders the input-plane section (and exits 0)
    rep = subprocess.run(
        [sys.executable, "tools/run_report.py", str(logdir)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "input plane:" in rep.stdout
    assert "worker 127_0_0_1" in rep.stdout
    rep_json = subprocess.run(
        [sys.executable, "tools/run_report.py", str(logdir), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert rep_json.returncode == 0
    doc = json.loads(rep_json.stdout)
    ip = doc["input_plane"]
    assert ip["data_prefetch_depth"] >= 1
    assert len(ip["workers"]) == 2
    assert 0.0 <= ip["data_wait_share"] <= 1.0
