"""The ISSUE 13 acceptance command, end to end in a subprocess.

``train.py --data-service 2 --fault-plan`` with a plan containing
``net_delay``, ``net_drop``, ``net_sever`` and ``dispatcher_kill`` must
complete to the target step with:

- zero lost/duplicated batches — proved by a gapless, strictly-increasing
  metrics.jsonl step sequence AND by zero evicted data workers (the sever
  was absorbed by same-worker reconnect-with-resume, not by re-sharding);
- every fault paired in ``faults.jsonl`` (schema gate);
- ``rpc_retries_total > 0`` and a full breaker open → half_open → closed
  cycle visible in ``metrics.prom``;
- a valid ``dispatcher.journal`` that replayed across the mid-epoch
  dispatcher kill;
- run_report's "rpc" section present and exit 0.

All on CPU.  Process-spawning, so slow-laned wholesale via
conftest's _PROCESS_TEST_FILES.
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PLAN = {
    "faults": [
        {"step": 10, "kind": "net_delay", "calls": 3, "delay_s": 0.05},
        # Targeted at the worker streams: the credits sit armed until the
        # sever below forces redials, each of which then fails once and
        # RETRIES — making `rpc_retries_total > 0` deterministic instead
        # of depending on which single-shot control-plane call happened
        # to swallow a match-all drop.
        {"step": 20, "kind": "net_drop", "calls": 2,
         "endpoint": "data_worker"},
        {"step": 30, "kind": "net_sever", "endpoint": "data_worker"},
        {"step": 45, "kind": "dispatcher_kill"},
    ]
}


def _load_jsonl(path):
    return [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]


def test_network_chaos_completes_exactly_once(tmp_path):
    logdir = tmp_path / "logs"
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps(PLAN))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [
            sys.executable, "train.py",
            "--workload", "mnist_lenet", "--test-size",
            "--steps", "70", "--batch-size", "32",
            "--log-every", "5", "--device", "cpu",
            "--data-service", "2",
            "--logdir", str(logdir),
            "--fault-plan", str(plan_path),
            "--restart-backoff", "0.05",
            "--flight-recorder",
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560,
    )
    assert res.returncode == 0, (res.stderr[-5000:], res.stdout[-1000:])
    log = res.stderr + res.stdout
    assert "done at step 70" in log

    # every network fault paired with a transport recovery, NO restarts
    # (the transport absorbed everything — restarts would mean it leaked)
    faults = _load_jsonl(logdir / "faults.jsonl")
    injected = [r for r in faults if r["phase"] == "injected"]
    recovered_ids = {r["id"] for r in faults if r["phase"] == "recovered"}
    assert {r["kind"] for r in injected} == {
        f["kind"] for f in PLAN["faults"]}
    assert {r["id"] for r in injected} == recovered_ids
    flight = _load_jsonl(logdir / "flight.jsonl")
    assert not [e for e in flight if e["kind"] == "restart"]

    # exactly-once: the training stream is gapless (strictly-increasing
    # step cadence, no step consumed twice or skipped) and no healthy
    # worker was evicted — the severed stream resumed in place
    rows = _load_jsonl(logdir / "metrics.jsonl")
    steps = [r["step"] for r in rows
             if "loss" in r and "eval_loss" not in r]
    assert steps == sorted(set(steps)), "duplicated/unordered step rows"
    assert steps[-1] == 70
    last = rows[-1]
    for r in rows:
        if "data_service_workers_dropped_total" in r:
            last = r
    assert last.get("data_service_workers_dropped_total", 0) == 0
    assert last.get("data_service_resharded_splits_total", 0) == 0
    assert last.get("data_service_stream_resumes_total", 0) >= 1

    # metrics.prom: retries happened, and the dispatcher endpoint breaker
    # went through a full open -> half_open -> closed recovery cycle
    prom = (logdir / "metrics.prom").read_text()
    retries = sum(
        float(m.group(1))
        for m in re.finditer(
            r'^rpc_retries_total\{[^}]*\} (\S+)', prom, re.M)
    )
    assert retries > 0, "no rpc retries recorded"
    for state in ("open", "half_open", "closed"):
        pat = (r'^breaker_transitions_total\{endpoint="dispatcher:'
               r'[^"]*",to="%s"\} (\S+)' % state)
        m = re.search(pat, prom, re.M)
        assert m and float(m.group(1)) >= 1, f"no transition to {state}"

    # the dispatcher journal survived the kill: a replay record follows
    # the original open, and the file is schema-clean
    journal = logdir / "dispatcher.journal"
    kinds = [json.loads(ln)["kind"]
             for ln in journal.read_text().splitlines() if ln.strip()]
    assert kinds[0] == "open"
    assert "replay" in kinds

    # schema gate over every stream the run produced
    gate = subprocess.run(
        [
            sys.executable, "tools/check_metrics_schema.py",
            str(logdir / "metrics.jsonl"), str(logdir / "faults.jsonl"),
            str(logdir / "metrics.prom"), str(journal),
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert gate.returncode == 0, gate.stdout + gate.stderr

    # run_report: rpc section green, exit 0
    report = subprocess.run(
        [sys.executable, "tools/run_report.py", str(logdir), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert report.returncode == 0, report.stdout + report.stderr
    doc = json.loads(report.stdout)
    rpc = doc["rpc"]
    assert rpc["retries_total"] > 0
    assert rpc["breaker_trips_total"] >= 1
    assert rpc["stream_resumes"] >= 1
    assert rpc["journal"]["replays"] >= 1
    assert rpc["journal"]["by_kind"].get("epoch_start", 0) >= 1
    res_section = doc["resilience"]
    assert res_section["unpaired"] == []
    assert res_section["faults_injected"] == len(PLAN["faults"])
