"""Slow-lane serving smoke: the ISSUE 6 acceptance command, end to end.

Boots ``serve.py`` as a real subprocess (random-init gpt_tiny, ephemeral
port), fires >= 16 concurrent requests with staggered arrivals, and
asserts the full contract:

- every response terminates correctly (EOS or length, tokens bounded);
- continuous batching actually happened: max observed batch occupancy
  > 1 AND at least one admission into a previously-freed slot;
- clean SIGTERM drain, then the post-hoc story holds: ``run_report.py``
  renders a serving section with non-zero p99 TTFT/e2e from
  ``requests.jsonl``, and ``check_metrics_schema.py`` passes on both
  serving streams.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_REQUESTS = 16
MAX_SLOTS = 4


def _post(port, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generatez",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    r = urllib.request.urlopen(req, timeout=timeout)
    return r.status, json.loads(r.read().decode())


def test_serve_smoke_concurrent_requests(tmp_path):
    logdir = str(tmp_path / "serve")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "serve.py"),
            "--config", "gpt_tiny", "--port", "0",
            "--max-slots", str(MAX_SLOTS), "--max-queue", "32",
            "--block-size", "8", "--prefill-chunk", "8",
            "--max-context", "128", "--logdir", logdir,
            "--log-every", "10", "--history-interval", "0.5",
        ],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        line = proc.stdout.readline()
        boot = json.loads(line)
        assert boot["serving"] is True
        # the line names what it runs on (chip_smoke.py refuses non-tpu)
        assert boot["device"]["platform"] == "cpu"
        assert boot["device"]["count"] >= 1 and boot["device"]["kind"]
        port = boot["port"]

        # eos probe: find a token greedy decoding provably emits early so
        # some requests terminate via EOS, not just length.
        _, probe = _post(port, {"prompt": [1, 2, 3, 4],
                                "max_new_tokens": 4})
        eos = probe["tokens"][1]

        results: dict[int, tuple] = {}
        errors: dict[int, Exception] = {}

        def client(i):
            payload = {
                "prompt": list(range(1, 5 + (i % 7))),
                "max_new_tokens": 6 + (i % 9),
                "seed": i,
            }
            if i % 3 == 0:
                payload["eos_token_id"] = eos
            try:
                results[i] = _post(port, payload)
            except Exception as e:  # noqa: BLE001 — assert after join
                errors[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:  # staggered arrivals, well inside one decode run
            t.start()
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == N_REQUESTS

        # every response terminates correctly
        for i, (status, body) in results.items():
            assert status == 200, body
            assert body["finish_reason"] in ("eos", "length"), body
            assert 1 <= body["new_tokens"] <= 6 + (i % 9)
            if body["finish_reason"] == "eos":
                assert body["tokens"][-1] == eos
            assert 0 <= body["ttft_s"] <= body["e2e_s"]

        # continuous batching actually happened.  The staggered arrivals
        # above almost always overlap, but nothing guarantees it — a run
        # where each request drains before the next lands leaves
        # occupancy_max at 1 and used to flake this assert off a single
        # snapshot.  Poll with a deadline, re-firing simultaneous bursts
        # until the engine has provably batched.
        def _state():
            r = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/generatez", timeout=10
            )
            return json.loads(r.read().decode())

        deadline = time.time() + 120
        state = _state()
        extra = 0
        while state["occupancy_max"] <= 1 and time.time() < deadline:
            burst = [threading.Thread(target=client,
                                      args=(N_REQUESTS + extra + j,))
                     for j in range(2 * MAX_SLOTS)]
            extra += 2 * MAX_SLOTS
            for t in burst:  # no stagger: arrivals land together
                t.start()
            for t in burst:
                t.join(timeout=180)
            state = _state()
        assert not errors, errors
        assert state["occupancy_max"] > 1, state
        assert state["counters"]["admits_into_freed_slot"] >= 1, state
        assert state["counters"]["ok"] >= N_REQUESTS
        assert state["kv"]["blocks_used"] == 0  # everything evicted

        # the live registry carries the SLO histograms
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/varz", timeout=10
        )
        varz = r.read().decode()
        assert "serve_batch_occupancy_count" in varz
        assert "serve_ttft_seconds_bucket" in varz

        # ISSUE 16 live surfaces: the step-log tail and the history store
        stepz = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stepz?n=8", timeout=10
        ).read().decode())
        assert stepz["steps_total"] > 0 and stepz["steps"]
        assert all(s["phase"] for s in stepz["steps"])
        metric = "serve_requests_total.status_ok"
        for _ in range(40):  # the sampler ticks every 0.5s
            histz = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/histz", timeout=10
            ).read().decode())
            if metric in histz["names"]:
                break
            time.sleep(0.25)
        assert histz["ticks"] >= 1 and histz["names"]
        assert metric in histz["names"], histz["names"][:20]
        windowed = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/histz?metric={metric}&window=600",
            timeout=10,
        ).read().decode())
        assert windowed["latest"] >= 1

        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, err[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)

    # post-hoc: run_report renders the serving section with non-zero tails
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         logdir, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert rep.returncode == 0, rep.stderr[-2000:]
    report = json.loads(rep.stdout)
    srv = report["serving"]
    assert srv["requests"] >= N_REQUESTS + 1  # + the eos probe
    assert srv["by_status"]["ok"] >= N_REQUESTS
    assert srv["ttft_s"]["p99"] > 0
    assert srv["e2e_s"]["p99"] > 0
    assert srv["occupancy_max"] > 1
    assert srv["tokens_generated"] > 0
    # ISSUE 16 post-hoc: tail attribution + the step-log digest
    ta = srv["tail_attribution"]
    assert ta["requests"] >= N_REQUESTS
    assert ta["covered_share"] >= 0.95  # components tile e2e within 5%
    assert ta["dominant"] in ("queue", "prefill", "stall", "decode",
                              "spec", "gap")
    assert srv["step_log"]["records"] > 0
    assert srv["step_log"]["tokens_committed"] > 0

    text = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         logdir],
        capture_output=True, text=True, timeout=120,
    )
    assert "serving:" in text.stdout and "peak batch occupancy" in text.stdout
    assert "tail attribution" in text.stdout
    assert "step log:" in text.stdout

    # tail_report explains p99 vs p50 with step-log evidence
    tail = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tail_report.py"),
         logdir, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert tail.returncode == 0, tail.stderr[-2000:]
    tail_doc = json.loads(tail.stdout)
    assert tail_doc["cohorts"]["dominant"] == ta["dominant"]
    assert tail_doc["coverage"]["covered_share"] >= 0.95
    assert tail_doc["evidence"]["overall"]["steps"] > 0

    # start-up left its phases as spans: one trace_id, in order, each
    # starting where the one before ends (ISSUE 24)
    with open(os.path.join(logdir, "trace.jsonl")) as f:
        spans = [json.loads(line) for line in f if line.strip()]
    startup = [r for r in spans if r["name"].startswith("startup.")]
    assert {r["kind"] for r in startup} == {"span"}
    assert {r["trace_id"] for r in startup} == {"startup"}
    top = [r for r in startup if "parent_id" not in r]
    assert [r["name"] for r in top] == [
        "startup.imports", "startup.backend", "startup.init_params",
        "startup.engine_build", "startup.listen", "startup.first_request",
        "startup.ready"]
    ready = top.pop()
    for a, b in zip(top, top[1:]):
        assert abs(b["t0"] - (a["t0"] + a["dur_s"])) <= 2e-6
    # start-up does not end at `listen` (ISSUE 50): the first request,
    # to the end of the first decode step, is its last phase, the engine
    # names what it waited for inside it, and `startup.ready` sums the
    # whole: nothing of it unnamed, its compile sums the phases'
    first_request = top[-1]
    kids = [r for r in startup if "parent_id" in r]
    assert [r["name"] for r in kids] == [
        "startup.first_wait", "startup.first_chunk", "startup.first_decode"]
    assert {r["parent_id"] for r in kids} == {first_request["span_id"]}
    assert sum(r["dur_s"] for r in kids) == pytest.approx(
        first_request["dur_s"], abs=1e-3)
    assert ready["unnamed_s"] == 0.0 and ready["t0"] == top[0]["t0"]
    assert ready["total_s"] == pytest.approx(
        sum(r["dur_s"] for r in top), abs=1e-4)
    sums = ("trace_s", "lower_s", "backend_s", "cache_load_s", "programs")
    for key in sums:
        assert ready[key] == pytest.approx(sum(r[key] for r in top), abs=1e-4)
        assert first_request[key] == pytest.approx(
            sum(r[key] for r in kids), abs=1e-4)
    phases = {r["span_id"]: r["name"] for r in startup}
    compiles = [r for r in spans if r["name"].startswith("compile.")]
    roots = [r for r in compiles if r.get("parent_id") in phases]
    assert ready["programs"] == sum(
        r["name"] == "compile.backend" for r in roots) > 2
    assert ready["backend_s"] == pytest.approx(sum(
        r["dur_s"] for r in roots if r["name"] == "compile.backend"),
        abs=1e-4)
    # both of the engine's programs were compiled for the first request
    by_phase = {}
    for r in roots:
        by_phase.setdefault(phases[r["parent_id"]], set()).add(r["program"])
    assert "jit(prefill_chunk)" in by_phase["startup.first_chunk"]
    assert "jit(decode)" in by_phase["startup.first_decode"]
    # ... and the iterations that held them say so in the step log
    with open(os.path.join(logdir, "steps.jsonl")) as f:
        steps = [json.loads(line) for line in f if line.strip()]
    assert steps[0]["compile_s"] > 0
    assert {"prefill_chunk", "decode"} <= set(
        steps[0]["compiled"].split(","))
    assert sum(r["compile_s"] > 0 for r in steps) < len(steps) / 2
    # no per-iteration row in trace.jsonl: requests and start-up only
    assert not [r for r in spans if r["name"].startswith("engine.")]

    # and all five serving streams are schema-clean
    assert os.path.exists(os.path.join(logdir, "steps.jsonl"))
    assert os.path.exists(os.path.join(logdir, "history.jsonl"))
    chk = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "check_metrics_schema.py"),
         os.path.join(logdir, "requests.jsonl"),
         os.path.join(logdir, "metrics.jsonl"),
         os.path.join(logdir, "steps.jsonl"),
         os.path.join(logdir, "trace.jsonl"),
         os.path.join(logdir, "history.jsonl")],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr

    # offline SLO burn recomputation from history.jsonl matches /sloz
    # shape-wise (serve.py installs no rules by default in this smoke:
    # just assert the replay machinery accepts the stream)
    from distributedtensorflow_tpu.obs import slo as slo_mod

    rows = [json.loads(line)
            for line in open(os.path.join(logdir, "history.jsonl"))]
    assert rows and all(set(r) == {"t", "values"} for r in rows)
    assert slo_mod.recompute_from_history([], rows) == []


def test_serve_smoke_prefix_cache_and_budget(tmp_path):
    """ISSUE 14 slow-lane smoke: serve.py with --prefix-cache and
    --prefill-budget, clients sharing a long prompt header.  Asserts the
    cache actually fired (serve_prefix_hits_total > 0 on /varz), the
    requests.jsonl rows carry the cached/prefilled split, the schema
    gates stay green, and run_report renders the prefix-cache section."""
    logdir = str(tmp_path / "serve_prefix")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "serve.py"),
            "--config", "gpt_tiny", "--port", "0",
            "--max-slots", "2", "--max-queue", "32",
            "--block-size", "8", "--prefill-chunk", "8",
            "--prefill-budget", "16", "--prefix-cache",
            "--max-context", "128", "--logdir", logdir,
            "--log-every", "5",
        ],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        boot = json.loads(proc.stdout.readline())
        port = boot["port"]
        header = list(range(1, 41))  # 5 whole 8-token blocks shared
        # warm request indexes the header blocks...
        _post(port, {"prompt": header + [100], "max_new_tokens": 4})
        # ...then every follow-up with the same header maps them shared
        results = [
            _post(port, {"prompt": header + [100 + i, 200 + i],
                         "max_new_tokens": 4})
            for i in range(6)
        ]
        for status, body in results:
            assert status == 200, body
            assert body["new_tokens"] >= 1

        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/varz", timeout=10
        )
        varz = r.read().decode()
        hits = [line for line in varz.splitlines()
                if line.startswith("serve_prefix_hits_total")]
        assert hits and float(hits[0].split()[-1]) > 0, hits
        cached = [line for line in varz.splitlines()
                  if line.startswith("serve_prefix_cached_tokens_total")]
        assert cached and float(cached[0].split()[-1]) >= 40 * 6

        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/generatez", timeout=10
        )
        state = json.loads(r.read().decode())
        assert state["prefix_cache"] is True
        assert state["prefill_budget"] == 16
        assert state["kv"]["prefix_hits"] >= 6
        assert state["kv"]["prefix_blocks_indexed"] >= 5

        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, err[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)

    rows = [json.loads(line)
            for line in open(os.path.join(logdir, "requests.jsonl"))]
    ok = [r for r in rows if r["status"] == "ok"]
    assert sum(r["cached_prefix_tokens"] > 0 for r in ok) >= 6
    assert all(r["cached_prefix_tokens"] + r["prefill_tokens"]
               == r["prompt_tokens"] for r in ok)

    chk = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "check_metrics_schema.py"),
         os.path.join(logdir, "requests.jsonl"),
         os.path.join(logdir, "metrics.jsonl"),
         os.path.join(logdir, "metrics.prom")],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr

    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         logdir, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert rep.returncode == 0, rep.stderr[-2000:]
    srv = json.loads(rep.stdout)["serving"]
    assert srv["prefix_cache"]["requests_with_hits"] >= 6
    assert srv["prefix_cache"]["cached_token_share"] > 0.5
    assert srv["prefill_budget"]["budget_tokens"] == 16

    text = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         logdir],
        capture_output=True, text=True, timeout=120,
    )
    assert "prefix cache: hit rate" in text.stdout


def test_serve_smoke_fused_speculative_streaming(tmp_path):
    """ISSUE 15 slow-lane smoke: serve.py with the full fast-path flag
    set (--fused-sampling --speculate --prefix-cache --prefill-budget),
    a streaming client, spec counters on /varz, schema gates green, and
    the run_report decode-fast-path digest."""
    logdir = str(tmp_path / "serve_spec")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "serve.py"),
            "--config", "gpt_tiny", "--port", "0",
            "--max-slots", "2", "--max-queue", "32",
            "--block-size", "8", "--prefill-chunk", "8",
            "--prefill-budget", "16", "--prefix-cache",
            "--fused-sampling", "--speculate", "4",
            "--max-context", "128", "--logdir", logdir,
            "--log-every", "5",
        ],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        boot = json.loads(proc.stdout.readline())
        port = boot["port"]
        periodic = (list(range(1, 9)) * 6)[:40]  # the drafter's habitat
        blocking = []
        for i in range(4):
            blocking.append(_post(
                port, {"prompt": periodic[i:] + periodic[:i],
                       "max_new_tokens": 16}))
        for status, body in blocking:
            assert status == 200, body
            assert body["new_tokens"] >= 1
            assert body["accepted"] <= body["drafted"]

        # streaming client: chunked token lines + the stats trailer,
        # token-for-token what the blocking reply for the same prompt
        # returned (greedy = deterministic)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generatez",
            data=json.dumps({"prompt": periodic, "max_new_tokens": 16,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        r = urllib.request.urlopen(req, timeout=120)
        assert r.status == 200
        lines = [json.loads(l) for l in r.read().decode().splitlines()]
        streamed = [t for l in lines if "tokens" in l and "done" not in l
                    for t in l["tokens"]]
        assert streamed == blocking[0][1]["tokens"]
        assert lines[-1]["done"] is True and lines[-1]["status"] == "ok"

        varz = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/varz", timeout=10).read().decode()
        drafted = [line for line in varz.splitlines()
                   if line.startswith("serve_spec_drafted_total")]
        assert drafted and float(drafted[0].split()[-1]) > 0, drafted
        assert "serve_decode_tokens_per_step_bucket" in varz

        state = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/generatez", timeout=10
        ).read().decode())
        assert state["fused_sampling"] is True
        assert state["speculate"] == 4
        assert state["tokens_per_step"] >= 1.0

        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, err[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)

    rows = [json.loads(line)
            for line in open(os.path.join(logdir, "requests.jsonl"))]
    ok = [r for r in rows if r["status"] == "ok"]
    assert sum(r["drafted"] for r in ok) > 0
    assert all(r["accepted"] <= r["drafted"] for r in ok)

    chk = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "check_metrics_schema.py"),
         os.path.join(logdir, "requests.jsonl"),
         os.path.join(logdir, "metrics.jsonl"),
         os.path.join(logdir, "metrics.prom")],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr

    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         logdir, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert rep.returncode == 0, rep.stderr[-2000:]
    fp = json.loads(rep.stdout)["serving"]["decode_fast_path"]
    assert fp["fused_sampling"] is True and fp["speculate"] == 4
    assert fp["drafted"] > 0
    assert fp["dispatches_per_step"] == 1.0
