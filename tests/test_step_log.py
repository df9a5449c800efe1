"""Engine step log + tail-latency attribution tests (ISSUE 16).

The load-bearing checks: (1) every working iteration leaves exactly one
step record with a valid phase mix and a wall split that tiles the step;
(2) the per-request attribution components are EXCLUSIVE — they sum to
the request's e2e within rounding, so tail reports can't double-count;
(3) the ring is a hard memory bound (``step_ring``) while
``steps_total`` keeps the lifetime count; (4) the streams the engine
writes are green under ``tools/check_metrics_schema.py``; (5) the
``/stepz`` live tail serves the same records over HTTP.
"""

import dataclasses
import json
import math
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, gpt_tiny
from distributedtensorflow_tpu.serve import Engine, ServeServer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_metrics_schema as checker  # noqa: E402
import tail_report  # noqa: E402

ATTR_FIELDS = (
    "attr_queue_s", "attr_prefill_s", "attr_stall_s",
    "attr_decode_s", "attr_spec_s", "attr_gap_s",
)


@pytest.fixture(scope="module")
def served_model():
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=64)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    return cfg, params, ids


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("max_context", 64)
    return Engine(params, cfg, **kw)


def _drain(engine, reqs, max_steps=500):
    for _ in range(max_steps):
        if all(r._done.is_set() for r in reqs):
            return
        engine.step()
    raise AssertionError("engine did not finish within max_steps")


def _load_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ------------------------------------------------------------ steps.jsonl


def test_steps_jsonl_invariants(served_model, tmp_path):
    """Every working iteration leaves one record; ids strictly increase,
    t never goes backwards, phases are the documented tokens, the wall
    split tiles step_s, and tokens_committed sums to the decode tokens
    actually produced (new_tokens - 1 first token per request)."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, logdir=str(tmp_path), log_every=1)
    reqs = [eng.submit(prompt, max_new_tokens=n) for n in (4, 2, 3)]
    _drain(eng, reqs)
    eng.stop()

    steps = _load_jsonl(os.path.join(tmp_path, "steps.jsonl"))
    assert steps, "no step records written"
    assert [s["step"] for s in steps] == list(range(1, len(steps) + 1))
    ts = [s["t"] for s in steps]
    assert ts == sorted(ts)
    valid = {"admit", "prefill", "decode"}
    for s in steps:
        assert s["phase"] == "idle" or \
            set(s["phase"].split("+")) <= valid, s["phase"]
        # exclusive phase walls tile the iteration
        assert s["admit_s"] + s["prefill_s"] + s["decode_s"] \
            <= s["step_s"] + 1e-5
        assert "device_s" not in s and "host_s" not in s  # host stopwatch
        assert 0 <= s["occupancy"] <= 2
        assert s["spec_accepted"] <= s["spec_drafted"]
    # decode tokens only: each request's first token is prefill's
    total_new = sum(len(r.tokens) for r in reqs)
    assert sum(s["tokens_committed"] for s in steps) == \
        total_new - len(reqs)
    assert sum(s["admitted"] for s in steps) == len(reqs)
    # engine-level accounting matches the stream
    assert eng.steps_total == len(steps)
    assert eng.state()["steps_total"] == len(steps)


def _children(span, name):
    return [c for c in span.children if c.name == name]


@pytest.mark.parametrize("fused", [False, True])
def test_step_record_is_read_off_the_span_tree(served_model, fused,
                                               request):
    """The record's walls ARE the iteration's ``engine.*`` span durations
    (one mechanism, one clock), and the tree has the documented shape on
    the host-sampling and the fused path alike."""
    from distributedtensorflow_tpu.obs import tracing

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, fused_sampling=fused)
    reqs = [eng.submit(prompt, max_new_tokens=n) for n in (4, 3)]
    seen, roots = set(), []
    sink = roots.append                     # every completed root span
    tracing.add_root_sink(sink)
    request.addfinalizer(lambda: tracing.remove_root_sink(sink))
    for _ in range(200):
        if all(r._done.is_set() for r in reqs):
            break
        assert eng.step()
        root, rec = roots[-1], eng.step_records()[-1]
        assert root.name == "engine.step" and len(roots) == rec["step"]
        kids = [c.name for c in root.children]
        assert kids[0] == "engine.admit" and kids[-1] == "engine.log"
        assert set(kids) <= {"engine.admit", "engine.prefill",
                             "engine.decode", "engine.log"}
        walls = {"admit_s": root.children[0].dur_s,
                 "prefill_s": 0.0, "decode_s": 0.0}
        for c in _children(root, "engine.prefill"):
            walls["prefill_s"] = c.dur_s
            chunks = _children(c, "engine.prefill_chunk")
            assert len(chunks) == rec["prefill_chunks"] > 0
            seen.update(g.name for ch in chunks for g in ch.children)
            seen.update(k.name for k in c.children)
        for c in _children(root, "engine.decode"):
            walls["decode_s"] = c.dur_s
            assert [k.name for k in c.children] == [
                "engine.decode.dispatch", "engine.decode.fetch",
                "engine.decode.commit"]
        for field, dur in walls.items():
            assert rec[field] == round(dur, 6), field
        log = root.children[-1]
        assert rec["step_s"] == round(log.t0 - root.t0, 6)
        assert rec["admit_s"] + rec["prefill_s"] + rec["decode_s"] \
            <= rec["step_s"] + 2e-6
        assert rec["step_s"] <= root.dur_s
        assert ("prefill" in rec["phase"]) == bool(rec["prefill_chunks"])
    assert all(r._done.is_set() for r in reqs)
    assert {"engine.prefill_chunk", "engine.first_token"} <= seen
    n = len(roots)
    assert not eng.step()            # idle: no iteration, no span, no record
    assert len(roots) == n == eng.steps_total
    eng.stop()


def test_iteration_roots_do_not_pile_up(served_model, tmp_path):
    """2,000 working iterations under serve.py's recorder: nothing is
    buffered per iteration (no begin_step is ever called in serving), no
    per-iteration row lands in trace.jsonl, the ring holds step_ring."""
    from distributedtensorflow_tpu.obs.tracing import TraceRecorder

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    path = tmp_path / "trace.jsonl"
    rec = TraceRecorder(str(path), step_rows=False).install()
    try:
        eng = _engine(cfg, params, step_ring=16)
        n = 0
        while n < 2000:
            if not any(r is not None for r in eng._slots):
                for _ in range(2):
                    eng.submit(prompt, max_new_tokens=40)
            assert eng.step()
            n += 1
        assert rec._roots == []
        assert set(rec.drain_window()) == {"engine.step"}
        assert len(eng.step_records()) == 16 and eng.steps_total == 2000
        eng.stop(drain=False)
    finally:
        rec.uninstall()
        rec.close()
    rows = _load_jsonl(path)
    assert rows and all(r.get("kind") == "span" for r in rows)
    assert not [r for r in rows if r["name"].startswith("engine.")]


def test_steps_and_requests_pass_schema_checker(served_model, tmp_path):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, logdir=str(tmp_path), log_every=1)
    reqs = [eng.submit(prompt, max_new_tokens=n) for n in (3, 5)]
    _drain(eng, reqs)
    eng.stop()
    for name in ("steps.jsonl", "requests.jsonl"):
        errors, _warnings = checker.check_file(os.path.join(tmp_path, name))
        assert errors == [], (name, errors)


def test_request_attribution_tiles_e2e(served_model, tmp_path):
    """The six components are exclusive: non-negative, and their sum
    reproduces the request's e2e to rounding — the invariant that makes
    p99-vs-p50 growth accounting meaningful."""
    cfg, params, ids = served_model
    prompts = [[int(t) for t in row] for row in np.asarray(ids)]
    eng = _engine(cfg, params, logdir=str(tmp_path), log_every=1)
    # 3 requests on 2 slots: the third queues, exercising attr_queue_s
    reqs = [eng.submit(prompts[i % 2], max_new_tokens=4) for i in range(3)]
    _drain(eng, reqs)
    eng.stop()

    rows = [r for r in _load_jsonl(os.path.join(tmp_path, "requests.jsonl"))
            if r.get("status") == "ok"]
    assert len(rows) == 3
    for row in rows:
        comps = [row[f] for f in ATTR_FIELDS]
        assert all(c >= 0 and math.isfinite(c) for c in comps), row
        total = sum(comps)
        assert total == pytest.approx(row["e2e_s"], abs=1e-4), \
            f"attribution sum {total} != e2e {row['e2e_s']}"
        # spec mirror fields ride every ok row (0 with speculation off)
        assert row["spec_drafted"] == row["drafted"]
        assert row["spec_accepted"] == row["accepted"]


def test_step_ring_bounded(served_model, tmp_path):
    """step_ring is a hard memory bound: the in-memory tail never
    exceeds it while steps_total keeps counting."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, step_ring=8)
    reqs = [eng.submit(prompt, max_new_tokens=8) for _ in range(3)]
    _drain(eng, reqs)
    assert eng.steps_total > 8
    assert len(eng.step_records()) == 8
    tail = eng.step_records(3)
    assert len(tail) == 3
    assert [s["step"] for s in tail] == \
        list(range(eng.steps_total - 2, eng.steps_total + 1))
    assert eng.state()["step_ring_size"] == 8


def test_budget_stall_recorded(served_model, tmp_path):
    """A prefill budget smaller than the pending prompt work leaves
    budget_stall=1 records and bumps the engine counter."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]  # 8 tokens, chunk=4
    eng = _engine(cfg, params, prefill_budget=4,
                  logdir=str(tmp_path), log_every=1)
    reqs = [eng.submit(prompt, max_new_tokens=2) for _ in range(2)]
    _drain(eng, reqs)
    eng.stop()
    assert eng.prefill_budget_stalls > 0
    assert eng.state()["prefill_budget_stalls"] == eng.prefill_budget_stalls
    steps = _load_jsonl(os.path.join(tmp_path, "steps.jsonl"))
    assert sum(s["budget_stall"] for s in steps) > 0
    # stalled requests still attribute cleanly (stall is a component)
    rows = [r for r in _load_jsonl(os.path.join(tmp_path, "requests.jsonl"))
            if r.get("status") == "ok"]
    for row in rows:
        assert sum(row[f] for f in ATTR_FIELDS) == pytest.approx(
            row["e2e_s"], abs=1e-4)


# ----------------------------------------------------------- tail_report


def test_tail_report_on_real_logdir(served_model, tmp_path, capsys):
    """tools/tail_report.py over a real engine run: coverage ~100%,
    a dominant component is named, text and --json modes both work."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, logdir=str(tmp_path), log_every=1)
    reqs = [eng.submit(prompt, max_new_tokens=n) for n in (2, 4, 6, 3)]
    _drain(eng, reqs)
    eng.stop()

    rep = tail_report.build(str(tmp_path))
    assert rep["parse_errors"] == 0
    cov = rep["coverage"]
    assert cov["rows"] == 4
    assert cov["covered_share"] == pytest.approx(1.0)
    cohorts = rep["cohorts"]
    assert cohorts["dominant"] in [label for label, _ in
                                   tail_report.COMPONENTS]
    assert cohorts["e2e_tail_s"] >= cohorts["e2e_p50_s"]
    # the step-log join found records inside the tail windows
    assert rep["step_records"] > 0
    assert rep["evidence"]["tail"]["steps"] >= 0
    text = tail_report.render(rep)
    assert "dominant" in text and cohorts["dominant"] in text

    assert tail_report.main([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["cohorts"]["dominant"] == cohorts["dominant"]


def test_tail_report_exit_codes(tmp_path, capsys):
    with pytest.raises(SystemExit):
        tail_report.build(str(tmp_path))  # no requests.jsonl: hard error
    # parse errors gate the exit code
    with open(tmp_path / "requests.jsonl", "w") as f:
        f.write(json.dumps({"status": "ok", "t": 1.0, "e2e_s": 0.5,
                            **{k: 0.0 for k in ATTR_FIELDS[:-1]},
                            "attr_gap_s": 0.5}) + "\n")
        f.write("{not json\n")
    assert tail_report.main([str(tmp_path)]) == 1
    capsys.readouterr()


# --------------------------------------------------------------- /stepz


def _get(port, path, timeout=10):
    try:
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        )
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_stepz_endpoint(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    engine = _engine(cfg, params).start()
    server = ServeServer(engine, 0).start()
    try:
        engine.generate(prompt, max_new_tokens=4)
        status, raw = _get(server.port, "/stepz")
        assert status == 200
        doc = json.loads(raw)
        assert doc["steps_total"] >= doc["n"] > 0
        assert doc["ring_size"] == engine.step_ring_size
        assert [s["step"] for s in doc["steps"]] == \
            sorted(s["step"] for s in doc["steps"])
        # the engine thread may log more steps after the snapshot
        assert doc["steps"][-1]["step"] <= engine.steps_total

        status, raw = _get(server.port, "/stepz?n=1")
        assert status == 200
        doc = json.loads(raw)
        assert doc["n"] == 1 and len(doc["steps"]) == 1

        status, raw = _get(server.port, "/stepz?n=zero")
        assert status == 400
        status, raw = _get(server.port, "/stepz?n=0")
        assert status == 400
    finally:
        server.stop()
        engine.stop()


def test_profilez_captures_engine_iterations(served_model, tmp_path,
                                             monkeypatch):
    """The trace an operator can take: POST /profilez?steps=N answers 200,
    the engine loop opens the window and closes it N iterations later, and
    what the (stubbed) profiler saw in between are the engine.* spans."""
    from distributedtensorflow_tpu.obs import capture as capture_mod
    from distributedtensorflow_tpu.obs import tracing

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    window = {"open": False, "dirs": [], "spans": []}

    class Annotation:
        def __init__(self, name, **attrs):
            if window["open"]:
                window["spans"].append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(tracing, "_TraceAnnotation", Annotation)
    cap = capture_mod.CaptureEngine(
        str(tmp_path),
        profiler_start=lambda d: (window["dirs"].append(d),
                                  window.update(open=True)),
        profiler_stop=lambda: window.update(open=False),
    )
    prev = capture_mod.install_engine(cap)
    engine = _engine(cfg, params, capture=cap).start()
    server = ServeServer(engine, 0).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/profilez?steps=3",
            method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            assert json.loads(resp.read())["accepted"] is True
        engine.generate(prompt, max_new_tokens=8, timeout=60)
    finally:
        server.stop()
        engine.stop()
        capture_mod.install_engine(prev)
    assert window["dirs"] == [str(tmp_path / "captures" / "0")]
    assert not window["open"]
    (row,) = _load_jsonl(tmp_path / "captures.jsonl")
    assert row["trigger"] == "manual" and "aborted" not in row
    assert row["step_end"] - row["step_begin"] == 3
    names = [n for n, _ in window["spans"]]
    assert names.count("engine.step") == 3
    assert {"engine.admit", "engine.prefill", "engine.prefill_chunk",
            "engine.decode", "engine.decode.dispatch",
            "engine.decode.fetch", "engine.decode.commit",
            "engine.log"} <= set(names)
    steps = [a["step"] for n, a in window["spans"] if n == "engine.step"]
    assert steps == list(range(row["step_begin"] + 1, row["step_end"] + 1))
    errors, _ = checker.check_file(str(tmp_path / "captures.jsonl"))
    assert errors == []



# ----------------------------------- the leaves tile the iteration (ISSUE 36)

LEAF_FIELDS = ("dispatch_s", "prelaunch_s", "fetch_s", "commit_s",
               "first_token_s", "log_prev_s", "between_s", "wait_s", "offcpu_s",
               "commit_cpu_s", "gc_s", "unnamed_s", "stream_lines",
               "stream_lag_max_s", "compile_s")


def _mixed_traffic(eng, cfg, n_requests=6):
    """Requests of unlike lengths, two up front and the rest one every
    third iteration: iterations that admit, prefill and decode at once,
    and ones that only decode.  Returns the finished requests."""
    rng = np.random.default_rng(11)
    jobs = [(rng.integers(0, cfg.vocab_size, n).tolist(), m)
            for n, m in ((9, 6), (5, 9), (14, 4), (3, 7), (11, 5),
                         (6, 8))[:n_requests]]
    reqs = [eng.submit(p, max_new_tokens=m, seed=i)
            for i, (p, m) in enumerate(jobs[:2])]
    pending = jobs[2:]
    for i in range(600):
        if pending and i % 3 == 0:
            p, m = pending.pop(0)
            reqs.append(eng.submit(p, max_new_tokens=m, seed=10 + i))
        if not pending and all(r._done.is_set() for r in reqs):
            return reqs
        eng.step()
    raise AssertionError("engine did not finish")


@pytest.mark.parametrize("budget", [None, 8])
@pytest.mark.parametrize("fused", [False, True])
def test_leaves_tile_the_iteration_and_rows_tile_the_thread(
        served_model, tmp_path, fused, budget):
    """Every row of a working iteration carries the leaves; they sum to
    ``step_s`` (``unnamed_s``), those of a decode — with, under a budget,
    the next iteration's chunks launched between dispatch and fetch,
    ``prelaunch_s`` — tile ``decode_s``, and ``log_prev_s + between_s +
    wait_s + step_s`` of consecutive rows is the wall between their
    stamps.  A chunk is counted by the record whose budget it spent."""
    cfg, params, _ = served_model
    eng = _engine(cfg, params, logdir=str(tmp_path), fused_sampling=fused,
                  prefill_budget=budget, max_slots=3)
    _mixed_traffic(eng, cfg)
    eng.stop()
    rows = _load_jsonl(os.path.join(tmp_path, "steps.jsonl"))
    assert len(rows) > 12
    assert {"admit+prefill+decode", "decode"} <= {r["phase"] for r in rows}
    for before, r in zip([{"prelaunch_s": 0.0}] + rows, rows):
        assert set(LEAF_FIELDS) <= set(r), r
        assert r["unnamed_s"] <= 0.02 * r["step_s"] + 1e-5
        leaves = r["dispatch_s"] + r["prelaunch_s"] + r["fetch_s"] \
            + r["commit_s"]
        assert leaves <= r["decode_s"] + 1e-5
        # launched under the decode step of the record before, all of a
        # record's chunks or none, and never more than a budget's worth
        assert r["prefill_prelaunched"] in (0, r["prefill_chunks"])
        assert (r["prefill_prelaunched"] > 0) == (before["prelaunch_s"] > 0)
        assert r["prefill_chunks"] * 4 <= (budget or 10 ** 6)
        assert r["chunk_tokens"] <= r["prefill_chunks"] * 4
        assert (r["chunk_tokens"] > 0) == (r["prefill_chunks"] > 0)
        if r["occupancy"]:
            assert leaves >= r["decode_s"] - 1e-5      # and tile it
            assert 0 < r["commit_cpu_s"] <= r["commit_s"] + 1e-4
        else:
            assert leaves == 0 == r["commit_cpu_s"]
        assert (r["first_token_s"] > 0) == (
            r["prefill_chunks"] > 0 and r["first_token_s"] > 0)
        assert r["first_token_s"] <= r["prefill_s"] + 1e-6
        assert r["wait_s"] == 0 and r["stream_lines"] == 0
    assert sum(r["first_token_s"] > 0 for r in rows) >= 3
    ahead = sum(r["prefill_prelaunched"] for r in rows)
    assert ahead == eng.prefill_prelaunched \
        == eng.state()["prefill_prelaunched"]
    assert sum(r["prefill_chunks"] for r in rows) == eng.prefill_chunks
    if budget is None:      # no filler is left where the decode step went
        assert ahead == 0 == sum(r["prelaunch_s"] for r in rows)
    else:
        assert ahead > 0
    # the rows tile the thread's life: row i's account begins where the
    # engine.log of row i-1 began, and `t` is stamped at that place
    tiled = sum(r["log_prev_s"] + r["between_s"] + r["wait_s"] + r["step_s"]
                for r in rows[1:])
    wall = rows[-1]["t"] - rows[0]["t"]
    assert abs(tiled - wall) <= 0.02 * wall + 1e-4 * len(rows)
    assert rows[0]["log_prev_s"] == 0 == rows[0]["between_s"]
    assert all(r["log_prev_s"] > 0 for r in rows[1:])
    errors, _ = checker.check_steps_file(
        os.path.join(tmp_path, "steps.jsonl"))
    assert errors == []


@pytest.mark.parametrize("field,value,message", [
    ("commit_s", 9.0, "dispatch_s+fetch_s+commit_s"),
    ("prelaunch_s", 9.0, "dispatch_s+fetch_s+commit_s"),
    ("prefill_prelaunched", 5, "'prefill_prelaunched'"),
    ("unnamed_s", 0.5, "do not tile"),
    ("offcpu_s", -0.1, "'offcpu_s'"),
    ("gc_s", "x", "'gc_s'"),
    ("stream_lines", 1.5, "'stream_lines'"),
    ("stream_lag_max_s", float("nan"), "'stream_lag_max_s'"),
    ("compile_s", -1.0, "'compile_s'"),
    ("compile_s", 0.25, "names no 'compiled' program"),
    ("compiled", "decode", "nothing took any time"),
])
def test_schema_checker_holds_the_leaf_fields(served_model, tmp_path, field,
                                              value, message):
    cfg, params, ids = served_model
    eng = _engine(cfg, params, logdir=str(tmp_path))
    _drain(eng, [eng.submit([1, 2, 3], max_new_tokens=3)])
    eng.stop()
    path = os.path.join(tmp_path, "steps.jsonl")
    rows = _load_jsonl(path)
    assert checker.check_steps_file(path)[0] == []
    rows[-1][field] = value
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    errors, _ = checker.check_steps_file(path)
    assert len(errors) == 1 and message in errors[0], errors
    # a log from before the fields is green
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps({k: v for k, v in r.items()
                                if k not in LEAF_FIELDS + (
                                    "compiled", "prefill_prelaunched")})
                    + "\n")
    assert checker.check_steps_file(path)[0] == []


def _decoding_engine(cfg, params, **kw):
    """An engine with two requests past their prefill, every program it
    will use compiled: each further ``step()`` is a decode iteration."""
    eng = _engine(cfg, params, **kw)
    for i in range(2):
        eng.submit([3, 1, 4, 1, 5], max_new_tokens=50 + i, seed=i)
    while eng._filling or eng._queue:
        eng.step()
    for _ in range(3):
        eng.step()
    return eng


def _after_commit(eng, fn):
    """Run ``fn()`` once, at the end of the next iteration's commit (where
    the stream threads' lines have been put)."""
    commit = eng._commit_tokens

    def once(*args, **kw):
        commit(*args, **kw)
        eng._commit_tokens = commit
        fn()

    eng._commit_tokens = once


def _busy(seconds):
    """Burn ``seconds`` of this thread's own CPU time, however long the box
    takes to grant them."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


@pytest.mark.parametrize("what", ["sleep", "busy"])
def test_offcpu_tells_waiting_from_working(served_model, what):
    """20 ms inside the commit: asleep, the engine thread was off the CPU
    for them and ``offcpu_s`` says so; in a busy loop that burns 20 ms of
    the thread's CPU it was working, and ``commit_cpu_s`` holds them instead
    (how long the loop was descheduled meanwhile is the box's business)."""
    cfg, params, _ = served_model
    eng = _decoding_engine(cfg, params)
    rows = []
    for _ in range(3):      # the best of three: the box is shared
        _after_commit(eng, lambda: (time.sleep if what == "sleep"
                                    else _busy)(0.02))
        eng.step()
        rows.append(eng.step_records()[-1])
        eng.step()
    assert all(r["commit_s"] >= 0.02 for r in rows)
    if what == "sleep":
        assert max(r["offcpu_s"] for r in rows) >= 0.8 * 0.02
        assert min(r["commit_cpu_s"] for r in rows) < 0.01
    else:
        assert all(r["commit_cpu_s"] >= 0.8 * 0.02 for r in rows)
    eng.stop(drain=False)


@pytest.mark.parametrize("where", ["engine", "other"])
def test_gc_s_counts_the_engine_threads_collections(served_model, where):
    import gc

    cfg, params, _ = served_model
    eng = _decoding_engine(cfg, params)
    gc.collect()
    gc.disable()            # only the collection below
    try:
        eng.step()
        if where == "engine":
            _after_commit(eng, gc.collect)
        else:
            worker = threading.Thread(target=gc.collect)
            _after_commit(eng, lambda: (worker.start(), worker.join()))
        eng.step()
        inside = eng.step_records()[-1]
        eng.step()
        after = eng.step_records()[-1]
    finally:
        gc.enable()
    assert after["gc_s"] == 0
    if where == "engine":
        assert 0 < inside["gc_s"] <= inside["commit_s"]
    else:
        assert inside["gc_s"] == 0
    eng.stop(drain=False)


def test_decode_iteration_costs_a_counted_number_of_clock_reads(
        served_model, monkeypatch):
    """What the accounting costs with no profiler open, as counts (a time
    would flake on a shared box): a decode iteration reads the thread's
    CPU clock at most 10 times and enters the seven spans it entered
    before ISSUE 36 (``engine.loop``, the one new span, is the loop's)."""
    from distributedtensorflow_tpu.obs import tracing

    cfg, params, _ = served_model
    eng = _decoding_engine(cfg, params)
    counts = {"cpu": 0, "spans": [], "wall": 0}
    thread_time, annotation = time.thread_time, tracing._annotation
    perf_counter = time.perf_counter

    def counted_cpu():
        counts["cpu"] += 1
        return thread_time()

    def counted_wall():
        counts["wall"] += 1
        return perf_counter()

    def counted_annotation(name, attrs):
        counts["spans"].append(name)
        return annotation(name, attrs)

    monkeypatch.setattr(time, "thread_time", counted_cpu)
    monkeypatch.setattr(time, "perf_counter", counted_wall)
    monkeypatch.setattr(tracing, "_annotation", counted_annotation)
    assert eng.step()
    monkeypatch.undo()
    assert eng.step_records()[-1]["phase"] == "decode"
    assert counts["spans"] == [
        "engine.step", "engine.admit", "engine.decode",
        "engine.decode.dispatch", "engine.decode.fetch",
        "engine.decode.commit", "engine.log"]
    assert 3 <= counts["cpu"] <= 10
    # one read opens the root with its first leaf, one a boundary between
    # two leaves, one closes the last leaf with the root
    assert counts["wall"] == 6
    eng.stop(drain=False)


def test_an_iteration_that_compiles_says_so(served_model, tmp_path):
    """A second shape once every program of the engine exists (ISSUE 50):
    the iteration it compiled in carries ``compile_s`` and the program's
    name in its step record, the rows around it read 0.0, ``state()``
    keeps the account, and the compile log's rows are ``trace_id``
    ``"compile"`` rows of ``trace.jsonl``."""
    from distributedtensorflow_tpu.obs.tracing import TraceRecorder

    cfg, params, _ = served_model

    @jax.jit
    def second_shape(x):
        return x + 1

    x = jnp.ones(7)
    with TraceRecorder(str(tmp_path / "trace.jsonl"), step_rows=False):
        eng = _decoding_engine(cfg, params, logdir=str(tmp_path))
        account = dict(eng.state()["compiles"])     # its own programs'
        assert account["count"] >= 2 and account["seconds"] > 0
        _after_commit(eng, lambda: second_shape(x))
        compiled_at = eng.steps_total + 1
        for _ in range(3):
            assert eng.step()
        compiles = eng.state()["compiles"]
        eng.stop(drain=False)
    rows = {r["step"]: r for r in _load_jsonl(tmp_path / "steps.jsonl")}
    row = rows[compiled_at]
    assert row["compile_s"] > 0 and row["compiled"] == "second_shape"
    assert row["compile_s"] <= row["commit_s"] <= row["step_s"]
    for around in (compiled_at - 1, compiled_at + 1, compiled_at + 2):
        assert rows[around]["compile_s"] == 0.0
        assert "compiled" not in rows[around]
    assert compiles["count"] == account["count"] + 1
    assert compiles["seconds"] == pytest.approx(
        account["seconds"] + row["compile_s"], abs=1e-5)
    assert compiles["last_program"] == "second_shape"
    assert compiles["last_t"] == row["t"]
    mine = [r for r in _load_jsonl(tmp_path / "trace.jsonl")
            if r["name"].startswith("compile.")
            and "second_shape" in r["program"]]
    assert [r["name"] for r in mine] == [
        "compile.trace", "compile.lower", "compile.backend"]
    assert {r["trace_id"] for r in mine} == {"compile"}
    assert sum(r["dur_s"] for r in mine) == pytest.approx(
        row["compile_s"], abs=1e-5)
    for name in ("steps.jsonl", "trace.jsonl"):
        assert checker.check_file(str(tmp_path / name)) == ([], [])


@pytest.mark.parametrize("what", ["sleep", "compile"])
def test_one_stalled_iteration_leaves_one_engine_stall_row(served_model,
                                                           tmp_path, what):
    """... and a stall that is a compilation names its cause: the row's
    ``compile_s`` and the ``compile.*`` spans under the leaf that held
    it."""
    from distributedtensorflow_tpu.obs.tracing import TraceRecorder

    cfg, params, _ = served_model

    @jax.jit
    def slow_to_trace(x):
        time.sleep(0.5)
        return x + 1

    x = jnp.ones(3)
    stall = {"sleep": lambda: time.sleep(0.5),
             "compile": lambda: slow_to_trace(x)}[what]
    path = tmp_path / "trace.jsonl"
    rec = TraceRecorder(str(path), step_rows=False).install()
    try:
        eng = _decoding_engine(cfg, params)
        for i in range(300):
            if not any(r is not None for r in eng._slots):
                for _ in range(2):
                    eng.submit([3, 1, 4, 1, 5], max_new_tokens=50)
            if i == 200:
                eng.step()          # (a decode iteration follows)
                _after_commit(eng, stall)
                stalled = eng.steps_total + 1
            assert eng.step()
        eng.stop(drain=False)
    finally:
        rec.uninstall()
        rec.close()
    stalls = [r for r in _load_jsonl(path) if r.get("kind") == "anomaly"]
    assert [r["anomaly"] for r in stalls] == ["engine_stall"]
    row, = stalls
    assert row["step"] == stalled and row["value"] >= 0.5
    assert row["value"] > 20 * row["median_s"]
    tree, = row["spans"]
    assert tree["name"] == "engine.step"
    decode = next(c for c in tree["children"] if c["name"] == "engine.decode")
    commit = decode["children"][-1]
    assert commit["name"] == "engine.decode.commit"
    assert commit["dur_s"] >= 0.5           # the leaf that held the time
    record = next(r for r in eng.step_records() if r["step"] == stalled)
    assert record["commit_s"] == commit["dur_s"]
    assert row["compile_s"] == record["compile_s"]
    if what == "compile":
        assert row["compile_s"] >= 0.5
        assert record["compiled"] == "slow_to_trace"
        assert [c["name"] for c in commit["children"]] == [
            "compile.trace", "compile.lower", "compile.backend"]
    else:
        assert row["compile_s"] == 0.0 and "children" not in commit
    assert record["offcpu_s"] >= 0.4        # ... and the thread slept
    errors, _ = checker.check_trace_file(str(path))
    assert errors == []


def test_stream_lag_reaches_the_step_log_and_the_registry(served_model,
                                                          monkeypatch):
    """A writer that takes 10 ms to put a line on its socket: the lag of
    a line is measured once its bytes have been handed to the socket, the
    registry histogram and the next step record hold it."""
    from distributedtensorflow_tpu.obs import registry as obs_registry
    from distributedtensorflow_tpu.serve import server as server_mod

    cfg, params, _ = served_model
    reg = obs_registry.Registry()
    eng = _engine(cfg, params, registry=reg)
    chunk = server_mod._chunk

    def slow_chunk(doc):
        time.sleep(0.01)            # the writer is 10 ms late with the line
        return chunk(doc)

    monkeypatch.setattr(server_mod, "_chunk", slow_chunk)
    with ServeServer(eng, port=0, registry=reg) as srv, eng:
        body = json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 6,
                           "stream": True}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generatez", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            lines = [json.loads(x) for x in resp.read().splitlines()]
        assert lines[-1]["status"] == "ok"
        tokens = sum(len(x.get("tokens", ())) for x in lines)
        assert tokens == 6
        # a record after the last line was written
        assert eng.submit([1, 2], max_new_tokens=2).wait(60)
    rows = eng.step_records()
    assert sum(r["stream_lines"] for r in rows) == len(lines) - 1
    assert max(r["stream_lag_max_s"] for r in rows) >= 0.01
    assert all((r["stream_lag_max_s"] > 0) == (r["stream_lines"] > 0)
               for r in rows)
    hist = reg.histogram("serve_stream_lag_seconds", "").stats()
    assert hist["count"] == len(lines) - 1 and hist["sum"] >= 0.01 * hist[
        "count"]
    assert any(r["wait_s"] > 0 for r in rows[1:])    # the loop idled first


def _engine_thread_events(trace):
    """The host events of the thread that ran ``engine.step``."""
    for events in trace["host"].values():
        if any(n == "engine.step" for n, _, _ in events):
            return events
    raise AssertionError("no engine.step in the trace")


def test_under_a_profiler_the_leaves_cover_the_engine_thread(served_model,
                                                             tmp_path):
    """The trace a ``--trace 1`` run takes, on the CPU: between the first
    and the last ``engine.step`` the leaves (the pattern of the
    ``idle_unattributed_pct.*`` files plus ``engine.loop``) leave holes of
    under 1 % of the engine thread's time, and with 5 ms of sleep after
    the commit's last line of Python no parent span is ever innermost for
    more than 20 us (one in twenty may be, on a shared box; none for a
    millisecond): the sleep is the commit's."""
    import re

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmark"))
    import trace_reduce

    cfg, params, _ = served_model
    eng = _engine(cfg, params, max_slots=3)
    decode = eng._run_decode_step

    def decode_then_sleep(prefill_s):
        decode(prefill_s)
        time.sleep(0.005)

    eng._run_decode_step = decode_then_sleep
    # compile everything first: the trace holds working iterations
    _drain(eng, [eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=3)])
    jax.profiler.start_trace(str(tmp_path))
    try:
        with eng:
            reqs = [eng.submit(list(range(1, 4 + 2 * i)), max_new_tokens=12)
                    for i in range(5)]
            for r in reqs:
                assert r.wait(120)
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(str(tmp_path)))
    events = _engine_thread_events(trace)
    steps = [(s, s + d) for n, s, d in events if n == "engine.step"]
    assert len(steps) >= 12
    t0, t1 = steps[0][0], steps[-1][1]
    leaf = re.compile(
        r"engine\.(admit|prefill_chunk|first_token|decode\.dispatch|"
        r"decode\.fetch|decode\.commit|log|wait|loop)")
    named = trace_reduce.merge([
        (max(s, t0), min(s + d, t1)) for n, s, d in events
        if leaf.fullmatch(n) and s + d > t0 and s < t1])
    holes = (t1 - t0) - sum(b - a for a, b in named)
    assert 0 <= holes < 0.01 * (t1 - t0), (holes, t1 - t0)
    # innermost = the event that started last among those open
    # (benchmark/trace_reduce.py:attribute_gaps): a parent is innermost
    # where nothing that started inside it covers it
    commits = [d for n, _, d in events if n == "engine.decode.commit"]
    assert len(commits) >= 12 and min(commits) >= 0.005
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    stretches = []
    for i, (name, s, d) in enumerate(order):
        if name not in ("engine.step", "engine.prefill", "engine.decode"):
            continue
        inside = []
        for n2, s2, d2 in order[i + 1:]:
            if s2 >= s + d:
                break
            inside.append((s2, min(s2 + d2, s + d)))
        cursor, worst = s, 0.0
        for a, b in trace_reduce.merge(inside):
            worst = max(worst, a - cursor)
            cursor = b
        stretches.append((max(worst, s + d - cursor), name))
    # none holds the 5 ms, or a piece of them; and 20 us but for the odd
    # one that a collection or the box's other work fell into
    assert max(stretches)[0] < 1e-3, max(stretches)
    long = [x for x in stretches if x[0] > 20e-6]
    assert len(long) <= max(1, len(stretches) // 20), long


def test_a_non_finite_number_still_leaves_strict_json(served_model, tmp_path):
    """The step log's fast path (``json.dumps`` alone) is for finite
    records; a fault's NaN takes the sentinel strings, as before."""
    cfg, params, _ = served_model
    eng = _engine(cfg, params, logdir=str(tmp_path))
    req = eng.submit([1, 2, 3], max_new_tokens=8)
    eng.step()
    eng.kv.billed_blocks = lambda slot: float("nan")
    _drain(eng, [req])
    eng.stop()
    with open(os.path.join(tmp_path, "steps.jsonl")) as f:
        rows = [json.loads(line, parse_constant=lambda c: pytest.fail(c))
                for line in f]
    assert rows[0]["kv_blocks_billed"] >= 0
    assert any(r["kv_blocks_billed"] == "NaN" for r in rows[1:])
