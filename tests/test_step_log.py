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

