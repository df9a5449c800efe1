"""A tenant is a validated field of a request (ISSUE 19, cut to that by
PR 56: the per-tenant usage ledger that was keyed by it is gone).

What is held here: (1) the grammar of a tenant identity; (2) KV block
billing (the step log's ``kv_blocks_billed``) is refcount-weighted, so a
shared prefix block splits 1/N between its mappers and the pool is never
double-billed; (3) the identity threads the whole request path (submit
kwarg → requests.jsonl → step-log admissions → the HTTP reply), also for
a request refused at the door; (4) the streams that remain pass the
schema checker and keep the key sets they had beside the ledger;
(5) ``tail_report --tenant`` reads them back; (6) nothing of the ledger
is left: no ``/usagez`` route, no ``usage.jsonl``, no ``serve_tenant_*``
family, no import of it in the engine, no section of ``run_report``.
"""

import ast
import dataclasses
import json
import os
import shutil
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, gpt_tiny
from distributedtensorflow_tpu.obs.registry import Registry
from distributedtensorflow_tpu.ops.attention import KVRows
from distributedtensorflow_tpu.serve import (
    Engine,
    PagedKVCache,
    QueueFullError,
    ServeServer,
)
from distributedtensorflow_tpu.serve import engine as serve_engine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_metrics_schema as checker  # noqa: E402
import run_report  # noqa: E402
import tail_report  # noqa: E402


def _load_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ----------------------------------------------------------- unit: grammar


def test_validate_tenant():
    assert serve_engine.validate_tenant(None) == "default"
    assert serve_engine.validate_tenant("") == "default"
    assert serve_engine.validate_tenant("alpha_2") == "alpha_2"
    assert serve_engine.validate_tenant("_x") == "_x"
    for bad in ("9lead", "a b", "a-b", "a" * 65, "é"):
        with pytest.raises(ValueError):
            serve_engine.validate_tenant(bad)


# ------------------------------------------------- unit: 1/refcount billing


def test_billed_blocks_refcount_weighted():
    kv = PagedKVCache(num_layers=1,
                      rows=KVRows(heads=1, kv_heads=1, head_dim=4),
                      max_slots=2, num_blocks=8, block_size=4, max_context=16)
    assert kv.billed_blocks(0) == 0.0
    prompt = list(range(8))
    assert kv.admit(0, 8) is not None       # 2 exclusive blocks
    assert kv.billed_blocks(0) == pytest.approx(2.0)
    kv.register_prefix(0, prompt)
    assert kv.admit(1, 8, prompt=prompt) is not None  # 1 shared + 1 own
    assert kv.billed_blocks(0) == pytest.approx(1.5)  # 1/2 + 1
    assert kv.billed_blocks(1) == pytest.approx(1.5)
    used = kv.allocator.num_blocks - kv.stats()["blocks_free"] \
        - kv.stats()["blocks_cached"]
    assert kv.billed_blocks(0) + kv.billed_blocks(1) == pytest.approx(used)


# ------------------------------------------------ engine: tenant threading


@pytest.fixture(scope="module")
def served_model():
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=64)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    return cfg, params, ids


def _engine(cfg, params, **kw):
    # a registry of its own: the process-wide one also holds whatever the
    # test files before this one in the worker registered, and the engine
    # writes all of it into ``metrics.prom``
    kw.setdefault("registry", Registry())
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


@pytest.fixture(scope="module")
def tenant_logdir(served_model, tmp_path_factory):
    """One drained two-tenant engine run, shared by the offline-join
    tests (the streams are read-only from here on)."""
    cfg, params, ids = served_model
    logdir = str(tmp_path_factory.mktemp("usage_run"))
    prompts = np.asarray(ids)
    eng = _engine(cfg, params, logdir=logdir, log_every=1,
                  prefix_cache=True)
    reqs = []
    for i, tenant in enumerate(("alpha", "beta", None, "alpha")):
        prompt = [int(t) for t in prompts[i % 2]]
        reqs.append(eng.submit(prompt, max_new_tokens=3 + i,
                               tenant=tenant))
    _drain(eng, reqs)
    eng.stop()
    return logdir


#: The key set of a step row of this run at PR 55's parent commit, beside
#: the ledger, and the chunk's two counters (PR 58: ``chunk_tokens``,
#: ``chunk_pairs``): every row has the first, some rows the second.
STEP_KEYS = {
    "active_slots", "admit_s", "admitted", "between_s", "budget_stall",
    "chunk_pairs", "chunk_tokens",
    "commit_cpu_s", "commit_s", "compile_s", "decode_s", "device_sampled",
    "dispatch_s", "evicted", "fetch_s", "filling_slots", "first_token_s",
    "gc_s", "kv_blocks_billed", "kv_blocks_freed", "kv_blocks_used_full",
    "log_prev_s", "logits_fetched", "occupancy", "offcpu_s", "phase",
    "prefill_chunks", "prefill_prelaunched", "prefill_s", "prelaunch_s",
    "queue_depth", "spec_accepted",
    "spec_drafted", "step", "step_s", "stream_lag_max_s", "stream_lines",
    "t", "tokens_committed", "unnamed_s", "wait_s",
}
STEP_KEYS_SOME_ROWS = {"admitted_tenants", "compiled"}


def test_engine_threads_tenant_everywhere(tenant_logdir):
    requests = _load_jsonl(os.path.join(tenant_logdir, "requests.jsonl"))
    assert sorted({r["tenant"] for r in requests}) == \
        ["alpha", "beta", "default"]
    steps = _load_jsonl(os.path.join(tenant_logdir, "steps.jsonl"))
    admitted = {}
    for s in steps:
        assert s["kv_blocks_billed"] >= 0.0
        if s["admitted"]:
            at = s["admitted_tenants"]
            assert sum(at.values()) == s["admitted"]
            for k, v in at.items():
                admitted[k] = admitted.get(k, 0) + v
    assert admitted == {"alpha": 2, "beta": 1, "default": 1}


def test_streams_pass_schema_checker(tenant_logdir):
    for name in ("steps.jsonl", "requests.jsonl", "metrics.jsonl",
                 "metrics.prom"):
        errors, _warnings = checker.check_file(
            os.path.join(tenant_logdir, name))
        assert errors == [], f"{name}: {errors}"


def test_rejected_request_leaves_its_row_under_its_tenant(served_model,
                                                          tmp_path):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, max_queue=1, logdir=str(tmp_path))
    eng.submit(prompt, max_new_tokens=2, tenant="greedy")
    with pytest.raises(QueueFullError):
        eng.submit(prompt, max_new_tokens=2, tenant="greedy")
    with pytest.raises(ValueError, match="tenant must match"):
        eng.submit(prompt, max_new_tokens=2, tenant="not a tenant!")
    eng.stop(drain=False)
    rows = _load_jsonl(tmp_path / "requests.jsonl")
    rejected = [r for r in rows if r["status"] == "rejected"]
    assert [r["tenant"] for r in rejected] == ["greedy"]
    # the malformed one was refused before it was a request: no row
    assert {r["tenant"] for r in rows} == {"greedy"}
    errors, _ = checker.check_file(str(tmp_path / "requests.jsonl"))
    assert errors == []


# ------------------------------------------ nothing of the ledger is left


def test_no_usage_file_and_the_step_rows_keep_their_keys(tenant_logdir):
    assert sorted(os.listdir(tenant_logdir)) == [
        "metrics.jsonl", "metrics.prom", "requests.jsonl", "steps.jsonl"]
    steps = _load_jsonl(os.path.join(tenant_logdir, "steps.jsonl"))
    assert set.intersection(*(set(s) for s in steps)) == STEP_KEYS
    assert set().union(*(set(s) for s in steps)) - STEP_KEYS == \
        STEP_KEYS_SOME_ROWS


def test_exposition_has_no_tenant_family(tenant_logdir):
    with open(os.path.join(tenant_logdir, "metrics.prom")) as f:
        prom = f.read()
    assert "serve_requests_total" in prom     # the engine's families are
    assert "serve_tenant_" not in prom
    assert "tenant=" not in prom


def test_engine_imports_registry_and_tracing_from_obs_and_no_more():
    with open(serve_engine.__file__) as f:
        tree = ast.parse(f.read())
    from_obs = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            module = "." * node.level + (node.module or "")
            if module == "..obs":
                from_obs |= {a.name for a in node.names}
            else:
                assert "obs" not in module.split("."), module
        elif isinstance(node, ast.Import):
            for a in node.names:
                assert ".obs" not in a.name, a.name
    assert from_obs == {"registry", "tracing"}


def _get(port, path, timeout=10):
    try:
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        )
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_usagez_is_no_route(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    engine = _engine(cfg, params).start()
    server = ServeServer(engine, 0).start()
    try:
        body = json.dumps({"prompt": prompt, "max_new_tokens": 3,
                           "tenant": "alpha"}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generatez", data=body),
            timeout=30)
        assert r.status == 200
        assert json.loads(r.read())["tenant"] == "alpha"
        status, _ = _get(server.port, "/usagez")
        assert status == 404
        status, _ = _get(server.port, "/usagez?json")
        assert status == 404
        routes = server.status_server.routes
        assert ("GET", "/generatez") in routes
        assert not [key for key in routes if "usage" in key[1]]
        status, index = _get(server.port, "/")
        assert "usagez" not in index
    finally:
        server.stop()
        engine.stop()


# ------------------------------------------------------- offline joins


def test_run_report_passes_over_an_older_runs_usage_file(tenant_logdir,
                                                         tmp_path, capsys):
    """A logdir written before PR 56 still holds a ``usage.jsonl``:
    ``run_report`` does not read it — no section, no parse error, even
    where the file is not JSON."""
    logdir = str(tmp_path / "older_run")
    shutil.copytree(tenant_logdir, logdir)
    with open(os.path.join(logdir, "usage.jsonl"), "w") as f:
        f.write(json.dumps({"t": 1.0, "kind": "tenants", "steps_total": 1,
                            "tenants": {"alpha": {"slot_s": 1.0}}}) + "\n")
        f.write("{not json\n")
    report = run_report.build_report(logdir)
    assert "usage" not in report
    assert report["parse_errors"] == 0
    assert report["serving"]["requests"] == 4
    assert "usage & capacity" not in run_report.render(report)
    assert run_report.main([logdir]) == 0
    capsys.readouterr()


def test_tail_report_tenant_filter(tenant_logdir, capsys):
    rep = tail_report.build(tenant_logdir, tenant="alpha")
    assert rep["tenant_filter"] == "alpha"
    assert sorted(rep["per_tenant"]) == ["alpha", "beta", "default"]
    assert rep["per_tenant"]["alpha"]["requests"] == 2
    full = tail_report.build(tenant_logdir)
    assert full["tenant_filter"] is None
    assert full["per_tenant"] == rep["per_tenant"]
    assert tail_report.main([tenant_logdir, "--tenant", "alpha"]) == 0
    assert "alpha" in capsys.readouterr().out
    # unknown tenant: no ok rows survive the filter -> exit 1
    assert tail_report.main([tenant_logdir, "--tenant", "nobody"]) == 1
    capsys.readouterr()
