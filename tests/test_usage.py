"""Per-tenant usage metering + capacity observability tests (ISSUE 19).

The load-bearing checks: (1) the UsageMeter integrals match hand math —
queue/slot/block-seconds and token-FLOPs charge exactly what the hooks
were fed; (2) KV block billing is refcount-weighted, so a shared prefix
block splits 1/N between its mappers and the pool is never
double-billed; (3) the tenant identity threads the whole request path
(submit kwarg → requests.jsonl → step-log admissions) and the ledger's
Σ-over-tenants integrals tile the steps.jsonl occupancy integrals
(conservation by construction, gated by the schema checker); (4) the
``/usagez`` endpoint serves the ledger with real status codes; (5) the
tenant label rides under the registry cardinality guard; (6) the offline
joins — ``capacity_report``, ``run_report``'s usage section,
``tail_report --tenant`` — read the streams back consistently.
"""

import dataclasses
import json
import os
import sys
import types
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, gpt_tiny
from distributedtensorflow_tpu.obs import usage as obs_usage
from distributedtensorflow_tpu.obs.registry import Registry
from distributedtensorflow_tpu.ops.attention import KVRows
from distributedtensorflow_tpu.serve import (
    Engine,
    PagedKVCache,
    QueueFullError,
    ServeServer,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import capacity_report  # noqa: E402
import check_metrics_schema as checker  # noqa: E402
import run_report  # noqa: E402
import tail_report  # noqa: E402


def _req(id="r0", tenant="alpha", *, t_submit=0.0, t_admit=0.0, t_done=0.0,
         prefill_tokens=0, prompt=(), tokens=(), accepted=0, status="ok"):
    return types.SimpleNamespace(
        id=id, tenant=tenant, t_submit=t_submit, t_admit=t_admit,
        t_done=t_done, prefill_tokens=prefill_tokens, prompt=list(prompt),
        tokens=list(tokens), accepted=accepted, status=status,
    )


def _load_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ------------------------------------------------------------ unit: meter


def test_validate_tenant():
    assert obs_usage.validate_tenant(None) == "default"
    assert obs_usage.validate_tenant("") == "default"
    assert obs_usage.validate_tenant("alpha_2") == "alpha_2"
    assert obs_usage.validate_tenant("_x") == "_x"
    for bad in ("9lead", "a b", "a-b", "a" * 65, "é"):
        with pytest.raises(ValueError):
            obs_usage.validate_tenant(bad)


def test_meter_integrals_hand_math(tmp_path):
    reg = Registry()
    m = obs_usage.UsageMeter(
        registry=reg, logdir=str(tmp_path), token_flops=10.0,
        device_kind="", max_slots=2, kv_blocks_total=8, flush_every=1,
    )
    a = _req("a", "alpha", t_submit=100.0, t_admit=100.5,
             prefill_tokens=8, prompt=[1] * 8, tokens=[5, 6, 7], accepted=1)
    m.on_admit(a)
    m.on_step(101.0, 0.25, [(a, 4.0)], 1)
    m.on_step(101.5, 0.75, [(a, 2.0)], 2)
    m.on_tokens({a.tenant: 3})
    m.on_finish(a)
    # a rejected request never admitted: queue time = submit -> done
    r = _req("b", "beta", t_submit=10.0, t_admit=0.0, t_done=10.25,
             status="rejected")
    m.on_finish(r)
    m.close()

    rows = _load_jsonl(tmp_path / "usage.jsonl")
    final = [x for x in rows if x.get("kind") == "tenants"][-1]
    assert final["final"] is True
    alpha = final["tenants"]["alpha"]
    assert alpha["queue_s"] == pytest.approx(0.5)
    assert alpha["slot_s"] == pytest.approx(1.0)           # 0.25 + 0.75
    assert alpha["block_s"] == pytest.approx(1.0 + 1.5)    # 4*0.25 + 2*0.75
    assert alpha["prefill_tokens"] == 8
    assert alpha["new_tokens"] == 3
    assert alpha["spec_accepted"] == 1
    assert alpha["requests_ok"] == 1
    assert alpha["est_flops"] == pytest.approx((8 + 3) * 10.0)
    beta = final["tenants"]["beta"]
    assert beta["requests_rejected"] == 1
    assert beta["queue_s"] == pytest.approx(0.25)
    assert beta["slot_s"] == 0.0

    creq = [x for x in rows if x.get("kind") == "request"]
    assert [c["id"] for c in creq] == ["a", "b"]
    assert creq[0]["slot_s"] == pytest.approx(1.0)
    assert creq[0]["block_s"] == pytest.approx(2.5)
    assert creq[0]["est_flops"] == pytest.approx(110.0)
    assert creq[1]["status"] == "rejected"

    scal = reg.scalars()
    assert scal["serve_tenant_tokens_total.tenant_alpha"] == 3.0
    assert scal["serve_tenant_slot_seconds_total.tenant_alpha"] == \
        pytest.approx(1.0)
    assert scal["serve_tenant_kv_block_seconds_total.tenant_alpha"] == \
        pytest.approx(2.5)
    assert scal["serve_tenant_requests_total.status_rejected.tenant_beta"] \
        == 1.0 or \
        scal["serve_tenant_requests_total.tenant_beta.status_rejected"] \
        == 1.0


def test_meter_cardinality_guard():
    reg = Registry(max_label_sets=2)
    m = obs_usage.UsageMeter(registry=reg, token_flops=1.0, device_kind="")
    for i in range(6):  # 6 tenants through a 2-label-set registry
        m.on_tokens({f"t{i}": 1})
    scal = reg.scalars()
    kept = [k for k in scal if k.startswith("serve_tenant_tokens_total.")]
    assert len(kept) == 2
    dropped = [k for k in scal
               if k.startswith("registry_dropped_series_total.")]
    assert dropped and sum(scal[k] for k in dropped) >= 4


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


def test_conservation_against_step_log(tenant_logdir):
    steps = _load_jsonl(os.path.join(tenant_logdir, "steps.jsonl"))
    rows = _load_jsonl(os.path.join(tenant_logdir, "usage.jsonl"))
    final = [x for x in rows if x.get("kind") == "tenants"][-1]
    tenants = final["tenants"]
    slot_int = sum(s["active_slots"] * s["step_s"] for s in steps)
    block_int = sum(s["kv_blocks_billed"] * s["step_s"] for s in steps)
    assert sum(t["slot_s"] for t in tenants.values()) == \
        pytest.approx(slot_int, abs=1e-3)
    assert sum(t["block_s"] for t in tenants.values()) == \
        pytest.approx(block_int, abs=1e-3)
    # token identities: rollup totals == requests.jsonl totals
    requests = _load_jsonl(os.path.join(tenant_logdir, "requests.jsonl"))
    assert sum(t["new_tokens"] for t in tenants.values()) == \
        sum(r["new_tokens"] for r in requests if r["status"] == "ok")


def test_streams_pass_schema_checker(tenant_logdir):
    for name in ("usage.jsonl", "steps.jsonl", "requests.jsonl"):
        errors, _warnings = checker.check_file(
            os.path.join(tenant_logdir, name))
        assert errors == [], f"{name}: {errors}"


def test_rejected_request_metered(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, max_queue=1)
    eng.submit(prompt, max_new_tokens=2, tenant="greedy")
    with pytest.raises(QueueFullError):
        for _ in range(8):
            eng.submit(prompt, max_new_tokens=2, tenant="greedy")
    snap = eng.usage.snapshot()
    assert snap["tenants"]["greedy"]["requests_rejected"] >= 1
    with pytest.raises(ValueError):
        eng.submit(prompt, max_new_tokens=2, tenant="not a tenant!")
    eng.stop(drain=False)


def test_usage_checker_negative(tmp_path):
    with open(tmp_path / "steps.jsonl", "w") as f:
        f.write(json.dumps({"t": 1.0, "step": 1, "step_s": 1.0,
                            "active_slots": 1,
                            "kv_blocks_billed": 4.0}) + "\n")
    acc = {"queue_s": 0.0, "slot_s": 1.0, "block_s": 1.0,
           "prefill_tokens": 1, "new_tokens": 1, "spec_accepted": 0,
           "requests_ok": 1, "requests_rejected": 0, "requests_error": 0,
           "est_flops": 1.0, "est_compute_s": 0.0}
    row = {"t": 2.0, "kind": "tenants", "steps_total": 1, "max_slots": 1,
           "kv_blocks_total": 8, "final": True, "tenants": {"a": acc}}
    path = tmp_path / "usage.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps(row) + "\n")
    errors, _ = checker.check_file(str(path))
    assert any("conservation" in e for e in errors), errors
    # tenant grammar violation on a request row
    with open(path, "w") as f:
        f.write(json.dumps({"t": 1.0, "kind": "request", "id": "x",
                            "tenant": "not valid!", "status": "ok",
                            "prompt_tokens": 1, "new_tokens": 1,
                            "queue_s": 0.0, "slot_s": 0.0, "block_s": 0.0,
                            "est_flops": 0.0}) + "\n")
    errors, _ = checker.check_file(str(path))
    assert any("tenant" in e for e in errors), errors


# --------------------------------------------------------------- /usagez


def _get(port, path, timeout=10):
    try:
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        )
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_usagez_endpoint(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    engine = _engine(cfg, params).start()
    server = ServeServer(engine, 0).start()
    engine.usage.install(server.status_server)
    try:
        body = json.dumps({"prompt": prompt, "max_new_tokens": 3,
                           "tenant": "alpha"}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generatez", data=body),
            timeout=30)
        assert r.status == 200
        assert json.loads(r.read())["tenant"] == "alpha"

        status, raw = _get(server.port, "/usagez")
        assert status == 200 and "alpha" in raw

        status, raw = _get(server.port, "/usagez?json")
        assert status == 200
        doc = json.loads(raw)
        assert doc["tenants"]["alpha"]["requests_ok"] == 1
        assert doc["tenants"]["alpha"]["new_tokens"] == 3

        status, raw = _get(server.port, "/usagez?tenant=alpha&json")
        assert status == 200
        assert list(json.loads(raw)["tenants"]) == ["alpha"]

        status, raw = _get(server.port, "/usagez?tenant=nobody")
        assert status == 404
        assert json.loads(raw)["tenants"] == ["alpha"]

        # bad tenant types/grammar are 400s at the frontend
        for bad in (123, "not a tenant!"):
            body = json.dumps({"prompt": prompt, "max_new_tokens": 2,
                               "tenant": bad}).encode()
            try:
                r = urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{server.port}/generatez",
                    data=body), timeout=30)
                status = r.status
            except urllib.error.HTTPError as e:
                status = e.code
            assert status == 400, bad
    finally:
        server.stop()
        engine.stop()


# ------------------------------------------------------- offline joins


def test_capacity_report_build(tenant_logdir, capsys):
    rep = capacity_report.build(tenant_logdir, rate_rps=2.0)
    shares = rep["tenants"]
    for field in ("slot_share", "block_share", "new_tokens_share"):
        assert sum(t[field] for t in shares.values()) == \
            pytest.approx(1.0, abs=0.01)
    assert rep["profile"]["requests_ok"] == 4
    sat = rep["saturation"]
    assert 0.0 <= sat["slot_utilization"] <= 1.0 + 1e-6
    assert sat["block_utilization"] is not None
    wi = rep["what_if"]
    assert wi["offered_rate_rps"] == 2.0
    assert wi["queue_growth_verdict"] in \
        ("queue grows without bound", "stable")
    assert wi["predicted_slot_occupancy"] == \
        pytest.approx(2.0 * rep["profile"]["mean_slot_s"])
    assert capacity_report.main([tenant_logdir, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["tenants"].keys() == shares.keys()


def test_capacity_report_exit_codes(tmp_path, capsys):
    with pytest.raises(SystemExit):
        capacity_report.build(str(tmp_path))  # no usage.jsonl
    with open(tmp_path / "usage.jsonl", "w") as f:
        f.write("{not json\n")
    assert capacity_report.main([str(tmp_path)]) == 1
    capsys.readouterr()


def test_run_report_usage_section(tenant_logdir, capsys):
    report = run_report.build_report(tenant_logdir)
    usg = report["usage"]
    assert sorted(usg["tenants"]) == ["alpha", "beta", "default"]
    assert usg["top_tenant_by_block_s"] in usg["tenants"]
    assert sum(t["block_share"] for t in usg["tenants"].values()) == \
        pytest.approx(1.0, abs=0.01)
    assert usg["requests_closed"]["ok"] == 4
    assert "capacity" in usg
    text = run_report.render(report)
    assert "usage & capacity" in text
    # usage.jsonl parse errors gate the exit code like every stream
    with open(os.path.join(tenant_logdir, "usage.jsonl"), "a") as f:
        f.write("{not json\n")
    try:
        assert run_report.main([tenant_logdir]) == 1
    finally:
        # restore the stream for any later reader of the fixture
        path = os.path.join(tenant_logdir, "usage.jsonl")
        with open(path) as f:
            lines = f.readlines()
        with open(path, "w") as f:
            f.writelines(lines[:-1])
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
