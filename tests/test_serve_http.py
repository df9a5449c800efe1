"""HTTP frontend tests: /generatez round trips with concurrent clients,
error mapping (400/429/504), and the StatusServer extra-route plumbing —
all in-process on CPU (same idiom as test_status_server.py)."""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, gpt_tiny
from distributedtensorflow_tpu.obs import Registry, StatusServer
from distributedtensorflow_tpu.serve import Engine, ServeServer


def _post(port, path, payload, timeout=60):
    data = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"},
    )
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def _get(port, path, timeout=10):
    try:
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        )
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture(scope="module")
def served_model():
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=64)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    return cfg, params, [int(t) for t in np.asarray(ids)[0]]


@pytest.fixture()
def frontend(served_model):
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=2, max_queue=8, block_size=4,
                    prefill_chunk=4, max_context=64).start()
    server = ServeServer(engine, 0).start()
    yield server, engine, prompt
    server.stop()
    engine.stop()


def test_roundtrip_and_state(frontend):
    server, engine, prompt = frontend
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 4})
    assert status == 200
    assert body["new_tokens"] == 4 and len(body["tokens"]) == 4
    assert body["finish_reason"] == "length"
    assert 0 <= body["ttft_s"] <= body["e2e_s"]
    status, raw = _get(server.port, "/generatez")
    assert status == 200
    st = json.loads(raw)
    assert st["counters"]["ok"] == 1
    assert st["max_slots"] == 2 and st["active_slots"] == 0


def test_concurrent_clients_batch(frontend):
    """Concurrent POSTs share decode steps: every reply is correct and
    the engine saw occupancy > 1."""
    server, engine, prompt = frontend
    results = {}

    def client(i):
        results[i] = _post(
            server.port, "/generatez",
            {"prompt": prompt[: 4 + i], "max_new_tokens": 8 + i,
             "seed": i},
        )

    threads = [threading.Thread(target=client, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ids = set()
    for i, (status, body) in results.items():
        assert status == 200, body
        assert body["new_tokens"] == 8 + i
        ids.add(body["id"])
    assert len(ids) == 6  # every request served distinctly
    assert engine.occupancy_max > 1  # continuous batching actually happened
    assert engine.counters["admits_into_freed_slot"] >= 1  # 6 reqs, 2 slots


def test_error_mapping_400(frontend):
    server, _, prompt = frontend
    for payload in (
        {"max_new_tokens": 4},                      # missing prompt
        {"prompt": "hi", "max_new_tokens": 4},      # not a token list
        {"prompt": [], "max_new_tokens": 4},        # empty
        {"prompt": prompt},                         # missing max_new_tokens
        {"prompt": prompt, "max_new_tokens": 0},    # engine validation
        {"prompt": [10 ** 9], "max_new_tokens": 4},  # out-of-vocab
        {"prompt": prompt, "max_new_tokens": 4.9},  # int fields are strict
        {"prompt": prompt, "max_new_tokens": 4, "top_k": True},  # no bools
    ):
        status, body = _post(server.port, "/generatez", payload)
        assert status == 400, payload
        assert "error" in body
    # malformed JSON body
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/generatez", data=b"{not json",
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 400
    # over-limit body: refused whole with 413, never truncated into a
    # half-parsed prompt
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/generatez",
        data=b'{"prompt": [' + b"1," * (1 << 20) + b'1]}',
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 413


def test_dead_engine_loop_visible_and_503(frontend):
    """A crashed scheduler loop flips /healthz to 503 and new POSTs are
    refused immediately instead of queueing onto a loop nothing drains."""
    server, engine, prompt = frontend
    engine._crashed = "XLA exploded (simulated)"
    status, body = _get(server.port, "/healthz")
    assert status == 503
    assert json.loads(body)["ok"] is False
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 2})
    assert status == 503
    assert "dead" in body["error"]
    engine._crashed = None  # let the fixture drain cleanly


def test_timeout_s_infinity_rejected(frontend):
    server, _, prompt = frontend
    status, body = _post(
        server.port, "/generatez",
        {"prompt": prompt, "max_new_tokens": 2, "timeout_s": float("inf")},
    )
    assert status == 400
    assert "timeout_s" in body["error"]


def test_timeout_s_zero_means_immediate_504(frontend):
    """An explicit timeout_s of 0 is honored (fire-and-poll), not
    silently replaced by the 300 s default."""
    server, engine, prompt = frontend
    status, body = _post(
        server.port, "/generatez",
        {"prompt": prompt, "max_new_tokens": 48, "timeout_s": 0},
    )
    assert status == 504
    assert "id" in body  # the request keeps running server-side


def test_backpressure_429_and_timeout_504(served_model):
    """An engine that is not consuming: the first request waits (504 on
    its small timeout), the queue fills, and the overflow request is
    429'd — then the engine starts and drains everyone."""
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=1, max_queue=1, block_size=4,
                    prefill_chunk=4, max_context=64)  # .start() deferred
    server = ServeServer(engine, 0).start()
    try:
        slow = {}

        def waiter():
            slow["res"] = _post(
                server.port, "/generatez",
                {"prompt": prompt, "max_new_tokens": 2, "timeout_s": 0.3},
            )

        t = threading.Thread(target=waiter)
        t.start()
        # wait until the first request occupies the queue
        deadline = [None] * 50
        for _ in deadline:
            if engine.state()["queue_depth"] >= 1:
                break
            time.sleep(0.02)
        assert engine.state()["queue_depth"] == 1
        status, body = _post(server.port, "/generatez",
                             {"prompt": prompt, "max_new_tokens": 2})
        assert status == 429
        assert "queue full" in body["error"]
        t.join(timeout=10)
        assert slow["res"][0] == 504  # timed out waiting, still queued
        engine.start()  # now drain it
        for _ in range(500):  # the stale 504'd request still fills the
            if engine.state()["queue_depth"] == 0:  # size-1 queue until
                break                               # the loop admits it
            time.sleep(0.02)
        ok = engine.generate(prompt, max_new_tokens=2, timeout=60)
        assert ok.status == "ok"
    finally:
        server.stop()
        engine.stop()


def test_statusz_family_rides_along(frontend):
    """The serving process exposes the whole introspection family next to
    /generatez, including the serve_* metrics on /varz."""
    server, engine, prompt = frontend
    _post(server.port, "/generatez", {"prompt": prompt, "max_new_tokens": 2})
    status, body = _get(server.port, "/healthz")
    assert status == 200
    health = json.loads(body)
    assert health["ok"] is True and "queue_depth" in health
    status, body = _get(server.port, "/varz")
    assert status == 200
    assert "serve_ttft_seconds" in body
    assert "serve_batch_occupancy" in body
    assert 'serve_requests_total{status="ok"}' in body
    status, body = _get(server.port, "/statusz")
    assert status == 200 and "serving" in body
    status, body = _get(server.port, "/helpz")
    assert status == 200 and "/generatez" in body


def test_status_server_extra_routes_unit():
    """The obs.StatusServer route hook itself: GET/POST dispatch, text vs
    JSON payloads, built-ins not shadowable."""
    reg = Registry()
    calls = {}

    def get_route(query):
        calls["get_q"] = query
        return 200, {"hello": "world"}

    def post_route(query, body):
        calls["post"] = (query, body)
        return 202, "accepted\n"

    srv = StatusServer(
        0, registry=reg,
        routes={
            ("GET", "/appz"): get_route,
            ("POST", "/appz"): post_route,
            ("GET", "/healthz"): get_route,  # must NOT shadow the builtin
        },
    ).start()
    try:
        status, body = _get(srv.port, "/appz?x=1")
        assert status == 200 and json.loads(body) == {"hello": "world"}
        assert calls["get_q"] == "x=1"
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/appz", data=b'{"k": 2}'
        )
        r = urllib.request.urlopen(req, timeout=10)
        assert r.status == 202 and r.read() == b"accepted\n"
        assert calls["post"][1] == b'{"k": 2}'
        status, body = _get(srv.port, "/healthz")
        assert status == 200
        assert json.loads(body)["ok"] is True  # builtin won, not get_route
    finally:
        srv.stop()


def test_drain_refuses_new_submits_with_503(frontend):
    """ISSUE 13 satellite: begin_drain() refuses NEW submits with 503
    immediately while the rest of the endpoint family stays up (in-flight
    responses still need the server)."""
    server, engine, prompt = frontend
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 2})
    assert status == 200
    server.begin_drain()
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 2})
    assert status == 503
    assert "draining" in body["error"]
    status, _ = _get(server.port, "/generatez")
    assert status == 200  # state introspection survives the drain


def test_queued_past_deadline_abandoned_server_side(served_model):
    """The per-request deadline is honored END TO END: a request whose
    deadline expires while it is still queued behind a busy slot is
    abandoned at admission (504, engine-side error), not decoded for a
    client that already gave up."""
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=1, max_queue=8, block_size=4,
                    prefill_chunk=4, max_context=64)
    try:
        # no loop running: submit queues; drive the scheduler by hand
        blocker = engine.submit(prompt, max_new_tokens=8)
        doomed = engine.submit(prompt, max_new_tokens=2, deadline_s=0.05)
        time.sleep(0.1)  # the doomed request's deadline passes in queue
        for _ in range(40):
            engine.step()
            if blocker.wait(0) and doomed.wait(0):
                break
        assert blocker.status == "ok"
        assert doomed.status == "error"
        assert doomed.deadline_exceeded
        assert "deadline" in doomed.error
        assert doomed.tokens == []  # never decoded
    finally:
        engine.stop(drain=False)


# ------------------------------------------------- streaming (ISSUE 15)


def _post_stream(port, payload, timeout=60):
    """POST /generatez with a streaming body; returns (status, lines)
    where lines are the parsed ndjson documents (urllib's http.client
    decodes the chunked transfer)."""
    data = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generatez", data=data,
        headers={"Content-Type": "application/json"},
    )
    r = urllib.request.urlopen(req, timeout=timeout)
    lines = [json.loads(l) for l in r.read().decode().splitlines() if l]
    return r.status, r.headers, lines


def test_streaming_tokens_then_trailer(frontend):
    """stream=true emits per-iteration token lines whose concatenation
    equals the blocking reply, then one trailer with the usual stats;
    requests.jsonl semantics (tested on the engine) are untouched."""
    server, engine, prompt = frontend
    status, blocking = _post(server.port, "/generatez",
                             {"prompt": prompt, "max_new_tokens": 6})
    assert status == 200
    status, headers, lines = _post_stream(
        server.port, {"prompt": prompt, "max_new_tokens": 6,
                      "stream": True})
    assert status == 200
    assert headers.get("Content-Type", "").startswith(
        "application/x-ndjson")
    token_lines = [l for l in lines if "tokens" in l and "done" not in l]
    assert len(token_lines) >= 2  # incremental, not one blob
    streamed = [t for l in token_lines for t in l["tokens"]]
    assert streamed == blocking["tokens"]  # greedy: identical output
    trailer = lines[-1]
    assert trailer["done"] is True and trailer["status"] == "ok"
    assert trailer["new_tokens"] == 6
    assert trailer["finish_reason"] == "length"
    assert 0 <= trailer["ttft_s"] <= trailer["e2e_s"]
    assert "tokens" not in trailer  # already streamed line by line
    assert trailer["accepted"] <= trailer["drafted"] or (
        trailer["drafted"] == 0 and trailer["accepted"] == 0)


def test_concurrent_streams_one_line_a_committed_iteration(frontend):
    """Every stream gets exactly one line for each iteration that
    committed a token of its request — the first token's and then one a
    decode iteration, never two iterations merged into a line — in the
    order committed, and the trailer last; concurrent streams that share
    decode iterations included (three streams over two slots)."""
    server, engine, prompt = frontend
    want = {}
    for i in range(3):
        status, body = _post(
            server.port, "/generatez",
            {"prompt": prompt[: 5 + i], "max_new_tokens": 7 + i})
        assert status == 200
        want[i] = body["tokens"]

    def settled():
        """The engine's newest step record, once it is the record of an
        iteration that left nothing behind: a reply is sent from inside
        the commit, before the iteration's record is written."""
        for _ in range(400):
            last = engine.step_records(1)[0]
            if not last["active_slots"] and not last["queue_depth"] \
                    and engine.steps_total == last["step"]:
                return last["step"]
            time.sleep(0.005)
        raise AssertionError("the engine did not settle")

    steps0 = settled()
    got = {}

    def client(i):
        got[i] = _post_stream(
            server.port, {"prompt": prompt[: 5 + i],
                          "max_new_tokens": 7 + i, "stream": True})

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, (status, _, lines) in got.items():
        assert status == 200
        assert [l.get("done", False) for l in lines] \
            == [False] * (7 + i) + [True]
        assert all(len(l["tokens"]) == 1 for l in lines[:-1])
        assert [l["tokens"][0] for l in lines[:-1]] == want[i]
        assert lines[-1]["status"] == "ok"
        assert lines[-1]["new_tokens"] == 7 + i
    settled()
    records = [r for r in engine.step_records() if r["step"] > steps0]
    # one line a request for its first token, one a slot a decode iteration
    assert sum(r["tokens_committed"] for r in records) + 3 \
        == sum(7 + i for i in range(3))
    assert max(r["occupancy"] for r in records) == 2


def test_streaming_submit_errors_keep_real_statuses(frontend):
    """Submit-time failures must NOT be smuggled into a 200 stream:
    validation still 400s before any chunk goes out."""
    server, engine, prompt = frontend
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 0,
                          "stream": True})
    assert status == 400
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 2,
                          "stream": "yes"})
    assert status == 400
    assert "stream" in body["error"]


def test_streaming_timeout_lands_in_trailer(served_model):
    """A stream whose request outlives timeout_s ends with a timeout
    trailer (headers are committed, so no 504 is possible) while the
    request keeps running server-side."""
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=1, max_queue=8, block_size=4,
                    prefill_chunk=4, max_context=64)
    server = ServeServer(engine, 0).start()
    try:
        # engine loop NOT started: nothing drains, the stream times out
        status, headers, lines = _post_stream(
            server.port, {"prompt": prompt, "max_new_tokens": 4,
                          "stream": True, "timeout_s": 0.3})
        assert status == 200
        assert lines[-1]["done"] is True
        assert lines[-1]["status"] == "timeout"
        assert "timeout" in lines[-1]["error"]
    finally:
        server.stop()
        engine.stop(drain=False)
