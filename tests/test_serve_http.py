"""HTTP frontend tests: /generatez round trips with concurrent clients,
error mapping (400/429/504), and the StatusServer extra-route plumbing —
all in-process on CPU (same idiom as test_status_server.py)."""

import dataclasses
import json
import os
import socket
import struct
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


@pytest.mark.parametrize("tenant, told", [
    pytest.param("9lead", "tenant must match", id="leading_digit"),
    pytest.param("a-b", "tenant must match", id="hyphen"),
    pytest.param("a" * 65, "tenant must match", id="65_characters"),
    pytest.param(123, "bad 'tenant': 123 (a string)", id="not_a_string"),
    pytest.param(None, None, id="none"),
    pytest.param("", None, id="empty"),
])
def test_tenant_is_validated_at_the_door(frontend, tenant, told):
    """A malformed ``tenant`` is a 400 that states the grammar (a value
    that is no string: that it must be one); none serves under
    ``"default"``, which the reply names."""
    server, engine, prompt = frontend
    status, body = _post(server.port, "/generatez",
                         {"prompt": prompt, "max_new_tokens": 2,
                          "tenant": tenant})
    if told is None:
        assert status == 200, body
        assert body["tenant"] == "default"
        assert engine.counters["ok"] == 1
        return
    assert status == 400, body
    assert told in body["error"]
    if isinstance(tenant, str):
        assert r"^[A-Za-z_][A-Za-z0-9_]{0,63}$" in body["error"]
        assert "(identifier-style, <= 64 chars)" in body["error"]
    assert engine.counters["submitted"] == 0


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


# ------------------------------- one writer for all streams (ISSUE 46)


def _open_stream(port, payload, *, rcvbuf=None):
    """POST a streaming request on a raw socket; the reply is left on it."""
    sock = socket.socket()
    if rcvbuf is not None:      # before connect: it sizes the window
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(60)
    sock.connect(("127.0.0.1", port))
    body = json.dumps({"stream": True, **payload}).encode()
    sock.sendall((f"POST /generatez HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
                  "Content-Type: application/json\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    return sock


def _read_stream(sock):
    """The reply's bytes up to and with the terminating chunk."""
    raw = b""
    while not raw.endswith(b"\r\n0\r\n\r\n"):
        data = sock.recv(1 << 16)
        assert data, f"closed early after {raw[-80:]!r}"
        raw += data
    return raw


def _framed(raw):
    """``(header lines, documents)`` of a chunked ndjson reply, held byte
    for byte to the framing: a chunk a line, ``{len:X}\\r\\n`` + the line
    as ``json.dumps`` writes it + ``\\r\\n``, then ``0\\r\\n\\r\\n``."""
    head, _, body = raw.partition(b"\r\n\r\n")
    head = head.decode("latin-1").split("\r\n")
    assert head[0] == "HTTP/1.1 200 OK"
    assert "Transfer-Encoding: chunked" in head
    assert "Content-Type: application/x-ndjson" in head
    assert body.endswith(b"0\r\n\r\n")
    docs, rest = [], body[:-5]
    while rest:
        size, _, rest = rest.partition(b"\r\n")
        n = int(size, 16)
        assert size == b"%X" % n and rest[n:n + 2] == b"\r\n"
        line, rest = rest[:n], rest[n + 2:]
        assert line.endswith(b"\n") and line.count(b"\n") == 1
        docs.append(json.loads(line))
        assert (json.dumps(docs[-1]) + "\n").encode() == line
    return head, docs


def _stream_counts(reg):
    return {k: reg.counter(f"serve_stream_{k}_total").value()
            for k in ("lines", "writer_wakes", "backlogged")}


def _until(cond, what, seconds=20):
    end = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.002)


def _parked_handlers():
    """The handler threads inside ``StreamWriter.serve``, by whether they
    are parked on their stream's event."""
    parked = running = 0
    for frame in sys._current_frames().values():
        inner, names = frame, []
        while frame is not None:
            names.append(frame.f_code.co_name)
            frame = frame.f_back
        if "serve" in names and "_reply_stream" in names:
            if inner.f_code.co_filename.endswith("threading.py") \
                    and names[:2] == ["wait", "wait"]:
                parked += 1
            else:
                running += 1
    return parked, running


def test_sixteen_streams_one_writer_wake_an_iteration(served_model):
    """16 concurrent streams of different lengths, the engine driven from
    here an iteration at a time: every client gets one line a committed
    iteration, its trailer and the terminating chunk in today's framing;
    the writer wakes once for all the lines of an iteration, and no
    thread but it runs for a line (the handlers are parked)."""
    cfg, params, prompt = served_model
    reg = Registry()
    engine = Engine(params, cfg, max_slots=8, max_queue=16, block_size=4,
                    prefill_chunk=4, max_context=64, registry=reg)
    want = [engine.submit(prompt[: 3 + i % 5], max_new_tokens=4 + i)
            for i in range(16)]
    while not all(r._done.is_set() for r in want):
        engine.step()
    steps0, threads0 = engine.steps_total, threading.active_count()
    ok0 = engine.counters["ok"]
    server = ServeServer(engine, 0, registry=reg).start()
    try:
        socks = []
        for i in range(16):     # one at a time: the queue's order is i's
            socks.append(_open_stream(server.port, {
                "prompt": prompt[: 3 + i % 5], "max_new_tokens": 4 + i}))
            _until(lambda: engine.state()["queue_depth"] == i + 1, "submit")
        _until(lambda: _parked_handlers() == (16, 0), "the handlers to park")
        total = sum(4 + i for i in range(16))
        while engine.step():
            # the server's thread, the writer and 16 handlers: no more, and
            # the handler of every request still running is parked (one
            # whose request ended runs once more, to leave)
            assert threading.active_count() <= threads0 + 18
            assert _parked_handlers()[0] >= 16 - (engine.counters["ok"] - ok0)
        _until(lambda: _stream_counts(reg)["lines"] == total, "the writer")
        counts = _stream_counts(reg)
        assert counts["writer_wakes"] <= (engine.steps_total - steps0) + 16
        decode_iterations = sum(
            r["occupancy"] > 0 for r in engine.step_records()
            if r["step"] > steps0)
        # (a wake a first token, a wake a decode iteration: never a line)
        assert counts["writer_wakes"] <= decode_iterations + 16
        assert counts["lines"] > 4 * decode_iterations
        for i, sock in enumerate(socks):
            _, docs = _framed(_read_stream(sock))
            assert [d["tokens"] for d in docs[:-1]] \
                == [[t] for t in want[i].tokens]
            assert docs[-1]["done"] is True and docs[-1]["status"] == "ok"
            assert docs[-1]["new_tokens"] == 4 + i
            assert list(docs[-1])[:3] == ["done", "status", "id"]
            sock.close()
        _until(lambda: threading.active_count() <= threads0 + 2,
               "the handlers to leave")
        st = json.loads(_get(server.port, "/generatez")[1])["streams"]
        assert st == {"open": 0, "pending_bytes": 0,
                      "lines": counts["lines"],
                      "wakes": counts["writer_wakes"], "backlogged": 0}
        assert reg.gauge("serve_streams_open").value() == 0
        rows = [r for r in engine.step_records() if r["step"] > steps0]
        assert sum(r["stream_lines"] for r in rows) <= counts["lines"]
    finally:
        server.stop()
        engine.stop(drain=False)


def test_a_client_that_stops_reading_delays_nobody():
    """Two long streams, one client reading and one not (small buffers on
    both sides of its connection): the reader gets every line, the engine
    finishes both requests, the stalled stream's lines wait in its own
    buffer — and are all there, in order, when its client reads at last."""
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=512)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    prompt = [int(t) for t in np.asarray(ids)[0]]
    reg = Registry()
    engine = Engine(params, cfg, max_slots=2, max_queue=4, block_size=16,
                    prefill_chunk=8, max_context=512, registry=reg)
    server = ServeServer(engine, 0, registry=reg)
    # accepted connections inherit it: a send buffer of the kernel's least
    server.status_server._httpd.socket.setsockopt(
        socket.SOL_SOCKET, socket.SO_SNDBUF, 1)
    server.start()
    n = 480
    try:
        stalled = _open_stream(server.port, {
            "prompt": prompt, "max_new_tokens": n}, rcvbuf=1)
        reader = _open_stream(server.port, {
            "prompt": prompt[:5], "max_new_tokens": n})
        _until(lambda: engine.state()["queue_depth"] == 2, "both submits")
        engine.start()
        _, docs = _framed(_read_stream(reader))
        assert len(docs) == n + 1 and docs[-1]["status"] == "ok"
        # the engine went on too: both requests are done server-side
        _until(lambda: engine.counters["ok"] == 2, "the stalled request")
        _until(lambda: server._streams.state()["lines"] == 2 * n
               and server._streams.state()["open"] == 1, "the writer")
        st = server._streams.state()
        assert st["pending_bytes"] > 0 and st["backlogged"] > 0
        assert _stream_counts(reg)["backlogged"] == st["backlogged"]
        assert reg.gauge("serve_streams_open").value() == 1
        # the reader's lines did not wait for the stalled stream's
        lag = reg.histogram("serve_stream_lag_seconds", "").stats()
        assert lag["count"] < 2 * n
        _, docs = _framed(_read_stream(stalled))
        assert len(docs) == n + 1 and docs[-1]["new_tokens"] == n
        assert all(len(d["tokens"]) == 1 for d in docs[:-1])
        _until(lambda: server._streams.state()["open"] == 0, "the release")
        assert server._streams.state()["pending_bytes"] == 0
        lag = reg.histogram("serve_stream_lag_seconds", "").stats()
        assert lag["count"] == 2 * n    # each line once its bytes had gone
        reader.close()
        stalled.close()
    finally:
        server.stop()
        engine.stop(drain=False)


def test_a_client_that_disconnects_is_dropped(served_model):
    """One of three clients resets its connection mid-stream: the writer
    drops it, its request finishes server-side, the other streams are
    whole, no stream stays open and no descriptor is left behind."""
    cfg, params, prompt = served_model
    reg = Registry()
    engine = Engine(params, cfg, max_slots=3, max_queue=4, block_size=4,
                    prefill_chunk=4, max_context=64, registry=reg)
    server = ServeServer(engine, 0, registry=reg).start()
    fds0 = len(os.listdir("/proc/self/fd"))
    try:
        socks = [_open_stream(server.port, {
            "prompt": prompt[: 4 + i], "max_new_tokens": 30})
            for i in range(3)]
        _until(lambda: engine.state()["queue_depth"] == 3, "the submits")
        for _ in range(8):
            engine.step()
        _until(lambda: server._streams.state()["open"] == 3, "three streams")
        # SO_LINGER 0: close() sends a reset, the next send fails
        socks[0].setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
        socks[0].close()
        while engine.step():
            time.sleep(0.002)   # the reset is back before the next line
        assert engine.counters["ok"] == 3 and engine.counters["error"] == 0
        for sock in socks[1:]:
            _, docs = _framed(_read_stream(sock))
            assert len(docs) == 31 and docs[-1]["status"] == "ok"
            sock.close()
        _until(lambda: server._streams.state()["open"] == 0, "the drop")
        st = server._streams.state()
        assert st["pending_bytes"] == 0 and st["lines"] < 90
        assert reg.gauge("serve_streams_open").value() == 0
        _until(lambda: len(os.listdir("/proc/self/fd")) <= fds0,
               "the descriptors to close")
    finally:
        server.stop()
        engine.stop(drain=False)


def test_timeout_trailer_is_the_writers(served_model):
    """``timeout_s`` runs out with the engine standing still after two
    iterations: the writer wakes at the deadline and writes today's
    trailer; the request's later lines go nowhere, and the connection
    serves the next request."""
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=1, max_queue=8, block_size=4,
                    prefill_chunk=4, max_context=64)
    engine.submit(prompt[:4], max_new_tokens=3)     # compiles the programs
    while engine.step():
        pass
    server = ServeServer(engine, 0).start()
    try:
        t0 = time.monotonic()
        sock = _open_stream(server.port, {
            "prompt": prompt[:4], "max_new_tokens": 6, "timeout_s": 0.3})
        _until(lambda: engine.state()["queue_depth"] == 1, "the submit")
        engine.step()       # the prompt's one chunk: two tokens, two lines
        engine.step()
        _, docs = _framed(_read_stream(sock))
        assert 0.3 <= time.monotonic() - t0 < 5
        assert [list(d) for d in docs[:-1]] == [["tokens"]] * 3
        assert docs[-1] == {"done": True, "status": "timeout", "id": "r1",
                            "error": "generation exceeded timeout_s=0.3"}
        while engine.step():
            pass
        assert engine.counters["ok"] == 2       # it ran on server-side
        st = server._streams.state()
        assert st["open"] == 0 and st["pending_bytes"] == 0
        assert st["lines"] == 3
        sock.sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n\r\n")
        assert sock.recv(1 << 16).startswith(b"HTTP/1.1 200 OK")
        sock.close()
    finally:
        server.stop()
        engine.stop(drain=False)


def test_drain_lets_streams_finish_and_stop_ends_them(served_model):
    """A stream open at ``begin_drain()`` runs to its trailer while new
    submits get 503; ``stop()`` ends the stream still open with an error
    trailer, lets its handler go and joins the writer."""
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=2, max_queue=8, block_size=4,
                    prefill_chunk=4, max_context=64)
    server = ServeServer(engine, 0).start()
    threads0 = threading.active_count()
    try:
        short = _open_stream(server.port, {
            "prompt": prompt, "max_new_tokens": 3})
        long = _open_stream(server.port, {
            "prompt": prompt[:5], "max_new_tokens": 50})
        _until(lambda: engine.state()["queue_depth"] == 2, "the submits")
        server.begin_drain()
        status, body = _post(server.port, "/generatez",
                             {"prompt": prompt, "max_new_tokens": 2,
                              "stream": True})
        assert status == 503 and "draining" in body["error"]
        for _ in range(12):
            engine.step()
        _, docs = _framed(_read_stream(short))
        assert len(docs) == 4 and docs[-1]["status"] == "ok"
        long_id = engine.state()["slots"][1]["id"]
        _until(lambda: server._streams.state()["open"] == 1, "one stream")
        server.stop()
        _, docs = _framed(_read_stream(long))
        assert docs[-1] == {"done": True, "status": "error", "id": long_id,
                            "error": "server stopped"}
        assert 1 < len(docs) < 51
        assert all(t.name != "dtf-serve-streams"
                   for t in threading.enumerate())
        short.close()       # its handler was waiting for a next request
        long.close()
        _until(lambda: threading.active_count() <= threads0 - 2,
               "the handlers to leave")
        assert engine.stream_sink is None
    finally:
        server.stop()
        engine.stop(drain=False)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dead_engine_ends_every_stream_with_the_error_trailer(served_model):
    """The engine's loop dies mid-stream (``test_dead_engine_loop_visible_
    and_503``'s sibling): every open stream, decoding or still queued,
    ends with the ``error`` trailer and the terminating chunk."""
    cfg, params, prompt = served_model
    engine = Engine(params, cfg, max_slots=2, max_queue=8, block_size=4,
                    prefill_chunk=4, max_context=64)
    server = ServeServer(engine, 0).start()
    decode, calls = engine._run_decode_step, []

    def dying(prefill_s):
        calls.append(prefill_s)
        if len(calls) == 5:
            raise RuntimeError("XLA exploded (simulated)")
        decode(prefill_s)

    engine._run_decode_step = dying
    try:
        socks = [_open_stream(server.port, {
            "prompt": prompt[: 4 + i], "max_new_tokens": 40})
            for i in range(3)]
        _until(lambda: engine.state()["queue_depth"] == 3, "the submits")
        engine.start()
        lines = []
        for sock in socks:
            _, docs = _framed(_read_stream(sock))
            assert docs[-1]["done"] is True
            assert docs[-1]["status"] == "error"
            assert "XLA exploded" in docs[-1]["error"]
            assert list(docs[-1]) == ["done", "status", "id", "error"]
            lines.append(len(docs) - 1)
            sock.close()
        assert sorted(lines)[0] == 0 and 0 < sorted(lines)[-1] <= 5
        assert _get(server.port, "/healthz")[0] == 503
        _until(lambda: server._streams.state()["open"] == 0, "the release")
    finally:
        server.stop()
        engine._thread = None   # it died: nothing to join
        engine.stop(drain=False)
