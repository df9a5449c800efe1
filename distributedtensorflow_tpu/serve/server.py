"""HTTP serving frontend: ``/generatez`` on the StatusServer pattern.

A thin blocking-JSON frontend over :class:`serve.engine.Engine`, riding
``obs.server.StatusServer`` (stdlib ``http.server`` background thread, one
handler thread per request; the streams' lines are all written by ONE
thread, :class:`StreamWriter`) so a serving process exposes the whole
introspection family — ``/healthz``, ``/statusz``, ``/varz`` (live
Prometheus incl. the ``serve_*`` SLO histograms), ``/threadz``, ``/memz``
— next to the generation endpoint, no third-party deps.

Endpoint contract (docs/API.md "Serving"):

- ``POST /generatez`` — body ``{"prompt": [int, ...], "max_new_tokens":
  int, "temperature"?: float, "top_k"?: int, "eos_token_id"?: int,
  "seed"?: int, "timeout_s"?: float, "trace_id"?: str, "tenant"?: str,
  "stream"?: bool}``.  Blocks until the request reaches a terminal
  state; replies 200 ``{"id", "tokens", "trace_id", "tenant",
  "finish_reason", "prompt_tokens", "new_tokens", "ttft_s", "tpot_s",
  "e2e_s", "drafted", "accepted"}``.  ``trace_id`` is the
  distributed-tracing id the engine's queue/prefill/decode spans carry
  (generated when absent); ``tenant`` is the validated identity
  (identifier-style, <= 64 chars; defaults to ``"default"``) that the
  request's requests.jsonl row, its slot in ``GET /generatez`` and the
  step log's ``admitted_tenants`` carry.
  Error mapping: malformed body/parameters → 400, queue full
  (backpressure) → 429, engine failure → 500, wall-clock timeout → 504
  (the request keeps running server-side; poll ``GET /generatez`` for
  slot state).

  With ``"stream": true`` the reply is a chunked-transfer
  ``application/x-ndjson`` stream: one ``{"tokens": [int, ...]}`` line
  per engine iteration AS each iteration commits tokens (a speculative
  burst arrives as one line), then a final trailer line ``{"done":
  true, "status": ..., ...}`` carrying the same stats the blocking
  reply would (or the error).  Because headers go out before the first
  token, submit-time failures still map to real 4xx/5xx statuses —
  only post-admission failures land in the trailer.  requests.jsonl
  rows are identical to blocking requests.  Between a stream's headers
  and its trailer no thread wakes for it but the engine's and the one
  :class:`StreamWriter` thread: the engine hands it every stream's line
  of an iteration in one call, the request's handler thread is parked
  until the terminating chunk is out.
- ``GET /generatez`` — engine state JSON: queue depth, slot occupancy
  (with each slot's ``prefill``/``decode`` phase), paged-KV budget,
  admission/eviction counters, the stream writer's census (``streams``:
  open streams, bytes waiting for a full socket, lines, wakes), and the
  prefix-cache census (``kv``:
  blocks free/used/cached, fragmentation, prefix occupancy, hit rate,
  evictions, CoW copies; ``prefill_budget``/``prefix_cache`` config) —
  the scheduler's live control surface.  The same census rides ``/varz``
  as ``serve_kv_*`` / ``serve_prefix_*`` registry metrics, so the fleet
  scraper (``obs.fleet``) sees it without a serve-specific endpoint.
- ``GET /stepz?n=`` — the engine step log's live tail: the newest ``n``
  (default 32) scheduler-iteration records from the bounded ring (the
  ``steps.jsonl`` schema), wrapped with ``ring_size`` / ``steps_total``
  — "what is the engine doing RIGHT NOW, iteration by iteration".
"""

from __future__ import annotations

import collections
import functools
import heapq
import itertools
import json
import logging
import math
import selectors
import socket
import threading
import time

from ..obs.server import StatusServer
from .engine import Engine, GenRequest, QueueFullError

logger = logging.getLogger("distributedtensorflow_tpu")

__all__ = ["ServeServer", "StreamWriter"]

#: Cap on how long one POST handler thread blocks awaiting generation.
DEFAULT_TIMEOUT_S = 300.0


def _as_int(v) -> int:
    """Strict JSON-int: 4.9 (or true) must 400, not truncate to 4."""
    if isinstance(v, bool) or not isinstance(v, int):
        raise ValueError(f"not an integer: {v!r}")
    return v


def _as_float(v) -> float:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise ValueError(f"not a number: {v!r}")
    return float(v)


def _ok_stats(req: GenRequest) -> dict:
    """The completed-request stat block: the blocking 200 body, and
    (minus ``tokens``, already streamed) the streaming trailer."""
    return {
        "id": req.id,
        "tokens": req.tokens,
        "trace_id": req.trace_id,
        "tenant": req.tenant,
        "finish_reason": req.finish_reason,
        "prompt_tokens": len(req.prompt),
        "new_tokens": len(req.tokens),
        "ttft_s": round(req.ttft_s, 6),
        "tpot_s": round(req.tpot_s, 6),
        "e2e_s": round(req.e2e_s, 6),
        "drafted": req.drafted,
        "accepted": req.accepted,
    }


def _error_trailer(status: str, req: GenRequest, error: str) -> dict:
    return {"done": True, "status": status, "id": req.id, "error": error}


def _trailer(req: GenRequest) -> dict:
    """The last line of the stream of a request that reached its end."""
    if req.status == "ok":
        trailer = {"done": True, "status": "ok", **_ok_stats(req)}
        del trailer["tokens"]  # already streamed line by line
        return trailer
    if req.deadline_exceeded:
        # engine-side deadline abandonment is the SAME condition the
        # writer's own expiry reports (and the blocking path maps to
        # 504): one status class, not a race
        return _error_trailer("timeout", req,
                              req.error or "deadline exceeded")
    return _error_trailer(req.status, req,
                          req.error or f"request {req.status}")


def _chunk(doc: dict) -> bytes:
    """One ndjson line as one HTTP/1.1 chunk."""
    data = (json.dumps(doc) + "\n").encode("utf-8")
    return b"%X\r\n%b\r\n" % (len(data), data)


_LAST_CHUNK = b"0\r\n\r\n"


class _Stream:
    """One streaming request as its writer sees it.  It rides the request
    (``GenRequest.stream``), so the engine's hand-over finds it without a
    look-up; but for ``timeout_s``, ``released`` and ``keep`` it is the
    writer thread's alone."""

    __slots__ = ("timeout_s", "req", "sock", "buf", "flushed", "marks",
                 "watched", "over", "released", "keep")

    def __init__(self, timeout_s: float):
        self.timeout_s = timeout_s
        self.req: GenRequest | None = None
        self.sock: socket.socket | None = None   # None: not attached (yet)
        self.buf = bytearray()      # bytes the socket has not taken
        self.flushed = 0            # bytes that have left ``buf``
        #: (``flushed`` once the line has left ``buf``, the line's commit
        #: stamp): the lines in ``buf``, oldest first
        self.marks: collections.deque = collections.deque()
        self.watched = False        # in the selector, for writability
        self.over = False           # takes no further line
        self.released = threading.Event()   # the handler thread parks here
        self.keep = False           # the connection may serve another request


class StreamWriter:
    """The one thread that writes every open ``/generatez`` stream.

    The engine calls :meth:`put` once for all the streams an iteration
    committed tokens for (``Engine.stream_sink``); the thread sleeps in a
    selector on a socketpair that ``put`` writes a byte to (no polling
    interval), and on waking formats every line of the batch and sends
    each to its socket without blocking.  A socket that would block keeps
    its unsent bytes in its stream's own buffer and is watched for
    writability: it delays no other stream and never the engine.  The
    thread also owns each stream's ``timeout_s`` deadline (it wakes at
    the earliest), writes the trailers and drops a stream whose client
    went away, while that request keeps running server-side.  A
    request's handler thread sends the headers, then parks in
    :meth:`serve` until its stream is over."""

    def __init__(self, engine: Engine, registry):
        self._note_line = engine.note_stream_line
        self._inbox: collections.deque = collections.deque()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._woken = False         # a byte is on its way to ``_wake_r``
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._wake_r, selectors.EVENT_READ)
        self._open: set[_Stream] = set()
        self._deadlines: list = []  # heap of (deadline, tie-break, stream)
        self._tie = itertools.count()
        self._pending = 0           # bytes in the streams' buffers
        self._lines = self._wakes = self._backlogged = 0
        self._lock = threading.Lock()   # ``_closed`` against ``serve``
        self._closed = False
        self._thread: threading.Thread | None = None
        self._m_lines = registry.counter(
            "serve_stream_lines_total",
            "token lines the stream writer took from the engine")
        self._m_wakes = registry.counter(
            "serve_stream_writer_wakes_total",
            "times the stream writer woke to lines of the engine's: lines "
            "a wake is about the decoding slots")
        self._m_backlogged = registry.counter(
            "serve_stream_backlogged_total",
            "token lines that met a full socket and waited in their "
            "stream's buffer")
        self._m_open = registry.gauge(
            "serve_streams_open", "streams a client is attached to")

    # -- any thread ----------------------------------------------------------

    def put(self, item) -> None:
        """``Engine.stream_sink``: a batch of ``(request, tokens,
        stamp)``.  An append and, if the thread may be asleep, one byte."""
        if self._closed:
            return      # nobody reads it any more
        self._inbox.append(item)
        if not self._woken:
            self._woken = True
            try:
                self._wake_w.send(b"\0")
            except OSError:     # full: it wakes anyway
                pass

    def serve(self, req: GenRequest, sock) -> bool:
        """A handler thread, with the chunked headers sent on ``sock``:
        give the connection to the writer and park until the stream is
        over.  True if the connection may serve another request.  ``sock``
        None (the headers met a dead client): the stream's lines go
        nowhere."""
        st = req.stream
        st.req = req
        if sock is None:
            self.put(("detach", st))
            return False
        timeout = sock.gettimeout()
        sock.setblocking(False)
        with self._lock:    # not beside ``_run``'s last look at the inbox
            if self._closed:
                return False
            self.put(("attach", st, sock))
        st.released.wait()
        sock.settimeout(timeout)
        return st.keep

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="dtf-serve-streams", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        """End every open stream (an ``error`` trailer where none was
        due yet) and join the thread."""
        if self._thread is not None:
            self.put(("stop",))
            self._thread.join(timeout=5)
            self._thread = None
        if not self._closed:    # never started
            self._closed = True
            self._close()

    def state(self) -> dict:
        """``GET /generatez``'s ``streams``."""
        return {"open": len(self._open), "pending_bytes": self._pending,
                "lines": self._lines, "wakes": self._wakes,
                "backlogged": self._backlogged}

    # -- the writer thread ---------------------------------------------------

    def _run(self) -> None:
        try:
            while self._turn():
                pass
        except Exception:
            logger.exception("the stream writer died")
            raise
        finally:
            with self._lock:
                self._closed = True
            for item in self._inbox:    # handlers that came too late
                if item[0] == "attach":
                    item[1].released.set()
            for st in list(self._open):
                if not st.over:
                    self._finish(st, _error_trailer(
                        "error", st.req, "server stopped"))
                if not st.released.is_set():    # what is unsent stays so
                    self._release(st, keep=False)
            self._close()

    def _close(self) -> None:
        self._sel.close()
        self._wake_r.close()
        self._wake_w.close()

    def _turn(self) -> bool:
        """Sleep until there is something to do, and do all of it.
        False once told to stop."""
        deadlines = self._deadlines
        while deadlines and deadlines[0][2].over:
            heapq.heappop(deadlines)
        timeout = None
        if deadlines:
            timeout = max(deadlines[0][0] - time.monotonic(), 0.0)
        for key, _ in self._sel.select(timeout):
            if key.data is None:
                self._wake_r.recv(4096)
            else:
                self._flush(key.data)   # a full socket took bytes again
        # before the inbox is read: a put that finds it set was read
        self._woken = False
        inbox, lines0, go_on = self._inbox, self._lines, True
        while inbox:
            item = inbox.popleft()
            if isinstance(item, list):
                self._batch(item)
            elif item[0] == "attach":
                self._attach(item[1], item[2])
            elif item[0] == "detach":
                self._lose(item[1])
            else:
                go_on = False
        if self._lines > lines0:
            self._wakes += 1
            self._m_wakes.inc()
            self._m_lines.inc(self._lines - lines0)
        now = time.monotonic()
        while deadlines and deadlines[0][0] <= now:
            st = heapq.heappop(deadlines)[2]
            if not st.over:
                self._finish(st, _error_trailer(
                    "timeout", st.req,
                    f"generation exceeded timeout_s={st.timeout_s}"))
        return go_on

    def _batch(self, batch: list) -> None:
        backlogged0 = self._backlogged
        for req, tokens, stamp in batch:
            st = req.stream
            if not isinstance(st, _Stream):
                continue    # submitted past the frontend: nobody reads it
            if tokens is None:
                if not st.over:
                    self._finish(st, _trailer(req))
            elif not st.over:
                self._lines += 1
                self._send(st, _chunk({"tokens": tokens}), stamp)
        if self._backlogged > backlogged0:
            self._m_backlogged.inc(self._backlogged - backlogged0)

    def _send(self, st: _Stream, data: bytes, stamp: float | None) -> None:
        """``data`` to the stream's socket, or as much as it takes now
        and the rest to the stream's buffer; ``stamp`` is a token line's."""
        sock = st.sock
        if sock is not None:
            sent = 0
            if not st.buf:
                try:
                    sent = sock.send(data)
                except BlockingIOError:
                    pass
                except OSError:
                    return self._lose(st)
                if sent == len(data):
                    if stamp is not None:
                        self._note_line(stamp)
                    return
                data = data[sent:]
                self._watch(st)
            if stamp is not None:
                self._backlogged += 1
        st.buf += data
        self._pending += len(data)
        if stamp is not None:
            st.marks.append((st.flushed + len(st.buf), stamp))

    def _flush(self, st: _Stream) -> None:
        """Send what the stream's buffer holds, as far as the socket
        takes it; a stream that is over and has nothing left lets its
        handler go."""
        if st.buf:
            try:
                sent = st.sock.send(st.buf)
            except BlockingIOError:
                sent = 0
            except OSError:
                return self._lose(st)
            del st.buf[:sent]
            self._pending -= sent
            st.flushed += sent
            marks = st.marks
            while marks and marks[0][0] <= st.flushed:
                self._note_line(marks.popleft()[1])
        if st.buf:
            self._watch(st)
        elif st.over:
            self._release(st, keep=True)
        elif st.watched:
            st.watched = False
            self._sel.unregister(st.sock)

    def _watch(self, st: _Stream) -> None:
        if not st.watched:
            st.watched = True
            self._sel.register(st.sock, selectors.EVENT_WRITE, st)

    def _attach(self, st: _Stream, sock) -> None:
        st.sock = sock
        self._open.add(st)
        self._m_open.set(len(self._open))
        if not st.over:
            heapq.heappush(self._deadlines, (
                time.monotonic() + st.timeout_s, next(self._tie), st))
        self._flush(st)     # the lines committed before the headers went

    def _finish(self, st: _Stream, trailer: dict) -> None:
        """The trailer and the terminating chunk; the handler goes once
        the socket has them."""
        st.over = True
        self._send(st, _chunk(trailer) + _LAST_CHUNK, None)
        if st.sock is not None and not st.buf:
            self._release(st, keep=True)

    def _lose(self, st: _Stream) -> None:
        """The client went away (or never saw the headers): its lines go
        nowhere from here on, the request runs on."""
        st.over = True
        self._pending -= len(st.buf)
        st.buf.clear()
        st.marks.clear()
        if st.sock is not None:
            self._release(st, keep=False)

    def _release(self, st: _Stream, keep: bool) -> None:
        if st.watched:
            st.watched = False
            self._sel.unregister(st.sock)
        st.sock = None
        self._open.discard(st)
        self._m_open.set(len(self._open))
        st.keep = keep
        st.released.set()


class ServeServer:
    """Background-thread HTTP server wrapping an :class:`Engine`.

    ``port=0`` binds an ephemeral port (``server.port`` tells).  The
    engine is NOT owned: callers start/stop it (so tests can drive the
    scheduler synchronously under a live frontend)."""

    def __init__(self, engine: Engine, port: int = 0, *,
                 host: str = "127.0.0.1", registry=None,
                 default_timeout_s: float = DEFAULT_TIMEOUT_S):
        self.engine = engine
        self._default_timeout_s = default_timeout_s
        self._draining = False
        self._srv = StatusServer(
            port, host=host, registry=registry,
            status_fn=lambda: {"serving": engine.state()},
            health_fn=self._health,
            routes={
                ("GET", "/generatez"): self._get_state,
                ("POST", "/generatez"): self._post_generate,
                ("GET", "/stepz"): self._stepz,
            },
        )
        self._streams = StreamWriter(engine, self._srv.registry)
        engine.stream_sink = self._streams.put

    @property
    def port(self) -> int:
        return self._srv.port

    @property
    def status_server(self):
        """The underlying :class:`obs.server.StatusServer` — exposed so
        fleet components (``SLOMonitor.install``, extra routes) can
        register endpoints next to ``/generatez``."""
        return self._srv

    def _health(self) -> dict:
        st = self.engine.state()
        return {
            # a dead scheduler loop must flip /healthz to 503 — the
            # process otherwise looks routable while serving nothing
            "ok": self.engine.healthy,
            "queue_depth": st["queue_depth"],
            "active_slots": st["active_slots"],
            "decode_steps": st["decode_steps"],
        }

    # -- handlers (HTTP threads) ---------------------------------------------

    def _get_state(self, query: str):
        return 200, {**self.engine.state(), "streams": self._streams.state()}

    def _stepz(self, query: str):
        """``GET /stepz`` — live tail of the engine step log: the newest
        ``n`` (default 32) per-iteration records from the bounded ring
        (phase mix, occupancy, token/draft deltas, admissions/evictions,
        prefill chunks + budget stalls, admit/prefill/decode walls) —
        the same records ``steps.jsonl`` persists."""
        from urllib.parse import parse_qs

        params = parse_qs(query or "", keep_blank_values=True)
        n = params.get("n", ["32"])[0]
        try:
            n = int(n)
            if n < 1:
                raise ValueError(n)
        except ValueError:
            return 400, {"error": f"bad 'n': {params.get('n')!r} "
                                  "(a positive integer)"}
        recs = self.engine.step_records(n)
        return 200, {
            "ring_size": self.engine.step_ring_size,
            "steps_total": self.engine.steps_total,
            "n": len(recs),
            "steps": recs,
        }

    def begin_drain(self) -> None:
        """Refuse NEW submits with 503 immediately (bounded SIGTERM
        drain): in-flight requests keep running and their responses still
        go out over the live server; the caller owns the wait-then-stop
        sequencing (serve.py ``--drain-timeout``)."""
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def _post_generate(self, query: str, body: bytes):
        if self._draining:
            return 503, {"error": "server draining (shutting down); "
                                  "resubmit elsewhere"}
        try:
            payload = json.loads(body or b"{}")
        except json.JSONDecodeError as e:
            return 400, {"error": f"invalid JSON body: {e}"}
        if not isinstance(payload, dict):
            return 400, {"error": "body must be a JSON object"}
        prompt = payload.get("prompt")
        if not isinstance(prompt, list) or not prompt or not all(
            isinstance(t, int) and not isinstance(t, bool) for t in prompt
        ):
            return 400, {"error": "'prompt' must be a non-empty list of "
                                  "token ids"}
        kwargs = {}
        for name, cast in (("max_new_tokens", _as_int),
                           ("temperature", _as_float),
                           ("top_k", _as_int), ("eos_token_id", _as_int),
                           ("seed", _as_int)):
            if payload.get(name) is not None:
                try:
                    kwargs[name] = cast(payload[name])
                except (TypeError, ValueError):
                    return 400, {"error": f"bad {name!r}: "
                                          f"{payload[name]!r}"}
        if "max_new_tokens" not in kwargs:
            return 400, {"error": "'max_new_tokens' is required"}
        trace_id = payload.get("trace_id")
        if trace_id is not None:
            # Distributed tracing: the caller's trace id rides the
            # request so the engine's queue/prefill/decode spans stitch
            # against upstream spans (timeline.py --fleet).
            if not isinstance(trace_id, str) or not 1 <= len(trace_id) <= 64:
                return 400, {"error": f"bad 'trace_id': {trace_id!r} "
                                      "(a 1..64-char string)"}
            kwargs["trace_id"] = trace_id
        tenant = payload.get("tenant")
        if tenant is not None:
            # The request's identity: the engine validates the grammar
            # (identifier-style) and maps violations to ValueError → 400
            # below; only the type is checked here.
            if not isinstance(tenant, str):
                return 400, {"error": f"bad 'tenant': {tenant!r} "
                                      "(a string)"}
            kwargs["tenant"] = tenant
        timeout = payload.get("timeout_s")
        if timeout is None:
            timeout = self._default_timeout_s
        try:
            timeout = float(timeout)
        except (TypeError, ValueError):
            return 400, {"error": f"bad 'timeout_s': {timeout!r}"}
        if not math.isfinite(timeout) or timeout < 0:
            # json.loads accepts the Infinity literal; Event.wait would
            # raise OverflowError AFTER the request had been submitted.
            return 400, {"error": f"'timeout_s' must be a finite number "
                                  f">= 0, got {timeout}"}
        timeout = min(timeout, threading.TIMEOUT_MAX)
        stream = payload.get("stream", False)
        if not isinstance(stream, bool):
            return 400, {"error": f"bad 'stream': {stream!r} (a boolean)"}
        try:
            # The client's timeout IS the request deadline, propagated
            # into the engine: a request still queued past it is
            # abandoned server-side instead of decoded for a client that
            # already gave up.
            req = self.engine.submit(
                prompt, deadline_s=timeout if timeout > 0 else None,
                stream=_Stream(timeout) if stream else False, **kwargs,
            )
        except QueueFullError as e:
            return 429, {"error": str(e)}
        except ValueError as e:
            return 400, {"error": str(e)}
        except RuntimeError as e:  # dead scheduler loop
            return 503, {"error": str(e)}
        if stream:
            # Chunked transfer: the StatusServer sends the headers and
            # calls this with the connection (obs.server._reply_stream);
            # the writer thread sends the lines, this thread parks.
            # Submit-time errors above kept their real statuses — from
            # here on failures ride the trailer line, since headers are
            # already committed.  The engine always terminates requests
            # (crash/stop included), so the trailer is guaranteed; the
            # timeout guards the stream the same way ``req.wait(timeout)``
            # guards the blocking path — on expiry the trailer reports it
            # and the request keeps running server-side (the engine-side
            # deadline already abandons requests still QUEUED past it).
            return 200, functools.partial(self._streams.serve, req)
        if not req.wait(timeout):
            return 504, {"error": f"generation exceeded timeout_s="
                                  f"{timeout}", "id": req.id}
        if req.deadline_exceeded:
            # The engine abandoned it at admission (overload): same
            # contract as the handler-side timer, observed server-side.
            return 504, {"error": req.error or "deadline exceeded",
                         "id": req.id}
        if req.status != "ok":
            return 500, {"error": req.error or f"request {req.status}",
                         "id": req.id}
        return 200, _ok_stats(req)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ServeServer":
        self._streams.start()
        self._srv.start()
        logger.info("serving frontend on port %d (POST /generatez)",
                    self.port)
        return self

    def stop(self) -> None:
        """Ends every open stream, joins the writer, then the server."""
        if self.engine.stream_sink == self._streams.put:
            self.engine.stream_sink = None
        self._streams.stop()
        self._srv.stop()

    close = stop

    def __enter__(self) -> "ServeServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
