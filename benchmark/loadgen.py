"""Open-loop HTTP load generator: one thread, every token timestamped.

Sends each request of a schedule at its due time whatever the server is
doing (open loop), reads ``POST /generatez`` ``"stream": true`` replies —
chunked ndjson, one ``{"tokens": [...]}`` line per engine iteration — and
stamps every line with the client's monotonic clock as it arrives.  One
``selectors`` loop over non-blocking sockets: no thread per request, so
the generator's own scheduling noise stays out of the gaps it measures.
At ``t_end`` it closes every stream and returns: there is no drain.

All times in the returned log are seconds relative to ``t_zero`` (the
window's opening) on ``time.monotonic()``.
"""

from __future__ import annotations

import errno
import json
import selectors
import socket
import time


class _Stream:
    """One in-flight request: socket, send buffer, incremental decoder of
    the chunked ndjson reply."""

    def __init__(self, req: dict, host: str, port: int, body: dict):
        self.req = req
        self.log = {"id": req["id"], "due": req["due"], "sent": None,
                    "prompt_tokens": len(req["prompt"]),
                    "max_new_tokens": req["max_new_tokens"],
                    "status": None, "token_times": [], "token_counts": [],
                    "done": None, "error": None}
        payload = json.dumps(body).encode()
        self.out = (
            f"POST /generatez HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload
        self.buf = b""
        self.headers_done = False
        self.chunked = False
        self.body = b""      # de-chunked bytes not yet split into lines
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setblocking(False)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        err = self.sock.connect_ex((host, port))
        if err not in (0, errno.EINPROGRESS):
            raise OSError(err, "connect failed")

    def feed(self, data: bytes, now: float) -> None:
        self.buf += data
        if not self.headers_done:
            head, sep, rest = self.buf.partition(b"\r\n\r\n")
            if not sep:
                return
            self.headers_done = True
            lines = head.decode("latin-1").split("\r\n")
            self.log["status"] = int(lines[0].split()[1])
            self.chunked = any(
                ln.lower().replace(" ", "") == "transfer-encoding:chunked"
                for ln in lines[1:])
            self.buf = rest
        if self.chunked:
            while True:
                size_line, sep, rest = self.buf.partition(b"\r\n")
                if not sep:
                    break
                size = int(size_line.split(b";")[0], 16)
                if len(rest) < size + 2:
                    break
                self.body += rest[:size]
                self.buf = rest[size + 2:]
                if size == 0:
                    break
        else:
            self.body += self.buf
            self.buf = b""
        while b"\n" in self.body:
            line, _, self.body = self.body.partition(b"\n")
            if line.strip():
                self._line(json.loads(line), now)
        if not self.chunked and self.log["status"] != 200 and self.body:
            try:
                self._line(json.loads(self.body), now)
                self.body = b""
            except json.JSONDecodeError:
                pass

    def _line(self, obj: dict, now: float) -> None:
        if "tokens" in obj and not obj.get("done"):
            self.log["token_times"].append(now)
            self.log["token_counts"].append(len(obj["tokens"]))
            self.log.setdefault("tokens", []).extend(obj["tokens"])
        elif obj.get("done"):
            self.log["done"] = now
            if obj.get("status") != "ok":
                self.log["error"] = obj.get("error") or obj.get("status")
        elif "error" in obj:
            self.log["error"] = obj["error"]


def run(host: str, port: int, schedule: list[dict], t_zero: float,
        t_end: float, sampling: dict | None = None,
        keep_tokens: bool = False, until_done: bool = False) -> list[dict]:
    """Drive ``schedule`` (each ``due`` relative to ``t_zero``, monotonic
    clock) until ``t_zero + t_end``; returns one log entry per request
    that was due before the end.  ``until_done`` returns as soon as every
    request has finished (set-up traffic; ``t_end`` is then a time limit)."""
    sel = selectors.DefaultSelector()
    pending = sorted(schedule, key=lambda r: r["due"])
    live: dict[int, _Stream] = {}
    logs = []
    nxt = 0
    deadline = t_zero + t_end
    while True:
        now = time.monotonic()
        if now >= deadline or (
                until_done and nxt == len(pending) and not live):
            break
        while nxt < len(pending) and t_zero + pending[nxt]["due"] <= now:
            req = pending[nxt]
            nxt += 1
            body = {"prompt": req["prompt"], "stream": True,
                    "max_new_tokens": req["max_new_tokens"],
                    "timeout_s": 600, "trace_id": req["id"],
                    **(sampling or {})}
            st = _Stream(req, host, port, body)
            logs.append(st.log)
            live[st.sock.fileno()] = st
            sel.register(st.sock, selectors.EVENT_WRITE | selectors.EVENT_READ,
                         st)
        wake = deadline
        if nxt < len(pending):
            wake = min(wake, t_zero + pending[nxt]["due"])
        for key, events in sel.select(max(wake - time.monotonic(), 0.0)):
            st = key.data
            now = time.monotonic()
            if events & selectors.EVENT_WRITE and st.out:
                try:
                    n = st.sock.send(st.out)
                except (BlockingIOError, InterruptedError):
                    n = 0
                except OSError as e:
                    st.log["error"] = f"send: {e}"
                    n = len(st.out)
                if st.log["sent"] is None and n:
                    st.log["sent"] = now - t_zero
                st.out = st.out[n:]
                if not st.out:
                    sel.modify(st.sock, selectors.EVENT_READ, st)
            if events & selectors.EVENT_READ:
                try:
                    data = st.sock.recv(1 << 16)
                except (BlockingIOError, InterruptedError):
                    continue
                except OSError as e:
                    data = b""
                    st.log["error"] = st.log["error"] or f"recv: {e}"
                if data:
                    st.feed(data, now - t_zero)
                if not data or st.log["done"] is not None or (
                        st.headers_done and st.log["status"] != 200):
                    sel.unregister(st.sock)
                    st.sock.close()
                    del live[key.fd]
    for st in live.values():   # the window is over: no drain
        sel.unregister(st.sock)
        st.sock.close()
    sel.close()
    if not keep_tokens:
        for entry in logs:
            entry.pop("tokens", None)
    return logs
