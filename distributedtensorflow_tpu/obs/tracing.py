"""Lightweight span tracing: wall-time trees per training step.

``with span("data_wait"): ...`` times a region.  Spans nest per thread
(children attach to the enclosing span); a completed *root* span is
delivered to the installed :class:`TraceRecorder`, which groups roots into
per-step rows, writes them to ``trace.jsonl``, and accumulates per-name
window totals the Trainer turns into the step-time breakdown
(data-wait / compute-dispatch / host-blocking / checkpoint / eval).

Design constraints:

- ``span`` must be exception-transparent — the Trainer's fit loop relies on
  ``StopIteration`` from ``next(it)`` escaping unchanged, so ``span`` is a
  plain class context manager, NOT a ``@contextmanager`` generator (PEP 479
  would turn an in-body StopIteration into RuntimeError).
- near-zero cost when no recorder is installed: two ``perf_counter`` calls,
  a list push/pop and one ``jax.profiler.TraceAnnotation`` (an atomic
  load while no profiler session is open);
- every ``span`` is mirrored into the profiler: while a ``jax.profiler``
  trace is open — whoever opened it — the span appears on the host thread
  under its own name, its keyword attributes as the event's stats, on the
  same clock as the device lanes.  No ``capture_active()`` gate: a trace
  started outside ``obs.capture`` must see the spans too;
- spans may complete on any thread (the Prefetcher's ``device_put`` worker);
  roots from any thread land in the currently open step row;
- where the leaves of a stretch must leave nothing of it unnamed (the
  serving engine's iteration), :class:`tiled` is the same ``Span`` and the
  same annotation with the boundaries shared: one clock read closes a leaf
  and opens the next, and a parent ends with its last child.

``trace.jsonl`` row schema (one JSON object per line)::

    {"step": int, "k": int, "t_wall": float,
     "spans": [{"name": str, "dur_s": float, "children": [...]}, ...]}
    {"kind": "anomaly", "step": int, "anomaly": str, "message": str,
     "value": float}
    {"kind": "anomaly", "anomaly": "engine_stall", "t": float, "step": int
     (the steps.jsonl record's), "value": float (step_s + log_prev_s),
     "median_s": float, "log_prev_s": float, "message": str,
     "spans": [tree]}
    {"kind": "span", "name": str, "trace_id": str, "span_id": str,
     "parent_id": str?, "t0": float unix seconds, "dur_s": float,
     "proc": int, ...}

The ``kind: "span"`` rows are **cross-process trace spans** (the fleet
observability plane, ISSUE 11): unlike the per-step span trees they carry
absolute wall-clock ``t0`` and a ``trace_id`` shared across process
boundaries, so ``tools/timeline.py --fleet`` can stitch a client span in
one process's ``trace.jsonl`` against the dispatcher/worker spans it
caused in another's.  The context travels as a two-field dict
``{"trace_id", "span_id"}`` — injected into RPC frames by the data-service
client, echoed through ``data/wire.py`` headers, and attached per serve
request — and :class:`remote_span` is the emitting context manager
(near-free when no recorder is installed).

A process's **start-up** is such a trace too: :class:`PhaseTrace` writes
its back-to-back phases (``startup.imports``, ``startup.backend``, ... —
docs/OBSERVABILITY.md has the list) as ``kind: "span"`` rows under the
one ``trace_id`` ``"startup"``, so what happens before the first step has
names and absolute times.

What JAX traces, lowers, compiles or loads from its cache is a row too
(:func:`install_compile_log`): ``compile.trace`` | ``compile.lower`` |
``compile.backend`` | ``compile.cache_load`` with the ``program`` (JAX's
``fun_name``), absolute ``t0`` and, on ``compile.backend``, ``cache``
(``"hit"`` | ``"miss"`` | ``"off"``) and ``cache_load_s``.  An event that
begins inside another on the same thread (a jitted helper traced inside
``jit_decode``) is its child (``parent_id``); sums and counters count
roots only.  Until the start-up trace says :meth:`PhaseTrace.ready` the
roots are children of the phase they ended in (``trace_id``
``"startup"``), after it they carry ``trace_id`` ``"compile"``; either
way the span open on the thread gets them as ``compile.*`` children, and
:func:`take_compiled` hands the iteration they happened in their seconds
and names.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any

__all__ = [
    "Span",
    "span",
    "tiled",
    "TraceRecorder",
    "active_recorder",
    "add_root_sink",
    "remove_root_sink",
    "current_context",
    "new_trace_id",
    "new_span_id",
    "record_remote_span",
    "remote_span",
    "PhaseTrace",
    "install_compile_log",
    "uninstall_compile_log",
    "take_compiled",
]

_tls = threading.local()


class Span:
    __slots__ = ("name", "t0", "dur_s", "children")

    def __init__(self, name: str):
        self.name = name
        self.t0 = 0.0
        self.dur_s = 0.0
        self.children: list[Span] = []

    def to_dict(self) -> dict[str, Any]:
        d: dict[str, Any] = {"name": self.name, "dur_s": round(self.dur_s, 6)}
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


#: ``jax.profiler.TraceAnnotation``, imported at the first span (this
#: module must stay importable before a backend is chosen).
_TraceAnnotation = None


def _annotation(name: str, attrs: dict[str, Any]):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation  # noqa: PLC0415

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **attrs)


class span:
    """``with span("train_step"): ...`` — time a region into the trace.

    Keyword attributes (``span("engine.step", step=7)``) go to the
    profiler annotation only; the span tree keeps names and durations."""

    __slots__ = ("_span", "_ann")

    def __init__(self, name: str, **attrs: Any):
        self._span = Span(name)
        self._ann = _annotation(name, attrs)

    def __enter__(self) -> Span:
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._ann.__enter__()
        self._span.t0 = time.perf_counter()
        stack.append(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self._span
        s.dur_s = time.perf_counter() - s.t0
        self._ann.__exit__(exc_type, exc, tb)
        stack = _tls.stack
        stack.pop()
        _completed(stack, s)
        return False


def _completed(stack: list, s: Span) -> None:
    """``s`` just closed with ``stack`` holding what is still open on this
    thread: a child goes to its parent, a root to the recorder and the
    sinks."""
    if stack:
        stack[-1].children.append(s)
        return
    rec = _recorder
    if rec is not None:
        rec._add_root(s)
    for sink in _root_sinks:
        # A sink raising inside __exit__ would REPLACE the body's
        # in-flight exception (StopIteration ends the fit loop) —
        # swallow unconditionally; sinks are telemetry, not logic.
        try:
            sink(s)
        except Exception:
            pass


class tiled:
    """``with tiled("engine.step", "engine.admit") as t: ...`` — a span
    whose descendants tile it: every instant from entry to exit lies inside
    exactly one leaf.

    The names after the root's are the path it opens with, and
    ``t.to(*path)`` names where the thread goes next, as the span names
    below the root (``t.to("engine.decode", "engine.decode.fetch")``).  ONE
    clock read closes what is open and not a parent on ``path``, and opens
    the rest of ``path``; its last name is always a new span, which is
    returned (its ``dur_s`` is final once the next ``to`` or the exit has
    closed it).  The entry opens the root and its first path with one read
    and the exit closes the open leaf, its parents and the root with one,
    so a parent neither precedes its first child nor outlives its last.
    Spans and annotations are ``span``'s: the tree, the recorder, the sinks
    and the profiler see no difference, and a plain ``span`` may nest
    inside a leaf."""

    __slots__ = ("root", "_open")

    def __init__(self, name: str, *first: str, **attrs: Any):
        self.root = Span(name)
        #: (span, annotation) from the root down to the open leaf
        self._open = [(self.root, _annotation(name, attrs))]
        self._open += [(Span(n), _annotation(n, {})) for n in first]

    def __enter__(self) -> "tiled":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        for _, ann in self._open:
            ann.__enter__()
        t = time.perf_counter()
        for s, _ in self._open:
            s.t0 = t
            stack.append(s)
        return self

    @property
    def parent(self) -> Span:
        """The open leaf's parent."""
        return self._open[-2][0]

    def _close(self, keep: int, t: float) -> None:
        """Close the open spans below the first ``keep`` (>= 1), innermost
        first, all at ``t``: each goes to its parent, which is open."""
        open_, stack = self._open, _tls.stack
        while len(open_) > keep:
            s, ann = open_.pop()
            s.dur_s = t - s.t0
            ann.__exit__(None, None, None)
            stack.pop()
            open_[-1][0].children.append(s)

    def to(self, *path: str) -> Span:
        open_ = self._open
        # the parents on `path` that are open already stay open
        keep, last = 1, len(path) - 1
        while keep <= last and keep < len(open_) \
                and open_[keep][0].name == path[keep - 1]:
            keep += 1
        new = [(Span(n), _annotation(n, {})) for n in path[keep - 1:]]
        t = time.perf_counter()
        self._close(keep, t)
        stack = _tls.stack
        for pair in new:
            s, ann = pair
            ann.__enter__()
            s.t0 = t
            stack.append(s)
            open_.append(pair)
        return s

    def __exit__(self, exc_type, exc, tb) -> bool:
        t = time.perf_counter()
        self._close(1, t)
        root, ann = self._open[0]
        root.dur_s = t - root.t0
        ann.__exit__(exc_type, exc, tb)
        stack = _tls.stack
        stack.pop()
        _completed(stack, root)
        return False


_recorder: "TraceRecorder | None" = None
_recorder_lock = threading.Lock()

#: Extra consumers of completed ROOT spans (the goodput ledger) — fed even
#: when no TraceRecorder is installed, so pre-fit spans (checkpoint
#: restore, AOT cost-estimate compile) are observable.  A tuple: reads on
#: the span hot path are lock-free snapshots.
_root_sinks: tuple = ()


def add_root_sink(fn) -> None:
    """Register ``fn(span)`` to receive every completed root span."""
    global _root_sinks
    with _recorder_lock:
        if fn not in _root_sinks:
            _root_sinks = _root_sinks + (fn,)


def remove_root_sink(fn) -> None:
    global _root_sinks
    with _recorder_lock:
        _root_sinks = tuple(f for f in _root_sinks if f is not fn)


def active_recorder() -> "TraceRecorder | None":
    return _recorder


class TraceRecorder:
    """Collects root spans into per-step rows and window totals.

    ``path=None`` keeps the recorder accounting-only (window totals for the
    breakdown, no file) — the Trainer installs one per fit either way.
    Only the chief process writes the file (the ``MetricWriter``
    convention); non-chief recorders still accumulate window totals so
    cross-host aggregation has per-host numbers to gather.

    ``step_rows=False`` is for a process with no step loop (``serve.py``:
    ``begin_step`` is never called, so buffered roots would only grow):
    completed roots feed the window totals and nothing is kept for a row;
    the file holds ``kind: "span"`` rows and events only.
    """

    def __init__(self, path: str | None = None, *, chief_only: bool = True,
                 step_rows: bool = True):
        self._f = None
        self._step_rows = step_rows
        if path is not None:
            chief = True
            if chief_only:
                try:
                    import jax  # noqa: PLC0415

                    chief = jax.process_index() == 0
                except Exception:
                    chief = True
            if chief:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                self._f = open(path, "a")
        self._lock = threading.Lock()
        self._step: int | None = None
        self._k = 1
        self._step_t0 = 0.0
        self._roots: list[Span] = []
        self._window: dict[str, float] = {}
        self._window_counts: dict[str, int] = {}

    # -- install / uninstall -------------------------------------------------

    def install(self) -> "TraceRecorder":
        global _recorder
        with _recorder_lock:
            _recorder = self
        return self

    def uninstall(self) -> None:
        global _recorder
        with _recorder_lock:
            if _recorder is self:
                _recorder = None

    def __enter__(self) -> "TraceRecorder":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()
        self.close()

    # -- span intake ---------------------------------------------------------

    def _add_root(self, s: Span) -> None:
        with self._lock:
            if self._step_rows:
                self._roots.append(s)
            self._window[s.name] = self._window.get(s.name, 0.0) + s.dur_s
            self._window_counts[s.name] = self._window_counts.get(s.name, 0) + 1

    # -- step grouping -------------------------------------------------------

    def begin_step(self, step: int, k: int = 1) -> None:
        """Open a step row; roots completing until ``end_step`` belong to it.

        An already-open row is flushed first, so a loop that only calls
        ``begin_step`` still emits every row.
        """
        with self._lock:
            if self._step is not None:
                self._flush_row_locked()
            self._step = step
            self._k = k
            self._step_t0 = time.perf_counter()
            self._roots = []

    def adjust_step(self, step: int, k: int = 1) -> None:
        """Relabel the open row — for callers whose step count is only
        final after the data fetch (a short prebundled trailing bundle
        shrinks the dispatch below the projected k)."""
        with self._lock:
            if self._step is not None:
                self._step = step
                self._k = k

    def end_step(self) -> None:
        with self._lock:
            self._flush_row_locked()

    def _flush_row_locked(self) -> None:
        if self._step is None:
            # roots outside any step (e.g. the final checkpoint after the
            # loop): emit them unanchored so the wall time is not lost.
            if self._roots and self._f is not None:
                self._write(
                    {"step": None,
                     "spans": [s.to_dict() for s in self._roots]}
                )
            self._roots = []
            return
        row = {
            "step": self._step,
            "k": self._k,
            "t_wall": round(time.perf_counter() - self._step_t0, 6),
            "spans": [s.to_dict() for s in self._roots],
        }
        self._step = None
        self._roots = []
        if self._f is not None:
            self._write(row)

    def write_event(self, event: dict[str, Any]) -> None:
        """Append an out-of-band row (anomalies, run markers)."""
        with self._lock:
            if self._f is not None:
                self._write(event)

    def _write(self, row: dict[str, Any]) -> None:
        from ..utils.metrics import json_sanitize  # noqa: PLC0415

        # allow_nan=False + sentinel strings: an anomaly event's value is
        # often NaN, and a bare NaN token is invalid strict JSON.
        self._f.write(json.dumps(json_sanitize(row), allow_nan=False) + "\n")
        self._f.flush()

    # -- breakdown window ----------------------------------------------------

    def drain_window(self) -> dict[str, float]:
        """Return and reset per-span-name total seconds since last drain.

        The Trainer divides these by the window's optimizer-step count to
        get the per-step breakdown fields.
        """
        with self._lock:
            totals, self._window = self._window, {}
            self._window_counts = {}
            return totals

    def close(self) -> None:
        with self._lock:
            self._flush_row_locked()
            if self._f is not None:
                self._f.close()
                self._f = None


# -- cross-process trace context (fleet observability plane) -----------------

_ctx_tls = threading.local()


#: Ids are 8 hex characters drawn once a process and 8 of a counter: no
#: system call (``uuid4`` reads ``os.urandom``, which lets go of the
#: interpreter on the engine thread) and unique within the process.
_id_prefix = os.urandom(4).hex()
_id_counter = itertools.count(1)


def _redraw_id_prefix() -> None:
    global _id_prefix
    _id_prefix = os.urandom(4).hex()


os.register_at_fork(after_in_child=_redraw_id_prefix)


def new_span_id() -> str:
    """A fresh 16-hex-char span id (unique per emitted span)."""
    return f"{_id_prefix}{next(_id_counter) & 0xFFFFFFFF:08x}"


#: A fresh 16-hex-char trace id (shared across every process a request
#: touches): the same draw.
new_trace_id = new_span_id


def current_context() -> dict[str, str] | None:
    """The calling thread's live trace context ``{"trace_id", "span_id"}``
    (the innermost open :class:`remote_span`), or None.  The returned dict
    is the wire-injectable form — put it in an RPC frame verbatim and the
    receiving process opens its span with ``remote_span(..., context=...)``
    to parent under it."""
    ctx = getattr(_ctx_tls, "ctx", None)
    return dict(ctx) if ctx else None


def record_remote_span(
    name: str,
    *,
    t0: float,
    dur_s: float,
    trace_id: str,
    span_id: str | None = None,
    parent_id: str | None = None,
    **fields: Any,
) -> dict[str, Any] | None:
    """Write one already-measured cross-process span row to the active
    recorder's ``trace.jsonl`` (the ``kind: "span"`` schema above).

    ``t0`` is absolute unix seconds — cross-process stitching cannot use
    the per-step rows' relative durations.  No-op (returns None) when no
    recorder is installed or it has no file; never raises (spans are
    telemetry, not logic)."""
    rec = _recorder
    if rec is None:
        return None
    row: dict[str, Any] = {
        "kind": "span",
        "name": str(name),
        "trace_id": str(trace_id),
        "span_id": str(span_id or new_span_id()),
        "t0": round(float(t0), 6),
        "dur_s": round(max(float(dur_s), 0.0), 6),
        "proc": os.getpid(),
    }
    if parent_id:
        row["parent_id"] = str(parent_id)
    row.update(fields)
    try:
        rec.write_event(row)
    except Exception:
        return None
    return row


class remote_span:
    """``with remote_span("data_service.fetch_split", split=3): ...`` —
    a cross-process span: absolute wall-clock timing plus trace-context
    propagation.

    On entry it resolves its trace context — an explicit ``context``
    (the ``{"trace_id", "span_id"}`` dict received over the wire, which
    becomes the parent), else the thread's current context, else a fresh
    trace — and installs itself as the thread's current context so nested
    ``remote_span``s and wire injections (:func:`current_context`) parent
    correctly.  On exit it restores the previous context and writes one
    ``kind: "span"`` row via :func:`record_remote_span`.

    Exception-transparent (plain class context manager, the ``span``
    rule) and near-free when no recorder is installed.  ``.context`` is
    readable while open AND after exit — a client stores it to parent
    later work under the same span."""

    __slots__ = ("name", "fields", "trace_id", "span_id", "parent_id",
                 "row", "_t0", "_prev")

    def __init__(self, name: str, *, context: dict | None = None,
                 **fields: Any):
        self.name = name
        self.fields = fields
        parent = context if isinstance(context, dict) else None
        if parent is None or not parent.get("trace_id"):
            parent = getattr(_ctx_tls, "ctx", None)
        self.trace_id = str((parent or {}).get("trace_id") or new_trace_id())
        self.parent_id = (parent or {}).get("span_id")
        self.span_id = new_span_id()
        self.row: dict[str, Any] | None = None
        self._t0 = 0.0
        self._prev = None

    @property
    def context(self) -> dict[str, str]:
        """Wire-injectable ``{"trace_id", "span_id"}`` of THIS span."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def __enter__(self) -> "remote_span":
        self._prev = getattr(_ctx_tls, "ctx", None)
        _ctx_tls.ctx = {"trace_id": self.trace_id, "span_id": self.span_id}
        self._t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.time() - self._t0
        _ctx_tls.ctx = self._prev
        self.row = record_remote_span(
            self.name, t0=self._t0, dur_s=dur, trace_id=self.trace_id,
            span_id=self.span_id, parent_id=self.parent_id, **self.fields,
        )
        return False


#: The fields a phase row (and ``startup.ready``) sums its compile roots
#: into: seconds of the roots of each name, the part of ``backend_s`` that
#: was the persistent cache's read, and the ``compile.backend`` roots
#: counted (programs compiled or loaded).
_COMPILE_SUMS = ("trace_s", "lower_s", "backend_s", "cache_load_s",
                 "programs")


def _add_compile_root(sums: dict[str, Any], row: dict[str, Any]) -> None:
    """Add one root row of the compile log to ``sums``."""
    phase = row["name"][len("compile."):]
    sums[phase + "_s"] = sums.get(phase + "_s", 0.0) + row["dur_s"]
    if phase == "backend":
        sums["programs"] = sums.get("programs", 0) + 1
        sums["cache_load_s"] = sums.get("cache_load_s", 0.0) \
            + row["cache_load_s"]
        if row["cache"] != "off":
            key = "cache_hits" if row["cache"] == "hit" else "cache_misses"
            sums[key] = sums.get(key, 0) + 1


def _rounded_sums(sums: dict[str, Any], keys=_COMPILE_SUMS) -> dict[str, Any]:
    return {k: round(sums.get(k, 0), 6) for k in keys}


class PhaseTrace:
    """Back-to-back phases of one stage of a process (its start-up) as
    ``kind: "span"`` rows under one ``trace_id``.

    ``mark(name)`` says: phase ``name`` ran from the previous mark (or
    ``t0``) until now — so the rows tile the interval with nothing unnamed
    between them.  ``open(name)`` starts a phase that encloses the marks
    made with ``parent=name`` until ``close(name)`` writes its row.  Rows
    made while no recorder is installed (imports and the backend come
    before a recorder can exist) wait and are written at the next call.

    A trace that owns the compile log (``install_compile_log(phases=
    trace)``) holds the log's rows until the stretch they ended in gets
    its name: they are written just before that phase's row, the roots as
    its children, and the row carries their sums (``trace_s``, ``lower_s``,
    ``backend_s``, ``cache_load_s``, ``programs``), as every open phase
    around it does.  ``ready()`` ends the trace with the summary row
    ``startup.ready`` and gives the compile log back.  Marks may come from
    another thread than the one that opened the trace (the engine's, the
    trainer's), one thread at a time; the compile log's rows from any.
    """

    def __init__(self, trace_id: str, t0: float | None = None):
        self.trace_id = trace_id
        self._t0 = self._t = time.time() if t0 is None else t0
        #: name -> [span id, t0, compile sums of the rows written inside]
        self._open: dict[str, list] = {}
        self._pending: list[dict[str, Any]] = []
        self._lock = threading.Lock()
        self._compiles: list[dict[str, Any]] | None = None
        self._total: dict[str, Any] = {}
        self._top_s = 0.0

    def mark(self, name: str, *, parent: str | None = None,
             **fields: Any) -> None:
        if parent is not None:
            fields["parent_id"] = self._open[parent][0]
        self._row(name, self._t, new_span_id(), **fields)

    def open(self, name: str) -> None:
        self._open[name] = [new_span_id(), self._t, {}]

    def close(self, name: str, **fields: Any) -> None:
        span_id, t0, sums = self._open.pop(name)
        self._row(name, t0, span_id, sums, **fields)

    def ready(self) -> None:
        """Start-up ended with the last row: one summary row
        ``startup.ready`` from the trace's ``t0`` to there.  ``total_s`` is
        what the process spent on the way, the compile sums are the whole
        trace's, and ``unnamed_s`` is ``total_s`` less the top-level
        phases: they tile it, so 0 up to rounding."""
        _release_compile_log(self)
        total = self._t - self._t0
        self._row_out(dict(
            name=self.trace_id + ".ready", t0=self._t0, dur_s=total,
            trace_id=self.trace_id, total_s=round(total, 6),
            **_rounded_sums(self._total, _COMPILE_SUMS + (
                "cache_hits", "cache_misses")),
            unnamed_s=round(max(total - self._top_s, 0.0), 6)))

    def _follow_compiles(self, row: dict[str, Any]) -> None:
        """The compile log's next row, to wait for its phase's name."""
        with self._lock:
            self._compiles.append(row)

    def _row(self, name: str, t0: float, span_id: str,
             sums: dict[str, Any] | None = None, **fields: Any) -> None:
        """The phase from ``t0`` to now; the clock moves to now."""
        self._t = time.time()
        rows: list[dict[str, Any]] = []
        if self._compiles is not None:
            with self._lock:
                rows, self._compiles = self._compiles, []
            inside: dict[str, Any] = {}
            for row in rows:
                if "parent_id" not in row:     # a root: this phase's child
                    row["parent_id"] = span_id
                    _add_compile_root(inside, row)
                row["trace_id"] = self.trace_id
            for acc in [self._total, *(o[2] for o in self._open.values())]:
                for k, v in inside.items():
                    acc[k] = acc.get(k, 0) + v
            if sums is not None:
                for k, v in sums.items():
                    inside[k] = inside.get(k, 0) + v
            fields.update(_rounded_sums(inside))
        if "parent_id" not in fields:
            self._top_s += self._t - t0
        self._row_out(*rows, dict(
            name=name, t0=t0, dur_s=self._t - t0, trace_id=self.trace_id,
            span_id=span_id, **fields))

    def _row_out(self, *rows: dict[str, Any]) -> None:
        self._pending += rows
        if _recorder is not None:
            pending, self._pending = self._pending, []
            for row in pending:
                record_remote_span(**row)


# -- the compile log ---------------------------------------------------------

#: ``jax.monitoring``'s time spans, by the row they become.  JAX says when
#: each begins (a scalar event, with the ``fun_name``) and when it ended
#: (the time span), on the thread that did the work.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: Inside a ``backend`` span: the persistent cache's read (a duration, and
#: only on a hit) and what the cache said.
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: A child shorter than this is left out of ``trace.jsonl``: every
#: ``jnp`` call of a traced function is a trace event of its own, a few
#: thousand a program and well under a millisecond each, and they explain
#: nothing their root does not.  A lowering, a compilation or a cache read
#: inside another event is always written.
COMPILE_CHILD_MIN_S = 0.01

_compile_tls = threading.local()


class _CompileLog:
    """The listeners' state: who gets the rows, and what compiled since
    the last :func:`take_compiled`.  A thread's open spans are a stack of
    ``[span id (drawn when a row needs it), program, cache, seconds of
    cache read]``."""

    def __init__(self):
        self.lock = threading.Lock()
        #: the start-up trace that holds the rows until ``ready()``
        self.phases: PhaseTrace | None = None
        #: ``[seconds, names]`` of the roots ended since the last take
        self.since: list | None = None

    def on_open(self, event: str, value: float, **kw: Any) -> None:
        if event in _COMPILE_SPANS:
            stack = getattr(_compile_tls, "stack", None)
            if stack is None:
                stack = _compile_tls.stack = []
            stack.append([None, str(kw.get("fun_name", "")), "off", 0.0])

    def on_event(self, event: str, **kw: Any) -> None:
        cache = _CACHE_EVENTS.get(event)
        stack = getattr(_compile_tls, "stack", None)
        if cache is not None and stack:
            stack[-1][2] = cache

    def on_duration(self, event: str, secs: float, **kw: Any) -> None:
        stack = getattr(_compile_tls, "stack", None)
        if event != _CACHE_LOAD or not stack:
            return
        top = stack[-1]
        top[0] = top[0] or new_span_id()
        top[3] += secs
        self.write(dict(name="compile.cache_load", program=top[1],
                        t0=time.time() - secs, dur_s=secs,
                        span_id=new_span_id(), parent_id=top[0]))

    def on_span(self, event: str, t0: float, t1: float, **kw: Any) -> None:
        phase = _COMPILE_SPANS.get(event)
        if phase is None:
            return
        stack = getattr(_compile_tls, "stack", None)
        # (a span that was open when the listeners came has no entry)
        span_id, program, cache, load_s = stack.pop() if stack else (
            None, str(kw.get("fun_name", "")), "off", 0.0)
        dur = max(t1 - t0, 0.0)
        row: dict[str, Any] = dict(name="compile." + phase, program=program,
                                   t0=t0, dur_s=dur)
        if phase == "backend":
            row["cache"] = cache
            row["cache_load_s"] = round(load_s, 6)
        if stack:
            if phase == "trace" and dur < COMPILE_CHILD_MIN_S \
                    and span_id is None:
                return
            parent = stack[-1]
            parent[0] = parent[0] or new_span_id()
            self.write(dict(row, span_id=span_id or new_span_id(),
                            parent_id=parent[0]))
            return
        self.write(dict(row, span_id=span_id or new_span_id()))
        self.count_root(row)

    def write(self, row: dict[str, Any]) -> None:
        phases = self.phases
        if phases is not None:
            phases._follow_compiles(row)
        else:
            record_remote_span(trace_id="compile", **row)

    def count_root(self, row: dict[str, Any]) -> None:
        """Sums count roots only: the registry's two counters, the
        iteration's account, and a ``compile.*`` child of the span open on
        this thread (a root span where none is: the goodput ledger books
        either as ``compile``)."""
        from .registry import counter  # noqa: PLC0415

        name, program, dur = row["name"], row["program"], row["dur_s"]
        seconds = counter(
            "jit_compile_seconds_total",
            "seconds of the compile log's roots by phase (trace, lower, "
            "backend; cache_load is the part of backend that read the "
            "persistent cache)")
        seconds.inc(dur, phase=name[len("compile."):])
        if name == "compile.backend":
            seconds.inc(row["cache_load_s"], phase="cache_load")
            counter(
                "jit_compiles_total",
                "programs compiled or loaded (compile.backend roots) by "
                "program and what the persistent cache said",
            ).inc(program=program, cache=row["cache"])
        # the function's own name, as the trace has it: not "jit(f)"
        if program.endswith(")"):
            program = program[program.find("(") + 1:-1]
        with self.lock:
            since = self.since
            if since is None:
                since = self.since = [0.0, []]
            since[0] += dur
            if program not in since[1]:
                since[1].append(program)
        s = Span(name)
        s.dur_s = dur
        _completed(getattr(_tls, "stack", None) or [], s)


_compile_log: _CompileLog | None = None


def install_compile_log(phases: PhaseTrace | None = None) -> None:
    """Listen to what JAX traces, lowers, compiles and loads, once a
    process: a second call registers nothing.  ``phases`` is the start-up
    trace that takes the rows from here until its ``ready()``."""
    global _compile_log
    with _recorder_lock:
        log = _compile_log
        if log is None:
            import jax.monitoring as monitoring  # noqa: PLC0415

            log = _compile_log = _CompileLog()
            monitoring.register_scalar_listener(log.on_open)
            monitoring.register_event_listener(log.on_event)
            monitoring.register_event_duration_secs_listener(log.on_duration)
            monitoring.register_event_time_span_listener(log.on_span)
    if phases is not None:
        phases._compiles = []
        log.phases = phases


def uninstall_compile_log() -> None:
    """Stop listening (tests)."""
    global _compile_log
    with _recorder_lock:
        log, _compile_log = _compile_log, None
    if log is not None:
        import jax.monitoring as monitoring  # noqa: PLC0415

        monitoring.unregister_scalar_listener(log.on_open)
        monitoring.unregister_event_listener(log.on_event)
        monitoring.unregister_event_duration_listener(log.on_duration)
        monitoring.unregister_event_time_span_listener(log.on_span)


def _release_compile_log(phases: PhaseTrace) -> None:
    """``phases`` is ready: the log's rows go straight to the recorder
    from here on, those ``phases`` still holds first."""
    log = _compile_log
    if log is not None and log.phases is phases:
        log.phases = None
    if phases._compiles:
        with phases._lock:
            rows, phases._compiles = phases._compiles, []
        for row in rows:
            record_remote_span(trace_id="compile", **row)


#: What an iteration with no compilation in it takes.
_NOTHING_COMPILED = (0.0, "")


def take_compiled() -> tuple[float, str]:
    """``(seconds, names)`` of the compile log's roots that ended since the
    last call, the programs' names joined by commas; ``(0.0, "")``, at the
    cost of one attribute read, where nothing did (or nothing listens)."""
    log = _compile_log
    if log is None or log.since is None:
        return _NOTHING_COMPILED
    with log.lock:
        (seconds, names), log.since = log.since, None
    return round(seconds, 6), ",".join(names)
