"""From a profiler trace to numbers: device busy/idle, time per named
operation, and idle gaps attributed to what the host was doing.

Copied in spirit from ``tools/analyze_trace.py`` (op families from the
device lane, trailing ``.N`` stripped) and extended with what it lacks:
the union of busy intervals, self time under nesting, and gap attribution.
It reads the profiler's own ``*.xplane.pb`` through
``jax.profiler.ProfileData`` (no TPU needed to read one) into a plain
structure, so the arithmetic below is checked on a small recorded trace
kept as JSON under ``tests/``:

    {"devices": {"<plane>": {"ops": [[name, start_s, dur_s], ...],
                             "modules": [[name, start_s, dur_s], ...]}},
     "host": {"<thread>": [[name, start_s, dur_s], ...]}}
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load_xplane(path: str) -> dict:
    """The plain structure above from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9] for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                          for e in line.events]
                if events:
                    name = line.name
                    while name in out["host"]:
                        name += "'"
                    out["host"][name] = events
    return out


def family(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%copy.4 = ...`` -> ``copy``."""
    name = name.split(" = ")[0].lstrip("%").split("(")[0]
    return re.sub(r"[.\d]+$", "", name) or name


def merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy(events: list) -> tuple[float, list[list[float]]]:
    """Seconds in which at least one event runs, and the merged intervals."""
    merged = merge([(s, s + d) for _, s, d in events if d > 0])
    return sum(e - s for s, e in merged), merged


def self_times(events: list) -> list[tuple[str, float, float, float]]:
    """``(name, start, duration, self seconds)`` in start order: self is the
    duration less the part nested children cover, so a ``while`` or
    ``call`` that wraps other operations is not counted twice."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [d for _, _, d in order]
    stack: list[int] = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(n, s, d, max(o, 0.0)) for (n, s, d), o in zip(order, own)]


def op_seconds(events: list, within: list | None = None) -> dict[str, float]:
    """Self seconds per operation family; ``within`` keeps only events
    that start inside one of those ``(start, end)`` intervals."""
    out: dict[str, float] = {}
    for name, s, _, own in self_times(events):
        if within is not None and not any(a <= s < b for a, b in within):
            continue
        fam = family(name)
        out[fam] = out.get(fam, 0.0) + own
    return out


def matching_seconds(events: list, pattern: str,
                     within: list | None = None) -> tuple[float, int]:
    """Summed self seconds and count of events whose name matches."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for name, s, _, own in self_times(events):
        if within is not None and not any(a <= s < b for a, b in within):
            continue
        if rx.search(name):
            total += own
            n += 1
    return total, n


def whole_executions(modules: list, pattern: str) -> list[tuple[float, float]]:
    """``(start, end)`` of the executions of the program whose name matches,
    without the ones the window's edges cut: a clipped execution is
    recorded with what was left of it, so anything under 0.9 of the longest
    is dropped."""
    rx = re.compile(pattern)
    hits = [(s, s + d) for n, s, d in modules if rx.search(n)]
    if not hits:
        return []
    longest = max(e - s for s, e in hits)
    return [(s, e) for s, e in hits if e - s >= 0.9 * longest]


def window_of(trace: dict) -> tuple[float, float]:
    """Traced window: first device operation's start to the last one's
    end, over all device planes (the host tracer starts earlier than the
    device's, so host events do not bound it)."""
    starts, ends = [], []
    for dev in trace["devices"].values():
        for _, s, d in dev["ops"]:
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("no operation ran on a device in this trace")
    return min(starts), max(ends)


def idle_gaps(merged: list, window: tuple[float, float]) -> list:
    t0, t1 = window
    gaps, cursor = [], t0
    for s, e in merged:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t1 > cursor:
        gaps.append((cursor, t1))
    return gaps


def dispatch_thread(host: dict) -> str | None:
    """The host thread that launches device programs: the Python thread
    (its events are interpreter frames, ``$file:line name``) with the most
    ``PjitFunction`` calls."""
    best, best_n = None, 0
    for name, events in host.items():
        n = sum(1 for e in events if e[0].startswith("PjitFunction"))
        if n > best_n and any(e[0].startswith("$") for e in events):
            best, best_n = name, n
    return best


def attribute_gaps(gaps: list, events: list, longest: int = 200) -> dict:
    """Idle seconds of the ``longest`` gaps, by the innermost host event
    running on the dispatch thread at each gap's midpoint (the event that
    started last among those covering it); ``(no host event)`` where none
    is.  The shorter gaps are summed under ``(shorter gaps)``."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    starts = [e[1] for e in order]
    ranked = sorted(gaps, key=lambda g: g[0] - g[1])
    out: dict[str, float] = {}
    rest = sum(b - a for a, b in ranked[longest:])
    if rest > 0:
        out["(shorter gaps)"] = rest
    for a, b in ranked[:longest]:
        mid = (a + b) / 2
        name = "(no host event)"
        i = bisect.bisect_right(starts, mid) - 1
        floor = max(i - 20000, -1)
        while i > floor:
            n, s, d = order[i]
            if s + d >= mid:
                name = n
                break
            i -= 1
        name = re.sub(r"[^A-Za-z0-9_.:<>()-]", "_", name)
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summarize(trace: dict) -> dict:
    """``busy_s`` (mean over device planes), ``window_s``, and the
    ``breakdown`` the result line carries."""
    window = window_of(trace)
    busy_s, ops_total = [], {}
    first_gaps = None
    for name in sorted(trace["devices"]):
        dev = trace["devices"][name]
        seconds, merged = busy(dev["ops"])
        busy_s.append(seconds)
        for fam, s in op_seconds(dev["ops"]).items():
            ops_total[fam] = ops_total.get(fam, 0.0) + s
        if first_gaps is None:
            first_gaps = idle_gaps(merged, window)
    n = len(busy_s)
    thread = dispatch_thread(trace["host"])
    by_host = attribute_gaps(first_gaps or [],
                             trace["host"].get(thread, []))
    return {
        "busy_s": sum(busy_s) / n,
        "window_s": window[1] - window[0],
        "breakdown": {
            "device_ops": top({k: v / n for k, v in ops_total.items()}),
            "idle_gaps": top(by_host),
        },
    }
