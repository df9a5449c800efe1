"""Reader ``trace_scope``: device time of one compiled program by the
``jax.named_scope`` (or flax module) its operations were traced under.

The plain structure ``trace_reduce.load_xplane`` keeps only name, start
and duration, and ``jax.profiler.ProfileData`` does not hand out what the
profiler stores once per operation (the event *metadata*: on a TPU the HLO
``op_name``, ``jit(decode)/jit(main)/h3/kv_write/scatter``, is there and
not on the event).  So this reader reads the ``.xplane.pb`` in
``trace_dir`` itself: a protobuf wire walk over the first device plane
(``tsl/profiler/protobuf/xplane.proto``; field numbers below), nothing
else of the file is decoded.  ``scope_path`` strips what is not a scope:
the ``jit(...)`` frames, the primitive at the end, and the transform
wrappers around a scope (``transpose(jvp(loss_head))`` is ``loss_head``).

args: ``program`` (regex on the modules line; only whole executions
count), ``scope`` (regex searched in the scope path; ``""`` matches every
operation, ``null`` only those with no scope at all), ``stat``: ``ms``
(default; self milliseconds per execution, nested operations not counted
twice) or ``pct`` (the same seconds as a share of the program's).  None
where the trace has no such program or its operations carry no path at all
(a program without scopes, an older runtime), so the line leaves the
metric out.
"""

import re
import struct

import trace_reduce

#: the metadata stat that holds the name-stack path (TPU runtime, jax 0.9)
PATH_STAT = "tf_op"
_FRAME = re.compile(r"^p?jit\(.*\)$")
_WRAP = re.compile(r"[A-Za-z_][\w.]*\(|\)")


def scope_path(op_name: str) -> str:
    """``jit(f)/jit(main)/transpose(jvp(M))/h3/attn/dot_general`` ->
    ``M/h3/attn``; ``jit(f)/jit(main)/convert_element_type`` -> ``""``."""
    parts = [p for p in op_name.split("/") if p and not _FRAME.match(p)]
    return "/".join(_WRAP.sub("", p) for p in parts[:-1]).strip("/")


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: memoryview):
    """``(field number, wire type, value)`` of one protobuf message:
    varints as int, length-delimited as memoryview, fixed as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            val = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield num, wire, val


def _map_entry(buf):
    key, value = 0, None
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _stat(buf, stat_names: dict) -> tuple[str, object]:
    """One XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5,
    bytes=6, ref=7 (a stat_metadata id whose name is the value)."""
    name, value = "", None
    for num, _, val in _fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num in (3, 4):
            value = val
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def device_plane(path: str) -> dict | None:
    """The first ``/device:TPU:N`` plane of the file: ``{"name", "lines":
    {line name: [(metadata id, start_s, dur_s), ...]}, "metadata": {id:
    {"name", "stats": {...}}}}``.  XSpace.planes=1; XPlane: name=2,
    lines=3, event_metadata=4, stat_metadata=5; XLine: name=2,
    timestamp_ns=3, events=4; XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3; XEventMetadata: id=1, name=2, stats=5;
    XStatMetadata: id=1, name=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    best = None
    for num, wire, plane in _fields(space):
        if num != 1 or wire != 2:
            continue
        name = next((bytes(v).decode() for n, w, v in _fields(plane)
                     if n == 2 and w == 2), "")
        if trace_reduce.DEVICE_PLANE.match(name) and (
                best is None or name < best[0]):
            best = (name, plane)
    if best is None:
        return None
    name, plane = best
    stat_names, raw_meta, raw_lines = {}, [], []
    for num, wire, val in _fields(plane):
        if wire != 2:
            continue
        if num == 5:
            key, meta = _map_entry(val)
            stat_names[key] = next(
                (bytes(v).decode() for n, w, v in _fields(meta)
                 if n == 2 and w == 2), "")
        elif num == 4:
            raw_meta.append(val)
        elif num == 3:
            raw_lines.append(val)
    metadata = {}
    for entry in raw_meta:
        key, meta = _map_entry(entry)
        item = {"name": "", "stats": {}}
        for n, w, v in _fields(meta):
            if n == 2 and w == 2:
                item["name"] = bytes(v).decode("utf-8", "replace")
            elif n == 5 and w == 2:
                stat, value = _stat(v, stat_names)
                item["stats"][stat] = value
        metadata[key] = item
    lines = {}
    for line in raw_lines:
        line_name, t0_ns, events = "", 0, []
        for n, w, v in _fields(line):
            if n == 2 and w == 2:
                line_name = bytes(v).decode()
            elif n == 3 and w == 0:
                t0_ns = v
            elif n == 4 and w == 2:
                meta_id = offset_ps = dur_ps = 0
                for en, ew, ev in _fields(v):
                    if ew != 0:
                        continue
                    if en == 1:
                        meta_id = ev
                    elif en == 2:
                        offset_ps = ev
                    elif en == 3:
                        dur_ps = ev
                events.append((meta_id, offset_ps, dur_ps))
        lines[line_name] = [(m, t0_ns * 1e-9 + o * 1e-12, d * 1e-12)
                            for m, o, d in events]
    return {"name": name, "lines": lines, "metadata": metadata}


def load(path: str) -> dict:
    """``{"ops": [[name, start_s, dur_s, scope path or None], ...],
    "modules": [[name, start_s, dur_s], ...]}`` of the first device."""
    plane = device_plane(path)
    out = {"ops": [], "modules": []}
    if plane is None:
        return out
    meta = plane["metadata"]
    paths = {}
    for key, item in meta.items():
        raw = item["stats"].get(PATH_STAT)
        paths[key] = None if raw is None else scope_path(str(raw))
    out["modules"] = [[meta[m]["name"], s, d] for m, s, d in
                      plane["lines"].get(trace_reduce.MODULES_LINE, [])]
    # an operation's name is its whole HLO line: keep `%copy.180`
    out["ops"] = [[meta[m]["name"].split(" = ")[0], s, d, paths[m]]
                  for m, s, d in
                  plane["lines"].get(trace_reduce.OPS_LINE, [])]
    return out


def read(ctx: dict, args: dict):
    scoped = ctx.get("scoped")
    if scoped is None:
        path = trace_reduce.find_xplane(ctx.get("trace_dir") or "")
        if not path:
            return None
        scoped = load(path)
    runs = trace_reduce.whole_executions(scoped["modules"], args["program"])
    if not runs:
        return None
    inside = [op for op in scoped["ops"]
              if any(a <= op[1] < b for a, b in runs)]
    if not any(op[3] for op in inside):
        return None                     # a program that names no scope
    paths = {(n, s): p for n, s, _, p in inside}
    scope = args.get("scope")
    rx = None if scope is None else re.compile(scope)
    hit = total = 0.0
    for name, start, _, own in trace_reduce.self_times(
            [op[:3] for op in inside]):
        total += own
        path = paths[(name, start)] or ""
        if rx.search(path) if rx is not None else not path:
            hit += own
    if args.get("stat", "ms") == "pct":
        return 100.0 * hit / total if total > 0 else None
    return 1e3 * hit / len(runs)
