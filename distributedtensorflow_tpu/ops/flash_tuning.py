"""On-disk autotune cache for the flash-attention block tiling.

``ops/flash_attention.py`` picks its (block_q, block_k) tiling from a
constant plus a divide-the-sequence fallback chain — one number for every
shape.  This module puts a **runtime-consulted cache** before it: a JSON file
keyed on (shape, dtype, platform) whose entries are produced either by
``tools/autotune_flash.py``'s timing microbench sweep or from a
CaptureEngine XPlane, and looked up by the kernel at trace time.

Resolution order inside the kernel (``flash_attention._resolve_blocks``):

1. explicit ``block_q=`` / ``block_k=`` arguments (the sweep driver);
2. ``DTFT_FLASH_BLOCK_Q/K`` env overrides (the on-chip A/B knob);
3. a cache entry matching (platform, dtype, seq, depth) and the kernel
   form (``layout``) — preferring an exact (batch, heads) match — whose
   blocks divide the sequence;
4. the retuned default chain.

Cache location: ``DTFT_FLASH_TUNE_CACHE`` env var, else
``flash_blocks.json`` beside this module — a file git tracks, so the
tiling a run uses is a function of the checkout and never of what some
other run left in a home directory.  No such file is committed today:
the default chain decides.  Set the env var to ``off`` to disable
consultation entirely (tests pin tilings that way).  The file is read at
most once per mtime (an in-process memo), so the per-trace cost is a
couple of stat calls.

Schema (validated by ``tools/check_metrics_schema.py``)::

    {"version": 1,
     "entries": [{"platform": "tpu", "dtype": "bfloat16",
                  "batch": 16, "heads": 12, "seq": 4096, "depth": 64,
                  "block_q": 1024, "block_k": 1024,
                  "layout": "bhsd",            # optional; the default
                  "ms": 17.1, "source": "sweep",
                  "timestamp": "2026-08-03T00:00:00"}, ...]}

``store()`` replaces any prior entry with the same key (newest
measurement wins) and writes atomically (tmp + rename).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any

logger = logging.getLogger("distributedtensorflow_tpu")

__all__ = [
    "cache_path",
    "load",
    "lookup",
    "store",
    "clear",
    "validate_doc",
    "SOURCES",
]

#: Provenance tags an entry may carry.
SOURCES = ("sweep", "xplane")

_ENV = "DTFT_FLASH_TUNE_CACHE"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "flash_blocks.json"
)

_memo_lock = threading.Lock()
_memo: dict[str, tuple[float, dict]] = {}  # path -> (mtime, doc)


def cache_path(path: str | None = None) -> str | None:
    """The effective cache file path; None when consultation is off."""
    if path is not None:
        return path
    env = os.environ.get(_ENV)
    if env == "off":
        return None
    return env or _DEFAULT


def load(path: str | None = None) -> dict:
    """The parsed cache document ({} when absent/off/corrupt) — mtime-
    memoized so the kernel's per-trace consult is cheap."""
    p = cache_path(path)
    if p is None:
        return {}
    try:
        mtime = os.stat(p).st_mtime
    except OSError:
        return {}
    with _memo_lock:
        hit = _memo.get(p)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        with open(p) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        logger.warning("flash tuning cache %s unreadable (%s); ignoring",
                       p, e)
        doc = {}
    if not isinstance(doc, dict):
        doc = {}
    with _memo_lock:
        _memo[p] = (mtime, doc)
    return doc


#: The kernel form an entry without ``layout`` was recorded for: the
#: (B, H, S, D) kernels, the only form before there were two.
DEFAULT_LAYOUT = "bhsd"


def _entry_key(e: dict) -> tuple:
    return (e.get("platform"), e.get("dtype"), e.get("batch"),
            e.get("heads"), e.get("seq"), e.get("depth"),
            e.get("layout", DEFAULT_LAYOUT))


def lookup(
    *,
    platform: str,
    dtype: str,
    seq: int,
    depth: int,
    batch: int | None = None,
    heads: int | None = None,
    layout: str = DEFAULT_LAYOUT,
    path: str | None = None,
) -> tuple[int, int] | None:
    """The cached (block_q, block_k) for a shape, or None.

    Matching is on (platform, dtype, seq, depth) and the kernel form
    (``layout``: ``"bhsd"``, or ``"qkv_tiles"`` for the kernels that read
    the fused projection; they spend their VMEM differently, so one's
    best tiling is no prior for the other); an entry that also
    matches (batch, heads) exactly beats a shape-generic one (batch and
    heads only scale the grid's embarrassingly-parallel axes, so a
    different-batch measurement of the same (seq, depth) is still the
    best available prior).  Entries whose blocks don't divide ``seq``
    are skipped — a corrupt or hand-edited cache must never turn into a
    Mosaic compile error."""
    doc = load(path)
    entries = doc.get("entries")
    if not isinstance(entries, list):
        return None
    best = None
    best_rank = -1
    for e in entries:
        if not isinstance(e, dict):
            continue
        if (e.get("platform") != platform or e.get("dtype") != dtype
                or e.get("seq") != seq or e.get("depth") != depth
                or e.get("layout", DEFAULT_LAYOUT) != layout):
            continue
        bq, bk = e.get("block_q"), e.get("block_k")
        if not (isinstance(bq, int) and isinstance(bk, int)
                and bq > 0 and bk > 0 and seq % bq == 0 and seq % bk == 0):
            continue
        rank = int(e.get("batch") == batch) + int(e.get("heads") == heads)
        if rank > best_rank:
            best, best_rank = (bq, bk), rank
    return best


def store(entry: dict[str, Any], path: str | None = None) -> str:
    """Insert/replace one measurement; returns the file path written.

    Required keys: platform, dtype, seq, depth, block_q, block_k.
    ``source`` defaults to "sweep"; a timestamp is stamped when absent.
    Atomic write; an existing entry with the same
    (platform, dtype, batch, heads, seq, depth, layout) key is replaced.
    """
    p = cache_path(path)
    if p is None:
        raise ValueError(
            f"flash tuning cache is disabled ({_ENV}=off); pass an "
            "explicit path"
        )
    missing = [k for k in ("platform", "dtype", "seq", "depth",
                           "block_q", "block_k") if entry.get(k) is None]
    if missing:
        raise ValueError(f"cache entry missing keys: {missing}")
    if entry["seq"] % entry["block_q"] or entry["seq"] % entry["block_k"]:
        raise ValueError(
            f"blocks ({entry['block_q']}, {entry['block_k']}) do not "
            f"divide seq {entry['seq']}"
        )
    entry = dict(entry)
    entry.setdefault("source", "sweep")
    if entry["source"] not in SOURCES:
        raise ValueError(
            f"source {entry['source']!r} not in {SOURCES}"
        )
    entry.setdefault("timestamp", time.strftime("%Y-%m-%dT%H:%M:%S"))
    doc = load(p)
    entries = [
        e for e in doc.get("entries", [])
        if isinstance(e, dict) and _entry_key(e) != _entry_key(entry)
    ]
    entries.append(entry)
    doc = {"version": 1, "entries": entries}
    os.makedirs(os.path.dirname(os.path.abspath(p)), exist_ok=True)
    tmp = f"{p}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, p)
    with _memo_lock:
        _memo.pop(p, None)
    return p


def clear(path: str | None = None) -> None:
    """Invalidate: remove the cache file (and its memo entry)."""
    p = cache_path(path)
    if p is None:
        return
    try:
        os.unlink(p)
    except FileNotFoundError:
        pass
    with _memo_lock:
        _memo.pop(p, None)


def validate_doc(doc: Any) -> list[str]:
    """Schema errors for a parsed cache document (shared logic for tests;
    ``tools/check_metrics_schema.py`` carries its own stdlib copy)."""
    errors: list[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"]
    if doc.get("version") != 1:
        errors.append(f"version {doc.get('version')!r} != 1")
    entries = doc.get("entries")
    if not isinstance(entries, list):
        return errors + ["'entries' is missing or not a list"]
    for i, e in enumerate(entries):
        where = f"entries[{i}]"
        if not isinstance(e, dict):
            errors.append(f"{where}: not an object")
            continue
        for k in ("platform", "dtype"):
            if not isinstance(e.get(k), str) or not e.get(k):
                errors.append(f"{where}: {k!r} is not a non-empty string")
        for k in ("seq", "depth", "block_q", "block_k"):
            v = e.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                errors.append(f"{where}: {k!r} {v!r} is not a positive int")
        if (isinstance(e.get("seq"), int) and isinstance(e.get("block_q"), int)
                and isinstance(e.get("block_k"), int)
                and e["block_q"] > 0 and e["block_k"] > 0):
            if e["seq"] % e["block_q"] or e["seq"] % e["block_k"]:
                errors.append(
                    f"{where}: blocks ({e['block_q']}, {e['block_k']}) do "
                    f"not divide seq {e['seq']}"
                )
        if e.get("source") is not None and e["source"] not in SOURCES:
            errors.append(
                f"{where}: source {e['source']!r} not in {SOURCES}"
            )
        ms = e.get("ms")
        if ms is not None and (
            isinstance(ms, bool) or not isinstance(ms, (int, float))
            or not (ms >= 0)
        ):
            errors.append(f"{where}: 'ms' {ms!r} is not a non-negative "
                          "number")
    return errors
