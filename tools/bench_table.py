#!/usr/bin/env python
"""Render BENCH_RESULTS/*.json into one markdown evidence table.

Usage::

    python tools/bench_table.py [BENCH_RESULTS] [--latest-only]

Groups rows by metric, sorts by timestamp, and prints the fields the
round verdicts audit: value, vs_baseline, both MFU accountings, and the
config knobs (batch/seq/remat/attn/xent/steps_per_call).  ``--latest-only``
keeps only the newest row per distinct config — the shape PARITY.md's
"Recorded evidence" section quotes.
"""

from __future__ import annotations

import argparse
import glob
import json
import os


CONFIG_KEYS = ("global_batch", "seq", "remat", "attn_impl", "xent_impl",
               "steps_per_call", "image_size", "n_chips")


def load_rows(directory: str) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        base = os.path.basename(path)
        if base.startswith("."):
            continue
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(d, dict) and ("metric" in d):
            d["_file"] = base
            rows.append(d)
    return rows


def config_sig(row: dict) -> tuple:
    return tuple((k, row.get(k)) for k in CONFIG_KEYS)


def fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:,.4g}" if abs(v) < 10 else f"{v:,.1f}"
    return str(v)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("directory", nargs="?", default="BENCH_RESULTS")
    ap.add_argument("--latest-only", action="store_true")
    args = ap.parse_args()

    rows = load_rows(args.directory)
    by_metric: dict[str, list[dict]] = {}
    for r in rows:
        by_metric.setdefault(r["metric"], []).append(r)

    for metric in sorted(by_metric):
        group = sorted(by_metric[metric], key=lambda r: r.get("timestamp", ""))
        if args.latest_only:
            latest: dict[tuple, dict] = {}
            for r in group:
                latest[config_sig(r)] = r
            group = sorted(latest.values(),
                           key=lambda r: r.get("timestamp", ""))
        print(f"\n### {metric}\n")
        print("| timestamp | value | vs_baseline | mfu_analytic | mfu_xla "
              "| config | file |")
        print("|---|---|---|---|---|---|---|")
        for r in group:
            cfg = " ".join(
                f"{k.replace('global_', '')}={r[k]}"
                for k in CONFIG_KEYS
                if r.get(k) not in (None, "")
            )
            err = r.get("error")
            val = f"ERR:{err}" if err else fmt(r.get("value"))
            print(
                f"| {r.get('timestamp', '?')} | {val} "
                f"| {fmt(r.get('vs_baseline'))} "
                f"| {fmt(r.get('mfu_analytic'))} "
                f"| {fmt(r.get('mfu_xla_cost'))} "
                f"| {cfg} | {r['_file']} |"
            )


if __name__ == "__main__":
    main()
