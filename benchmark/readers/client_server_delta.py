"""Reader ``client_server_delta``: median over the window's completed
requests of (client's time from send to first token) minus (server's own
``ttft_s`` in ``requests.jsonl``), joined on the ``trace_id`` the
generator sends: what the HTTP front end and the loopback add.
args: ``file``, ``scale``."""

import os
import statistics

import harness


def read(ctx: dict, args: dict):
    rows = harness.read_jsonl(os.path.join(ctx["out"], args["file"]))
    server = {r["trace_id"]: r["ttft_s"] for r in rows
              if r.get("status") == "ok" and "ttft_s" in r}
    deltas = []
    for r in ctx.get("logs", []):
        if (r["due"] >= 0 and r["token_times"] and r["sent"] is not None
                and r["id"] in server):
            deltas.append(r["token_times"][0] - r["sent"] - server[r["id"]])
    if not deltas:
        return None
    return statistics.median(deltas) * args.get("scale", 1.0)
