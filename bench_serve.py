#!/usr/bin/env python
"""Offered-load + prefix-caching + interference benchmarks for the
serving engine (ISSUE 6 / ISSUE 14).

bench_generate.py measures the raw decode loop; this measures the SYSTEM —
the continuous-batching engine under request traffic, driving
`serve.Engine` directly (no HTTP, so the numbers are the scheduler's, not
the socket stack's).  Three sweeps, selectable via ``BENCH_SERVE_MODE``
(``all`` default, or ``load`` / ``prefix`` / ``interference``):

- **offered load** (ISSUE 6): a Poisson-ish arrival sweep; per rate,
  request-level SLOs (TTFT / TPOT / e2e p50+p99), batch occupancy,
  rejects, delivered tokens/sec, plus the per-phase latency shares from
  the engine's exclusive attribution fields (ISSUE 16:
  ``queue_share_mean`` / ``prefill_share_mean`` / ``decode_share_mean``
  — mean fraction of each request's e2e spent queued, in prefill
  compute + interference stall, and in decode compute + speculation).
- **shared prefix** (ISSUE 14): N prompts sharing a long common header
  (the system-prompt / few-shot pattern), offered at saturation with
  ``prefix_cache`` OFF vs ON — the ON arm maps the header's KV blocks
  refcount+1 instead of re-prefilling them, so the headline is the
  tokens/sec speedup at the same offered load.
- **long-prompt interference** (ISSUE 14): victims in steady decode, one
  intruder with an N×-length prompt arriving mid-decode.  Without a
  prefill budget the intruder's whole chunked prefill runs between two
  decode steps and every victim's inter-token latency eats it (stall
  scales with the intruder's prompt); with ``--prefill-budget`` the
  scheduler interleaves at most one budget's worth of chunks per decode
  step, so victim TPOT/ITL p99 is bounded by the budget, independent of
  the intruder length.
- **decode fast path** (ISSUE 15, ``spec`` / ``--spec-sweep``): A/B/C
  arms — host sampling vs ``fused_sampling`` vs fused + ``speculate K``
  — over a *repetitive-suffix* workload (periodic prompts, greedy: the
  n-gram drafter hits, bursts amortize dispatches) and a *random-text*
  workload (uniform prompts, seeded temperature sampling: the drafter
  whiffs and speculation must cost ~nothing because draft-less
  iterations run the one-token fused program).  Per arm: tokens/sec,
  TPOT p50/p99, draft acceptance rate, mean tokens per decode step PER
  SLOT (1.0 without speculation, up to K+1 on accepted bursts), and
  dispatches per decode step (decode program executions + host
  sampling rounds, the iterations that fetched the logits for a request
  with temperature > 0: the per-token round-trip count each running
  request experiences — 2 where the host samples, 1 where the token comes
  off the device with the step: fused, or the one-token program's arg-max
  in greedy traffic).

Evidence discipline (same contract as bench_generate.py): headline
operating points are the MEDIAN OF 3 independent trials with relative
spread recorded; one JSON document on stdout (one line).  Exits non-zero
without a TPU: every row here is a time or a rate of the device.

Knobs (env): ``BENCH_SERVE_RATES`` (comma req/s, default "2,8,32"),
``BENCH_SERVE_N`` (requests per point, default 32), ``BENCH_SERVE_NEW``
(max_new_tokens, default 32), ``BENCH_SERVE_PROMPT`` (max prompt len,
default 64), ``BENCH_SERVE_SLOTS`` (default 8), ``BENCH_SERVE_MODEL``
(``small``/``tiny``), ``BENCH_SERVE_HEADER`` (shared header tokens,
default 256), ``BENCH_SERVE_BUDGET`` (prefill budget tokens, default 2
chunks), ``BENCH_SERVE_CTX`` (serving max_context, default 1024 — the
decode gather scales with it, so slow boxes shrink it),
``BENCH_SERVE_SPEC_K`` (draft length, default 4) /
``BENCH_SERVE_SPEC_PROMPT`` (spec-sweep prompt tokens; ``--spec-sweep``
on argv == ``BENCH_SERVE_MODE=spec``), and ``BENCH_SERVE_TEST=1`` tiny
wiring check (tiny model, 2 slots, few requests, nothing persisted).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import numpy as np

from bench_common import persist_result, start
from distributedtensorflow_tpu.serve import QueueFullError


def _percentile(vals, q):
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, max(0, int(round(q * len(s))) - 1))]


def _median_of(trials: list[dict], key: str) -> tuple[dict, float]:
    """The trial whose ``key`` is the median, plus the relative spread."""
    vals = [t[key] for t in trials]
    med = statistics.median(vals)
    pick = dict(sorted(trials, key=lambda t: t[key])[len(trials) // 2])
    pick["spread"] = round(
        (max(vals) - min(vals)) / med, 4) if med else 0.0
    pick["trials"] = len(trials)
    return pick, med


def _run_point(engine, *, rate: float, n: int, new: int, prompt_max: int,
               vocab: int, seed: int) -> dict:
    """Offer ``n`` requests at ``rate`` req/s; block until all terminal."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    reqs, rejected = [], 0
    t0 = time.perf_counter()
    for i in range(n):
        time.sleep(float(gaps[i]))
        prompt = rng.integers(0, vocab, size=int(rng.integers(4, prompt_max)))
        try:
            reqs.append(engine.submit(list(map(int, prompt)),
                                      max_new_tokens=new))
        except QueueFullError:  # backpressure is a data point; any other
            rejected += 1        # submit error must fail the bench loudly
    for r in reqs:
        r.wait()
    makespan = time.perf_counter() - t0
    ok = [r for r in reqs if r.status == "ok"]
    tokens = sum(len(r.tokens) for r in ok)
    ttft = [r.ttft_s for r in ok]
    tpot = [r.tpot_s for r in ok if len(r.tokens) > 1]
    e2e = [r.e2e_s for r in ok]
    occ = [r.occ_max for r in ok if r.occ_steps]
    out = {
        "rate_rps": rate,
        "requests": n,
        "ok": len(ok),
        "rejected": rejected,
        "tokens_per_sec": round(tokens / makespan, 1) if makespan else 0.0,
        "ttft_p50_s": round(_percentile(ttft, 0.50), 4),
        "ttft_p99_s": round(_percentile(ttft, 0.99), 4),
        "tpot_p50_s": round(_percentile(tpot, 0.50), 4),
        "tpot_p99_s": round(_percentile(tpot, 0.99), 4),
        "e2e_p50_s": round(_percentile(e2e, 0.50), 4),
        "e2e_p99_s": round(_percentile(e2e, 0.99), 4),
        "occupancy_mean": (round(statistics.fmean(
            r.occ_sum / r.occ_steps for r in ok if r.occ_steps), 2)
            if any(r.occ_steps for r in ok) else 0.0),
        "occupancy_max": max(occ, default=0),
    }
    # per-phase latency shares from the engine's exclusive attribution
    # fields (ISSUE 16): where each request's e2e went, averaged over ok
    # requests — queue wait vs prefill (compute + interference stall) vs
    # decode (compute + speculation window).
    attr_ok = [r for r in ok if r.e2e_s > 0]
    if attr_ok:
        out["queue_share_mean"] = round(statistics.fmean(
            max(r.t_admit - r.t_submit, 0.0) / r.e2e_s for r in attr_ok
        ), 4)
        out["prefill_share_mean"] = round(statistics.fmean(
            (r.attr_prefill_s + r.attr_stall_s) / r.e2e_s for r in attr_ok
        ), 4)
        out["decode_share_mean"] = round(statistics.fmean(
            (r.attr_decode_s + r.attr_spec_s) / r.e2e_s for r in attr_ok
        ), 4)
    return out


def _offered_load_sweep(make_engine, *, rates, n, new, prompt_max,
                        vocab) -> dict:
    engine = make_engine()
    engine.generate(list(range(4)), max_new_tokens=2, timeout=300)  # warm
    points = []
    head_rate = rates[-1]  # the highest offered load is the headline
    head_pts = []
    for rate in rates:
        trials = 3 if rate == head_rate else 1
        for t in range(trials):
            pt = _run_point(
                engine, rate=rate, n=n, new=new, prompt_max=prompt_max,
                vocab=vocab, seed=17 * t + int(rate),
            )
            (head_pts if rate == head_rate else points).append(pt)
    head, med = _median_of(head_pts, "tokens_per_sec")
    points.append(head)
    engine.stop()
    return {"value": med, "headline": head, "curve": points}


def _shared_prefix_sweep(make_engine, *, header: int, tail_max: int,
                         n: int, new: int, vocab: int) -> dict:
    """N prompts sharing a ``header``-token prefix, offered at saturation
    (all submitted at once), prefix cache OFF vs ON.  The ON engine's
    index is pre-warmed with one pass so every timed trial measures the
    steady state a long-running server sits in."""
    rng = np.random.default_rng(7)
    hdr = list(map(int, rng.integers(0, vocab, size=header)))
    prompts = [
        hdr + list(map(int, rng.integers(
            0, vocab, size=int(rng.integers(1, tail_max + 1)))))
        for _ in range(n)
    ]
    arms = {}
    for on in (False, True):
        engine = make_engine(prefix_cache=on)
        engine.generate(list(range(4)), max_new_tokens=2, timeout=300)
        warm = [engine.submit(p, max_new_tokens=2) for p in prompts[:2]]
        for r in warm:
            r.wait(600)
        trials = []
        for t in range(3):
            t0 = time.perf_counter()
            reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
            for r in reqs:
                r.wait(600)
            makespan = time.perf_counter() - t0
            ok = [r for r in reqs if r.status == "ok"]
            trials.append({
                "tokens_per_sec": round(
                    sum(len(r.tokens) for r in ok) / makespan, 1),
                "ok": len(ok),
                "ttft_p50_s": round(
                    _percentile([r.ttft_s for r in ok], 0.50), 4),
                "ttft_p99_s": round(
                    _percentile([r.ttft_s for r in ok], 0.99), 4),
                "e2e_p99_s": round(
                    _percentile([r.e2e_s for r in ok], 0.99), 4),
                "cached_prefix_tokens": sum(
                    r.cached_prefix_tokens for r in ok),
                "prompt_tokens": sum(len(r.prompt) for r in ok),
            })
        head, med = _median_of(trials, "tokens_per_sec")
        st = engine.state()
        head["prefix_hit_rate"] = st["kv"]["prefix_hit_rate"]
        head["cached_token_share"] = round(
            head["cached_prefix_tokens"] / head["prompt_tokens"], 4
        ) if head["prompt_tokens"] else 0.0
        engine.stop()
        arms["on" if on else "off"] = {"tokens_per_sec": med, **head}
    speedup = (arms["on"]["tokens_per_sec"]
               / arms["off"]["tokens_per_sec"]
               if arms["off"]["tokens_per_sec"] else 0.0)
    return {
        "header_tokens": header,
        "tail_max_tokens": tail_max,
        "requests": n,
        "max_new_tokens": new,
        "off": arms["off"],
        "on": arms["on"],
        "speedup": round(speedup, 3),
    }


def _interference_sweep(make_engine, *, victims: int, victim_prompt: int,
                        victim_new: int, mults, budget: int,
                        vocab: int) -> dict:
    """Victims in steady decode; one ``mult``×-length intruder prompt
    arrives mid-decode.  Reports victim TPOT p99 and worst inter-token
    stall, unbudgeted vs budgeted — the budgeted stall must be flat in
    the intruder length (the acceptance claim)."""
    rng = np.random.default_rng(11)
    vprompts = [
        list(map(int, rng.integers(0, vocab, size=victim_prompt)))
        for _ in range(victims)
    ]
    # ONE engine per budget arm, reused across mults and trials (no
    # state crosses trials: the prefix cache is off and the pool drains
    # when every request terminates) — a fresh engine per trial would
    # re-trace the three serving programs 12x for identical shapes.
    engines = {}
    for b in (None, budget):
        engines[b] = make_engine(prefill_budget=b)
        engines[b].generate(list(range(4)), max_new_tokens=2, timeout=300)
    rows = []
    for mult in mults:
        iprompt = list(map(int, rng.integers(
            0, vocab, size=victim_prompt * mult)))
        for b in (None, budget):
            engine = engines[b]
            trials = []
            for t in range(3):
                vs = [engine.submit(p, max_new_tokens=victim_new)
                      for p in vprompts]
                deadline = time.time() + 300
                while (any(v.t_first_token == 0.0 for v in vs)
                       and time.time() < deadline):
                    time.sleep(0.002)  # victims reach steady decode
                intruder = engine.submit(iprompt, max_new_tokens=2)
                for r in vs + [intruder]:
                    r.wait(600)
                ok = [v for v in vs if v.status == "ok"]
                trials.append({
                    "victim_tpot_p99_s": round(
                        _percentile([v.tpot_s for v in ok], 0.99), 4),
                    "victim_itl_max_s": round(
                        max((v.itl_max_s for v in ok), default=0.0), 4),
                    "intruder_ttft_s": round(intruder.ttft_s, 4),
                    "victims_ok": len(ok),
                })
            head, _ = _median_of(trials, "victim_itl_max_s")
            rows.append({
                "intruder_mult": mult,
                "intruder_prompt_tokens": len(iprompt),
                "prefill_budget": b or 0,
                **head,
            })
    for engine in engines.values():
        engine.stop()
    return {
        "victims": victims,
        "victim_prompt_tokens": victim_prompt,
        "victim_new_tokens": victim_new,
        "budget_tokens": budget,
        "rows": rows,
    }


def _spec_sweep(make_engine, *, n: int, new: int, prompt_len: int,
                vocab: int, speculate: int) -> dict:
    """Host vs fused vs fused+speculate over a repetitive-suffix and a
    random-text workload (saturation offered load, counters per-trial
    deltas so one engine per arm serves every trial)."""
    rng = np.random.default_rng(23)
    period = 8
    base = list(map(int, rng.integers(0, vocab, size=period)))
    rep_prompts = []
    for _ in range(n):
        head = list(map(int, rng.integers(0, vocab, size=4)))
        body = (base * (prompt_len // period + 2))[: prompt_len - len(head)]
        rep_prompts.append(head + body)
    rand_prompts = [
        list(map(int, rng.integers(0, vocab, size=prompt_len)))
        for _ in range(n)
    ]
    workloads = {
        # greedy: deterministic, the drafter's best case
        "repetitive": (rep_prompts, {}),
        # seeded sampling over uniform prompts: the drafter's worst case
        "random": (rand_prompts, {"temperature": 1.0, "top_k": 64}),
    }
    arm_cfg = {
        "host": {},
        "fused": {"fused_sampling": True},
        "spec": {"fused_sampling": True, "speculate": speculate},
    }
    out = {"speculate": speculate, "requests": n, "max_new_tokens": new,
           "prompt_tokens": prompt_len,
           "workloads": {wname: {} for wname in workloads}}
    # Arm-outer: ONE engine (one paged KV pool + compiled program set)
    # resident at a time — three simultaneous gpt_small pools would
    # triple peak host memory for nothing, since the counters are
    # per-trial deltas anyway.
    for aname, akw in arm_cfg.items():
        engine = make_engine(**akw)
        # Warm with one prompt from EACH workload: a periodic prompt
        # drafts, so the spec arm's T=K+1 verify program compiles here
        # instead of inside trial 1.
        engine.generate(rep_prompts[0], max_new_tokens=new, timeout=300)
        engine.generate(rand_prompts[0], max_new_tokens=new,
                        temperature=1.0, top_k=64, timeout=300)
        for wname, (prompts, skw) in workloads.items():
            trials = []
            for _ in range(3):
                c0 = dict(engine.counters)
                steps0 = engine.decode_steps
                t0 = time.perf_counter()
                reqs = [engine.submit(p, max_new_tokens=new, seed=j, **skw)
                        for j, p in enumerate(prompts)]
                for r in reqs:
                    r.wait(600)
                makespan = time.perf_counter() - t0
                ok = [r for r in reqs if r.status == "ok"]
                dc = {k: engine.counters[k] - c0[k] for k in c0}
                steps = engine.decode_steps - steps0
                tokens = sum(len(r.tokens) for r in ok)
                tpot = [r.tpot_s for r in ok if len(r.tokens) > 1]
                dispatches = dc["decode_dispatches"] + dc["host_sample_rounds"]
                trials.append({
                    "tokens_per_sec": round(tokens / makespan, 1)
                    if makespan else 0.0,
                    "ok": len(ok),
                    "tpot_p50_s": round(_percentile(tpot, 0.50), 4),
                    "tpot_p99_s": round(_percentile(tpot, 0.99), 4),
                    "drafted": dc["spec_drafted"],
                    "accepted": dc["spec_accepted"],
                    "acceptance_rate": round(
                        dc["spec_accepted"] / dc["spec_drafted"], 4)
                    if dc["spec_drafted"] else 0.0,
                    # per SLOT (decode_tokens over slot-steps): 1.0
                    # without speculation, matching the engine's
                    # tokens_per_step scalar and histogram
                    "tokens_per_decode_step": round(
                        dc["decode_tokens"] / dc["slot_steps"], 3)
                    if dc["slot_steps"] else 0.0,
                    # per decode step every running slot commits >= 1
                    # token, so this is the per-token round-trip count a
                    # request experiences: 2 where the host samples
                    # (program + logits pull/sample/feed-back), 1 where
                    # the token comes with the step (fused; greedy)
                    "dispatches_per_step": round(dispatches / steps, 3)
                    if steps else 0.0,
                })
            head, med = _median_of(trials, "tokens_per_sec")
            out["workloads"][wname][aname] = {"tokens_per_sec": med, **head}
        engine.stop()
    rep, rnd = out["workloads"]["repetitive"], out["workloads"]["random"]

    def _ratio(a, b):
        return round(a / b, 3) if b else 0.0

    # the acceptance claims: speculation wins where the drafter hits,
    # and costs <10% vs plain fused where it whiffs (the acceptance-rate
    # telemetry explains which regime a workload is in)
    out["repetitive_speedup_vs_host"] = _ratio(
        rep["spec"]["tokens_per_sec"], rep["host"]["tokens_per_sec"])
    out["repetitive_speedup_vs_fused"] = _ratio(
        rep["spec"]["tokens_per_sec"], rep["fused"]["tokens_per_sec"])
    out["fused_speedup_vs_host"] = _ratio(
        rep["fused"]["tokens_per_sec"], rep["host"]["tokens_per_sec"])
    out["random_spec_vs_fused"] = _ratio(
        rnd["spec"]["tokens_per_sec"], rnd["fused"]["tokens_per_sec"])
    out["random_regression_vs_fused"] = round(
        1.0 - out["random_spec_vs_fused"], 4)
    return out


def main() -> None:
    start("bench_serve")
    import dataclasses

    from distributedtensorflow_tpu.models import (
        GPTLM,
        gpt_small,
        gpt_tiny,
    )
    from distributedtensorflow_tpu.serve import Engine

    test_size = os.environ.get("BENCH_SERVE_TEST") == "1"
    model = os.environ.get("BENCH_SERVE_MODEL",
                           "tiny" if test_size else "small")
    cfg = gpt_tiny() if model == "tiny" else gpt_small()
    mode = os.environ.get("BENCH_SERVE_MODE", "all")
    if "--spec-sweep" in sys.argv[1:]:
        mode = "spec"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", "2" if test_size else "8"))
    n = int(os.environ.get("BENCH_SERVE_N", "6" if test_size else "32"))
    new = int(os.environ.get("BENCH_SERVE_NEW", "8" if test_size else "32"))
    prompt_max = int(os.environ.get(
        "BENCH_SERVE_PROMPT", "16" if test_size else "64"))
    rates = tuple(
        float(r) for r in os.environ.get(
            "BENCH_SERVE_RATES", "16" if test_size else "2,8,32"
        ).split(",")
    )
    header = int(os.environ.get(
        "BENCH_SERVE_HEADER", "32" if test_size else "256"))
    block = 8 if test_size else 16
    chunk = 8 if test_size else 32
    budget = int(os.environ.get("BENCH_SERVE_BUDGET", str(2 * chunk)))
    max_context = int(os.environ.get(
        "BENCH_SERVE_CTX", "128" if test_size else "1024"))
    cfg = dataclasses.replace(cfg, max_seq=max_context)

    params = GPTLM(cfg).init(
        jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
        deterministic=True,
    )["params"]

    def make_engine(prefix_cache=False, prefill_budget=None,
                    fused_sampling=False, speculate=0):
        return Engine(
            params, cfg, max_slots=slots, max_queue=max(4 * n, 64),
            block_size=block, prefill_chunk=chunk,
            prefix_cache=prefix_cache, prefill_budget=prefill_budget,
            fused_sampling=fused_sampling, speculate=speculate,
            max_context=max_context,
        ).start()

    platform = jax.devices()[0].platform
    base = {
        "max_slots": slots,
        "model": model,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    result = dict(base)
    if mode in ("all", "load"):
        load = _offered_load_sweep(
            make_engine, rates=rates, n=n, new=new, prompt_max=prompt_max,
            vocab=cfg.vocab_size,
        )
        result.update({
            "metric": "serve_offered_load_tokens_per_sec",
            "value": load["value"],
            "unit": "tokens/sec",
            "vs_baseline": None,  # no public anchor for this serving config
            "headline": load["headline"],
            "curve": load["curve"],
            "requests_per_point": n,
            "max_new_tokens": new,
        })
        if not test_size:
            persist_result("serve", result)
    if mode in ("all", "prefix"):
        prefix = _shared_prefix_sweep(
            make_engine, header=header, tail_max=max(prompt_max // 4, 4),
            n=n, new=new, vocab=cfg.vocab_size,
        )
        result["shared_prefix"] = prefix
        if not test_size:
            persist_result("serve_prefix", {
                "metric": "serve_shared_prefix_speedup",
                "value": prefix["speedup"],
                "unit": "x tokens/sec (prefix_cache on/off)",
                **base, **prefix,
            })
    if mode in ("all", "spec"):
        spec = _spec_sweep(
            make_engine, n=n, new=new,
            prompt_len=int(os.environ.get(
                "BENCH_SERVE_SPEC_PROMPT", "24" if test_size else "64")),
            vocab=cfg.vocab_size,
            speculate=int(os.environ.get("BENCH_SERVE_SPEC_K", "4")),
        )
        result["spec"] = spec
        if not test_size:
            # the headline is speculation ON vs OFF at an otherwise
            # identical engine; the vs-host ratio rides alongside
            persist_result("serve_spec", {
                "metric": "serve_spec_decode_speedup",
                "value": spec["repetitive_speedup_vs_fused"],
                "unit": "x tokens/sec (speculation on vs off, "
                        "repetitive-suffix workload)",
                **base, **spec,
            })
    if mode in ("all", "interference"):
        interference = _interference_sweep(
            make_engine,
            victims=min(2 if test_size else 3, slots - 1) or 1,
            victim_prompt=8 if test_size else 32,
            victim_new=12 if test_size else 48,
            mults=(2, 4) if test_size else (4, 8),
            budget=budget, vocab=cfg.vocab_size,
        )
        result["interference"] = interference
        if not test_size:
            persist_result("serve_interference", {
                "metric": "serve_interference_victim_itl",
                "unit": "seconds (victim worst inter-token stall)",
                **base, **interference,
            })
    print(json.dumps(result))


if __name__ == "__main__":
    main()
