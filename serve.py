#!/usr/bin/env python
"""serve.py — continuous-batching generation server entry point (ISSUE 6).

Loads a GPT config (checkpoint or random init), builds the paged-KV
serving engine, and fronts it with the ``/generatez`` HTTP endpoint plus
the whole ``/statusz`` introspection family.  One process per host; the
model may be mesh-sharded (GSPMD partitions both serving programs the
same way it partitions the dense-cache reference, ``models.generate``).

Examples:

  # random-init tiny model on an ephemeral port (CI smoke):
  python serve.py --config gpt_tiny --port 0 --logdir /tmp/serve

  # serve a trained gpt_lm checkpoint:
  python serve.py --config gpt_small --checkpoint ckpts/ --port 8600 \\
      --max-slots 8 --max-queue 128 --block-size 32

On startup one JSON line goes to stdout — ``{"serving": true, "port": N,
"logdir": ..., "device": {"platform", "kind", "count"}}`` — so launchers
(and the smokes) can find an ephemeral port and see what it runs on.  SIGINT/SIGTERM drain in-flight requests, flush ``requests.jsonl``
/ ``metrics.prom``, and exit 0.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()  # start-up spans count from here

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402


#: --config choice -> (GPTConfig factory name, matching train.py workload).
CONFIGS = {
    "gpt_tiny": ("gpt_tiny", ("gpt_lm", True)),
    "gpt_small": ("gpt_small", ("gpt_lm", False)),
    "gpt_medium": ("gpt_medium", ("gpt_medium_lm", False)),
    # the afmoe family (models/afmoe.py): random init only, no trainer yet
    "afmoe_tiny": ("afmoe_tiny", None),
    "trinity_large_ep8": ("trinity_large_ep8", None),
    # the joyai family (models/joyai.py): latent attention, random init only
    "joyai_tiny": ("joyai_tiny", None),
    "joyai_llm_flash": ("joyai_llm_flash", None),
    # the same family with its indexer on (GLM-5: learned sparse attention)
    "glm5_tiny": ("glm5_tiny", None),
    "glm5_ep16": ("glm5_ep16", None),
    # the jamba family (models/jamba.py): Mamba + attention layers, random init
    "jamba_tiny": ("jamba_tiny", None),
    "jamba2_3b": ("jamba2_3b", None),
    # the mimo family (models/mimo.py): window layers with a sink beside full
    # ones, a K/V head count a kind, keys wider than values; random init
    "mimo_tiny": ("mimo_tiny", None),
    "mimo_v25_ep16": ("mimo_v25_ep16", None),
    # the lfm2 family (models/lfm2.py): gated short-convolution layers that
    # keep a tail a slot beside attention layers, routed experts; random init
    "lfm2_tiny": ("lfm2_tiny", None),
    "lfm2_24b_a2b": ("lfm2_24b_a2b", None),
    # the evabyte family (models/evabyte.py): EVA layers that keep token rows
    # in a tumbling ring AND chunk summaries in a pool that grows; random init
    "evabyte_tiny": ("evabyte_tiny", None),
    "evabyte_6_5b": ("evabyte_6_5b", None),
    # the ling family (models/ling.py): Kimi-Delta-Attention layers that keep
    # a matrix state a head a slot beside latent-attention layers,
    # group-limited experts; random init
    "ling_tiny": ("ling_tiny", None),
    "ling3_flash_ep8": ("ling3_flash_ep8", None),
    # the nemotron_h family (models/nemotron_h.py): one part a layer — Mamba-2
    # layers that keep a matrix state a head a slot, latent expert layers,
    # attention layers without rotary; random init
    "nemotron_h_tiny": ("nemotron_h_tiny", None),
    "nemotron3_super_ep4": ("nemotron3_super_ep4", None),
    # the qwen3_next family (models/qwen3_next.py): Gated DeltaNet layers (a
    # matrix state a value head a slot under one unbounded scalar gate, fewer
    # key heads) beside gated attention at heads of 256, softmax-routed
    # experts and a gated shared expert in every layer; random init
    "qwen3_next_tiny": ("qwen3_next_tiny", None),
    "qwen3_next_ep4": ("qwen3_next_ep4", None),
    # the ouro family (models/ouro.py): a looped stack — the layers run
    # total_ut_steps times over shared weights, a K/V layer slot a pass a
    # layer, sandwich norms, an exit gate read after every pass; random init
    "ouro_tiny": ("ouro_tiny", None),
    "ouro_2_6b": ("ouro_2_6b", None),
}


def build_params(args, cfg):
    """Checkpoint-or-random parameter init.

    ``--checkpoint`` restores the newest VERIFIED train checkpoint (the
    resilience-tentpole fallback applies) via the matching train.py
    workload's state template, then serves its ``params``; otherwise a
    seeded random init (load tests, CI)."""
    import jax

    if not args.checkpoint:
        from distributedtensorflow_tpu.serve.model import family_of

        logging.info("random-init params (no --checkpoint)")
        return family_of(cfg).init_params(cfg, jax.random.PRNGKey(args.seed))
    if CONFIGS[args.config][1] is None:
        raise SystemExit(
            f"--checkpoint: --config {args.config} has no trainer to "
            "have written one; it serves a seeded random init")
    from distributedtensorflow_tpu.checkpoint import CheckpointManager
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train.state import create_sharded_state
    from distributedtensorflow_tpu.workloads import get_workload

    workload, test_size = CONFIGS[args.config][1]
    wl = get_workload(workload, test_size=test_size)
    mesh = build_mesh(MeshSpec(data=-1))
    state, _ = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(args.seed),
        rules=wl.layout, fsdp=wl.fsdp,
    )
    # ZeRO-aware: a checkpoint trained under --zero stores its optimizer
    # state replica-chunked; the layout probe rechunks it into this
    # unchunked template (serving only reads params, but the restore
    # target must match the saved tree to verify the manifest).
    from distributedtensorflow_tpu.parallel.zero import restore_latest_zero

    restored = restore_latest_zero(
        CheckpointManager(args.checkpoint), state, mesh, None
    )
    if restored is None:
        raise SystemExit(
            f"--checkpoint {args.checkpoint}: no usable checkpoint found"
        )
    logging.info("restored checkpoint step %d from %s",
                 int(restored.step), args.checkpoint)
    return restored.params


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", choices=sorted(CONFIGS), default="gpt_small")
    p.add_argument("--checkpoint", default=None,
                   help="train.py checkpoint dir to serve (default: "
                        "random init)")
    p.add_argument("--port", type=int, default=8600,
                   help="HTTP port (0 = ephemeral; printed on stdout)")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address (loopback default; the endpoints "
                        "have no auth)")
    p.add_argument("--max-slots", type=int, default=4,
                   help="concurrent decode slots (the batch dimension of "
                        "the compiled decode program)")
    p.add_argument("--max-queue", type=int, default=64,
                   help="bounded request queue; beyond it POSTs get 429")
    p.add_argument("--block-size", type=int, default=16,
                   help="paged-KV block size in tokens")
    p.add_argument("--kv-blocks", type=int, default=None,
                   help="total KV pool blocks (default: max-slots * "
                        "max-context/block-size = no oversubscription)")
    p.add_argument("--kv-window-blocks", type=int, default=None,
                   help="blocks of the window layers' pool, for a model "
                        "whose layers are in groups by attention kind "
                        "(--kv-blocks is then the full layers' pool; "
                        "default: every slot's ring of window + one "
                        "prefill chunk + one block)")
    p.add_argument("--prefill-chunk", type=int, default=16,
                   help="prefill program width in tokens")
    p.add_argument("--prefill-budget", type=int, default=0,
                   help="max prefill tokens per scheduler iteration "
                        "(decode-integrated chunked prefill: every "
                        "iteration runs at most this many tokens of "
                        "prefill chunks, round-robin across unfilled "
                        "requests, THEN one decode step for all running "
                        "slots — a long prompt cannot stall in-flight "
                        "decode by more than one budget's worth of "
                        "chunks; 0 = unbudgeted)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="copy-on-write prefix caching: whole token-"
                        "aligned KV blocks of completed prompts are "
                        "indexed by content hash and mapped refcount+1 "
                        "into later requests sharing the prefix, so "
                        "prefill starts at the first uncached token; "
                        "refcount-0 blocks stay warm and are LRU-evicted "
                        "only under pool pressure")
    p.add_argument("--fused-sampling", action="store_true",
                   help="decode fast path: fold greedy and temperature/"
                        "top-k sampling into the compiled decode program "
                        "— per-slot PRNG keys and last tokens stay "
                        "device-resident, the host gets one small "
                        "(tokens, counts) fetch per iteration for EOS/"
                        "logging instead of a logits pull + numpy "
                        "softmax + token feed-back per token")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="self-speculative decoding (requires "
                        "--fused-sampling): a model-free n-gram drafter "
                        "proposes up to K tokens from the request's own "
                        "history, verified in ONE multi-token paged "
                        "attention pass; greedy output is token-for-"
                        "token the sequential path's, sampling is exact "
                        "via rejection sampling.  Pays off when "
                        "continuations repeat context (code, few-shot, "
                        "extraction); novel text degrades to the plain "
                        "fused path (0 = off)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="longest suffix n-gram the drafter matches "
                        "against the request history")
    p.add_argument("--max-context", type=int, default=None,
                   help="serving context cap (default: model max_seq)")
    p.add_argument("--max-new-cap", type=int, default=None,
                   help="reject requests asking for more new tokens")
    p.add_argument("--logdir", default=None,
                   help="writes requests.jsonl / metrics.jsonl / "
                        "steps.jsonl / history.jsonl / "
                        "metrics.prom (and, with tracing, trace.jsonl) "
                        "here")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="bounded SIGTERM drain: refuse new submits with "
                        "503 immediately, finish in-flight requests, and "
                        "force-exit (exception flight event, exit 1) if "
                        "any are still running after this many seconds")
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--step-ring", type=int, default=512,
                   help="engine step-log ring size: every scheduler "
                        "iteration leaves one structured record (phase "
                        "mix, occupancy, token deltas, phase wall "
                        "split) in a bounded ring served at GET "
                        "/stepz and appended to <logdir>/steps.jsonl")
    p.add_argument("--history-interval", type=float, default=2.0,
                   help="embedded metrics history store (obs.tsdb): "
                        "sample the registry (and SLO good/total "
                        "snapshots) every this many seconds into fixed-"
                        "memory downsampling rings, served at GET /histz "
                        "and appended to <logdir>/history.jsonl (offline "
                        "SLO burn recomputation); 0 = off")
    p.add_argument("--history-points", type=int, default=360,
                   help="history ring size per series: on overflow the "
                        "ring decimates 2:1 and doubles its resolution, "
                        "so memory stays fixed for any run length")
    p.add_argument("--slo-rules", default=None, metavar="JSON",
                   help="SLO rule file (obs.slo schema): evaluate burn "
                        "rates over the serve_* histograms on a "
                        "background thread, expose slo_burn_rate{slo=,"
                        "window=} in /varz and GET /sloz, raise "
                        "slo_violation flight events on threshold trips")
    p.add_argument("--alert-rules", default=None, metavar="JSON",
                   help="alert rule file (obs.alerts schema): evaluate "
                        "threshold/burn/absence/anomaly rules over the "
                        "registry / history store / SLO monitor on a "
                        "background thread; firings append "
                        "<logdir>/alerts.jsonl, write incident evidence "
                        "bundles under <logdir>/incidents/, and serve "
                        "GET /alertz + /healthz?deep=1")
    p.add_argument("--alert-interval", type=float, default=5.0,
                   help="seconds between alert rule evaluations")
    p.add_argument("--alert-webhook", default=None, metavar="URL",
                   help="POST every alert transition to this http:// URL "
                        "as JSON (through net.rpc: deadline, retries, "
                        "circuit breaker)")
    p.add_argument("--slo-interval", type=float, default=5.0,
                   help="seconds between SLO burn-rate evaluations")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(message)s",
    )

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.obs.capture import (
        CaptureEngine,
        install_engine,
    )
    from distributedtensorflow_tpu.obs.tracing import (
        PhaseTrace,
        TraceRecorder,
        install_compile_log,
    )
    from distributedtensorflow_tpu.serve import Engine, ServeServer

    # Start-up as spans with absolute time (trace_id "startup" in
    # <logdir>/trace.jsonl): each mark names the stretch since the last,
    # and what JAX traces, lowers, compiles or loads inside it is its
    # child.  It ends in the engine, with the first decode step.
    startup = PhaseTrace("startup", T_PROCESS_START)
    install_compile_log(startup)
    startup.mark("startup.imports")
    runtime.init_compile_cache()
    device = runtime.device_summary()  # also initialises the backend
    logging.info("device: %s", json.dumps(device))
    startup.mark("startup.backend")
    # Distributed request tracing: with a logdir, every completed request
    # leaves queue/prefill/decode spans in <logdir>/trace.jsonl keyed by
    # its trace_id (client-suppliable via POST /generatez) — the stream
    # tools/timeline.py --fleet stitches across processes.  Installed
    # before the parameters are built, so start-up lands in it too; no
    # step rows: the engine's iteration trees go to the step log and the
    # profiler, not to this file.
    tracer = None
    flight = None
    capture = None
    if args.logdir:
        import os

        from distributedtensorflow_tpu.obs.flight_recorder import (
            FlightRecorder,
            install_recorder,
        )

        tracer = TraceRecorder(
            os.path.join(args.logdir, "trace.jsonl"), step_rows=False
        ).install()
        # Flight ring for lifecycle forensics: the drain-timeout
        # `exception` event (and anything else record_event raises)
        # lands in <logdir>/flight.jsonl.
        flight = FlightRecorder(
            path=os.path.join(args.logdir, "flight.jsonl")
        )
        install_recorder(flight)
        flight.install_crash_hooks()
        # Profiler windows an operator can ask for: POST /profilez?steps=N
        # on the status server arms one, the engine loop opens and closes
        # it by iteration, <logdir>/captures/<id>/ holds the trace with
        # the engine.* spans beside the device lanes.
        capture = CaptureEngine(args.logdir)
        install_engine(capture)
    cfg = getattr(models, CONFIGS[args.config][0])()
    params = build_params(args, cfg)
    import jax

    jax.block_until_ready(params)  # init is asynchronous: charge it here
    startup.mark("startup.init_params", config=args.config)
    engine = Engine(
        params, cfg,
        max_slots=args.max_slots, max_queue=args.max_queue,
        block_size=args.block_size, num_blocks=args.kv_blocks,
        window_blocks=args.kv_window_blocks,
        prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget or None,
        prefix_cache=args.prefix_cache,
        fused_sampling=args.fused_sampling or args.speculate > 0,
        speculate=args.speculate,
        spec_ngram=args.spec_ngram,
        max_context=args.max_context,
        max_new_cap=args.max_new_cap, logdir=args.logdir,
        log_every=args.log_every, step_ring=args.step_ring,
        capture=capture,
    ).start()
    startup.mark("startup.engine_build",
                 decode_attention=engine.programs.decode_attention,
                 chunk_attention=engine.programs.chunk_attention,
                 chunk_scan=engine.programs.chunk_scan,
                 state_form=engine.programs.state_form,
                 cache_row_bytes=engine.kv.row_bytes,
                 cache_layer_slots=engine.kv.layer_slots,
                 kv_groups=engine.kv_groups())
    server = ServeServer(engine, args.port, host=args.host).start()

    slo_monitor = None
    if args.slo_rules:
        from distributedtensorflow_tpu.obs.slo import SLOMonitor, load_rules

        try:
            rules = load_rules(args.slo_rules)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            raise SystemExit(f"--slo-rules {args.slo_rules}: {e}")
        slo_monitor = SLOMonitor(
            rules, interval_s=args.slo_interval
        ).install(server.status_server).start()
        logging.info("slo monitor: %d rule(s) from %s (GET /sloz)",
                     len(rules), args.slo_rules)

    history = None
    if args.history_interval > 0:
        from distributedtensorflow_tpu.obs.tsdb import MetricsHistory

        # the embedded history store samples the registry (and, with
        # --slo-rules, each rule's good/total snapshot, so burn rates are
        # recomputable offline from history.jsonl) next to the SLO
        # monitor; GET /histz answers windowed queries from the rings
        history = MetricsHistory(
            interval_s=args.history_interval,
            points_per_series=args.history_points,
            logdir=args.logdir,
            rules=slo_monitor.rules if slo_monitor is not None else None,
        ).install(server.status_server).start()
        logging.info("metrics history: sampling every %.1fs (GET /histz)",
                     args.history_interval)

    alert_manager = None
    if args.alert_rules:
        from distributedtensorflow_tpu.obs import alerts as alertslib

        try:
            alert_rules = alertslib.load_rules(args.alert_rules)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            raise SystemExit(f"--alert-rules {args.alert_rules}: {e}")
        sinks = [alertslib.log_sink]
        if args.alert_webhook:
            sinks.append(alertslib.make_webhook_sink(args.alert_webhook))
        alert_manager = alertslib.AlertManager(
            alert_rules,
            interval_s=args.alert_interval,
            logdir=args.logdir,
            history=history,
            slo_monitor=slo_monitor,
            sinks=sinks,
            step_records_fn=engine.step_records,
        )
        alert_manager.install(server.status_server)
        components = {
            "alerts": alert_manager.health_component,
            "engine": alertslib.engine_health_component(engine, server),
        }
        if slo_monitor is not None:
            components["slo"] = alertslib.slo_health_component(slo_monitor)
        server.status_server.deep_health_fn = \
            alertslib.compose_deep_health(components)
        alert_manager.start()
        logging.info(
            "alerts: %d rule(s) from %s evaluated every %.1fs%s "
            "(GET /alertz)",
            len(alert_rules), args.alert_rules, args.alert_interval,
            f" (webhook {args.alert_webhook})" if args.alert_webhook
            else "",
        )

    stop = threading.Event()

    def _on_signal(signum, frame):
        logging.info("signal %d: draining and shutting down", signum)
        stop.set()

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)

    # The launcher/smoke contract: one machine-readable line on stdout.
    print(json.dumps({
        "serving": True, "port": server.port, "config": args.config,
        "max_slots": args.max_slots, "logdir": args.logdir,
        "device": device,
    }), flush=True)
    startup.mark("startup.listen", port=server.port)
    startup.open("startup.first_request")
    engine.startup_trace = startup
    logging.info(
        "serving %s on %s:%d (slots=%d queue=%d block=%d prefix_cache=%s "
        "prefill_budget=%s fused_sampling=%s speculate=%d)",
        args.config, args.host, server.port, args.max_slots,
        args.max_queue, args.block_size, args.prefix_cache,
        args.prefill_budget or "unbudgeted",
        args.fused_sampling or args.speculate > 0, args.speculate,
    )
    while not stop.is_set():
        time.sleep(0.2)
    if alert_manager is not None:
        # before the SLO monitor: stop() runs one final evaluation (so
        # resolve rows land) and burn rules read the monitor's state
        alert_manager.stop()
    if slo_monitor is not None:
        slo_monitor.stop()
    # Bounded drain (--drain-timeout): refuse NEW submits with 503 right
    # away, keep the server up so in-flight responses still go out,
    # finish what is running, and force-exit at the bound instead of
    # hanging forever on a wedged request.
    server.begin_drain()
    drain_deadline = time.monotonic() + max(args.drain_timeout, 0.0)
    drained = False
    while time.monotonic() < drain_deadline:
        st = engine.state()
        if st["queue_depth"] == 0 and st["active_slots"] == 0:
            drained = True
            break
        time.sleep(0.1)
    forced = not drained
    if forced:
        st = engine.state()
        logging.error(
            "drain timeout (%.1fs): %d queued + %d active request(s) "
            "still running; forcing exit",
            args.drain_timeout, st["queue_depth"], st["active_slots"],
        )
        from distributedtensorflow_tpu.obs import record_event

        record_event(
            "exception", reason="drain_timeout",
            drain_timeout_s=args.drain_timeout,
            queued=st["queue_depth"], active=st["active_slots"],
        )
        if flight is not None:
            flight.dump(reason="drain_timeout")
    server.stop()
    engine.stop(drain=not forced)
    if history is not None:
        # stopped after the engine drain: the final tick snapshots the
        # completed run's counters into history.jsonl
        history.stop()
    if capture is not None:
        install_engine(None)
    if tracer is not None:
        tracer.uninstall()
        tracer.close()
    if flight is not None:
        flight.record("serve_shutdown", drained=drained,
                      forced=forced)
        flight.dump(reason="shutdown")
    st = engine.state()
    logging.info(
        "served %d ok / %d rejected / %d error; %d tokens, peak "
        "occupancy %d%s", st["counters"]["ok"], st["counters"]["rejected"],
        st["counters"]["error"], st["counters"]["tokens_generated"],
        st["occupancy_max"], " (FORCED exit at drain bound)" if forced
        else "",
    )
    return 1 if forced else 0


if __name__ == "__main__":
    sys.exit(main())
