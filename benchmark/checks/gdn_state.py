"""Check ``gdn_state``: the float32 matrix state a Gated DeltaNet layer keeps
a slot, as the served programs leave it on this process's device, against the
reference's recurrence on the same inputs.

``checks/kda_state.py``'s check (its text says what served tokens cannot show
and how the probe sits under ``serve.model``'s two names for ``ops.kda``; its
``probed`` and ``with_inputs`` are used as they are) for a rule whose gate is a
scalar a value head and whose q/k heads are fewer than its value heads: the
probe notes ``q, k`` (.., Hk, K), ``v`` (.., Hv, V), ``g`` (.., Hv, 1) and
``beta`` (.., Hv) as the layer hands them to the scan or the step, the
reference's ``gdn_recurrence`` (a token at a time, float32, on the host's CPU)
advances its own ``(Hv, K, V)`` state of the same slot and layer over the real
tokens of those inputs, from zeros at a request's first chunk, and the slot's
rows of the group's array (stored transposed) are read beside it.

The number compared is ``state_rel_err``: over every program, checked slot
and Gated DeltaNet layer, the largest ``|S_system - S_reference|`` of a layer
over the largest ``|S_reference|`` of that layer; its limit is
``state_rel_err_limit``.  A state rounded to bfloat16 anywhere on the way
differs by 2^-9 of its largest values (``tools/gdn_controls.py --check state
--control bf16_state`` patches that in and must read ``ok`` false).

What this number covers: the scan and the step, the slot's read and write and
the array's type, *given their inputs*.  The q, k, v, g and beta are the
program's own (its projections, convolution, gate and norms produced them), so
a fault upstream of the rule passes here; and the engine is the check's own,
of the configuration's shapes, not the timed server.  The served tokens' check
is the one that holds the whole layer, on the timed server.

The reference runs beside the device, not after it: a layer's recurrence is a
chain of its own (a state depends on that layer's last state alone), so each
layer has one worker thread that advances it and compares, in the order the
programs ran, while the engine goes on to its next program.  ``seconds_by``
in the result says where the check's time went.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def run(spec: dict, reference) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve import model
    from distributedtensorflow_tpu.serve.engine import Engine

    probe = harness.load_module(os.path.join(HERE, "kda_state.py"))
    config = spec["config"]
    t0 = time.time()
    cfg = getattr(models, config["system_config"])()
    family = model.family_of(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(spec["seed"]))
    engine = Engine(params, cfg, max_slots=config["max_slots"],
                    block_size=config["block_size"],
                    num_blocks=config.get("kv_blocks"),
                    prefill_chunk=config["prefill_chunk"],
                    prefill_budget=config.get("prefill_budget"),
                    max_context=config["max_context"])
    jax.block_until_ready(engine.kv.pools())
    seconds = {"weights_and_engine": time.time() - t0}
    programs, state_layers = engine.programs, engine.kv.layers["state"]
    rng = np.random.default_rng(spec["seed"])
    prompts = rng.integers(0, config["vocab_size"], (
        spec["requests"], spec["prompt_tokens"]))

    recurrence = jax.jit(reference.gdn_recurrence)
    host = jax.devices("cpu")[0]
    shape = (config["linear_num_value_heads"], config["linear_key_head_dim"],
             config["linear_value_head_dim"])
    want = {}           # (slot, layer) -> the reference's state
    worst = [{"state_rel_err": 0.0, "at": None} for _ in state_layers]
    calls = {"prefill": 0, "decode": 0}
    workers = [ThreadPoolExecutor(1) for _ in state_layers]
    jobs = []

    def compare(li: int, slot: int, layer, got, fresh: bool, at: dict):
        """Layer ``li``'s worker: the reference over the slot's rows of that
        layer's inputs, then the slot's matrices as the program left them
        beside it."""
        if fresh:
            want[slot, li] = jax.device_put(
                jnp.zeros(shape, jnp.float32), host)
        q, k, v, g, beta = layer    # the gate a scalar a head: (.., Hv, 1)
        with jax.default_matmul_precision("highest"):
            want[slot, li] = recurrence(*jax.device_put(
                [q, k, v, g[..., 0], beta], host), want[slot, li])[1]
        ref = np.asarray(want[slot, li]).swapaxes(-1, -2)  # stored transposed
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        if not err <= worst[li]["state_rel_err"]:       # a nan is the worst
            worst[li] = {"state_rel_err": err, "at": at}

    def advance(slot: int, inputs, rows, fresh: bool, what: str) -> None:
        """Hands each layer's worker ``rows`` of its inputs and the slot's
        rows of the group's array, both read to the host here: the next
        program overwrites the array."""
        got = np.asarray(engine.kv.state.pools[-1][:, slot])
        for li, layer in enumerate(jax.device_get(
                [[x[rows] for x in layer] for layer in inputs])):
            jobs.append(workers[li].submit(
                compare, li, slot, layer, got[li], fresh,
                {"program": what, "slot": slot, "layer": state_layers[li],
                 "call": calls[what]}))

    seen: list = []
    t0 = time.time()
    with probe.probed(model, seen):
        prefill = probe.with_inputs(programs.prefill_chunk, seen)
        decode = probe.with_inputs(programs.decode, seen)

        def prefill_chunk(*args):
            out, inputs = prefill(*args)
            engine.kv.set_pools(out[1])
            calls["prefill"] += 1
            start, valid = int(args[3]), int(args[6])
            advance(int(args[4]["state"][0]), inputs, slice(0, valid),
                    start == 0, "prefill")
            return out

        def decode_step(*args):
            out, inputs = decode(*args)
            engine.kv.set_pools(out[2])
            calls["decode"] += 1
            for slot in np.flatnonzero(np.asarray(args[5])):
                advance(int(slot), inputs, slice(slot, slot + 1), False,
                        "decode")
            return out

        programs.prefill_chunk, programs.decode = prefill_chunk, decode_step
        reqs = [engine.submit([int(t) for t in p],
                              max_new_tokens=spec["new_tokens"])
                for p in prompts]
        while not all(r._done.is_set() for r in reqs):
            engine.step()
    seconds["programs"] = time.time() - t0
    t0 = time.time()
    for job in jobs:
        job.result()
    for worker in workers:
        worker.shutdown()
    seconds["reference_after_the_programs"] = time.time() - t0
    # the largest over the layers; a nan is larger than any number
    worst = max(worst, key=lambda w: (w["state_rel_err"] != w["state_rel_err"],
                                      w["state_rel_err"]))
    short = sum(len(r.tokens) != spec["new_tokens"] for r in reqs)
    return {**worst, "state_rel_err_limit": spec["state_rel_err_limit"],
            "programs_checked": calls,
            "slots_checked": sorted({slot for slot, _ in want}),
            "layers_checked": list(state_layers),
            "chunk_scan": programs.chunk_scan,
            "state_step": cfg.state_rows.step_formulation(cfg.kernel_impl),
            "requests_short_of_tokens": short,
            "seconds_by": {k: round(v, 2) for k, v in seconds.items()},
            "ok": bool(worst["state_rel_err"]
                       <= spec["state_rel_err_limit"] and short == 0
                       and calls["prefill"] and calls["decode"])}
