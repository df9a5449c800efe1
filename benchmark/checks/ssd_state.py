"""Check ``ssd_state``: the float32 matrix state a Mamba-2 layer keeps a slot,
as the served programs leave it on this process's device, against the
reference's recurrence on the same inputs.

What served tokens cannot show (PERF.md section 2, PR 52): a state kept in
less than float32 moves a logit by less than bfloat16 activations already do,
so the regret of the served tokens reads the same.  Here nothing but the
state's own arithmetic and storage differs between the two sides.  The
configuration's engine (``Engine`` with its ``max_slots``, pool, chunk and
context: the cell's programs over the cell's cache, on weights made from the
seed as the server makes them) serves ``requests`` seeded prompts of
``prompt_tokens`` and ``new_tokens`` greedy tokens each, the engine scheduling
as it does (a length of its own, not the served check's: the reference's
recurrence costs the host ~1 ms a token a layer of 128 heads, all of it
set-up).  Its two programs are the engine's own functions traced once more
with one more result: the ``x, dt, a, b, c, d`` each Mamba-2 layer hands
``ops.ssd``'s scan or step (``serve.model`` calls both by name; the probe
passes them through untouched, so everything between those values and the
state in the group's array — the chunked products, the in-place step, the
slot's read and write, the array's type — is the timed path's).  After every
program the reference's ``ssd_recurrence`` (a token at a time, float32)
advances its own state of the same slot and layer over the real tokens of
those inputs, from zeros at a request's first chunk, and the slot's rows of
the group's array are read beside it.  The reference runs on the host's CPU
(PR 52: token by token on the TPU the chip's ``exp`` compounds into the
state, the reference's and the system's alike).

The number compared is ``state_rel_err``: over every program, checked slot
and Mamba-2 layer, the largest ``|S_system - S_reference|`` of a layer over
the largest ``|S_reference|`` of that layer; its limit is
``state_rel_err_limit``.  A scan or step that computes the recurrence in
float32 differs from the token-by-token form by the order of its sums; a state
rounded to bfloat16 anywhere on the way by 2^-9 of its largest values
(``tools/ssd_controls.py --check state --control bf16_state`` patches that in
and must read ``ok`` false).
"""

from __future__ import annotations

import contextlib
import functools


@contextlib.contextmanager
def probed(model, seen: list):
    """``serve.model``'s two names for ``ops.ssd`` made to note their inputs
    in ``seen`` (tracers, while a program is traced) and pass them on."""
    scan, step = model.ssd_chunk_scan, model.ssd_step

    def noting(fn):
        @functools.wraps(fn)
        def call(x, dt, a, b, c, d, *rest, **kw):
            seen.append((x, dt, a, b, c, d))
            return fn(x, dt, a, b, c, d, *rest, **kw)
        return call

    model.ssd_chunk_scan, model.ssd_step = noting(scan), noting(step)
    try:
        yield
    finally:
        model.ssd_chunk_scan, model.ssd_step = scan, step


def with_inputs(program, seen: list):
    """``program`` (one of the engine's jitted programs) traced again as
    ``-> (its results, the Mamba-2 layers' inputs in layer order)``; the pools
    donated as it donates them."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(1,))
    def traced(*args):
        del seen[:]
        out = program.__wrapped__(*args)
        return out, tuple(seen)
    return traced


def run(spec: dict, reference) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve import model
    from distributedtensorflow_tpu.serve.engine import Engine

    config = spec["config"]
    cfg = getattr(models, config["system_config"])()
    family = model.family_of(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(spec["seed"]))
    engine = Engine(params, cfg, max_slots=config["max_slots"],
                    block_size=config["block_size"],
                    num_blocks=config.get("kv_blocks"),
                    prefill_chunk=config["prefill_chunk"],
                    prefill_budget=config.get("prefill_budget"),
                    max_context=config["max_context"])
    programs, state_layers = engine.programs, engine.kv.layers["state"]
    rng = np.random.default_rng(spec["seed"])
    prompts = rng.integers(0, config["vocab_size"], (
        spec["requests"], spec["prompt_tokens"]))

    recurrence = jax.jit(reference.ssd_recurrence)
    host = jax.devices("cpu")[0]
    shape = (config["mamba_num_heads"], config["mamba_head_dim"],
             config["ssm_state_size"])
    want = {}           # slot -> the reference's state a Mamba-2 layer
    worst = {"state_rel_err": 0.0, "at": None}
    calls = {"prefill": 0, "decode": 0}

    def advance(slot: int, inputs, rows, fresh: bool, what: str) -> None:
        """The reference over ``rows`` of every layer's inputs, then the
        slot's rows of the group's array beside it."""
        if fresh:
            want[slot] = [jax.device_put(jnp.zeros(shape, jnp.float32), host)
                          for _ in state_layers]

        def mine(layer):        # a, d are a head's, the rest a token's
            x, dt, a, b, c, d = layer
            f32 = jnp.float32
            return jax.device_put(
                [x[rows].astype(f32), dt[rows].astype(f32), a.astype(f32),
                 b[rows].astype(f32), c[rows].astype(f32), d.astype(f32)],
                host)

        with jax.default_matmul_precision("highest"):
            want[slot] = [recurrence(*mine(layer), state)[1]
                          for layer, state in zip(inputs, want[slot])]
        got = np.asarray(engine.kv.state.pools[-1][:, slot])
        for li, state in enumerate(want[slot]):
            ref = np.asarray(state)
            err = float(np.abs(got[li] - ref).max() / np.abs(ref).max())
            if err > worst["state_rel_err"]:
                worst.update(state_rel_err=err, at={
                    "program": what, "slot": slot,
                    "layer": state_layers[li],
                    "call": calls[what]})

    seen: list = []
    with probed(model, seen):
        prefill = with_inputs(programs.prefill_chunk, seen)
        decode = with_inputs(programs.decode, seen)

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
    short = sum(len(r.tokens) != spec["new_tokens"] for r in reqs)
    return {**worst, "state_rel_err_limit": spec["state_rel_err_limit"],
            "programs_checked": calls, "slots_checked": sorted(want),
            "layers_checked": list(state_layers),
            "chunk_scan": programs.chunk_scan,
            "requests_short_of_tokens": short,
            "ok": bool(worst["state_rel_err"]
                       <= spec["state_rel_err_limit"] and short == 0
                       and calls["prefill"] and calls["decode"])}
