"""Is the paged K/V pool kept in place by every program that takes it?

The pool is the largest thing a serving process holds on the device, and
five compiled programs take it, all of which return it: ``prefill_chunk``,
``decode``, ``fused_decode`` at T = 1 and at T > 1 (``serve.model``, built
as the engine builds them: ``make_programs``; a family that is not served
through the fused programs has three) and ``copy_block``.  If the form the pool is stored in is not the form a
program computes in, XLA converts all of it on the way in and back on the
way out, on every call — nothing fails, a decode step is just a third
slower and a prefill chunk forty times (PERF.md §5, PR 25).  This module
reads that off the compiled programs:

- :func:`pool_programs` builds the five programs with abstract arguments
  (shapes only, so nothing is allocated and a *described* device will do);
- :func:`pool_relayouts` lists the ``copy`` / ``transpose`` / ``convert``
  operations of an optimised HLO module whose result is a whole number of
  pool layers, outside scope ``paged_attn``;
- :func:`donated_pools` names the pools that the module's
  ``input_output_alias`` hands from input to output in place;
- :func:`check_pool_programs` compiles and applies both.

``python -m distributedtensorflow_tpu.serve.pool_check --max-slots 32 ...``
runs the check on the device JAX finds and prints one JSON line (a leg of
``chip_smoke.py``); ``tests/test_kernel_export_gpt2.py`` and
``tests/test_kernel_export_families.py`` run it against a described v5e,
without a chip.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from ..ops.ssm import SSMState
from . import kv_cache
from .model import FUSED, family_of, make_programs

#: A pool among a compiled module's arguments, as the family programs take
#: it: ``pools['full'][0]`` is the group's ``k_pool`` (or its one pool of
#: latent rows), ``[1]`` its ``v_pool`` (``copy_block`` takes ``pools[0]``,
#: ``pools[1]``).
_POOLS_ARG = re.compile(r"^pools(?:\[\\?'(\w+)\\?'\])?\[(\d)\]$")
_POOL_NAMES = ("k_pool", "v_pool")
#: the arrays of a state group by their place in it, donated like the pools
#: (``ops.ssm``: an ``SSMState`` has both, a ``ConvTail`` the first alone; a
#: ``DeltaState``'s four are handed in by name, ``state_names``)
_STATE_NAMES = SSMState.names

_RELAYOUT_OPS = {"copy", "copy-start", "copy-done", "transpose", "convert"}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%\S+ = \(?(\w+)\[([\d,]*)\](\{[^ ]*\})?\S* ([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def pool_programs(cfg, *, max_slots: int, num_blocks: int, block_size: int,
                  chunk: int, draft: int, sharding=None,
                  window_blocks: int | None = None):
    """``{name: (jitted program, abstract arguments)}`` for the five
    programs that take the pool, at the shapes an ``Engine`` with these
    settings gives them (``cfg.max_seq`` is the serving context; a model of
    one full group, as GPT-2; of a full group and a window group of
    ``window_blocks`` blocks, each of its own rows — different layers', or
    the same layers' token rows and chunk summaries (evabyte, whose prefill
    chunk takes the count of real tokens too); or of a full group and a
    state group, whose arrays are ``pools["state"]`` and whose prefill chunk
    takes the count of real tokens)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32 = jnp.int32
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: family_of(cfg).init_params(
            cfg, jax.random.PRNGKey(0))))
    layers = kv_cache.layer_groups(cfg)
    paged = [name for name in layers if name != "state"]
    blocks = {"full": num_blocks, "window": window_blocks or num_blocks}
    pools = {name: tuple(
        sds(kv_cache.pool_shape(len(layers[name]), blocks[name], block_size,
                                width), cfg.dtype)
        for width in kv_cache.group_rows(cfg, name).widths)
        for name in paged}
    # a table column a block of rows: a row a token, or (a group of chunk
    # summaries) one a ``tokens_per_row`` of them
    columns = {name: cfg.max_seq // getattr(kv_cache.group_rows(cfg, name),
                                            "tokens_per_row", 1) // block_size
               for name in paged}
    table_rows = {name: sds((columns[name],), i32) for name in paged}
    tables = {name: sds((max_slots, columns[name]), i32) for name in paged}
    slots_i32 = sds((max_slots,), i32)
    active = sds((max_slots,), jnp.bool_)
    scalar = sds((), i32)
    valid = ()
    if "state" in layers:
        pools["state"] = tuple(
            sds((len(layers["state"]), max_slots, *shape), dtype)
            for shape, dtype in cfg.state_rows.arrays(cfg.dtype))
        table_rows["state"] = sds((1,), i32)
        tables["state"] = sds((max_slots, 1), i32)
        valid = (scalar,)
    if hasattr(cfg.cache_rows, "summary_group"):
        valid = (scalar,)       # a summary a chunk the prompt fills
    programs = make_programs(cfg, chunk=chunk, block_size=block_size,
                             layers=layers)

    def fused_args(t_width):
        return (params, pools, sds((max_slots, t_width), i32), slots_i32,
                tables, slots_i32, active, sds((max_slots, 2), jnp.uint32),
                slots_i32, sds((max_slots,), jnp.float32), slots_i32)

    found = {
        "prefill_chunk": (
            programs.prefill_chunk,
            (params, pools, sds((chunk,), i32), scalar, table_rows, scalar,
             *valid)),
        "decode": (
            programs.decode,
            (params, pools, slots_i32, tables, slots_i32, active)),
        "copy_block": (
            kv_cache._copy_block_fn(block_size),
            (pools["full"], scalar, scalar)),
    }
    if programs.family in FUSED:      # the others are refused them
        found["fused_decode"] = (programs.fused(0), fused_args(1))
        found["fused_decode_spec"] = (programs.fused(draft),
                                      fused_args(draft + 1))
    return found


def pool_relayouts(hlo_text: str, layer_elems: int) -> list[str]:
    """``"<op> <dtype>[<shape>]<layout> <op_name>"`` for every ``copy``,
    ``transpose`` or ``convert`` (fused or not) of an optimised HLO module
    whose result holds a whole number of pool layers (``layer_elems``
    elements each) and whose scope is not ``paged_attn``: the pool, or a
    layer of it, changing form.  What attention gathers for the slots is
    smaller than a layer, and a weight's cast (the embedding table is
    larger than a layer of the cells' pool) is no whole number of them."""
    found = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(4) not in _RELAYOUT_OPS:
            continue
        dtype, dims, layout, op = m.groups()
        elems = 1
        for d in dims.split(","):
            elems *= int(d or 1)
        if elems < layer_elems or elems % layer_elems:
            continue
        name = _OP_NAME.search(line)
        name = name.group(1) if name else ""
        if "/paged_attn/" not in name:
            found.append(f"{op} {dtype}[{dims}]{layout or ''} {name}".strip())
    return found


_HLO_DTYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def state_relayouts(hlo_text: str, slots: int, arrays) -> list[str]:
    """The same of a state group's arrays: every ``copy`` or ``transpose``
    whose result is, in the stored type, every slot's state of one layer or
    of all (``(slots, *shape)`` behind at most a layer dimension, for a
    ``(shape, dtype)`` of ``arrays``, ``ops.ssm.SSMState.arrays``): the array,
    or a layer of it, changing form.  One slot's state, which a prefill chunk
    reads and writes, is smaller.  Not counted: a ``convert`` (a step's
    select and float32 arithmetic on a layer of bfloat16 tails is fused
    arithmetic, not a change of form) and the ``copy-start`` / ``copy-done``
    pair with which the compiler keeps a small array in its nearer memory
    through a program and puts it back (``S(1)`` in the layout)."""
    want = {(_HLO_DTYPES[jnp.dtype(dt).name], slots, *shape)
            for shape, dt in arrays}
    found = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(4) not in ("copy", "transpose"):
            continue
        dtype, dims, layout, op = m.groups()
        dims_t = tuple(int(d) for d in dims.split(",") if d)
        if (dtype, *dims_t) in want or (dtype, *dims_t[1:]) in want:
            name = _OP_NAME.search(line)
            found.append(f"{op} {dtype}[{dims}]{layout or ''} "
                         f"{name.group(1) if name else ''}".strip())
    return found


def _entry_parameters(hlo_text: str, state_names=_STATE_NAMES
                      ) -> dict[int, tuple[str, str]]:
    """Parameter number -> (argument name, shape with layout) of the entry
    computation of an HLO module's text; a pool is named ``k_pool`` or
    ``v_pool`` however the program takes it, a state group's arrays
    ``conv_tail`` and ``scan_state`` (``state_names``: what the group's
    form calls them, ``cfg.state_rows.names``)."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    params = {}
    for m in re.finditer(
            r"= (\S+) parameter\((\d+)\)[^\n]*?op_name=\"([^\"]*)\"", entry):
        name = m.group(3)
        pool = _POOLS_ARG.match(name)
        if pool:
            group = pool.group(1)
            names = state_names if group == "state" else _POOL_NAMES
            name = names[int(pool.group(2))]
            if group not in (None, "full", "state"):
                name = f"{group}.{name}"        # "window.k_pool"
        params[int(m.group(2))] = (name, m.group(1))
    return params


def donated_pools(hlo_text: str, state_names=_STATE_NAMES) -> set[str]:
    """The arguments among ``k_pool`` / ``v_pool`` (and a state group's
    ``conv_tail`` / ``scan_state``) that the module's ``input_output_alias``
    gives to an output: the donation took."""
    head = hlo_text[:hlo_text.index("\n")]
    head = head.partition("input_output_alias=")[2]
    # "{ {1}: (195, {}, may-alias), {2}: (196, {}, may-alias) }, entry_..."
    aliased = {int(n) for n in re.findall(
        r"\{[\d, ]*\}: \((\d+), ", head.partition("entry_computation")[0])}
    return {name for number, (name, _)
            in _entry_parameters(hlo_text, state_names).items()
            if number in aliased
            and name.rpartition(".")[2] in _POOL_NAMES + tuple(state_names)}


def check_pool_programs(programs: dict, layer_elems: int,
                        state: tuple | None = None,
                        state_names=_STATE_NAMES) -> dict:
    """Compile each of :func:`pool_programs` and report, per program, the
    pool-sized relayouts (and, with ``state = (slots, arrays)``, the
    state-sized ones: :func:`state_relayouts`), the donated pools, the layout
    the program takes ``k_pool`` in and the compile time.  ``state_names``
    names the state group's arrays where they are not an ``SSMState``'s."""
    report = {}
    for name, (fn, args) in programs.items():
        t0 = time.monotonic()
        text = fn.lower(*args).compile().as_text()
        layouts = {arg: shape for arg, shape
                   in _entry_parameters(text, state_names).values()}
        report[name] = {
            "relayouts": pool_relayouts(text, layer_elems)
            + (state_relayouts(text, *state) if state else []),
            "donated": sorted(donated_pools(text, state_names)),
            "k_pool": layouts.get("k_pool"),
            "compile_s": round(time.monotonic() - t0, 2),
        }
    return report


def failures(report: dict, pools: int = 2, state: tuple[str, ...] = (),
             window: bool = False) -> list[str]:
    """What :func:`check_pool_programs` found wrong, one line each, for a
    group of ``pools`` pools (the K/V pair, or one pool of latent rows), with
    ``window`` a window group's beside the full group's, and, with ``state``
    the names of a state group's arrays (``cfg.state_rows.names``), those
    (``copy_block`` takes the full group's pools only)."""
    bad = []
    for name, r in report.items():
        for op in r["relayouts"]:
            bad.append(f"{name}: pool-sized {op}")
        want = list(_POOL_NAMES[:pools])
        if window and name != "copy_block":
            want = sorted(want + [f"window.{n}" for n in want])
        if state and name != "copy_block":
            want = sorted(want + list(state))
        if r["donated"] != want:
            bad.append(f"{name}: donated in place only {r['donated']}")
    forms = {r["k_pool"] for r in report.values()}
    if len(forms) != 1:
        bad.append(f"the programs take the pool in different forms: {forms}")
    return bad


def main(argv=None) -> int:
    import dataclasses

    from .. import models, runtime

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="gpt_medium")
    p.add_argument("--max-slots", type=int, default=32)
    p.add_argument("--kv-blocks", type=int, default=2048)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=1024)
    p.add_argument("--prefill-chunk", type=int, default=16)
    p.add_argument("--speculate", type=int, default=4)
    args = p.parse_args(argv)

    cfg = dataclasses.replace(getattr(models, args.config)(),
                              max_seq=args.max_context)
    widths = kv_cache.group_rows(cfg, "full").widths
    layers = kv_cache.layer_groups(cfg)
    shape = kv_cache.pool_shape(len(layers["full"]), args.kv_blocks,
                                args.block_size, widths[0])
    state = None
    if "state" in layers:
        state = (args.max_slots, cfg.state_rows.arrays(cfg.dtype))
    report = check_pool_programs(
        pool_programs(cfg, max_slots=args.max_slots,
                      num_blocks=args.kv_blocks, block_size=args.block_size,
                      chunk=args.prefill_chunk, draft=args.speculate),
        layer_elems=shape[1] * shape[2], state=state,
        state_names=cfg.state_rows.names if state else _STATE_NAMES)
    # the pool as the process holds it between calls
    pool = jnp.zeros(shape, cfg.dtype)
    bad = failures(report, pools=len(widths),
                   state=cfg.state_rows.names if state else ())
    print(json.dumps({
        "device": runtime.device_summary(),
        "pool_shape": shape, "pool_dtype": str(pool.dtype),
        "pool_bytes": pool.nbytes,
        "resident_layout": str(pool.format.layout),
        "programs": report, "failures": bad,
    }))
    for line in bad:
        print(f"pool_check: FAILED: {line}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
