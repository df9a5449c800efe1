"""Regression gate for the full-manual shard_map pipeline region.

``models/gpt_pipeline.py`` runs its pipeline region as a FULL-manual
shard_map (every mesh axis manual, kernels manually sliced, explicit
row-parallel psums).  It was written that way around two partial-manual
lowering failures of the jax it was developed on (a ``PartitionId`` the
SPMD partitioner rejected in the forward, an ``IsManualSubgroup`` abort
under grad).  The installed jax compiles and differentiates the
partial-manual form again, so the workaround is removable (ROADMAP D9);
until that rewrite lands, this file keeps the formulation the pipeline
actually uses under test.  Subprocess probes: a lowering failure of this
kind aborts the process.
"""

import os
import subprocess
import sys
import textwrap

# A pipeline-shaped region on a data x pipe mesh: a lax.scan whose carry
# crosses ticks and a ppermute handoff per tick.
_PROBE_PRELUDE = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
jax.config.update("jax_platforms", "cpu")
mesh = jax.make_mesh((2, 4), ("data", "pipe"))
PERM = [(i, (i + 1) % 4) for i in range(4)]

def body(w, xs):
    def tick(carry, x):
        y = jnp.maximum((x + carry) @ w, 0.0)
        return jax.lax.ppermute(y, "pipe", PERM), y
    carry, hist = jax.lax.scan(tick, xs[0], xs)
    return hist

def region(dtype):
    sm = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, "pipe")),
        out_specs=P(None, "pipe"), check_vma=False,
    )
    w = jnp.eye(8, dtype=dtype)
    xs = jnp.arange(4 * 8 * 8, dtype=dtype).reshape(4, 8, 8) / 100.0
    return sm, w, xs
"""


def _run_probe(snippet: str) -> subprocess.CompletedProcess:
    code = _PROBE_PRELUDE + textwrap.dedent(snippet)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-X", "faulthandler", "-c", code],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )


def test_full_manual_pipeline_region_compiles_and_grads():
    """The formulation the pipeline actually uses: a full-manual region
    compiles AND differentiates, in fp32 and bf16.  Either leg breaking
    means the entire pipeline path (gpt_pipeline.py and the 1F1B engine)
    is at risk."""
    for dtype, leg in (("jnp.float32", "fp32"), ("jnp.bfloat16", "bf16")):
        r = _run_probe(f"""
        sm, w, xs = region({dtype})
        out = jax.jit(sm)(w, xs)
        assert out.dtype == {dtype}
        g = jax.jit(jax.grad(
            lambda w, xs: sm(w, xs).astype(jnp.float32).sum()
        ))(w, xs)
        assert g.shape == w.shape
        print("{leg}-ok")
        """)
        assert r.returncode == 0 and f"{leg}-ok" in r.stdout, (
            f"{leg} full-manual pipeline region no longer compiles/grads — "
            "the whole pipeline path is at risk:\n"
            f"{r.stderr[-2000:]}"
        )
