"""Async parameter-server semantics: stale gradients, elasticity, placement.

Reference behaviors under test (SURVEY.md §3.3, §2.1 PS rows):
- variables partitioned across PS tasks; embeddings split axis-0 by the
  sharded-variable partitioners and reassembled losslessly;
- workers pull possibly-stale params and push grads applied with NO
  barrier — observed staleness > 0 under concurrency;
- one worker async == sequential SGD (staleness degenerates to 0);
- a SIGKILLed worker does not stop training: the survivors keep the
  global version advancing and the job finishes (elasticity, the
  "workers are stateless" property);
- Wide&Deep (config #5) trains: loss falls under 2-worker async.
"""

import time

import numpy as np
import optax
import pytest

from distributedtensorflow_tpu.parallel.param_server import (
    AsyncPSClient,
    AsyncPSTrainer,
    PlacementPlan,
    PSServer,
    partition_params,
    reassemble,
    split_like,
)
from distributedtensorflow_tpu.parallel.sharding import (
    FixedShardsPartitioner,
    MinSizePartitioner,
)


def _toy_params():
    rng = np.random.default_rng(0)
    return {
        "embed_0/embedding": rng.standard_normal((64, 8)).astype(np.float32),
        "mlp_0/kernel": rng.standard_normal((16, 4)).astype(np.float32),
        "mlp_0/bias": np.zeros((4,), np.float32),
    }


# --- placement --------------------------------------------------------------


def test_partition_roundtrip_unsplit():
    flat = _toy_params()
    shards, plan = partition_params(flat, num_ps=3)
    # every variable placed exactly once, nothing split
    assert sum(len(s) for s in shards) == len(flat)
    out = reassemble(plan, shards)
    for k in flat:
        np.testing.assert_array_equal(out[k], flat[k])


def test_partition_splits_embedding_rows():
    flat = _toy_params()
    shards, plan = partition_params(
        flat, num_ps=2, partitioner=FixedShardsPartitioner(2)
    )
    # the 64-row embedding is split axis-0 into 2 pieces on distinct PSs
    pieces = plan.pieces["embed_0/embedding"]
    assert len(pieces) == 2
    assert {p.ps for p in pieces} == {0, 1}
    assert [p.start for p in pieces] == [0, 32]
    out = reassemble(plan, shards)
    np.testing.assert_array_equal(out["embed_0/embedding"],
                                  flat["embed_0/embedding"])


def test_partition_min_size_keeps_small_vars_whole():
    flat = _toy_params()
    shards, plan = partition_params(
        flat, num_ps=2, partitioner=MinSizePartitioner(min_shard_bytes=1 << 20)
    )
    assert all(len(plan.pieces[k]) == 1 for k in flat)
    out = reassemble(plan, shards)
    for k in flat:
        np.testing.assert_array_equal(out[k], flat[k])


def test_split_like_matches_placement():
    flat = _toy_params()
    shards, plan = partition_params(
        flat, num_ps=2, partitioner=FixedShardsPartitioner(2)
    )
    grads = {k: np.ones_like(v) for k, v in flat.items()}
    per_ps = split_like(plan, grads)
    for ps in range(2):
        assert set(per_ps[ps]) == set(shards[ps])


def test_plan_json_roundtrip():
    _, plan = partition_params(_toy_params(), num_ps=2,
                               partitioner=FixedShardsPartitioner(2))
    again = PlacementPlan.from_json(plan.to_json())
    assert again == plan


# --- PS server / client -----------------------------------------------------


@pytest.fixture()
def ps_pair():
    flat = _toy_params()
    shards, plan = partition_params(flat, num_ps=2)
    servers = [
        PSServer(s, lambda: optax.sgd(0.5)) for s in shards
    ]
    try:
        yield flat, plan, servers
    finally:
        for s in servers:
            s.stop()


def test_pull_push_applies_sgd(ps_pair):
    flat, plan, servers = ps_pair
    client = AsyncPSClient([s.address for s in servers], plan, worker_id=0)
    params, versions = client.pull()
    assert versions == [0, 0]
    grads = {k: np.ones_like(v) for k, v in flat.items()}
    stats = client.push(grads, versions)
    assert stats["staleness"] == [0, 0]
    after, versions2 = client.pull()
    assert versions2 == [1, 1]
    for k in flat:
        np.testing.assert_allclose(after[k], flat[k] - 0.5, rtol=1e-6)


def test_stale_push_recorded(ps_pair):
    flat, plan, servers = ps_pair
    addrs = [s.address for s in servers]
    a = AsyncPSClient(addrs, plan, worker_id=0)
    b = AsyncPSClient(addrs, plan, worker_id=1)
    grads = {k: np.zeros_like(v) for k, v in flat.items()}
    _, va = a.pull()
    _, vb = b.pull()          # b pulls the same version as a
    a.push(grads, va)          # a applies first
    stats = b.push(grads, vb)  # b's push is now one version stale
    assert stats["staleness"] == [1, 1]
    hist = AsyncPSClient(addrs, plan).stats()[0]["staleness_hist"]
    assert hist.get("1") == 1 and hist.get("0") == 1


def test_push_wrong_keys_rejected(ps_pair):
    flat, plan, servers = ps_pair
    client = AsyncPSClient([s.address for s in servers], plan)
    bad = {k + "_nope": v for k, v in
           {k: np.zeros_like(v) for k, v in flat.items()}.items()}
    with pytest.raises(Exception):
        client.push(bad, [0, 0])


# --- construction/failure validation ----------------------------------------


def test_mutable_collections_rejected():
    # cifar_resnet20 has batch_stats — no PS placement story; must fail
    # at construction with a clear message, not in every worker.
    with pytest.raises(ValueError, match="batch_stats"):
        AsyncPSTrainer("cifar_resnet20", num_workers=1, steps=1)


def test_worker_crash_raises_at_join():
    t = AsyncPSTrainer("widedeep", num_ps=1, num_workers=1, steps=2,
                       batch_size=32)
    # sabotage the spec the child reads: get_workload raises -> exit 1
    t._spec["workload"] = "no_such_workload"
    with t:
        t.start()
        with pytest.raises(RuntimeError, match="without being killed"):
            t.join(timeout=120)


# --- TF_CONFIG ps/worker cluster launcher (legacy PS path) -------------------


def test_tf_config_ps_cluster_end_to_end():
    """One process per TF_CONFIG task: 2 ps + chief + worker, all rc=0,
    ps tasks absorb exactly the push budget, workers observe staleness."""
    import json
    import os
    import subprocess
    import sys

    from distributedtensorflow_tpu.testing import pick_unused_port

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ports = [pick_unused_port() for _ in range(4)]
    cluster = {
        "ps": [f"127.0.0.1:{ports[0]}", f"127.0.0.1:{ports[1]}"],
        "chief": [f"127.0.0.1:{ports[2]}"],
        "worker": [f"127.0.0.1:{ports[3]}"],
    }
    # idle-timeout 360, not 120: the ps tier's idle clock ticks from
    # startup, and under a fully loaded box (suite + watcher) the four
    # children's jax imports serialize — at 120 the ps tasks gave up
    # before the workers finished importing (observed 2026-08-01, twice:
    # workers then report "PS tasks unreachable").  The 420s communicate
    # timeout below still bounds orphaned processes.
    flags = ["--workload", "widedeep", "--test-size", "--steps", "4",
             "--batch-size", "32", "--idle-timeout", "360"]
    procs = []
    outs = []
    try:
        for task_type, index in (("ps", 0), ("ps", 1), ("chief", 0),
                                 ("worker", 0)):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # no virtual devices in the children
            # Children must not inherit a persistent-compile-cache setup
            # (suite-context leak class: four children serializing on the
            # shared cache's file locks deadlocked this test for four
            # full-suite runs, 2026-08-01) nor a TPU platform (the ps
            # cluster is host-side by design).
            for k in list(env):
                if k.startswith(("JAX_COMPILATION_CACHE",
                                 "JAX_PERSISTENT_CACHE")):
                    env.pop(k)
            env["JAX_PLATFORMS"] = "cpu"
            # Workers' PS-reachability wait: the default 180s expired
            # once under full-suite load (2026-08-01 run 4) — all four
            # children's jax imports AND widedeep model builds serialize
            # on this 1-core box before the ps tier binds.
            env["DTFT_PS_WAIT_S"] = "360"
            env["TF_CONFIG"] = json.dumps(
                {"cluster": cluster,
                 "task": {"type": task_type, "index": index}}
            )
            procs.append(subprocess.Popen(
                [sys.executable, "train.py", *flags], cwd=repo, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        roles = ["ps0", "ps1", "chief", "worker"]
        for p in procs:
            # 600s: must exceed the 360s worker wait + import/build time.
            out, _ = p.communicate(timeout=600)
            outs.append(out)
        # Collect EVERY task's tail before asserting: the first-failure
        # assert used to show only one child's output, and the ~1.8 KB
        # XLA cpu-AOT banner swallowed even that — three suite-context
        # failures went undiagnosable (2026-08-01).  The digest strips
        # banner lines and labels each task.
        def tail(out):
            lines = [
                ln for ln in out.splitlines()
                if "cpu_aot_loader" not in ln and "machine features" not in ln
            ]
            return "\n".join(lines[-6:])

        digest = "\n".join(
            f"--- {r} rc={p.returncode} ---\n{tail(o)}"
            for r, p, o in zip(roles, procs, outs)
        )
        for r, p in zip(roles, procs):
            assert p.returncode == 0, f"{r} failed\n{digest}"
    finally:  # a hung/failed task must not orphan its peers
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate(timeout=10)
    # each ps shard absorbed exactly workers*steps pushes
    assert "done at version 8" in outs[0], outs[0][-800:]
    assert "done at version 8" in outs[1], outs[1][-800:]
    # chief is worker 0, worker task is worker 1; both report staleness
    assert "chief task 0 = async worker 0/2" in outs[2]
    assert "worker task 0 = async worker 1/2" in outs[3]
    assert "staleness" in outs[2] and "staleness" in outs[3]


# --- end-to-end async training (Wide&Deep, reference config #5) -------------


def test_async_widedeep_trains_and_is_async():
    t = AsyncPSTrainer(
        "widedeep", num_ps=2, num_workers=2, steps=15, batch_size=128,
        partitioner=FixedShardsPartitioner(2),
    )
    with t:
        t.start()
        t.join(timeout=240)
        results = t.worker_results()
        assert set(results) == {0, 1}, f"workers finished: {set(results)}"
        # async progress: both workers pushed every step, applied immediately
        assert t.global_version() == 2 * 2 * 15  # workers*ps*steps
        first, last = t.first_last_mean_loss()
        assert last < first, f"loss did not fall: {first:.3f} -> {last:.3f}"
        # loss mixing across workers: each worker's loss history reflects
        # updates it never computed (can't assert directly, but staleness>0
        # proves peer updates landed between its pull and push)
        staleness = [s for _, st in results.values() for s in st]
        assert any(s > 0 for s in staleness), (
            "no stale push observed — workers ran serialized, not async"
        )


def test_async_ps_survives_worker_kill():
    t = AsyncPSTrainer(
        "widedeep", num_ps=2, num_workers=2, steps=30, batch_size=64,
        worker_sleep_s=0.05,
    )
    with t:
        t.start()
        # wait for training to actually start, then kill worker 1
        deadline = time.monotonic() + 120
        while t.global_version() < 8:
            assert time.monotonic() < deadline, "training never started"
            time.sleep(0.1)
        v_before = t.global_version()
        t.kill_worker(1)
        t.join(timeout=240)
        # the survivor finished its full budget and kept version advancing
        results = t.worker_results()
        assert 0 in results and 1 not in results
        assert t.global_version() > v_before
        assert len(results[0][0]) == 30
        # evaluate on the final (post-kill) params: still a trained model
        metrics = t.evaluate(batches=2)
        assert "accuracy" in metrics


def test_single_worker_async_matches_sequential_sgd():
    """One worker, zero staleness: async == the sync SGD sequence."""
    import jax

    from distributedtensorflow_tpu.data.input_pipeline import InputContext
    from distributedtensorflow_tpu.parallel.param_server import (
        _flatten,
        _unflatten,
    )
    from distributedtensorflow_tpu.workloads import get_workload

    steps, batch = 5, 32
    t = AsyncPSTrainer(
        "widedeep", num_ps=2, num_workers=1, steps=steps, batch_size=batch,
        make_optimizer=lambda: optax.sgd(0.1), seed=0,
    )
    with t:
        t.start()
        t.join(timeout=240)
        (losses, staleness), = t.worker_results().values()
        assert all(s == 0 for s in staleness)
        async_params = _flatten(t.current_params())

    # sequential replay with identical seeds/data/optimizer
    wl = get_workload("widedeep", test_size=True, global_batch_size=batch)
    variables = wl.init_fn(jax.random.PRNGKey(0))
    params = variables["params"]
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    data = wl.input_fn(InputContext(1, 0, batch), 0)
    rng = jax.random.PRNGKey(1000)

    def loss_of(p, b, r):
        loss, _ = wl.loss_fn(p, {}, b, r)
        return loss

    grad_fn = jax.jit(jax.value_and_grad(loss_of))
    seq_losses = []
    for _ in range(steps):
        rng, sub = jax.random.split(rng)
        loss, grads = grad_fn(params, next(data), sub)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        seq_losses.append(float(loss))

    np.testing.assert_allclose(losses, seq_losses, rtol=1e-5)
    seq_flat = _flatten(params)
    for k in seq_flat:
        np.testing.assert_allclose(
            async_params[k], seq_flat[k], rtol=1e-5, atol=1e-6
        )


def test_wedged_peer_cannot_pin_serve_until():
    """A client that connects and then never sends its request must not
    block serve_until past the bounded drain: the handler counts the
    connection as inflight from accept (so stop() can't race a received
    push), and the post-done drain is capped (_DRAIN_CAP_S) so a
    half-open peer can't pin the ps task past its exit condition."""
    import socket

    from distributedtensorflow_tpu.parallel import param_server as ps_mod

    server = PSServer(_toy_params(), lambda: optax.sgd(0.1))
    try:
        # Wedge: open the connection, send nothing, keep it alive.
        wedge = socket.create_connection(("127.0.0.1", server.port))
        time.sleep(0.3)  # let the handler thread enter its blocking recv
        t0 = time.monotonic()
        # total_updates=0 holds immediately; only the wedged connection
        # keeps inflight nonzero.  Must return within the drain cap.
        version = server.serve_until(0, poll_s=0.01)
        elapsed = time.monotonic() - t0
        assert version == 0
        assert elapsed < ps_mod._DRAIN_CAP_S + 2.0, (
            f"serve_until took {elapsed:.1f}s — drain cap not applied"
        )
        wedge.close()
    finally:
        server.stop()


def test_serve_until_startup_grace_outlives_idle_timeout():
    """Before the first push the ps task waits ``startup_grace_s``, not
    ``idle_timeout_s`` — the fix for the startup race where a ps tier
    idles out exactly while slow workers are still booting.  After the
    first push the strict idle clock applies."""
    import threading

    server = PSServer({}, lambda: optax.sgd(0.1), port=0)
    out = {}

    def run():
        t0 = time.monotonic()
        out["version"] = server.serve_until(
            None, idle_timeout_s=0.4, startup_grace_s=3.0, poll_s=0.05
        )
        out["elapsed"] = time.monotonic() - t0

    th = threading.Thread(target=run, daemon=True)
    try:
        th.start()
        # At 1s (far past idle_timeout_s) the server must still be
        # alive: no push has landed, so the grace clock governs.
        time.sleep(1.0)
        assert th.is_alive(), "ps task idled out during the startup grace"
        th.join(timeout=10)
        assert not th.is_alive()
        # It exited via the grace bound (>= 3s), not the idle bound.
        assert out["elapsed"] >= 2.9, out
    finally:
        server.stop()
