"""Record-file dataset tests: native reader + AutoShardPolicy semantics.

Reference model: SURVEY.md §2.3 — ``AutoShardPolicy`` {OFF,AUTO,FILE,DATA}
(`options.py:89`), `auto_shard_dataset` (`input_ops.py:28`).
"""


import numpy as np
import pytest

from distributedtensorflow_tpu.data import InputContext, record_dataset, write_record_shards
from distributedtensorflow_tpu.native import native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library not buildable here"
)


def _make_shards(tmp_path, n_shards=4, n_examples=32):
    def gen():
        for i in range(n_examples):
            yield {
                "x": np.full((3,), i, np.float32),
                "label": np.array(i % 7, np.int64),
            }

    return write_record_shards(
        gen(), str(tmp_path / "train-{:03d}.rec"), num_shards=n_shards
    ), n_examples


def _ids(batches):
    return sorted(
        int(v) for b in batches for v in np.asarray(b["x"])[:, 0].ravel()
    )


def test_roundtrip_unbatched(tmp_path):
    paths, n = _make_shards(tmp_path)
    examples = list(record_dataset(paths))
    assert len(examples) == n
    assert sorted(int(e["x"][0]) for e in examples) == list(range(n))
    assert examples[0]["label"].dtype == np.int64


def test_batching_shapes(tmp_path):
    paths, n = _make_shards(tmp_path)
    batches = list(record_dataset(paths, batch_size=8))
    assert len(batches) == n // 8
    assert batches[0]["x"].shape == (8, 3)
    assert batches[0]["label"].shape == (8,)


def test_file_sharding_partitions_exactly(tmp_path):
    paths, n = _make_shards(tmp_path, n_shards=4)
    seen = []
    for host in range(2):
        ctx = InputContext(2, host, 0)
        seen.append(
            _ids(record_dataset(paths, ctx, batch_size=4, policy="FILE"))
        )
    assert sorted(seen[0] + seen[1]) == list(range(n))
    assert not set(seen[0]) & set(seen[1])


def test_data_sharding_partitions_exactly(tmp_path):
    # 3 files / 2 hosts: FILE can't balance; DATA must still partition.
    paths, n = _make_shards(tmp_path, n_shards=3, n_examples=30)
    seen = []
    for host in range(2):
        ctx = InputContext(2, host, 0)
        seen.append(
            _ids(record_dataset(paths, ctx, batch_size=5, policy="DATA",
                                num_threads=1))
        )
    assert sorted(seen[0] + seen[1]) == list(range(n))
    assert not set(seen[0]) & set(seen[1])


def test_data_sharding_exact_despite_threads_and_shuffle(tmp_path):
    """DATA partitioning must hold with the DEFAULT reader config (threads,
    shuffle): stream order is forced host-identical internally."""
    paths, n = _make_shards(tmp_path, n_shards=3, n_examples=30)
    seen = []
    for host in range(2):
        ctx = InputContext(2, host, 0)
        seen.append(
            _ids(record_dataset(paths, ctx, batch_size=5, policy="DATA",
                                num_threads=4, shuffle_buffer=8, seed=3))
        )
    assert sorted(seen[0] + seen[1]) == list(range(n))
    assert not set(seen[0]) & set(seen[1])


def test_auto_policy_selects_by_divisibility(tmp_path):
    from distributedtensorflow_tpu.data.recordio_dataset import _resolve_policy

    assert _resolve_policy("AUTO", 4, 2) == "FILE"
    assert _resolve_policy("AUTO", 3, 2) == "DATA"
    assert _resolve_policy("off", 3, 2) == "OFF"


def test_off_policy_every_host_sees_all(tmp_path):
    paths, n = _make_shards(tmp_path)
    ctx = InputContext(2, 1, 0)
    assert _ids(record_dataset(paths, ctx, batch_size=4, policy="OFF")) == list(range(n))


def test_shuffle_reproducible_per_seed(tmp_path):
    paths, n = _make_shards(tmp_path, n_shards=1)
    a = _ids_ordered(record_dataset(paths, shuffle_buffer=16, seed=5, num_threads=1))
    b = _ids_ordered(record_dataset(paths, shuffle_buffer=16, seed=5, num_threads=1))
    c = _ids_ordered(record_dataset(paths, shuffle_buffer=16, seed=6, num_threads=1))
    assert a == b != c
    assert sorted(a) == list(range(n))


def _ids_ordered(it):
    return [int(e["x"][0]) for e in it]


def test_file_sharding_insufficient_files_raises(tmp_path):
    paths, _ = _make_shards(tmp_path, n_shards=1)
    with pytest.raises(ValueError):
        list(record_dataset(paths, InputContext(2, 0, 0), policy="FILE"))


def test_validation_is_eager(tmp_path):
    """Config errors must raise at call time, not at first next() inside a
    prefetch thread."""
    paths, _ = _make_shards(tmp_path, n_shards=1)
    with pytest.raises(ValueError):
        record_dataset([])  # no iteration
    with pytest.raises(ValueError):
        record_dataset(paths, policy="BOGUS")
    with pytest.raises(ValueError):
        record_dataset(paths, InputContext(2, 0, 0), policy="FILE")


def test_train_from_record_files_end_to_end(tmp_path, devices):
    """The --data-dir path: write record shards, read them back with AUTO
    sharding, and train the mnist workload to decreasing loss — the
    reference's file-based tf.data input story on the native reader."""
    import jax
    import numpy as np

    from distributedtensorflow_tpu.data import write_record_shards
    from distributedtensorflow_tpu.data.input_pipeline import (
        InputContext,
        synthetic_classification,
    )
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import (
        create_sharded_state,
        make_train_step,
    )
    from distributedtensorflow_tpu.workloads import get_workload

    src = synthetic_classification(
        InputContext(1, 0, 32), image_shape=(28, 28, 1), num_classes=10,
        seed=0, steps=30,
    )

    def examples():
        for batch in src:
            for i in range(len(batch["label"])):
                yield {"image": batch["image"][i], "label": batch["label"][i]}

    files = write_record_shards(
        examples(), str(tmp_path / "train-{:03d}.rio"), num_shards=4
    )

    mesh = build_mesh(MeshSpec(data=2), devices[:2])
    wl = get_workload("mnist_lenet", global_batch_size=32)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0)
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    ctx = InputContext(1, 0, 32)
    it = record_dataset(files, ctx, batch_size=ctx.per_host_batch_size,
                        shuffle_buffer=256, seed=0)
    rng = jax.random.PRNGKey(0)
    losses = []
    for _ in range(15):
        state, metrics = step(state, next(it), rng)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_lm_trains_from_record_files(tmp_path, devices):
    """LM records (examples/make_records.py --kind lm): {input_ids} token
    records feed gpt_lm through the same --data-dir path the image
    workloads use, and the loss falls."""
    import jax

    from distributedtensorflow_tpu.data import write_record_shards
    from distributedtensorflow_tpu.data.input_pipeline import InputContext
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import (
        create_sharded_state,
        make_train_step,
    )
    from distributedtensorflow_tpu.workloads import get_workload

    rng_np = np.random.default_rng(0)

    def examples():
        for _ in range(256):
            start = int(rng_np.integers(0, 512))
            step_ = int(rng_np.integers(1, 7))
            ids = (start + step_ * np.arange(64)) % 512
            yield {"input_ids": ids.astype(np.int32)}

    files = write_record_shards(
        examples(), str(tmp_path / "lm-{:03d}.rio"), num_shards=2
    )

    mesh = build_mesh(MeshSpec(data=2), devices[:2])
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=8,
                      seq_len=64)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
        rules=wl.layout,
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    ctx = InputContext(1, 0, 8)
    from distributedtensorflow_tpu.data import repeated_record_dataset

    it = repeated_record_dataset(files, ctx,
                                 batch_size=ctx.per_host_batch_size,
                                 shuffle_buffer=64, seed=0)
    rng = jax.random.PRNGKey(0)
    losses = []
    for _ in range(25):
        state, metrics = step(state, next(it), rng)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]


def test_seq2seq_trains_from_record_files(tmp_path, devices):
    """seq2seq records (examples/make_records.py --kind seq2seq):
    {encoder_ids, targets} copy-task records feed t5_seq2seq through the
    same --data-dir path, and the loss falls — the record layer is
    schema-generic, so the new family costs zero reader changes."""
    import jax

    from distributedtensorflow_tpu.data import (
        repeated_record_dataset,
        write_record_shards,
    )
    from distributedtensorflow_tpu.data.input_pipeline import InputContext
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import (
        create_sharded_state,
        make_train_step,
    )
    from distributedtensorflow_tpu.workloads import get_workload

    rng_np = np.random.default_rng(0)

    def examples():
        for _ in range(256):
            ids = rng_np.integers(2, 512, size=12)
            ids[int(rng_np.integers(6, 13)):] = 1  # pad tail
            ids = ids.astype(np.int32)
            yield {"encoder_ids": ids, "targets": ids.copy()}

    files = write_record_shards(
        examples(), str(tmp_path / "s2s-{:03d}.rio"), num_shards=2
    )
    mesh = build_mesh(MeshSpec(data=2), devices[:2])
    wl = get_workload("t5_seq2seq", test_size=True, global_batch_size=16,
                      seq_len=12)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
        rules=wl.layout,
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    ctx = InputContext(1, 0, 16)
    it = repeated_record_dataset(files, ctx,
                                 batch_size=ctx.per_host_batch_size,
                                 shuffle_buffer=64, seed=0)
    rng = jax.random.PRNGKey(0)
    losses = []
    for _ in range(30):
        state, metrics = step(state, next(it), rng)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses[::8]
