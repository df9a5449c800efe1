"""Record-file datasets on the native C++ reader, with auto-sharding.

Connects the compiled record IO (``native.RecordReader`` — the tf.data
C++-reader role) to the input pipeline, reproducing the reference's
``AutoShardPolicy`` semantics (SURVEY.md §2.3: `options.py:89`
{OFF, AUTO, FILE, DATA}, graph-rewrite in `input_ops.py:28`):

- **FILE**: each host reads a disjoint subset of the files — zero wasted
  IO, requires ``len(files) % num_hosts == 0`` for balance (the reference
  errors likewise when files < workers).
- **DATA**: every host reads every file but keeps only its every-k-th
  record — works for any file count, k-1/k of decode bandwidth wasted
  (exactly the reference's trade-off).
- **AUTO**: FILE when the file count divides evenly, else DATA.
- **OFF**: no sharding (every host sees everything).

Examples on disk are raw-tensor-wire feature dicts (``data.wire``: one
JSON header + raw array bytes per record — numpy arrays only, no pickle,
no per-record zip container), written by :func:`write_example`.
:func:`decode_example` sniffs the payload, so files written by the older
``.npz``-per-record codec keep reading; the record framing's own CRC32C
already covers integrity, so the wire-level checksum stays off here.
"""

from __future__ import annotations

import io
from collections.abc import Callable, Iterator, Sequence

import numpy as np

from . import wire as wirelib
from .input_pipeline import InputContext
from ..native import RecordReader, RecordWriter

Example = dict[str, np.ndarray]


def encode_example(example: Example, wire: str = "raw") -> bytes:
    """Serialize one example (``wire="raw"`` default; ``"npz"`` writes the
    legacy per-record zip archive for old readers)."""
    if wire == "raw":
        return wirelib.encode_tensors(example)
    if wire != "npz":
        raise ValueError(f"unknown wire format {wire!r}")
    buf = io.BytesIO()
    np.savez(buf, **example)
    return buf.getvalue()


def decode_example(record: bytes) -> Example:
    if wirelib.is_raw(record):
        return wirelib.decode_tensors(record)
    with np.load(io.BytesIO(record)) as z:
        return {k: z[k] for k in z.files}


def write_example(writer: RecordWriter, example: Example) -> None:
    writer.write(encode_example(example))


def _resolve_policy(policy: str, n_files: int, n_hosts: int) -> str:
    policy = policy.upper()
    if policy == "AUTO":
        return "FILE" if n_files % n_hosts == 0 else "DATA"
    if policy not in ("FILE", "DATA", "OFF"):
        raise ValueError(f"unknown shard policy {policy!r}")
    return policy


def _shuffled(examples_fn, buffer_size: int, rng) -> Callable[[], Iterator[Example]]:
    """Streaming shuffle over an iterator factory (host-side, post-shard)."""

    def gen() -> Iterator[Example]:
        buf: list[Example] = []
        for ex in examples_fn():
            buf.append(ex)
            if len(buf) >= buffer_size:
                ix = int(rng.integers(len(buf)))
                buf[ix], buf[-1] = buf[-1], buf[ix]
                yield buf.pop()
        rng.shuffle(buf)
        yield from buf

    return gen


def record_dataset(
    files: Sequence[str],
    ctx: InputContext | None = None,
    *,
    batch_size: int | None = None,
    policy: str = "AUTO",
    decode_fn: Callable[[bytes], Example] = decode_example,
    shuffle_buffer: int = 0,
    seed: int = 0,
    num_threads: int | None = None,
    drop_remainder: bool = True,
) -> Iterator[Example]:
    """Stream batches from record files, sharded per host.

    ``num_threads=None`` (the default) gates reader threads on the host:
    ``min(4, cpu_count)`` — on a 1-core host extra reader threads only add
    contention.  Pass an
    explicit value to force it (e.g. for file interleaving semantics).

    Yields dicts of stacked arrays with a leading ``batch_size`` dim (the
    per-host batch; pass ``ctx.per_host_batch_size`` upstream).  With
    ``batch_size=None`` yields individual decoded examples.

    Argument validation happens HERE, eagerly — not at first iteration —
    so a config typo fails at job setup rather than inside a prefetch
    thread mid-training.
    """
    files = list(files)
    if not files:
        raise ValueError("record_dataset needs at least one file")
    if num_threads is None:
        from ..native.recordio import available_cpus

        # CPUs this PROCESS may use (affinity/cgroup-aware), not the
        # machine's core count — a container pinned to 1 CPU on a 64-core
        # host must not spawn 4 contending readers.
        num_threads = max(1, min(4, available_cpus()))
    n_hosts = ctx.num_input_pipelines if ctx else 1
    host = ctx.input_pipeline_id if ctx else 0
    policy = _resolve_policy(policy, len(files), n_hosts)

    if policy == "FILE" and n_hosts > 1:
        if len(files) < n_hosts:
            raise ValueError(
                f"FILE sharding needs >= 1 file per host "
                f"({len(files)} files, {n_hosts} hosts)"
            )
        files = files[host::n_hosts]

    return _record_dataset_iter(
        files, policy, host, n_hosts, batch_size=batch_size,
        decode_fn=decode_fn, shuffle_buffer=shuffle_buffer, seed=seed,
        num_threads=num_threads, drop_remainder=drop_remainder,
    )


def _record_dataset_iter(
    files, policy, host, n_hosts, *, batch_size, decode_fn, shuffle_buffer,
    seed, num_threads, drop_remainder,
) -> Iterator[Example]:
    data_sharded = policy == "DATA" and n_hosts > 1
    # DATA sharding partitions by *stream position*, so every host must see
    # the IDENTICAL stream order: single reader thread, no native shuffle,
    # host-independent everything.  Shuffling then happens host-side (below)
    # on the post-shard subset.  FILE/OFF streams are per-host already, so
    # the native threaded reader + in-reader shuffle are safe there.
    reader = RecordReader(
        files,
        num_threads=1 if data_sharded else num_threads,
        shuffle_buffer=0 if data_sharded else shuffle_buffer,
        seed=seed * 1_000_003 + host,
    )

    def examples() -> Iterator[Example]:
        with reader:
            for i, record in enumerate(reader):
                if data_sharded and i % n_hosts != host:
                    continue
                yield decode_fn(record)

    if data_sharded and shuffle_buffer > 1:
        examples = _shuffled(
            examples, shuffle_buffer,
            np.random.default_rng(seed * 1_000_003 + host),
        )

    if batch_size is None:
        yield from examples()
        return

    stack: list[Example] = []
    for ex in examples():
        stack.append(ex)
        if len(stack) == batch_size:
            yield {
                k: np.stack([e[k] for e in stack]) for k in stack[0]
            }
            stack = []
    if stack and not drop_remainder:
        yield {k: np.stack([e[k] for e in stack]) for k in stack[0]}


def write_record_shards(
    examples: Iterator[Example],
    path_template: str,  # e.g. "/data/train-{:05d}.rec"
    *,
    num_shards: int,
) -> list[str]:
    """Round-robin examples into ``num_shards`` record files; returns paths."""
    paths = [path_template.format(i) for i in range(num_shards)]
    writers = [RecordWriter(p) for p in paths]
    try:
        for i, ex in enumerate(examples):
            write_example(writers[i % num_shards], ex)
    finally:
        for w in writers:
            w.close()
    return paths


def repeated_record_dataset(
    files: Sequence[str],
    ctx: InputContext | None = None,
    *,
    batch_size: int | None = None,
    policy: str = "AUTO",
    decode_fn: Callable[[bytes], Example] = decode_example,
    shuffle_buffer: int = 0,
    seed: int = 0,
    on_epoch=None,
) -> Iterator[Example]:
    """Endless epoch-cycling stream over record files (tf.data ``repeat()``).

    Finite files must not end training with StopIteration; each epoch
    reshuffles with ``seed + epoch``.  ``on_epoch(epoch)`` (optional) is
    called after each completed pass — the trainer logs it.
    """
    epoch = 0
    while True:
        yielded = False
        for batch in record_dataset(
            files, ctx, batch_size=batch_size, policy=policy,
            decode_fn=decode_fn, shuffle_buffer=shuffle_buffer,
            seed=seed + epoch,
        ):
            yielded = True
            yield batch
        if not yielded:
            # drop_remainder batching of an undersized shard: without this
            # the loop would re-read the files forever yielding nothing.
            raise ValueError(
                f"record epoch produced 0 batches from {len(files)} files "
                f"(batch_size={batch_size}): this host's shard holds fewer "
                "examples than one batch — shrink the batch or add data"
            )
        epoch += 1
        if on_epoch is not None:
            on_epoch(epoch)
