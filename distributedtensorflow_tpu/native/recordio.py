"""Python surface over the native record IO (C++ threaded reader/writer).

The record format is the classic length+CRC32C framing, so files written
here are interchangeable with TFRecord files (the reference's on-disk input
format — SURVEY.md §2.3 tf.data).  The reader's multi-file threading and
shuffle buffer run entirely in C++; Python only sees finished ``bytes``.
"""

from __future__ import annotations

import ctypes
import weakref
from collections.abc import Iterator, Sequence

from .lib import load_native_library


def crc32c(data: bytes) -> int:
    """Raw CRC32-C of ``data`` (native: SSE4.2 crc32 instruction when the
    CPU has it, slice-by-8 table fallback)."""
    return load_native_library().dtf_crc32c(data, len(data))


def masked_crc32c(data: bytes) -> int:
    """Masked CRC32-C as stored in the record framing."""
    return load_native_library().dtf_crc32c_masked(data, len(data))


class RecordWriter:
    """Writes length+CRC framed records to one file."""

    def __init__(self, path: str):
        self._lib = load_native_library()
        self._h = self._lib.dtf_writer_open(str(path).encode())
        if not self._h:
            raise OSError(f"cannot open {path!r} for writing")
        # GC safety net: a dropped writer still flushes and closes its FILE*.
        self._finalizer = weakref.finalize(
            self, self._lib.dtf_writer_close, self._h
        )

    def write(self, record: bytes) -> None:
        if self._h is None:
            raise ValueError("writer is closed")
        if self._lib.dtf_writer_write(self._h, record, len(record)) != 0:
            raise OSError("record write failed")

    def flush(self) -> None:
        if self._h is not None:
            self._lib.dtf_writer_flush(self._h)

    def close(self) -> None:
        if self._h is not None:
            self._finalizer.detach()
            self._lib.dtf_writer_close(self._h)
            self._h = None

    def __enter__(self) -> "RecordWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class RecordCorruptionError(IOError):
    """A record failed CRC verification or had broken framing."""


def available_cpus() -> int:
    """CPUs THIS PROCESS may use — affinity/cgroup-aware where the OS
    exposes it (``sched_getaffinity``), else ``cpu_count``.  The single
    definition behind reader-thread defaults and the bench's
    ``hw_concurrency`` field, so the two cannot disagree."""
    import os

    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


class RecordReader:
    """Iterates records from many files with C++ reader threads.

    Args:
      paths: record files; assigned round-robin to reader threads, so with
        ``num_threads > 1`` records from different files interleave (the
        tf.data ``interleave`` behavior).
      num_threads: C++ reader threads (clamped to ``len(paths)``).
      shuffle_buffer: >1 enables streaming shuffle over a buffer of this many
        records (the ``shuffle(buffer_size)`` contract).
      seed: shuffle RNG seed — same seed + same single-threaded file order
        reproduces the same stream.
      verify_crc: verify per-record CRCs (cheap: hardware CRC32C where
        available, slice-by-8 fallback; single pass).

    Note: records cross the FFI boundary in batches (up to 4x the
    producer bounds — ~1024 records / ~8 MB), so a
    :class:`RecordCorruptionError` surfaces at BATCH granularity — up to
    one batch later than the corrupt record itself, after earlier records
    in that window were already yielded.  The trade buys the ~5x
    batched-FFI throughput win over per-record ctypes calls.

    Shards must be IMMUTABLE while a reader is open: regular files are
    mmap-ed for speed, and a concurrent truncation faults (SIGBUS) the
    process instead of surfacing a read error.  (Appending a new shard
    file alongside is fine; rewriting one being read is not — the same
    contract as the reference's record readers.)
    """

    def __init__(
        self,
        paths: Sequence[str],
        *,
        num_threads: int = 1,
        shuffle_buffer: int = 0,
        seed: int = 0,
        verify_crc: bool = True,
    ):
        if not paths:
            raise ValueError("RecordReader needs at least one file")
        self._lib = load_native_library()
        arr = (ctypes.c_char_p * len(paths))(
            *[str(p).encode() for p in paths]
        )
        self._h = self._lib.dtf_reader_open(
            arr, len(paths), num_threads, shuffle_buffer, seed, int(verify_crc)
        )
        if not self._h:
            raise OSError(f"cannot open record files {list(paths)!r}")
        # Batched pulls: one FFI round-trip per ~batch of records (the
        # per-record ctypes path is slower than plain Python file reads).
        # _pending holds sliced-out records.
        self._pending: list[bytes] = []
        self._pending_ix = 0
        # GC safety net: a dropped, unexhausted reader still joins its C++
        # worker threads and frees queued records.
        self._finalizer = weakref.finalize(
            self, self._lib.dtf_reader_close, self._h
        )

    def __iter__(self) -> Iterator[bytes]:
        return self

    def __next__(self) -> bytes:
        if self._pending_ix < len(self._pending):
            rec = self._pending[self._pending_ix]
            self._pending_ix += 1
            return rec
        if self._h is None:
            raise StopIteration
        buf = ctypes.POINTER(ctypes.c_uint8)()
        lens = ctypes.POINTER(ctypes.c_uint64)()
        # Limits >= the producer's packing bounds (read from the C ABI so
        # the two can't drift apart) keep the handoff zero-copy in C.
        n = self._lib.dtf_reader_next_packed(
            self._h, ctypes.byref(buf), ctypes.byref(lens),
            4 * self._lib.dtf_reader_batch_records(),
            4 * self._lib.dtf_reader_batch_bytes(),
        )
        if n == 0:
            self.close()
            raise StopIteration
        if n == -2:
            self.close()
            raise RecordCorruptionError(
                "corrupt record encountered (bad CRC or framing)"
            )
        try:
            sizes = lens[:n]
            # One bulk copy, then C-speed bytes slicing.  (Measured faster
            # than per-record ctypes.string_at despite the extra copy: a
            # ctypes call costs ~1us while a ~KB memcpy costs ~50ns; the
            # <=8MB blob is transient.)
            blob = ctypes.string_at(buf, sum(sizes))
        finally:
            self._lib.dtf_free(buf)
            self._lib.dtf_free(lens)
        out, off = [], 0
        for size in sizes:
            out.append(blob[off:off + size])
            off += size
        self._pending = out
        self._pending_ix = 1
        return out[0]

    def read_batches(self):
        """Yield ``(payload, lengths)`` batch VIEWS — the zero-copy path.

        ``payload`` is a uint8 numpy view over the C batch buffer
        (concatenated record bytes); ``lengths`` a uint64 numpy view of
        per-record lengths (offsets = ``np.cumsum(lengths)``).  One FFI
        round-trip per producer batch (~256 records) and **no per-record
        Python object creation** — on a single core the per-record
        ``bytes`` construction is what pins the iterator API at
        pure-Python speed, so fixed-shape/tokenized
        consumers that can slice numpy views should use this.

        Both views alias memory that is FREED when the generator advances
        or closes — copy (``payload.copy()``) anything that must outlive
        the iteration step.  Do not interleave with the per-record
        iterator on the same reader: both consume the same stream.
        """
        import numpy as np

        lib = self._lib
        while self._h is not None:
            buf = ctypes.POINTER(ctypes.c_uint8)()
            lens = ctypes.POINTER(ctypes.c_uint64)()
            # exact producer bounds -> every pull is a whole-batch handoff
            n = lib.dtf_reader_next_packed(
                self._h, ctypes.byref(buf), ctypes.byref(lens),
                lib.dtf_reader_batch_records(),
                lib.dtf_reader_batch_bytes(),
            )
            if n == 0:
                self.close()
                return
            if n == -2:
                self.close()
                raise RecordCorruptionError(
                    "corrupt record encountered (bad CRC or framing)"
                )
            try:
                lengths = np.ctypeslib.as_array(lens, shape=(n,))
                payload = np.ctypeslib.as_array(
                    buf, shape=(int(lengths.sum()),)
                )
                yield payload, lengths
            finally:
                lib.dtf_free(buf)
                lib.dtf_free(lens)

    def close(self) -> None:
        if self._h is not None:
            self._finalizer.detach()
            self._lib.dtf_reader_close(self._h)
            self._h = None

    def __enter__(self) -> "RecordReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
