"""Locate, build (if needed), and load ``libdtf_native.so``.

Build-on-demand keeps the no-network constraint honest: the .so is compiled
from the in-repo C++ sources with the system g++, never downloaded.  The
build is cheap (<5s) and happens at most once per state of the sources;
concurrent builders (e.g. pytest-xdist, multi-process tests) are serialized
with an exclusive lock file.

Whether a built .so is current is decided by CONTENT: a sidecar file holds
the hash of the sources it was compiled from.  File times say nothing once
a tree has been copied (the chip tool copies ignored files too, so a .so
built here from other sources would otherwise ride along and win).
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import subprocess
from pathlib import Path

logger = logging.getLogger("distributedtensorflow_tpu")

_PACKAGE_DIR = Path(__file__).resolve().parent
_NATIVE_DIR = _PACKAGE_DIR.parent.parent / "native"
_SOURCES = ("src/crc32c.cc", "src/recordio.cc", "src/ringcomm.cc")

_lib: ctypes.CDLL | None = None


def _lib_path() -> Path:
    override = os.environ.get("DTF_NATIVE_LIB")
    if override:
        return Path(override)
    return _NATIVE_DIR / "libdtf_native.so"


def _sources_hash() -> str:
    h = hashlib.sha256()
    for rel in _SOURCES + ("src/crc32c.h",):
        h.update(rel.encode())
        h.update((_NATIVE_DIR / rel).read_bytes())
    return h.hexdigest()


def _hash_path(so: Path) -> Path:
    return so.with_suffix(".so.srchash")


def _needs_build(so: Path) -> bool:
    if not so.exists():
        return True
    if os.environ.get("DTF_NATIVE_LIB"):
        return False  # a prebuilt library named from outside is used as is
    try:
        return _hash_path(so).read_text().strip() != _sources_hash()
    except FileNotFoundError:
        return True


def build_native_library(force: bool = False) -> Path:
    """Compile the shared library from ``native/src`` if missing or stale."""
    so = _lib_path()
    if not force and not _needs_build(so):
        return so
    if not (_NATIVE_DIR / "src").is_dir():
        raise FileNotFoundError(
            f"native sources not found under {_NATIVE_DIR}; set DTF_NATIVE_LIB "
            "to a prebuilt libdtf_native.so"
        )
    so.parent.mkdir(parents=True, exist_ok=True)
    lock_path = so.with_suffix(".lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if not force and not _needs_build(so):
                return so  # another process built it while we waited
            # Link to a temp path and atomically rename: a concurrent
            # process's lock-free _needs_build() fast path must never see
            # (and dlopen) a half-written .so.
            tmp = so.with_suffix(f".tmp.{os.getpid()}.so")
            cmd = [
                os.environ.get("CXX", "g++"),
                "-O3", "-std=c++17", "-fPIC", "-Wall", "-Wextra", "-pthread",
                *[str(_NATIVE_DIR / s) for s in _SOURCES],
                "-shared", "-pthread", "-o", str(tmp),
            ]
            logger.info("building native library: %s", " ".join(cmd))
            subprocess.run(cmd, check=True, capture_output=True, text=True)
            # invalidate first: a crash between the two renames must leave
            # "stale", never a new hash beside an old library
            _hash_path(so).unlink(missing_ok=True)
            os.replace(tmp, so)
            _hash_path(so).write_text(_sources_hash() + "\n")
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native build failed:\n{e.stderr}"
            ) from e
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return so


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    u8p = c.POINTER(c.c_uint8)
    # record IO
    lib.dtf_writer_open.restype = c.c_void_p
    lib.dtf_writer_open.argtypes = [c.c_char_p]
    lib.dtf_writer_write.restype = c.c_int
    lib.dtf_writer_write.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.dtf_writer_flush.restype = c.c_int
    lib.dtf_writer_flush.argtypes = [c.c_void_p]
    lib.dtf_writer_close.restype = None
    lib.dtf_writer_close.argtypes = [c.c_void_p]
    lib.dtf_reader_open.restype = c.c_void_p
    lib.dtf_reader_open.argtypes = [
        c.POINTER(c.c_char_p), c.c_int, c.c_int, c.c_int, c.c_uint64, c.c_int,
    ]
    lib.dtf_reader_next.restype = c.c_int64
    lib.dtf_reader_next.argtypes = [c.c_void_p, c.POINTER(u8p)]
    lib.dtf_reader_next_packed.restype = c.c_int64
    lib.dtf_reader_next_packed.argtypes = [
        c.c_void_p, c.POINTER(u8p), c.POINTER(c.POINTER(c.c_uint64)),
        c.c_int64, c.c_int64,
    ]
    lib.dtf_reader_batch_records.restype = c.c_int64
    lib.dtf_reader_batch_records.argtypes = []
    lib.dtf_reader_batch_bytes.restype = c.c_int64
    lib.dtf_reader_batch_bytes.argtypes = []
    lib.dtf_reader_close.restype = None
    lib.dtf_reader_close.argtypes = [c.c_void_p]
    lib.dtf_free.restype = None
    lib.dtf_free.argtypes = [c.c_void_p]
    lib.dtf_crc32c.restype = c.c_uint32
    lib.dtf_crc32c.argtypes = [c.c_char_p, c.c_uint64]
    lib.dtf_crc32c_masked.restype = c.c_uint32
    lib.dtf_crc32c_masked.argtypes = [c.c_char_p, c.c_uint64]
    # ring collectives
    lib.dtf_comm_create.restype = c.c_void_p
    lib.dtf_comm_create.argtypes = [
        c.c_int, c.c_int, c.POINTER(c.c_char_p), c.c_int,
    ]
    lib.dtf_comm_rank.restype = c.c_int
    lib.dtf_comm_rank.argtypes = [c.c_void_p]
    lib.dtf_comm_size.restype = c.c_int
    lib.dtf_comm_size.argtypes = [c.c_void_p]
    lib.dtf_comm_destroy.restype = None
    lib.dtf_comm_destroy.argtypes = [c.c_void_p]
    lib.dtf_comm_allreduce.restype = c.c_int
    lib.dtf_comm_allreduce.argtypes = [
        c.c_void_p, c.c_void_p, c.c_uint64, c.c_int, c.c_int,
    ]
    lib.dtf_comm_allgather.restype = c.c_int
    lib.dtf_comm_allgather.argtypes = [
        c.c_void_p, c.c_void_p, c.c_uint64, c.c_void_p,
    ]
    lib.dtf_comm_broadcast.restype = c.c_int
    lib.dtf_comm_broadcast.argtypes = [
        c.c_void_p, c.c_void_p, c.c_uint64, c.c_int,
    ]
    lib.dtf_comm_barrier.restype = c.c_int
    lib.dtf_comm_barrier.argtypes = [c.c_void_p]
    return lib


def load_native_library() -> ctypes.CDLL:
    """Load (building first if necessary) the native library, once."""
    global _lib
    if _lib is None:
        _lib = _declare(ctypes.CDLL(str(build_native_library())))
    return _lib


def native_available() -> bool:
    """True when the native library can be loaded on this machine."""
    try:
        load_native_library()
        return True
    except Exception as e:  # no g++, unwritable checkout, ...
        logger.warning("native library unavailable: %s", e)
        return False
