"""ctypes binding to the native storage engine (native/storage_engine.cpp).

Builds the shared library on demand with g++ (no pip deps) and falls back
cleanly to the pure-Python paths when a toolchain is unavailable. The
checksum implementations are bit-identical (RFC 7693 keyed BLAKE2b-128),
verified by tests/test_native.py against hashlib.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "native", "storage_engine.cpp")
_LIB = os.path.join(_REPO, "native", "libtb_storage.so")
_HDR = os.path.join(_REPO, "native", "blake2b.h")
_CLIENT_SRC = os.path.join(_REPO, "native", "tb_client.cpp")
_CLIENT_LIB = os.path.join(_REPO, "native", "libtb_client.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _source_hash(src: str) -> str:
    """Content hash of what `src` compiles from (itself + blake2b.h)."""
    h = hashlib.sha256()
    for path in (src, _HDR):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build(src: str, lib: str, *extra: str) -> bool:
    """Compile `src` into `lib` and record the hash of the sources it
    was built from. A failed build is reported, never silent: the
    pure-Python engine is correct but it is not what was asked for."""
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-fPIC", "-shared", *extra, "-o", tmp, src],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib)
        with open(tmp, "w") as f:
            f.write(_source_hash(src))
        os.replace(tmp, lib + ".sha256")
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        logging.getLogger("tigerbeetle_tpu.native").warning(
            "native build of %s failed (%r) %s — serving from the "
            "pure-Python engine", os.path.basename(src), e,
            detail.decode(errors="replace")[-400:])
        return False


def _stale(lib: str, src: str) -> bool:
    """True unless `lib` was built from exactly the present sources
    (keyed on their content hash: mtimes say nothing in a fresh
    checkout or a copied tree)."""
    try:
        with open(lib + ".sha256") as f:
            built_from = f.read().strip()
    except OSError:
        return True
    return not os.path.exists(lib) or built_from != _source_hash(src)


def load() -> Optional[ctypes.CDLL]:
    """The loaded library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SRC):
            return None
        if _stale(_LIB, _SRC):
            if not _build(_SRC, _LIB, "-pthread"):
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            return None
        u64 = ctypes.c_uint64
        u32 = ctypes.c_uint32
        p = ctypes.c_char_p
        lib.tbs_checksum.argtypes = [p, u64, p, u64, p]
        lib.tbs_open.argtypes = [p, u64, ctypes.c_int]
        lib.tbs_open.restype = ctypes.c_int
        lib.tbs_close.argtypes = [ctypes.c_int]
        lib.tbs_read.argtypes = [ctypes.c_int, u64, p, u64]
        lib.tbs_read.restype = ctypes.c_int64
        lib.tbs_write.argtypes = [ctypes.c_int, u64, p, u64]
        lib.tbs_write.restype = ctypes.c_int64
        lib.tbs_sync.argtypes = [ctypes.c_int]
        lib.tbs_wal_scan.argtypes = [
            ctypes.c_int, u64, u64, u32, u64, p, u64, p, u64, p, p, p]
        lib.tbs_wal_scan.restype = ctypes.c_int
        lib.tbs_wal_append.argtypes = [
            ctypes.c_int, u64, u64, u32, u64, p, u64]
        lib.tbs_wal_append.restype = ctypes.c_int
        vp = ctypes.c_void_p
        lib.tbio_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.tbio_create.restype = vp
        lib.tbio_submit_write.argtypes = [vp, u64, p, u64]
        lib.tbio_submit_write.restype = ctypes.c_long
        lib.tbio_submit_write_pair.argtypes = [vp, u64, p, u64, u64, p, u64]
        lib.tbio_submit_write_pair.restype = ctypes.c_long
        lib.tbio_submit_read.argtypes = [vp, u64, u64]
        lib.tbio_submit_read.restype = ctypes.c_long
        lib.tbio_poll.argtypes = [vp, ctypes.POINTER(u64), ctypes.c_long]
        lib.tbio_poll.restype = ctypes.c_long
        lib.tbio_fetch.argtypes = [vp, u64, p, u64]
        lib.tbio_fetch.restype = ctypes.c_long
        lib.tbio_drain.argtypes = [vp, ctypes.c_int]
        lib.tbio_drain.restype = ctypes.c_int
        lib.tbio_destroy.argtypes = [vp]
        _lib = lib
        return _lib


def checksum_native(data: bytes, key: bytes) -> Optional[int]:
    lib = load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(16)
    lib.tbs_checksum(data, len(data), key, len(key), out)
    return int.from_bytes(out.raw, "little")


class NativeFile:
    """Native pread/pwrite file handle (storage engine core)."""

    def __init__(self, path: str, size: int, create: bool):
        lib = load()
        assert lib is not None, "native engine unavailable"
        self.lib = lib
        self.fd = lib.tbs_open(path.encode(), size, 1 if create else 0)
        if self.fd < 0:
            raise OSError(f"tbs_open failed for {path}")

    def read(self, offset: int, size: int) -> bytes:
        buf = ctypes.create_string_buffer(size)
        n = self.lib.tbs_read(self.fd, offset, buf, size)
        if n < 0:
            raise OSError("tbs_read failed")
        return buf.raw

    def write(self, offset: int, data: bytes) -> None:
        if self.lib.tbs_write(self.fd, offset, data, len(data)) < 0:
            raise OSError("tbs_write failed")

    def sync(self) -> None:
        if self.lib.tbs_sync(self.fd) != 0:
            raise OSError("tbs_sync (fsync) failed")

    def close(self) -> None:
        if self.fd >= 0:
            self.lib.tbs_close(self.fd)
            self.fd = -1

    # -------------------------------------------------------------- WAL ops

    def wal_scan(self, hdr_zone_off: int, prep_zone_off: int,
                 slot_count: int, prepare_size_max: int,
                 hdr_key: bytes, body_key: bytes):
        """Returns (states: bytes[slot_count], headers: bytes)."""
        headers = ctypes.create_string_buffer(slot_count * 256)
        states = ctypes.create_string_buffer(slot_count)
        scratch = ctypes.create_string_buffer(prepare_size_max + 256)
        rc = self.lib.tbs_wal_scan(
            self.fd, hdr_zone_off, prep_zone_off, slot_count,
            prepare_size_max, hdr_key, len(hdr_key), body_key, len(body_key),
            headers, states, scratch)
        if rc != 0:
            raise OSError("tbs_wal_scan failed")
        return states.raw, headers.raw

    def wal_append(self, hdr_zone_off: int, prep_zone_off: int, slot: int,
                   prepare_size_max: int, msg: bytes) -> None:
        rc = self.lib.tbs_wal_append(
            self.fd, hdr_zone_off, prep_zone_off, slot, prepare_size_max,
            msg, len(msg))
        if rc != 0:
            raise OSError("tbs_wal_append failed")


def available() -> bool:
    return load() is not None


# ------------------------------------------------------- tb_client library

_client_lock = threading.Lock()
_client_lib: Optional[ctypes.CDLL] = None
_client_tried = False


def load_client() -> Optional[ctypes.CDLL]:
    """The native tb_client library (native/tb_client.cpp), built on
    demand; None when unavailable."""
    global _client_lib, _client_tried
    with _client_lock:
        if _client_lib is not None or _client_tried:
            return _client_lib
        _client_tried = True
        if not os.path.exists(_CLIENT_SRC):
            return None
        if _stale(_CLIENT_LIB, _CLIENT_SRC):
            if not _build(_CLIENT_SRC, _CLIENT_LIB, "-pthread"):
                return None
        try:
            lib = ctypes.CDLL(_CLIENT_LIB)
        except OSError:
            return None
        _client_lib = lib
        return _client_lib


class AsyncEngine:
    """Submission/completion IO engine over a native file descriptor
    (native/storage_engine.cpp tbio_* — the io_uring-shaped layer,
    reference: src/io/linux.zig). Writes copy their payload at submit;
    drain() is the completion + durability barrier."""

    def __init__(self, native_file: "NativeFile", workers: int = 4):
        self.lib = native_file.lib
        self.handle = self.lib.tbio_create(native_file.fd, workers)
        if not self.handle:
            raise OSError("tbio_create failed")

    def submit_write(self, offset: int, data: bytes) -> int:
        op = self.lib.tbio_submit_write(self.handle, offset, data, len(data))
        assert op > 0
        return op

    def submit_read(self, offset: int, size: int) -> int:
        op = self.lib.tbio_submit_read(self.handle, offset, size)
        assert op > 0
        return op

    def submit_write_pair(self, off1: int, data1: bytes,
                          off2: int, data2: bytes) -> int:
        """Tracked ordered write pair (the async WAL append: prepare body
        strictly before its redundant header); completion via poll/fetch."""
        op = self.lib.tbio_submit_write_pair(
            self.handle, off1, data1, len(data1), off2, data2, len(data2))
        assert op > 0
        return op

    def submit_write_tracked(self, offset: int, data: bytes) -> int:
        """Tracked single write (a pair with an empty second leg): the
        caller reaps the completion via fetch — used where the reader
        needs to wait on ONE write, not the whole engine."""
        op = self.lib.tbio_submit_write_pair(
            self.handle, offset, data, len(data), 0, b"", 0)
        assert op > 0
        return op

    def poll(self, max_ids: int = 4096) -> list[int]:
        """Nonblocking: ids of completions ready to fetch (reads and
        tracked writes). The window must exceed any realistic number of
        unreaped completions, or tokens beyond it are invisible to
        callers that gate progress on them."""
        arr = (ctypes.c_uint64 * max_ids)()
        n = self.lib.tbio_poll(self.handle, arr, max_ids)
        return [int(arr[i]) for i in range(n)]

    def fetch(self, op_id: int, size: int = 0) -> bytes:
        buf = ctypes.create_string_buffer(size) if size else None
        n = self.lib.tbio_fetch(self.handle, op_id, buf, size)
        if n == -2:
            raise KeyError(f"async op {op_id} unknown or already fetched")
        if n < 0:
            raise OSError(f"async op {op_id} failed ({n})")
        return buf.raw[:n] if buf is not None else b""

    def drain(self, sync: bool = False) -> None:
        rc = self.lib.tbio_drain(self.handle, 1 if sync else 0)
        if rc != 0:
            # Distinct from IOError: block-level IOError is handled by
            # repair paths; a failed async WRITE means durability is
            # compromised and must propagate (the failure is sticky in
            # the engine — every later drain re-reports it).
            raise RuntimeError(
                "async write failed (sticky): storage compromised")

    def close(self) -> None:
        if self.handle:
            self.lib.tbio_destroy(self.handle)
            self.handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
