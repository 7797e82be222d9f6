"""Python binding for the native async file-I/O engine.

Reference surface: ``deepspeed/ops/op_builder/async_io.py`` (builder) +
``csrc/aio/py_lib/deepspeed_py_aio_handle.cpp`` (``aio_handle`` with
``pread/pwrite/async_pread/async_pwrite/wait``).  The native engine is
``csrc/aio/dst_aio.cpp`` in this repo, compiled on first use with g++
into ``build/`` (not committed; rebuilt when missing or when the hash of
the source recorded beside it differs) and driven through ctypes (no
pybind11 in the toolchain).  Buffers are numpy arrays (pinned-host staging is the
caller's concern — see runtime/swap_tensor/).
"""

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_SRC = os.path.join(_REPO_ROOT, "csrc", "aio", "dst_aio.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "build")
_SO = os.path.join(_BUILD_DIR, "libdst_aio.so")
_SO_HASH = _SO + ".sha256"       # hash of the source the .so was built from

_lib = None
_lib_lock = threading.Lock()


class AsyncIOBuilder:
    """JIT build of the native engine (reference ``OpBuilder.jit_load``)."""

    NAME = "async_io"

    def is_compatible(self) -> bool:
        from shutil import which
        return which("g++") is not None and os.path.exists(_SRC)

    def load(self):
        return _load_lib()

    @staticmethod
    def so_path() -> str:
        return _SO


def _build_if_stale():
    """Compile the engine unless ``build/`` holds one built from this very
    source.  Decided by content hash, not mtime: a copy of the tree (an
    archive, a checkout) does not preserve mtimes.  Each build lands under
    its own temporary name and is renamed into place, so concurrent first
    uses (test workers) never load a half-written file."""
    with open(_SRC, "rb") as f:
        src_hash = hashlib.sha256(f.read()).hexdigest()
    if os.path.exists(_SO) and os.path.exists(_SO_HASH):
        with open(_SO_HASH) as f:
            if f.read().strip() == src_hash:
                return
    os.makedirs(_BUILD_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=_BUILD_DIR, suffix=".so.tmp")
    os.close(fd)
    try:
        subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                        "-pthread", _SRC, "-o", tmp],
                       check=True, capture_output=True)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(tmp + ".hash", "w") as f:
        f.write(src_hash)
    os.replace(tmp + ".hash", _SO_HASH)


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        _build_if_stale()
        lib = ctypes.CDLL(_SO)
        lib.dst_aio_create.restype = ctypes.c_void_p
        lib.dst_aio_create.argtypes = [ctypes.c_int, ctypes.c_long, ctypes.c_int]
        lib.dst_aio_destroy.argtypes = [ctypes.c_void_p]
        for fn in (lib.dst_aio_submit_read, lib.dst_aio_submit_write):
            fn.restype = ctypes.c_long
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_long, ctypes.c_long]
        lib.dst_aio_wait.restype = ctypes.c_int
        lib.dst_aio_wait.argtypes = [ctypes.c_void_p, ctypes.c_long]
        for fn in (lib.dst_aio_sync_pread, lib.dst_aio_sync_pwrite):
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_long, ctypes.c_long]
        _lib = lib
        return _lib


def _buf(arr: np.ndarray):
    assert arr.flags["C_CONTIGUOUS"], "aio buffers must be contiguous"
    return arr.ctypes.data_as(ctypes.c_void_p)


class AIOHandle:
    """The ``aio_handle`` equivalent: sync + async reads/writes of numpy
    buffers against files, with ``wait`` joining async requests."""

    def __init__(self, block_size: int = 1 << 20, queue_depth: int = 32,
                 single_submit: bool = False, overlap_events: bool = True,
                 num_threads: int = 4, use_o_direct: bool = False):
        self.block_size = block_size
        self.queue_depth = queue_depth
        self.single_submit = single_submit
        self.overlap_events = overlap_events
        self.num_threads = num_threads
        lib = _load_lib()
        self._lib = lib
        block = 0 if single_submit else block_size
        self._h = lib.dst_aio_create(num_threads, block, int(use_o_direct))
        self._pending = set()

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self.wait()
                self._lib.dst_aio_destroy(self._h)
                self._h = None
        except Exception:
            pass

    # ---- sync ---------------------------------------------------------- #
    def pread(self, buffer: np.ndarray, path: str, offset: int = 0):
        rc = self._lib.dst_aio_sync_pread(self._h, path.encode(), _buf(buffer),
                                          buffer.nbytes, offset)
        if rc != 0:
            raise OSError(rc, f"aio pread {path!r} failed", path)

    def pwrite(self, buffer: np.ndarray, path: str, offset: int = 0):
        rc = self._lib.dst_aio_sync_pwrite(self._h, path.encode(), _buf(buffer),
                                           buffer.nbytes, offset)
        if rc != 0:
            raise OSError(rc, f"aio pwrite {path!r} failed", path)

    # ---- async --------------------------------------------------------- #
    def async_pread(self, buffer: np.ndarray, path: str, offset: int = 0) -> int:
        rid = self._lib.dst_aio_submit_read(self._h, path.encode(), _buf(buffer),
                                            buffer.nbytes, offset)
        self._pending.add(rid)
        return rid

    def async_pwrite(self, buffer: np.ndarray, path: str, offset: int = 0) -> int:
        rid = self._lib.dst_aio_submit_write(self._h, path.encode(), _buf(buffer),
                                             buffer.nbytes, offset)
        self._pending.add(rid)
        return rid

    def wait(self, request_id: Optional[int] = None) -> int:
        """Join one request (or all); returns the number joined."""
        ids = ([request_id] if request_id is not None
               else sorted(self._pending))
        joined = 0
        for rid in ids:
            rc = self._lib.dst_aio_wait(self._h, rid)
            self._pending.discard(rid)
            if rc != 0:
                raise OSError(rc, f"aio request {rid} failed")
            joined += 1
        return joined
