"""ctypes bindings for the native (C++) data-loader kernels.

Loads ``_build/libpicotron_data.<source hash>.so``, building it with g++ when
no library for THIS ``dataloader.cc`` exists yet (``_build/`` is not
committed, and a copied tree keeps no meaningful mtimes, so the library is
keyed by the source's content: a stale one is never loaded). Every binding
has a numpy fallback in ``picotron_tpu.data`` producing bitwise-identical
results, so the framework runs unchanged where a toolchain is unavailable;
``loader()`` says which of the two is in use; set
``PICOTRON_DISABLE_NATIVE=1`` to force the fallback.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "dataloader.cc")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, "_build", f"libpicotron_data.{digest}.so")


_SO = _so_path()

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _build() -> bool:
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    # Compile to a per-pid temp name and atomically rename into place:
    # concurrent first importers (e.g. the sweep launcher starting several
    # trainers) must never dlopen a half-written .so.
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    i64 = ctypes.c_int64
    lib.affine_chain.argtypes = [i32p, u8p, i64p, i64, i64, i64, i64]
    lib.affine_chain.restype = None
    lib.gather_batch.argtypes = [i32p, i64, i64p, i64, i32p, i32p]
    lib.gather_batch.restype = None
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, or None when disabled/unbuildable."""
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("PICOTRON_DISABLE_NATIVE") == "1":
        return None
    if not os.path.exists(_SO) and not _build():
        return None
    try:
        _lib = _declare(ctypes.CDLL(_SO))
    except OSError:
        _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None


def loader() -> str:
    """Which implementation serves the data-loader kernels in this process."""
    return "native (g++)" if available() else "numpy fallback"


def affine_chain(toks: np.ndarray, jumps: np.ndarray, jump_vals: np.ndarray,
                 a: int, b: int, vocab: int) -> None:
    """In-place sequential chain fill; toks[0] must be pre-set."""
    lib = get_lib()
    assert lib is not None
    lib.affine_chain(toks, jumps, jump_vals, len(toks), a, b, vocab)


def gather_batch(samples: np.ndarray, indices: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
    """samples [n, chunk] int32, indices [rows] int64 ->
    (input_ids, target_ids) each [rows, chunk-1] int32."""
    lib = get_lib()
    assert lib is not None
    n_rows, chunk = len(indices), samples.shape[1]
    input_ids = np.empty((n_rows, chunk - 1), np.int32)
    target_ids = np.empty((n_rows, chunk - 1), np.int32)
    lib.gather_batch(samples, chunk, indices, n_rows, input_ids, target_ids)
    return input_ids, target_ids
