"""ctypes binding for the native row-gather packer (packer.cpp).

The shared library is compiled from ``packer.cpp`` with the system
``g++`` the first time it is needed and kept next to this file under a
name that carries the sha256 of that source, so the library a process
loads is always the one this checkout's source builds — never one left
behind by another tree, an older source or a copy of the directory.
Where no compiler is present or the build fails, ``gather_rows`` uses
numpy and says so: one warning in the log, and ``native_status()`` for
callers that report which implementation packed their cohorts.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

logger = logging.getLogger("fedml_tpu")

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "packer.cpp"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_fallback_reason = ""


def lib_path() -> Path:
    """Where the library built from this checkout's ``packer.cpp`` lives."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _HERE / f"_libpacker-{digest}.so"


def _build(lib: Path) -> str:
    """Compile ``packer.cpp`` into ``lib``; returns "" or why it failed."""
    # compile to a process-unique temp path and rename into place:
    # concurrent processes (pytest-xdist, multi-process launches) must
    # never dlopen a partially-written .so
    tmp = lib.with_suffix(f".tmp.{os.getpid()}.so")
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, lib)
    except FileNotFoundError:
        return "g++ not found"
    except subprocess.CalledProcessError as e:
        tmp.unlink(missing_ok=True)
        return f"g++ failed: {e.stderr.decode(errors='replace')[-300:]}"
    except (OSError, subprocess.SubprocessError) as e:
        tmp.unlink(missing_ok=True)
        return f"build failed: {e}"
    # libraries built from earlier versions of the source are dead weight
    for old in _HERE.glob("_libpacker*.so"):
        if old != lib and ".tmp." not in old.name:
            old.unlink(missing_ok=True)
    return ""


def _open(path: Path) -> ctypes.CDLL:
    """dlopen ``path`` — only under the name this checkout's source
    hashes to."""
    want = lib_path().name
    if path.name != want:
        raise ValueError(
            f"{path.name} was not built from this checkout's packer.cpp "
            f"(its library is {want})"
        )
    lib = ctypes.CDLL(str(path))
    lib.gather_rows.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32,
    ]
    lib.gather_rows.restype = None
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _fallback_reason
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("FEDML_TPU_NO_NATIVE"):
            _fallback_reason = "FEDML_TPU_NO_NATIVE is set"
            return None
        path = lib_path()
        if not path.exists():
            _fallback_reason = _build(path)
        if not _fallback_reason:
            try:
                _lib = _open(path)
            except OSError as e:
                _fallback_reason = f"dlopen failed: {e}"
        if _fallback_reason:
            logger.warning(
                "native packer unavailable (%s): packing with numpy",
                _fallback_reason,
            )
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_status() -> str:
    """Which implementation ``gather_rows`` runs, and why if not native."""
    if _load() is not None:
        return f"native ({lib_path().name})"
    return f"numpy ({_fallback_reason})"


def gather_rows(
    src: np.ndarray,
    idx: np.ndarray,
    out: Optional[np.ndarray] = None,
    *,
    n_threads: int = 0,
) -> np.ndarray:
    """out[i] = src[idx[i]] over leading-axis rows.

    src must be C-contiguous; idx is any integer array (flattened).
    out, if given, must be C-contiguous with shape
    (idx.size, *src.shape[1:]) and src's dtype.  n_threads=0 picks the
    hardware count.  Returns out.
    """
    if not src.flags.c_contiguous:
        src = np.ascontiguousarray(src)
    flat_idx = np.ascontiguousarray(idx, dtype=np.int64).ravel()
    out_shape = (flat_idx.size, *src.shape[1:])
    if out is None:
        out = np.empty(out_shape, dtype=src.dtype)
    else:
        if out.shape != out_shape or out.dtype != src.dtype:
            raise ValueError(
                f"out has shape {out.shape}/{out.dtype}, "
                f"need {out_shape}/{src.dtype}"
            )
        if not out.flags.c_contiguous:
            raise ValueError("out must be C-contiguous")

    lib = _load()
    if lib is None or src.size == 0 or flat_idx.size == 0:
        if flat_idx.size:
            np.take(src, np.clip(flat_idx, 0, src.shape[0] - 1),
                    axis=0, out=out)
        return out

    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    lib.gather_rows(
        src.ctypes.data_as(ctypes.c_char_p),
        ctypes.c_int64(src.shape[0]),
        flat_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.c_char_p),
        ctypes.c_int64(flat_idx.size),
        ctypes.c_int64(row_bytes),
        ctypes.c_int32(n_threads),
    )
    return out
