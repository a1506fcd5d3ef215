"""ctypes bindings for the native wavekit kernels (see wavekit.cpp).

The library is BUILT from ``wavekit.cpp`` by this module the first time
it is imported (g++, ~1 s) and cached next to the source under a name
that carries the source's hash, so a given commit always runs the same
input path: a stale or foreign ``.so`` lying ignored in the checkout is
never picked up, and a failed build is an error, not a silent switch to
numpy (which normalizes in float64, not float32). ``SEIST_TPU_NATIVE=0``
is the explicit choice of the pure-numpy implementations.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "wavekit.cpp")


def lib_path() -> str:
    """Where the library for THIS source lives (hash-named)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libwavekit-{digest}.so")


def _build(path: str) -> None:
    """Compile wavekit.cpp to ``path`` atomically (concurrent importers —
    loader worker processes, xdist workers — each build to a private temp
    name and rename; last writer wins with identical bytes)."""
    fd, tmp = tempfile.mkstemp(prefix="libwavekit-", suffix=".so.tmp", dir=_DIR)
    os.close(fd)
    try:
        subprocess.run(
            [
                os.environ.get("CXX", "g++"),
                "-O3", "-fPIC", "-shared", "-std=c++17",
                "-o", tmp, _SRC,
            ],
            check=True,
            capture_output=True,
            text=True,
        )
        os.replace(tmp, path)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {path} from wavekit.cpp failed (SEIST_TPU_NATIVE=0 "
            f"selects the numpy path explicitly):\n{e.stderr}"
        ) from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> Optional[ctypes.CDLL]:
    if os.environ.get("SEIST_TPU_NATIVE", "auto") == "0":
        return None
    path = lib_path()
    if not os.path.exists(path):
        _build(path)
    lib = ctypes.CDLL(path)
    lib.znorm_f32.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
    ]
    lib.soft_label_add_f64.argtypes = [
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64,
    ]
    return lib


_lib: Optional[ctypes.CDLL] = _load()


def available() -> bool:
    return _lib is not None


_NORM_MODES = {"std": 0, "max": 1, "": 2}


def znorm(data: np.ndarray, mode: str) -> bool:
    """In-place per-channel normalize of a C-contiguous (C, L) float32
    array. Returns False (caller should use numpy) when unsupported."""
    if (
        _lib is None
        or data.dtype != np.float32
        or not data.flags.c_contiguous
        or data.ndim != 2
        or mode not in _NORM_MODES
    ):
        return False
    _lib.znorm_f32(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        data.shape[0],
        data.shape[1],
        _NORM_MODES[mode],
    )
    return True


def soft_label_add(
    out: np.ndarray, idxs: np.ndarray, window: np.ndarray, width: int
) -> bool:
    """Add label windows into ``out`` (float64, length L) at ``idxs``.
    Returns False when the native path is unavailable (including windows
    wider than the array — the numpy path raises loudly on that config and
    the native kernel must not silently clip it)."""
    if (
        _lib is None
        or out.dtype != np.float64
        or not out.flags.c_contiguous
        or width + 1 > out.shape[0]
    ):
        return False
    idxs = np.ascontiguousarray(idxs, dtype=np.int64)
    window = np.ascontiguousarray(window, dtype=np.float64)
    _lib.soft_label_add_f64(
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        out.shape[0],
        idxs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        idxs.shape[0],
        window.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        width,
    )
    return True
