"""ctypes binding for the chainermn_native C++ runtime.

Reference parity: the Cython NCCL binding + CuPy pack/unpack kernels were the
reference's compiled layer (SURVEY.md §2.2). On TPU the collectives are
XLA's, so the compiled layer here covers the host data path:
``pack``/``unpack`` (the ``_memory_utility`` analog), threaded
``gather_rows`` (batch assembly), and the double-buffered prefetch loader
(see chainermn_tpu/training/loader.py).

Builds lazily with g++ on first use (pybind11 is not in the toolchain; a
plain C ABI + ctypes is) from ``native/chainermn_native.cpp`` — the
library is gitignored, so a checkout always builds its own. When the
build fails the numpy implementations take over (same semantics, fewer
threads) and a ``RuntimeWarning`` carries the compiler's message.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import List, Optional, Sequence

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")


def _build_and_load() -> Optional[ctypes.CDLL]:
    so = os.path.join(_SRC_DIR, "libchainermn_native.so")
    src = os.path.join(_SRC_DIR, "chainermn_native.cpp")
    if not os.path.exists(so) or (
        os.path.getmtime(src) > os.path.getmtime(so)
    ):
        # concurrent first users each build, then publish atomically
        tmp = f"{so[:-3]}.{os.getpid()}.tmp.so"
        try:
            subprocess.run(
                ["g++", "-O3", "-std=c++17", "-fPIC", "-pthread", "-shared",
                 "-o", tmp, src],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp, so)
        except (OSError, subprocess.SubprocessError) as e:
            err = getattr(e, "stderr", None) or b""
            warnings.warn(
                f"chainermn_native: building {src} failed ({e}); the numpy "
                f"fallbacks take over.\n{err.decode('utf-8', 'replace')}",
                RuntimeWarning)
            return None
    lib = ctypes.CDLL(so)

    i64p = ctypes.POINTER(ctypes.c_int64)
    vpp = ctypes.POINTER(ctypes.c_void_p)
    lib.cmn_pack.argtypes = [vpp, i64p, i64p, ctypes.c_int64,
                             ctypes.c_void_p, ctypes.c_int]
    lib.cmn_unpack.argtypes = [ctypes.c_void_p, vpp, i64p, i64p,
                               ctypes.c_int64, ctypes.c_int]
    lib.cmn_gather_rows.argtypes = [ctypes.c_void_p, ctypes.c_int64, i64p,
                                    ctypes.c_int64, ctypes.c_void_p,
                                    ctypes.c_int]
    lib.cmn_loader_create.restype = ctypes.c_void_p
    lib.cmn_loader_create.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int64, ctypes.c_int64,
                                      ctypes.c_int64, ctypes.c_int,
                                      ctypes.c_int]
    lib.cmn_loader_submit.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64]
    lib.cmn_loader_next.restype = ctypes.c_int
    lib.cmn_loader_next.argtypes = [ctypes.c_void_p, vpp, vpp]
    lib.cmn_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.cmn_loader_destroy.argtypes = [ctypes.c_void_p]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if not _TRIED:
            _LIB = _build_and_load()
            _TRIED = True
        return _LIB


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# pack / unpack (reference: _memory_utility.pack_params / unpack_params)
# ---------------------------------------------------------------------------


def pack(arrays: Sequence[np.ndarray], n_threads: int = 4) -> np.ndarray:
    """Concatenate arrays' bytes into one flat uint8 buffer."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    sizes = np.array([a.nbytes for a in arrays], dtype=np.int64)
    offsets = np.zeros_like(sizes)
    np.cumsum(sizes[:-1], out=offsets[1:])
    flat = np.empty(int(sizes.sum()), dtype=np.uint8)
    lib = get_lib()
    if lib is None:
        for a, o, s in zip(arrays, offsets, sizes):
            flat[o:o + s] = a.view(np.uint8).reshape(-1)
        return flat
    srcs = (ctypes.c_void_p * len(arrays))(
        *[a.ctypes.data for a in arrays])
    lib.cmn_pack(srcs, sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                 offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                 len(arrays), flat.ctypes.data, n_threads)
    return flat


def unpack(flat: np.ndarray, like: Sequence[np.ndarray],
           n_threads: int = 4) -> List[np.ndarray]:
    """Split a flat uint8 buffer back into arrays shaped like ``like``."""
    sizes = np.array([a.nbytes for a in like], dtype=np.int64)
    offsets = np.zeros_like(sizes)
    np.cumsum(sizes[:-1], out=offsets[1:])
    outs = [np.empty_like(a) for a in like]
    lib = get_lib()
    if lib is None:
        for o, off, s in zip(outs, offsets, sizes):
            o.view(np.uint8).reshape(-1)[:] = flat[off:off + s]
        return outs
    dsts = (ctypes.c_void_p * len(outs))(*[o.ctypes.data for o in outs])
    lib.cmn_unpack(flat.ctypes.data, dsts,
                   sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                   offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                   len(outs), n_threads)
    return outs


# ---------------------------------------------------------------------------
# row gather (batch assembly primitive)
# ---------------------------------------------------------------------------


def gather_rows(base: np.ndarray, indices: np.ndarray,
                out: Optional[np.ndarray] = None,
                n_threads: int = 4) -> np.ndarray:
    """out[i] = base[indices[i]] — threaded when the native lib is up."""
    base = np.ascontiguousarray(base)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    if out is None:
        out = np.empty((len(indices),) + base.shape[1:], base.dtype)
    lib = get_lib()
    if lib is None:
        np.take(base, indices, axis=0, out=out)
        return out
    row_bytes = base.dtype.itemsize * int(np.prod(base.shape[1:], initial=1))
    lib.cmn_gather_rows(
        base.ctypes.data, row_bytes,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(indices), out.ctypes.data, n_threads)
    return out
