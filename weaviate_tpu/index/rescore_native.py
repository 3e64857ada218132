"""ctypes bridge to the native float32 rescoring (native/rescore.cpp).

The last step of a compressed dispatch under pq.rescore (index/tpu.py
`_rescore_f32`) scores the candidates the scan selected from the float32
rows the host keeps. `distances` does it in one native pass: every
candidate's row is read from `host_vecs` once and scored in registers,
`[queries, candidates]` float32 is all that is written, the GIL is let go
for the whole call, and a call over enough rows splits them over a few
threads of its own (native/rescore.cpp `rescore_threads`; the source also
documents the fixed summation order that makes the bits independent of the
thread count).

`load()` builds (once a checkout) and loads the library; an index calls it
when it enters the compressed form, so no request ever compiles. A request
only asks `distances`, which serves from a library that is loaded and says
why where it cannot (`no_library`, `layout`, `metric`): the caller's numpy
path then serves, and `/debug/perf` `rescore.by` counts both.
"""

from __future__ import annotations

import ctypes
import logging
import threading
from typing import Optional

import numpy as np

from weaviate_tpu import _native
from weaviate_tpu.entities import vectorindex as vi

# the library's metric numbers (native/rescore.cpp `Metric`)
_METRICS = {vi.DISTANCE_COSINE: 0, vi.DISTANCE_DOT: 1, vi.DISTANCE_L2: 2,
            vi.DISTANCE_MANHATTAN: 3}

_lib = None
_lib_failed = False
_lib_lock = threading.Lock()


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            lib = ctypes.CDLL(_native.ensure_built("rescore"))
            lib.rescore_f32.restype = ctypes.c_int
            lib.rescore_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int, ctypes.c_void_p, ctypes.c_int,
            ]
            lib.rescore_threads.restype = ctypes.c_int
            lib.rescore_threads.argtypes = [ctypes.c_int64, ctypes.c_int64]
            _lib = lib
        except Exception as e:  # noqa: BLE001 — the numpy path serves
            _lib_failed = True
            logging.getLogger(__name__).warning(
                "native rescoring unavailable (%s: %s); compressed "
                "dispatches score their candidates through numpy",
                type(e).__name__, e)
        return _lib


def load() -> bool:
    """Build the library where it has to be and load it. For the index's
    set-up (entering the compressed form), never for a request."""
    return _load() is not None


def distances(host_vecs: np.ndarray, slots: np.ndarray, q: np.ndarray,
              metric: str, threads: int = 0
              ) -> tuple[Optional[np.ndarray], Optional[str]]:
    """Float32 distances of candidate rows: host_vecs [capacity, D] f32,
    slots [B, R] int32 (-1 = missing: +inf), q [B, D] f32 -> ([B, R] f32,
    None), or (None, reason) where the native call cannot serve and the
    caller's numpy has to. `threads` 0 leaves the count to the library
    (what the index passes); the tests state one."""
    lib = _lib
    if lib is None:
        return None, "no_library"
    code = _METRICS.get(metric)
    if code is None:
        return None, "metric"
    if (host_vecs.dtype != np.float32 or host_vecs.ndim != 2
            or not host_vecs.flags.c_contiguous
            or q.dtype != np.float32 or q.shape != (slots.shape[0],
                                                    host_vecs.shape[1])):
        return None, "layout"
    # the program's slots are a column block of its packed output: 40 KB
    slots = np.ascontiguousarray(slots, np.int32)
    q = np.ascontiguousarray(q)
    b, r = slots.shape
    out = np.empty((b, r), np.float32)
    ran = lib.rescore_f32(
        host_vecs.ctypes.data, host_vecs.shape[0], host_vecs.shape[1],
        slots.ctypes.data, q.ctypes.data, b, r, code, out.ctypes.data,
        threads)
    if ran < 0:
        return None, "layout"
    return out, None
