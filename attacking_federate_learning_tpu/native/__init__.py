"""Native (C++) host-runtime kernels, built on demand.

The TPU compute path is JAX/XLA; this package holds the *host*
runtime's native kernels — currently the incremental exact Bulyan
selection (bulyan_select.cpp), which turns the reference's O(n^3)
sequential selection (reference defences.py:55-70) into O(n^2) total so
exact-semantics Bulyan is tractable at the 10k-client north star.

Build model: ``g++ -O3 -shared`` at first use, cached next to the source
keyed on the source hash (so edits rebuild, repeat runs don't).  Loading
is strictly best-effort — any failure (no compiler, read-only tree,
unsupported platform) returns None and callers fall back to the NumPy
implementations in defenses/host.py.  ``FL_NATIVE=0`` disables the
native path outright (used by tests to pin the fallback).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "bulyan_select.cpp")
_lock = threading.Lock()
_lib = None
_loaded = False


def _build_and_load():
    with open(_SRC, "rb") as fh:
        src = fh.read()
    tag = hashlib.sha256(src).hexdigest()[:16]
    so = os.path.join(_DIR, f"_bulyan_{tag}.so")
    if not os.path.exists(so):
        tmp = f"{so}.tmp.{os.getpid()}"
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=300,
        )
        os.replace(tmp, so)  # atomic: concurrent builders race safely
    lib = ctypes.CDLL(so)
    fn = lib.fl_bulyan_select
    fn.restype = ctypes.c_int
    fn.argtypes = [
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    tm = lib.fl_trimmed_mean
    tm.restype = ctypes.c_int
    tm.argtypes = [
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
    ]
    md = lib.fl_median
    md.restype = ctypes.c_int
    md.argtypes = [
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_int32,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
    ]
    return lib


def get_lib():
    """The loaded native library, or None if unavailable."""
    global _lib, _loaded
    with _lock:
        if _loaded:
            return _lib
        _loaded = True
        if os.environ.get("FL_NATIVE", "1") == "0":
            return None
        try:
            _lib = _build_and_load()
        except Exception:
            _lib = None
        return _lib


def native_bulyan_selection(D, order, users_count, corrupted_count,
                            set_size, batch_select=1,
                            paper_scoring=False):
    """Run the incremental selection; returns the selected index array
    (np.int32, length set_size) or None if the native path is
    unavailable or declines (caller falls back to NumPy)."""
    lib = get_lib()
    if lib is None:
        return None
    n = D.shape[0]
    if not (0 < set_size <= n):
        return None
    D = np.ascontiguousarray(D, np.float32)
    order = np.ascontiguousarray(order, np.int32)
    out = np.empty(set_size, np.int32)
    rc = lib.fl_bulyan_select(
        D, order, n, int(users_count), int(corrupted_count),
        int(set_size), int(max(1, batch_select)),
        1 if paper_scoring else 0, out,
    )
    if rc != 0:
        return None
    return out


def native_median(sel):
    """Column-blocked native coordinate-wise median; (d,) f32 or None."""
    lib = get_lib()
    if lib is None:
        return None
    n, d = sel.shape
    if n == 0 or d == 0:
        return None
    sel = np.ascontiguousarray(sel, np.float32)
    out = np.empty(d, np.float32)
    rc = lib.fl_median(sel, n, d, out)
    if rc != 0:
        return None
    return out


def native_trimmed_mean(sel, number_to_consider):
    """Column-blocked native trimmed mean; returns the (d,) f32 result
    or None if the native path is unavailable/ineligible (caller falls
    back to NumPy)."""
    lib = get_lib()
    if lib is None:
        return None
    n, d = sel.shape
    k = int(number_to_consider)
    if not (0 < k <= n) or n == 0 or d == 0:
        return None
    sel = np.ascontiguousarray(sel, np.float32)
    out = np.empty(d, np.float32)
    rc = lib.fl_trimmed_mean(sel, n, d, k, out)
    if rc != 0:
        return None
    return out
