"""Native host-runtime loader: compiles + loads the C++ helpers in
``native/`` on first use (ctypes ABI; reference's ingest hot loops are C++
too — src/io/bin.cpp / dense_bin.hpp).  Built from the committed sources
into ``<cache root>/native`` (utils/cache.py), never from or into a
directory outside the checkout's own caches.  Falls back to numpy when no
compiler is available — host-side binning only, results are identical —
so the framework stays pure-Python-runnable; ``get_lib() is None`` says
which one ran."""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")


def _build_dir() -> str:
    from .cache import cache_root
    path = os.path.join(cache_root(), "native")
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        pass  # read-only checkout: the build below fails -> numpy path
    return path


def _n_threads() -> int:
    return max(1, min(os.cpu_count() or 1, 32))


def _build_and_load() -> Optional[ctypes.CDLL]:
    src = os.path.join(_NATIVE_DIR, "binning.cc")
    if not os.path.exists(src):
        return None
    lib_path = os.path.join(_build_dir(), "libbinning.so")
    if (not os.path.exists(lib_path) or
            os.path.getmtime(lib_path) < os.path.getmtime(src)):
        tmp = f"{lib_path}.{os.getpid()}.tmp"  # per-pid: no build races
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
                 src, "-o", tmp],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib_path)
        except Exception:
            return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    lib.bin_numerical.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32]
    lib.bin_matrix_f64.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        _LIB = _build_and_load()
    return _LIB


def build_capi_shim() -> Optional[str]:
    """Compile the native ``LGBM_*`` ABI shim (native/capi_shim.cc) and
    return the shared-library path, or None if the toolchain/headers are
    unavailable.  The shim exports the reference's out-pointer calling
    convention (c_api.h) as real C symbols backed by the embedded
    interpreter; dlopen it from C/C++/ctypes and call LGBM_* directly.
    """
    import sysconfig
    src = os.path.join(_NATIVE_DIR, "capi_shim.cc")
    if not os.path.exists(src):
        return None
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    # python version in the name: a shim linked against another
    # libpython must never be reused after an interpreter upgrade
    lib_path = os.path.join(_build_dir(),
                            f"liblightgbm_tpu_capi-py{ver}.so")
    if (os.path.exists(lib_path) and
            os.path.getmtime(lib_path) >= os.path.getmtime(src)):
        return lib_path
    tmp = f"{lib_path}.{os.getpid()}.tmp"  # per-pid: no build races
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src,
           f"-I{inc}", "-o", tmp]
    if libdir:
        cmd += [f"-L{libdir}", f"-Wl,-rpath,{libdir}"]
    cmd += [f"-lpython{ver}"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, lib_path)
    except Exception:
        return None
    return lib_path


def _ptr(a: np.ndarray, ct):
    return a.ctypes.data_as(ctypes.POINTER(ct))


def bin_numerical(values: np.ndarray, uppers: np.ndarray, num_bin: int,
                  missing_nan: bool) -> Optional[np.ndarray]:
    """Threaded value->bin for one numerical column; None -> use numpy."""
    lib = get_lib()
    if lib is None or len(values) < (1 << 16):
        return None
    vals = np.ascontiguousarray(values, np.float64)
    ub = np.ascontiguousarray(uppers, np.float64)
    out = np.empty(len(vals), np.uint8)
    lib.bin_numerical(_ptr(vals, ctypes.c_double), len(vals),
                      _ptr(ub, ctypes.c_double), len(ub), int(num_bin),
                      1 if missing_nan else 0,
                      _ptr(out, ctypes.c_uint8), _n_threads())
    return out


def bin_matrix_numerical(X: np.ndarray, uppers_list, num_bins, missing_nan
                         ) -> Optional[np.ndarray]:
    """Threaded whole-matrix binning (all columns NUMERICAL with <=256
    bins); None -> use the per-column python path."""
    lib = get_lib()
    if lib is None or X.shape[0] * X.shape[1] < (1 << 18):
        return None
    n, f = X.shape
    Xc = np.ascontiguousarray(X, np.float64)
    uppers_flat = np.ascontiguousarray(np.concatenate(uppers_list),
                                       np.float64)
    offsets = np.zeros(f + 1, np.int64)
    offsets[1:] = np.cumsum([len(u) for u in uppers_list])
    nb = np.ascontiguousarray(num_bins, np.int32)
    mn = np.ascontiguousarray(missing_nan, np.int32)
    out = np.empty((n, f), np.uint8)
    lib.bin_matrix_f64(_ptr(Xc, ctypes.c_double), n, f,
                       _ptr(uppers_flat, ctypes.c_double),
                       _ptr(offsets, ctypes.c_int64),
                       _ptr(nb, ctypes.c_int32), _ptr(mn, ctypes.c_int32),
                       _ptr(out, ctypes.c_uint8), _n_threads())
    return out
