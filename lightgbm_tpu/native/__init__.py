"""Native (C++) ingest runtime: fast parallel text parsing and binning.

Loads ``parser.cpp`` as a shared object via ctypes, building it with g++ on
first use (cached beside the source; rebuilt when the source is newer).
Every entry point has a pure-numpy fallback in ``io/`` — the native path is
an accelerator, not a dependency (the reference's equivalent machinery is
``src/io/parser.cpp`` + ``DatasetLoader::ExtractFeatures*``, which is
mandatory C++; here Python remains the source of truth for semantics and
the C++ is held to byte-identical outputs by tests).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "parser.cpp")
_SO = os.path.join(_DIR, "_parser.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _build() -> bool:
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", _SO]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except Exception:
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None when unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib
        need_build = (not os.path.exists(_SO) or
                      os.path.getmtime(_SO) < os.path.getmtime(_SRC))
        if need_build and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            _build_failed = True
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        f64p = ctypes.POINTER(ctypes.c_double)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.SetParserSectionBytes.argtypes = [ctypes.c_int64]
        lib.SetParserSectionBytes.restype = None
        lib.CountDelimited.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                       ctypes.c_int, i64p, i64p]
        lib.ParseDelimited.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                       ctypes.c_int, ctypes.c_int64,
                                       ctypes.c_int64, f64p]
        lib.CountLibSVM.argtypes = [ctypes.c_char_p, i64p, i64p]
        lib.ParseLibSVM.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_int64, f64p, f64p]
        lib.BinValues.argtypes = [f64p, ctypes.c_int64, ctypes.c_int64,
                                  f64p, i64p, i32p, i32p, u8p, i32p, u16p]
        for fn in ("CountDelimited", "ParseDelimited", "CountLibSVM",
                   "ParseLibSVM", "BinValues"):
            getattr(lib, fn).restype = ctypes.c_int
        _lib = lib
        return _lib


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def parse_delimited(path: str, delim: str, skip_rows: int = 0
                    ) -> Optional[np.ndarray]:
    """CSV/TSV -> dense [rows, cols] float64, or None if native unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    pb = path.encode()
    if lib.CountDelimited(pb, delim.encode(), skip_rows,
                          ctypes.byref(rows), ctypes.byref(cols)):
        return None
    out = np.empty((rows.value, cols.value), np.float64)
    if lib.ParseDelimited(pb, delim.encode(), skip_rows, rows.value,
                          cols.value, _ptr(out, ctypes.c_double)):
        return None
    return out


def parse_libsvm(path: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """LibSVM -> (features [rows, cols], labels [rows]) or None."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    pb = path.encode()
    if lib.CountLibSVM(pb, ctypes.byref(rows), ctypes.byref(cols)):
        return None
    out = np.zeros((rows.value, cols.value), np.float64)
    labels = np.empty(rows.value, np.float64)
    if lib.ParseLibSVM(pb, rows.value, cols.value,
                       _ptr(out, ctypes.c_double), _ptr(labels, ctypes.c_double)):
        return None
    return out, labels


def bin_values(data: np.ndarray, mappers, used_features) -> Optional[np.ndarray]:
    """Raw [n, F_total] float64 -> binned [n, F_used] uint16 using the
    per-feature BinMappers; None if native unavailable.  Semantics match
    ``BinMapper.value_to_bin`` exactly (tests enforce equality)."""
    lib = get_lib()
    if lib is None:
        return None
    from ..io.bin import BinType, MissingType
    cols = len(used_features)
    n = data.shape[0]
    uppers, offsets, nan_bins, default_bins, is_cat, cat_perm = \
        [], [0], [], [], [], []
    for f in used_features:
        m = mappers[f]
        if m.bin_type == BinType.CATEGORICAL:
            cats = np.asarray(m.bin_2_categorical, np.float64)
            order = np.argsort(cats)
            uppers.append(cats[order])
            cat_perm.append(order.astype(np.int32) + 1)
            nan_bins.append(-1)
            default_bins.append(0)
            is_cat.append(1)
        else:
            ub = np.asarray(m.bin_upper_bound, np.float64)
            uppers.append(ub)
            cat_perm.append(np.zeros(len(ub), np.int32))
            nan_bins.append(m.num_bin - 1
                            if m.missing_type == MissingType.NAN else -1)
            default_bins.append(int(np.searchsorted(ub, 0.0, side="left"))
                                if len(ub) else 0)
            is_cat.append(0)
        offsets.append(offsets[-1] + len(uppers[-1]))
    uppers_c = (np.concatenate(uppers) if uppers else np.zeros(0)).astype(np.float64)
    cat_perm_c = (np.concatenate(cat_perm) if cat_perm else
                  np.zeros(0, np.int32)).astype(np.int32)
    offsets_c = np.asarray(offsets, np.int64)
    nan_c = np.asarray(nan_bins, np.int32)
    def_c = np.asarray(default_bins, np.int32)
    cat_c = np.asarray(is_cat, np.uint8)

    # every column used, in order (the usual dense table): no copy of it
    sub = (data if list(used_features) == list(range(data.shape[1]))
           and data.dtype == np.float64 and data.flags.c_contiguous
           else np.ascontiguousarray(data[:, list(used_features)], np.float64))
    out = np.empty((n, cols), np.uint16)
    if lib.BinValues(_ptr(sub, ctypes.c_double), n, cols,
                     _ptr(uppers_c, ctypes.c_double),
                     _ptr(offsets_c, ctypes.c_int64),
                     _ptr(nan_c, ctypes.c_int32), _ptr(def_c, ctypes.c_int32),
                     _ptr(cat_c, ctypes.c_uint8),
                     _ptr(cat_perm_c, ctypes.c_int32),
                     _ptr(out, ctypes.c_uint16)):
        return None
    return out


__all__ = ["get_lib", "parse_delimited", "parse_libsvm", "bin_values"]
