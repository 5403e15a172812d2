"""Native runtime components (C, built on demand, ctypes-bound).

The reference keeps its data layer in C++ (data_feed.cc, data_set.cc); here
the hot MultiSlot text parser is C compiled at first use with the system
compiler. The binding has a pure-Python fallback so the framework still
works without a toolchain, at a much slower ingest: falling back warns, and
`native_available()` says which one a process got.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading
import warnings

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "multislot_parser.c")

_lock = threading.Lock()
_lib = None
_build_failed = False


def _so_path() -> str:
    """The library's path carries a hash of its source: a copy or a
    checkout resets mtimes, so only the content can say whether a binary
    found on disk was built from the committed `.c`."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_multislot_{digest}.so")


def _load():
    """Compile (if no binary of this source exists) and load the parser
    library; None, after one warning, if that is not possible."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            so = _so_path()
            if not os.path.exists(so):
                cc = os.environ.get("CC", "cc")
                # build beside the target, then rename: a concurrent
                # process never loads a half-written library
                fd, tmp = tempfile.mkstemp(dir=_DIR, prefix=".build_",
                                           suffix=".so")
                os.close(fd)
                try:
                    subprocess.run(
                        [cc, "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
                        check=True, capture_output=True)
                    os.replace(tmp, so)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
                for stale in glob.glob(os.path.join(_DIR, "_multislot*.so")):
                    if stale != so:
                        os.unlink(stale)
            lib = ctypes.CDLL(so)
            lib.multislot_count.restype = ctypes.c_longlong
            lib.multislot_count.argtypes = [ctypes.c_char_p]
            lib.multislot_parse.restype = ctypes.c_longlong
            lib.multislot_parse.argtypes = [
                ctypes.c_char_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_double), ctypes.c_longlong,
            ]
            _lib = lib
        except (OSError, subprocess.CalledProcessError) as e:
            _build_failed = True
            detail = getattr(e, "stderr", b"") or b""
            warnings.warn(
                "paddle_tpu.native: could not build/load the C MultiSlot "
                f"parser ({e!r} {detail.decode(errors='replace')[-500:]}); "
                "falling back to the pure-Python parser — dataset ingest "
                "will be MUCH slower", RuntimeWarning, stacklevel=3)
    return _lib


def parse_multislot_file(path: str, widths: list[int]) -> np.ndarray:
    """Parse one MultiSlot text file -> [n_samples, sum(widths)] float64."""
    lib = _load()
    if lib is not None:
        n = lib.multislot_count(path.encode())
        if n < 0:
            raise IOError(f"cannot read '{path}'")
        out = np.zeros((n, int(sum(widths))), dtype=np.float64)
        w = (ctypes.c_longlong * len(widths))(*widths)
        got = lib.multislot_parse(
            path.encode(), len(widths), w,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n)
        if got == -2:
            raise ValueError(f"malformed MultiSlot line in '{path}'")
        if got < 0:
            raise IOError(f"cannot read '{path}'")
        return out[:got]
    return _parse_multislot_py(path, widths)


def _parse_multislot_py(path: str, widths: list[int]) -> np.ndarray:
    """Pure-Python fallback with identical semantics."""
    rows = []
    row_width = int(sum(widths))
    with open(path) as f:
        for line in f:
            toks = line.split()
            if not toks:
                continue
            row = np.zeros(row_width, dtype=np.float64)
            i, off = 0, 0
            for w in widths:
                if i >= len(toks):
                    raise ValueError(f"malformed MultiSlot line in '{path}'")
                cnt = int(toks[i])
                i += 1
                vals = toks[i:i + cnt]
                if len(vals) != cnt:
                    raise ValueError(f"malformed MultiSlot line in '{path}'")
                i += cnt
                for j, v in enumerate(vals[:w]):
                    row[off + j] = float(v)
                off += w
            rows.append(row)
    if not rows:
        return np.zeros((0, row_width), dtype=np.float64)
    return np.stack(rows)


def native_available() -> bool:
    return _load() is not None
