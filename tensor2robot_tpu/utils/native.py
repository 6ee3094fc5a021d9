"""ctypes loader for the native host-data-path kernels.

Compiles `native/gather.cc` into a shared library on first use (g++,
cached under `native/_build/` in a file named after the source's
content hash, so a copied or stale binary is never trusted by age)
and exposes typed wrappers. Everything degrades gracefully: no
compiler, a failed build, or an exotic dtype all fall back to the
numpy implementations, so the Python-only install keeps working — the
native path is a throughput upgrade for many-core TPU hosts, not a
hard dependency (the reference's data loaders were native for the
same reason).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from tensor2robot_tpu.telemetry import metrics as tmetrics

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LOAD_FAILED = False

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_BUILD_DIR = os.path.join(_SRC_DIR, "_build")
_SRC = os.path.join(_SRC_DIR, "gather.cc")


def _lib_path() -> str:
  """The library path for the CURRENT source: keyed on gather.cc's
  content, not its mtime (a copy of the tree keeps neither order nor
  age of its files)."""
  with open(_SRC, "rb") as f:
    digest = hashlib.sha256(f.read()).hexdigest()[:16]
  return os.path.join(_BUILD_DIR, f"libt2r_native.{digest}.so")


def _build(lib_path: str) -> Optional[str]:
  os.makedirs(_BUILD_DIR, exist_ok=True)
  # Compile to a per-process temp name, then atomically rename: actor
  # and learner processes racing on a fresh checkout must never dlopen
  # a half-written library.
  tmp_path = f"{lib_path}.{os.getpid()}.tmp"
  cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
         _SRC, "-o", tmp_path]
  try:
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    os.replace(tmp_path, lib_path)
  except (OSError, subprocess.SubprocessError):
    try:
      os.unlink(tmp_path)
    except OSError:
      pass
    return None
  return lib_path


def load_library() -> Optional[ctypes.CDLL]:
  """The native library, building it if needed; None when unavailable."""
  global _LIB, _LOAD_FAILED
  with _LOCK:
    if _LIB is not None or _LOAD_FAILED:
      return _LIB
    try:
      path = _lib_path()
    except OSError:  # no source shipped: the numpy paths serve
      _LOAD_FAILED = True
      return None
    if not os.path.exists(path):
      path = _build(path)
    if path is None:
      _LOAD_FAILED = True
      return None
    try:
      lib = ctypes.CDLL(path)
    except OSError:
      _LOAD_FAILED = True
      return None
    for fn in (lib.t2r_gather_rows, lib.t2r_scatter_rows):
      fn.restype = None
      fn.argtypes = [
          ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
          ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
      ]
    _LIB = lib
    return _LIB


def native_available() -> bool:
  return load_library() is not None


def _rows_ok(arr: np.ndarray) -> bool:
  return arr.flags.c_contiguous and arr.size > 0


def gather_rows(src: np.ndarray, idx: np.ndarray,
                out: Optional[np.ndarray] = None,
                num_threads: int = 0) -> np.ndarray:
  """out[i] = src[idx[i]] along axis 0; threaded when the lib loads.

  Matches `src[idx]` exactly — including negative indexing and an
  IndexError on out-of-range values, so behavior never depends on
  whether the toolchain was present. `out` (optional) reuses a
  preallocated batch buffer, eliminating the allocation churn of
  fancy indexing.
  """
  idx = np.ascontiguousarray(idx, dtype=np.int64)
  n = src.shape[0]
  if idx.size:
    lo, hi = int(idx.min()), int(idx.max())
    if lo < -n or hi >= n:
      raise IndexError(
          f"index {hi if hi >= n else lo} is out of bounds for axis 0 "
          f"with size {n}")
    if lo < 0:  # numpy-style negative indexing
      idx = np.where(idx < 0, idx + n, idx)
  if out is None:
    out = np.empty((idx.shape[0],) + src.shape[1:], dtype=src.dtype)
  elif (out.shape != (idx.shape[0],) + src.shape[1:]
        or out.dtype != src.dtype):
    # Validate BEFORE the native memcpy: a too-small or reinterpreted
    # buffer must raise on every path, not corrupt memory on one.
    raise ValueError(
        f"gather_rows: out shape/dtype {out.shape}/{out.dtype} does "
        f"not match {(idx.shape[0],) + src.shape[1:]}/{src.dtype}.")
  lib = load_library()
  # Rows by path, so that a run can tell whether the library built and
  # served it (docs/OBSERVABILITY.md).
  if lib is None or not _rows_ok(src) or not _rows_ok(out):
    np.take(src, idx, axis=0, out=out)
    tmetrics.counter("native.gather_rows.fallback_rows").inc(
        idx.shape[0])
    return out
  tmetrics.counter("native.gather_rows.native_rows").inc(idx.shape[0])
  row_bytes = int(src.dtype.itemsize * np.prod(src.shape[1:], dtype=np.int64))
  lib.t2r_gather_rows(
      src.ctypes.data_as(ctypes.c_void_p),
      idx.ctypes.data_as(ctypes.c_void_p),
      out.ctypes.data_as(ctypes.c_void_p),
      ctypes.c_int64(idx.shape[0]), ctypes.c_int64(row_bytes),
      ctypes.c_int32(num_threads))
  return out


def scatter_rows(dst: np.ndarray, idx: np.ndarray, src: np.ndarray,
                 num_threads: int = 0) -> None:
  """dst[idx[i]] = src[i] along axis 0; threaded when the lib loads.

  `idx` must not contain duplicates (ring-buffer writes never do: a
  batched add targets distinct slots). Shape and bounds mismatches
  raise like the numpy assignment they replace — the native memcpy
  must never be reachable with out-of-range addresses.
  """
  idx = np.ascontiguousarray(idx, dtype=np.int64)
  src = np.asarray(src)
  if src.shape != (idx.shape[0],) + dst.shape[1:]:
    raise ValueError(
        f"scatter_rows: src shape {src.shape} does not match "
        f"{(idx.shape[0],) + dst.shape[1:]} (len(idx), dst row shape).")
  n = dst.shape[0]
  if idx.size:
    lo, hi = int(idx.min()), int(idx.max())
    if lo < -n or hi >= n:
      raise IndexError(
          f"index {hi if hi >= n else lo} is out of bounds for axis 0 "
          f"with size {n}")
    if lo < 0:
      idx = np.where(idx < 0, idx + n, idx)
  lib = load_library()
  if lib is None or not _rows_ok(dst) or not _rows_ok(src):
    dst[idx] = src
    return
  src = np.ascontiguousarray(src, dtype=dst.dtype)
  row_bytes = int(dst.dtype.itemsize * np.prod(dst.shape[1:], dtype=np.int64))
  lib.t2r_scatter_rows(
      src.ctypes.data_as(ctypes.c_void_p),
      idx.ctypes.data_as(ctypes.c_void_p),
      dst.ctypes.data_as(ctypes.c_void_p),
      ctypes.c_int64(idx.shape[0]), ctypes.c_int64(row_bytes),
      ctypes.c_int32(num_threads))
