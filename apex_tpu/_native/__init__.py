"""apex_tpu._native — ctypes bindings for the C++ host runtime.

Loads libapex_tpu_C.so, building it from apex_tpu_C.cpp (build.sh, plain
g++) on first use when it is not there — the library is never committed,
so a fresh clone and a long-lived checkout take the same path.  Every
entry point has a numpy fallback, so a host without a compiler keeps
working — the reference's graceful-degradation invariant
(README.md:90-95) applied to the host runtime.  A build that was
attempted and failed, or a library built from another revision of the
source, says so on stderr once; it is not silently replaced by numpy.

API:
  available() -> bool
  flatten(list[np.ndarray]) -> np.ndarray           (apex_C.flatten)
  unflatten(flat, like) -> list[np.ndarray]         (apex_C.unflatten)
  plan_buckets(sizes, message_size) -> np.ndarray   (DDP bucket planner)
  preprocess_images(u8_nhwc, mean, std, data_format="NCHW"|"NHWC")
      -> normalized f32, transposed to NCHW or delivered NHWC in place
      order (input pipeline)
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import sys
from typing import List, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libapex_tpu_C.so")
# what apex_native_version() in apex_tpu_C.cpp returns at this revision
_ABI_VERSION = 3

_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _give_up(why: str) -> None:
    """Fall back to numpy for the rest of the process, and say why."""
    global _load_failed
    _load_failed = True
    print(f"apex_tpu._native: {why}; using the numpy fallbacks",
          file=sys.stderr)


def _try_load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:  # don't shell out to the compiler on every call
        return None
    if not os.path.exists(_SO):
        if shutil.which("g++") is None:
            _load_failed = True     # no toolchain: the library is absent
            return None
        try:
            subprocess.run(["bash", os.path.join(_HERE, "build.sh")],
                           check=True, capture_output=True, text=True,
                           timeout=120)
        except subprocess.CalledProcessError as e:
            tail = (e.stderr or "").strip().splitlines()[-3:]
            _give_up(f"build.sh exited {e.returncode}: "
                     + " | ".join(tail))
            return None
        except (OSError, subprocess.TimeoutExpired) as e:
            _give_up(f"build.sh did not run to an end ({e})")
            return None
    try:
        lib = ctypes.CDLL(_SO)
        lib.apex_native_version.restype = ctypes.c_int
        found = int(lib.apex_native_version())
    except (OSError, AttributeError) as e:
        _give_up(f"cannot load {_SO} ({e})")
        return None
    if found != _ABI_VERSION:
        _give_up(f"{_SO} is ABI v{found} but apex_tpu_C.cpp is "
                 f"v{_ABI_VERSION} — delete it to rebuild")
        return None
    lib.apex_flatten.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_int64, ctypes.c_void_p]
    lib.apex_unflatten.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p)]
    lib.apex_plan_buckets.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32)]
    lib.apex_plan_buckets.restype = ctypes.c_int
    lib.apex_preprocess_nhwc_u8_to_nchw_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float)]
    lib.apex_preprocess_nhwc_u8_to_nhwc_f32.argtypes = \
        lib.apex_preprocess_nhwc_u8_to_nchw_f32.argtypes
    lib.apex_loader_create.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
        ctypes.c_int, ctypes.c_uint64, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int]
    lib.apex_loader_create.restype = ctypes.c_void_p
    lib.apex_loader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p)]
    lib.apex_loader_next.restype = ctypes.c_int64
    lib.apex_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.apex_loader_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def available() -> bool:
    return _try_load() is not None


def flatten(tensors: Sequence[np.ndarray]) -> np.ndarray:
    """Concatenate same-dtype host arrays into one contiguous 1-D buffer."""
    tensors = [np.ascontiguousarray(t) for t in tensors]
    if not tensors:
        return np.zeros((0,), np.float32)
    dt = tensors[0].dtype
    if any(t.dtype != dt for t in tensors):
        raise TypeError("flatten() requires a same-dtype list")
    total = sum(t.size for t in tensors)
    lib = _try_load()
    if lib is None:
        return np.concatenate([t.reshape(-1) for t in tensors])
    out = np.empty((total,), dt)
    n = len(tensors)
    srcs = (ctypes.c_void_p * n)(
        *[t.ctypes.data_as(ctypes.c_void_p) for t in tensors])
    sizes = (ctypes.c_int64 * n)(*[t.size for t in tensors])
    lib.apex_flatten(srcs, sizes, n, dt.itemsize,
                     out.ctypes.data_as(ctypes.c_void_p))
    return out


def unflatten(flat: np.ndarray, like: Sequence[np.ndarray]
              ) -> List[np.ndarray]:
    flat = np.ascontiguousarray(flat)
    lib = _try_load()
    outs = [np.empty(t.shape, flat.dtype) for t in like]
    if lib is None:
        off = 0
        for o in outs:
            o[...] = flat[off:off + o.size].reshape(o.shape)
            off += o.size
        return outs
    n = len(outs)
    dsts = (ctypes.c_void_p * n)(
        *[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
    sizes = (ctypes.c_int64 * n)(*[o.size for o in outs])
    lib.apex_unflatten(flat.ctypes.data_as(ctypes.c_void_p), sizes, n,
                       flat.dtype.itemsize, dsts)
    return outs


def plan_buckets(sizes: Sequence[int], message_size: int) -> np.ndarray:
    """Greedy in-order bucket ids (DDP bucketing, distributed.py:338-361)."""
    sizes = np.asarray(list(sizes), np.int64)
    lib = _try_load()
    if lib is None:
        ids = np.zeros(len(sizes), np.int32)
        bucket = filled = 0
        for i, s in enumerate(sizes):
            ids[i] = bucket
            filled += int(s)
            if filled >= message_size:
                bucket += 1
                filled = 0
        return ids
    ids = np.zeros(len(sizes), np.int32)
    lib.apex_plan_buckets(
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(sizes),
        message_size, ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return ids


def version() -> int:
    """ABI version of the loaded native lib (0 when unavailable)."""
    lib = _try_load()
    return int(lib.apex_native_version()) if lib is not None else 0


def preprocess_images(images_u8: np.ndarray, mean: Sequence[float],
                      std: Sequence[float],
                      data_format: str = "NCHW") -> np.ndarray:
    """NHWC uint8 -> normalized float32 on host threads, delivered NCHW
    (default) or NHWC (no transpose)."""
    images_u8 = np.ascontiguousarray(images_u8)
    n, h, w, c = images_u8.shape
    nhwc_out = data_format == "NHWC"
    lib = _try_load()
    if lib is None:
        f = images_u8.astype(np.float32)
        f = (f - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)
        return np.ascontiguousarray(f if nhwc_out
                                    else f.transpose(0, 3, 1, 2))
    out = np.empty((n, h, w, c) if nhwc_out else (n, c, h, w), np.float32)
    mean_c = (ctypes.c_float * c)(*[float(m) for m in mean])
    std_c = (ctypes.c_float * c)(*[float(s) for s in std])
    fn = (lib.apex_preprocess_nhwc_u8_to_nhwc_f32 if nhwc_out
          else lib.apex_preprocess_nhwc_u8_to_nchw_f32)
    fn(images_u8.ctypes.data_as(ctypes.c_void_p),
       out.ctypes.data_as(ctypes.c_void_p), n, h, w, c, mean_c, std_c)
    return out
