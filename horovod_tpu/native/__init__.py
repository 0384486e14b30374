"""ctypes loader for libhvdtpu, the native host-side runtime.

Builds the shared library on first use when a toolchain is present
(make + g++); when the build fails, one warning says so and everything runs
on the pure-Python paths — mirroring how the reference gates features on
what was compiled in (reference: horovod_*_built checks,
operations.cc:1307-1449). ``native_built()`` says which runtime is in use.
"""

import ctypes
import os
import subprocess
import threading

from horovod_tpu.common import logging as hvd_logging

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libhvdtpu.so")
_lock = threading.Lock()
_lib = None
_tried = False


def _stale():
    """True when libhvdtpu.so predates any native source (or is absent).

    The .so is a gitignored build artifact, so a checkout that updates
    src/ keeps whatever binary an earlier build left behind — and ctypes
    would happily load it. That was the root of the long-tailed
    "escapes_json" timeline flake: a stale writer built before the
    JsonEscape backslash case shipped kept serving whichever session
    (and whichever test order) imported native first, until an unrelated
    missing-symbol AttributeError forced a rebuild. Compare mtimes like
    make would and rebuild eagerly instead."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    srcs = [os.path.join(_HERE, "Makefile")]
    src_dir = os.path.join(_HERE, "src")
    try:
        srcs += [os.path.join(src_dir, f) for f in os.listdir(src_dir)]
    except OSError:
        pass
    try:
        return any(os.path.getmtime(s) > built for s in srcs)
    except OSError:
        return True


def _build():
    # Build into a process-private target and publish with an atomic rename,
    # so concurrent first-use builds (multiple workers, shared NFS checkout)
    # can never leave a torn libhvdtpu.so behind.
    tmp = f"libhvdtpu.{os.getpid()}.so"
    try:
        subprocess.run(["make", "-C", _HERE, "-s", f"TARGET={tmp}"],
                       check=True, capture_output=True, timeout=120)
        os.replace(os.path.join(_HERE, tmp), _LIB_PATH)
        return True
    except (subprocess.CalledProcessError, FileNotFoundError, OSError,
            subprocess.TimeoutExpired) as e:
        out = getattr(e, "stderr", b"") or b""
        hvd_logging.warning(
            "native host runtime not built (%s %s); using the pure-Python "
            "paths", e, out.decode(errors="replace")[-500:])
        try:
            os.unlink(os.path.join(_HERE, tmp))
        except OSError:
            pass
        return False


def _bind(lib):
    lib.hvd_timeline_create.restype = ctypes.c_int64
    lib.hvd_timeline_create.argtypes = [ctypes.c_char_p]
    lib.hvd_timeline_record.restype = None
    lib.hvd_timeline_record.argtypes = [
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char,
        ctypes.c_double, ctypes.c_double, ctypes.c_int64]
    lib.hvd_timeline_close.restype = None
    lib.hvd_timeline_close.argtypes = [ctypes.c_int64]
    lib.hvd_timeline_count.restype = ctypes.c_int64
    lib.hvd_timeline_count.argtypes = [ctypes.c_int64]
    u16p = ctypes.POINTER(ctypes.c_uint16)
    f32p = ctypes.POINTER(ctypes.c_float)
    for name, argtypes in [
        ("hvd_fp32_to_bf16", [f32p, u16p, ctypes.c_int64]),
        ("hvd_bf16_to_fp32", [u16p, f32p, ctypes.c_int64]),
        ("hvd_fp32_to_fp16", [f32p, u16p, ctypes.c_int64]),
        ("hvd_fp16_to_fp32", [u16p, f32p, ctypes.c_int64]),
        ("hvd_bf16_accumulate", [u16p, u16p, ctypes.c_int64]),
        ("hvd_adasum_combine", [f32p, f32p, f32p, ctypes.c_int64]),
    ]:
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = argtypes
    i64 = ctypes.c_int64
    i64p = ctypes.POINTER(ctypes.c_int64)
    for name, restype, argtypes in [
        ("hvd_sched_create", i64, [i64, i64]),
        ("hvd_sched_destroy", None, [i64]),
        ("hvd_sched_set_threshold", None, [i64, i64]),
        ("hvd_sched_enqueue", ctypes.c_int32, [i64, i64, i64, i64]),
        ("hvd_sched_pending", i64, [i64]),
        ("hvd_sched_flush", i64, [i64, i64p, i64p, i64]),
        ("hvd_cache_lookup", i64, [i64, i64]),
        ("hvd_cache_hits", i64, [i64]),
        ("hvd_cache_size", i64, [i64]),
        ("hvd_group_register", i64, [i64, i64p, i64]),
        ("hvd_group_of", i64, [i64, i64]),
        ("hvd_group_deregister", None, [i64, i64]),
    ]:
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes
    return lib


def get_lib():
    """The loaded library, or None when native support is unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _stale() and not _build() and not os.path.exists(_LIB_PATH):
            return None
        try:
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
            hvd_logging.debug("loaded native runtime %s", _LIB_PATH)
        except (OSError, AttributeError) as e:
            # AttributeError: a stale prebuilt .so missing newer symbols.
            # Rebuild, then load under a unique path — dlopen caches by path
            # string, so reloading _LIB_PATH would return the stale handle.
            hvd_logging.debug("native runtime stale/unloadable (%s); "
                              "rebuilding", e)
            _lib = None
            if not _build():
                hvd_logging.warning(
                    "failed to load native runtime (%s) and rebuild is "
                    "unavailable; using Python fallbacks", e)
            else:
                import shutil
                import tempfile
                fd, tmppath = tempfile.mkstemp(suffix=".so",
                                               prefix="libhvdtpu.reload.")
                os.close(fd)
                try:
                    shutil.copy2(_LIB_PATH, tmppath)
                    _lib = _bind(ctypes.CDLL(tmppath))
                    hvd_logging.debug("reloaded native runtime via %s",
                                      tmppath)
                except (OSError, AttributeError) as e2:  # pragma: no cover
                    hvd_logging.warning(
                        "failed to load native runtime: %s", e2)
                    _lib = None
                finally:
                    try:
                        os.unlink(tmppath)  # handle stays valid on Linux
                    except OSError:
                        pass
        return _lib


def native_built():
    return get_lib() is not None


# ---- numpy-facing convenience wrappers ----

def _as_ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def _require_lib():
    lib = get_lib()
    if lib is None:
        raise RuntimeError(
            "native runtime not built (no working make/g++ toolchain); "
            "check HOROVOD_LOG_LEVEL=debug for the build error")
    return lib


def fp32_to_bf16(src):
    import numpy as np
    lib = _require_lib()
    src = np.ascontiguousarray(src, np.float32)
    out = np.empty(src.shape, np.uint16)
    lib.hvd_fp32_to_bf16(_as_ptr(src, ctypes.c_float),
                         _as_ptr(out, ctypes.c_uint16), src.size)
    return out


def bf16_to_fp32(src):
    import numpy as np
    lib = _require_lib()
    src = np.ascontiguousarray(src, np.uint16)
    out = np.empty(src.shape, np.float32)
    lib.hvd_bf16_to_fp32(_as_ptr(src, ctypes.c_uint16),
                         _as_ptr(out, ctypes.c_float), src.size)
    return out


def fp32_to_fp16(src):
    import numpy as np
    lib = _require_lib()
    src = np.ascontiguousarray(src, np.float32)
    out = np.empty(src.shape, np.uint16)
    lib.hvd_fp32_to_fp16(_as_ptr(src, ctypes.c_float),
                         _as_ptr(out, ctypes.c_uint16), src.size)
    return out


def fp16_to_fp32(src):
    import numpy as np
    lib = _require_lib()
    src = np.ascontiguousarray(src, np.uint16)
    out = np.empty(src.shape, np.float32)
    lib.hvd_fp16_to_fp32(_as_ptr(src, ctypes.c_uint16),
                         _as_ptr(out, ctypes.c_float), src.size)
    return out


def bf16_accumulate(src, dst):
    """dst += src on bf16 (uint16-viewed) buffers, accumulating in fp32 —
    host-side wire-dtype accumulation (reference: half.cc fp16 sum ops).
    Returns the accumulated buffer (``dst`` itself when it was already a
    contiguous uint16 array, else a copy)."""
    import numpy as np
    lib = _require_lib()
    src = np.ascontiguousarray(src, np.uint16)
    dst = np.ascontiguousarray(dst, np.uint16)
    if src.size != dst.size:
        raise ValueError(
            f"bf16_accumulate: size mismatch src={src.size} dst={dst.size}")
    lib.hvd_bf16_accumulate(_as_ptr(src, ctypes.c_uint16),
                            _as_ptr(dst, ctypes.c_uint16), src.size)
    return dst


def adasum_combine(a, b):
    import numpy as np
    lib = _require_lib()
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    if a.size != b.size:
        raise ValueError(
            f"adasum_combine: size mismatch a={a.size} b={b.size}")
    out = np.empty(a.shape, np.float32)
    lib.hvd_adasum_combine(_as_ptr(a, ctypes.c_float),
                           _as_ptr(b, ctypes.c_float),
                           _as_ptr(out, ctypes.c_float), a.size)
    return out


class NativeTimeline:
    """Chrome-trace writer backed by the C++ drain thread."""

    def __init__(self, path):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime not built")
        self._lib = lib
        self._handle = lib.hvd_timeline_create(path.encode())
        if self._handle == 0:
            raise OSError(f"cannot open timeline file {path}")

    def record(self, name, cat, ph, ts_us, dur_us=0.0, tid=0):
        self._lib.hvd_timeline_record(
            self._handle, name.encode(), cat.encode(),
            ph.encode() if isinstance(ph, str) else ph,
            float(ts_us), float(dur_us), int(tid))

    def count(self):
        return self._lib.hvd_timeline_count(self._handle)

    def close(self):
        if self._handle:
            self._lib.hvd_timeline_close(self._handle)
            self._handle = 0


class BucketScheduler:
    """Native bucketing scheduler + LRU response cache + group table
    (reference: the C++ cycle-loop bucket assembly operations.cc:747-853,
    response_cache.h:45, group_table.h). Raises when the native runtime is
    unavailable — callers fall back to the Python path."""

    def __init__(self, threshold_bytes, cache_capacity=1024):
        self._lib = _require_lib()
        self._h = self._lib.hvd_sched_create(int(threshold_bytes),
                                             int(cache_capacity))

    def set_threshold(self, threshold_bytes):
        self._lib.hvd_sched_set_threshold(self._h, int(threshold_bytes))

    def enqueue(self, tensor_id, key_hash, nbytes):
        """True when the accumulated bytes crossed the threshold."""
        return bool(self._lib.hvd_sched_enqueue(
            self._h, int(tensor_id), int(key_hash), int(nbytes)))

    def pending(self):
        return int(self._lib.hvd_sched_pending(self._h))

    def flush(self):
        """-> dict tensor_id -> bucket_id (enqueue order preserved)."""
        import numpy as np
        n = self.pending()
        if n == 0:
            return {}
        tids = np.empty(n, np.int64)
        bids = np.empty(n, np.int64)
        nb = self._lib.hvd_sched_flush(
            self._h, _as_ptr(tids, ctypes.c_int64),
            _as_ptr(bids, ctypes.c_int64), n)
        if nb < 0:  # pragma: no cover - cap == pending() by construction
            raise RuntimeError("scheduler flush capacity mismatch")
        return dict(zip(tids.tolist(), bids.tolist()))

    def cache_lookup(self, signature):
        """Stable slot id on hit, -1 on miss (inserted)."""
        return int(self._lib.hvd_cache_lookup(self._h, int(signature)))

    def cache_stats(self):
        return {"hits": int(self._lib.hvd_cache_hits(self._h)),
                "size": int(self._lib.hvd_cache_size(self._h))}

    def register_group(self, tensor_ids):
        import numpy as np
        ids = np.asarray(list(tensor_ids), np.int64)
        return int(self._lib.hvd_group_register(
            self._h, _as_ptr(ids, ctypes.c_int64), ids.size))

    def group_of(self, tensor_id):
        return int(self._lib.hvd_group_of(self._h, int(tensor_id)))

    def deregister_group(self, group_id):
        self._lib.hvd_group_deregister(self._h, int(group_id))

    def close(self):
        if getattr(self, "_h", 0):
            self._lib.hvd_sched_destroy(self._h)
            self._h = 0

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass
