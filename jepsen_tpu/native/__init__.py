"""Native (C++) components, built on demand with the system toolchain.

The reference's compute-critical searches are native too (JVM-JIT-compiled
knossos/elle, SURVEY.md §2.5); here the host-side hot kernel is a C++
shared library compiled with g++ at first use and loaded via ctypes —
no pybind11 dependency. The TPU path (ops/jitlin) is independent of this;
the native library is the *CPU* fast path and fallback oracle.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

logger = logging.getLogger("jepsen.native")

_HERE = Path(__file__).parent
_SRC = _HERE / "wgl.cpp"
_lock = threading.Lock()
_lib = None
_lib_failed = False

_SAN_FLAGS = ("-O1", "-g", "-fno-omit-frame-pointer",
              "-fsanitize=address,undefined", "-fno-sanitize-recover=all")


def _build_dir() -> Path:
    d = os.environ.get("JEPSEN_NATIVE_BUILD_DIR")
    return Path(d) if d else _HERE


def host_cpu_tag() -> bytes:
    """The host CPU's model and feature flags: ``-march=native`` code
    runs only where they match, so they key every built artifact (a
    tree copied to another machine rebuilds instead of dying of
    SIGILL)."""
    try:
        with open("/proc/cpuinfo", "rb") as fh:
            lines = fh.read().splitlines()
    except OSError:
        return b""
    keep = [ln for ln in lines
            if ln.startswith((b"model name", b"flags"))]
    return b"\n".join(keep[:2])


def _so_path(san: bool = False) -> Path:
    src_hash = hashlib.sha256(_SRC.read_bytes()
                              + host_cpu_tag()).hexdigest()[:16]
    stem = "_libwgl_san" if san else "_libwgl"
    return _build_dir() / f"{stem}-{src_hash}.so"


def build(force: bool = False, san: bool = False) -> Path:
    """Compiles wgl.cpp to a hash-stamped .so (cached). ``san`` builds
    the ASan+UBSan variant as a distinct artifact (doc/static-analysis.md
    "Native code")."""
    so = _so_path(san=san)
    if so.exists() and not force:
        return so
    so.parent.mkdir(parents=True, exist_ok=True)
    # per-process tmp name: concurrent builders must not interleave g++
    # output before the atomic publish
    tmp = so.with_suffix(f".so.tmp{os.getpid()}")
    perf = _SAN_FLAGS if san else ("-O3", "-march=native")
    cmd = ["g++", *perf, "-std=c++17", "-shared", "-fPIC",
           "-o", str(tmp), str(_SRC)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        # -march=native can fail on exotic hosts; retry portable
        cmd = [c for c in cmd if c != "-march=native"]
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, so)
    logger.info("built %s", so)
    return so


def _san_on() -> bool:
    return os.environ.get("JEPSEN_TPU_NATIVE_SAN", "").strip().lower() \
        in ("1", "true", "yes", "on")


def lib():
    """The loaded library, or None when unbuildable (no g++).

    Under ``JEPSEN_TPU_NATIVE_SAN=1`` (the sanitizer lane's child env,
    ``columnar_c.san_env()``) this loads the ASan+UBSan build instead —
    and REFUSES to serve the uninstrumented one when the ASan runtime
    is not preloaded: the lane must fall back to the Python search,
    never masquerade."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            san = _san_on()
            if san:
                from jepsen_tpu.native import columnar_c
                if not columnar_c._asan_mapped():
                    raise RuntimeError(
                        "san wgl requested but libasan is not preloaded")
            so = build(san=san)
            l = ctypes.CDLL(str(so))
            l.wgl_check.restype = ctypes.c_int
            l.wgl_check.argtypes = [
                ctypes.POINTER(ctypes.c_int8),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
            ]
            _lib = l
        except Exception:  # noqa: BLE001
            logger.warning("native wgl unavailable; using Python search",
                           exc_info=True)
            _lib_failed = True
    return _lib


def available() -> bool:
    return lib() is not None


def check_stream_native(stream, init_state: int = 0,
                        max_configs: int = 20_000_000):
    """Runs the C++ search over an EventStream. Returns a LinearResult, or
    None when the native path can't handle the input (falls back to
    Python): >63 slots, unbuilt library."""
    from jepsen_tpu.checker.linear_cpu import LinearResult

    l = lib()
    if l is None:
        return None
    kind = np.ascontiguousarray(stream.kind, dtype=np.int8)
    slot = np.ascontiguousarray(stream.slot, dtype=np.int32)
    f = np.ascontiguousarray(stream.f, dtype=np.int32)
    a = np.ascontiguousarray(stream.a, dtype=np.int32)
    b = np.ascontiguousarray(stream.b, dtype=np.int32)
    stats = (ctypes.c_int64 * 3)()
    rc = l.wgl_check(
        kind.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        slot.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        f.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        b.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(kind), init_state, 0, max_configs, stats)
    died, peak, _explored = stats[0], stats[1], stats[2]
    if rc == -2:
        return None
    if rc == -1:
        return LinearResult(valid="unknown", configs_max=int(peak),
                            algorithm="jitlin-native")
    valid = rc == 1
    return LinearResult(
        valid=valid,
        failed_event=int(died),
        failed_op_index=int(stream.op_index[died]) if died >= 0 else -1,
        configs_max=int(peak),
        algorithm="jitlin-native",
    )
