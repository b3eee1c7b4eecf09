"""Build + load the C columnar-history parser as an extension module.

Same on-demand g++ pattern as the WGL library (`native/__init__.py`),
but this one needs the CPython C API (it walks PyObject histories), so
it is loaded as a real extension module via importlib rather than
ctypes. Unavailable toolchain degrades silently: callers get ``None``
and use the pure-Python/numpy path.

Sanitizer lane: ``mod(san=True)`` builds an ASan+UBSan variant
(Serebryany et al., USENIX ATC 2012) with its own hash-stamped name so
both variants coexist in the build dir. Loading it requires the ASan
runtime to be FIRST in the process's library list — GCC's libasan
aborts the whole process on a late dlopen otherwise — so the loader
refuses unless libasan is already mapped (``LD_PRELOAD``; see
``san_env()``), and the test/fuzz harnesses re-exec a child with that
environment rather than gambling the parent.
"""
from __future__ import annotations

import hashlib
import importlib.machinery
import importlib.util
import logging
import os
import subprocess
import sysconfig
import threading
from pathlib import Path

logger = logging.getLogger("jepsen.native")

_HERE = Path(__file__).parent
_SRC = _HERE / "columnar_ext.c"
_lock = threading.Lock()
_mod = None
_mod_failed = False
_mod_san = None
_mod_san_failed = False

PLAIN_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC")
SAN_FLAGS = ("-O1", "-g", "-fno-omit-frame-pointer",
             "-fsanitize=address,undefined", "-fno-sanitize-recover=all",
             "-shared", "-fPIC")

# last attempted compile per variant, for the probe-failure log line
_last_cmd: dict[str, list] = {}


def _build_dir() -> Path:
    d = os.environ.get("JEPSEN_NATIVE_BUILD_DIR")
    return Path(d) if d else _HERE


def _so_path(san: bool = False) -> Path:
    from jepsen_tpu.native import host_cpu_tag
    src_hash = hashlib.sha256(_SRC.read_bytes()
                              + host_cpu_tag()).hexdigest()[:16]
    stem = "_columnar_c_san" if san else "_columnar_c"
    return _build_dir() / f"{stem}-{src_hash}.so"


def build(force: bool = False, san: bool = False) -> Path:
    so = _so_path(san=san)
    if so.exists() and not force:
        return so
    so.parent.mkdir(parents=True, exist_ok=True)
    # per-process tmp name: concurrent builders (pytest workers, parallel
    # sessions) must not interleave g++ output before the atomic publish
    tmp = so.with_suffix(f".so.tmp{os.getpid()}")
    inc = sysconfig.get_paths()["include"]
    flags = SAN_FLAGS if san else PLAIN_FLAGS
    cmd = ["g++", *flags, f"-I{inc}", "-o", str(tmp), str(_SRC)]
    _last_cmd["san" if san else "plain"] = cmd
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError:
        cmd = [c for c in cmd if c != "-march=native"]
        _last_cmd["san" if san else "plain"] = cmd
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, so)
    logger.info("built %s", so)
    return so


def _asan_mapped() -> bool:
    """True when the ASan runtime is already loaded in THIS process
    (LD_PRELOAD). dlopen'ing a gcc -fsanitize=address .so without it
    doesn't fail politely — libasan calls Die() and takes the whole
    interpreter down, so the check must happen before the attempt."""
    try:
        with open("/proc/self/maps", "rb") as fh:
            return b"libasan" in fh.read()
    except OSError:
        return False


def san_env(base: dict | None = None) -> dict | None:
    """Environment for a child process that can load the sanitizer
    variant: LD_PRELOADs the ASan+UBSan runtimes and sets conservative
    sanitizer options. None when the runtimes can't be resolved.

    detect_leaks is OFF: interpreter-lifetime allocations (interned
    strings, module state) dominate any exit report; the lane exists
    for OOB/UAF/UB, the lint rules cover the leak-on-error-path class.
    """
    libs = []
    for name in ("libasan.so", "libubsan.so"):
        try:
            p = subprocess.run(["g++", f"-print-file-name={name}"],
                               capture_output=True, text=True,
                               check=True).stdout.strip()
        except (OSError, subprocess.CalledProcessError):
            return None
        if not p or "/" not in p:
            return None
        libs.append(p)
    env = dict(base if base is not None else os.environ)
    env["LD_PRELOAD"] = ":".join(
        libs + [x for x in env.get("LD_PRELOAD", "").split(":") if x])
    env["ASAN_OPTIONS"] = env.get(
        "ASAN_OPTIONS", "detect_leaks=0:abort_on_error=1")
    env["UBSAN_OPTIONS"] = env.get(
        "UBSAN_OPTIONS", "halt_on_error=1:print_stacktrace=1")
    env["JEPSEN_TPU_NATIVE_SAN"] = "1"
    return env


def _load(so: Path, name: str):
    loader = importlib.machinery.ExtensionFileLoader(name, str(so))
    spec = importlib.util.spec_from_file_location(name, str(so),
                                                 loader=loader)
    m = importlib.util.module_from_spec(spec)
    loader.exec_module(m)
    return m


def mod(san: bool = False):
    """The extension module, or None when unbuildable (or, for the
    sanitizer variant, unloadable in this process)."""
    global _mod, _mod_failed, _mod_san, _mod_san_failed
    if san:
        if _mod_san is not None or _mod_san_failed:
            return _mod_san
    elif _mod is not None or _mod_failed:
        return _mod
    with _lock:
        if san:
            if _mod_san is not None or _mod_san_failed:
                return _mod_san
        elif _mod is not None or _mod_failed:
            return _mod
        variant = "san" if san else "plain"
        try:
            if san and not _asan_mapped():
                # a late dlopen of libasan Die()s the interpreter —
                # never attempt it; the caller re-execs with san_env()
                raise RuntimeError(
                    "ASan runtime not preloaded in this process "
                    "(LD_PRELOAD libasan first; see san_env())")
            so = build(san=san)
            # both variants load under the module name the C source
            # exports (PyInit__columnar_c); they're distinguished by
            # path, and a process only ever loads one variant
            m = _load(so, "_columnar_c")
            if san:
                _mod_san = m
            else:
                _mod = m
        except Exception:  # noqa: BLE001
            cmd = _last_cmd.get(variant)
            logger.warning(
                "native columnar parser unavailable (variant=%s, "
                "cmd=%s); using Python builder", variant,
                " ".join(cmd) if cmd else "<not compiled>",
                exc_info=True)
            if san:
                _mod_san_failed = True
            else:
                _mod_failed = True
    return _mod_san if san else _mod


def available() -> bool:
    return mod() is not None
