"""Build + load the native kernels (g++ → .so, consumed via ctypes).

No pybind11 in this image; the C ABI + ctypes is the binding layer. The
shared object is compiled with ``-march=native``, so it is only valid for
the source, flags and CPU it was built from: its file name carries a hash
of all three, and an object built elsewhere (a copied tree, another host,
an older source) is never loaded — this host builds its own. Loading is
process-cached.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "kernels.cpp")
_FLAGS = (
    "-O3", "-march=native", "-fPIC", "-shared", "-fopenmp", "-std=c++17",
)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class NativeBuildError(RuntimeError):
    pass


def _cpu_signature() -> str:
    """The host CPU as ``-march=native`` sees it: model name and feature
    flags of the first processor (falls back to the machine type)."""
    import platform

    sig = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features"):
                    sig.append(line.strip())
                if not line.strip() and len(sig) > 1:
                    break  # first processor only
    except OSError:
        sig.append(platform.processor())
    return "\n".join(sig)


def object_path() -> str:
    """Path of the shared object for THIS source, flags and host CPU."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_signature().encode())
    return os.path.join(_DIR, f"libdlps_kernels-{h.hexdigest()[:16]}.so")


def build(force: bool = False) -> str:
    """Compile kernels.cpp unless this host's object exists; returns the
    .so path."""
    so = object_path()
    with _lock:
        if not force and os.path.exists(so):
            return so
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", *_FLAGS, _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except FileNotFoundError as e:
            raise NativeBuildError(f"g++ not available: {e}") from e
        except subprocess.CalledProcessError as e:
            raise NativeBuildError(f"native build failed:\n{e.stderr}") from e
        os.replace(tmp, so)
        return so


def load() -> ctypes.CDLL:
    """Build if needed and load with typed signatures (process-cached)."""
    global _lib
    if _lib is not None:
        return _lib
    path = build()
    lib = ctypes.CDLL(path)
    dp = ctypes.POINTER(ctypes.c_double)
    lib.dlps_normal_eq.argtypes = [
        dp, dp, ctypes.c_int, ctypes.c_int, ctypes.c_double, dp, dp
    ]
    lib.dlps_normal_eq.restype = None
    lib.dlps_cholesky.argtypes = [dp, ctypes.c_int]
    lib.dlps_cholesky.restype = ctypes.c_int
    lib.dlps_cho_solve.argtypes = [dp, dp, ctypes.c_int, dp]
    lib.dlps_cho_solve.restype = None
    lib.dlps_num_threads.argtypes = []
    lib.dlps_num_threads.restype = ctypes.c_int
    _lib = lib
    return lib


def available() -> bool:
    try:
        load()
        return True
    except NativeBuildError:
        return False
