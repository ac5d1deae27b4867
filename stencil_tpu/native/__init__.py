"""ctypes loader for the native components (native/qap.cpp,
native/paraview.cpp).

The shared library is never shipped: importing this module runs
``make -C native`` (a plain g++ -shared build; make's mtime tracking makes
it a no-op when the library is fresh) and loads what that build produced.
When the build fails (no compiler, no ``make``) the import raises
``ImportError``, and the callers take their pure-Python implementations
knowingly — a library left over from another build or another machine is
never loaded. The C ABI is
the stable boundary — no pybind11 needed.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libstencil_native.so")
_NATIVE_SRC = os.path.join(os.path.dirname(os.path.dirname(_DIR)), "native")


def _build() -> None:
    subprocess.run(
        ["make", "-s", "-C", _NATIVE_SRC],
        check=True,
        capture_output=True,
        timeout=120,
    )


def _load() -> ctypes.CDLL:
    try:
        _build()
    except (OSError, subprocess.SubprocessError) as e:
        raise ImportError(f"native library not built: {e}") from e
    lib = ctypes.CDLL(_SO)
    dp = ctypes.POINTER(ctypes.c_double)
    sp = ctypes.POINTER(ctypes.c_size_t)
    lib.stencil_qap_solve.argtypes = [ctypes.c_int, dp, dp, ctypes.c_double, sp, dp]
    lib.stencil_qap_solve.restype = ctypes.c_int
    lib.stencil_qap_solve_catch.argtypes = [ctypes.c_int, dp, dp, sp, dp]
    lib.stencil_qap_solve_catch.restype = ctypes.c_int
    lib.stencil_paraview_write.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.POINTER(dp),
    ]
    lib.stencil_paraview_write.restype = ctypes.c_int
    return lib


_LIB = _load()


def paraview_write(path: str, header: str, origin, size, qs) -> None:
    """Stream one block's CSV rows (Z,Y,X,q0,...) from C++.

    ``origin``/``size`` are (z, y, x) tuples; ``qs`` is a list of dense
    [sz, sy, sx] float64 arrays. Emits byte-identical output to the
    Python fallback (shortest-round-trip floats, Python-repr rules)."""
    arrs = [np.ascontiguousarray(q, dtype=np.float64) for q in qs]
    dp = ctypes.POINTER(ctypes.c_double)
    ptrs = (dp * len(arrs))(*[a.ctypes.data_as(dp) for a in arrs])
    rc = _LIB.stencil_paraview_write(
        path.encode(), header.encode(),
        int(origin[0]), int(origin[1]), int(origin[2]),
        int(size[0]), int(size[1]), int(size[2]),
        len(arrs), ptrs,
    )
    if rc != 0:
        raise OSError(f"stencil_paraview_write({path!r}) failed rc={rc}")


class qap_native:
    """Native QAP entry points mirroring stencil_tpu.parallel.qap."""

    @staticmethod
    def solve(w: np.ndarray, d: np.ndarray, timeout_s: float) -> Tuple[List[int], float]:
        n = w.shape[0]
        w = np.ascontiguousarray(w, dtype=np.float64)
        d = np.ascontiguousarray(d, dtype=np.float64)
        f = np.zeros(n, dtype=np.uintp)
        c = ctypes.c_double()
        dp = ctypes.POINTER(ctypes.c_double)
        sp = ctypes.POINTER(ctypes.c_size_t)
        timed_out = _LIB.stencil_qap_solve(
            n,
            w.ctypes.data_as(dp),
            d.ctypes.data_as(dp),
            timeout_s,
            f.ctypes.data_as(sp),
            ctypes.byref(c),
        )
        if timed_out:
            from ..utils import logging as log

            log.warn("qap.solve (native) timed out; result is best-so-far")
        return [int(i) for i in f], float(c.value)

    @staticmethod
    def solve_catch(w: np.ndarray, d: np.ndarray) -> Tuple[List[int], float]:
        n = w.shape[0]
        w = np.ascontiguousarray(w, dtype=np.float64)
        d = np.ascontiguousarray(d, dtype=np.float64)
        f = np.zeros(n, dtype=np.uintp)
        c = ctypes.c_double()
        dp = ctypes.POINTER(ctypes.c_double)
        sp = ctypes.POINTER(ctypes.c_size_t)
        _LIB.stencil_qap_solve_catch(
            n,
            w.ctypes.data_as(dp),
            d.ctypes.data_as(dp),
            f.ctypes.data_as(sp),
            ctypes.byref(c),
        )
        return [int(i) for i in f], float(c.value)
