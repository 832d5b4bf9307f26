"""Shared loader for the native C++ libraries (native/*.so).

One build-if-stale + ctypes.CDLL bootstrap used by both native bindings
(data/native.py for the runtime library, sim/native_sim.py for the
simulator engine) — the ffcompile.sh analogue of the reference build.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "native")


def load_native_lib(so_name: str, src_name: str,
                    make_target: str) -> ctypes.CDLL:
    """Build ``make_target`` in native/ when ``so_name`` is missing or
    older than ``src_name``, then dlopen it.  A library that is there
    but does not load (copied from another machine: the Makefile builds
    with ``-march=native``) is rebuilt once and loaded again.

    Raises OSError / subprocess.CalledProcessError on build or load
    failure — callers decide whether native support is optional.
    """
    so = os.path.join(NATIVE_DIR, so_name)
    src = os.path.join(NATIVE_DIR, src_name)

    def build():
        subprocess.run(["make", "-B", "-C", NATIVE_DIR, make_target],
                       check=True, capture_output=True)

    if (not os.path.exists(so)
            or os.path.getmtime(so) < os.path.getmtime(src)):
        build()
    try:
        return ctypes.CDLL(so)
    except OSError:
        build()
        return ctypes.CDLL(so)
