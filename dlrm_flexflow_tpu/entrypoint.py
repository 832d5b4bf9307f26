"""What every entry point does before it touches the device.

Three small pieces shared by the programs a user (or the driver) starts
— ``apps/dlrm.py:run``, ``bench.py``, ``scripts/serve_bench.py``,
``chip_smoke.py`` — and deliberately NOT run at package import or from
``tests/conftest.py``:

* :func:`enable_compile_cache` places JAX's persistent compilation cache;
* :func:`device_info` / :func:`device_line` say what the program runs on
  (``jax.devices()[0].platform``, ``.device_kind``, the device count);
* :func:`require_tpu` refuses a backend that silently fell back to CPU.

JAX initialises its backend lazily, so calling these first is early
enough.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional

#: ``<repo>/.jax_cache`` — the path is part of the cache key, so it is
#: one fixed directory inside the checkout (never a tempdir, pid or
#: timestamp) and is listed in ``.gitignore``.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the variable
    itself and nothing is touched here.  Where it is unset, the cache
    goes to :data:`DEFAULT_CACHE_DIR`.  Must run before the process's
    first compile (JAX decides once per process whether a cache is in
    use)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_info() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` as JAX reports them."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_line(info: Optional[Dict[str, object]] = None) -> str:
    info = info or device_info()
    return (f"device: platform={info['platform']} kind={info['kind']!r} "
            f"count={info['count']}")


def require_tpu(allow_requested_cpu: bool = False) -> Dict[str, object]:
    """Print the device line and exit non-zero unless the backend is a
    TPU.  If libtpu fails to initialise JAX falls back to the CPU with a
    warning; a measurement must not carry on through that.

    ``allow_requested_cpu``: a caller who set ``JAX_PLATFORMS=cpu``
    themselves asked for the CPU (tests, rehearsals) and gets it."""
    info = device_info()
    print(device_line(info), flush=True)
    if info["platform"] == "tpu":
        return info
    asked = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if allow_requested_cpu and asked == "cpu":
        return info
    print(f"refusing to run: JAX found platform {info['platform']!r}, "
          f"not 'tpu'"
          + (" (set JAX_PLATFORMS=cpu to run on the CPU on purpose)"
             if allow_requested_cpu else ""),
          file=sys.stderr, flush=True)
    raise SystemExit(2)
