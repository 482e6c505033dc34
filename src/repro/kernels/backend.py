"""Shared backend detection and on-disk cache placement for the kernels.

``interpret=None`` everywhere in the kernel stack means "auto": compile
the kernel on a TPU, run its body under the Pallas interpreter on the
CPU, and refuse any other backend rather than hide it behind the
interpreter. Kept in its own tiny module so both the raw kernels
(gf256_matmul, xor_parity) and the public wrappers (ops) can share one
resolution point without an import cycle.

Caches live inside the checkout, under the git-ignored ``.cache/``:
the autotune winners (kernels/autotune.py) and, unless
``JAX_COMPILATION_CACHE_DIR`` places it elsewhere, JAX's persistent
compilation cache (``enable_compile_cache``).
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".cache"


def resolve_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU and interpret on the CPU; "
        f"the default backend is {backend!r}"
    )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache is the fixed
    ``.cache/jax`` of this checkout (a fixed path, since the path is part
    of what a later run must match to hit). Every compile is kept, however
    quick, so a later process skips all of them."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR / "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
