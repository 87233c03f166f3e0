"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

The one place that turns ``interpret=None`` into a decision, so every
kernel entry point resolves it the same way; plus the compile-cache
setting that entry-point scripts share.
"""
from __future__ import annotations

import os

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else by the default backend: ``False`` on a
    TPU, ``True`` on the CPU (tests), and an error anywhere else -- a
    kernel written for a TPU never silently runs interpreted on another
    accelerator."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on a TPU or interpreted on the "
        f"CPU; the default backend is {platform!r}")


def use_compile_cache(default_dir: str) -> str:
    """Turn on JAX's persistent compilation cache for a command-line run.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins;
    otherwise the cache goes to ``default_dir`` -- a fixed path, because
    the path is part of the cache key.  Returns the directory in use.
    Called by entry-point scripts only; importing the library never
    touches the cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
