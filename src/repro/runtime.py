"""Where JAX computations run and where their compiled programs are kept.

:func:`default_platform` names the platform new computations run on;
:func:`enable_compile_cache` is called first thing by every entry
point's ``main`` (never at import), so repeated runs of a checkout reuse
compiled programs.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory: fixed (the path is part of every
#: cache key), resolved from this file rather than the working directory
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def default_platform() -> str:
    """Platform of the default device in effect (``jax.default_device``),
    else of the default backend: ``"tpu"``, ``"cpu"``, ..."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, that
    directory is used and nothing is changed here.  Otherwise the cache
    goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
