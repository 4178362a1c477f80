"""The one device gate, and where the compiled programs are kept.

``ensure_jax_safe()`` is the call every production device touchpoint
makes before its first jax use (the ``jax-wedge`` lint pass checks that
it dominates them). It answers one question — is JAX's default backend
an accelerator? — and changes nothing: it never touches
``jax_platforms`` and never starts a process. A process that should run
on the CPU says so itself (``JAX_PLATFORMS=cpu``); a process that finds
no accelerator runs the CPU paths and says so, it is never moved there.

``configure_compile_cache()`` places JAX's persistent compilation cache.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (the directory holding the ``spacedrive_tpu`` package)
CHECKOUT = Path(__file__).resolve().parents[2]


def ensure_jax_safe() -> bool:
    """True when JAX's default backend is an accelerator (not ``cpu``)."""
    import jax

    return jax.default_backend() != "cpu"


def device_info() -> dict:
    """``{platform, kind, count}`` of JAX's default devices, as reported."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it. ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX and
    no other directory is set in code. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (the path is part of the cache key, so
    it never carries a temp name, pid or time)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
