"""Native (C++) runtime components, built on demand with the system g++.

The reference's equivalents are Rust crates with SIMD/FFI cores (blake3 crate,
sd-crypto, sd-ffmpeg). Here each component is a small C++ translation unit
compiled to a shared library at first import and loaded with ctypes — no
pybind11 dependency. Build artifacts land in ``native/_build`` (gitignored);
a failed toolchain leaves the pure-Python path in charge.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
from pathlib import Path

_DIR = Path(__file__).parent
_BUILD = _DIR / "_build"


def _artifact_key(cmd_flags: list[str], srcs: list[Path]) -> str:
    """Hash of the compiler flags and the source bytes: an artifact is
    reused only when it was built from exactly these. (An mtime test would
    trust whatever ``_build/`` a copied tree brought along.)"""
    h = hashlib.sha256("\0".join(cmd_flags).encode())
    for src in srcs:
        h.update(b"\0" + src.name.encode() + b"\0" + src.read_bytes())
    return h.hexdigest()[:16]


def build_shared(name: str, sources: list[str], extra_flags: list[str] | None = None,
                 extra_libs: list[str] | None = None) -> Path:
    """Compile ``sources`` (relative to native/) into
    ``_build/lib<name>-<key>.so``, keyed on the sources and flags (see
    :func:`_artifact_key`), and point ``_build/lib<name>.so`` at it for
    linkers. Concurrent builders race benignly: each compiles to a temp
    file then renames. ``extra_libs`` (-l/-L flags) go AFTER the sources
    — link order matters."""
    srcs = [_DIR / s for s in sources]
    flags = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
             *(extra_flags or [])]
    out = _BUILD / f"lib{name}-{_artifact_key(flags + (extra_libs or []), srcs)}.so"
    if not out.exists():
        _BUILD.mkdir(exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD)
        os.close(fd)
        cmd = [*flags, *map(str, srcs), "-o", tmp, *(extra_libs or [])]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
            os.replace(tmp, out)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
    link = _BUILD / f"lib{name}.so"
    if not (link.is_symlink() and os.readlink(link) == out.name):
        fd, tmp = tempfile.mkstemp(dir=_BUILD)
        os.close(fd)
        os.unlink(tmp)
        os.symlink(out.name, tmp)
        os.replace(tmp, link)
    return out


def build_ffi() -> Path:
    """Build the embedded-core C-ABI shim (sd_core_ffi.cc) against this
    interpreter's libpython (python3-config --embed flags)."""
    includes = subprocess.run(
        ["python3-config", "--includes"],
        check=True, capture_output=True, text=True).stdout.split()
    ldflags = subprocess.run(
        ["python3-config", "--ldflags", "--embed"],
        check=True, capture_output=True, text=True).stdout.split()
    return build_shared("sdcoreffi", ["sd_core_ffi.cc"],
                        extra_flags=includes, extra_libs=ldflags)
