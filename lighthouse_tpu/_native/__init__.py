"""Native (C) runtime components, built on demand with the system compiler.

The reference links vendored native libraries for its hot loops (blst asm,
ring's SHA-NI — ``crypto/eth2_hashing/Cargo.toml``). Here the native layer
is compiled from checked-in C at first import and loaded via ctypes; every
caller has a pure-Python fallback so a missing toolchain degrades to slow,
not broken.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import re
import subprocess
import sysconfig
from pathlib import Path

_DIR = Path(__file__).resolve().parent


def _compiler() -> tuple[str, str] | None:
    """(command, version text) of the first working C compiler."""
    for cc in (os.environ.get("CC"), "cc", "gcc", "g++", "clang"):
        if not cc:
            continue
        try:
            out = subprocess.run(
                [cc, "--version"], capture_output=True, check=True, text=True
            )
            return cc, out.stdout
        except (OSError, subprocess.CalledProcessError):
            continue
    return None


_LOAD_CACHE: dict[str, ctypes.CDLL | None] = {}
_INCLUDE = re.compile(rb'^\s*#\s*include\s+"([^"]+)"', re.M)


def build_and_load(stem: str, extra_flags: tuple[str, ...] = ()) -> ctypes.CDLL | None:
    """Compile ``<stem>.c`` into ``lib<stem>-<key>.so`` (unless that
    build exists) and dlopen it. Returns None when no compiler is
    available or the build fails. The outcome — INCLUDING failure — is
    cached per stem, so hot callers with a pure-Python fallback
    (SecretKey.sign/public_key) never re-probe the compiler per call."""
    if stem in _LOAD_CACHE:
        return _LOAD_CACHE[stem]
    _LOAD_CACHE[stem] = out = _build_and_load_uncached(stem, extra_flags)
    return out


def build_key(src: Path, flags: list[str], compiler: tuple[str, str]) -> str:
    """Hash of everything the library is built from: the source, the
    local headers it includes, the flags and the compiler's identity. A
    library built from anything else (another checkout, another host's
    compiler) carries another key and is never loaded in its place."""
    h = hashlib.sha256()
    code = src.read_bytes()
    h.update(code)
    for name in sorted(set(_INCLUDE.findall(code))):
        header = src.parent / name.decode()
        h.update(name + b"\0")
        h.update(header.read_bytes() if header.exists() else b"<system>")
    h.update("\0".join(flags).encode())
    h.update("\0".join(compiler).encode())
    return h.hexdigest()[:16]


def _build_and_load_uncached(
    stem: str, extra_flags: tuple[str, ...] = ()
) -> ctypes.CDLL | None:
    src = _DIR / f"{stem}.c"
    if not src.exists():
        return None
    cc = _compiler()
    if cc is None:
        return None
    flags = ["-O3", "-fPIC", "-shared", *extra_flags]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    so = _DIR / f"lib{stem}-{build_key(src, flags, cc)}{suffix}"
    if not so.exists():
        # Compile to a per-pid temp path and os.replace: concurrent
        # importers never dlopen a half-written file.
        tmp = so.with_suffix(f".tmp{os.getpid()}")
        cmd = [cc[0], *flags, str(src), "-o", str(tmp)]
        try:
            subprocess.run(cmd, capture_output=True, check=True)
            os.replace(tmp, so)
        except (OSError, subprocess.CalledProcessError):
            tmp.unlink(missing_ok=True)
            return None
        _remove_stale(stem, suffix, keep=so)
    try:
        return ctypes.CDLL(str(so))
    except OSError:
        return None


def _remove_stale(stem: str, suffix: str, keep: Path) -> None:
    """Delete the libraries of ``stem`` built from other sources, flags
    or compilers (and the unkeyed name older checkouts built). Builds in
    progress (``.tmp<pid>``) are left alone."""
    stale = re.compile(
        rf"lib{re.escape(stem)}(-[0-9a-f]{{16}})?{re.escape(suffix)}"
    )
    for path in _DIR.iterdir():
        if path != keep and stale.fullmatch(path.name):
            path.unlink(missing_ok=True)
