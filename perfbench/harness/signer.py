"""ctypes binding of the C signer (``signer/``) that makes the traffic:
the registry's public keys and the signatures of every set.

``signer/bls12381.c`` and its tables are a frozen copy of the program's
own C engine (``lighthouse_tpu/_native/bls12381.c``, tables generated
from ``lighthouse_tpu/crypto/params.py``), kept because it signs at
registry scale in seconds. It decides nothing: every verdict is judged
by the pure-Python reference (``ref/bls.py``), which shares no code and
no table with it, so a fault shared by this signer and the program
shows as mismatches. It is compiled on first use into ``build/`` inside
the checkout (git-ignored), under a name keyed on the sources and the
compiler.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(HERE, "signer")
BUILD = os.path.join(HERE, "build")
# the Ethereum proof-of-possession ciphersuite tag (consensus-specs)
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

_lock = threading.Lock()
_lib = None


def _build() -> str:
    names = ("bench_ext.c", "bls12381.c", "bls12381_tables.h")
    h = hashlib.sha256()
    for name in names:
        with open(os.path.join(SRC, name), "rb") as f:
            h.update(f.read())
    cc = os.environ.get("CC") or "cc"
    h.update(cc.encode())
    out = os.path.join(BUILD, f"libbenchref-{h.hexdigest()[:16]}.so")
    if not os.path.exists(out):
        os.makedirs(BUILD, exist_ok=True)
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(
            [cc, "-O3", "-fPIC", "-shared", "-o", tmp,
             os.path.join(SRC, "bench_ext.c")],
            check=True, capture_output=True,
        )
        os.replace(tmp, out)
    return out


def lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            handle = ctypes.CDLL(_build())
            for fn in ("bench_init", "bench_keygen", "bench_sign_batch",
                       "bench_g2_shift", "bls_selftest"):
                getattr(handle, fn).restype = ctypes.c_int
            handle.bench_keygen.argtypes = (
                ctypes.c_char_p, ctypes.c_uint32, ctypes.c_void_p)
            handle.bench_sign_batch.argtypes = (
                ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p,
                ctypes.c_uint32, ctypes.c_uint32, ctypes.c_char_p,
                ctypes.c_void_p)
            handle.bench_g2_shift.argtypes = (
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32, ctypes.c_int,
                ctypes.c_void_p)
            handle.bench_init()
            if handle.bls_selftest() != 1:
                raise RuntimeError("signer selftest failed")
            _lib = handle
    return _lib


def keygen(sk0: int, n: int) -> bytes:
    """Raw affine x||y (96 bytes each) of ``[sk0 + i] G1`` for i < n."""
    buf = ctypes.create_string_buffer(96 * n)
    if lib().bench_keygen(sk0.to_bytes(32, "big"), n, buf) != 1:
        raise RuntimeError("bench_keygen failed")
    return buf.raw


def sign_batch(message: bytes, sks) -> list:
    """Compressed ``[sk] H(message)`` for each secret key in ``sks``."""
    sks = list(sks)
    n = len(sks)
    if not n:
        return []
    keys = b"".join(int(k).to_bytes(32, "big") for k in sks)
    buf = ctypes.create_string_buffer(96 * n)
    lib().bench_sign_batch(bytes(message), len(message), DST, len(DST),
                           n, keys, buf)
    raw = buf.raw
    return [raw[i * 96:(i + 1) * 96] for i in range(n)]


def g2_shift(sig: bytes, dmsg: bytes, sign: int) -> bytes:
    """Compressed ``sig + sign * H(dmsg)``."""
    buf = ctypes.create_string_buffer(96)
    if lib().bench_g2_shift(bytes(sig), bytes(dmsg), len(dmsg), DST,
                            len(DST), int(sign), buf) != 1:
        raise RuntimeError("bench_g2_shift: signature does not decompress")
    return buf.raw

