"""The validator registry of a configuration, made from nothing but its
size.

Validator ``i`` holds the secret key ``SK0 + i``: a deterministic
function of the index, the same in every run and every checkout, so the
public keys do not depend on the seed (the seed picks committees,
participation, messages and arrivals). Keys come from the benchmark's C
signer (``signer/``); the program only receives them as the
``PublicKey`` objects its pubkey cache would hold, and the reference
derives each public key from its secret key itself.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

from . import signer

# order of the BLS12-381 groups
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
SK0 = int.from_bytes(
    hashlib.sha256(b"perfbench validator registry").digest(), "big"
) % (R - (1 << 32)) + 1
CHUNK = 1 << 16


def secret_key(index: int) -> int:
    return SK0 + int(index)


def summed_secret_key(indices) -> int:
    """The secret key whose signature equals the aggregate of the
    members' signatures over one message."""
    return (SK0 * len(indices) + sum(int(i) for i in indices)) % R


def raw_keys(n: int, threads: int) -> bytes:
    """Raw affine x||y of the first ``n`` validators' public keys."""
    starts = list(range(0, n, CHUNK))
    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        parts = list(pool.map(
            lambda s: signer.keygen(SK0 + s, min(CHUNK, n - s)), starts
        ))
    return b"".join(parts)


class Registry:
    """``pubkeys``: the program's ``PublicKey`` objects, index-aligned,
    append-only (what a ``ValidatorPubkeyCache`` exposes to the device
    key table)."""

    def __init__(self, n: int, threads: int | None = None):
        from lighthouse_tpu.crypto.bls import PublicKey
        from lighthouse_tpu.crypto.cpu.curve import G1Point
        from lighthouse_tpu.crypto.cpu.fields import Fq

        self.n = int(n)
        raw = raw_keys(self.n, threads or os.cpu_count() or 1)
        frm = int.from_bytes
        self.pubkeys = [
            PublicKey(G1Point(Fq(frm(raw[o:o + 48], "big")),
                              Fq(frm(raw[o + 48:o + 96], "big"))))
            for o in range(0, 96 * self.n, 96)
        ]
