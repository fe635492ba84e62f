"""What every loop's traffic is made of: real signed signature sets from
a seed. A loop (``loops/<name>.py``) composes submissions from these
pieces under the parameters of one traffic file and one configuration;
nothing here imports the program's own generators.

Every message is a 32-byte root derived from the seed and the duty it
stands for (slot, committee), so its signing and hashing cost is that
of a real one; every signature is made with the benchmark's C signer
(``signer``); an aggregate is signed with the summed secret key of its
participants, the same group element as the sum of their signatures.
A tampered set carries ``sig + t * H(d)``, t = +1 or -1: a pair of them
is invalid set by set while the pair's unweighted sum is unchanged.
"""

from __future__ import annotations

import hashlib
import random
from concurrent.futures import ThreadPoolExecutor

from . import registry, signer


def root(*parts) -> bytes:
    h = hashlib.sha256(b"perfbench")
    for p in parts:
        h.update(b"|" + str(p).encode())
    return h.digest()


class Sub:
    """One submission: due time (s from the window's start), kind, and
    its sets as ``(signer indices, message, tamper)`` specs until
    :func:`sign_all` fills ``sigs``; tamper is ``(d, t)`` or None."""

    __slots__ = ("due", "kind", "specs", "sigs", "tampered", "sets")

    def __init__(self, due: float, kind: str, specs):
        self.due = due
        self.kind = kind
        self.specs = specs
        self.sigs = None            # compressed signatures, one per set
        self.tampered = any(t is not None for _i, _m, t in specs)
        self.sets = None            # the program's SignatureSets, built last


class Chain:
    """Duties of a mainnet-preset chain over a registry of
    ``validators``: per-epoch committee shuffling and the sync committee,
    from the seed."""

    def __init__(self, cfg: dict, seed: int):
        self.seed = int(seed)
        self.V = int(cfg["validators"])
        self.spe = int(cfg["slots_per_epoch"])
        self.cps = int(cfg["committees_per_slot"])
        self.size = self.V // (self.spe * self.cps)
        if self.size * self.spe * self.cps != self.V:
            raise ValueError("registry does not split into equal committees")
        self._perm = {}
        rng = random.Random(root("sync-committee", seed))
        self.sync_committee = rng.sample(range(self.V), int(cfg["sync_committee_size"]))

    def committee(self, slot: int, c: int):
        epoch = slot // self.spe
        perm = self._perm.get(epoch)
        if perm is None:
            import numpy as np

            g = np.random.default_rng(
                int.from_bytes(root("shuffle", self.seed, epoch)[:8], "big"))
            perm = self._perm[epoch] = g.permutation(self.V)
        at = ((slot % self.spe) * self.cps + c) * self.size
        return perm[at:at + self.size]


def participants(rng, members, k: int):
    """``k`` of ``members``, sorted, drawn by ``rng``."""
    return sorted(int(v) for v in rng.sample(list(members), max(1, k)))


def sign_all(subs, threads: int) -> None:
    """Fill every submission's signatures: one C call per distinct
    message, spread over ``threads``."""
    jobs = {}
    for si, sub in enumerate(subs):
        for k, (idx, msg, _t) in enumerate(sub.specs):
            sk = (registry.secret_key(idx[0]) if len(idx) == 1
                  else registry.summed_secret_key(idx))
            jobs.setdefault(msg, []).append((si, k, sk))
    for sub in subs:
        sub.sigs = [None] * len(sub.specs)

    def run(item):
        msg, rows = item
        sigs = signer.sign_batch(msg, [sk for _, _, sk in rows])
        for (si, k, _), sig in zip(rows, sigs):
            tweak = subs[si].specs[k][2]
            if tweak is not None:
                sig = signer.g2_shift(sig, tweak[0], tweak[1])
            subs[si].sigs[k] = sig

    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        list(pool.map(run, jobs.items()))


def build_sets(subs, reg) -> None:
    """The program's input objects for each submission."""
    from lighthouse_tpu.crypto.bls import Signature, SignatureSet

    keys = reg.pubkeys
    for sub in subs:
        sub.sets = [
            SignatureSet(Signature.deserialize(sig), [keys[i] for i in idx],
                         msg, signing_indices=idx)
            for (idx, msg, _t), sig in zip(sub.specs, sub.sigs)
        ]
