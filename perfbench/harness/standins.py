"""Backends that take the device's place under the scheduler: the
control (the reference with one guarantee broken) and the planted
faults the harness's own test must catch.

Each verifies ``(Signature, [G1Point, ...], message)`` triples, the
shape ``lighthouse_tpu.crypto.bls.verify_signature_sets`` hands its
active backend, with the benchmark's pure-Python reference
(``ref/bls.py``): the public keys are the points it is handed, summed.
"""

from __future__ import annotations

import random
import threading

from ref import bls


def _sets(prepared):
    return [
        ([bls.from_affine((p.x.n, 0), (p.y.n, 0)) for p in points],
         bytes(msg), bytes(sig.serialize()))
        for sig, points, msg in prepared
    ]


class ReferenceBackend:
    """The reference in the device's place. ``scalars="ones"`` is the
    control: batch verification with every random scalar set to 1, the
    step that would tempt a later PR (it skips the scalar
    multiplications); it accepts any batch whose signature errors cancel
    in an unweighted sum, which breaks the guarantee that each invalid
    signature is rejected. ``fault`` plants one of the harness test's
    faults: ``flip`` (every 3rd answer inverted where it is produced),
    ``half`` (the first half of each batch left out of the check) or
    ``stale`` (each call answers with the previous call's verdict)."""

    name = "bench-reference"

    def __init__(self, scalars: str = "random", fault: str | None = None,
                 seed: int = 0):
        self.scalars = scalars
        self.fault = fault
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._calls = 0
        self._last = True

    def verify_signature_sets(self, prepared) -> bool:
        sets = _sets(prepared)
        if not sets:
            return False
        if self.fault == "half" and len(sets) > 1:
            sets = sets[len(sets) // 2:]
        with self._lock:
            self._calls += 1
            call = self._calls
            rands = ([1] * len(sets) if self.scalars == "ones" else
                     [self._rng.getrandbits(64) | 1 for _ in sets])
        ok = bls.verify_sets(sets, rands)
        if self.fault == "flip" and call % 3 == 0:
            ok = not ok
        if self.fault == "stale":
            with self._lock:
                ok, self._last = self._last, ok
        return ok


def register(name: str, **kw) -> str:
    """Register a stand-in with the program's backend registry under
    ``name``; returns the name, for ``Service(backend=...)``."""
    from lighthouse_tpu.crypto import backend

    backend.register(name, lambda: ReferenceBackend(**kw))
    return name
