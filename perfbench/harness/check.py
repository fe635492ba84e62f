"""The comparison that decides ``correct``: every verdict the window
produced against the plain reference verifier (``ref/bls.py``) on the
same sets.

A submission's reference verdict is one batch verification of its own
sets with fresh nonzero 64-bit scalars drawn from the seed. Each set's
public key is derived by the reference from the secret keys that define
the registry (``registry.secret_key``): the program's public keys,
aggregates and signatures are not taken. A verdict that never came, or
that came as an exception, counts as a mismatch, like a wrong one. The
one number compared is the count of mismatches; its limit is 0.

The reference runs after the window, in worker processes that import
nothing but ``ref/bls.py`` (no JAX, so no chip): a block of 131 sets
costs it about two seconds of one core. With ``control`` it also gives
the control's verdicts on the same sets: the reference with every
scalar 1, which accepts a pair of signature errors that cancel in an
unweighted sum.
"""

from __future__ import annotations

import multiprocessing
import multiprocessing.resource_tracker
import os
import random
import time
from concurrent.futures import ProcessPoolExecutor

from . import registry
from .traffic import root


def _job(sub, rng_seed: int):
    rng = random.Random(rng_seed)
    sets = [(registry.summed_secret_key(idx), msg, sig)
            for (idx, msg, _t), sig in zip(sub.specs, sub.sigs)]
    return sets, [rng.getrandbits(64) | 1 for _ in sets]


def _verdicts(job, control: bool):
    from ref import bls

    sets, scalars = job
    prepared = bls.prepare(sets)
    if prepared is None:
        return False, False
    want = bls.verify_prepared(prepared, scalars)
    ctrl = bls.verify_prepared(prepared, [1] * len(sets)) if control else None
    return want, ctrl


def _count(verdicts, want) -> tuple:
    mismatches = missing = 0
    for got, w in zip(verdicts, want):
        if not isinstance(got, bool):
            missing += 1
            mismatches += 1
        elif got != w:
            mismatches += 1
    return mismatches, missing


def compare(subs, verdicts, seed: int, workers: int, control: bool = False) -> dict:
    """Counts over the submissions in ``subs`` (all of them: each was due
    in the window): compared, mismatches, missing, tampered, rejected by
    the reference, the reference's wall time and, with ``control``, the
    control's mismatches against the reference."""
    t0 = time.perf_counter()
    base = int.from_bytes(root("reference", seed)[:8], "big")
    jobs = [_job(s, base + i) for i, s in enumerate(subs)]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=max(1, min(workers, len(jobs) or 1)),
                             mp_context=ctx) as pool:
        out = list(pool.map(_verdicts, jobs, [control] * len(jobs)))
    # the pool's semaphores started multiprocessing's resource tracker;
    # end it and wait for it, so the run leaves no process behind
    multiprocessing.resource_tracker._resource_tracker._stop()
    want = [w for w, _c in out]
    mismatches, missing = _count(verdicts, want)
    got = {
        "compared": len(subs),
        "mismatches": mismatches,
        "missing": missing,
        "rejected_by_reference": sum(1 for w in want if not w),
        "tampered": sum(1 for s in subs if s.tampered),
        "reference_s": time.perf_counter() - t0,
    }
    if control:
        got["control_mismatches"] = _count([c for _w, c in out], want)[0]
    return got


def workers() -> int:
    """Reference processes: the host's cores, at most 12."""
    return max(1, min(12, os.cpu_count() or 1))
