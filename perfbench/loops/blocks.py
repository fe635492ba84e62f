"""The closed block loop: whole blocks, one at a time, through
``VerificationScheduler.verify_now(kind="block")``, a new block each
call, as range sync imports them back to back.

A block holds the proposer and randao sets, ``attestations`` aggregates
over distinct committees of the two previous slots, and the sync
aggregate. Each attestation's participation is one of ``attestations``
shares spaced evenly over the mix's range, in an order drawn from the
seed, so every block and every seed carries the same number of keys.
Every ``tampered_every_blocks``-th block (the first included) carries
a tampered pair among its first half of attestations, where a check of
only the second half misses it.

The warm-up drives ``warmup_blocks`` blocks; the window signs enough
blocks for ``window_headroom`` times its length at the warm-up's median
block time, and stops early only if it verifies them all.
"""

from __future__ import annotations

import math
import random
import statistics
import time

from jax.profiler import TraceAnnotation

from harness.cell import Result
from harness.traffic import Chain, Sub, participants, root


def traffic(cfg: dict, mix: dict, seed: int, stream: str, seconds: float,
            warm: Result | None):
    if warm is None:
        n = int(mix["warmup_blocks"])
    else:
        times = warm.latency_s[1:] or warm.latency_s
        n = math.ceil(float(mix["window_headroom"]) * seconds
                      / statistics.median(times)) + 1
    chain = Chain(cfg, seed)
    rng = random.Random(root(stream, seed))
    spec = mix["block"]
    a = int(spec["attestations"])
    lo, hi = spec["participation"]
    shares = [lo + (hi - lo) * (j + 0.5) / a for j in range(a)]
    every = int(mix["tampered_every_blocks"])
    out = []
    for b in range(n):
        slot = 2 + b
        proposer = rng.randrange(chain.V)
        specs = [
            ([proposer], root("block", stream, seed, slot), None),
            ([proposer], root("randao", stream, seed, slot // chain.spe), None),
        ]
        duties = [(slot - 1 - d, c) for d in (0, 1) for c in range(chain.cps)]
        rng.shuffle(shares)
        for (s, c), share in zip(rng.sample(duties, a), shares):
            specs.append((participants(rng, chain.committee(s, c),
                                       round(chain.size * share)),
                          root("att", stream, seed, s, c), None))
        lo_s, hi_s = spec["sync_participation"]
        k = round(len(chain.sync_committee) * rng.uniform(lo_s, hi_s))
        specs.append((participants(rng, chain.sync_committee, k),
                      root("block", stream, seed, slot - 1), None))
        if b % every == 0:
            d = root("tamper", stream, seed, b)
            for k, t in zip(rng.sample(range(2, 2 + a // 2), 2), (1, -1)):
                idx, msg, _ = specs[k]
                specs[k] = (idx, msg, (d, t))
        out.append(Sub(0.0, "block", specs))
    return out


def drive(sched, subs, seconds: float) -> Result:
    """Verify ``subs`` one after another until ``seconds`` have passed;
    the window closes when the last block started in it returns."""
    res = Result(len(subs))
    t0 = res.t_start = time.perf_counter()
    n = 0
    while n < len(subs) and time.perf_counter() - t0 < seconds:
        t = time.perf_counter()
        with TraceAnnotation("perfbench.verify_now"):
            try:
                res.verdicts[n] = bool(sched.verify_now(subs[n].sets, "block"))
            except Exception as e:  # the verdict the caller would have seen
                res.verdicts[n] = e
        res.latency_s.append(time.perf_counter() - t)
        n += 1
    res.t_end = time.perf_counter()
    res.due = n
    return res
