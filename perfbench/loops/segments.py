"""The closed segment loop: range sync's chain segments, one at a time,
through ``backend_verify_bulk(chain, sets, kind="chain_segment")``, the
call ``BeaconChain.signature_verify_chain_segment`` makes, with the
harness's scheduler as the chain's ``verification_scheduler``. The call
cuts a segment into ``bulk_flush_sets`` chunks and submits them all to
the scheduler's bulk class; the next segment goes in when the verdict
of this one returns, as range sync imports a batch's epochs in turn.
The verdict is one bool per segment.

A segment holds the blocks of one epoch's slots (``segment_blocks``),
each built by ``loops/blocks.py``: consecutive blocks carry aggregates
of the same committees with other participants, so messages repeat
within a segment and within a chunk. Every ``tampered_every_segments``-th
segment (the first included) carries one block with a pair of
attestation signatures whose errors cancel in an unweighted sum, both
in the first half of one chunk, where a check of only the chunk's
second half misses them: the first such segment of a stream in its
last, partial chunk, the others in a chunk, a block and two
attestations drawn from the seed.

The capacity sampler runs while the loop drives, as a node's does, so
the bulk admission valve reads a live headroom. The warm-up drives
``warmup_segments`` segments; the window signs enough segments for
``window_headroom`` times its length at the warm-up's median segment
time, and stops early only if it verifies them all.
"""

from __future__ import annotations

import math
import random
import statistics
import time
import types

from jax.profiler import TraceAnnotation

from harness.cell import Result
from harness.traffic import Sub, root
from loops import blocks

KIND = "chain_segment"


def traffic(cfg: dict, mix: dict, seed: int, stream: str, seconds: float,
            warm: Result | None):
    if warm is None:
        n = int(mix["warmup_segments"])
    else:
        times = warm.latency_s[1:] or warm.latency_s
        n = math.ceil(float(mix["window_headroom"]) * seconds
                      / statistics.median(times)) + 1
    per = int(cfg["segment_blocks"])
    # blocks.py numbers its blocks from slot 2: the first whole epoch
    # starts ``skip`` blocks in; the blocks before it are dropped unsigned
    skip = int(cfg["slots_per_epoch"]) - 2
    total = skip + n * per
    built = blocks.traffic(
        cfg, dict(mix, warmup_blocks=total, tampered_every_blocks=total + 1),
        seed, stream, 0.0, None)[skip:]
    chunk = int(cfg["scheduler"]["bulk_flush_sets"])
    a = int(mix["block"]["attestations"])
    rng = random.Random(root("segments", stream, seed))
    every = int(mix["tampered_every_segments"])
    out = []
    for k in range(n):
        segment = built[k * per:(k + 1) * per]
        specs = [s for b in segment for s in b.specs]
        if k % every == 0:
            pairs = _pairs(segment, a, chunk, len(specs))
            chunks = sorted(pairs)
            c = chunks[-1] if k == 0 else rng.choice(chunks)
            d = root("tamper", stream, seed, "segment", k)
            for i, t in zip(rng.sample(rng.choice(pairs[c]), 2), (1, -1)):
                idx, msg, _ = specs[i]
                specs[i] = (idx, msg, (d, t))
        out.append(Sub(0.0, KIND, specs))
    return out


def _pairs(segment, a: int, chunk: int, n: int) -> dict:
    """Where a tampered pair may sit: by chunk, the attestation sets of
    each block that has two or more in the first half of that chunk
    (where a check of only the chunk's second half misses them), as
    flat indices into the segment's ``n`` sets."""
    out, at = {}, 0
    for b in segment:
        # a block's sets: proposer, randao, ``a`` attestations, sync
        by_chunk = {}
        for i in range(at + 2, at + 2 + a):
            c = i // chunk
            if i - c * chunk < min(chunk, n - c * chunk) // 2:
                by_chunk.setdefault(c, []).append(i)
        for c, atts in by_chunk.items():
            if len(atts) >= 2:
                out.setdefault(c, []).append(atts)
        at += len(b.specs)
    return out


def drive(sched, subs, seconds: float) -> Result:
    """Verify ``subs`` one segment after another until ``seconds`` have
    passed; the window closes when the last segment started in it
    returns."""
    from lighthouse_tpu.utils import timeseries
    from lighthouse_tpu.verification_service import backend_verify_bulk

    chain = types.SimpleNamespace(verification_scheduler=sched)
    if timeseries.enabled():
        timeseries.start_sampler()
    res = Result(len(subs))
    t0 = res.t_start = time.perf_counter()
    n = 0
    try:
        while n < len(subs) and time.perf_counter() - t0 < seconds:
            t = time.perf_counter()
            with TraceAnnotation("perfbench.verify_bulk"):
                try:
                    res.verdicts[n] = bool(
                        backend_verify_bulk(chain, subs[n].sets, kind=KIND))
                except Exception as e:  # the verdict the caller would have seen
                    res.verdicts[n] = e
            res.latency_s.append(time.perf_counter() - t)
            n += 1
    finally:
        res.t_end = time.perf_counter()
        timeseries.stop_sampler()
    res.due = n
    return res
