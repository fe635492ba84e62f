"""Bulk QoS class + headroom-driven admission control (ISSUE 15).

The robustness contract under test: ``submit(sets, kind, qos="bulk")``
queues deadline-insensitive work on a separate bounded queue that is
flushed only at gossip idle onto the big rungs, never preempts the
deadline class, pauses under the admission controller's two signals
(capacity headroom below the floor, gossip ``slo_burn`` latch) with
one journal event per excursion and hysteresis on resume, and degrades
overflow to the CALLER's thread — so under any bulk load gossip's
verdict-latency SLO is indistinguishable from the no-bulk baseline.

Everything here runs on stub verify functions (tier-1-eligible, no
jax); the staged-device half of the class rides the existing zgate
pipelines unchanged (a bulk flush is just a flush to the backend).
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from lighthouse_tpu.utils import flight_recorder as fr
from lighthouse_tpu.utils import metrics
from lighthouse_tpu.verification_service import (
    BulkAdmissionController,
    FlushPlanner,
    SloTracker,
    VerificationScheduler,
    backend_verify_bulk,
    traffic,
)


@pytest.fixture
def recorder(tmp_path):
    prev = fr.configure(
        capacity=4096, enabled=True, dump=False, dump_dir=str(tmp_path),
    )
    fr.clear()
    try:
        yield
    finally:
        fr.configure(**prev)
        fr.clear()


def _sets(n: int, kind: str = "x", pks: int = 1) -> list:
    return traffic.synthetic_sets(kind, n, pks, max(1, n // 8))


def _poison_sets(n: int) -> list:
    return [(None, [None], b"POISON") for _ in range(n)]


def _verify_ok(sets) -> bool:
    return not any(s[2] == b"POISON" for s in sets)


def _events(kind: str):
    return fr.events([kind])


def _counter(name: str) -> dict:
    m = metrics.get(name)
    if m is None:
        return {}
    return {k: c.value for k, c in m.children().items()}


def _latency_counts() -> dict:
    m = metrics.get("verification_scheduler_verdict_latency_seconds")
    return {k: c.total for k, c in m.children().items()} if m else {}


def _delta(after: dict, before: dict) -> dict:
    return {
        k: v - before.get(k, 0)
        for k, v in after.items()
        if v - before.get(k, 0) > 0
    }


def _dial(value: float):
    """A scripted headroom feed the tests steer."""
    state = {"h": value}

    def read():
        return state["h"]

    read.state = state
    return read


def _controller(headroom=0.5, **kw):
    d = _dial(headroom)
    kw.setdefault("min_interval_s", 0.0)
    ctl = BulkAdmissionController(headroom_fn=d, **kw)
    ctl.dial = d.state
    return ctl


def _scheduler(**kw) -> VerificationScheduler:
    kw.setdefault("verify_fn", _verify_ok)
    kw.setdefault("deadline_ms", 40.0)
    kw.setdefault("bulk_linger_ms", 15.0)
    return VerificationScheduler(**kw).start()


# ---------------------------------------------------------------------------
# The bulk queue: submission surface + flush policy
# ---------------------------------------------------------------------------


def test_submit_rejects_unknown_qos():
    sched = _scheduler()
    try:
        with pytest.raises(ValueError):
            sched.submit(_sets(1), "x", qos="express")
    finally:
        sched.stop()


def test_empty_bulk_submission_resolves_false_immediately():
    sched = _scheduler()
    try:
        assert sched.submit([], "backfill", qos="bulk").result(1) is False
    finally:
        sched.stop()


def test_bulk_flushes_at_gossip_idle_with_big_chunks(recorder):
    """A saturating bulk queue drains in bulk_flush_sets chunks under
    the `bulk` trigger, lands on the big-rung plan, and ticks the
    class-split counters."""
    bulk_before = _counter("verification_scheduler_bulk_sets_total")
    sched = _scheduler(bulk_flush_sets=128, bulk_linger_ms=5.0)
    try:
        futs = [
            sched.submit(_sets(64, "backfill", 4), "backfill", qos="bulk")
            for _ in range(4)
        ]
        assert all(f.result(10) for f in futs)
    finally:
        sched.stop()
    flushes = [
        e for e in _events("scheduler_flush")
        if e["fields"].get("qos") == "bulk"
    ]
    assert flushes, "no bulk-class flushes journaled"
    for e in flushes:
        assert e["fields"]["n_sets"] <= 128
        assert e["fields"]["trigger"] in ("bulk", "shutdown")
    assert any(e["fields"]["trigger"] == "bulk" for e in flushes)
    # full chunks: the 256 queued sets drain 128 at a time
    assert max(e["fields"]["n_sets"] for e in flushes) == 128
    d = _delta(
        _counter("verification_scheduler_bulk_sets_total"), bulk_before
    )
    assert d.get(("backfill",)) == 256
    st = sched.status()
    assert st["bulk"]["queue_sets"] == 0
    assert st["bulk"]["flushes_total"] >= 2


def test_bulk_never_preempts_deadline_class(recorder):
    """An already-ELIGIBLE bulk chunk (full, lingered-out) still yields
    to gossip that arrived after it: trigger priority is deadline >
    bulk, bulk waits for gossip idle, and no flush mixes the classes."""

    def verify(sets):
        time.sleep(0.05)
        return _verify_ok(sets)

    # deadline generous enough that the 50 ms stub verify cannot miss:
    # a gossip miss would latch the burn alert and (correctly!) throttle
    # bulk — this test isolates the never-preempt trigger priority
    sched = _scheduler(
        verify_fn=verify, deadline_ms=400.0, max_batch_sets=1,
        bulk_flush_sets=16, bulk_linger_ms=1.0,
    )
    try:
        # g1's full-trigger flush occupies the flush thread for ~50 ms
        g1 = sched.submit(_sets(1, "u"), "unaggregated")
        time.sleep(0.01)
        # while it runs: a FULL bulk chunk becomes eligible, THEN more
        # gossip arrives behind it
        bulk = sched.submit(_sets(16, "backfill"), "backfill", qos="bulk")
        time.sleep(0.005)
        g2 = sched.submit(_sets(1, "u"), "unaggregated")
        assert g1.result(10) and g2.result(10)
        assert bulk.result(10) is True
    finally:
        sched.stop()
    flushes = _events("scheduler_flush")
    bulk_ts = [
        e["t"] for e in flushes if e["fields"].get("qos") == "bulk"
    ]
    gossip_ts = [
        e["t"] for e in flushes if e["fields"].get("qos") == "deadline"
    ]
    assert bulk_ts and len(gossip_ts) == 2
    # no flush ever mixes the classes
    for e in flushes:
        kinds = e["fields"]["kinds"].split("+")
        if e["fields"].get("qos") == "bulk":
            assert kinds == ["backfill"]
        else:
            assert "backfill" not in kinds
    # the later-arriving gossip flushed BEFORE the already-eligible bulk
    assert max(gossip_ts) < min(bulk_ts)


def test_bulk_overflow_sheds_to_caller_thread(recorder):
    """Bulk-queue overflow degrades the submission to a synchronous
    verify in the CALLER's thread (path bulk_shed), never gossip's
    flush thread; the throttled queue keeps holding what it accepted."""
    ctl = _controller(headroom=0.0)  # throttled: the queue holds
    lat_before = _latency_counts()
    shed_before = _counter("verification_scheduler_bulk_shed_total")
    sched = _scheduler(
        bulk_admission=ctl, bulk_max_queue_sets=8, bulk_flush_sets=8,
        bulk_linger_ms=1.0,
    )
    try:
        held = sched.submit(_sets(6, "backfill"), "backfill", qos="bulk")
        time.sleep(0.05)
        assert not held.done()  # throttled, parked
        caller_thread = threading.get_ident()
        seen = {}
        real = sched._verify

        def spy(sets):
            seen["thread"] = threading.get_ident()
            return real(sets)

        sched._verify = spy
        over = sched.submit(_sets(6, "backfill"), "backfill", qos="bulk")
        assert over.result(1) is True  # resolved synchronously
        assert seen["thread"] == caller_thread
        sched._verify = real
        assert not held.done()
        # resume: the held future drains
        ctl.dial["h"] = 0.9
        assert held.result(10) is True
    finally:
        sched.stop()
    d = _delta(_counter("verification_scheduler_bulk_shed_total"),
               shed_before)
    assert d.get(("backfill",)) == 1
    lat = _delta(_latency_counts(), lat_before)
    assert lat.get(("backfill", "bulk_shed")) == 1
    assert lat.get(("backfill", "bulk")) == 1
    sheds = [
        e for e in _events("scheduler_shed")
        if e["fields"].get("qos") == "bulk"
    ]
    assert len(sheds) == 1


def test_stopped_scheduler_degrades_bulk_to_direct_call():
    sched = _scheduler()
    sched.stop()
    assert sched.submit(
        _sets(3, "backfill"), "backfill", qos="bulk"
    ).result(1) is True


def test_shutdown_drains_bulk_queue_every_future_resolves():
    """stop() covers BOTH classes — queued bulk resolves even while
    admission is throttled (the drain contract beats the valve)."""
    ctl = _controller(headroom=0.0)
    sched = _scheduler(bulk_admission=ctl, bulk_flush_sets=16)
    futs = [
        sched.submit(_sets(8, "backfill"), "backfill", qos="bulk")
        for _ in range(3)
    ]
    time.sleep(0.05)
    assert not any(f.done() for f in futs)
    sched.stop()
    assert all(f.result(5) is True for f in futs)


def test_bulk_poison_bisected_to_its_submitter(recorder):
    """Verdict identity holds on the bulk path: a poisoned bulk
    submission rejects alone; its co-flushed neighbor stays True."""
    sched = _scheduler(bulk_flush_sets=64, bulk_linger_ms=5.0)
    try:
        good = sched.submit(_sets(8, "backfill"), "backfill", qos="bulk")
        bad = sched.submit(_poison_sets(8), "backfill", qos="bulk")
        assert good.result(10) is True
        assert bad.result(10) is False
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# Admission controller
# ---------------------------------------------------------------------------


def test_admission_throttles_below_floor_one_event_per_excursion(recorder):
    ctl = _controller(headroom=0.5, floor=0.10, resume_headroom=0.20)
    ev_before = _counter(
        "verification_scheduler_bulk_throttle_events_total"
    )
    assert ctl.evaluate() is True
    ctl.dial["h"] = 0.05
    assert ctl.evaluate() is False
    # a continuing excursion re-confirms silently
    for _ in range(5):
        assert ctl.evaluate() is False
    throttles = _events("bulk_throttle")
    assert len(throttles) == 1
    assert throttles[0]["fields"]["reason"] == "headroom"
    assert throttles[0]["fields"]["headroom"] == 0.05
    # hysteresis: back above the floor is NOT enough
    ctl.dial["h"] = 0.15
    assert ctl.evaluate() is False
    assert not _events("bulk_resume")
    ctl.dial["h"] = 0.25
    assert ctl.evaluate() is True
    resumes = _events("bulk_resume")
    assert len(resumes) == 1
    assert resumes[0]["fields"]["throttled_s"] >= 0
    d = _delta(
        _counter("verification_scheduler_bulk_throttle_events_total"),
        ev_before,
    )
    assert d.get(("headroom",)) == 1
    st = ctl.status()
    assert st["throttled"] is False and st["excursions_total"] == 1


def test_admission_unknown_headroom_is_no_signal(recorder):
    """A box without the estimator (None) or a broken feed (raises)
    keeps the pre-admission-control behavior — bulk flows."""
    ctl = BulkAdmissionController(
        headroom_fn=lambda: None, min_interval_s=0.0
    )
    assert ctl.evaluate() is True

    def boom():
        raise RuntimeError("estimator down")

    ctl2 = BulkAdmissionController(headroom_fn=boom, min_interval_s=0.0)
    assert ctl2.evaluate() is True
    assert not _events("bulk_throttle")


def test_admission_slo_burn_latch_pauses_and_rearms(recorder):
    """A live gossip burn latch throttles regardless of headroom; the
    latch expiring (plus headroom clear) resumes."""
    latched = {"kinds": ["unaggregated"]}

    class Trk:
        def latched_kinds(self, now=None):
            return latched["kinds"]

    ctl = BulkAdmissionController(
        headroom_fn=lambda: 0.9, tracker=Trk(), min_interval_s=0.0
    )
    assert ctl.evaluate() is False
    t = _events("bulk_throttle")
    assert len(t) == 1 and t[0]["fields"]["reason"] == "slo_burn"
    assert t[0]["fields"]["latched_kinds"] == "unaggregated"
    latched["kinds"] = []
    assert ctl.evaluate() is True
    assert len(_events("bulk_resume")) == 1


# ---------------------------------------------------------------------------
# Per-class SLO tracking (slo.py)
# ---------------------------------------------------------------------------


def test_bulk_samples_skip_burn_buckets_and_label_summary():
    trk = SloTracker(window=64)
    t0 = 1000.0
    for i in range(50):
        trk.observe("backfill", "bulk", 0.5, False, now=t0 + i * 0.01,
                    qos="bulk")
    trk.observe("unaggregated", "fused", 0.01, False, now=t0 + 1.0)
    summ = trk.summary(now=t0 + 1.0)
    assert summ["kinds"]["backfill"]["qos"] == "bulk"
    assert summ["kinds"]["backfill"]["burn"] is None
    assert summ["kinds"]["backfill"]["p50_ms"] > 0  # quantiles visible
    assert summ["kinds"]["unaggregated"]["qos"] == "deadline"
    assert summ["kinds"]["unaggregated"]["burn"] is not None
    burn = trk.burn(now=t0 + 1.0)
    assert "backfill" not in burn["kinds"]
    assert "unaggregated" in burn["kinds"]


def test_bulk_arrival_forces_past_admission_rate_limit(recorder):
    """The first bulk submission after a signal collapse must journal
    bulk_throttle BEFORE its sets could queue, even when it lands
    within the evaluator's rate-limit window of the flush loop's last
    (still-admitted) read — the arrival-side evaluate() is FORCED."""
    ctl = _controller(headroom=0.5, min_interval_s=600.0)
    sched = _scheduler(bulk_admission=ctl, bulk_flush_sets=4)
    try:
        assert ctl.evaluate() is True  # burns the rate-limit window
        ctl.dial["h"] = 0.01  # collapse: below the 0.10 floor
        f = sched.submit(_sets(4, "backfill"), "backfill", qos="bulk")
        t = _events("bulk_throttle")
        assert len(t) == 1 and t[0]["fields"]["reason"] == "headroom"
        assert not f.done()  # parked, not flushed
    finally:
        sched.stop()  # the shutdown drain resolves it regardless
    assert f.result(5) is True


def test_mixed_kind_window_miss_ratio_is_deadline_scoped():
    """A mixed-class kind's saturating bulk stream must not dilute its
    windowed miss ratio: the denominator counts DEADLINE-class samples
    only (quantiles stay all-class; the per-path rows separate them)."""
    trk = SloTracker(window=1024)
    t0 = 4000.0
    for i in range(10):
        trk.observe("x", "fused", 9.9, True, now=t0 + i * 0.001)
    for i in range(500):
        trk.observe("x", "bulk", 0.5, False, now=t0 + 1 + i * 0.001,
                    qos="bulk")
    doc = trk.summary(now=t0 + 2.0)["kinds"]["x"]
    assert doc["window_count"] == 510
    assert doc["window_miss_ratio"] == 1.0  # 10/10, not 10/510
    # a pure-bulk kind reads 0.0 (no deadline denominator), not a crash
    trk.observe("y", "bulk", 0.5, False, now=t0 + 3.0, qos="bulk")
    assert trk.summary(now=t0 + 4.0)["kinds"]["y"]["window_miss_ratio"] == 0.0


def test_mixed_class_kind_label_is_sticky_deadline():
    """A kind served under BOTH classes (the trace format allows it)
    must keep its burn visibility: last-writer-wins labeling would let
    one bulk sample hide an ACTIVE gossip burn excursion from burn()
    and summary() while deadline samples keep feeding the buckets."""
    trk = SloTracker(window=64)
    t0 = 3000.0
    trk.observe("x", "fused", 9.9, True, now=t0)  # deadline-class miss
    trk.observe("x", "bulk", 0.5, False, now=t0 + 0.01, qos="bulk")
    summ = trk.summary(now=t0 + 0.02)
    assert summ["kinds"]["x"]["qos"] == "deadline"
    assert summ["kinds"]["x"]["burn"] is not None
    assert "x" in trk.burn(now=t0 + 0.02)["kinds"]
    # a bulk-only kind stays bulk (absent from the burn doc)
    trk.observe("y", "bulk", 0.5, False, now=t0 + 0.03, qos="bulk")
    assert trk.summary(now=t0 + 0.04)["kinds"]["y"]["qos"] == "bulk"
    assert "y" not in trk.burn(now=t0 + 0.04)["kinds"]


def test_latched_kinds_only_ever_names_deadline_kinds():
    trk = SloTracker(window=64)
    t0 = 2000.0
    # a miss storm on BOTH kinds — but bulk misses are defined away
    # before observe() in the batcher; even if a caller lied, the bulk
    # samples never reach the burn buckets, so no latch can exist
    for i in range(200):
        trk.observe("backfill", "bulk", 9.9, True, now=t0 + i * 0.01,
                    qos="bulk")
        trk.observe("unaggregated", "fused", 9.9, True, now=t0 + i * 0.01)
    latched = trk.latched_kinds(now=t0 + 2.5)
    assert "backfill" not in latched
    assert latched == ["unaggregated"]


def test_bulk_verdicts_never_tick_deadline_misses(recorder):
    """A bulk verdict slower than the SLO budget is NOT a miss — the
    class is deadline-insensitive by contract."""
    miss_before = _counter("verification_scheduler_deadline_misses_total")

    def slow(sets):
        time.sleep(0.12)
        return True

    sched = _scheduler(
        verify_fn=slow, deadline_ms=10.0, bulk_linger_ms=1.0,
        slo_grace=2.0,
    )
    try:
        assert sched.submit(
            _sets(4, "backfill"), "backfill", qos="bulk"
        ).result(10) is True
    finally:
        sched.stop()
    d = _delta(
        _counter("verification_scheduler_deadline_misses_total"),
        miss_before,
    )
    assert d.get(("backfill",)) is None
    assert not [
        e for e in _events("deadline_miss")
        if e["fields"]["kind"] == "backfill"
    ]


# ---------------------------------------------------------------------------
# Class-aware planning (planner.py)
# ---------------------------------------------------------------------------


class _Sub:
    def __init__(self, kind, sets):
        self.kind = kind
        self.sets = sets


def _m8_sets(n: int) -> list:
    """Geometry-only sets with at most 8 distinct messages, so warm
    rungs at the M=8 pad can cover any drain size."""
    return [(None, [None], b"m%d" % (i % 8)) for i in range(n)]


def _bulk_subs(total=512, per=128):
    return [_Sub("backfill", _m8_sets(per)) for _ in range(total // per)]


def test_bulk_plan_fills_largest_warm_rungs():
    """A 512-set bulk drain whose exact rung is cold re-bins onto the
    largest covering warm rung (two warm 256s beat one CPU-shed 512);
    the deadline class keeps its pre-ISSUE-15 plan (cold single)."""
    p = FlushPlanner(enabled=True)
    subs = _bulk_subs()
    warm = [(256, 1, 8)]
    bulk_plan = p.plan(subs, warm_rungs=warm, qos="bulk")
    assert bulk_plan.mode == "planned"
    assert [sb.rung for sb in bulk_plan.sub_batches] == [
        (256, 1, 8), (256, 1, 8),
    ]
    assert not any(sb.cold for sb in bulk_plan.sub_batches)
    dl_plan = p.plan(subs, warm_rungs=warm, qos="deadline")
    assert dl_plan.mode == "single"
    assert dl_plan.sub_batches[0].cold
    # and with no warm registry both classes take the exact big rung
    assert p.plan(subs, qos="bulk").rungs_label() == "512x1x8"


def test_bulk_rebin_covers_per_set_distinct_message_drains():
    """THE wired bulk workload (chain-segment/backfill proposal sigs:
    one DISTINCT message per set, m_req == n_sets): a 512-set drain
    whose (512,1,512) rung is still cold — it compiles LAST by design —
    re-bins onto a warm (256,1,256) rung, because coverage is judged
    per CHUNK (a 256-set chunk has at most 256 unique messages), not
    against the whole batch's m_req=512, which no smaller rung could
    ever satisfy."""
    p = FlushPlanner(enabled=True)
    subs = [
        _Sub("backfill",
             [(None, [None], b"d%d-%d" % (j, i)) for i in range(64)])
        for j in range(8)
    ]
    warm = [(256, 1, 256)]
    plan = p.plan(subs, warm_rungs=warm, qos="bulk")
    assert plan.mode == "planned"
    assert [sb.rung for sb in plan.sub_batches] == [
        (256, 1, 256), (256, 1, 256),
    ]
    assert not any(sb.cold for sb in plan.sub_batches)
    seen = [id(s) for sb in plan.sub_batches for s in sb.subs]
    assert sorted(seen) == sorted(id(s) for s in subs)
    # warm rungs that could only serve sliver chunks (an M=8 plane
    # against a distinct-message drain) are not worth re-binning for:
    # the drain stays one cold bin and decide_flush sheds exactly it
    sliver = p.plan(subs, warm_rungs=[(256, 1, 8)], qos="bulk")
    assert all(sb.cold for sb in sliver.sub_batches)


def test_bulk_plan_atomic_submission_larger_than_warm_stays_cold():
    """Submissions never split: one 300-set atomic submission cannot
    re-bin into 256-rungs — it keeps its own cold bin (and sheds),
    while its co-flushed neighbors still land warm."""
    p = FlushPlanner(enabled=True)
    subs = [
        _Sub("backfill", _m8_sets(300)),
        _Sub("backfill", _m8_sets(100)),
        _Sub("backfill", _m8_sets(100)),
    ]
    warm = [(256, 1, 8)]
    plan = p.plan(subs, warm_rungs=warm, qos="bulk")
    cold = [sb for sb in plan.sub_batches if sb.cold]
    warm_sbs = [sb for sb in plan.sub_batches if not sb.cold]
    assert len(cold) == 1 and cold[0].n_sets == 300
    assert warm_sbs and sum(sb.n_sets for sb in warm_sbs) == 200
    # every submission covered exactly once
    seen = [id(s) for sb in plan.sub_batches for s in sb.subs]
    assert sorted(seen) == sorted(id(s) for s in subs)


def test_bulk_dp_floor_keeps_chunks_big():
    """On a 4-shard mesh a 128-set bulk drain uses at most 2 shards
    (BULK_DP_MIN_SETS=64), where the deadline class would spread to 4."""
    from lighthouse_tpu.verification_service.planner import BULK_DP_MIN_SETS

    assert BULK_DP_MIN_SETS == 64
    p = FlushPlanner(enabled=True, dp_min_sets=8)
    subs = [_Sub("backfill", _sets(16, "backfill")) for _ in range(8)]
    shards = [0, 1, 2, 3]
    bulk_plan = p.plan(subs, shards=shards, qos="bulk")
    assert len(bulk_plan.shards_used()) <= 2
    dl_plan = p.plan(subs, shards=shards, qos="deadline")
    assert len(dl_plan.shards_used()) >= len(bulk_plan.shards_used())


# ---------------------------------------------------------------------------
# Chain wiring
# ---------------------------------------------------------------------------


def test_backend_verify_bulk_without_scheduler_is_direct(monkeypatch):
    from lighthouse_tpu.crypto import bls as _bls

    called = {}

    def direct(sets):
        called["n"] = len(sets)
        return True

    monkeypatch.setattr(_bls, "verify_signature_sets", direct)

    class Chain:
        pass

    assert backend_verify_bulk(Chain(), _sets(5), "backfill") is True
    assert called["n"] == 5


def test_backend_verify_bulk_routes_through_bulk_class(recorder):
    sched = _scheduler(bulk_linger_ms=5.0)

    class Chain:
        verification_scheduler = sched

    before = _counter("verification_scheduler_arrival_sets_total")
    try:
        assert backend_verify_bulk(
            Chain(), _sets(7, "chain_segment"), "chain_segment"
        ) is True
    finally:
        sched.stop()
    d = _delta(
        _counter("verification_scheduler_arrival_sets_total"), before
    )
    assert d.get(("chain_segment", "bulk")) == 7


# ---------------------------------------------------------------------------
# Lockstep + trace format
# ---------------------------------------------------------------------------


def test_backend_verify_bulk_chunks_big_segments(recorder):
    """The helper CHUNKS a big segment into bulk_flush_sets-sized
    submissions: submissions are atomic and a drain takes the first one
    whole, so one 10-set segment submitted whole would flush as one
    batch and break the head-of-line bound (a gossip arrival waits at
    most ONE chunk's wall). 10 sets at chunk 4 -> three flushes."""
    sched = _scheduler(bulk_flush_sets=4, bulk_linger_ms=1.0)

    class Chain:
        pass

    chain = Chain()
    chain.verification_scheduler = sched
    try:
        assert backend_verify_bulk(
            chain, _sets(10, "backfill"), "backfill"
        ) is True
        st = sched.status()
        assert st["bulk"]["sets_flushed_total"] == 10
        assert st["bulk"]["flushes_total"] == 3  # 4 + 4 + 2
    finally:
        sched.stop()


def test_utilization_numerator_excludes_parked_bulk_demand():
    """The admission valve must never throttle on demand it itself
    controls: the estimator's utilization numerator counts
    deadline-class arrivals + ADMITTED bulk service, not raw bulk
    offered demand — a persistent parked submitter would otherwise
    hold headroom below the resume threshold forever. The per-kind
    arrival SERIES keeps the full demand picture."""
    from lighthouse_tpu.utils import timeseries

    arrivals = metrics.counter_vec(
        "verification_scheduler_arrival_sets_total",
        labelnames=("kind", "path"),
    )
    served = metrics.counter_vec(
        "verification_scheduler_bulk_sets_total", labelnames=("kind",),
    )
    # ensure every label exists before the baseline pass (a first
    # sighting rates nothing)
    arrivals.with_labels("bq_gossip", "submit")
    arrivals.with_labels("bq_backfill", "bulk")
    served.with_labels("bq_backfill")
    timeseries.reset()
    try:
        t0 = time.time()
        assert timeseries.sample(now=t0) is not None  # baseline pass
        arrivals.with_labels("bq_gossip", "submit").inc(100)
        arrivals.with_labels("bq_backfill", "bulk").inc(1000)  # parked
        served.with_labels("bq_backfill").inc(40)  # admitted service
        est = timeseries.sample(now=t0 + 10.0)
        # 10 deadline sets/s + 4 served bulk sets/s; NOT + 100 parked
        assert est["arrival_sets_per_sec"] == pytest.approx(14.0)
    finally:
        timeseries.reset()


def test_lone_saturating_bulk_does_not_throttle_itself(
    recorder, monkeypatch,
):
    """A lone range sync that fills the device (gossip idle) reads the
    device's headroom as nearly gone — its own admitted service is in
    the utilization numerator — but the valve reads the headroom the
    DEADLINE class leaves, so it stays open; deadline demand at the
    same rate closes it. Before, the valve read ``headroom_ratio`` and
    the lone caller throttled itself once per sampling interval."""
    from lighthouse_tpu.utils import timeseries

    arrivals = metrics.counter_vec(
        "verification_scheduler_arrival_sets_total",
        labelnames=("kind", "path"),
    )
    served = metrics.counter_vec(
        "verification_scheduler_bulk_sets_total", labelnames=("kind",),
    )
    arrivals.with_labels("bq_seg_gossip", "submit")
    arrivals.with_labels("bq_seg_sync", "bulk")
    served.with_labels("bq_seg_sync")
    # 100 sets/s of capacity, whatever other tests left measured
    monkeypatch.setattr(
        timeseries, "measured_cost_per_set", lambda: (0.01, "override")
    )
    timeseries.reset()
    try:
        t0 = time.time()
        assert timeseries.sample(now=t0) is not None  # baseline pass
        # the segment's chunks: all submitted, 95% of capacity served
        arrivals.with_labels("bq_seg_sync", "bulk").inc(4192)
        served.with_labels("bq_seg_sync").inc(95)
        est = timeseries.sample(now=t0 + 1.0)
        assert est["headroom_ratio"] == pytest.approx(0.05)
        assert est["deadline_headroom_ratio"] == pytest.approx(1.0)
        ctl = BulkAdmissionController(min_interval_s=0.0)
        assert ctl.evaluate() is True
        assert not _events("bulk_throttle")
        # the same rate of deadline-class demand leaves no headroom
        arrivals.with_labels("bq_seg_gossip", "submit").inc(95)
        est = timeseries.sample(now=t0 + 2.0)
        assert est["deadline_headroom_ratio"] == pytest.approx(0.05)
        assert ctl.evaluate() is False
        assert len(_events("bulk_throttle")) == 1
    finally:
        timeseries.reset()


def test_lockstep_models_bulk_queue_deterministically():
    evs = traffic.bulk_backfill_under_gossip(duration_s=4.0, seed=7)
    assert any(e.get("qos") == "bulk" for e in evs)
    a = traffic.lockstep_replay(evs, bulk_flush_sets=256)
    b = traffic.lockstep_replay(evs, bulk_flush_sets=256)
    assert a["digest"] == b["digest"]
    bulk_flushes = [f for f in a["flushes"] if f["qos"] == "bulk"]
    assert bulk_flushes
    for f in bulk_flushes:
        assert f["n_sets"] <= 256
        assert all("backfill" == sb["kinds"] for sb in f["sub_batches"])
    gossip_flushes = [f for f in a["flushes"] if f["qos"] == "deadline"]
    assert gossip_flushes
    for f in gossip_flushes:
        for sb in f["sub_batches"]:
            assert "backfill" not in sb["kinds"]
    assert a["bulk"]["sets_offered"] == sum(
        e["n_sets"] for e in evs if e.get("qos") == "bulk"
    )


def test_trace_format_rejects_bad_qos(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with pytest.raises(ValueError):
        traffic.write_trace(
            path,
            [{"t": 0.0, "kind": "x", "n_sets": 1, "qos": "turbo"}],
            name="t", seed=0,
        )
    with pytest.raises(ValueError):
        traffic.write_trace(
            path,
            [{"t": 0.0, "kind": "x", "n_sets": 1, "qos": "bulk",
              "path": "verify_now"}],
            name="t", seed=0,
        )
    # a valid bulk event round-trips with its class
    traffic.write_trace(
        path,
        [{"t": 0.0, "kind": "backfill", "n_sets": 4, "qos": "bulk"}],
        name="t", seed=0,
    )
    _h, evs = traffic.read_trace(path)
    assert evs[0]["qos"] == "bulk"


# ---------------------------------------------------------------------------
# The acceptance gate: class isolation under saturating bulk
# ---------------------------------------------------------------------------

_GOSSIP_KINDS = ("unaggregated", "aggregate", "sync_message")


def _gossip_slo(report):
    out = {}
    for kind in _GOSSIP_KINDS:
        rec = report["slo"]["kinds"].get(kind)
        if rec:
            out[kind] = {
                "p99_ms": rec["p99_ms"],
                "miss": rec["window_miss_ratio"],
            }
    return out


def test_bulk_isolation_gossip_slo_indistinguishable(
    recorder, monkeypatch,
):
    """THE ISSUE 15 acceptance (stub backend): replay the
    bulk_backfill_under_gossip composite vs its gossip-only baseline —
    same gossip arrivals by construction. Gossip per-kind p99 and miss
    ratio under saturating bulk within 10% (+ a small absolute slack
    for timer jitter on a contended box) of the baseline; bulk drains
    >= 80% of offered sets via idle-time bulk flushes by trace end."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools import traffic_replay

    # small chunks: on this stub backend one 512-set chunk's wall would
    # rival the deadline — the documented head-of-line knob
    monkeypatch.setenv("LIGHTHOUSE_TPU_SCHED_BULK_FLUSH_SETS", "64")
    monkeypatch.setenv("LIGHTHOUSE_TPU_SCHED_BULK_LINGER_MS", "10")
    dur, seed, scale, deadline = 4.0, 9, 0.5, 60.0
    kw = dict(
        set_factory=traffic.synthetic_sets,
        deadline_ms=deadline,
        max_batch_sets=256,
        time_scale=scale,
        max_workers=96,
    )
    base_evs = traffic.gossip_steady(duration_s=dur, seed=seed)
    comp_evs = traffic.bulk_backfill_under_gossip(
        duration_s=dur, seed=seed
    )
    # the composite's gossip half IS the baseline trace
    assert [e for e in comp_evs if e.get("qos") != "bulk"] == base_evs
    baseline = traffic_replay.run_timed_replay(
        base_evs, verify_fn=traffic_replay.make_stub_verify(0.0002), **kw
    )
    fr.clear()
    bulk_before = _counter("verification_scheduler_bulk_sets_total")
    composite = traffic_replay.run_timed_replay(
        comp_evs, verify_fn=traffic_replay.make_stub_verify(0.0002), **kw
    )
    base_slo = _gossip_slo(baseline)
    comp_slo = _gossip_slo(composite)
    assert set(comp_slo) == set(base_slo)
    for kind in base_slo:
        p99_0 = base_slo[kind]["p99_ms"]
        p99_1 = comp_slo[kind]["p99_ms"]
        # 10% relative + 15 ms absolute: quantiles on a box this slow
        # carry timer jitter larger than 10% of a near-zero baseline
        assert p99_1 <= p99_0 * 1.10 + 15.0, (
            f"{kind}: gossip p99 moved {p99_0} -> {p99_1} under bulk"
        )
        m0, m1 = base_slo[kind]["miss"], comp_slo[kind]["miss"]
        assert m1 <= m0 * 1.10 + 0.02, (
            f"{kind}: gossip miss ratio moved {m0} -> {m1} under bulk"
        )
    # bulk throughput floor: >= 80% of offered sets drained by genuine
    # idle-time bulk flushes (the shutdown drain is excluded — it would
    # flatter a scheduler that never found idle time)
    offered = sum(
        e["n_sets"] for e in comp_evs if e.get("qos") == "bulk"
    )
    assert offered > 0
    drained = sum(
        e["fields"]["n_sets"]
        for e in _events("scheduler_flush")
        if e["fields"].get("qos") == "bulk"
        and e["fields"]["trigger"] == "bulk"
    )
    assert drained >= 0.8 * offered, (
        f"bulk drained {drained}/{offered} before shutdown"
    )
    d = _delta(
        _counter("verification_scheduler_bulk_sets_total"), bulk_before
    )
    assert d.get(("backfill",)) == offered  # every future resolved
    assert composite["verdicts"]["error"] == 0


def test_bulk_throttle_journals_before_gossip_miss_burst(recorder):
    """The predictive-ordering pin: headroom collapses BEFORE the
    backend slows (the estimator's certified lead, ISSUE 14), so the
    admission controller's bulk_throttle journal entry strictly
    precedes the first gossip deadline miss of the burst."""
    slow = {"on": False}

    def verify(sets):
        if slow["on"]:
            time.sleep(0.15)
        return _verify_ok(sets)

    class NoLatch:
        # isolate the headroom signal: the REAL tracker's burn latch
        # would also (correctly) hold the throttle for a full fast
        # window after the injected misses, stalling this test's resume
        def latched_kinds(self, now=None):
            return []

    d = _dial(0.6)
    ctl = BulkAdmissionController(
        headroom_fn=d, tracker=NoLatch(), floor=0.10,
        resume_headroom=0.20, min_interval_s=0.0,
    )
    ctl.dial = d.state
    sched = _scheduler(
        verify_fn=verify, deadline_ms=25.0, slo_grace=2.0,
        bulk_admission=ctl, bulk_flush_sets=16, bulk_linger_ms=5.0,
    )
    try:
        # steady state: gossip + bulk both flowing
        assert sched.submit(_sets(1, "u"), "unaggregated").result(5)
        assert sched.submit(
            _sets(16, "backfill"), "backfill", qos="bulk"
        ).result(5)
        # the dial collapses (prediction) ...
        ctl.dial["h"] = 0.02
        held = sched.submit(
            _sets(16, "backfill"), "backfill", qos="bulk"
        )
        time.sleep(0.05)
        assert not held.done()  # bulk paused, throttle journaled
        # ... THEN the saturation actually lands on gossip
        slow["on"] = True
        futs = [
            sched.submit(_sets(1, "u"), "unaggregated") for _ in range(3)
        ]
        assert all(f.result(10) for f in futs)
        slow["on"] = False
        ctl.dial["h"] = 0.9
        assert held.result(10) is True
    finally:
        sched.stop()
    throttles = _events("bulk_throttle")
    misses = [
        e for e in _events("deadline_miss")
        if e["fields"]["kind"] == "unaggregated"
    ]
    assert throttles and misses, (len(throttles), len(misses))
    assert throttles[0]["t"] < misses[0]["t"], (
        "bulk_throttle must precede the gossip miss burst"
    )
    assert len(_events("bulk_resume")) == 1


# ---------------------------------------------------------------------------
# jax-freedom (the verification_service import rule)
# ---------------------------------------------------------------------------


def test_admission_module_jax_free_subprocess():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from lighthouse_tpu.verification_service import admission\n"
         "ctl = admission.BulkAdmissionController(\n"
         "    headroom_fn=lambda: 0.05, min_interval_s=0.0)\n"
         "assert ctl.evaluate() is False\n"
         "assert ctl.status()['throttled'] is True\n"
         "from lighthouse_tpu.verification_service import traffic\n"
         "evs = traffic.bulk_backfill_under_gossip(duration_s=2.0, seed=1)\n"
         "rep = traffic.lockstep_replay(evs)\n"
         "assert rep['bulk']['flushes'] >= 0\n"
         "assert 'jax' not in sys.modules, 'bulk layer must stay jax-free'\n"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
