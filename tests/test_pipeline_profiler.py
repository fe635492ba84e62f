"""Pipeline-occupancy profiler (ISSUE 12): per-shard device idle-gap
(bubble) attribution, flush critical-path timelines, and the
overlap-potential projection — at the scheduling layer (stub/fake
device backends that report their own pack/stage walls; no jax)."""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import pytest

from lighthouse_tpu.crypto.device import mesh as mesh_mod
from lighthouse_tpu.utils import flight_recorder
from lighthouse_tpu.utils import pipeline_profiler as pp
from lighthouse_tpu.verification_service import VerificationScheduler
from lighthouse_tpu.verification_service.planner import FlushPlanner

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def prof():
    """Clean, enabled profiler + journal; state restored after."""
    prev = pp.configure(enabled=True)
    pp.reset()
    flight_recorder.clear()
    yield pp
    pp.configure(**prev)
    pp.reset()
    flight_recorder.clear()


@pytest.fixture
def mesh2():
    m = mesh_mod.DeviceMesh(devices=[None, None])
    mesh_mod.set_mesh(m)
    yield m
    mesh_mod.clear_mesh(m)


def make_fake_device_verify(pack_s: float, device_s: float,
                            fail_msgs=frozenset()):
    """A backend that behaves like the staged device path from the
    profiler's point of view: it reports a host pack wall and a
    per-shard device dispatch wall through the SAME hooks the real
    packers and ``_run_stage`` call."""

    def verify(sets):
        t0 = time.perf_counter()
        if pack_s > 0:
            time.sleep(pack_s)
        pp.note_pack_wall(t0, time.perf_counter())
        shard = mesh_mod.current_shard() or 0
        d0 = time.perf_counter()
        if device_s > 0:
            time.sleep(device_s)
        pp.note_stage_wall("stage2", shard, d0, time.perf_counter())
        return all(m not in fail_msgs for (_s, _p, m) in sets)

    return verify


def _mk_sets(n, msg=b"good", pubkeys=1):
    return [(None, [None] * pubkeys, msg) for _ in range(n)]


def _feed(sched, submissions):
    """Submit concurrently (bucket-full fires on the last feeder) and
    wait for every verdict; returns the per-submission results."""
    futs = [None] * len(submissions)

    def go(i):
        kind, sets = submissions[i]
        futs[i] = sched.submit(sets, kind)

    threads = [
        threading.Thread(target=go, args=(i,))
        for i in range(len(submissions))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [f.result(timeout=60) for f in futs]


# ---------------------------------------------------------------------------
# Attribution arithmetic
# ---------------------------------------------------------------------------


def test_gap_attribution_exact_and_priority_ordered():
    """The cause split is exact interval arithmetic: overlapping
    activities assign in priority order over still-uncovered
    sub-intervals, the remainder is `other`, and the split always sums
    to the gap."""
    activity = [
        ("pack", 0.0, 2.0),
        ("plan", 1.0, 3.0),     # overlaps pack on [1,2): pack wins there
        ("queue_empty", 4.0, 5.0),
    ]
    out = pp._attribute_gap(0.0, 6.0, activity)
    assert out["pack"] == pytest.approx(2.0)
    assert out["plan"] == pytest.approx(1.0)   # only [2,3)
    assert out["queue_empty"] == pytest.approx(1.0)
    assert out["other"] == pytest.approx(2.0)  # [3,4) + [5,6)
    assert sum(out.values()) == pytest.approx(6.0)
    # activity fully outside the gap contributes nothing
    out2 = pp._attribute_gap(10.0, 11.0, activity)
    assert out2 == {"other": pytest.approx(1.0)}


def test_per_cause_seconds_sum_to_measured_idle(prof):
    """Through a real scheduler: the shard's per-cause bubble seconds
    sum EXACTLY to its measured idle, and the /metrics counters agree
    with the summary document."""
    from lighthouse_tpu.utils import metrics

    bub = metrics.get("bls_device_bubble_seconds_total")
    before = {k: c.value for k, c in bub.children().items()}
    sched = VerificationScheduler(
        verify_fn=make_fake_device_verify(0.01, 0.003),
        deadline_ms=5.0, max_batch_sets=64,
    ).start()
    try:
        for i in range(10):
            assert sched.submit(_mk_sets(1, b"m%d" % (i % 2)),
                                "unaggregated").result(timeout=30)
            time.sleep(0.003)
    finally:
        sched.stop()
    doc = prof.summary()
    sh = doc["shards"]["0"]
    assert sh["dispatches"] >= 2 and sh["gaps"] >= 1
    assert sh["idle_s"] > 0
    assert sum(sh["causes"].values()) == pytest.approx(
        sh["idle_s"], abs=2e-5
    )
    assert 0.0 < sh["bubble_ratio"] < 1.0
    counter_idle = sum(
        c.value - before.get(k, 0.0)
        for k, c in bub.children().items() if k[0] == "0"
    )
    # summary rounds to 6 decimals; the counter is exact
    assert counter_idle == pytest.approx(sh["idle_s"], abs=1e-5)


# ---------------------------------------------------------------------------
# Replay-driven cause attribution (the acceptance shape)
# ---------------------------------------------------------------------------


def _replay(pack_s: float, seed=7, duration=3.0):
    import tools.traffic_replay as traffic_replay
    from lighthouse_tpu.verification_service import traffic

    events = traffic.GENERATORS["gossip_steady"](
        seed=seed, duration_s=duration
    )

    def set_factory(kind, n_sets, pubkeys, messages):
        return traffic.synthetic_sets(kind, n_sets, pubkeys, messages)

    return traffic_replay.run_timed_replay(
        events,
        verify_fn=make_fake_device_verify(pack_s, 0.002),
        set_factory=set_factory,
        deadline_ms=25.0,
        time_scale=0.25,
    )


def test_injected_slow_pack_flips_dominant_cause_to_pack(prof):
    """Gossip-steady replay through the real scheduler: with a cheap
    pack the dominant bubble cause is the traffic/batching structure
    (queue_empty/other — the deadline the scheduler deliberately waits
    is not the pipeline's fault); inject a slow pack (the
    --slow-flush-every-style hook, here on every flush) and the
    dominant cause flips to `pack` — the instrument ROADMAP item 5
    needs pointing at the right culprit."""
    rep = _replay(pack_s=0.0002)
    base = prof.summary()["shards"]["0"]
    assert rep["verdicts"]["error"] == 0
    assert base["dominant_cause"] != "pack", base
    prof.reset()
    flight_recorder.clear()
    rep = _replay(pack_s=0.03)
    slow = prof.summary()["shards"]["0"]
    assert rep["verdicts"]["error"] == 0
    assert slow["dominant_cause"] == "pack", slow
    assert sum(slow["causes"].values()) == pytest.approx(
        slow["idle_s"], abs=2e-5
    )
    # the flush records see the same story: pack dominates the
    # critical path of most flushes
    evs = flight_recorder.events(kinds=["pipeline_flush"])
    assert evs
    crit = [e["fields"]["critical_path"] for e in evs]
    assert crit.count("pack") > len(crit) // 2, crit


# ---------------------------------------------------------------------------
# Exactly-once flush records
# ---------------------------------------------------------------------------


def test_pipeline_flush_exactly_once_incl_bisection(prof):
    """One pipeline_flush row per scheduler flush — a flush whose fused
    verdict is False and bisects still journals exactly one row, and
    the backpressure shed path (no flush) journals none."""
    sched = VerificationScheduler(
        verify_fn=make_fake_device_verify(
            0.0, 0.001, fail_msgs=frozenset([b"poison"])
        ),
        deadline_ms=50.0, max_batch_sets=8,
    ).start()
    try:
        res = _feed(sched, [
            ("unaggregated", _mk_sets(2, b"good")),
            ("aggregate", _mk_sets(2, b"poison")),
            ("sync_message", _mk_sets(2, b"good")),
        ])
    finally:
        sched.stop()
    assert res.count(False) == 1  # the poison, isolated by bisection
    flushes = flight_recorder.events(kinds=["scheduler_flush"])
    pipeline = flight_recorder.events(kinds=["pipeline_flush"])
    assert len(flushes) >= 1
    assert len(pipeline) == len(flushes), (len(pipeline), len(flushes))
    # the bisected flush's record carries the whole resolution tree's
    # device time (retries included) and the False verdict
    row = pipeline[0]["fields"]
    assert row["verdict"] is False
    assert row["device_s"] > 0
    # backpressure shed (scheduler stopped): resolves in the caller's
    # thread, NOT a flush — no pipeline_flush row
    n = len(flight_recorder.events(kinds=["pipeline_flush"]))
    assert sched.submit(_mk_sets(1), "unaggregated").result(timeout=30)
    assert len(flight_recorder.events(kinds=["pipeline_flush"])) == n


def test_pipeline_flush_row_on_cold_route_shed(prof):
    """A flush shed to the compile-service CPU fallback (cold rung)
    still journals exactly one pipeline_flush row — with the fallback
    wall as the critical path and the bubble cause `compile` feeding
    the next dispatch's gap."""
    from lighthouse_tpu.compile_service import CompileService

    device_verify = make_fake_device_verify(0.0, 0.002)

    def slow_compile(b, k, m):
        time.sleep(0.5)
        return {}

    svc = CompileService(
        rungs=((1024, 1024, 1024),),  # never routes this traffic warm
        compile_rung_fn=slow_compile,
        fallback_verify_fn=lambda sets: (time.sleep(0.02), True)[1],
    ).start()
    sched = VerificationScheduler(
        verify_fn=device_verify, deadline_ms=20.0, max_batch_sets=8,
        compile_service=svc,
    ).start()
    try:
        # a sync BEFORE the shed flush: the next dispatch's gap then
        # spans the fallback window, so its seconds attribute to
        # `compile` (the fallback wall was compile-caused)
        t0 = time.perf_counter()
        pp.note_stage_wall("stage2", 0, t0, t0 + 1e-4)
        assert sched.submit(_mk_sets(2), "unaggregated").result(timeout=30)
        rows = flight_recorder.events(kinds=["pipeline_flush"])
        assert len(rows) == 1
        row = rows[0]["fields"]
        assert row["fallback_s"] > 0
        assert row["critical_path"] == "fallback"
        assert row["device_s"] == 0.0
        t0 = time.perf_counter()
        pp.note_stage_wall("stage2", 0, t0, t0 + 1e-4)
    finally:
        sched.stop()
        svc.stop()
    causes = pp.summary()["shards"]["0"]["causes"]
    assert causes.get("compile", 0.0) > 0, causes


# ---------------------------------------------------------------------------
# Concurrency conservation
# ---------------------------------------------------------------------------


def test_eight_thread_conservation(prof):
    """8 concurrent recorders over 2 shards: no exception, per-shard
    cause seconds sum exactly to idle, and overlap-clipping keeps busy
    bounded by the wall (concurrent dispatches on one shard are never
    double-counted)."""
    t_start = time.perf_counter()

    def worker(idx):
        shard = idx % 2
        for _ in range(40):
            t0 = time.perf_counter()
            pp.note_pack_wall(t0, t0 + 0.0002)
            d0 = time.perf_counter()
            time.sleep(0.0005)
            pp.note_stage_wall("stage2", shard, d0, time.perf_counter())

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    doc = prof.summary()
    assert set(doc["shards"]) == {"0", "1"}
    for sh in doc["shards"].values():
        assert sh["dispatches"] == 160
        assert sum(sh["causes"].values()) == pytest.approx(
            sh["idle_s"], abs=2e-5
        )
        # clipped busy can never exceed the elapsed wall even with 4
        # threads dispatching on the shard concurrently
        assert sh["busy_s"] <= wall * 1.05
        assert sh["idle_s"] <= wall * 1.05


# ---------------------------------------------------------------------------
# Disabled-path cost
# ---------------------------------------------------------------------------


def test_disabled_hooks_under_one_microsecond():
    prev = pp.configure(enabled=False)
    try:
        n = 20_000
        hooks = (
            lambda: pp.note_stage_wall("stage2", 0, 1.0, 2.0),
            lambda: pp.note_pack_wall(1.0, 2.0),
            lambda: pp.flush_begin("t", "k", 1, 1, 0.0),
        )
        for hook in hooks:
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(n):
                    hook()
                best = min(best, (time.perf_counter() - t0) / n)
            assert best < 1e-6, (
                f"disabled profiler hook costs {best * 1e9:.0f} ns — too "
                f"expensive to leave always-on in the verification hot path"
            )
        # flush_scope(None) is the shared no-op
        assert pp.flush_begin("t", "k", 1, 1, 0.0) is None
        with pp.flush_scope(None):
            pass
        assert pp.flush_end(None) is None
    finally:
        pp.configure(**prev)


# ---------------------------------------------------------------------------
# dp shard lanes
# ---------------------------------------------------------------------------


def test_dp_two_shard_lanes(prof, mesh2):
    """A dp-split flush on a 2-shard placeholder mesh: both shards
    accumulate busy time and bubble state, the pipeline_flush row
    carries the shard axis, and the mesh health rows serve per-chip
    bubble ratios."""
    sched = VerificationScheduler(
        verify_fn=make_fake_device_verify(0.002, 0.003),
        deadline_ms=200.0, max_batch_sets=16,
        flush_planner=FlushPlanner(dp_min_sets=1),
    ).start()
    try:
        for _round in range(2):
            res = _feed(sched, [
                ("unaggregated", _mk_sets(1, b"m%d" % i))
                for i in range(16)
            ])
            assert all(res)
    finally:
        sched.stop()
    doc = prof.summary()
    assert {"0", "1"} <= set(doc["shards"]), doc["shards"].keys()
    for s in ("0", "1"):
        assert doc["shards"][s]["busy_s"] > 0
    rows = flight_recorder.events(kinds=["pipeline_flush"])
    assert any(r["fields"]["dp_shards"] == "[0, 1]" for r in rows), [
        r["fields"]["dp_shards"] for r in rows
    ]
    chips = mesh2.status()["chips"]
    assert all(c["bubble_ratio"] is not None for c in chips
               if doc["shards"].get(str(c["shard"]), {}).get("gaps"))
    # overlap projection is live and sane
    ov = doc["overlap_potential"]
    assert ov["projected_wall_s"] <= ov["measured_wall_s"] + 1e-9
    assert ov["projected_speedup"] >= 1.0


# ---------------------------------------------------------------------------
# Overlap projection semantics
# ---------------------------------------------------------------------------


def test_overlap_projection_hides_smaller_of_pack_and_device(prof):
    rec = pp.flush_begin("explicit", "unaggregated", 1, 4, 0.001)
    assert rec is not None
    time.sleep(0.05)
    rec.add("pack", 0.03)
    rec.add("device", 0.015, shard=0)
    row = pp.flush_end(rec, verdict=True, mode="single", n_sub_batches=1)
    assert row["critical_path"] == "pack"
    # projected = max(pack, device) + residual: the smaller leg hides
    assert row["projected_wall_s"] < row["wall_s"]
    assert row["overlap_speedup"] > 1.0
    assert row["saturation"] == pytest.approx(0.03 / 0.045, rel=1e-3)
    doc = prof.summary()
    assert doc["flushes"]["count"] == 1
    assert doc["overlap_potential"]["projected_speedup"] > 1.0


def test_overlap_projection_uses_busiest_lane_on_dp_flush(prof):
    """Concurrent dp workers' pack/device walls SUM past the flush wall
    — the projection must reason per dispatching lane, or a 2-shard
    flush's go/no-go dial would read a permanent 1.0 on exactly the
    multi-chip nodes it sizes."""
    rec = pp.flush_begin("full", "unaggregated", 2, 8, 0.0)
    barrier = threading.Barrier(2)

    def worker(shard):
        # both lanes ALIVE concurrently (a finished thread's ident can
        # be reused, which would merge the lanes — real dp workers all
        # run simultaneously)
        barrier.wait()
        rec.add("pack", 0.02, shard=shard)
        rec.add("device", 0.03, shard=shard)
        time.sleep(0.05)  # the lane's simulated wall

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    row = pp.flush_end(rec, verdict=True, mode="planned", n_sub_batches=2)
    # phase SUMS exceed max(pack, device) per lane: pack 0.04, device
    # 0.06 against a ~0.06 wall — the old sum-based projection pinned
    # at wall (speedup 1.0); per-lane it hides each lane's 0.02 pack
    assert row["dp_shards"] == [0, 1]
    assert row["projected_wall_s"] < row["wall_s"] - 0.01, row
    assert row["overlap_speedup"] > 1.2, row


def test_open_queue_empty_wait_covers_mid_wait_gap(prof):
    """A verify_now dispatch landing while the flush thread is STILL
    parked on an empty queue must attribute its gap to queue_empty —
    the completed interval only reaches the ring at wake, too late for
    a gap that closes mid-wait."""
    t0 = time.perf_counter()
    pp.note_stage_wall("stage2", 0, t0, t0 + 1e-4)  # establish last sync
    pp.note_idle_begin(time.perf_counter())          # wait opens, no end yet
    time.sleep(0.02)
    d0 = time.perf_counter()
    pp.note_stage_wall("stage2", 0, d0, d0 + 1e-4)   # verify_now mid-wait
    causes = pp.summary()["shards"]["0"]["causes"]
    assert causes.get("queue_empty", 0.0) > 0.015, causes
    pp.note_idle_end(d0, time.perf_counter())        # wake closes it


# ---------------------------------------------------------------------------
# Tools: jax-freedom + chrome lanes
# ---------------------------------------------------------------------------


def test_profiler_and_report_tool_are_jax_free():
    """The profiler and tools/pipeline_report.py must never import jax:
    a lockstep bubble model runs on boxes with no backend at all."""
    code = (
        "import sys\n"
        "from lighthouse_tpu.utils import pipeline_profiler as pp\n"
        "rec = pp.flush_begin('t', 'k', 1, 2, 0.0)\n"
        "pp.note_pack_wall(1.0, 1.1)\n"
        "pp.note_stage_wall('stage2', 0, 1.2, 1.3)\n"
        "pp.note_stage_wall('stage2', 0, 1.5, 1.6)\n"
        "pp.flush_end(rec, verdict=True)\n"
        "doc = pp.summary()\n"
        "assert doc['shards']['0']['idle_s'] > 0\n"
        "import tools.pipeline_report as pr\n"
        "from lighthouse_tpu.verification_service import traffic\n"
        "ev = traffic.GENERATORS['gossip_steady'](seed=3, duration_s=6)\n"
        "rep = pr.bubble_model(ev, shards=[0, 1])\n"
        "assert rep['per_shard'] and rep['n_flushes'] > 0\n"
        "assert rep['overlap_potential']['projected_speedup'] >= 1.0\n"
        "assert 'jax' not in sys.modules, 'pipeline tooling must stay jax-free'\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr


def test_pipeline_report_cli_model_mode(tmp_path):
    out = tmp_path / "pipe.json"
    import tools.pipeline_report as pipeline_report

    assert pipeline_report.main([
        "--generate", "gossip_steady", "--seed", "5", "--duration", "6",
        "--dp", "2", "--json", "--out", str(out),
    ]) == 0
    import json

    rep = json.loads(out.read_text())
    assert rep["mode"] == "bubble_model"
    assert set(rep["per_shard"]) <= {"0", "1"}
    assert "MODELED" in rep["assumption"]
    # live mode renders a health document's pipeline block
    health = tmp_path / "health.json"
    health.write_text(json.dumps({"data": {"pipeline": pp.summary()}}))
    assert pipeline_report.main(["--health-json", str(health)]) == 0
