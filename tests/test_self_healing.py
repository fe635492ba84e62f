"""Self-healing mesh + retry-with-backoff layers (ISSUE 13), at the
scheduling layer (placeholder devices, jax-free dispatch): shard
probation/recovery with backoff, the dispatch watchdog converting
hangs into failover, verify_now bypass failover, compile retry,
key-table re-sync scheduling, and the shutdown/recovery races the
issue names (Client.stop() during an active probe, concurrent loss +
re-admission under 8-thread traffic). The end-to-end chaos gate is
tests/test_zgate9_chaos.py."""

from __future__ import annotations

import threading
import time

import pytest

from lighthouse_tpu.crypto.device import mesh as mesh_mod
from lighthouse_tpu.utils import fault_injection as fi
from lighthouse_tpu.utils import flight_recorder
from lighthouse_tpu.verification_service import VerificationScheduler
from lighthouse_tpu.verification_service.batcher import WatchdogTimeout
from lighthouse_tpu.verification_service.planner import FlushPlanner


def _mk_sets(kind, n, pubkeys=1, messages=2):
    return [
        (None, [None] * pubkeys,
         kind.encode() + (i % messages).to_bytes(4, "big"))
        for i in range(n)
    ]


def _feed(sched, subs, timeout=60):
    futs = [None] * len(subs)

    def one(i):
        futs[i] = sched.submit(subs[i][1], subs[i][0])

    threads = [
        threading.Thread(target=one, args=(i,)) for i in range(len(subs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [f.result(timeout=timeout) for f in futs]


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def mesh2():
    m = mesh_mod.DeviceMesh(
        devices=[None, None], probe_base_s=0.05, probe_max_s=0.3
    )
    mesh_mod.set_mesh(m)
    yield m
    m.stop_recovery()
    mesh_mod.clear_mesh(m)


@pytest.fixture(autouse=True)
def _disarm_faults():
    fi.clear()
    yield
    fi.clear()


# ---------------------------------------------------------------------------
# Probation / recovery state machine
# ---------------------------------------------------------------------------


def test_lost_shard_enters_probation_and_recovers_with_backoff(mesh2):
    """Loss → probation (journaled, attempt 0) → failed probes back
    off with growing attempts → a passing probe re-admits the shard
    (shard_recovered journaled, counters move, health page tells the
    story)."""
    gate = {"ok": False}
    mesh2.start_recovery(probe_fn=lambda shard: gate["ok"])
    seq0 = len(flight_recorder.events(["shard_probation"]))
    assert mesh2.note_failure(1, RuntimeError("chip gone"), lost=True)
    assert mesh2.is_probing(1)
    assert mesh2.probing_shards() == [1]
    st = mesh2.status()
    assert st["probation_shards"] == [1]
    assert st["chips"][1]["probation"] is True
    # at least two failed probes: attempts grow, each journaled with
    # its next backoff
    _wait(
        lambda: mesh2.status()["chips"][1]["probe_attempts"] >= 2,
        msg="two failed probes",
    )
    if flight_recorder.enabled():
        probs = flight_recorder.events(["shard_probation"])[seq0:]
        attempts = [e["fields"]["attempt"] for e in probs]
        assert attempts[0] == 0  # probation entry
        assert sorted(attempts) == attempts, attempts
        assert all(e["fields"]["next_probe_s"] > 0 for e in probs)
    # clear the fault: the next probe re-admits
    gate["ok"] = True
    _wait(lambda: mesh2.healthy_shards() == [0, 1], msg="re-admission")
    st = mesh2.status()
    assert st["probation_shards"] == []
    assert st["recoveries_total"] == 1
    assert st["chips"][1]["recovered_total"] == 1
    if flight_recorder.enabled():
        recs = flight_recorder.events(["shard_recovered"])
        assert recs and recs[-1]["fields"]["shard"] == 1
        assert recs[-1]["fields"]["probes"] >= 3
        assert recs[-1]["fields"]["down_s"] > 0


def test_scheduler_replans_onto_recovered_shard(mesh2):
    """The planner needs no wiring for recovery: the flush after
    re-admission re-reads healthy_shards() and dp-splits across both
    chips again."""
    broken = {"on": False}

    def verify(sets):
        if broken["on"] and mesh_mod.current_shard() == 1:
            raise RuntimeError("injected chip loss")
        return True

    mesh2.start_recovery(probe_fn=lambda shard: not broken["on"])
    n = 16
    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=10_000.0, max_batch_sets=n,
        flush_planner=FlushPlanner(dp_min_sets=4),
    ).start()
    try:
        broken["on"] = True
        assert all(_feed(
            sched, [("unaggregated", _mk_sets("u", 1)) for _ in range(n)]
        ))
        assert mesh2.healthy_shards() == [0]
        broken["on"] = False
        _wait(lambda: mesh2.healthy_shards() == [0, 1], msg="recovery")
        assert all(_feed(
            sched, [("unaggregated", _mk_sets("u", 1)) for _ in range(n)]
        ))
        last = sched.status()["planner"]["last_plan"]
        assert last["dp_shards"] == [0, 1], last
    finally:
        sched.stop()


def test_operator_restore_during_probation_wins(mesh2):
    """restore_shard() mid-probation clears the probation state; a
    late probe result must not double-count a recovery."""
    mesh2.start_recovery(probe_fn=lambda shard: False)
    mesh2.note_failure(1, RuntimeError("gone"), lost=True)
    assert mesh2.is_probing(1)
    mesh2.restore_shard(1)
    assert not mesh2.is_probing(1)
    assert mesh2.healthy_shards() == [0, 1]
    time.sleep(0.2)  # any in-flight probe resolves against cleared state
    assert mesh2.status()["recoveries_total"] == 0


def test_stop_recovery_during_active_probe_returns_bounded(mesh2):
    """The shutdown race the issue names: stop during a probe that is
    actively sleeping must return within its bounded join, leave the
    mesh consistent, and a later start_recovery works."""
    probing = threading.Event()

    def slow_probe(shard):
        probing.set()
        time.sleep(1.5)
        return False

    mesh2.start_recovery(probe_fn=slow_probe, base_backoff_s=0.01)
    mesh2.note_failure(1, RuntimeError("gone"), lost=True)
    assert probing.wait(5.0), "probe never started"
    t0 = time.perf_counter()
    mesh2.stop_recovery(timeout=0.2)
    assert time.perf_counter() - t0 < 1.0
    assert not mesh2.recovery_running()
    assert mesh2.healthy_shards() == [0]  # still lost, state consistent
    # a fresh worker takes over cleanly (the abandoned probe's thread
    # is superseded by the identity check)
    mesh2.start_recovery(probe_fn=lambda shard: True, base_backoff_s=0.02)
    _wait(lambda: mesh2.healthy_shards() == [0, 1], msg="fresh worker")


def test_client_stop_during_active_probation_probe():
    """Client.stop() while a probation probe is mid-flight: stop must
    not wedge, must stop the recovery worker, and must cancel any
    pending key-table resync timer."""
    from lighthouse_tpu.client import ClientBuilder, ClientConfig
    from lighthouse_tpu.crypto import backend as bls_backend
    from lighthouse_tpu.types.chain_spec import minimal_spec

    client = ClientBuilder(
        ClientConfig(
            preset_base="minimal", http_enabled=False,
            bls_backend="fake", verification_scheduler=False,
        ),
        minimal_spec(),
    ).with_interop_genesis(8).build()
    probing = threading.Event()

    def slow_probe(shard):
        probing.set()
        time.sleep(1.0)
        return False

    m = mesh_mod.DeviceMesh(
        devices=[None, None], probe_base_s=0.01, probe_max_s=0.05
    )
    mesh_mod.set_mesh(m)
    client.chain.device_mesh = m
    try:
        m.start_recovery(probe_fn=slow_probe)
        m.note_failure(1, RuntimeError("gone"), lost=True)
        assert probing.wait(5.0), "probe never started"
        t0 = time.perf_counter()
        client.stop()
        stop_wall = time.perf_counter() - t0
        assert not m.recovery_running()
        assert stop_wall < 15.0, stop_wall
        assert mesh_mod.get_active_mesh() is None
    finally:
        m.stop_recovery()
        mesh_mod.clear_mesh(m)
        # the builder set the GLOBAL backend to "fake"; later test
        # files verify real signatures through it — restore
        bls_backend.set_backend("cpu")


def _tpu_builder():
    from lighthouse_tpu.client import ClientBuilder, ClientConfig
    from lighthouse_tpu.types.chain_spec import minimal_spec

    return ClientBuilder(
        ClientConfig(
            preset_base="minimal", http_enabled=False, bls_backend="tpu",
            verification_scheduler=False, compile_service=False,
        ),
        minimal_spec(),
    ).with_interop_genesis(8)


def test_tpu_node_refuses_the_cpu_unless_asked_by_name(monkeypatch):
    """A tpu node on a machine whose JAX found no TPU fails to build;
    only an explicit JAX_PLATFORMS=cpu (the test suite's own) lets the
    device programs run on the CPU."""
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        _tpu_builder().build()
    assert mesh_mod.get_active_mesh() is None


def test_tpu_node_without_its_key_table_fails_to_start(monkeypatch):
    """A key-table start-up failure stops the build (it used to log a
    warning and serve without the table) and leaves no mesh behind."""
    from lighthouse_tpu.crypto import backend as bls_backend
    from lighthouse_tpu.crypto.device import key_table

    def no_memory(self, reason="delta"):
        raise RuntimeError("no device memory for the key table")

    monkeypatch.setattr(key_table.DeviceKeyTable, "sync", no_memory)
    try:
        with pytest.raises(RuntimeError, match="no device memory"):
            _tpu_builder().build()
        assert mesh_mod.get_active_mesh() is None
        assert key_table.get_active_table() is None
    finally:
        bls_backend.set_backend("cpu")


def test_concurrent_loss_and_recovery_under_8_thread_traffic(mesh2):
    """The concurrency race the issue names: 8 submitter threads drive
    continuous traffic while shard 1 dies and recovers mid-stream —
    every verdict stays True, nothing deadlocks or strands a future,
    and the mesh ends recovered."""
    broken = {"on": False}

    def verify(sets):
        if broken["on"] and mesh_mod.current_shard() == 1:
            raise RuntimeError("injected chip loss")
        time.sleep(0.001)
        return True

    mesh2.start_recovery(
        probe_fn=lambda shard: not broken["on"], base_backoff_s=0.03
    )
    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=50.0, max_batch_sets=32,
        flush_planner=FlushPlanner(dp_min_sets=4),
    ).start()
    results = []
    rlock = threading.Lock()
    stop_feeding = threading.Event()

    def feeder(i):
        while not stop_feeding.is_set():
            f = sched.submit(_mk_sets("u", 1), "unaggregated")
            try:
                ok = f.result(timeout=30)
            except Exception as e:  # noqa: BLE001 — collected for the assert
                ok = e
            with rlock:
                results.append(ok)

    threads = [
        threading.Thread(target=feeder, args=(i,)) for i in range(8)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)            # healthy 2-shard serving
        broken["on"] = True        # kill shard 1 mid-traffic
        _wait(lambda: mesh2.healthy_shards() == [0], msg="loss")
        time.sleep(0.3)            # degraded serving + failing probes
        broken["on"] = False       # chip heals
        _wait(lambda: mesh2.healthy_shards() == [0, 1], msg="recovery")
        time.sleep(0.3)            # recovered 2-shard serving
    finally:
        stop_feeding.set()
        for t in threads:
            t.join(timeout=30)
        sched.stop()
    assert results, "feeders made no progress"
    bad = [r for r in results if r is not True]
    assert not bad, f"{len(bad)} non-True results, e.g. {bad[:3]}"
    assert mesh2.status()["recoveries_total"] >= 1


# ---------------------------------------------------------------------------
# Dispatch watchdog
# ---------------------------------------------------------------------------


def test_watchdog_reaps_hang_into_failover(mesh2):
    """A hung shard-1 dispatch is abandoned at the deadline and fails
    over to shard 0: verdicts True, flush wall bounded, shard 1 lost
    (probation), watchdog_reaped journaled + counted."""
    def verify(sets):
        if mesh_mod.current_shard() == 1:
            time.sleep(3.0)  # the hang
        return True

    n = 16
    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=60_000.0, max_batch_sets=n,
        watchdog_s=0.3,
        flush_planner=FlushPlanner(dp_min_sets=4),
    ).start()
    try:
        t0 = time.perf_counter()
        assert all(_feed(
            sched, [("unaggregated", _mk_sets("u", 1)) for _ in range(n)]
        ))
        wall = time.perf_counter() - t0
        assert wall < 2.0, f"flush thread wedged: {wall:.2f}s"
        assert mesh2.healthy_shards() == [0]
        assert mesh2.is_probing(1)
        assert sched.status()["watchdog_reaped_total"] >= 1
        if flight_recorder.enabled():
            reaps = flight_recorder.events(["watchdog_reaped"])
            assert reaps and reaps[-1]["fields"]["shard"] == 1
            assert reaps[-1]["fields"]["deadline_s"] == 0.3
    finally:
        sched.stop()


def test_watchdog_work_hang_propagates_and_keeps_shard(mesh2):
    """When the failover dispatch hangs the same way, the WORK is the
    problem: WatchdogTimeout reaches the leaf submissions and the
    shard keeps its health (the pre-mesh exception contract)."""
    def verify(sets):
        time.sleep(1.0)  # hangs on EVERY shard
        return True

    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=60_000.0, max_batch_sets=4,
        watchdog_s=0.2,
        flush_planner=FlushPlanner(dp_min_sets=2),
    ).start()
    try:
        futs = [
            sched.submit(_mk_sets("u", 1), "unaggregated")
            for _ in range(4)
        ]
        sched.flush()
        for f in futs:
            with pytest.raises(WatchdogTimeout):
                f.result(timeout=30)
        assert mesh2.healthy_shards() == [0, 1], (
            "a work-induced hang must not cost a chip"
        )
    finally:
        sched.stop()


def test_watchdog_preserves_exception_types_and_attribution(mesh2):
    """The watchdog thread relays the ORIGINAL exception object (not a
    wrapper) and runs the verify under the caller's dispatch scope."""
    seen_shards = []

    def verify(sets):
        seen_shards.append(mesh_mod.current_shard())
        raise ValueError("deterministic backend bug")

    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=60_000.0, max_batch_sets=2,
        watchdog_s=5.0,
        flush_planner=FlushPlanner(dp_min_sets=1),
    ).start()
    try:
        f = sched.submit(_mk_sets("u", 2), "unaggregated")
        sched.flush()
        with pytest.raises(ValueError):
            f.result(timeout=30)
        assert all(s is not None for s in seen_shards), seen_shards
        assert mesh2.healthy_shards() == [0, 1]
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# verify_now bypass failover (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def test_verify_now_fails_over_once_and_drops_chip(mesh2):
    calls = []

    def verify(sets):
        s = mesh_mod.current_shard()
        calls.append(s)
        if s == 0:
            raise RuntimeError("chip 0 gone")
        return True

    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=10_000.0
    ).start()
    try:
        assert sched.verify_now(_mk_sets("b", 2), "block") is True
        assert calls == [0, 1], calls
        assert mesh2.healthy_shards() == [1]
        assert mesh2.is_probing(0)
        # the next bypass goes straight to the survivor
        assert sched.verify_now(_mk_sets("b", 2), "block") is True
        assert calls[-1] == 1
    finally:
        sched.stop()


def test_verify_now_work_failure_propagates_and_keeps_shards(mesh2):
    def verify(sets):
        raise ValueError("work bug")

    sched = VerificationScheduler(
        verify_fn=verify, deadline_ms=10_000.0
    ).start()
    try:
        with pytest.raises(ValueError):
            sched.verify_now(_mk_sets("b", 2), "block")
        assert mesh2.healthy_shards() == [0, 1]
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# Compile retry (ISSUE 13)
# ---------------------------------------------------------------------------


def test_compile_retry_recovers_transient_failure():
    from lighthouse_tpu.compile_service import CompileService

    fails = {"n": 0}

    def compile_rung(b, k, m):
        if fails["n"] < 2:
            fails["n"] += 1
            raise RuntimeError("transient compile failure")
        return {
            s: {"seconds": 0.01, "fresh": True}
            for s in ("stage1", "stage2", "stage3")
        }

    svc = CompileService(rungs=((2, 1, 1),), compile_rung_fn=compile_rung)
    svc.retry_base_s = 0.03
    svc.retry_max_s = 0.06
    svc.start()
    try:
        _wait(lambda: bool(svc.registry.warm_rungs()), msg="rung warm")
        st = svc.status()
        assert st["failed_total"] == 2, st
        assert st["retry"]["retries_total"] == 2, st
        assert st["retry"]["pending"] == [], st
        if flight_recorder.enabled():
            retries = flight_recorder.events(["compile_retry"])
            assert [e["fields"]["attempt"] for e in retries][-2:] == [1, 2]
            assert all(
                e["fields"]["delay_s"] > 0 for e in retries[-2:]
            )
    finally:
        svc.stop()


def test_compile_retry_respects_attempt_cap():
    from lighthouse_tpu.compile_service import CompileService

    calls = []

    def always_fail(b, k, m):
        calls.append((b, k, m))
        raise RuntimeError("deterministic compile failure")

    svc = CompileService(rungs=((4, 1, 1),), compile_rung_fn=always_fail)
    svc.retry_base_s = 0.02
    svc.retry_max_s = 0.04
    svc.start()
    try:
        _wait(
            lambda: svc.status()["failed_total"]
            == svc.retry_max_attempts,
            msg="attempt cap reached",
        )
        time.sleep(0.2)  # no further retries fire past the cap
        st = svc.status()
        assert st["failed_total"] == svc.retry_max_attempts, st
        assert st["retry"]["pending"] == [], st
        assert len(calls) == svc.retry_max_attempts
        assert svc.registry.warm_rungs() == []
    finally:
        svc.stop()


def test_compile_retry_state_clears_on_invalidate():
    from lighthouse_tpu.compile_service import CompileService

    def always_fail(b, k, m):
        raise RuntimeError("nope")

    svc = CompileService(rungs=((8, 1, 1),), compile_rung_fn=always_fail)
    svc.retry_base_s = 5.0  # park a pending retry
    svc.start()
    try:
        _wait(
            lambda: svc.status()["retry"]["pending"] != [],
            msg="pending retry",
        )
        svc.invalidate()
        st = svc.status()
        assert st["retry"]["pending"] == [], st
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Key-table re-sync (ISSUE 13)
# ---------------------------------------------------------------------------


def _tiny_table(n=3, **kw):
    import types

    from lighthouse_tpu.crypto import bls as host_bls
    from lighthouse_tpu.crypto.device import key_table as kt

    pks = [
        types.SimpleNamespace(
            point=host_bls.SecretKey(51_000 + i).public_key().point
        )
        for i in range(n)
    ]
    cache = types.SimpleNamespace(pubkeys=list(pks))
    return kt.DeviceKeyTable(cache, max_aggregates=4, **kw), cache


def test_failed_delta_schedules_resync_that_catches_up():
    tbl, _cache = _tiny_table()
    tbl._resync_base_s = 0.03
    fi.arm("key_table_sync", nth=1)  # first sync fails, retry passes
    assert tbl.sync_or_schedule(reason="delta") is None
    st = tbl.status()
    assert st["resyncs"]["scheduled"] == 1, st
    assert st["resync_pending"] is True, st
    _wait(lambda: len(tbl) == 3, msg="resync catch-up")
    st = tbl.status()
    assert st["resyncs"]["ok"] == 1, st
    assert st["resync_failures"] == 0, st
    # the retry's sync is journaled under reason=recovery
    if flight_recorder.enabled():
        syncs = flight_recorder.events(["key_table_sync"])
        assert syncs and syncs[-1]["fields"]["reason"] == "recovery"
    tbl.close()


def test_resync_keeps_retrying_with_backoff_until_success():
    tbl, _cache = _tiny_table()
    tbl._resync_base_s = 0.02
    tbl._resync_max_s = 0.05
    fi.arm("key_table_sync", every=1, count=3)  # first 3 syncs fail
    assert tbl.sync_or_schedule(reason="delta") is None
    _wait(lambda: len(tbl) == 3, msg="eventual catch-up")
    st = tbl.status()
    assert st["resyncs"]["ok"] == 1, st
    assert st["resyncs"]["error"] == 2, st       # retries 1-2 failed
    assert st["resyncs"]["scheduled"] == 3, st   # 3 timers armed
    tbl.close()


def test_close_cancels_pending_resync():
    tbl, _cache = _tiny_table()
    tbl._resync_base_s = 5.0  # park the retry far out
    fi.arm("key_table_sync", nth=1)
    assert tbl.sync_or_schedule(reason="delta") is None
    assert tbl.status()["resync_pending"] is True
    tbl.close()
    assert tbl.status()["resync_pending"] is False
    time.sleep(0.1)
    assert len(tbl) == 0  # nothing synced after close
    # and a closed table refuses to schedule new retries
    fi.clear()
    fi.arm("key_table_sync", nth=1)
    assert tbl.sync_or_schedule(reason="delta") is None
    assert tbl.status()["resync_pending"] is False
