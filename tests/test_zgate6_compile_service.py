"""Compile-service x scheduler x staged device pipeline gate (ISSUE 5
acceptance): with the service enabled, a scheduler flush onto a COLD
bucket rung returns the correct per-submission verdicts WITHOUT blocking
on the multi-minute XLA staged compile — it is shed to the counted
synchronous CPU-native fallback (``cold_route`` journaled) while the
background worker compiles the rung — and after ``compile_ready`` the
same traffic runs ON DEVICE with zero fresh staged compiles. A second
test asserts the persistent-cache warm restart in subprocesses, loudly
skipping where the JAX build lacks the cache knob or the known XLA:CPU
AOT cache-load crash of this host family fires (tests/conftest.py).

Named ``test_zgate6_*`` so it tail-sorts after zgate5 inside the tier-1
wall-clock window (tests/conftest.py discipline): the background rung
compile is the same ~minutes XLA:CPU staged compile zgate5 pays, and it
must never displace functional dots. Wall budget: the cold-phase flush
is asserted to resolve in well under the compile time, and the warm wait
is bounded."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from lighthouse_tpu.compile_service import (
    CompileService,
    clear_service,
    set_service,
)
from lighthouse_tpu.crypto import backend, bls
from lighthouse_tpu.crypto import device
from lighthouse_tpu.crypto.backend import set_backend
from lighthouse_tpu.utils import flight_recorder as fr
from lighthouse_tpu.utils import metrics
from lighthouse_tpu.verification_service import VerificationScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recompiles_total() -> float:
    m = metrics.get("bls_device_recompiles_total")
    if m is None:
        return 0.0
    return sum(c.value for c in m.children().values())


def _submit_round(sched, subs_sets, kinds):
    futs = [None] * len(subs_sets)
    barrier = threading.Barrier(len(subs_sets))

    def feeder(i):
        barrier.wait()
        futs[i] = sched.submit(subs_sets[i], kinds[i % len(kinds)])

    threads = [
        threading.Thread(target=feeder, args=(i,))
        for i in range(len(subs_sets))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [f.result(timeout=1800) for f in futs]


def test_zgate6_cold_rung_never_stalls_flush_then_runs_warm():
    # single-pubkey sets over ONE shared message: every fused round maps
    # to device geometry (K=1, M=1), so the B bucket alone governs the
    # rung — and the poison (wrong signer) isolates via fallback bisection
    msg = b"\x66" * 32
    sets = []
    for i in range(3):
        sk = bls.SecretKey(700 + i)
        pk = bls.PublicKey.deserialize(sk.public_key().serialize())
        sig = bls.Signature.deserialize(sk.sign(msg).serialize())
        sets.append(bls.SignatureSet.single_pubkey(sig, pk, msg))
    sk_bad, sk_other = bls.SecretKey(800), bls.SecretKey(801)
    poison = bls.SignatureSet.single_pubkey(
        bls.Signature.deserialize(sk_other.sign(msg).serialize()),
        bls.PublicKey.deserialize(sk_bad.public_key().serialize()),
        msg,
    )

    # guarantee COLD: zgate5 compiles these same (B=4, K=1, M=1) staged
    # programs when a full run reaches it first in this process
    device.reset_compiled_state()

    set_backend("tpu")
    svc = CompileService(rungs=((4, 1, 1),)).start()
    set_service(svc)
    sched = VerificationScheduler(
        deadline_ms=300.0, max_batch_sets=256, max_queue_sets=1024,
        compile_service=svc,
    ).start()
    kinds = ("unaggregated", "aggregate", "sync_message")
    try:
        # --- phase 1: flush while the rung compiles in the background ---
        shed_counter = metrics.get(
            "compile_service_cold_routes_total"
        ).with_labels("shed")
        shed_before = shed_counter.value
        t0 = time.perf_counter()
        r1 = _submit_round(
            sched, [[sets[0]], [sets[1]], [sets[2]], [poison]], kinds
        )
        cold_latency = time.perf_counter() - t0
        assert r1 == [True, True, True, False], r1
        # the verdicts arrived from the FALLBACK, in a fraction of the
        # staged compile's minutes — the flush never blocked on XLA
        assert cold_latency < 150.0, (
            f"cold-bucket flush took {cold_latency:.1f}s — it must be "
            f"served without waiting on the staged compile"
        )
        assert shed_counter.value >= shed_before + 1
        routed = fr.events(kinds=("cold_route",))
        assert any(
            e["fields"]["action"] == "shed"
            and e["fields"]["caller"].startswith("flush:")
            and e["fields"]["exact_b"] == 4
            for e in routed
        ), routed[-5:]
        assert svc.registry.warm_rungs() == [], (
            "phase 1 must have run strictly before the rung warmed — "
            "rerun: the box compiled faster than the flush resolved"
        )

        # --- phase 2: wait for the background compile_ready ------------
        deadline = time.monotonic() + 1200
        while time.monotonic() < deadline and not svc.registry.warm_rungs():
            time.sleep(1.0)
        assert svc.registry.warm_rungs(), "background compile never finished"
        ready = fr.events(kinds=("compile_ready",))
        assert any(
            e["fields"]["b"] == 4 and e["fields"]["source"] == "aot"
            for e in ready
        )

        # --- phase 3: same traffic, now ON DEVICE, zero fresh compiles -
        compiles_after_warm = _recompiles_total()
        fallback_span_before = len(
            [e for e in fr.events(kinds=("cold_route",))]
        )
        r2 = _submit_round(sched, [[sets[0]], [sets[1]], [sets[2]]], kinds)
        assert r2 == [True, True, True]
        assert _recompiles_total() == compiles_after_warm, (
            "warm traffic on the AOT-compiled rung must not compile any "
            "staged program"
        )
        assert len(fr.events(kinds=("cold_route",))) == fallback_span_before, (
            "the warm flush must not route cold"
        )
        st = svc.status()
        assert st["cold_routes"]["shed"] >= 1
    finally:
        sched.stop()
        svc.stop()
        clear_service(svc)
        set_backend("cpu")
    assert backend.active_name() == "cpu"


_CHILD = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from lighthouse_tpu.compile_service import cache as cs_cache
status = cs_cache.enable_persistent_cache()
assert status["dir"] == {cache!r}, status
if not status["enabled"]:
    print(json.dumps({{"unsupported": status["reason"]}}))
    raise SystemExit(0)
import jax.numpy as jnp
from jax import lax
from lighthouse_tpu.crypto.device import fp

def chain(a):
    def body(acc, _):
        return fp.mul(acc, a), None
    out, _ = lax.scan(body, a, None, length=8)
    return out

x = jnp.ones((64, fp.NL), jnp.int32)
t0 = time.perf_counter()
jax.block_until_ready(jax.jit(chain)(x))
compile_s = time.perf_counter() - t0
man = cs_cache.Manifest({cache!r})
key = cs_cache.manifest_key(
    cs_cache.environment_key(fp.get_impl()), "probe", 64, 1, 1
)
prebaked = man.has(key)
man.add(key, source="zgate6")
n_cache_files = len(
    [n for n in os.listdir({cache!r})
     if n != "manifest.json" and not n.endswith(".tmp")]
)
print(json.dumps({{"compile_s": round(compile_s, 3),
                   "prebaked": prebaked,
                   "n_cache_files": n_cache_files}}))
"""


def test_zgate6_persistent_cache_warm_restart_subprocess(tmp_path):
    """Warm restart across PROCESSES: the first child compiles with the
    persistent cache + manifest enabled; the second child (a "restarted
    node") must find the manifest entry prebaked — the node-level
    warm-start signal — and load the executable from disk instead of
    compiling fresh. Loud skip where this host family's known XLA:CPU
    cache-load SIGSEGV fires."""
    cache_dir = str(tmp_path / "cache")

    def run_child():
        code = _CHILD.format(repo=REPO, cache=cache_dir)
        return subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=240,
        )

    r1 = run_child()
    if r1.returncode < 0:
        pytest.skip(
            f"persistent-cache child died with signal {-r1.returncode} "
            f"(known XLA:CPU AOT cache crash on this host family)"
        )
    assert r1.returncode == 0, r1.stderr[-800:]
    doc1 = json.loads(r1.stdout.strip().splitlines()[-1])
    if "unsupported" in doc1:
        pytest.skip(f"jax persistent compile cache unsupported: {doc1['unsupported']}")
    assert doc1["prebaked"] is False  # truly cold first boot
    if doc1["n_cache_files"] == 0:
        pytest.skip(
            "persistent cache wrote no entries on this jax build — "
            "warm-restart unverifiable here"
        )

    r2 = run_child()
    if r2.returncode < 0:
        pytest.skip(
            f"persistent-cache RELOAD died with signal {-r2.returncode} "
            f"(known XLA:CPU AOT cache-load crash on this host family, "
            f"see tests/conftest.py)"
        )
    assert r2.returncode == 0, r2.stderr[-800:]
    doc2 = json.loads(r2.stdout.strip().splitlines()[-1])
    # the restarted process warm-starts: manifest hit (the node-level
    # "zero fresh staged compiles" signal) over the same executables
    assert doc2["prebaked"] is True
    assert doc2["n_cache_files"] >= doc1["n_cache_files"]
