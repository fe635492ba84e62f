"""North-star benchmark: BLS signature-set verifications/sec on one chip.

Workload shape follows BASELINE.md config #3 (gossip aggregate batch):
each aggregate attestation costs three signature sets (selection proof,
aggregator signature, aggregate attestation signature over the committee —
reference ``beacon_node/beacon_chain/src/attestation_verification/batch.rs:77-107``).

END-TO-END measurement (VERDICT r1 weakness #3): every rep re-packs the
raw (compressed-signature, pubkeys, message) sets — host byte wrangling +
randomness + hash_to_field only — and runs the STAGED device pipeline
(``verify_batch_raw_staged``: decompression, hash-to-curve, aggregation,
subgroup checks and the multi-pairing all on device, three jitted stages
that cache independently).

Hardening (VERDICT r4 item #8):
* median-of-5 timing on BOTH legs (device and native-C baseline) with
  spread recorded, instead of mean-of-2;
* committee-size buckets K in {16, 128, 512} measured separately
  (mainnet committees are ~128-512; K=16 alone understates padding) with
  the padding-waste fraction per bucket;
* a wall-clock budget: buckets are skipped (and marked) rather than
  blowing the driver's window — silent truncation would read as
  "covered everything".

The bench runs on a TPU only: with no chip it exits non-zero and prints
no result. One process holds the chip; every leg that starts a child
process pins that child to the CPU (stub or cpu-native backends, virtual
meshes) and says so in its record (``child_platform``). The persistent
compilation cache (``compile_service/cache.py``) keeps recurring runs
cheap.

Per-impl legs (VERDICT r5 rec #2): the headline bucket is re-measured
in-process under ``FP_IMPL=matmul_int8`` (the int8 limb-split MXU
decomposition of ``fp.mul``) after the toeplitz_int32 headline, and both
land in ``fp_impl_legs`` so rounds can track the contraction engines
separately.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
``vs_baseline`` is the ratio of the measured device throughput to the
NATIVE C CPU baseline (`_native/bls12381.c`, backend "cpu-native" — the
blst-class baseline BASELINE.md demands) measured in-process on the SAME
workload; ``vs_target`` tracks the 50k aggregate-verifications/sec goal
from BASELINE.json (one aggregate = 3 sets). The line also names the
device it ran on (``device``: platform, kind, count) and the padded
bucket shapes.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

# Headline geometry (TPU): one gossip aggregate batch, reference mix.
N_AGG = 64
COMMITTEE = 16
N_MSGS = 8
B_PAD = 256
K_PAD = 16
M_PAD = 8
# Extra committee-size buckets (mainnet: ~128-512 validators/committee).
# Per bucket: 8 aggregates x 3 sets, padded to B=32 lanes.
EXTRA_BUCKETS = [
    {"K": 128, "n_agg": 8, "B": 32, "M": 4},
    {"K": 512, "n_agg": 8, "B": 32, "M": 4},
]
TARGET_AGG_PER_SEC = 50_000.0
BENCH_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "2400"))
REPS = 5

_T0 = time.perf_counter()


def _budget_left() -> float:
    return BENCH_BUDGET_S - (time.perf_counter() - _T0)


def _cpu_child_env() -> dict:
    """Environment for a child process: pinned to the CPU, because this
    process holds the chip and a child that touched it would fail or
    hang. Legs that use it report ``child_platform: cpu``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def build_sets(n_agg: int, committee: int, n_msgs: int):
    """Raw signature sets, reference mix: per aggregate, two single-pubkey
    sets + one committee set. Aggregate signatures are produced with the
    summed secret key (same group element as aggregating per-signer
    signatures) to keep host-oracle setup time bounded."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.params import R

    sks = [bls.SecretKey(1_000 + i) for i in range(committee)]
    pks = [sk.public_key().point for sk in sks]
    sk_agg = bls.SecretKey(sum(1_000 + i for i in range(committee)) % R)
    msgs = [bytes([m + 1]) * 32 for m in range(n_msgs)]
    # signatures stay COMPRESSED (lazy Signature): the device decompresses
    single0 = {m: bls.Signature.deserialize(sks[0].sign(m).serialize()) for m in msgs}
    single1 = {m: bls.Signature.deserialize(sks[1].sign(m).serialize()) for m in msgs}
    agg = {m: bls.Signature.deserialize(sk_agg.sign(m).serialize()) for m in msgs}

    sets = []
    for i in range(n_agg):
        m = msgs[i % n_msgs]
        sets.append((single0[m], [pks[0]], m))
        sets.append((single1[m], [pks[1]], m))
        sets.append((agg[m], pks, m))
    return sets


def _median_spread(samples: list[float]) -> tuple[float, float]:
    med = statistics.median(samples)
    spread = (max(samples) - min(samples)) / med if med else 0.0
    return med, spread


def measure_scheduler_leg(sets, B, K, M, n_callers: int = 4, reps: int = 3):
    """Fused-scheduler vs direct per-caller throughput at the headline
    geometry (ISSUE 4). Both legs run the SAME compiled staged program at
    the SAME padded shape — no new XLA compiles: the `direct` leg pays
    ``n_callers`` dispatches of a 1/n-occupied bucket (the fragmented
    traffic shape the scheduler exists to fix), the `fused` leg pays one
    full-occupancy dispatch assembled by concurrent ``submit()`` calls
    from ``n_callers`` feeder threads."""
    import threading

    import jax

    from lighthouse_tpu.crypto.device.bls import (
        pack_signature_sets_raw,
        verify_batch_raw_staged,
    )
    from lighthouse_tpu.verification_service import VerificationScheduler

    chunk = (len(sets) + n_callers - 1) // n_callers
    chunks = [sets[i: i + chunk] for i in range(0, len(sets), chunk)]

    def device_verify(s):
        args = pack_signature_sets_raw(s, pad_b=B, pad_k=K, pad_m=M)
        return bool(jax.block_until_ready(verify_batch_raw_staged(*args)))

    assert device_verify(sets) is True  # warm (shape compiled by headline)

    direct = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for c in chunks:
            assert device_verify(c)
        direct.append(time.perf_counter() - t0)

    kinds = ("unaggregated", "aggregate", "sync_message", "sync_contribution")
    sched = VerificationScheduler(
        verify_fn=device_verify,
        deadline_ms=2000.0,
        max_batch_sets=len(sets),  # bucket-full fires on the last feeder
        max_queue_sets=4 * len(sets),
    ).start()
    fused = []
    try:
        for _ in range(reps):
            futs = [None] * len(chunks)

            def feed(i):
                futs[i] = sched.submit(chunks[i], kinds[i % len(kinds)])

            threads = [
                threading.Thread(target=feed, args=(i,))
                for i in range(len(chunks))
            ]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            assert all(f.result(timeout=600) for f in futs)
            fused.append(time.perf_counter() - t0)
    finally:
        sched.stop()

    d_med, d_spread = _median_spread(direct)
    f_med, f_spread = _median_spread(fused)
    n = len(sets)
    return {
        "n_callers": len(chunks),
        "sets_per_caller": chunk,
        "B": B, "K": K, "M": M, "reps": reps,
        "direct_sets_per_sec": round(n / d_med, 2),
        "direct_rep_spread": round(d_spread, 3),
        "fused_sets_per_sec": round(n / f_med, 2),
        "fused_rep_spread": round(f_spread, 3),
        "fused_vs_direct": round(d_med / f_med, 4),
    }


def measure_planner_leg(sets, B, K, M, reps: int = 3):
    """Planned multi-rung flush vs legacy single-rung flush at the
    headline shape (ISSUE 6), same warm cache. The LEGACY leg is the
    pre-planner behavior: the whole fused mix padded onto the one
    headline rung (B, K, M) — already compiled by the headline bucket,
    so it pays zero new XLA work. The PLANNED leg routes the same
    traffic through the scheduler's shape-aware planner: kind-
    homogeneous sub-batches (single-pubkey gossip sets on a K=1 rung,
    committee sets on a small-B rung) whose compiles are paid once in
    the warm-up flush; the measured reps then run at steady state —
    recompile delta recorded to prove it stays 0. Per-leg sets/s and
    padding_waste (the shared B*K*M lane formula) land in the JSON."""
    import threading

    import jax

    from lighthouse_tpu.crypto.device import bls as device_bls
    from lighthouse_tpu.utils import metrics
    from lighthouse_tpu.verification_service import (
        VerificationScheduler,
        live_lanes,
        padded_lanes,
        padding_waste_ratio,
    )

    singles = [s for s in sets if len(s[1]) == 1]
    committees = [s for s in sets if len(s[1]) > 1]
    if not singles or not committees:
        return {"skipped": "workload has no kind mix to split"}
    n = len(sets)
    live = live_lanes(
        sum(len(pks) for _, pks, _ in sets),
        len({bytes(m) for _, _, m in sets}),
    )
    # kind-faithful submissions: the two single-pubkey sets per
    # aggregate are unaggregated-style, the committee set aggregate-style
    subs = [("unaggregated", singles), ("aggregate", committees)]

    def legacy_verify(s):
        # pad-everything-to-the-headline-rung: the pre-planner flush
        args = device_bls.pack_signature_sets_raw(
            s, pad_b=B, pad_k=K, pad_m=M
        )
        return bool(
            jax.block_until_ready(device_bls.verify_batch_raw_staged(*args))
        )

    def _recompiles() -> float:
        m = metrics.get("bls_device_recompiles_total")
        return sum(c.value for c in m.children().values()) if m else 0.0

    def _gauge(name) -> float:
        m = metrics.get(name)
        return float(m.value) if m is not None else float("nan")

    def run_flush(sched) -> float:
        futs = [None] * len(subs)

        def feed(i):
            futs[i] = sched.submit(subs[i][1], subs[i][0])

        threads = [
            threading.Thread(target=feed, args=(i,))
            for i in range(len(subs))
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert all(f.result(timeout=1800) for f in futs)
        return time.perf_counter() - t0

    def measure(verify_fn, plan_on):
        sched = VerificationScheduler(
            verify_fn=verify_fn, deadline_ms=10_000.0,
            max_batch_sets=n,  # bucket-full fires on the last feeder
            max_queue_sets=4 * n, plan_flushes=plan_on,
        ).start()
        try:
            run_flush(sched)  # warm-up: the planned leg's compiles land here
            rec_before = _recompiles()
            samples = [run_flush(sched) for _ in range(reps)]
            steady_recompiles = _recompiles() - rec_before
            st = sched.status()
        finally:
            sched.stop()
        med, spread = _median_spread(samples)
        return {
            "sets_per_sec": round(n / med, 2),
            "rep_spread": round(spread, 3),
            "steady_recompiles": steady_recompiles,
            "plan": st["planner"]["last_plan"],
            "scheduler_waste_gauge": round(
                _gauge("verification_scheduler_padding_waste_ratio"), 4
            ),
            "device_waste_gauge": round(
                _gauge("bls_device_padding_waste_ratio"), 4
            ),
        }

    legacy = measure(legacy_verify, plan_on=False)
    # the legacy verify bypasses TpuBackend and pads to the HEADLINE
    # rung (B, K, M), not the scheduler plan's exact rung, so neither
    # gauge describes what it actually dispatched — report its waste
    # from the same shared formula at the rung it really padded to and
    # drop the gauge readings rather than ship contradictory numbers
    legacy["padding_waste"] = round(
        padding_waste_ratio(live, padded_lanes(B, K, M)), 4
    )
    legacy["rung"] = [B, K, M]
    del legacy["device_waste_gauge"]  # not touched by the direct packer
    del legacy["scheduler_waste_gauge"]  # reflects the plan, not the pad
    del legacy["plan"]  # ditto: the plan's exact rung was never dispatched

    planned = measure(
        device_bls.TpuBackend().verify_signature_sets, plan_on=True
    )
    planned["padding_waste"] = planned["plan"]["padding_waste"]

    return {
        "n_sets": n,
        "reps": reps,
        "legacy": legacy,
        "planned": planned,
        "planned_vs_legacy": round(
            planned["sets_per_sec"] / legacy["sets_per_sec"], 4
        ) if legacy["sets_per_sec"] else None,
    }


def measure_pipeline_leg(sets, B, K, M, reps: int = 3, n_callers: int = 4):
    """Pipeline-occupancy profile at the headline rung (ISSUE 12):
    device bubble ratio with cause attribution, flush-thread saturation
    and the overlap-potential projection — the sizing input for ROADMAP
    item 5's double-buffered pack pipeline, measured through the REAL
    scheduler at the already-warm headline shape (plan_flushes off +
    headline pad = zero new XLA compiles; the steady-recompile delta
    pins it). ``bubble_ratio`` feeds the bench_diff gate."""
    import threading

    import jax

    from lighthouse_tpu.crypto.device.bls import (
        pack_signature_sets_raw,
        verify_batch_raw_staged,
    )
    from lighthouse_tpu.utils import metrics, pipeline_profiler
    from lighthouse_tpu.verification_service import VerificationScheduler

    if not pipeline_profiler.enabled():
        return {"skipped": "pipeline profiler disabled"}

    def device_verify(s):
        args = pack_signature_sets_raw(s, pad_b=B, pad_k=K, pad_m=M)
        return bool(jax.block_until_ready(verify_batch_raw_staged(*args)))

    # -O-safe warm-up raise (the headline bucket already compiled this
    # shape; a failure here is a workload bug, not a compile)
    if device_verify(sets) is not True:
        raise RuntimeError("pipeline leg warm-up batch must verify")

    def _recompiles() -> float:
        m = metrics.get("bls_device_recompiles_total")
        return sum(c.value for c in m.children().values()) if m else 0.0

    pipeline_profiler.reset()
    rec0 = _recompiles()
    chunk = (len(sets) + n_callers - 1) // n_callers
    chunks = [sets[i: i + chunk] for i in range(0, len(sets), chunk)]
    kinds = ("unaggregated", "aggregate", "sync_message", "sync_contribution")
    sched = VerificationScheduler(
        verify_fn=device_verify,
        deadline_ms=2000.0,
        max_batch_sets=len(sets),  # bucket-full fires on the last feeder
        max_queue_sets=4 * len(sets),
        plan_flushes=False,  # keep every flush on the one warm rung
    ).start()
    try:
        for _ in range(reps):
            futs = [None] * len(chunks)

            def feed(i):
                futs[i] = sched.submit(chunks[i], kinds[i % len(kinds)])

            threads = [
                threading.Thread(target=feed, args=(i,))
                for i in range(len(chunks))
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            if not all(f.result(timeout=1800) for f in futs):
                raise RuntimeError("pipeline leg flushes must verify")
    finally:
        sched.stop()
    doc = pipeline_profiler.summary()
    shard0 = doc["shards"].get("0", {})
    ov = doc["overlap_potential"]
    return {
        "B": B, "K": K, "M": M, "n_sets": len(sets), "reps": reps,
        "flushes": doc["flushes"]["count"],
        "steady_recompiles": _recompiles() - rec0,
        "bubble_ratio": shard0.get("bubble_ratio"),
        "dominant_bubble_cause": shard0.get("dominant_cause"),
        "bubble_causes_s": shard0.get("causes"),
        "flush_thread_saturation": doc["flush_thread_saturation"],
        "flush_phases_s": {
            p: doc["flushes"][f"{p}_s"]
            for p in pipeline_profiler.FLUSH_PHASES
        },
        "flush_wall_s": doc["flushes"]["wall_s"],
        "overlap": {
            "measured_sets_per_sec": ov["measured_sets_per_sec"],
            "projected_sets_per_sec": ov["projected_sets_per_sec"],
            "projected_speedup": ov["projected_speedup"],
        },
    }


def measure_key_table_leg(sets, B, K, M, reps: int = 3):
    """Device-resident pubkey table on/off at the headline bucket
    (ISSUE 10), same repeat-validator traffic both legs: the OFF leg
    re-packs and re-ships the G1 limb planes every rep (the measured
    >0.9 re-upload shape the table exists to kill), the ON leg ships a
    (B, K) index plane and gathers device-side. Both legs dispatch the
    SAME already-warm staged rung; the ON leg's one new compile is the
    sub-second gather program, paid in its warm-up rep and pinned by
    the steady-recompile delta. Per-leg pubkeys bytes/set (the
    acceptance metric, live operand), pack seconds and sets/s land in
    the JSON; ``pubkeys_bytes_per_set`` feeds the bench_diff gate."""
    import types as _types

    import jax

    from lighthouse_tpu.crypto.device import bls as device_bls
    from lighthouse_tpu.crypto.device import key_table as key_table_mod
    from lighthouse_tpu.utils import metrics, transfer_ledger

    if not transfer_ledger.enabled():
        return {"skipped": "transfer ledger disabled"}

    n = len(sets)

    def _pubkeys_bytes():
        doc = transfer_ledger.summary()
        return doc.get("h2d_bytes_by_operand", {}).get("pubkeys", 0)

    def _pack_total_s():
        doc = transfer_ledger.summary()
        return doc.get("pack_seconds", {}).get("total", {}).get("sum_s", 0.0)

    def _recompiles() -> float:
        m = metrics.get("bls_device_recompiles_total")
        return sum(c.value for c in m.children().values()) if m else 0.0

    def _measure(run_once):
        # warm-up (compiles land here); -O-safe — an assert would strip
        # the warm-up itself and bill the first timed rep for the compile
        if run_once() is not True:
            raise RuntimeError("key-table leg warm-up batch must verify")
        rec0 = _recompiles()
        pk0, pack0 = _pubkeys_bytes(), _pack_total_s()
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_once()
            samples.append(time.perf_counter() - t0)
        med, spread = _median_spread(samples)
        return {
            "sets_per_sec": round(n / med, 2),
            "rep_spread": round(spread, 3),
            "pubkeys_bytes_per_set": round(
                (_pubkeys_bytes() - pk0) / (reps * n), 1
            ),
            "pack_s_per_batch": round((_pack_total_s() - pack0) / reps, 4),
            "steady_recompiles": _recompiles() - rec0,
        }

    def run_off():
        args = device_bls.pack_signature_sets_raw(
            sets, pad_b=B, pad_k=K, pad_m=M
        )
        return bool(
            jax.block_until_ready(device_bls.verify_batch_raw_staged(*args))
        )

    off = _measure(run_off)

    # the table mirrors exactly this workload's distinct points (the
    # bench's stand-in for the node's ValidatorPubkeyCache; same
    # identity-map contract: the wrappers pin the very point objects
    # the sets carry)
    points, seen = [], set()
    for _sig, pks, _m in sets:
        for p in pks:
            if id(p) not in seen:
                seen.add(id(p))
                points.append(p)
    cache = _types.SimpleNamespace(
        pubkeys=[_types.SimpleNamespace(point=p) for p in points]
    )
    table = key_table_mod.DeviceKeyTable(cache)
    table.sync(reason="startup")
    key_table_mod.set_table(table)
    try:

        def run_on():
            res = table.resolve_sets(sets)
            if res is None:
                raise RuntimeError("bench sets must be table-resident")
            resolved, dev, agg, collapsed = res
            # the bench dispatches directly (no backend), so it commits
            # the shipping-path accounting the hit-ratio reads
            table.count_shipped(len(sets) - collapsed, collapsed)
            args = device_bls.pack_signature_sets_indexed(
                sets, resolved, pad_b=B, pad_k=K, pad_m=M
            )
            return bool(
                jax.block_until_ready(
                    device_bls.verify_batch_raw_staged_gather(dev, agg, *args)
                )
            )

        on = _measure(run_on)
    finally:
        key_table_mod.clear_table(table)
    st = table.status()
    on["hit_ratio"] = st["hit_ratio"]
    on["collapsed_sets"] = st["sets"]["collapsed"]
    on["aggregate_rows"] = st["aggregates_resident"]
    off_b, on_b = off["pubkeys_bytes_per_set"], on["pubkeys_bytes_per_set"]
    return {
        "B": B, "K": K, "M": M, "n_sets": n, "reps": reps,
        "off": off,
        "on": on,
        "pubkeys_bytes_per_set_reduction": (
            round(1.0 - on_b / off_b, 4) if off_b else None
        ),
        "table_validators": st["validators_resident"],
        "table_upload_bytes": st["upload_bytes"],
    }


def measure_replay_leg(
    generator: str = "epoch_boundary_flood",
    seed: int = 7,
    duration_s: float = 8.0,
    time_scale: float = 0.5,
    deadline_ms: float = 50.0,
) -> dict:
    """Mainnet-shaped traffic replay (ISSUE 7): per-kind p50/p99 verdict
    latency and deadline-miss ratio under the epoch-boundary attestation
    flood, measured through the REAL scheduler stack — the arrival-model
    counterpart of every steady-state leg above, and the standing
    acceptance surface for roadmap items 1-3 (docs/TRAFFIC_REPLAY.md).
    Runs ``tools/traffic_replay.py`` in a SUBPROCESS (crash/wedge costs
    a marker, never the bench line) against the cpu-native backend —
    real crypto, no XLA compiles, so the leg measures SCHEDULING latency
    at a budget the driver can afford; the report records which backend
    actually ran (a stub fallback can never masquerade as measured
    crypto)."""
    replay = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "traffic_replay.py",
    )
    leg_timeout = min(300.0, _budget_left() - 60)
    if leg_timeout < 60:
        return {"skipped": "budget"}
    env = _cpu_child_env()
    try:
        r = subprocess.run(
            [sys.executable, replay,
             "--generate", generator, "--seed", str(seed),
             "--duration", str(duration_s),
             "--time-scale", str(time_scale),
             "--deadline-ms", str(deadline_ms),
             "--verify", "native", "--json"],
            capture_output=True, text=True, timeout=leg_timeout, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"skipped": f"timeout>{leg_timeout:.0f}s"}
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}: {r.stderr[-200:]}"}
    try:
        report = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"unparseable output: {r.stdout[-200:]}"}
    slo = report["slo"]
    rec = {
        "generator": generator,
        "seed": seed,
        "n_events": report["n_events"],
        "n_sets": report["n_sets"],
        "time_scale": time_scale,
        "deadline_ms": slo["deadline_ms"],
        "verify_backend": report["config"]["verify_backend"],
        "wall_s": report["wall_s"],
        "arrival_fidelity": report["arrival_fidelity"],
        "dispatch_lag_p99_ms": report["dispatch_lag_ms"]["p99"],
        "deadline_misses_total": slo["deadline_misses_total"],
        "per_kind": {
            kind: {
                "count": rec["count_total"],
                "p50_ms": rec["p50_ms"],
                "p99_ms": rec["p99_ms"],
                "miss_ratio": rec["window_miss_ratio"],
                "paths": {p: v["count"] for p, v in rec["paths"].items()},
            }
            for kind, rec in slo["kinds"].items()
        },
        "plans": report["scheduler"]["planner"],
    }
    rec["data_movement"] = _replay_data_movement(
        generator, seed, duration_s, deadline_ms, time_scale
    )
    return rec


def _replay_data_movement(
    generator: str, seed: int, duration_s: float,
    deadline_ms: float, time_scale: float,
) -> dict:
    """Modeled data movement for the replay trace (ISSUE 8): a jax-free
    ``tools/transfer_report.py`` subprocess prices the SAME trace's
    flush plans with the shared byte model and models the pubkey
    re-upload ratio (same validators re-sign every epoch) — the replay
    leg runs cpu-native crypto, so measured device bytes do not exist
    here and a modeled number is reported AS a model. The lockstep
    model runs the leg's OWN flush policy: the wall-clock deadline is
    converted to trace time (deadline / time_scale), so the modeled
    flush plans are the ones the live leg actually aggregated."""
    if _budget_left() < 90:
        return {"skipped": "budget"}
    report_tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "transfer_report.py",
    )
    trace_deadline_ms = deadline_ms / max(time_scale, 1e-9)
    try:
        r = subprocess.run(
            [sys.executable, report_tool,
             "--generate", generator, "--seed", str(seed),
             "--duration", str(duration_s),
             "--deadline-ms", str(trace_deadline_ms), "--json"],
            capture_output=True, text=True, timeout=60,
        )
    except subprocess.TimeoutExpired:
        return {"skipped": "timeout>60s"}
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}: {r.stderr[-200:]}"}
    try:
        rep = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"unparseable output: {r.stdout[-200:]}"}
    n_sets = sum(rec["sets"] for rec in rep["per_kind"].values())
    return {
        "mode": rep["mode"],
        "est_h2d_bytes_total": rep["est_h2d_bytes_total"],
        "est_h2d_bytes_per_set": (
            round(rep["est_h2d_bytes_total"] / n_sets, 1) if n_sets else None
        ),
        "h2d_bytes_by_operand": rep["h2d_bytes_by_operand"],
        "padding_bytes_share": rep["padding_bytes_share"],
        "pubkey_bytes_share": rep["pubkey_bytes_share"],
        "modeled_reupload_ratio": rep["reupload_model"]["ratio"],
        "dedup_opportunity_bytes": rep["dedup_opportunity_bytes"],
        "dedup_ceiling_bytes": rep["dedup_ceiling_bytes"],
    }


def measure_capacity_leg(
    headline_sets_per_sec: float,
    generator: str = "saturation_ramp",
    seed: int = 11,
    duration_s: float = 20.0,
    deadline_ms: float = 25.0,
) -> dict:
    """Capacity/headroom estimator leg (ISSUE 14): lockstep-replay a
    ``saturation_ramp`` trace through the estimator at THIS RUN's
    measured headline cost (1 / headline sets/s) — a jax-free
    ``tools/capacity_report.py`` subprocess — and record where the ramp
    saturates, where the modeled miss onset lands, and the predictive
    lead between them. The ramp and its bulk-backfill floor are SCALED
    to the measured capacity (mid-ramp crossing; floor bursts sized to
    drain inside ~40% of the SLO budget), so the leg stays meaningful
    from the 5 sets/s XLA-emulated box to the 567 sets/s cpu-native
    one. ``headroom_ratio`` (at trace end) and ``predictive_lead_s``
    are LEARNED, not gated, by ``tools/bench_diff.py``."""
    if not headline_sets_per_sec or headline_sets_per_sec <= 0:
        return {"skipped": "no headline throughput"}
    if _budget_left() < 60:
        return {"skipped": "budget"}
    report_tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "capacity_report.py",
    )
    capacity = float(headline_sets_per_sec)
    cost = 1.0 / capacity
    budget_s = (deadline_ms / 1000.0) * 2.0  # default slo_grace
    # nominal ramp mean rate at scale 1 ≈ (5+80)/2 + floor; scale so
    # capacity crosses mid-ramp, and size floor bursts to ~40% budget
    rate_scale = max(0.01, capacity / 46.0)
    backfill_sets = max(1, int(capacity * budget_s * 0.4))
    try:
        r = subprocess.run(
            [sys.executable, report_tool,
             "--generate", generator, "--seed", str(seed),
             "--duration", str(duration_s),
             "--rate-scale", f"{rate_scale:.6g}",
             "--param", f"backfill_sets={backfill_sets}",
             "--cost-per-set", f"{cost:.9g}",
             "--deadline-ms", str(deadline_ms), "--json"],
            capture_output=True, text=True, timeout=60, env=_cpu_child_env(),
        )
    except subprocess.TimeoutExpired:
        return {"skipped": "timeout>60s"}
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}: {r.stderr[-200:]}"}
    try:
        rep = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"unparseable output: {r.stdout[-200:]}"}
    return {
        "generator": generator,
        "seed": seed,
        # False when serving ONE set already exceeds the SLO budget
        # (the 5 sets/s XLA-emulated box): misses are then structural,
        # not saturation-driven, and the predictive lead can go
        # negative — the estimator still reads demand honestly
        "budget_feasible": capacity * budget_s >= 1.0,
        "modeled_capacity_sets_per_sec": rep["model"][
            "capacity_sets_per_sec"
        ],
        "cost_s_per_set": rep["model"]["cost_s_per_set"],
        "rate_scale": round(rate_scale, 6),
        "backfill_sets": backfill_sets,
        "n_sets": rep["n_sets"],
        "saturated_at_s": rep["saturated_at_s"],
        "miss_onset_s": rep["miss_onset_s"],
        "predictive_lead_s": rep["predictive_lead_s"],
        "headroom_min": rep["headroom_min"],
        "headroom_ratio": rep["headroom_final"],
        "peak_wait_ms": rep["peak_wait_ms"],
    }


def measure_epoch_flood_leg(
    seed: int = 7,
    duration_s: float = 12.0,
    time_scale: float = 0.25,
    deadline_ms: float = 50.0,
    slot_s: float = 2.0,
) -> dict:
    """Slot-aligned epoch-flood leg (ISSUE 17): replay the canonical
    ``epoch_boundary_flood`` trace with the chain-time axis on and
    score WHERE in chain time the tail lives — the per-slot p99 spread
    between the flood slots and the quiet slots, plus the committee
    first-sighting hit ratio (ROADMAP item 3's go/no-go dial: the
    flood's committee tuples repeat, so most sightings should collapse
    to cache hits). Stub-backend subprocess (seconds): the leg measures
    slot ATTRIBUTION under load, not crypto. Both headline numbers are
    LEARNED, not gated, by ``tools/bench_diff.py``."""
    replay = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "traffic_replay.py",
    )
    leg_timeout = min(120.0, _budget_left() - 60)
    if leg_timeout < 30:
        return {"skipped": "budget"}
    env = _cpu_child_env()
    try:
        r = subprocess.run(
            [sys.executable, replay,
             "--generate", "epoch_boundary_flood", "--seed", str(seed),
             "--duration", str(duration_s),
             "--time-scale", str(time_scale),
             "--deadline-ms", str(deadline_ms),
             "--slot-s", str(slot_s),
             "--verify", "stub:0.0005", "--json"],
            capture_output=True, text=True, timeout=leg_timeout, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"skipped": f"timeout>{leg_timeout:.0f}s"}
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}: {r.stderr[-200:]}"}
    try:
        report = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"unparseable output: {r.stdout[-200:]}"}
    slots = [s for s in report.get("slots", []) if s["sets"]]
    if not slots:
        return {"error": "no slot cards in replay report"}
    # flood slots by demand, not by position: the flood window's cards
    # carry well over the median per-slot set count
    counts = sorted(s["sets"] for s in slots)
    median_sets = counts[len(counts) // 2]
    flood = [s for s in slots if s["sets"] > 2 * median_sets]
    quiet = [s for s in slots if s not in flood]
    p99s = [s["p99_ms"] for s in slots if s["p99_ms"] is not None]
    ct = report.get("chain_time", {})
    return {
        "generator": "epoch_boundary_flood",
        "seed": seed,
        "slot_s": slot_s,
        "time_scale": time_scale,
        "n_slots": len(slots),
        "flood_slots": sorted(s["slot"] for s in flood),
        "flood_sets": sum(s["sets"] for s in flood),
        "quiet_sets": sum(s["sets"] for s in quiet),
        "flood_p99_ms": (
            round(max(s["p99_ms"] for s in flood), 3) if flood else None
        ),
        "quiet_p99_ms": (
            round(
                sorted(s["p99_ms"] for s in quiet)[len(quiet) // 2], 3
            ) if quiet else None
        ),
        "p99_spread_ms": (
            round(max(p99s) - min(p99s), 3) if len(p99s) > 1 else 0.0
        ),
        "misses_in_flood_slots": sum(s["misses"] for s in flood),
        "misses_total": sum(s["misses"] for s in slots),
        "committee_sightings": ct.get("committee_sightings"),
        "first_sighting_hit_ratio": ct.get("first_sighting_hit_ratio"),
    }


def measure_lookahead_leg(
    seed: int = 7,
    duration_s: float = 12.0,
    time_scale: float = 0.25,
    deadline_ms: float = 50.0,
    slot_s: float = 2.0,
) -> dict:
    """Duty-lookahead leg (ISSUE 19): the canonical epoch-boundary
    flood replayed twice — reactive-only vs ``--lookahead`` (the
    duty-lookahead warm pre-seeding each epoch's committees before
    their first signature). Scores the first-sighting hit ratio pair
    (acceptance: ~0.8 off, 1.0 on with ZERO first sightings), the
    flood-slot p99 on each side, and the warm's attribution (committees
    warmed, host vs device sums — the replay is stub-backend, so sums
    are virtual and host_sums must stay 0 inside verify spans either
    way). Two stub subprocesses (seconds); headline numbers LEARNED by
    ``tools/bench_diff.py``."""
    replay = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "traffic_replay.py",
    )
    env = _cpu_child_env()

    def _run(lookahead: bool) -> dict:
        leg_timeout = min(120.0, _budget_left() - 60)
        if leg_timeout < 30:
            return {"skipped": "budget"}
        cmd = [sys.executable, replay,
               "--generate", "epoch_boundary_flood", "--seed", str(seed),
               "--duration", str(duration_s),
               "--time-scale", str(time_scale),
               "--deadline-ms", str(deadline_ms),
               "--slot-s", str(slot_s),
               "--verify", "stub:0.0005", "--json"]
        if lookahead:
            cmd.append("--lookahead")
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True, timeout=leg_timeout,
                env=env,
            )
        except subprocess.TimeoutExpired:
            return {"skipped": f"timeout>{leg_timeout:.0f}s"}
        if r.returncode != 0:
            return {"error": f"rc={r.returncode}: {r.stderr[-200:]}"}
        try:
            return json.loads(r.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            return {"error": f"unparseable output: {r.stdout[-200:]}"}

    def _side(report: dict) -> dict:
        if "skipped" in report or "error" in report:
            return report
        ct = report.get("chain_time", {})
        slots = [s for s in report.get("slots", []) if s["sets"]]
        counts = sorted(s["sets"] for s in slots)
        median_sets = counts[len(counts) // 2] if counts else 0
        flood = [s for s in slots if s["sets"] > 2 * median_sets]
        lifetime = ct.get("lifetime", {})
        side = {
            "first_sighting_hit_ratio": ct.get("first_sighting_hit_ratio"),
            "first_sightings": ct.get("first_sightings"),
            "sighting_hits": ct.get("sighting_hits"),
            "flood_p99_ms": (
                round(max(s["p99_ms"] for s in flood), 3)
                if flood and all(
                    s["p99_ms"] is not None for s in flood
                ) else None
            ),
            "verdicts": report.get("verdicts"),
            "lookahead_host_sums": lifetime.get("lookahead_host_sums", 0),
        }
        la = ct.get("lookahead")
        if la:
            side["epochs_warmed"] = la.get("epochs_warmed")
            side["committees_warmed"] = la.get("committees")
            side["prewarmed"] = la.get("prewarmed")
        return side

    off = _side(_run(lookahead=False))
    on = _side(_run(lookahead=True))
    out = {
        "generator": "epoch_boundary_flood",
        "seed": seed,
        "slot_s": slot_s,
        "time_scale": time_scale,
        "off": off,
        "on": on,
    }
    r_off = off.get("first_sighting_hit_ratio")
    r_on = on.get("first_sighting_hit_ratio")
    if r_off is not None and r_on is not None:
        out["hit_ratio_gain"] = round(r_on - r_off, 4)
        # the acceptance pair at a glance: on-side reaches unity with
        # zero first sightings, and neither side pays host EC sums in
        # verify spans (warm sums are attributed off-path)
        out["on_reaches_unity"] = bool(
            r_on >= 1.0 and on.get("first_sightings") == 0
        )
        out["verdicts_identical"] = bool(
            off.get("verdicts") == on.get("verdicts")
        )
    return out


def measure_chaos_leg(
    generator: str = "gossip_steady",
    seed: int = 5,
    duration_s: float = 6.0,
    time_scale: float = 0.5,
    deadline_ms: float = 100.0,
) -> dict:
    """Self-healing under chaos (ISSUE 13): a gossip-steady replay on a
    2-shard mesh with one INJECTED shard loss and an in-replay
    recovery — kill → probation (backoff probes through the same
    verify seam) → re-admission — recording the SLO miss ratio during
    degradation, the time-to-recover, and post-recovery sets/s (the
    dp axis must come back, not just survive). Stub backend in a
    SUBPROCESS: the leg certifies the RECOVERY machinery's latency
    economics, which live entirely in the scheduling layer (the
    staged-device half of degradation is `tests/test_zgate8_multichip`;
    the chaos gate is `tests/test_zgate9_chaos`). bench_diff gates
    `time_to_recover_s` — a recovery that slows >20% is a regression
    in the node's capacity restoration, exactly what the committee
    cost model assumes never leaks (PAPERS.md arxiv 2302.00418)."""
    replay = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "traffic_replay.py",
    )
    leg_timeout = min(240.0, _budget_left() - 60)
    if leg_timeout < 60:
        return {"skipped": "budget"}
    env = _cpu_child_env()
    try:
        r = subprocess.run(
            [sys.executable, replay,
             "--generate", generator, "--seed", str(seed),
             "--duration", str(duration_s),
             "--time-scale", str(time_scale),
             "--deadline-ms", str(deadline_ms),
             "--dp", "2", "--kill-shard", "1", "--kill-after", "3",
             "--revive-shard", "1", "--revive-after", "10",
             "--probe-base-s", "0.1",
             "--verify", "stub:0.001", "--json"],
            capture_output=True, text=True, timeout=leg_timeout, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"skipped": f"timeout>{leg_timeout:.0f}s"}
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}: {r.stderr[-200:]}"}
    try:
        report = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"unparseable output: {r.stdout[-200:]}"}
    rec = report.get("recovery") or {}
    mesh = report.get("mesh") or {}
    if not rec.get("recovered"):
        # a chaos leg that never exercised recovery must be LOUD: the
        # gated time_to_recover_s is absent and bench_diff reports the
        # skipped gate instead of silently passing
        return {
            "error": "injected shard loss did not recover in-replay",
            "recovery": rec,
        }
    return {
        "generator": generator,
        "seed": seed,
        "time_scale": time_scale,
        "deadline_ms": deadline_ms,
        "verify_backend": report["config"]["verify_backend"],
        "n_events": report["n_events"],
        "n_sets": report["n_sets"],
        "wall_s": report["wall_s"],
        "verdicts": report["verdicts"],
        "time_to_recover_s": rec["time_to_recover_s"],
        "probes": rec["probes"],
        "flushes_served_degraded": rec["flushes_served_degraded"],
        "sets_served_degraded": rec["sets_served_degraded"],
        "slo_miss_ratio_degraded": rec["slo_miss_ratio_degraded"],
        "post_recovery_sets_per_sec": rec.get("post_recovery_sets_per_sec"),
        "recoveries_total": mesh.get("recoveries_total"),
        "healthy_shards_final": mesh.get("healthy_shards"),
        "deadline_misses_total": report["slo"]["deadline_misses_total"],
    }


def measure_bulk_leg(
    seed: int = 9,
    duration_s: float = 4.0,
    time_scale: float = 0.5,
    deadline_ms: float = 60.0,
) -> dict:
    """Bulk QoS class isolation (ISSUE 15): replay the
    ``bulk_backfill_under_gossip`` composite vs its gossip-only
    baseline — BYTE-IDENTICAL gossip arrivals by construction
    (docs/TRAFFIC_REPLAY.md) — through a live scheduler with a stub
    backend, each in a subprocess. Records gossip's worst-kind p99 and
    miss ratio in both runs (``gossip_p99_under_bulk_ms`` is GATED by
    ``tools/bench_diff.py`` — a growing number means the bulk class
    started moving gossip's tail, the exact failure mode the class
    exists to prevent) plus the bulk side's served throughput, sheds
    and throttle excursions. Chunk size is pinned small for the stub
    backend (one 512-set chunk's wall would rival the deadline here —
    the documented head-of-line knob)."""
    replay = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "traffic_replay.py",
    )
    env = _cpu_child_env()
    env["LIGHTHOUSE_TPU_SCHED_BULK_FLUSH_SETS"] = "64"
    env["LIGHTHOUSE_TPU_SCHED_BULK_LINGER_MS"] = "10"
    reports = {}
    for label, gen in (
        ("baseline", "gossip_steady"),
        ("bulk", "bulk_backfill_under_gossip"),
    ):
        leg_timeout = min(120.0, _budget_left() - 60)
        if leg_timeout < 45:
            return {"skipped": "budget"}
        try:
            r = subprocess.run(
                [sys.executable, replay,
                 "--generate", gen, "--seed", str(seed),
                 "--duration", str(duration_s),
                 "--time-scale", str(time_scale),
                 "--deadline-ms", str(deadline_ms),
                 "--workers", "96",
                 "--verify", "stub:0.0002", "--json"],
                capture_output=True, text=True, timeout=leg_timeout,
                env=env,
            )
        except subprocess.TimeoutExpired:
            return {"skipped": f"timeout>{leg_timeout:.0f}s"}
        if r.returncode != 0:
            return {"error": f"{label}: rc={r.returncode}: {r.stderr[-200:]}"}
        try:
            reports[label] = json.loads(r.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            return {"error": f"{label}: unparseable: {r.stdout[-200:]}"}

    def worst_gossip(rep):
        p99 = miss = 0.0
        for kind in ("unaggregated", "aggregate", "sync_message"):
            rec = rep["slo"]["kinds"].get(kind)
            if rec:
                p99 = max(p99, rec["p99_ms"])
                miss = max(miss, rec["window_miss_ratio"])
        return p99, miss

    p99_0, miss_0 = worst_gossip(reports["baseline"])
    p99_1, miss_1 = worst_gossip(reports["bulk"])
    bulk_st = reports["bulk"]["scheduler"]["bulk"]
    wall = reports["bulk"]["wall_s"]
    return {
        "generator": "bulk_backfill_under_gossip",
        "seed": seed,
        "time_scale": time_scale,
        "deadline_ms": deadline_ms,
        "verify_backend": reports["bulk"]["config"]["verify_backend"],
        "gossip_p99_baseline_ms": p99_0,
        "gossip_p99_under_bulk_ms": p99_1,
        "gossip_p99_ratio": (
            round(p99_1 / p99_0, 4) if p99_0 else None
        ),
        "gossip_miss_ratio_baseline": miss_0,
        "gossip_miss_ratio_under_bulk": miss_1,
        "bulk_sets_flushed": bulk_st["sets_flushed_total"],
        "bulk_sets_per_sec": (
            round(bulk_st["sets_flushed_total"] / wall, 2) if wall else None
        ),
        "bulk_flushes": bulk_st["flushes_total"],
        "bulk_shed_total": bulk_st["shed_total"],
        "throttle_excursions": bulk_st["admission"]["excursions_total"],
        "verdicts": reports["bulk"]["verdicts"],
    }


def measure_watchtower_leg(
    seed: int = 7,
    duration_s: float = 14.0,
    rate_scale: float = 2.2,
    deadline_ms: float = 250.0,
) -> dict:
    """Anomaly-watchtower economics (ISSUE 18): the acceptance
    ``saturation_ramp`` replay twice — watchtower OFF then ON (isolated
    sampler + evaluator armed around the replay) — recording (a) the
    evaluator's throughput overhead as the sets/s delta, flagged
    against the documented <1% budget, and (b) the DETECTION LEAD on
    the ON run: how many seconds the ``headroom_floor`` page opened
    before the first deadline-miss burst. Both ride the trajectory
    LEARNED (stub-backend wall-clock instruments, not SLOs); the hard
    acceptance lives in ``tests/test_watchtower.py``. A negative lead
    here means the watchtower has become a postmortem tool — exactly
    what the predictive headroom dial (COST_MODEL.md) exists to
    prevent."""
    replay = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "traffic_replay.py",
    )
    env = _cpu_child_env()
    base_args = [
        sys.executable, replay,
        "--generate", "saturation_ramp", "--seed", str(seed),
        "--duration", str(duration_s), "--rate-scale", str(rate_scale),
        "--deadline-ms", str(deadline_ms), "--workers", "256",
        "--verify", "stub:0.005", "--json",
    ]
    reports = {}
    for label, extra in (("off", []), ("on", ["--watchtower"])):
        leg_timeout = min(120.0, _budget_left() - 60)
        if leg_timeout < 45:
            return {"skipped": "budget"}
        try:
            r = subprocess.run(
                base_args + extra, capture_output=True, text=True,
                timeout=leg_timeout, env=env,
            )
        except subprocess.TimeoutExpired:
            return {"skipped": f"timeout>{leg_timeout:.0f}s"}
        if r.returncode != 0:
            return {"error": f"{label}: rc={r.returncode}: {r.stderr[-200:]}"}
        try:
            reports[label] = json.loads(r.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            return {"error": f"{label}: unparseable: {r.stdout[-200:]}"}

    def rate(rep):
        return rep["n_sets"] / rep["wall_s"] if rep["wall_s"] else 0.0

    rate_off, rate_on = rate(reports["off"]), rate(reports["on"])
    overhead = (rate_off - rate_on) / rate_off if rate_off else None
    wt = reports["on"].get("watchtower") or {}
    lead = wt.get("lead") or {}
    incidents = wt.get("incidents") or []
    return {
        "generator": "saturation_ramp",
        "seed": seed,
        "rate_scale": rate_scale,
        "deadline_ms": deadline_ms,
        "verify_backend": reports["on"]["config"]["verify_backend"],
        "n_sets": reports["on"]["n_sets"],
        "sets_per_sec_off": round(rate_off, 2),
        "sets_per_sec_on": round(rate_on, 2),
        "overhead_ratio": round(overhead, 4) if overhead is not None else None,
        "overhead_under_1pct": (
            overhead is not None and overhead < 0.01
        ),
        "n_incidents": lead.get("n_incidents"),
        "first_incident_detector": lead.get("first_incident_detector"),
        "first_incident_t": lead.get("first_incident_t"),
        "first_miss_burst_t": lead.get("first_miss_burst_t"),
        "lead_time_s": lead.get("lead_time_s"),
        "incident_detectors": sorted(
            {i.get("detector") for i in incidents if i.get("detector")}
        ),
    }


def measure_dp_leg(
    n_sets: int = 16, reps: int = 3, messages: int = 2
) -> dict:
    """Served multi-chip data-parallel verify, 1 vs 2 devices
    (ISSUE 11): the SAME single-pubkey gossip mix driven through the
    real scheduler+planner+TpuBackend stack on a virtual mesh, per-chip
    and aggregate sets/s recorded. Each width runs in a SUBPROCESS with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (the flag
    must precede jax init) and ``JAX_PLATFORMS=cpu`` — the virtual-mesh
    recipe DP_SCALING.json already certifies for the raw program, now
    measured through the served path. Honest caveat recorded in the
    record: on this box every virtual device shares the same physical
    cores, so the 2-device aggregate does NOT beat 1-device wall-clock
    here — the leg certifies the served sharding machinery (plan
    shapes, per-chip dispatch, zero steady recompiles per shard) and
    the per-chip numbers; the aggregate win is the real-chip story
    (COST_MODEL.md per-chip scaling)."""
    legs = {}
    for n_dev in (1, 2):
        leg_timeout = min(1500.0, _budget_left() - 120)
        if leg_timeout < 400:
            legs[f"dp{n_dev}"] = {"skipped": "budget"}
            continue
        env = _cpu_child_env()
        xla = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in xla:
            env["XLA_FLAGS"] = (
                xla + " --xla_force_host_platform_device_count=2"
            ).strip()
        try:
            r = subprocess.run(
                [sys.executable, __file__, "--dp-leg",
                 str(n_dev), str(n_sets), str(reps), str(messages)],
                capture_output=True, text=True, timeout=leg_timeout,
                env=env,
            )
        except subprocess.TimeoutExpired:
            legs[f"dp{n_dev}"] = {"skipped": f"timeout>{leg_timeout:.0f}s"}
            continue
        if r.returncode != 0:
            legs[f"dp{n_dev}"] = {
                "error": f"rc={r.returncode}: {r.stderr[-200:]}"
            }
            continue
        try:
            legs[f"dp{n_dev}"] = json.loads(r.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            legs[f"dp{n_dev}"] = {"error": f"unparseable: {r.stdout[-200:]}"}
    rec = {
        "n_sets": n_sets,
        "reps": reps,
        "caveat": (
            "virtual CPU mesh: all devices share one host's cores, so "
            "aggregate wall-clock does not scale here; the leg "
            "certifies served dp sharding + per-chip accounting"
        ),
        **legs,
    }
    one = legs.get("dp1", {}).get("sets_per_sec")
    two = legs.get("dp2", {}).get("sets_per_sec")
    if one and two:
        rec["aggregate_speedup"] = round(two / one, 4)
    return rec


def _dp_leg_main(argv) -> None:
    """Subprocess body for the dp leg: build an n_devices mesh, drive
    the scheduler's (dp x rung) plan with real staged device verifies,
    and print per-chip + aggregate sets/s as one JSON line."""
    import threading

    n_dev, n_sets, reps, messages = (int(v) for v in argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from lighthouse_tpu.crypto.device import mesh as mesh_mod
    from lighthouse_tpu.crypto.device.bls import TpuBackend
    from lighthouse_tpu.utils import metrics
    from lighthouse_tpu.verification_service import VerificationScheduler

    mesh = mesh_mod.DeviceMesh(n_devices=n_dev)
    mesh_mod.set_mesh(mesh)

    # single-pubkey gossip mix over a few messages: the kind the dp
    # axis splits first (K=1 rungs are the cheapest XLA:CPU compiles,
    # keeping the leg affordable; the plan shapes generalize)
    from lighthouse_tpu.crypto import bls

    sk = bls.SecretKey(4242)
    pk = sk.public_key().point
    msgs = [bytes([m + 1]) * 32 for m in range(messages)]
    sigs = {m: bls.Signature.deserialize(sk.sign(m).serialize()) for m in msgs}
    sets = [(sigs[msgs[i % messages]], [pk], msgs[i % messages])
            for i in range(n_sets)]

    from lighthouse_tpu.verification_service.planner import FlushPlanner

    backend = TpuBackend()
    sched = VerificationScheduler(
        verify_fn=backend.verify_signature_sets,
        deadline_ms=60_000.0,
        max_batch_sets=n_sets,  # bucket-full fires on the last feeder
        max_queue_sets=4 * n_sets,
        # threshold scaled to the leg's workload so the flush always
        # splits across the full mesh width (the default dp_min_sets=8
        # is a production trickle guard, not a bench knob)
        flush_planner=FlushPlanner(
            dp_min_sets=max(1, n_sets // (2 * max(1, n_dev)))
        ),
    ).start()

    def _recompiles() -> float:
        m = metrics.get("bls_device_recompiles_total")
        return sum(c.value for c in m.children().values()) if m else 0.0

    def run_flush() -> float:
        futs = [None] * n_sets

        def feed(i):
            futs[i] = sched.submit([sets[i]], "unaggregated")

        threads = [
            threading.Thread(target=feed, args=(i,)) for i in range(n_sets)
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if not all(f.result(timeout=1800) for f in futs):
            raise RuntimeError("dp leg batch must verify")
        return time.perf_counter() - t0

    try:
        run_flush()  # warm-up: the per-shard rung compiles land here
        rec0 = _recompiles()
        samples = [run_flush() for _ in range(reps)]
        steady = _recompiles() - rec0
        st = sched.status()
    finally:
        sched.stop()
        mesh_mod.clear_mesh(mesh)
    med, spread = _median_spread(samples)
    mstat = mesh.status()
    print(json.dumps({
        "n_devices": n_dev,
        "sets_per_sec": round(n_sets / med, 2),
        "rep_spread": round(spread, 3),
        "steady_recompiles": steady,
        "plan": st["planner"]["last_plan"],
        "per_chip": {
            str(c["shard"]): {
                "sets_total": c["sets_total"],
                "dispatches": c["dispatches"],
                "healthy": c["healthy"],
            }
            for c in mstat["chips"]
        },
        "healthy_shards": mstat["healthy_shards"],
    }))


def _data_movement_block(before, after, n_sets, n_packs, step_s) -> dict:
    """The headline bucket's data-movement attribution (ISSUE 8), from
    the transfer-ledger summary DELTA across the measured reps: bytes/set
    by operand, effective H2D bandwidth over the device_put phase, host
    pack share of one verify step (mean pack over the MEDIAN measured
    step, so the one-time warm-up compile cannot dilute the share and
    cold vs cache-warm runs stay comparable), and the repeat-pubkey
    window (the same sets re-pack every rep — the gossip steady-state
    shape where the device-resident pubkey table wins, ROADMAP item 2)."""
    ops = {}
    for op, v in after.get("h2d_bytes_by_operand", {}).items():
        d = v - before.get("h2d_bytes_by_operand", {}).get(op, 0)
        if d:
            ops[op] = d
    total = sum(ops.values())
    denom = max(1, n_packs * n_sets)

    def _phase_sum(doc, phase):
        return doc.get("pack_seconds", {}).get(phase, {}).get("sum_s", 0.0)

    pack_s = _phase_sum(after, "total") - _phase_sum(before, "total")
    dput_s = _phase_sum(after, "device_put") - _phase_sum(before, "device_put")
    reup = after.get("pubkey_reupload", {})
    # zero counted bytes = the ledger was disabled (the pack-phase
    # histogram is always-on, so dput_s alone proves nothing): byte
    # facts become null, never a confident 0.0 — the same unmeasured-
    # vs-zero guard transfer_ledger.summary() applies
    measured = total > 0
    return {
        "n_packs": n_packs,
        "ledger_enabled": measured,
        "h2d_bytes_total": total if measured else None,
        "h2d_bytes_per_set": round(total / denom, 1) if measured else None,
        "h2d_bytes_per_set_by_operand": (
            {op: round(v / denom, 1) for op, v in sorted(ops.items())}
            if measured else None
        ),
        "d2h_bytes_total": (
            after.get("d2h_bytes_total", 0)
            - before.get("d2h_bytes_total", 0)
        ) if measured else None,
        "effective_h2d_bandwidth_bytes_per_s": (
            round(total / dput_s, 1) if dput_s > 0 and measured else None
        ),
        "pack_seconds_total": round(pack_s, 4),
        "pack_share_of_verify_wall": (
            round((pack_s / n_packs) / step_s, 4) if step_s > 0 else None
        ),
        # the acceptance metric of the device key table (ISSUE 10): live
        # G1 bytes shipped per set — the key_table_leg measures its
        # on-table counterpart
        "pubkeys_bytes_per_set": (
            round(ops.get("pubkeys", 0) / denom, 1) if measured else None
        ),
        "pubkey_reupload_ratio": reup.get("ratio") if measured else None,
        "pubkey_reupload_window": reup.get("records") if measured else None,
        "device_memory": after.get("device_memory"),
    }


def measure_native_baseline(sets, reps: int = REPS):
    """Median-of-reps sets/s of the native C backend on the same workload
    (the reference seam, blst.rs:36-119, measured as BASELINE.md
    requires). None when no C toolchain is available."""
    try:
        from lighthouse_tpu.crypto.native import NativeBackend

        native = NativeBackend()
    except Exception:
        return None, 0.0
    assert native.verify_signature_sets(sets) is True
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        native.verify_signature_sets(sets)
        samples.append(time.perf_counter() - t0)
    med, spread = _median_spread(samples)
    return len(sets) / med, spread


def measure_bucket(pack, verify, sets, B, K, M, reps: int = REPS):
    """Median-of-reps end-to-end (pack + device) throughput for one
    padded bucket shape. Returns a record dict."""
    import jax

    def run_once():
        args = pack(sets, pad_b=B, pad_k=K, pad_m=M)
        out = verify(*args)
        jax.block_until_ready(out)
        return out

    t0 = time.perf_counter()
    ok = run_once()  # warm-up: compile
    warm_s = time.perf_counter() - t0
    assert bool(ok) is True, "benchmark batch must verify"

    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_once()
        samples.append(time.perf_counter() - t0)
    med, spread = _median_spread(samples)
    n_sets = len(sets)
    real_pk_slots = sum(len(pks) for _, pks, _ in sets)
    return {
        "B": B, "K": K, "M": M, "n_sets": n_sets,
        "sets_per_sec": round(n_sets / med, 2),
        "step_s": round(med, 4),
        "rep_spread": round(spread, 3),
        "warmup_s": round(warm_s, 1),
        "padding_waste": round(1.0 - real_pk_slots / (B * K), 4),
    }


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench.py measures the chip; JAX found {dev.platform!r}",
            file=sys.stderr,
        )
        sys.exit(2)
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }

    from lighthouse_tpu.compile_service import cache as compile_cache

    compile_cache.enable_persistent_cache()

    from lighthouse_tpu.crypto.device import fp as device_fp
    from lighthouse_tpu.crypto.device.bls import (
        pack_signature_sets_raw,
        stage_latency_summary,
        verify_batch_raw_staged,
    )

    from lighthouse_tpu.utils import transfer_ledger

    sets = build_sets(N_AGG, COMMITTEE, N_MSGS)
    dm_before = transfer_ledger.summary()
    headline = measure_bucket(
        pack_signature_sets_raw, verify_batch_raw_staged, sets,
        B_PAD, K_PAD, M_PAD,
    )
    # Data-movement attribution for the headline bucket (ISSUE 8): the
    # ledger delta over exactly the warm-up + reps packs above.
    data_movement = _data_movement_block(
        dm_before, transfer_ledger.summary(),
        n_sets=headline["n_sets"], n_packs=REPS + 1,
        step_s=headline["step_s"],
    )
    # Per-stage attribution from the new telemetry histograms, read
    # BEFORE the extra buckets run so the quantiles describe the headline
    # geometry (the family keeps accumulating across buckets).
    headline["stage_latency"] = stage_latency_summary(device_fp.get_impl())

    buckets = [headline]
    for spec in EXTRA_BUCKETS:
        if _budget_left() < 600:
            buckets.append({"K": spec["K"], "skipped": "budget"})
            continue
        try:
            bsets = build_sets(spec["n_agg"], spec["K"], spec["M"])
            buckets.append(
                measure_bucket(
                    pack_signature_sets_raw, verify_batch_raw_staged,
                    bsets, spec["B"], spec["K"], spec["M"],
                )
            )
        except Exception as e:  # a failed bucket must not kill the line
            buckets.append({"K": spec["K"], "error": str(e)[:200]})

    # Fused-scheduler vs fragmented per-caller dispatch at the headline
    # shape (same compiled program both legs, see measure_scheduler_leg).
    if _budget_left() < 300:
        scheduler_leg = {"skipped": "budget"}
    else:
        try:
            scheduler_leg = measure_scheduler_leg(sets, B_PAD, K_PAD, M_PAD)
        except Exception as e:  # the leg must not kill the line
            scheduler_leg = {"error": str(e)[:200]}

    # Planned multi-rung flush vs legacy single-rung flush (ISSUE 6):
    # the padding-waste fix measured at the headline mix. The planned
    # leg pays its sub-batch rung compiles inside its warm-up flush, so
    # it needs real budget; skipped-with-marker beats silent truncation.
    if _budget_left() < 900:
        planner_leg = {"skipped": "budget"}
    else:
        try:
            planner_leg = measure_planner_leg(sets, B_PAD, K_PAD, M_PAD)
        except Exception as e:  # the leg must not kill the line
            planner_leg = {"error": str(e)[:200]}

    # Pipeline-occupancy profile at the headline rung (ISSUE 12):
    # bubble ratio + cause split, flush-thread saturation, and the
    # overlap-potential projection (ROADMAP item 5's go/no-go number).
    # Cheap: the headline rung is already warm, zero new compiles.
    if _budget_left() < 240:
        pipeline_leg = {"skipped": "budget"}
    else:
        try:
            pipeline_leg = measure_pipeline_leg(sets, B_PAD, K_PAD, M_PAD)
        except Exception as e:  # the leg must not kill the line
            pipeline_leg = {"error": str(e)[:200]}

    # Device key table on/off at the headline bucket (ISSUE 10): the
    # pubkey-plane bytes/set drop and pack-time delta under the same
    # repeat-validator traffic. The staged rung is already warm; the ON
    # leg adds only the sub-second gather compile (warm-up rep).
    if _budget_left() < 240:
        key_table_leg = {"skipped": "budget"}
    else:
        try:
            key_table_leg = measure_key_table_leg(sets, B_PAD, K_PAD, M_PAD)
        except Exception as e:  # the leg must not kill the line
            key_table_leg = {"error": str(e)[:200]}

    # Mainnet-shaped replay (ISSUE 7): per-class p50/p99 verdict latency
    # under the epoch-boundary flood — the arrival model the SLO layer
    # certifies, folded into the trajectory. Subprocess, budget-guarded.
    if _budget_left() < 180:
        replay_leg = {"skipped": "budget"}
    else:
        try:
            replay_leg = measure_replay_leg()
        except Exception as e:  # the leg must not kill the line
            replay_leg = {"error": str(e)[:200]}

    # Capacity leg (ISSUE 14): the headroom estimator lockstep-replayed
    # over a saturation_ramp at this run's measured headline cost —
    # jax-free subprocess, seconds. Records the saturation point and
    # the predictive lead before the modeled miss onset.
    try:
        capacity_leg = measure_capacity_leg(headline["sets_per_sec"])
    except Exception as e:  # the leg must not kill the line
        capacity_leg = {"error": str(e)[:200]}

    # Chaos leg (ISSUE 13): injected shard loss + in-replay recovery on
    # a 2-shard mesh — SLO miss ratio during degradation,
    # time-to-recover (gated by tools/bench_diff.py) and post-recovery
    # sets/s. Subprocess, budget-guarded, stub backend (seconds).
    if _budget_left() < 120:
        chaos_leg = {"skipped": "budget"}
    else:
        try:
            chaos_leg = measure_chaos_leg()
        except Exception as e:  # the leg must not kill the line
            chaos_leg = {"error": str(e)[:200]}

    # Bulk-QoS isolation leg (ISSUE 15): gossip SLO under saturating
    # backfill vs the gossip-only baseline + bulk sets/s — stub-backend
    # subprocesses, seconds. gossip_p99_under_bulk_ms is GATED.
    if _budget_left() < 120:
        bulk_leg = {"skipped": "budget"}
    else:
        try:
            bulk_leg = measure_bulk_leg()
        except Exception as e:  # the leg must not kill the line
            bulk_leg = {"error": str(e)[:200]}

    # Slot-aligned epoch-flood leg (ISSUE 17): per-slot p99 spread
    # between flood and quiet slots + the committee first-sighting hit
    # ratio on the canonical flood trace — stub-backend subprocess,
    # seconds. Both headline numbers are learned by bench_diff.
    if _budget_left() < 90:
        epoch_flood_leg = {"skipped": "budget"}
    else:
        try:
            epoch_flood_leg = measure_epoch_flood_leg()
        except Exception as e:  # the leg must not kill the line
            epoch_flood_leg = {"error": str(e)[:200]}

    # Duty-lookahead leg (ISSUE 19): the canonical flood replayed
    # reactive-only vs --lookahead — the first-sighting hit-ratio pair
    # (~0.8 -> 1.0 with zero firsts), flood p99 on each side, verdict
    # identity. Two stub subprocesses, seconds; learned by bench_diff.
    if _budget_left() < 120:
        lookahead_leg = {"skipped": "budget"}
    else:
        try:
            lookahead_leg = measure_lookahead_leg()
        except Exception as e:  # the leg must not kill the line
            lookahead_leg = {"error": str(e)[:200]}

    # Watchtower leg (ISSUE 18): the acceptance saturation ramp with
    # the anomaly evaluator off vs on — evaluator overhead (flagged
    # against the <1% budget) and the measured detection lead of the
    # headroom page over the first miss burst. Stub-backend
    # subprocesses, seconds. Both numbers learned by bench_diff.
    if _budget_left() < 120:
        watchtower_leg = {"skipped": "budget"}
    else:
        try:
            watchtower_leg = measure_watchtower_leg()
        except Exception as e:  # the leg must not kill the line
            watchtower_leg = {"error": str(e)[:200]}

    # Served multi-chip dp verify, 1 vs 2 virtual devices (ISSUE 11):
    # per-chip + aggregate sets/s through the real scheduler/planner/
    # backend stack. Subprocesses (XLA_FLAGS must precede jax init),
    # budget-guarded; the compile cache keeps repeats cheap.
    if _budget_left() < 1000:
        dp_leg = {"skipped": "budget"}
    else:
        try:
            dp_leg = measure_dp_leg()
        except Exception as e:  # the leg must not kill the line
            dp_leg = {"error": str(e)[:200]}

    baseline, base_spread = measure_native_baseline(sets)
    sets_per_sec = headline["sets_per_sec"]
    agg_per_sec = sets_per_sec / 3.0

    # Per-implementation leg (VERDICT r5 rec #2): re-run the HEADLINE
    # bucket in this process under the OTHER fp.mul engine
    # (crypto/device/fp.py) — by default matmul_int8, the int8-MXU
    # decomposition. Runs LAST; skipped-with-marker beats silent
    # truncation.
    headline_impl = device_fp.get_impl()
    alt_impl = (
        device_fp.IMPL_MATMUL_INT8
        if headline_impl != device_fp.IMPL_MATMUL_INT8
        else device_fp.IMPL_TOEPLITZ_INT32
    )
    impl_legs = {headline_impl: headline}
    if _budget_left() < 300:
        impl_legs[alt_impl] = {"skipped": "budget"}
    else:
        device_fp.set_impl(alt_impl)
        try:
            rec = measure_bucket(
                pack_signature_sets_raw, verify_batch_raw_staged, sets,
                B_PAD, K_PAD, M_PAD,
            )
            rec["fp_impl"] = alt_impl
            rec["stage_latency"] = stage_latency_summary(alt_impl)
            impl_legs[alt_impl] = rec
        except Exception as e:  # the alt leg must not kill the line
            impl_legs[alt_impl] = {"error": str(e)[:200]}
        finally:
            device_fp.set_impl(headline_impl)

    # legs whose child processes were pinned to the CPU say so
    for leg in (
        replay_leg, capacity_leg, chaos_leg, bulk_leg, epoch_flood_leg,
        lookahead_leg, watchtower_leg, dp_leg,
    ):
        leg["child_platform"] = "cpu"

    print(
        json.dumps(
            {
                "metric": "bls_sigset_verifications_per_sec_per_chip",
                "value": sets_per_sec,
                "unit": "sets/s",
                "vs_baseline": (
                    round(sets_per_sec / baseline, 4) if baseline else 0.0
                ),
                "vs_target": round(agg_per_sec / TARGET_AGG_PER_SEC, 4),
                "device": device,
                "baseline_backend": "cpu-native" if baseline else "unavailable",
                "baseline_sets_per_sec": round(baseline, 2) if baseline else None,
                "baseline_rep_spread": round(base_spread, 3),
                "reps": REPS,
                "shapes": {"B": B_PAD, "K": K_PAD, "M": M_PAD,
                           "n_sets": headline["n_sets"]},
                "fp_impl": headline_impl,
                "fp_impl_legs": impl_legs,
                "stage_latency": headline.get("stage_latency", {}),
                "data_movement": data_movement,
                "scheduler_leg": scheduler_leg,
                "planner_leg": planner_leg,
                "pipeline_leg": pipeline_leg,
                "key_table_leg": key_table_leg,
                "replay_leg": replay_leg,
                "capacity_leg": capacity_leg,
                "chaos_leg": chaos_leg,
                "bulk_leg": bulk_leg,
                "epoch_flood_leg": epoch_flood_leg,
                "lookahead_leg": lookahead_leg,
                "watchtower_leg": watchtower_leg,
                "dp_leg": dp_leg,
                "buckets": buckets,
            }
        )
    )


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--dp-leg":
        _dp_leg_main(sys.argv[2:])
    else:
        main()
