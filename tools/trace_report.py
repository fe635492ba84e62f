"""Chrome-trace report for the staged device BLS verifier.

Runs one (or more) staged ``verify_signature_sets`` batches with the
span subsystem enabled and writes a chrome://tracing JSON — open it at
``chrome://tracing`` or https://ui.perfetto.dev to see where the
gossip-to-verdict wall-clock goes (pack vs stage1/2/3 dispatch+sync vs
verdict host-sync), per thread. Also prints the per-stage p50/p99 from
the ``bls_device_stage_seconds`` histogram family so the trace and the
scrape can be cross-checked.

Usage (``--cpu`` or ``JAX_PLATFORMS=cpu`` runs it on the CPU; without
them it needs the chip):

    JAX_PLATFORMS=cpu python tools/trace_report.py -o /tmp/bls_trace.json
    python tools/trace_report.py --cpu --sets 6 --committee 4 --reps 2

The first rep includes jit compile (visible as the long stage spans);
pass ``--reps 2`` to also capture warm-cache dispatches.

``--replay <trace.jsonl>`` switches the workload to a TRAFFIC REPLAY
(ISSUE 7, docs/TRAFFIC_REPLAY.md): the arrival trace is replayed
against a live verification scheduler under tracing (stub backend —
the scheduling layer is the subject, no jax needed), so the chrome
trace shows every ``scheduler.flush`` / ``scheduler.sub_batch`` /
``scheduler.bypass`` / ``scheduler.shed_fallback`` span over the
arrival timeline, and the printed summary carries the per-kind SLO
report instead of stage quantiles:

    python tools/trace_report.py --replay /tmp/flood.jsonl \\
        --time-scale 0.5 -o /tmp/replay_trace.json

Both modes add PER-SHARD DEVICE LANES (ISSUE 12): device-stage spans
(or, for stub replays, ``scheduler.sub_batch`` spans) are mirrored onto
one synthetic timeline lane per dp shard, with the idle gaps between
them drawn as explicit ``bubble:<cause>`` slices — the chrome view of
the pipeline profiler's ``bls_device_bubble_seconds_total`` counters
(docs/OBSERVABILITY.md, pipeline section).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---------------------------------------------------------------------------
# Per-shard device lanes (ISSUE 12): before the pipeline profiler, every
# flush/sub_batch/stage span rendered on its host THREAD's timeline — a
# 2-chip replay read as one interleaved lane and a device idle gap was
# invisible. These helpers group the device-side spans by their `shard`
# attribute onto synthetic per-shard lanes and draw the gaps between
# consecutive device spans as explicit `bubble:<cause>` slices, so the
# chrome view shows occupancy per chip at a glance. The cause label is a
# trace-local approximation (dominant overlap with host pack /
# compile-fallback spans); the exact attribution is the profiler's
# `bls_device_bubble_seconds_total{shard,cause}` counters.
# ---------------------------------------------------------------------------

LANE_TID_BASE = 1 << 20  # synthetic tids: far above real thread ids
DEVICE_STAGE_NAMES = ("bls.gather", "bls.stage1", "bls.stage2", "bls.stage3")
MIN_BUBBLE_US = 20.0


def _dominant_cause(g0: float, g1: float, causes) -> str:
    """Largest-overlap host activity inside the gap [g0, g1] µs, or
    ``other`` when nothing overlaps — the same name the profiler's
    cause catalogue gives the uncovered remainder, so the trace slices
    and the counters speak one vocabulary (trace-local label; the
    profiler counters are the exact attribution)."""
    best, best_overlap = "other", 0.0
    acc: dict = {}
    for cause, a0, a1 in causes:
        ov = min(a1, g1) - max(a0, g0)
        if ov > 0:
            acc[cause] = acc.get(cause, 0.0) + ov
    for cause, ov in acc.items():
        if ov > best_overlap:
            best, best_overlap = cause, ov
    return best


def add_device_lanes(trace: dict, min_bubble_us: float = MIN_BUBBLE_US) -> dict:
    """Augment a chrome trace IN PLACE with per-shard device lanes:
    device-stage spans (``bls.stage*``/``bls.gather``; falls back to
    ``scheduler.sub_batch`` for stub replays that never reach a device)
    are mirrored onto one synthetic lane per shard, and the gaps
    between consecutive spans on a lane become ``bubble:<cause>``
    slices. Returns {lanes, bubbles, source}."""
    evs = trace["traceEvents"]
    stage = [
        e for e in evs
        if e.get("ph") == "X" and e.get("name") in DEVICE_STAGE_NAMES
    ]
    source = "device_stage"
    if not stage:
        stage = [
            e for e in evs
            if e.get("ph") == "X" and e.get("name") == "scheduler.sub_batch"
        ]
        source = "sub_batch"
    lanes: dict = {}
    for e in stage:
        shard = e.get("args", {}).get("shard")
        shard = 0 if shard in (None, "None") else int(shard)
        lanes.setdefault(shard, []).append(e)
    causes = []
    for e in evs:
        if e.get("ph") != "X":
            continue
        if e.get("name") == "bls.pack":
            causes.append(("pack", e["ts"], e["ts"] + e["dur"]))
        elif e.get("name") == "compile_service.fallback_verify":
            causes.append(("compile", e["ts"], e["ts"] + e["dur"]))
    new = []
    n_bubbles = 0
    for shard, sevs in sorted(lanes.items()):
        tid = LANE_TID_BASE + shard
        pid = sevs[0]["pid"]
        new.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": f"device shard {shard}"},
        })
        sevs.sort(key=lambda e: e["ts"])
        last_end = None
        for e in sevs:
            if last_end is not None and e["ts"] - last_end > min_bubble_us:
                cause = _dominant_cause(last_end, e["ts"], causes)
                new.append({
                    "name": f"bubble:{cause}", "ph": "X",
                    "ts": round(last_end, 3),
                    "dur": round(e["ts"] - last_end, 3),
                    "pid": pid, "tid": tid,
                    "args": {"shard": shard, "cause": cause},
                })
                n_bubbles += 1
            lane_ev = dict(e)
            lane_ev["tid"] = tid
            new.append(lane_ev)
            end = e["ts"] + e["dur"]
            last_end = end if last_end is None else max(last_end, end)
    trace["traceEvents"] = evs + new
    return {"lanes": len(lanes), "bubbles": n_bubbles, "source": source}


def write_trace_with_lanes(out_path: str) -> tuple:
    """Export the recorded spans + per-shard device lanes to
    ``out_path``; returns (event count, lane info)."""
    from lighthouse_tpu.utils import tracing

    trace = tracing.chrome_trace()
    lane_info = add_device_lanes(trace)
    with open(out_path, "w") as f:
        json.dump(trace, f)
    return len(trace["traceEvents"]), lane_info


def build_sets(n_sets: int, committee: int, n_msgs: int):
    """Small raw workload: ``(lazy compressed Signature, [pk points],
    message)`` triples, the shape ``TpuBackend.verify_signature_sets``
    routes to the staged device program."""
    from lighthouse_tpu.crypto import bls

    sks = [bls.SecretKey(7_000 + i) for i in range(committee)]
    pks = [sk.public_key().point for sk in sks]
    msgs = [bytes([m + 1]) * 32 for m in range(n_msgs)]
    sets = []
    for i in range(n_sets):
        m = msgs[i % n_msgs]
        agg = bls.AggregateSignature.infinity()
        for sk in sks:
            agg.add_assign(sk.sign(m))
        sets.append(
            (bls.Signature.deserialize(agg.serialize()), list(pks), m)
        )
    return sets


def stage_quantile_summary() -> dict:
    """{stage: {fp_impl, p50_s, p99_s, mean_s, count}} from the metric
    family the verifier populates (docs/OBSERVABILITY.md)."""
    from lighthouse_tpu.crypto.device.bls import stage_latency_summary

    return stage_latency_summary()


def replay_main(args) -> None:
    """--replay mode: arrival-trace replay under tracing — the chrome
    view of a whole replay run (scheduler flush/sub-batch/bypass/shed
    spans on the arrival timeline) plus the per-kind SLO summary."""
    from lighthouse_tpu.utils import tracing
    from lighthouse_tpu.verification_service import traffic

    import tools.traffic_replay as traffic_replay

    header, events = traffic.read_trace(args.replay)
    tracing.enable()
    tracing.clear()
    verify_fn, backend_name, set_factory = traffic_replay.resolve_verify(
        args.verify
    )
    report = traffic_replay.run_timed_replay(
        events,
        verify_fn=verify_fn,
        set_factory=set_factory,
        deadline_ms=args.deadline_ms,
        time_scale=args.time_scale,
    )
    n, lane_info = write_trace_with_lanes(args.out)
    print(
        json.dumps(
            {
                "trace": args.out,
                "events": n,
                "dropped": tracing.dropped(),
                "device_lanes": lane_info,
                "replayed": {
                    "trace_file": args.replay,
                    "name": header.get("name"),
                    "n_events": len(events),
                    "verify_backend": backend_name,
                    "wall_s": report["wall_s"],
                },
                "slo": report["slo"],
            }
        )
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-o", "--out", default="/tmp/bls_trace.json",
                    help="chrome trace output path")
    ap.add_argument("--sets", type=int, default=4)
    ap.add_argument("--committee", type=int, default=2)
    ap.add_argument("--msgs", type=int, default=2)
    ap.add_argument("--reps", type=int, default=1,
                    help="verify repetitions (first includes compile)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin JAX_PLATFORMS=cpu before importing jax")
    ap.add_argument("--replay", default=None, metavar="TRACE",
                    help="chrome-trace a traffic replay of this arrival "
                    "trace instead of the staged verify workload")
    ap.add_argument("--verify", default="stub:0.0005",
                    help="replay backend (--replay only; see "
                    "tools/traffic_replay.py)")
    ap.add_argument("--deadline-ms", type=float, default=25.0,
                    help="replay scheduler deadline (--replay only)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="replay arrival-time multiplier (--replay only)")
    args = ap.parse_args(argv)
    if args.reps < 1:
        ap.error("--reps must be >= 1")

    if args.cpu:
        # BEFORE the replay dispatch: --replay --verify device must
        # honour the platform pin exactly like the staged workload does
        os.environ["JAX_PLATFORMS"] = "cpu"

    if args.replay:
        replay_main(args)
        return

    from lighthouse_tpu.utils import tracing

    tracing.enable()
    tracing.clear()

    from lighthouse_tpu.crypto.device.bls import TpuBackend

    sets = build_sets(args.sets, args.committee, args.msgs)
    backend = TpuBackend()
    with tracing.span("trace_report.run", reps=args.reps):
        for rep in range(args.reps):
            with tracing.span("trace_report.rep", rep=rep):
                ok = backend.verify_signature_sets(sets)
    assert ok is True, "trace workload must verify"

    n, lane_info = write_trace_with_lanes(args.out)
    print(
        json.dumps(
            {
                "trace": args.out,
                "events": n,
                "dropped": tracing.dropped(),
                "device_lanes": lane_info,
                "verdict": bool(ok),
                "stage_latency": stage_quantile_summary(),
            }
        )
    )


if __name__ == "__main__":
    main()
