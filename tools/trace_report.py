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

The staged-verify mode also records a ``jax.profiler`` trace of its
run into ``--profile-dir`` (``create_perfetto_trace=True``): the real
device operations, named by the programs' scopes (``bls.stage3`` /
``miller_loop`` ...), on one clock with the program's spans, which the
profiler records beside its own (``utils/tracing.py``). Open its
``perfetto_trace.json.gz`` at https://ui.perfetto.dev, or the directory
with TensorBoard's profile plugin.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def write_chrome_trace(out_path: str) -> int:
    """Export the recorded spans to ``out_path``; returns the event
    count."""
    from lighthouse_tpu.utils import tracing

    return tracing.export_chrome(out_path)


def find_perfetto_trace(profile_dir: str):
    """The ``perfetto_trace.json.gz`` a profiler session wrote under
    ``profile_dir`` (None if it wrote none)."""
    import glob

    found = sorted(glob.glob(
        os.path.join(profile_dir, "**", "perfetto_trace.json.gz"),
        recursive=True,
    ))
    return found[-1] if found else None


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
    n = write_chrome_trace(args.out)
    print(
        json.dumps(
            {
                "trace": args.out,
                "events": n,
                "dropped": tracing.dropped(),
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
    ap.add_argument("--profile-dir", default=None,
                    help="jax.profiler trace directory of the staged "
                    "verify run (default: <out>.profile)")
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

    import jax

    from lighthouse_tpu.crypto.device.bls import TpuBackend

    profile_dir = args.profile_dir or os.path.splitext(args.out)[0] + ".profile"
    sets = build_sets(args.sets, args.committee, args.msgs)
    backend = TpuBackend()
    with jax.profiler.trace(profile_dir, create_perfetto_trace=True):
        with tracing.span("trace_report.run", reps=args.reps):
            for rep in range(args.reps):
                with tracing.span("trace_report.rep", rep=rep):
                    ok = backend.verify_signature_sets(sets)
    assert ok is True, "trace workload must verify"

    n = write_chrome_trace(args.out)
    print(
        json.dumps(
            {
                "trace": args.out,
                "events": n,
                "dropped": tracing.dropped(),
                "profile_dir": profile_dir,
                "perfetto_trace": find_perfetto_trace(profile_dir),
                "verdict": bool(ok),
                "stage_latency": stage_quantile_summary(),
            }
        )
    )


if __name__ == "__main__":
    main()
