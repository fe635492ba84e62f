"""Benchmark of lighthouse_tpu's BLS signature-verification service on
one TPU chip. One command runs one cell of ``BENCHMARK.json`` once:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): JAX and the device, the validator
registry, the device key table synced from it, the cell's pinned compile
rungs (JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``),
the signed traffic, and a warm-up of the cell's own mix. Then the window
drives the service for ``--seconds``; ``--trace 1`` records a profiler
trace of it and reports the per-layer metrics instead of the end-to-end
ones. Every verdict of the window is then compared with the benchmark's
own reference verifier (``ref/bls.py``).

Standard error carries progress and, last, each number compared beside
its limit; the last line of standard output is the result. Without a
TPU the run exits non-zero and prints no result, except for the CPU
rehearsal, asked for with ``JAX_PLATFORMS=cpu`` and ``--validators``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--validators", type=int, default=None,
                    help="registry size; with JAX_PLATFORMS=cpu it asks for "
                    "the CPU rehearsal")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # the compile cache is the checkout's own, whatever the machine sets
    # (the CPU rehearsal keeps none: XLA:CPU entries may not load back)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        # no eviction: the evicting cache's bookkeeping races between the
        # compile service's thread and the main thread, and one lost
        # entry then fails every later write
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path[:0] = [ROOT, HERE]
    from harness import cell as C
    from harness import check, trace as tr

    cell = C.Cell(args.workload)
    device = C.require_device(cell.spec["chips"], args.validators is not None)
    import jax

    dev0 = jax.devices()[0]
    validators = args.validators or int(cell.cfg["validators"])
    threads = max(1, min(16, os.cpu_count() or 1))
    C.log("device", **device, validators=validators)

    verify_fn = _annotated_verify() if args.trace else None
    setup = C.Setup(cell, validators, cell.rungs, threads, verify_fn=verify_fn)
    C.log("compiled", rungs=setup.compiled)
    if args.trace:
        _annotate_layers(setup.svc)
    setup.warm_up(args.seed)
    w = C.window(setup, args.seed, args.seconds, bool(args.trace), dev0)
    # the process's set-up: everything from its start to the window's
    setup_s = w.res.t_start - T_PROCESS
    C.log("setup", setup_s=setup_s, **setup.phases)
    print(json.dumps({"setup": dict(setup.phases, setup_s=setup_s,
                                    rungs=setup.compiled)}), flush=True)
    setup.svc.stop()

    res, subs = w.res, w.subs
    judged, verdicts = subs[:res.due], res.verdicts[:res.due]
    cmp = check.compare(judged, verdicts, args.seed, check.workers())
    fallback = sum(w.delta.hist("verification_scheduler_verdict_latency_seconds",
                                path=p)[0] for p in ("fallback", "shed"))
    failed = int(fallback) + sum(1 for v in verdicts if not isinstance(v, bool))
    window_s = res.t_end - res.t_start
    compiles = C.in_window_compiles(w.delta)
    sets = sum(len(s.specs) for s in judged)
    C.log("window", window_s=window_s, attempted=res.due, failed=failed,
          sets=sets, **compiles, **cmp)
    run = {"window_s": window_s, "submissions": res.due, "sets": sets,
           "setup_s": setup_s}
    result = {"correct": cmp["mismatches"] == 0, "attempted": res.due,
              "failed": failed}
    C.log("routes", **C.routes(w.t_wall))
    reduced = None
    if args.trace and device["platform"] == "tpu":
        reduced = tr.reduce(*tr.load(tr.find_xplane(C.TRACE_DIR)))
    ctx = types.SimpleNamespace(delta=w.delta, trace=reduced, run=run)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = C.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    if reduced is not None:
        result["device"] = dict(device, memory_peak_bytes=w.memory_peak_bytes,
                                busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        C.log("longest idle gaps", gaps=reduced["longest_gaps"])
    else:
        result["device"] = dict(device, memory_peak_bytes=w.memory_peak_bytes)
    result["in_window_compiles"] = compiles
    checks = {"verdict_mismatches": {"value": cmp["mismatches"], "limit": 0}}
    result["checks"] = checks
    for name, c in checks.items():
        C.log(f"check {name} {c['value']} limit {c['limit']} "
              f"(of {cmp['compared']} verdicts)")
    print(json.dumps(result), flush=True)
    return 0


def _annotated_verify():
    """The scheduler's backend call, wrapped in a profiler span (traced
    runs only): pack, dispatch and device stages of one batch."""
    from jax.profiler import TraceAnnotation
    from lighthouse_tpu.crypto import bls

    def verify(sets):
        with TraceAnnotation("perfbench.verify"):
            return bls.verify_signature_sets(sets)

    return verify


def _annotate_layers(svc) -> None:
    """Profiler spans around the harness-built objects' entries into the
    planner, the compile service's routing and the key table (traced
    runs only)."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, attr, span):
        fn = getattr(obj, attr)

        def inner(*a, **k):
            with TraceAnnotation(span):
                return fn(*a, **k)

        setattr(obj, attr, inner)

    wrap(svc.scheduler._planner, "plan", "perfbench.plan")
    wrap(svc.table, "resolve_sets", "perfbench.keys")
    if svc.compile is not None:
        wrap(svc.compile, "decide_flush", "perfbench.route")


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except SystemExit as e:
        print(e, file=sys.stderr)
    except BaseException:
        import traceback

        traceback.print_exc()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        # daemon threads of the program (compile worker, recovery probe)
        # may still hold the interpreter; nothing is left to wait for
        os._exit(code)
