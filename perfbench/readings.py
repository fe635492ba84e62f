"""The readings behind the limit of ``correct``: several windows of one
cell in one process, set up once, one per seed. For each window it
prints one JSON line with the program's mismatches against the
reference (the lower reading) and, with ``--control 1``, the control's
mismatches on the same blocks (the upper reading: the reference with
every scalar 1, put in the program's place). Not run by the benchmark
itself.

    python3 perfbench/readings.py --workload block-1m.sync --seconds 20 \\
        --seeds 11,12,13 --control 1
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--validators", type=int, default=None)
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path[:0] = [ROOT, HERE]
    from harness import cell as C
    from harness import check

    cell = C.Cell(args.workload)
    device = C.require_device(cell.spec["chips"], args.validators is not None)
    import jax

    validators = args.validators or int(cell.cfg["validators"])
    threads = max(1, min(16, os.cpu_count() or 1))
    setup = C.Setup(cell, validators, cell.rungs, threads)
    seeds = [int(s) for s in args.seeds.split(",")]
    C.log("set up", device=device, phases=setup.phases, rungs=setup.compiled)
    setup.warm_up(seeds[0])
    for seed in seeds:
        w = C.window(setup, seed, args.seconds, False, jax.devices()[0])
        res = w.res
        t = time.perf_counter()
        cmp = check.compare(w.subs[:res.due], res.verdicts[:res.due], seed,
                            check.workers(), control=bool(args.control))
        print(json.dumps({
            "workload": args.workload, "seed": seed, "attempted": res.due,
            "window_s": res.t_end - res.t_start,
            "block_ms": 1e3 * (res.t_end - res.t_start) / max(1, res.due),
            **cmp,
            "fallback": sum(w.delta.hist(
                "verification_scheduler_verdict_latency_seconds", path=p)[0]
                for p in ("fallback", "shed")),
            "cold_routes": w.delta.count("compile_service_cold_routes_total"),
            **C.in_window_compiles(w.delta),
        }), flush=True)
        C.log("window done", seed=seed, compare_s=time.perf_counter() - t)
    setup.svc.stop()
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)
