"""Prebake the staged device BLS programs into the persistent compile
cache (ISSUE 5): run the CompileService's ladder walk synchronously so a
node (or bench) started afterwards with the same cache dir warm-starts
with zero fresh XLA staged compiles.

    # list the walk without importing jax or compiling anything
    python tools/warmup.py --dry-run

    # bake the default ladder under the active engine into the cache in
    # force (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
    python tools/warmup.py

    # bake specific rungs, one JSON line at the end
    python tools/warmup.py --rungs 4:1:1,64:16:8 --json

The platform is whatever JAX resolves. Each rung compiles the three
staged programs through the same ``lowering.warm_staged`` path the
in-node service uses, so the executables, the manifest entries and the
recompile accounting all match what the node will look for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_rungs(raw: str):
    rungs = []
    for chunk in raw.split(","):
        chunk = chunk.strip()
        if not chunk:
            continue
        parts = chunk.split(":")
        if len(parts) != 3:
            raise SystemExit(f"malformed rung {chunk!r}; expected B:K:M")
        rungs.append(tuple(int(p) for p in parts))
    if not rungs:
        raise SystemExit("--rungs parsed to an empty plan")
    return tuple(rungs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rungs",
        default=None,
        help="comma list of B:K:M bucket rungs (default: the service's "
        "ladder plan, LIGHTHOUSE_TPU_COMPILE_RUNGS-overridable)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=1,
        help="dp mesh width (ISSUE 11): the walk becomes the mesh "
        "ladder — rung x device, headline rungs first across every "
        "chip (default 1 = the single-device walk). A virtual mesh "
        "needs XLA_FLAGS=--xla_force_host_platform_device_count=N set "
        "before jax initializes",
    )
    ap.add_argument(
        "--dry-run",
        action="store_true",
        help="print the ladder walk in priority order and exit — no jax "
        "import, no compiles",
    )
    ap.add_argument(
        "--json", action="store_true", help="print one summary JSON line"
    )
    args = ap.parse_args(argv)

    # plan construction is deliberately jax-free (service.py imports no
    # jax at module level) so --dry-run stays instant on any host
    from lighthouse_tpu.compile_service import service as csvc_mod
    from lighthouse_tpu.compile_service import cache as cache_mod

    rungs = (
        _parse_rungs(args.rungs)
        if args.rungs
        else (csvc_mod._env_rungs() or csvc_mod.DEFAULT_RUNGS)
    )
    cache_dir = cache_mod.cache_dir()

    if args.devices <= 0:
        raise SystemExit("--devices must be positive")

    if args.dry_run:
        if args.devices > 1:
            # the mesh ladder (ISSUE 11): headline rungs first ACROSS
            # devices — every chip gets the big warm rung before any
            # chip gets the next one (same order CompileService.start
            # enqueues with a mesh attached)
            print(
                f"mesh ladder walk ({len(rungs)} rungs x "
                f"{args.devices} devices, priority order):"
            )
            i = 0
            for b, k, m in rungs:
                for dev in range(args.devices):
                    i += 1
                    print(f"  {i}. B={b} K={k} M={m} dev={dev}")
        else:
            print(f"ladder walk ({len(rungs)} rungs, priority order):")
            for i, (b, k, m) in enumerate(rungs):
                print(f"  {i + 1}. B={b} K={k} M={m}")
        # gathered variants (ISSUE 10): with a device key table attached
        # the service also warms the "gather" program per (B, K) —
        # capacity-keyed, sub-second, warmed in-node (never prebaked:
        # the gather is compiled against the LIVE table's capacity rung,
        # which a CLI bake cannot know). Listed so the prebake story
        # stays honest about what a warm start does NOT cover.
        gather_rungs = sorted({(b, k) for (b, k, _m) in rungs})
        print(
            f"gathered rungs (device key-table gather, warmed in-node "
            f"when a table is attached; {len(gather_rungs)} programs per "
            f"capacity rung):"
        )
        for b, k in gather_rungs:
            print(f"  gather B={b} K={k}")
        # MSM ladder (ISSUE 16): opt-in (ClientConfig.device_msm), warmed
        # in-node alongside the first staged rung per (impl, device).
        # Keyed on the point axis only — never perturbs the staged
        # shapes above. Each rung warms BOTH programs of the pair (G1
        # windowed MSM + G2 point-sum).
        print(
            f"msm rungs (device aggregation MSM/G2-sum pair, warmed "
            f"in-node when device_msm is enabled; "
            f"{len(csvc_mod.MSM_RUNGS)} rungs x 2 programs):"
        )
        for n in csvc_mod.MSM_RUNGS:
            print(f"  msm N={n}")
        print(f"cache_dir: {cache_dir}")
        return 0

    manifest = None
    cache_status = cache_mod.enable_persistent_cache()
    if cache_status["enabled"]:
        manifest = cache_mod.Manifest(cache_dir)
    else:
        # no manifest over a dead cache: a prebaked claim with no
        # executables behind it would falsify warm-start reporting
        print(
            f"persistent cache off ({cache_status['reason']}); "
            f"warming this process only",
            file=sys.stderr,
        )

    from lighthouse_tpu.compile_service import lowering
    from lighthouse_tpu.crypto.device import fp

    mesh = None
    if args.devices > 1:
        # a real mesh: the warm_staged shard scope commits the dummy
        # args (and so the compile) to each chip in turn
        from lighthouse_tpu.crypto.device import mesh as mesh_mod

        mesh = mesh_mod.DeviceMesh(n_devices=args.devices)
        mesh_mod.set_mesh(mesh)

    impl = fp.get_impl()
    env_key = cache_mod.environment_key(impl)
    records = []
    t_total = time.perf_counter()
    for b, k, m in rungs:
        for dev in range(args.devices):
            prebaked = bool(
                manifest is not None
                and all(
                    manifest.has(
                        cache_mod.manifest_key(env_key, s, b, k, m, device=dev)
                    )
                    for s in lowering.STAGES
                )
            )
            files_before = (
                cache_mod.executable_entries(cache_dir)
                if manifest is not None
                else None
            )
            t0 = time.perf_counter()
            stages = lowering.warm_staged(
                b, k, m, shard=dev if mesh is not None else None
            )
            seconds = time.perf_counter() - t0
            if manifest is not None:
                # manifest honesty (same probe as
                # CompileService._compile_rung): a fresh compile that
                # left no new executable behind must not claim the rung
                # prebaked — unless it already was (a cache-served warm
                # restart adds no files)
                persisted = cache_mod.persisted_after(
                    cache_dir,
                    files_before,
                    any(r["fresh"] for r in stages.values()),
                )
                if persisted or prebaked:
                    manifest.add_many(
                        [
                            cache_mod.manifest_key(
                                env_key, stage, b, k, m, device=dev
                            )
                            for stage in lowering.STAGES
                        ],
                        source="warmup_cli",
                    )
                else:
                    print(
                        f"cache stored no executable for B={b} K={k} "
                        f"M={m} dev={dev}; manifest NOT updated",
                        file=sys.stderr,
                    )
            rec = {
                "b": b, "k": k, "m": m, "fp_impl": impl,
                "seconds": round(seconds, 2),
                "manifest_prebaked": prebaked,
                "stages": {
                    s: {"seconds": round(r["seconds"], 2), "fresh": r["fresh"]}
                    for s, r in stages.items()
                },
            }
            if args.devices > 1:
                rec["device"] = dev
            records.append(rec)
            dev_tag = f" dev={dev}" if args.devices > 1 else ""
            print(
                f"warmed B={b} K={k} M={m}{dev_tag} [{impl}] in "
                f"{seconds:7.2f}s"
                f"{' (manifest: prebaked)' if prebaked else ''}",
                flush=True,
            )
    if mesh is not None:
        from lighthouse_tpu.crypto.device import mesh as mesh_mod

        mesh_mod.clear_mesh(mesh)
    summary = {
        "fp_impl": impl,
        "devices": args.devices,
        "total_s": round(time.perf_counter() - t_total, 2),
        "cache": cache_status,
        "rungs": records,
    }
    if args.json:
        print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
