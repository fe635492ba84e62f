"""Kernel-family microbench: achieved MAC/s (or point-adds/s) per kernel.

Grown from the original fp.mul bench (VERDICT r5 rec #2) into the
ISSUE 16 kernel-surface families:

* ``fp``   — the base fp.mul engines (int32 Toeplitz dot vs int8 MXU
  decomposition vs the Pallas tile): a jitted ``lax.scan`` chain of
  DEPTH dependent batched products over N lanes, so dispatch overhead
  amortizes and XLA cannot dead-code the work. MAC/s counts the
  schoolbook contraction only (NCOLS x NL = 2016 MACs per lane per
  step).
* ``fp2``  — fp2.mul / fp2.sq under both fp2 engines (``composed`` XLA
  vs the ``fused_pallas`` Karatsuba tile); 3x resp. 2x the base
  contraction per lane-step.
* ``line`` — the Miller-loop doubling line-eval step under both line
  engines (dependency-levelled ``fused`` vs ``composed``); MAC/s uses
  the step's fp-lane count (31 fp products/lane-step).
* ``msm``  — the windowed G1 MSM at committee-sized N; point-adds/s
  counts the dominant masked bucket-reduction lanes
  (N x N_WINDOWS x N_BUCKETS group additions).

Every family pins cross-engine byte-identity of the canonical outputs
(sha256 digest) before reporting a ratio — a fast wrong kernel must
fail the bench, not win it.

Prints ONE JSON line and writes ``BENCH_FP_MUL.json`` (the fp family,
backward compatible) plus ``BENCH_KERNELS.json`` (all families) at the
repo root; ``tools/cost_model.py`` folds both artifacts into the
measured-constants table of docs/COST_MODEL.md.

Usage: python benches/bench_fp_mul.py [--n 4096] [--depth 16] [--reps 5]
       [--impls toeplitz_int32,matmul_int8,pallas_int8]
       [--families fp,fp2,line,msm] [--fp2-n 512] [--msm-n 512]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _measure_impl(name: str, n: int, depth: int, reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from lighthouse_tpu.crypto.device import fp
    from lighthouse_tpu.crypto import device

    fp.set_impl(name)
    device.reset_compiled_state()  # impl dispatch is trace-time; drop stale kernels

    rng = np.random.default_rng(0xF9)
    x = jnp.asarray(rng.integers(0, fp.MASK + 1, (n, fp.NL), dtype=np.int32))
    y = jnp.asarray(rng.integers(0, fp.MASK + 1, (n, fp.NL), dtype=np.int32))

    @jax.jit
    def chain(a, b):
        def body(acc, _):
            return fp.mul(acc, b), None

        out, _ = lax.scan(body, a, None, length=depth)
        return out

    t0 = time.perf_counter()
    ref = jax.block_until_ready(chain(x, y))
    compile_s = time.perf_counter() - t0

    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, y))
        samples.append(time.perf_counter() - t0)
    med = statistics.median(samples)
    spread = (max(samples) - min(samples)) / med if med else 0.0

    macs = n * depth * fp.NCOLS * fp.NL
    # cross-impl correctness pin: the FULL canonical output must agree
    # bit-for-bit across engines (checked by the caller via this digest;
    # a bytes hash, so compensating differences cannot cancel)
    import hashlib

    digest = hashlib.sha256(
        np.ascontiguousarray(np.asarray(fp.canonical(ref))).tobytes()
    ).hexdigest()
    return {
        "impl": name,
        "mac_per_sec": macs / med,
        "step_s": med,
        "rep_spread": round(spread, 3),
        "compile_s": round(compile_s, 2),
        "digest": digest,
    }


def _digest(arr) -> str:
    import hashlib

    import numpy as np

    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(arr)).tobytes()
    ).hexdigest()


def _time_chain(chain, args, reps: int) -> dict:
    """Shared clock body: one compile dispatch, then ``reps`` timed
    dispatches; returns the first output + median/spread/compile_s."""
    import jax

    t0 = time.perf_counter()
    ref = jax.block_until_ready(chain(*args))
    compile_s = time.perf_counter() - t0
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(*args))
        samples.append(time.perf_counter() - t0)
    med = statistics.median(samples)
    spread = (max(samples) - min(samples)) / med if med else 0.0
    return {
        "ref": ref,
        "step_s": med,
        "rep_spread": round(spread, 3),
        "compile_s": round(compile_s, 2),
    }


def _measure_fp2(kind: str, impl: str, n: int, depth: int, reps: int) -> dict:
    """fp2.mul / fp2.sq chain under fp2 engine ``impl``."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax import lax

    from lighthouse_tpu.crypto import device
    from lighthouse_tpu.crypto.device import fp, fp2

    fp2.set_impl(impl)
    device.reset_compiled_state()

    rng = np.random.default_rng(0xF2)
    x = jnp.asarray(rng.integers(0, fp.MASK + 1, (n, 2, fp.NL), dtype=np.int32))
    y = jnp.asarray(rng.integers(0, fp.MASK + 1, (n, 2, fp.NL), dtype=np.int32))

    @jax.jit
    def chain(a, b):
        def body(acc, _):
            out = fp2.mul(acc, b) if kind == "mul" else fp2.sq(acc)
            return out, None

        out, _ = lax.scan(body, a, None, length=depth)
        return out

    rec = _time_chain(chain, (x, y), reps)
    # fp lanes per fp2 lane-step: Karatsuba mul = 3, squaring = 2
    lanes = 3 if kind == "mul" else 2
    macs = n * depth * lanes * fp.NCOLS * fp.NL
    return {
        "impl": impl,
        "mac_per_sec": macs / rec["step_s"],
        "step_s": rec["step_s"],
        "rep_spread": rec["rep_spread"],
        "compile_s": rec["compile_s"],
        "digest": _digest(fp2.canonical(rec["ref"])),
    }


# fp products per Miller-loop doubling line-eval step (one batch lane):
# 6 fp2 squarings x2 + 5 fp2 products x3 + 2 fp-scalar scalings x2.
LINE_DBL_FP_LANES = 31


def _measure_line(impl: str, n: int, depth: int, reps: int) -> dict:
    """Miller-loop doubling line-eval chain under line engine ``impl``."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax import lax

    from lighthouse_tpu.crypto import device
    from lighthouse_tpu.crypto.device import fp, fp2, pairing

    pairing.set_line_impl(impl)
    device.reset_compiled_state()

    rng = np.random.default_rng(0x71)

    def rnd(shape):
        return jnp.asarray(
            rng.integers(0, fp.MASK + 1, (*shape, fp.NL), dtype=np.int32)
        )

    T0 = (rnd((n, 2)), rnd((n, 2)), rnd((n, 2)))
    xP, yP = rnd((n,)), rnd((n,))

    @jax.jit
    def chain(X, Y, Z, xp, yp):
        def body(T, _):
            Tn, _s0, _sv, _sv2 = pairing._dbl_step(T, xp, yp)
            return Tn, None

        T, _ = lax.scan(body, (X, Y, Z), None, length=depth)
        return T[0]

    rec = _time_chain(chain, (*T0, xP, yP), reps)
    macs = n * depth * LINE_DBL_FP_LANES * fp.NCOLS * fp.NL
    return {
        "impl": impl,
        "mac_per_sec": macs / rec["step_s"],
        "step_s": rec["step_s"],
        "rep_spread": rec["rep_spread"],
        "compile_s": rec["compile_s"],
        "digest": _digest(fp2.canonical(rec["ref"])),
    }


def _measure_msm(n: int, reps: int) -> dict:
    """Windowed G1 MSM at committee-sized N: point-adds/s over the
    masked bucket-reduction lanes (the dominant term)."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.device import bls as dbls
    from lighthouse_tpu.crypto.device import curve, msm

    rng = np.random.default_rng(0x3A)
    from lighthouse_tpu.crypto.cpu.curve import g1_generator

    # successive generator multiples (cheap host adds, no host MSM)
    pts, p = [], g1_generator()
    for _ in range(n):
        pts.append(p)
        p = p + g1_generator()
    xy, inf = curve.pack_g1(pts)
    sw = np.zeros((n, 2), np.int32)
    for i in range(n):
        s = int.from_bytes(rng.bytes(8), "big")
        sw[i] = np.array(
            [(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], np.uint32
        ).view(np.int32)

    chain = jax.jit(msm.msm_g1_fn)
    rec = _time_chain(
        chain, (jnp.asarray(xy), jnp.asarray(inf), jnp.asarray(sw)), reps
    )
    adds = n * msm.N_WINDOWS * msm.N_BUCKETS
    oxy, oinf = rec["ref"]
    return {
        "impl": "windowed_g1",
        "point_adds_per_sec": adds / rec["step_s"],
        "step_s": rec["step_s"],
        "rep_spread": rec["rep_spread"],
        "compile_s": rec["compile_s"],
        "digest": _digest(np.concatenate(
            [np.asarray(oxy).ravel(), np.asarray(oinf).ravel().astype(np.int32)]
        )),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--depth", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument(
        "--impls", default="toeplitz_int32,matmul_int8",
        help="comma list; pallas_int8 is opt-in (interpret mode off-TPU "
             "is a semantics check, not a speed measurement)",
    )
    ap.add_argument(
        "--families", default="fp,fp2,line,msm",
        help="comma list of kernel families to measure (fp, fp2, line, "
             "msm). The fused_pallas fp2 engine runs in interpreter "
             "mode off-TPU: a semantics check, not a speed measurement.",
    )
    ap.add_argument("--fp2-n", type=int, default=512)
    ap.add_argument("--fp2-depth", type=int, default=8)
    ap.add_argument("--line-n", type=int, default=256)
    ap.add_argument("--line-depth", type=int, default=4)
    ap.add_argument("--msm-n", type=int, default=512)
    ap.add_argument("--msm-reps", type=int, default=3)
    args = ap.parse_args()

    # Default to the CPU mesh unless a platform was explicitly requested:
    # this bench must always print a line, even on hosts without a chip.
    if "JAX_PLATFORMS" not in os.environ:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    try:
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    except Exception:
        pass

    from lighthouse_tpu.crypto.device import fp, fp2, pairing
    from lighthouse_tpu.crypto import device

    families = [f.strip() for f in args.families.split(",") if f.strip()]
    kernels: dict = {}

    def _rows_entry(rows, rate_key):
        return {
            r["impl"]: {
                rate_key: round(r[rate_key], 1),
                "step_s": round(r["step_s"], 5),
                "rep_spread": r["rep_spread"],
                "compile_s": r["compile_s"],
            }
            for r in rows
        }

    prev = fp.get_impl()
    prev_fp2 = fp2.get_impl()
    prev_line = pairing.get_line_impl()
    out = None
    try:
        if "fp" in families:
            rows = []
            for name in args.impls.split(","):
                rows.append(
                    _measure_impl(name.strip(), args.n, args.depth, args.reps)
                )
            digests = {r["digest"] for r in rows}
            assert len(digests) == 1, (
                f"impls disagree on canonical output: {rows}"
            )

            by_name = {r["impl"]: r for r in rows}
            ratio = None
            if "toeplitz_int32" in by_name and "matmul_int8" in by_name:
                ratio = (
                    by_name["matmul_int8"]["mac_per_sec"]
                    / by_name["toeplitz_int32"]["mac_per_sec"]
                )

            out = {
                "metric": "fp_mul_achieved_mac_per_sec",
                "backend": jax.devices()[0].platform,
                "n_lanes": args.n,
                "depth": args.depth,
                "reps": args.reps,
                "macs_per_lane": fp.NCOLS * fp.NL,
                "split_shift": fp.SPLIT_SHIFT,
                "impls": _rows_entry(rows, "mac_per_sec"),
                "matmul_int8_vs_toeplitz_int32": (
                    round(ratio, 3) if ratio else None
                ),
            }
            (REPO / "BENCH_FP_MUL.json").write_text(
                json.dumps(out, indent=1) + "\n"
            )
            print(json.dumps(out))
            kernels["fp_mul"] = {
                "n": args.n, "depth": args.depth,
                "impls": _rows_entry(rows, "mac_per_sec"),
            }

        if "fp2" in families:
            for kind in ("mul", "sq"):
                rows = [
                    _measure_fp2(kind, impl, args.fp2_n, args.fp2_depth,
                                 args.reps)
                    for impl in (fp2.IMPL_COMPOSED, fp2.IMPL_FUSED_PALLAS)
                ]
                assert len({r["digest"] for r in rows}) == 1, (
                    f"fp2 {kind} engines disagree: {rows}"
                )
                kernels[f"fp2_{kind}"] = {
                    "n": args.fp2_n, "depth": args.fp2_depth,
                    "impls": _rows_entry(rows, "mac_per_sec"),
                }

        if "line" in families:
            rows = [
                _measure_line(impl, args.line_n, args.line_depth, args.reps)
                for impl in (
                    pairing.IMPL_LINE_COMPOSED, pairing.IMPL_LINE_FUSED
                )
            ]
            assert len({r["digest"] for r in rows}) == 1, (
                f"line engines disagree: {rows}"
            )
            kernels["line_dbl"] = {
                "n": args.line_n, "depth": args.line_depth,
                "fp_lanes_per_step": LINE_DBL_FP_LANES,
                "impls": _rows_entry(rows, "mac_per_sec"),
            }

        if "msm" in families:
            rows = [_measure_msm(args.msm_n, args.msm_reps)]
            kernels["msm_g1"] = {
                "n": args.msm_n,
                "impls": _rows_entry(rows, "point_adds_per_sec"),
            }
    finally:
        fp.set_impl(prev)
        fp2.set_impl(prev_fp2)
        pairing.set_line_impl(prev_line)
        device.reset_compiled_state()

    if kernels:
        kout = {
            "metric": "kernel_family_rates",
            "backend": jax.devices()[0].platform,
            "fp_impl": prev,
            "reps": args.reps,
            "kernels": kernels,
        }
        (REPO / "BENCH_KERNELS.json").write_text(
            json.dumps(kout, indent=1) + "\n"
        )
        print(json.dumps(kout))


if __name__ == "__main__":
    main()
