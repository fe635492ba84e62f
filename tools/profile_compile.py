"""Per-stage XLA compile-time profile of the device BLS program (CPU
backend, small shapes). Identifies which stage dominates the minutes-long
compile (VERDICT r2 missing #2). Run ALONE — one XLA process at a time.

Usage: JAX_PLATFORMS=cpu python tools/profile_compile.py [B] [K] [M]
"""

import os
import sys

# the CPU compile profile: pinned before JAX initializes a backend
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from lighthouse_tpu.compile_service.lowering import timed_lower_compile
from lighthouse_tpu.crypto.device import bls as dbls
from lighthouse_tpu.crypto.device import curve, fp, fp2, htc, pairing, tower

B = int(sys.argv[1]) if len(sys.argv) > 1 else 16
K = int(sys.argv[2]) if len(sys.argv) > 2 else 4
M = int(sys.argv[3]) if len(sys.argv) > 3 else 4


def clock(name, fn, *args):
    # one shared lower+compile clock (compile_service/lowering.py) so
    # this profile times exactly what the compile service compiles
    rec = timed_lower_compile(fn, args)
    print(
        f"{name:32s} lower {rec['lower_s']:7.2f}s  "
        f"compile {rec['compile_s']:7.2f}s  hlo_lines {rec['hlo_lines']}",
        flush=True,
    )
    return rec


g1 = jnp.zeros((B, 2, fp.NL), jnp.int32)
g1k = jnp.zeros((B, K, 2, fp.NL), jnp.int32)
g2 = jnp.zeros((B, 2, 2, fp.NL), jnp.int32)
f12 = jnp.zeros((B, 2, 3, 2, fp.NL), jnp.int32)
bits = jnp.zeros((B, 64), jnp.int32)
mask = jnp.zeros((B,), bool)
u = jnp.zeros((M, 2, 2, fp.NL), jnp.int32)

clock("fp.mul", fp.mul, g1[:, 0], g1[:, 1])
clock("fp.inv", fp.inv, g1[:, 0])
clock("fp2.sqrt(htc)", htc.sqrt, g2[:, 0])
clock(
    "decompress_g2",
    lambda x, s: dbls.decompress_g2(x, s),
    g2[:, 0],
    mask,
)
clock("map_to_g2", htc.map_to_g2, u)
clock(
    "g2_subgroup",
    lambda p: dbls.g2_in_subgroup(curve.from_affine(fp2, p[:, 0], p[:, 1])),
    g2,
)
clock(
    "scalar_mul_bits_g1",
    lambda p, b: curve.scalar_mul_bits(
        fp, curve.from_affine(fp, p[:, 0], p[:, 1]), b
    ),
    g1,
    jnp.zeros((B, 64), jnp.int32),
)
clock(
    "sum_points_g1_K",
    lambda p: curve.sum_points(
        fp, curve.from_affine(fp, p[..., 0, :], p[..., 1, :]), axis=1
    ),
    g1k,
)
clock(
    "miller_loop",
    lambda a, b: pairing.miller_loop(
        (a[:, 0], a[:, 1], jnp.zeros((B,), bool)),
        (b[:, 0], b[:, 1], jnp.zeros((B,), bool)),
    ),
    g1,
    g2,
)
clock("tree_reduce_f12", lambda f: curve.tree_reduce(f, 0, tower.mul, tower.ones()), f12)
clock("final_exp_is_one", pairing.final_exp_is_one, f12[0:1].squeeze(0), )
clock(
    "verify_batch_raw (FULL)",
    dbls.verify_batch_raw_fn,
    g1k,
    jnp.zeros((B, K), bool),
    g2[:, 0],
    mask,
    u,
    jnp.zeros((B,), jnp.int32),
    jnp.zeros((B, 2), jnp.int32),
    mask,
)
