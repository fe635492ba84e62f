"""Device hash-to-curve vs the host oracle: bit-exact parity + sqrt/sgn0
primitives. Fast enough for the default suite (one moderate compile)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.cpu.fields import Fq, Fq2
from lighthouse_tpu.crypto.cpu.hash_to_curve import hash_to_g2
from lighthouse_tpu.crypto.device import curve, fp, fp2, htc
from lighthouse_tpu.crypto.params import DST, P


def test_sqrt_and_sgn0(rng):
    vals = []
    for _ in range(4):
        q = Fq2(Fq(rng.randrange(P)), Fq(rng.randrange(P)))
        vals.append(q * q)  # guaranteed squares
    vals.append(Fq2(Fq(0), Fq(0)))
    arr = jnp.asarray(
        np.stack([
            np.stack([fp.int_to_limbs(v.c0.n), fp.int_to_limbs(v.c1.n)])
            for v in vals
        ])
    )
    roots, ok = jax.jit(htc.sqrt)(arr)
    roots, ok = np.asarray(roots), np.asarray(ok)
    for i, v in enumerate(vals):
        assert bool(ok[i]), f"square {i} must have a root"
        got = Fq2(
            Fq(fp.limbs_to_int(np.asarray(fp.canonical(roots[i][0])))),
            Fq(fp.limbs_to_int(np.asarray(fp.canonical(roots[i][1])))),
        )
        assert got * got == v
    # sgn0 parity vs oracle
    sg_vals = np.asarray(jax.jit(htc.sgn0)(arr))
    for i, v in enumerate(vals):
        assert int(sg_vals[i]) == v.sgn0()


@pytest.mark.parametrize("engine", [fp.IMPL_TOEPLITZ_INT32, fp.IMPL_MATMUL_INT8])
def test_sqrt_matches_oracle(rng, engine):
    """Same (root, ok) as the oracle's Fq2.sqrt on squares, non-squares
    and 0, under both fp.mul engines."""
    vals = [Fq2(Fq(0), Fq(0))]
    while len(vals) < 9:
        q = Fq2(Fq(rng.randrange(P)), Fq(rng.randrange(P)))
        if len(vals) < 5:
            q = q * q
        if (q.sqrt() is None) == (len(vals) >= 5):
            vals.append(q)
    arr = jnp.asarray(np.stack([
        np.stack([fp.int_to_limbs(v.c0.n), fp.int_to_limbs(v.c1.n)])
        for v in vals
    ]))
    with fp.impl(engine):
        roots, ok = jax.jit(lambda a: htc.sqrt(a))(arr)
    roots = np.asarray(fp.canonical(roots))
    for i, v in enumerate(vals):
        want = v.sqrt()
        assert bool(ok[i]) == (want is not None), i
        if want is not None:
            got = (fp.limbs_to_int(roots[i][0]), fp.limbs_to_int(roots[i][1]))
            assert got == (want.c0.n, want.c1.n), i


def test_map_to_g2_matches_oracle(rng):
    msgs = [bytes([rng.randrange(256) for _ in range(32)]) for _ in range(3)]
    u = jnp.asarray(htc.messages_to_u(msgs, DST))
    pts = jax.jit(htc.map_to_g2)(u)
    x, y, inf = (np.asarray(c) for c in curve.to_affine(fp2, pts))
    for b, m in enumerate(msgs):
        want = hash_to_g2(m, DST)
        assert not inf[b]
        assert fp.limbs_to_int(x[b, 0]) == want.x.c0.n
        assert fp.limbs_to_int(x[b, 1]) == want.x.c1.n
        assert fp.limbs_to_int(y[b, 0]) == want.y.c0.n
        assert fp.limbs_to_int(y[b, 1]) == want.y.c1.n
