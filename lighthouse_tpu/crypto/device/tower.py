"""Device extension-field tower Fp6 / Fp12 for the BLS12-381 pairing.

Layouts (leading dims are batch dims, broadcast everywhere):

* Fp6  = Fp2[v]/(v^3 - xi), xi = 1+u:  ``int32[..., 3, 2, 32]``
* Fp12 = Fp6[w]/(w^2 - v):             ``int32[..., 2, 3, 2, 32]``

Algorithms mirror the host oracle ``crypto/cpu/fields.{Fq6,Fq12}`` (tested
for bit-equality), expressed over the batched :mod:`.fp2` primitives.
Frobenius constants are computed at import from public curve parameters
(same derivation as the oracle's ``GAMMA6_1/GAMMA6_2/GAMMA12``).

All 27/18/81-lane product stacks funnel into :func:`fp.mul`, so the tower
inherits the active ``FP_IMPL`` contraction engine (int32 VPU dot or the
int8 MXU decomposition) transparently.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..params import P
from ..cpu.fields import GAMMA6_1, GAMMA6_2, GAMMA12
from . import fp, fp2

ELEM_NDIM_6 = 3
ELEM_NDIM_12 = 4


# ---------------------------------------------------------------------------
# Fp6
# ---------------------------------------------------------------------------

def f6_pack(c0, c1, c2):
    return jnp.stack([c0, c1, c2], axis=-3)


def f6_c(x, i):
    return x[..., i, :, :]


def f6_zeros(shape=()):
    return jnp.zeros((*shape, 3, 2, fp.NL), jnp.int32)


def f6_ones(shape=()):
    return f6_pack(fp2.ones(shape), fp2.zeros(shape), fp2.zeros(shape))


def f6_add(x, y):
    return fp.add(x, y)


def f6_sub(x, y):
    return fp.sub(x, y)


def f6_neg(x):
    return fp.neg(x)


def _f6_prod_terms(x, y):
    """The 9 Fp2 operand pairs of a schoolbook Fp6 product."""
    a = [f6_c(x, i) for i in range(3)]
    b = [f6_c(y, i) for i in range(3)]
    return [
        (a[0], b[0]),
        (a[0], b[1]), (a[1], b[0]),
        (a[0], b[2]), (a[1], b[1]), (a[2], b[0]),
        (a[1], b[2]), (a[2], b[1]),
        (a[2], b[2]),
    ]


def _f6_combine(p):
    """Recombine the 9 products with v^3 = xi folding (oracle Fq6.__mul__)."""
    t0 = p[0]
    t1 = fp2.add(p[1], p[2])
    t2 = fp2.add(fp2.add(p[3], p[4]), p[5])
    t3 = fp2.add(p[6], p[7])
    t4 = p[8]
    return f6_pack(
        fp2.add(t0, fp2.mul_by_u_plus_1(t3)),
        fp2.add(t1, fp2.mul_by_u_plus_1(t4)),
        t2,
    )


def f6_mul(x, y):
    """Schoolbook over Fp2; all 9 products in one batched fp.mul."""
    return _f6_combine(fp2.mul_pairs(_f6_prod_terms(x, y)))


def f6_sq(x):
    return f6_mul(x, x)


def f6_scale(x, k):
    """Multiply every Fp2 coefficient by the fp2 element ``k``."""
    p = fp2.mul_pairs([(f6_c(x, i), k) for i in range(3)])
    return f6_pack(*p)


def f6_mul_by_v(x):
    """(c0, c1, c2) -> (xi*c2, c0, c1)."""
    return f6_pack(fp2.mul_by_u_plus_1(f6_c(x, 2)), f6_c(x, 0), f6_c(x, 1))


def f6_inv(x):
    a0, a1, a2 = f6_c(x, 0), f6_c(x, 1), f6_c(x, 2)
    p = fp2.mul_pairs(
        [(a0, a0), (a1, a2), (a2, a2), (a0, a1), (a1, a1), (a0, a2)]
    )
    t0 = fp2.sub(p[0], fp2.mul_by_u_plus_1(p[1]))
    t1 = fp2.sub(fp2.mul_by_u_plus_1(p[2]), p[3])
    t2 = fp2.sub(p[4], p[5])
    q = fp2.mul_pairs([(a0, t0), (a2, t1), (a1, t2)])
    den = fp2.add(q[0], fp2.mul_by_u_plus_1(fp2.add(q[1], q[2])))
    d = fp2.inv(den)
    r = fp2.mul_pairs([(t0, d), (t1, d), (t2, d)])
    return f6_pack(*r)


# ---------------------------------------------------------------------------
# Fp12
# ---------------------------------------------------------------------------

def pack(c0, c1):
    return jnp.stack([c0, c1], axis=-4)


def c0(x):
    return x[..., 0, :, :, :]


def c1(x):
    return x[..., 1, :, :, :]


def zeros(shape=()):
    return jnp.zeros((*shape, 2, 3, 2, fp.NL), jnp.int32)


def ones(shape=()):
    return pack(f6_ones(shape), f6_zeros(shape))


def add(x, y):
    return fp.add(x, y)


def sub(x, y):
    return fp.sub(x, y)


def neg(x):
    return fp.neg(x)


def mul(x, y):
    """Karatsuba over Fp6: the 3 Fp6 products' 27 Fp2 products go through
    ONE batched fp.mul (81 Fp lanes) — graph-small, matmul-large."""
    a0, a1 = c0(x), c1(x)
    b0, b1 = c0(y), c1(y)
    terms = (
        _f6_prod_terms(a0, b0)
        + _f6_prod_terms(a1, b1)
        + _f6_prod_terms(f6_add(a0, a1), f6_add(b0, b1))
    )
    prods = fp2.mul_pairs(terms)
    t0 = _f6_combine(prods[0:9])
    t1 = _f6_combine(prods[9:18])
    m = _f6_combine(prods[18:27])
    return pack(
        f6_add(t0, f6_mul_by_v(t1)),
        f6_sub(f6_sub(m, t0), t1),
    )


def sq(x):
    """Dedicated squaring: (a + bw)^2 = (a^2 + v b^2) + 2ab w via the
    complex trick — 2 Fp6 products (18 Fp2 products in one batched
    fp.mul) vs 27 for the generic multiply. (A Granger-Scott cyclotomic
    squaring for the final-exp chains is a further planned cut.)"""
    a, b = c0(x), c1(x)
    terms = _f6_prod_terms(a, b) + _f6_prod_terms(
        f6_add(a, b), f6_add(a, f6_mul_by_v(b))
    )
    prods = fp2.mul_pairs(terms)
    t = _f6_combine(prods[0:9])          # ab
    u = _f6_combine(prods[9:18])         # (a+b)(a+vb) = a^2 + v b^2 + ab(1+v)
    c0_ = f6_sub(f6_sub(u, t), f6_mul_by_v(t))
    return pack(c0_, f6_add(t, t))


def conjugate(x):
    """x^(p^6): negate the w component. Inverse of unitary elements."""
    return pack(c0(x), f6_neg(c1(x)))


def inv(x):
    a, b = c0(x), c1(x)
    d = f6_inv(f6_sub(f6_sq(a), f6_mul_by_v(f6_sq(b))))
    return pack(f6_mul(a, d), f6_neg(f6_mul(b, d)))


def select(mask, a, b):
    return jnp.where(mask[..., None, None, None, None], a, b)


def canonical(x):
    return fp.canonical(x)


def is_one(x):
    one = jnp.broadcast_to(ones(), x.shape)
    return jnp.all(canonical(x) == canonical(one), axis=(-1, -2, -3, -4))


def eq(x, y):
    return jnp.all(canonical(x) == canonical(y), axis=(-1, -2, -3, -4))


# Frobenius gamma constants (public, derived from xi = 1+u).
_G6_1 = (GAMMA6_1.c0.n, GAMMA6_1.c1.n)
_G6_2 = (GAMMA6_2.c0.n, GAMMA6_2.c1.n)
_G12 = (GAMMA12.c0.n, GAMMA12.c1.n)


def frobenius(x):
    """x -> x^p (oracle Fq12.frobenius); gamma products in one batch."""
    g61 = fp2.const(*_G6_1)
    g62 = fp2.const(*_G6_2)
    g12 = fp2.const(*_G12)
    a, b = c0(x), c1(x)
    ca = [fp2.conjugate(f6_c(a, i)) for i in range(3)]
    cb = [fp2.conjugate(f6_c(b, i)) for i in range(3)]
    p = fp2.mul_pairs(
        [
            (ca[1], g61), (ca[2], g62),
            (cb[0], g12),
            (cb[1], fp2.mul(g61, g12)), (cb[2], fp2.mul(g62, g12)),
        ]
    )
    return pack(f6_pack(ca[0], p[0], p[1]), f6_pack(p[2], p[3], p[4]))


def frobenius_n(x, n: int):
    for _ in range(n):
        x = frobenius(x)
    return x


def pow_const(x, e: int):
    """x**e for fixed non-negative e; e == 0 -> one. Negative exponents are
    the caller's job (conjugate for unitary elements, inv otherwise)."""
    assert e >= 0
    if e == 0:
        return jnp.broadcast_to(ones(), x.shape).astype(jnp.int32)
    return fp.square_multiply(x, e, sq, mul)


def from_fp2(a):
    """Embed an fp2 element into Fp12 (constant coefficient)."""
    shape = a.shape[:-2]
    out = zeros(shape)
    return out.at[..., 0, 0, :, :].set(a)


# ---------------------------------------------------------------------------
# Host packing: oracle Fq6/Fq12 <-> device arrays
# ---------------------------------------------------------------------------

def pack_f12(vals) -> np.ndarray:
    """cpu Fq12 list -> int32[n, 2, 3, 2, 32]."""
    out = []
    for v in vals:
        halves = []
        for h in (v.c0, v.c1):
            coeffs = []
            for c in (h.c0, h.c1, h.c2):
                coeffs.append(
                    np.stack([fp.int_to_limbs(c.c0.n), fp.int_to_limbs(c.c1.n)])
                )
            halves.append(np.stack(coeffs))
        out.append(np.stack(halves))
    return np.stack(out)


def unpack_f12(arr):
    """Device Fp12 array [n, 2, 3, 2, 32] -> list of cpu Fq12."""
    from ..cpu.fields import Fq2, Fq6, Fq12

    arr = np.asarray(canonical(jnp.asarray(arr)))
    out = []
    for v in arr.reshape(-1, 2, 3, 2, fp.NL):
        halves = []
        for h in v:
            halves.append(
                Fq6(
                    *[
                        Fq2.from_ints(
                            fp.limbs_to_int(c[0]) % P, fp.limbs_to_int(c[1]) % P
                        )
                        for c in h
                    ]
                )
            )
        out.append(Fq12(*halves))
    return out
