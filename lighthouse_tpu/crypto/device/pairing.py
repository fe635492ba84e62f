"""Batched optimal-ate pairing on BLS12-381, device-side.

Everything is batched over leading dims and branch-free (selects only), so
one jitted graph serves any number of (G1, G2) pairs. The structure is the
TPU-idiomatic version of what blst's ``verify_multiple_aggregate_signatures``
does on CPU (``/root/reference/crypto/bls/src/impls/blst.rs:114-118``):
shared Miller loops, one product, one final exponentiation.

Differences from the host oracle (``crypto/cpu/pairing.py``), which works
affine over Fq12 with per-step inversions:

* G2 points stay on the twist E'(Fp2) in **Jacobian projective** form —
  no inversions inside the loop.
* Line functions are evaluated in **sparse form**. Derivation: untwisting
  ``(x', y') -> (x'/w^2, y'/w^3)`` maps the affine line
  ``l = m*(xP - xT) - (yP - yT)`` to
  ``l = -yP + (m xP) w^-1 + (yT - m xT) w^-3``; scaling by the slope
  denominator (an Fp2 value — final exponentiation kills any Fp2 factor,
  since ``(p^2-1) | (p^12-1)/r``) and by ``xi = w^6`` gives the
  polynomial sparse element ``s0 + s_v w^3 + s_v2 w^5`` with

      dbl step (T=(X,Y,Z) Jacobian):  s0 = -2YZ^3 yP * xi,
          s_v = 2Y^2 - 3X^3,          s_v2 = 3X^2 Z^2 xP
      add step (Q=(x2,y2) affine):    s0 = -HZ yP * xi,
          s_v = HZ y2 - R x2,         s_v2 = R xP
          with H = x2 Z^2 - X, R = y2 Z^3 - Y

  In the 2-3-2 tower, ``w^3 = v w`` and ``w^5 = v^2 w``, so the sparse
  element occupies slots (c0.c0, c1.c1, c1.c2) and multiplies a general
  Fp12 element in 18 Fp2 muls (vs 27 generic).
* The final-exponentiation hard part uses the x-chain
  ``d = (x-1)^2 (x+p) (x^2+p^2-1)/3 + 1`` — machine-verified against
  ``(p^4-p^2+1)/r`` at import — with conjugation standing in for
  inversion on unitary values.
"""

from __future__ import annotations

import contextlib
import os

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..params import P, R, X
from . import curve, fp, fp2, tower
from .scopes import scoped

X_ABS = -X  # 0xd201000000010000, the positive BLS parameter


# ---------------------------------------------------------------------------
# Line-evaluation engine seam (ISSUE 16)
#
# ``composed`` emits each Miller-loop step as ~13-15 individual fp2
# dispatches; ``fused`` restructures the same formulas into
# dependency-leveled ``fp2.mul_pairs``/``fp2.sq_batch`` batches (5-6
# dispatches per step), which both shrinks the staged HLO bodies and
# hands whole batches to the fused Fp2 Pallas kernel when that engine is
# active. Identical canonical values either way (differentially pinned).
# ---------------------------------------------------------------------------

IMPL_LINE_COMPOSED = "composed"
IMPL_LINE_FUSED = "fused"

_LINE_IMPLS = (IMPL_LINE_COMPOSED, IMPL_LINE_FUSED)

_active_line_impl = os.environ.get(
    "LIGHTHOUSE_TPU_LINE_IMPL", IMPL_LINE_COMPOSED
)
if _active_line_impl not in _LINE_IMPLS:
    raise KeyError(
        f"LIGHTHOUSE_TPU_LINE_IMPL={_active_line_impl!r} unknown; "
        f"have {sorted(_LINE_IMPLS)}"
    )


def get_line_impl() -> str:
    return _active_line_impl


def set_line_impl(name: str) -> None:
    """Select the line-eval step shape. Trace-time dispatch: callers
    holding jitted programs must call ``device.reset_compiled_state()``
    afterwards (same contract as ``fp.set_impl``)."""
    global _active_line_impl
    if name not in _LINE_IMPLS:
        raise KeyError(
            f"unknown line impl {name!r}; have {sorted(_LINE_IMPLS)}"
        )
    _active_line_impl = name


@contextlib.contextmanager
def line_impl(name: str):
    """Scoped line-impl switch (restores the previous choice)."""
    prev = _active_line_impl
    set_line_impl(name)
    try:
        yield
    finally:
        set_line_impl(prev)


# ---------------------------------------------------------------------------
# Sparse line element: (s0, sv, sv2) occupying Fp12 slots c0.c0, c1.c1, c1.c2
# ---------------------------------------------------------------------------

def mul_by_line(f, s0, sv, sv2):
    """General Fp12 times the sparse line element; the 18 Fp2 products go
    through one batched fp.mul."""
    a, b = tower.c0(f), tower.c1(f)  # Fp6 halves
    a0, a1, a2 = tower.f6_c(a, 0), tower.f6_c(a, 1), tower.f6_c(a, 2)
    b0, b1, b2 = tower.f6_c(b, 0), tower.f6_c(b, 1), tower.f6_c(b, 2)
    xi = fp2.mul_by_u_plus_1

    p = fp2.mul_pairs(
        [
            (a0, s0), (a1, s0), (a2, s0),        # a*L0
            (b0, s0), (b1, s0), (b2, s0),        # b*L0
            (b1, sv2), (b2, sv), (b0, sv), (b2, sv2), (b0, sv2), (b1, sv),  # b*L1
            (a1, sv2), (a2, sv), (a0, sv), (a2, sv2), (a0, sv2), (a1, sv),  # a*L1
        ]
    )
    a_l0 = tower.f6_pack(p[0], p[1], p[2])
    b_l0 = tower.f6_pack(p[3], p[4], p[5])
    bl1 = tower.f6_pack(
        xi(fp2.add(p[6], p[7])), fp2.add(p[8], xi(p[9])), fp2.add(p[10], p[11])
    )
    al1 = tower.f6_pack(
        xi(fp2.add(p[12], p[13])), fp2.add(p[14], xi(p[15])), fp2.add(p[16], p[17])
    )
    return tower.pack(
        tower.f6_add(a_l0, tower.f6_mul_by_v(bl1)),
        tower.f6_add(al1, b_l0),
    )


def _scale_batch(pairs):
    """[(fp2 elem, fp scalar)] -> [elem * scalar] with every component
    product in ONE fp.mul (the fused-step spelling of fp2.scale)."""
    xs = fp2._bstack([x for x, _ in pairs], -3)
    ks = fp2._bstack([k[..., None, :] for _, k in pairs], -3)
    t = fp.mul(xs, ks)
    return [t[..., i, :, :] for i in range(len(pairs))]


def _dbl_step(T, xP, yP):
    """Jacobian doubling of T on E'(Fp2) + sparse line coefficients at
    P = (xP, yP) in G1 affine, under the active line engine. Returns
    (T2, s0, sv, sv2)."""
    if _active_line_impl == IMPL_LINE_FUSED:
        return _dbl_step_fused(T, xP, yP)
    return _dbl_step_composed(T, xP, yP)


def _dbl_step_composed(T, xP, yP):
    Xc, Yc, Zc = T
    A = fp2.sq(Xc)              # X^2
    B = fp2.sq(Yc)              # Y^2
    C = fp2.sq(B)               # Y^4
    D = fp2.sub(fp2.sq(fp2.add(Xc, B)), fp2.add(A, C))
    D = fp2.add(D, D)           # 4XY^2
    E = fp2.add(fp2.add(A, A), A)  # 3X^2
    F = fp2.sq(E)
    X3 = fp2.sub(F, fp2.add(D, D))
    Y3 = fp2.sub(fp2.mul(E, fp2.sub(D, X3)), fp2.mul_small(C, 8))
    Z3 = fp2.mul(fp2.add(Yc, Yc), Zc)  # 2YZ

    Z2 = fp2.sq(Zc)
    # s0 = -2YZ^3 * yP * xi; 2YZ^3 = Z3 * Z2
    z3z2 = fp2.mul(Z3, Z2)
    s0 = fp2.mul_by_u_plus_1(fp2.neg(fp2.scale(z3z2, yP)))
    # sv = 2Y^2 - 3X^3
    sv = fp2.sub(fp2.add(B, B), fp2.mul(E, Xc))
    # sv2 = 3X^2 Z^2 * xP
    sv2 = fp2.scale(fp2.mul(E, Z2), xP)
    return (X3, Y3, Z3), s0, sv, sv2


def _dbl_step_fused(T, xP, yP):
    """Same doubling + line formulas, restructured into dependency-leveled
    batches: 3 squaring/mul batches + 1 product + 1 scale batch."""
    Xc, Yc, Zc = T
    A, B, Z2 = fp2.sq_batch([Xc, Yc, Zc])
    E = fp2.add(fp2.add(A, A), A)  # 3X^2
    C, XB2, F = fp2.sq_batch([B, fp2.add(Xc, B), E])
    D = fp2.sub(XB2, fp2.add(A, C))
    D = fp2.add(D, D)              # 4XY^2
    X3 = fp2.sub(F, fp2.add(D, D))
    EdX, Z3, EX, EZ2 = fp2.mul_pairs(
        [(E, fp2.sub(D, X3)), (fp2.add(Yc, Yc), Zc), (E, Xc), (E, Z2)]
    )
    Y3 = fp2.sub(EdX, fp2.mul_small(C, 8))
    sv = fp2.sub(fp2.add(B, B), EX)          # 2Y^2 - 3X^3
    (z3z2,) = fp2.mul_pairs([(Z3, Z2)])      # 2YZ^3
    s0c, sv2 = _scale_batch([(z3z2, yP), (EZ2, xP)])
    s0 = fp2.mul_by_u_plus_1(fp2.neg(s0c))
    return (X3, Y3, Z3), s0, sv, sv2


# ---------------------------------------------------------------------------
# Miller loop (batched)
# ---------------------------------------------------------------------------

_XBITS = np.array([int(b) for b in bin(X_ABS)[2:]], np.int32)


@scoped("miller_loop")
def miller_loop(g1_aff, g2_aff):
    """f_{|x|,Q}(P) conjugated (negative parameter), batched.

    ``g1_aff = (x, y, inf)`` with x,y fp [..., 32]; ``g2_aff = (x, y, inf)``
    with x,y fp2 [..., 2, 32]. Lanes where either point is at infinity
    yield one (so they do not affect a product of Miller values).
    """
    xP, yP, infP = g1_aff
    xQ, yQ, infQ = g2_aff

    batch = xP.shape[:-1]
    T0 = (xQ, yQ, fp2.ones(batch))
    f0 = jnp.broadcast_to(tower.ones(), (*batch, 2, 3, 2, fp.NL)).astype(jnp.int32)

    def body(carry, bit):
        f, T = carry
        f = tower.sq(f)
        T2, s0, sv, sv2 = _dbl_step(T, xP, yP)
        f = mul_by_line(f, s0, sv, sv2)
        # conditional add-step (bit is traced; both branches computed)
        T3, a0, av, av2 = _add_line(T2, xQ, yQ, xP, yP)
        fa = mul_by_line(f, a0, av, av2)
        take = bit == 1
        f = tower.select(jnp.broadcast_to(take, batch), fa, f)
        T = curve.select(fp2, jnp.broadcast_to(take, batch), T3, T2)
        return (f, T), None

    (f, _), _ = lax.scan(body, (f0, T0), jnp.asarray(_XBITS[1:]))
    # negative x: conjugate
    f = tower.conjugate(f)
    # infinity lanes -> 1
    one = jnp.broadcast_to(tower.ones(), f.shape).astype(jnp.int32)
    return tower.select(infP | infQ, one, f)


def _add_line(T, xQ, yQ, xP, yP):
    """Mixed addition T + Q with sparse line coefficients at P, under the
    active line engine."""
    if _active_line_impl == IMPL_LINE_FUSED:
        return _add_line_fused(T, xQ, yQ, xP, yP)
    return _add_line_composed(T, xQ, yQ, xP, yP)


def _add_line_composed(T, xQ, yQ, xP, yP):
    Xc, Yc, Zc = T
    Z2 = fp2.sq(Zc)
    U2 = fp2.mul(xQ, Z2)
    S2 = fp2.mul(yQ, fp2.mul(Zc, Z2))
    H = fp2.sub(U2, Xc)
    Rr = fp2.sub(S2, Yc)
    HH = fp2.sq(H)
    HHH = fp2.mul(H, HH)
    V = fp2.mul(Xc, HH)
    X3 = fp2.sub(fp2.sub(fp2.sq(Rr), HHH), fp2.add(V, V))
    Y3 = fp2.sub(fp2.mul(Rr, fp2.sub(V, X3)), fp2.mul(Yc, HHH))
    Z3 = fp2.mul(Zc, H)  # = HZ

    s0 = fp2.mul_by_u_plus_1(fp2.neg(fp2.scale(Z3, yP)))
    sv = fp2.sub(fp2.mul(Z3, yQ), fp2.mul(Rr, xQ))
    sv2 = fp2.scale(Rr, xP)
    return (X3, Y3, Z3), s0, sv, sv2


def _add_line_fused(T, xQ, yQ, xP, yP):
    """Same mixed-addition + line formulas in dependency-leveled batches:
    1 squaring + 4 product batches + 1 scale batch."""
    Xc, Yc, Zc = T
    Z2 = fp2.sq(Zc)
    U2, ZZ2 = fp2.mul_pairs([(xQ, Z2), (Zc, Z2)])
    H = fp2.sub(U2, Xc)
    S2, HH = fp2.mul_pairs([(yQ, ZZ2), (H, H)])
    Rr = fp2.sub(S2, Yc)
    HHH, V, R2, Z3 = fp2.mul_pairs(
        [(H, HH), (Xc, HH), (Rr, Rr), (Zc, H)]
    )
    X3 = fp2.sub(fp2.sub(R2, HHH), fp2.add(V, V))
    t = fp2.mul_pairs(
        [(Rr, fp2.sub(V, X3)), (Yc, HHH), (Z3, yQ), (Rr, xQ)]
    )
    Y3 = fp2.sub(t[0], t[1])
    sv = fp2.sub(t[2], t[3])
    s0c, sv2 = _scale_batch([(Z3, yP), (Rr, xP)])
    s0 = fp2.mul_by_u_plus_1(fp2.neg(s0c))
    return (X3, Y3, Z3), s0, sv, sv2


# ---------------------------------------------------------------------------
# Final exponentiation
# ---------------------------------------------------------------------------

def _exp_pos(f, e: int):
    """f^e for fixed positive e (the shared window ladder)."""
    return tower.pow_const(f, e)


def _conj_exp(f, e: int):
    """f^e for fixed NEGATIVE e on a unitary f: conj(f^|e|)."""
    return tower.conjugate(_exp_pos(f, -e))


@scoped("final_exp")
def final_exponentiation(f):
    """f^((p^12-1)/r), batched, exact. Easy part then the machine-checked
    x-chain. Used where the VALUE matters (oracle-parity pairing tests);
    the verification path uses :func:`final_exp_is_one` instead."""
    t = _easy_part(f)
    # Hard: d = (x-1)^2 (x+p) (x^2+p^2-1) / 3 + 1 applied as a chain.
    lam = (X - 1) // 3  # negative
    a = _conj_exp(t, lam)          # t^((x-1)/3)
    a = _conj_exp(a, X - 1)        # t^((x-1)^2/3)
    b = tower.mul(_conj_exp(a, X), tower.frobenius(a))        # a^(x+p)
    c = _conj_exp(_conj_exp(b, X), X)                         # b^(x^2)
    c = tower.mul(c, tower.frobenius_n(b, 2))                 # * b^(p^2)
    c = tower.mul(c, tower.conjugate(b))                      # * b^(-1)
    return tower.mul(c, t)                                    # * t  (the +1)


def _easy_part(f):
    """f^((p^6-1)(p^2+1)) — output is unitary (conj == inverse)."""
    t = tower.mul(tower.conjugate(f), tower.inv(f))
    return tower.mul(tower.frobenius_n(t, 2), t)


# -- compile-light final-exp decision procedure -----------------------------
#
# The verification paths only need "does f^((p^12-1)/r) == 1", so they can
# exponentiate by 3*(hard part) instead (r is prime != 3, so cubing is a
# bijection on the r-torsion): Fuentes-Castaneda's 3h = (x-1)^2 (x+p)
# (x^2+p^2-1) + 3 expands in powers of p to FOUR x-polynomial exponents
#
#   3h = lam0 + lam1 p + lam2 p^2 + lam3 p^3
#   lam0 = (x-1)^2 (x^3-x) + 3,  lam1 = (x-1)^2 (x^2-1),
#   lam2 = (x-1)^2 x,            lam3 = (x-1)^2
#
# evaluated as ONE shared-squaring multi-exponentiation over the Frobenius
# powers t^(p^i) (frobenius = a handful of fp2 muls). The five separate
# square-multiply ladders + glue of the exact chain were ~54k HLO lines of
# the device program; this is one scan with one Fp12 mul per bit.

_LAM = [
    (X - 1) ** 2 * (X**3 - X) + 3,
    (X - 1) ** 2 * (X**2 - 1),
    (X - 1) ** 2 * X,
    (X - 1) ** 2,
]
assert (
    sum(l * P**i for i, l in enumerate(_LAM)) == 3 * (P**4 - P**2 + 1) // R
), "multi-exp hard-part decomposition is wrong"


def _multiexp_bits() -> np.ndarray:
    """Per-step subset indices: bit i of step s selects base i (MSB
    first). int32 [n_steps]."""
    mags = [abs(l) for l in _LAM]
    n = max(m.bit_length() for m in mags)
    idx = np.zeros(n, np.int32)
    for i, m in enumerate(mags):
        for s in range(n):
            bit = (m >> (n - 1 - s)) & 1
            idx[s] |= bit << i
    return idx


_MULTIEXP_IDX = _multiexp_bits()


@scoped("final_exp")
def final_exp_is_one(f):
    """True iff final_exponentiation(f) == 1, via the 3h multi-exp."""
    t = _easy_part(f)
    bases = [t]
    for _ in range(3):
        bases.append(tower.frobenius(bases[-1]))
    # negative exponents on unitary values: conjugate the base
    bases = [
        tower.conjugate(b) if lam < 0 else b
        for b, lam in zip(bases, _LAM)
    ]
    # subset-product table T[s] = prod_{i in s} bases[i], built by ONE
    # scan over (dst, a, b) steps so the tower.mul body is emitted once
    # (the popcount-level batched version emitted it three times);
    # dependency order: every step's operands are already final.
    shape = f.shape
    one = jnp.broadcast_to(tower.ones(), shape).astype(jnp.int32)
    table = jnp.stack(
        [one, bases[0], bases[1], one, bases[2]]
        + [one] * 3
        + [bases[3]]
        + [one] * 7
    )  # [16, ..., 2,3,2,NL]; composite slots filled by the scan
    steps = jnp.asarray(
        [
            (3, 1, 2), (5, 1, 4), (9, 1, 8), (6, 2, 4), (10, 2, 8),
            (12, 4, 8), (7, 3, 4), (11, 3, 8), (13, 5, 8), (14, 6, 8),
            (15, 7, 8),
        ],
        jnp.int32,
    )

    def build(T, step):
        d, a, b = step[0], step[1], step[2]
        prod = tower.mul(jnp.take(T, a, axis=0), jnp.take(T, b, axis=0))
        return lax.dynamic_update_index_in_dim(T, prod, d, axis=0), None

    table, _ = lax.scan(build, table, steps)

    idx = jnp.asarray(_MULTIEXP_IDX)
    acc0 = jnp.take(table, idx[0], axis=0)

    def body(acc, i):
        acc = tower.sq(acc)
        acc = tower.mul(acc, jnp.take(table, i, axis=0))
        return acc, None

    acc, _ = lax.scan(body, acc0, idx[1:])
    return tower.is_one(acc)


# ---------------------------------------------------------------------------
# Multi-pairing
# ---------------------------------------------------------------------------

def multi_pairing(g1_aff, g2_aff, axis: int = 0):
    """prod_i e(P_i, Q_i) over a batch axis: batched Miller loops, scan
    product, one final exponentiation. Returns an Fp12 element (reduced
    over ``axis``)."""
    f = miller_loop(g1_aff, g2_aff)
    f = curve.tree_reduce(f, axis, tower.mul, tower.ones())
    return final_exponentiation(f)


def multi_pairing_is_one(g1_aff, g2_aff, axis: int = 0):
    """prod_i e(P_i, Q_i) == 1, with the compile-light multi-exp final
    exponentiation — the form every verification program uses."""
    f = miller_loop(g1_aff, g2_aff)
    f = curve.tree_reduce(f, axis, tower.mul, tower.ones())
    return final_exp_is_one(f)


def pairing(g1_aff, g2_aff):
    """e(P, Q), batched elementwise (no reduction)."""
    return final_exponentiation(miller_loop(g1_aff, g2_aff))
