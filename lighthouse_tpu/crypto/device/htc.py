"""Batched hash-to-curve for G2 on device (RFC 9380
BLS12381G2_XMD:SHA-256_SSWU_RO_, the suite the reference's blst backend
runs natively — ``/root/reference/crypto/bls/src/impls/blst.rs:14``).

Split of labor:

* host: ``expand_message_xmd`` (native batched SHA-256) + the mod-p
  reduction of the 64-byte uniform chunks — byte wrangling, not FLOPs;
* device (this module): everything algebraic, fully batched and
  branch-free — simplified SWU on the 3-isogenous curve E2', the derived
  3-isogeny back to E2, and Budroni-Pintore psi-based cofactor clearing.

Round 1 did all of this per message in pure Python at ~285 ms/message —
the end-to-end bottleneck (VERDICT "what's weak" #2). Here the whole
message batch moves through a handful of batched Fp2 ops and three scan
ladders.

The Fp2 square root uses the p == 3 (mod 4) extension-field algorithm
(same as the host oracle ``cpu/fields.py`` ``Fq2.sqrt``), evaluated
branch-free over the batch with both SSWU candidates stacked so the two
exponentiation ladders are shared by every candidate of every lane.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .. import iso3_g2
from ..cpu.fields import Fq, Fq2
from ..cpu.pairing import PSI_CX, PSI_CY
from ..params import ISO3_A, ISO3_B, ISO3_Z, P, X
from . import curve, fp, fp2
from .scopes import scoped

# ---------------------------------------------------------------------------
# Constants (host-derived, embedded as device arrays)
# ---------------------------------------------------------------------------

def _fq2(v) -> Fq2:
    return Fq2.from_ints(*v)


_A2 = _fq2(ISO3_A)
_B2 = _fq2(ISO3_B)
_Z2 = _fq2(ISO3_Z)
_NEG_B_DIV_A = (-_B2) * _A2.inverse()
_B_DIV_ZA = _B2 * (_Z2 * _A2).inverse()


def _dc(q: Fq2):
    """Fq2 -> device fp2 constant [2, NL]."""
    return fp2.const(q.c0.n, q.c1.n)


_PSI_CX_D = (PSI_CX.c0.n, PSI_CX.c1.n)
_PSI_CY_D = (PSI_CY.c0.n, PSI_CY.c1.n)

X_ABS = -X


def f2pow(x, e: int):
    """Fp2 fixed-exponent ladder (the shared window ladder in fp)."""
    return fp.square_multiply(x, e, fp2.sq, fp2.mul)


# ---------------------------------------------------------------------------
# Fp2 primitives for the map
# ---------------------------------------------------------------------------

def sgn0(x):
    """RFC 9380 §4.1 sgn0 for m=2, batched -> int32 [...] in {0,1}."""
    d = fp2.canonical(x)  # [..., 2, NL] strict digits
    c0d, c1d = d[..., 0, :], d[..., 1, :]
    sign0 = c0d[..., 0] & 1
    zero0 = jnp.all(c0d == 0, axis=-1)
    sign1 = c1d[..., 0] & 1
    return jnp.where(zero0, sign1, sign0)


@scoped("sqrt")
def sqrt(x):
    """Batched Fp2 square root -> (root, is_square). ``root`` is valid
    only where ``is_square``; x == 0 gives (0, True)."""
    a1 = f2pow(x, (P - 3) // 4)
    x0 = fp2.mul(a1, x)
    alpha = fp2.mul(a1, x0)
    neg_one = jnp.broadcast_to(fp2.const(P - 1, 0), alpha.shape).astype(jnp.int32)
    is_neg1 = fp2.eq(alpha, neg_one)
    # alpha == -1: root = u * x0  ((a+bu)*u = -b + au)
    cand1 = fp2.pack(fp.neg(fp2.c1(x0)), fp2.c0(x0))
    b = f2pow(fp2.add(fp2.ones(alpha.shape[:-2]), alpha), (P - 1) // 2)
    cand2 = fp2.mul(b, x0)
    root = fp2.select(is_neg1, cand1, cand2)
    ok = fp2.eq(fp2.sq(root), x)
    return root, ok


# ---------------------------------------------------------------------------
# Simplified SWU on E2' (batched, branch-free)
# ---------------------------------------------------------------------------

@scoped("sswu")
def sswu_pre(u):
    """Pre-sqrt half of simplified SWU: u -> (x1, x2, g) where
    ``g = [gx1, gx2]`` stacked on axis -3 awaits ONE sqrt ladder. Split
    out so the caller can merge the sqrt with other square roots in the
    same program (one shared ladder — compile-size lever)."""
    shape = u.shape[:-2]
    Z = jnp.broadcast_to(_dc(_Z2), u.shape).astype(jnp.int32)
    A = jnp.broadcast_to(_dc(_A2), u.shape).astype(jnp.int32)
    B = jnp.broadcast_to(_dc(_B2), u.shape).astype(jnp.int32)

    zu2 = fp2.mul(Z, fp2.sq(u))
    tv1 = fp2.add(fp2.sq(zu2), zu2)
    tv1_inv = fp2.inv(tv1)  # inv(0) == 0
    x1 = fp2.mul(
        jnp.broadcast_to(_dc(_NEG_B_DIV_A), u.shape).astype(jnp.int32),
        fp2.add(fp2.ones(shape), tv1_inv),
    )
    x1 = fp2.select(
        fp2.is_zero(tv1),
        jnp.broadcast_to(_dc(_B_DIV_ZA), u.shape).astype(jnp.int32),
        x1,
    )
    gx1 = fp2.add(fp2.mul(fp2.add(fp2.sq(x1), A), x1), B)
    x2 = fp2.mul(zu2, x1)
    # gx2 = (Z u^2)^3 * gx1 (standard SSWU identity)
    zu2_3 = fp2.mul(fp2.sq(zu2), zu2)
    gx2 = fp2.mul(zu2_3, gx1)
    return x1, x2, jnp.stack([gx1, gx2], axis=-3)


@scoped("sswu")
def sswu_post(u, x1, x2, roots, ok):
    """Post-sqrt half: candidate roots -> affine (x, y) with the RFC 9380
    sign rule. ``roots``/``ok`` are sqrt outputs of ``sswu_pre``'s g."""
    is1 = ok[..., 0]
    x = fp2.select(is1, x1, x2)
    y = fp2.select(is1, roots[..., 0, :, :], roots[..., 1, :, :])
    # sign: sgn0(y) must equal sgn0(u)
    flip = sgn0(u) != sgn0(y)
    y = fp2.select(flip, fp2.neg(y), y)
    return x, y


def map_to_curve_sswu(u):
    """u: fp2 [..., 2, NL] -> affine (x, y) on the iso-curve E2'."""
    x1, x2, g = sswu_pre(u)
    roots, ok = sqrt(g)
    return sswu_post(u, x1, x2, roots, ok)


# ---------------------------------------------------------------------------
# 3-isogeny E2' -> E2
# ---------------------------------------------------------------------------

def _iso3_coeff_table() -> np.ndarray:
    """All four isogeny polynomials padded to a common degree and stacked:
    int32 [max_len, 4, 2, NL], highest coefficient first (Horner order).
    Zero-padding the short polynomial at the top degree is exact
    (0*x + c)."""
    import numpy as _np

    polys = [iso3_g2.X_NUM, iso3_g2.X_DEN, iso3_g2.Y_NUM, iso3_g2.Y_DEN]
    n = max(len(p) for p in polys)
    out = _np.zeros((n, 4, 2, fp.NL), _np.int32)
    for j, poly in enumerate(polys):
        padded = list(poly) + [(0, 0)] * (n - len(poly))
        for d, c in enumerate(reversed(padded)):  # MSB-first for Horner
            q = _fq2(c)
            out[d, j, 0] = fp.int_to_limbs(q.c0.n)
            out[d, j, 1] = fp.int_to_limbs(q.c1.n)
    return out


_ISO3_TABLE = _iso3_coeff_table()


def iso3_map(x, y):
    """Derived 3-isogeny (coefficients from ``tools/derive_iso3.py``).
    All four polynomials are evaluated by ONE Horner scan over a stacked
    coefficient table (one fp2.mul body instead of ~11 — compile-size
    lever), and the two denominator inverses share one batched fp2.inv."""
    from jax import lax

    table = jnp.asarray(_ISO3_TABLE)  # [deg, 4, 2, NL]
    x4 = jnp.broadcast_to(
        x[..., None, :, :], (*x.shape[:-2], 4, 2, fp.NL)
    ).astype(jnp.int32)
    acc0 = jnp.broadcast_to(table[0], x4.shape).astype(jnp.int32)

    def body(acc, c):
        return fp2.add(fp2.mul(acc, x4), jnp.broadcast_to(c, x4.shape)), None

    acc, _ = lax.scan(body, acc0, table[1:])
    xn, xd, yn, yd = (acc[..., j, :, :] for j in range(4))
    dens = fp2.inv(jnp.stack([xd, yd], axis=-3))
    x_out = fp2.mul(xn, dens[..., 0, :, :])
    y_out = fp2.mul(fp2.mul(y, yn), dens[..., 1, :, :])
    return x_out, y_out


# ---------------------------------------------------------------------------
# psi endomorphism + Budroni-Pintore cofactor clearing
# ---------------------------------------------------------------------------

def psi_jac(pt):
    """(X, Y, Z) -> (conj(X) CX, conj(Y) CY, conj(Z)) — same derivation as
    the subgroup check's psi (``device/bls.py``)."""
    x, y, z = pt
    return (
        fp2.mul(fp2.conjugate(x), fp2.const(*_PSI_CX_D)),
        fp2.mul(fp2.conjugate(y), fp2.const(*_PSI_CY_D)),
        fp2.conjugate(z),
    )


def clear_cofactor(pt):
    """[X^2-X-1]P + [X-1]psi(P) + psi^2([2]P) (RFC 9380 App. G.3).

    The two [X]-multiplications ([X]P, then [X][X]P) run through ONE
    emitted scalar-mul body via an outer length-2 scan (the inner
    double-and-add scan appears once in HLO — compile-size lever)."""
    from jax import lax

    def round_(carry, _):
        q = curve.scalar_mul_const(fp2, carry, X_ABS)
        q = curve.neg(fp2, q)                    # [X]·, X < 0
        return q, q

    _, qs = lax.scan(round_, pt, None, length=2)
    xp = tuple(c[0] for c in qs)                 # [X]P
    x2p = tuple(c[1] for c in qs)                # [X^2]P
    neg_p = curve.neg(fp2, pt)
    neg_xp = curve.neg(fp2, xp)
    part1 = curve.add(fp2, curve.add(fp2, x2p, neg_xp), neg_p)
    part2 = psi_jac(curve.add(fp2, xp, neg_p))
    part3 = psi_jac(psi_jac(curve.dbl(fp2, pt)))
    return curve.add(fp2, curve.add(fp2, part1, part2), part3)


# ---------------------------------------------------------------------------
# The batched map: u values -> G2 Jacobian points
# ---------------------------------------------------------------------------

@scoped("map_to_g2")
def map_to_g2_post(u, x1, x2, roots, ok):
    """Post-sqrt remainder of the RO map: SSWU sign-pick, isogeny, the
    count-axis add, cofactor clearing. ``roots/ok`` are sqrt outputs of
    ``sswu_pre(u)``'s stacked g (callers may have merged that sqrt with
    other square roots in the program)."""
    x, y = sswu_post(u, x1, x2, roots, ok)
    x, y = iso3_map(x, y)
    q = curve.from_affine(fp2, x, y)
    q0 = tuple(c[..., 0, :, :] for c in q)
    q1 = tuple(c[..., 1, :, :] for c in q)
    return clear_cofactor(curve.add(fp2, q0, q1))


def map_to_g2(u):
    """u: fp2 [..., 2 (count), 2, NL] -> G2 Jacobian point [...] — the
    full RO map: two SSWU maps, isogeny, one add, cofactor clearing."""
    x1, x2, g = sswu_pre(u)              # batched over [..., 2]
    roots, ok = sqrt(g)
    return map_to_g2_post(u, x1, x2, roots, ok)


# ---------------------------------------------------------------------------
# Host half: messages -> u limbs (native SHA-256, cheap)
# ---------------------------------------------------------------------------

def messages_to_u(messages, dst: bytes) -> np.ndarray:
    """[m_0..m_{B-1}] -> int32 [B, 2, 2, NL] of hash_to_field outputs."""
    from ..cpu.hash_to_curve import expand_message_xmd

    out = np.zeros((len(messages), 2, 2, fp.NL), np.int32)
    L = 64
    for b, msg in enumerate(messages):
        uniform = expand_message_xmd(msg, dst, 2 * 2 * L)
        for i in range(2):
            for j in range(2):
                off = L * (j + i * 2)
                v = int.from_bytes(uniform[off:off + L], "big") % P
                out[b, i, j] = fp.int_to_limbs(v)
    return out
