"""The benchmark's plain BLS12-381 verifier: the reference that decides
``correct``. Pure Python, written from the standards below; it shares
no code and no constant table with the program under test, nor with the
C signer that makes the traffic (``perfbench/signer/``).

Sources (each constant is typed from the text cited beside it):

* [PFC] draft-irtf-cfrg-pairing-friendly-curves-11, section 4.2.1
  (BLS12_381): the seed x, the prime p, the order r, E: y^2 = x^3 + 4,
  the twist E': y^2 = x^3 + 4(1 + I) over Fp2 = Fp[I]/(I^2 + 1), the
  G1 generator. p and r are checked against x below.
* [H2C] RFC 9380: expand_message_xmd (5.3.1), hash_to_field (5.2),
  sgn0 (4.1), the simplified SWU map (6.6.2), the suite
  BLS12381G2_XMD:SHA-256_SSWU_RO_ (8.8.2: A', B', Z), the 3-isogeny map
  (appendix E.3), clear_cofactor and psi (appendix G.3).
* [BLS] draft-irtf-cfrg-bls-signature-05: CoreVerify with the
  proof-of-possession tag the consensus specs use (4.2.3), the sum of
  public keys (2.5), the subgroup check of a signature (2.8).
* [ZC] the ZCash serialization of BLS12-381 points (compressed, flags
  in the top three bits of the first byte, x = c1 || c0 in G2), which
  the consensus specs use.

Public keys are given as points, or as secret keys whose public key is
[sk] g1 (the benchmark's registry is defined by its secret keys; the
tests pin [sk] g1 to the interop and EIP-2335 keypairs). A batch of
sets (public key, message, signature) verifies when

    prod_i e([r_i] pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1

with nonzero scalars r_i: random in the reference; all 1 in the
control, which then accepts signature errors that cancel in the sum.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

# [PFC] 4.2.1
X = -0xD201000000010000
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
assert R == X**4 - X**2 + 1
assert P == (X - 1) ** 2 * R // 3 + X
G1_X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1_Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1
# [BLS] 4.2.3, the tag of the consensus specs
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# ---------------------------------------------------------------- Fp2
# (a, b) is a + b*I with I^2 = -1; every element is kept reduced.

F2_ZERO = (0, 0)
F2_ONE = (1, 0)
XI = (1, 1)                      # 1 + I: E' has b = 4*XI, Fp12 has w^6 = XI
B1 = (4, 0)
B2 = (4, 4)


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = a0 * b0
    t1 = a1 * b1
    return ((t0 - t1) % P, ((a0 + a1) * (b0 + b1) - t0 - t1) % P)


def f2_sqr(a):
    a0, a1 = a
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def f2_scale(a, k):
    return (a[0] * k % P, a[1] * k % P)


def f2_inv(a):
    a0, a1 = a
    t = pow(a0 * a0 + a1 * a1, -1, P)
    return (a0 * t % P, -a1 * t % P)


def f2_conj(a):
    return (a[0], -a[1] % P)


def f2_pow(a, e):
    out = F2_ONE
    for bit in bin(e)[2:]:
        out = f2_sqr(out)
        if bit == "1":
            out = f2_mul(out, a)
    return out


def fp_sqrt(a):
    """A square root of ``a`` in Fp (p = 3 mod 4), or None."""
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a % P else None


def f2_sqrt(a):
    """A square root of ``a`` in Fp2, or None: with a = a0 + a1*I and
    s = x0 + x1*I, x0^2 = (a0 +- sqrt(a0^2 + a1^2)) / 2, x1 = a1 / 2x0."""
    a0, a1 = a
    if a1 == 0:
        s = fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        s = fp_sqrt(-a0 % P)
        return (0, s) if s is not None else None
    alpha = fp_sqrt((a0 * a0 + a1 * a1) % P)
    if alpha is None:
        return None
    half = pow(2, -1, P)
    x0 = fp_sqrt((a0 + alpha) * half % P)
    if x0 is None:
        x0 = fp_sqrt((a0 - alpha) * half % P)
    if x0 is None:
        return None
    s = (x0, a1 * pow(2 * x0, -1, P) % P)
    return s if f2_sqr(s) == a else None


# ---------------------------------------------------------------- Fp12
# Fp12 = Fp2[w]/(w^6 - XI), held as 12 ints: c_i = (t[2i], t[2i+1]) is
# the Fp2 coefficient of w^i. Fp6 = Fp2[v]/(v^3 - XI) with v = w^2.

F12_ONE = [1] + [0] * 11


def _f12_reduce(acc):
    """22 ints (w^0..w^10) to 12, with w^6 = XI."""
    out = acc[:12]
    for k in range(6, 11):
        re, im = acc[2 * k], acc[2 * k + 1]
        out[2 * k - 12] += re - im
        out[2 * k - 11] += re + im
    return [c % P for c in out]


def f12_mul(a, b):
    acc = [0] * 22
    for i in range(6):
        a0, a1 = a[2 * i], a[2 * i + 1]
        if not (a0 or a1):
            continue
        s = a0 + a1
        for j in range(6):
            b0, b1 = b[2 * j], b[2 * j + 1]
            t0 = a0 * b0
            t1 = a1 * b1
            k = 2 * (i + j)
            acc[k] += t0 - t1
            acc[k + 1] += s * (b0 + b1) - t0 - t1
    return _f12_reduce(acc)


def f12_mul_line(f, l0, l2, l3):
    """f * (l0 + l2 w^2 + l3 w^3), with l0, l2 in Fp2 and l3 in Fp."""
    acc = [0] * 22
    l00, l01 = l0
    l20, l21 = l2
    s0, s2 = l00 + l01, l20 + l21
    for i in range(6):
        a0, a1 = f[2 * i], f[2 * i + 1]
        sa = a0 + a1
        t0, t1 = a0 * l00, a1 * l01
        acc[2 * i] += t0 - t1
        acc[2 * i + 1] += sa * s0 - t0 - t1
        t0, t1 = a0 * l20, a1 * l21
        acc[2 * i + 4] += t0 - t1
        acc[2 * i + 5] += sa * s2 - t0 - t1
        acc[2 * i + 6] += a0 * l3
        acc[2 * i + 7] += a1 * l3
    return _f12_reduce(acc)


def f12_conj(f):
    """f^(p^6): w^(p^6) = -w, Fp2 is fixed."""
    return [c if (k // 2) % 2 == 0 else -c % P for k, c in enumerate(f)]


# w^(p-1) = XI^((p-1)/6), so (c_i w^i)^p = conj(c_i) XI^(i(p-1)/6) w^i
_FROB = [f2_pow(XI, i * (P - 1) // 6) for i in range(6)]


def f12_frob(f):
    out = []
    for i in range(6):
        out.extend(f2_mul(f2_conj((f[2 * i], f[2 * i + 1])), _FROB[i]))
    return out


def _f6_mul(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    m = f2_mul
    c0 = f2_add(m(a0, b0), f2_mul(XI, f2_add(m(a1, b2), m(a2, b1))))
    c1 = f2_add(f2_add(m(a0, b1), m(a1, b0)), f2_mul(XI, m(a2, b2)))
    c2 = f2_add(f2_add(m(a0, b2), m(a1, b1)), m(a2, b0))
    return (c0, c1, c2)


def _f6_inv(c):
    c0, c1, c2 = c
    t0 = f2_sub(f2_sqr(c0), f2_mul(XI, f2_mul(c1, c2)))
    t1 = f2_sub(f2_mul(XI, f2_sqr(c2)), f2_mul(c0, c1))
    t2 = f2_sub(f2_sqr(c1), f2_mul(c0, c2))
    d = f2_add(f2_mul(c0, t0), f2_mul(XI, f2_add(f2_mul(c2, t1), f2_mul(c1, t2))))
    di = f2_inv(d)
    return (f2_mul(t0, di), f2_mul(t1, di), f2_mul(t2, di))


def f12_inv(f):
    """(A + B w)^-1 = (A - B w) / (A^2 - v B^2), A and B in Fp6."""
    c = [(f[2 * i], f[2 * i + 1]) for i in range(6)]
    a, b = (c[0], c[2], c[4]), (c[1], c[3], c[5])
    bb = _f6_mul(b, b)
    d = _f6_mul(a, a)
    d = (f2_sub(d[0], f2_mul(XI, bb[2])), f2_sub(d[1], bb[0]), f2_sub(d[2], bb[1]))
    di = _f6_inv(d)
    na = _f6_mul(a, di)
    nb = _f6_mul(b, di)
    out = []
    for k in range(3):
        out.extend(na[k])
        out.extend(f2_neg(nb[k]))
    return out


def f12_pow(f, e):
    out = F12_ONE
    for bit in bin(e)[2:]:
        out = f12_mul(out, out)
        if bit == "1":
            out = f12_mul(out, f)
    return out


# ---------------------------------------------------------------- curves
# Jacobian (X, Y, Z) over Fp2, Z = 0 the point at infinity. G1 points
# are embedded with zero imaginary parts: the a = 0 formulas need no b.

INF = (F2_ONE, F2_ONE, F2_ZERO)


def is_inf(p):
    return p[2] == F2_ZERO


def g_neg(p):
    return (p[0], f2_neg(p[1]), p[2])


def g_dbl(p):
    """a = 0 doubling: A = X^2, B = Y^2, C = B^2, D = 2((X + B)^2 - A - C),
    E = 3A; X3 = E^2 - 2D, Y3 = E(D - X3) - 8C, Z3 = 2YZ (Fp2 products
    written out: this and g_add carry most of the reference's time)."""
    (x0, x1), (y0, y1), (z0, z1) = p
    if not (z0 or z1):
        return p
    a0, a1 = (x0 + x1) * (x0 - x1) % P, 2 * x0 * x1 % P
    b0, b1 = (y0 + y1) * (y0 - y1) % P, 2 * y0 * y1 % P
    c0, c1 = (b0 + b1) * (b0 - b1) % P, 2 * b0 * b1 % P
    s0, s1 = x0 + b0, x1 + b1
    d0 = 2 * ((s0 + s1) * (s0 - s1) - a0 - c0) % P
    d1 = 2 * (2 * s0 * s1 - a1 - c1) % P
    e0, e1 = 3 * a0, 3 * a1
    x30 = ((e0 + e1) * (e0 - e1) - 2 * d0) % P
    x31 = (2 * e0 * e1 - 2 * d1) % P
    t0, t1 = d0 - x30, d1 - x31
    m0, m1 = e0 * t0, e1 * t1
    y30 = (m0 - m1 - 8 * c0) % P
    y31 = ((e0 + e1) * (t0 + t1) - m0 - m1 - 8 * c1) % P
    m0, m1 = y0 * z0, y1 * z1
    z30 = 2 * (m0 - m1) % P
    z31 = 2 * ((y0 + y1) * (z0 + z1) - m0 - m1) % P
    return ((x30, x31), (y30, y31), (z30, z31))


def _m(a0, a1, b0, b1):
    t0, t1 = a0 * b0, a1 * b1
    return (t0 - t1) % P, ((a0 + a1) * (b0 + b1) - t0 - t1) % P


def g_add(p, q):
    """U1 = X1 Z2^2, U2 = X2 Z1^2, S1 = Y1 Z2^3, S2 = Y2 Z1^3, H = U2 - U1,
    r = 2(S2 - S1), I = (2H)^2, J = H I, V = U1 I; X3 = r^2 - J - 2V,
    Y3 = r(V - X3) - 2 S1 J, Z3 = ((Z1 + Z2)^2 - Z1^2 - Z2^2) H."""
    (x10, x11), (y10, y11), (z10, z11) = p
    if not (z10 or z11):
        return q
    (x20, x21), (y20, y21), (z20, z21) = q
    if not (z20 or z21):
        return p
    a0, a1 = (z10 + z11) * (z10 - z11) % P, 2 * z10 * z11 % P
    b0, b1 = (z20 + z21) * (z20 - z21) % P, 2 * z20 * z21 % P
    u10, u11 = _m(x10, x11, b0, b1)
    u20, u21 = _m(x20, x21, a0, a1)
    s10, s11 = _m(*_m(y10, y11, z20, z21), b0, b1)
    s20, s21 = _m(*_m(y20, y21, z10, z11), a0, a1)
    h0, h1 = (u20 - u10) % P, (u21 - u11) % P
    r0, r1 = (s20 - s10) % P, (s21 - s11) % P
    if not (h0 or h1):
        return g_dbl(p) if not (r0 or r1) else INF
    i0, i1 = 4 * (h0 + h1) * (h0 - h1) % P, 8 * h0 * h1 % P
    j0, j1 = _m(h0, h1, i0, i1)
    r0, r1 = 2 * r0, 2 * r1
    v0, v1 = _m(u10, u11, i0, i1)
    x30 = ((r0 + r1) * (r0 - r1) - j0 - 2 * v0) % P
    x31 = (2 * r0 * r1 - j1 - 2 * v1) % P
    t0, t1 = _m(r0, r1, v0 - x30, v1 - x31)
    w0, w1 = _m(s10, s11, j0, j1)
    y30, y31 = (t0 - 2 * w0) % P, (t1 - 2 * w1) % P
    s0, s1 = z10 + z20, z11 + z21
    k0 = ((s0 + s1) * (s0 - s1) - a0 - b0) % P
    k1 = (2 * s0 * s1 - a1 - b1) % P
    return ((x30, x31), (y30, y31), _m(k0, k1, h0, h1))


def g_mul(p, k):
    if k < 0:
        return g_mul(g_neg(p), -k)
    out = INF
    for bit in bin(k)[2:]:
        out = g_dbl(out)
        if bit == "1":
            out = g_add(out, p)
    return out


def to_affine(p):
    """(x, y) in Fp2; the point must not be at infinity."""
    zi = f2_inv(p[2])
    zi2 = f2_sqr(zi)
    return f2_mul(p[0], zi2), f2_mul(f2_mul(p[1], zi2), zi)


def from_affine(x, y):
    return (x, y, F2_ONE)


def on_curve(p, b):
    x, y = to_affine(p)
    return f2_sqr(y) == f2_add(f2_mul(f2_sqr(x), x), b)


def same_point(p, q):
    if is_inf(p) or is_inf(q):
        return is_inf(p) and is_inf(q)
    return to_affine(p) == to_affine(q)


G1 = from_affine((G1_X, 0), (G1_Y, 0))

# [j * 16^i] g1 for i < 64, j < 16: [k] g1 costs 64 additions
_G1_TABLE = []


def g1_mul_base(k):
    """[k] g1, for 0 <= k < 2^256."""
    if not _G1_TABLE:
        base = G1
        for _ in range(64):
            row = [INF]
            for _ in range(15):
                row.append(g_add(row[-1], base))
            _G1_TABLE.append(row)
            base = g_add(row[-1], base)
    out = INF
    for i in range(64):
        d = (k >> (4 * i)) & 15
        if d:
            out = g_add(out, _G1_TABLE[i][d])
    return out


def in_g2(q):
    """[BLS] 2.8, subgroup_check_E2: the point has order r. Tested as
    psi(Q) == [x] Q, which holds on E' exactly for the points of G2
    (Scott, "A note on group membership tests for G1, G2 and GT on BLS
    pairing-friendly curves", ePrint 2021/1130, section 4); the tests
    check it against [r] Q == O."""
    return same_point(psi(q), g_mul(q, X))


# ---------------------------------------------------------------- encoding [ZC]

_HALF = (P - 1) // 2


def g1_compress(p) -> bytes:
    if is_inf(p):
        return bytes([0xC0]) + bytes(47)
    (x, _), (y, _) = to_affine(p)
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if y > _HALF else 0)
    return bytes(out)


def g1_decompress(data: bytes):
    """The G1 point of a 48-byte compressed encoding, or None."""
    if len(data) != 48 or not data[0] & 0x80:
        return None
    if data[0] & 0x40:
        return INF if data[0] == 0xC0 and not any(data[1:]) else None
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    if x >= P:
        return None
    y = fp_sqrt((x * x * x + 4) % P)
    if y is None:
        return None
    if (y > _HALF) != bool(data[0] & 0x20):
        y = P - y
    return from_affine((x, 0), (y, 0))


def _f2_largest(y) -> bool:
    return y[1] > _HALF or (y[1] == 0 and y[0] > _HALF)


def g2_compress(q) -> bytes:
    if is_inf(q):
        return bytes([0xC0]) + bytes(95)
    x, y = to_affine(q)
    out = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if _f2_largest(y) else 0)
    return bytes(out)


def g2_decompress(data: bytes):
    """The E' point of a 96-byte compressed encoding, or None (the
    subgroup is checked apart)."""
    if len(data) != 96 or not data[0] & 0x80:
        return None
    if data[0] & 0x40:
        return INF if data[0] == 0xC0 and not any(data[1:]) else None
    x1 = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if x0 >= P or x1 >= P:
        return None
    x = (x0, x1)
    y = f2_sqrt(f2_add(f2_mul(f2_sqr(x), x), B2))
    if y is None:
        return None
    if _f2_largest(y) != bool(data[0] & 0x20):
        y = f2_neg(y)
    return from_affine(x, y)


# ---------------------------------------------------------------- hash to G2 [H2C]


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    """5.3.1 with SHA-256."""
    ell = -(-n // 32)
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00"
                        + dst_prime).digest()
    b = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        x = bytes(u ^ v for u, v in zip(b0, b[-1]))
        b.append(hashlib.sha256(x + bytes([i]) + dst_prime).digest())
    return b"".join(b)[:n]


def hash_to_field(msg: bytes, dst: bytes):
    """5.2 with count 2, m 2, L 64: two elements of Fp2."""
    u = expand_message_xmd(msg, dst, 256)
    e = [int.from_bytes(u[64 * k:64 * (k + 1)], "big") % P for k in range(4)]
    return (e[0], e[1]), (e[2], e[3])


def sgn0(a) -> int:
    """4.1 with m = 2."""
    return (a[0] & 1) | (int(a[0] == 0) & (a[1] & 1))


# 8.8.2: the isogenous curve E'': y^2 = x^3 + A x + B, and Z
ISO_A = (0, 240)
ISO_B = (1012, 1012)
ISO_Z = (P - 2, P - 1)


def _iso_curve(x):
    return f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(ISO_A, x)), ISO_B)


def map_to_curve_sswu(u):
    """6.6.2, the straight-line description: a point of E''."""
    zu2 = f2_mul(ISO_Z, f2_sqr(u))
    tv1 = f2_add(f2_sqr(zu2), zu2)
    if tv1 == F2_ZERO:
        x1 = f2_mul(ISO_B, f2_inv(f2_mul(ISO_Z, ISO_A)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(ISO_B), f2_inv(ISO_A)),
                    f2_add(F2_ONE, f2_inv(tv1)))
    x, y = x1, f2_sqrt(_iso_curve(x1))
    if y is None:                # gx1 is not a square
        x = f2_mul(zu2, x1)
        y = f2_sqrt(_iso_curve(x))
    if sgn0(u) != sgn0(y):
        y = f2_neg(y)
    return x, y


# Appendix E.3: the 3-isogeny E'' -> E', k_(i,j) as (real, imaginary)
_K1 = [
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0,
     0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
     0),
]
_K2 = [
    (0,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
    (0xC,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
    F2_ONE,
]
_K3 = [
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
     0),
]
_K4 = [
    (0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
    (0,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3),
    (0x12,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99),
    F2_ONE,
]


def _poly(coeffs, x):
    out = F2_ZERO
    for c in reversed(coeffs):
        out = f2_add(f2_mul(out, x), c)
    return out


def iso_map(x, y):
    """A point of E'' to E' (Jacobian); infinity where a denominator is 0."""
    xd, yd = _poly(_K2, x), _poly(_K4, x)
    if xd == F2_ZERO or yd == F2_ZERO:
        return INF
    xo = f2_mul(_poly(_K1, x), f2_inv(xd))
    yo = f2_mul(f2_mul(y, _poly(_K3, x)), f2_inv(yd))
    return from_affine(xo, yo)


# Appendix G.3: psi(x, y) = (c1 conj(x), c2 conj(y))
_PSI_X = f2_inv(f2_pow(XI, (P - 1) // 3))
_PSI_Y = f2_inv(f2_pow(XI, (P - 1) // 2))


def psi(p):
    x, y, z = p
    return (f2_mul(_PSI_X, f2_conj(x)), f2_mul(_PSI_Y, f2_conj(y)), f2_conj(z))


def clear_cofactor(p):
    """G.3: [x^2 - x - 1] P + [x - 1] psi(P) + psi^2([2] P)."""
    t1 = g_mul(p, X)
    t2 = psi(p)
    t3 = psi(psi(g_dbl(p)))
    t3 = g_add(t3, g_neg(t2))
    t2 = g_mul(g_add(t1, t2), X)
    t3 = g_add(g_add(t3, t2), g_neg(t1))
    return g_add(t3, g_neg(p))


@lru_cache(maxsize=4096)
def hash_to_g2(msg: bytes, dst: bytes = DST):
    """hash_to_curve of the suite BLS12381G2_XMD:SHA-256_SSWU_RO_ (Jacobian)."""
    u0, u1 = hash_to_field(msg, dst)
    q = g_add(iso_map(*map_to_curve_sswu(u0)), iso_map(*map_to_curve_sswu(u1)))
    return clear_cofactor(q)


def sign(sk: int, msg: bytes, dst: bytes = DST) -> bytes:
    return g2_compress(g_mul(hash_to_g2(msg, dst), sk % R))


# ---------------------------------------------------------------- pairing

_LOOP = bin(-X)[3:]              # bits of |x| after the leading one


def _f2_inv_all(vals):
    """Inverses of many nonzero Fp2 elements, one Fp inversion for all
    (Montgomery's trick on the norms)."""
    norms = [(a0 * a0 + a1 * a1) % P for a0, a1 in vals]
    pre, acc = [], 1
    for n in norms:
        pre.append(acc)
        acc = acc * n % P
    inv = pow(acc, -1, P)
    out = [None] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        t = pre[i] * inv % P
        inv = inv * norms[i] % P
        a0, a1 = vals[i]
        out[i] = (a0 * t % P, -a1 * t % P)
    return out


def miller_loop(pairs):
    """prod_i f_{|x|, Q_i}(P_i) for pairs ((xp, yp) in Fp, (xq, yq) in
    Fp2), lines on the twist multiplied by w^3 (a factor the final
    exponentiation removes, as it removes vertical lines): at P the line
    through T with slope L reads yp w^3 - L xp w^2 + (L xT - yT)."""
    f = F12_ONE
    ts = [q for _p, q in pairs]
    for bit in _LOOP:
        f = f12_mul(f, f)
        invs = _f2_inv_all([f2_scale(yt, 2) for _xt, yt in ts])
        for i, ((xp, yp), _q) in enumerate(pairs):
            xt, yt = ts[i]
            lam = f2_mul(f2_scale(f2_sqr(xt), 3), invs[i])
            f = f12_mul_line(f, f2_sub(f2_mul(lam, xt), yt), f2_scale(lam, -xp % P), yp)
            x3 = f2_sub(f2_sqr(lam), f2_scale(xt, 2))
            ts[i] = (x3, f2_sub(f2_mul(lam, f2_sub(xt, x3)), yt))
        if bit == "1":
            invs = _f2_inv_all([f2_sub(q[0], t[0]) for (_p, q), t in zip(pairs, ts)])
            for i, ((xp, yp), (xq, yq)) in enumerate(pairs):
                xt, yt = ts[i]
                lam = f2_mul(f2_sub(yq, yt), invs[i])
                f = f12_mul_line(f, f2_sub(f2_mul(lam, xt), yt), f2_scale(lam, -xp % P), yp)
                x3 = f2_sub(f2_sub(f2_sqr(lam), xt), xq)
                ts[i] = (x3, f2_sub(f2_mul(lam, f2_sub(xt, x3)), yt))
    return f


_HARD = (P**4 - P**2 + 1) // R
assert _HARD * R == P**4 - P**2 + 1


def final_exponentiation(f):
    """f^((p^12 - 1) / r) = f^((p^6 - 1)(p^2 + 1)(p^4 - p^2 + 1) / r)."""
    f = f12_mul(f12_conj(f), f12_inv(f))
    f = f12_mul(f12_frob(f12_frob(f)), f)
    return f12_pow(f, _HARD)


def pairing_product_is_one(pairs) -> bool:
    """pairs of (G1 point, G2 point), Jacobian: prod e(P, Q) == 1."""
    aff = []
    for p, q in pairs:
        if is_inf(p) or is_inf(q):
            continue
        (xp, _), (yp, _) = to_affine(p)
        aff.append(((xp, yp), to_affine(q)))
    return final_exponentiation(miller_loop(aff)) == F12_ONE


# ---------------------------------------------------------------- verification


def public_key(key):
    """A set's public key: a secret key (int), whose key is [sk] g1, or
    the list of its signers' G1 points (Jacobian), summed ([BLS] 2.5)."""
    if isinstance(key, int):
        return None if key % R == 0 else key % R
    out = INF
    for p in key:
        out = g_add(out, p)
    return None if is_inf(out) else out


def prepare(sets):
    """Each set (key, message, compressed signature) as (public key,
    H(m), signature point), or None when any signature fails to decode,
    is at infinity or lies outside G2, or any public key is at infinity:
    the batch is then invalid."""
    out = []
    for key, msg, sig in sets:
        pk = public_key(key)
        q = g2_decompress(bytes(sig))
        if pk is None or q is None or is_inf(q) or not in_g2(q):
            return None
        out.append((pk, hash_to_g2(bytes(msg)), q))
    return out


def verify_prepared(prepared, scalars) -> bool:
    """The batch equation over prepared sets, one scalar each."""
    if not prepared:
        return False
    pairs = []
    sig_sum = INF
    for (pk, h, q), r in zip(prepared, scalars):
        if isinstance(pk, int):
            pk = g1_mul_base(pk * r % R)
        else:
            pk = g_mul(pk, r)
        pairs.append((pk, h))
        sig_sum = g_add(sig_sum, g_mul(q, r))
    pairs.append((g_neg(G1), sig_sum))
    return pairing_product_is_one(pairs)


def verify_sets(sets, scalars) -> bool:
    prepared = prepare(sets)
    return prepared is not None and verify_prepared(prepared, scalars)
