"""Device Fp limb arithmetic vs Python-int ground truth.

Runs under the DEFAULT fp.mul implementation; the whole module is
re-collected under the int8 limb-split engine by
``test_zgate1_fp_impl_matrix.py`` (tail-sorted so the doubled runtime
cannot displace functional coverage inside the tier-1 wall-clock)."""

import numpy as np
import pytest

from lighthouse_tpu.crypto.params import P, X
from lighthouse_tpu.crypto.device import fp


def _rand_elems(rng, n):
    return [rng.randrange(P) for _ in range(n)]


def _pack(vals):
    return np.stack([fp.int_to_limbs(v) for v in vals])


def _val(arr):
    arr = np.asarray(arr)
    if arr.ndim == 1:
        return fp.limbs_to_int(arr) % P
    return [fp.limbs_to_int(a) % P for a in arr]


EDGES = [0, 1, 2, P - 1, P - 2, (1 << 381) % P, 0xFFF, 1 << 372]


def test_roundtrip_limbs():
    for v in EDGES:
        assert fp.limbs_to_int(fp.int_to_limbs(v)) == v


def test_add_sub_mul_batched(rng):
    xs = _rand_elems(rng, 8) + EDGES
    ys = EDGES + _rand_elems(rng, 8)
    X, Y = _pack(xs), _pack(ys)
    assert _val(fp.add(X, Y)) == [(a + b) % P for a, b in zip(xs, ys)]
    assert _val(fp.sub(X, Y)) == [(a - b) % P for a, b in zip(xs, ys)]
    assert _val(fp.mul(X, Y)) == [(a * b) % P for a, b in zip(xs, ys)]
    assert _val(fp.neg(X)) == [(-a) % P for a in xs]
    assert _val(fp.sq(X)) == [a * a % P for a in xs]


def test_relaxed_invariant_holds_after_chains(rng):
    """Chained ops keep limbs within [0, LIMB_MAX] (the documented invariant
    that makes every overflow bound valid)."""
    xs = _rand_elems(rng, 4) + [P - 1, 0]
    X = _pack(xs)
    acc = X
    for _ in range(5):
        acc = fp.mul(fp.add(acc, X), fp.sub(acc, X))
        arr = np.asarray(acc)
        assert arr.min() >= 0 and arr.max() <= fp.LIMB_MAX
    expect = xs
    acc2 = list(xs)
    for _ in range(5):
        acc2 = [((a + x) * (a - x)) % P for a, x in zip(acc2, expect)]
    assert _val(acc) == acc2


def test_canonical_strict_and_unique(rng):
    xs = _rand_elems(rng, 4) + EDGES
    X = _pack(xs)
    # Push through ops to get relaxed representations, then canonicalize.
    relaxed = fp.add(fp.mul(X, X), X)
    can = np.asarray(fp.canonical(relaxed))
    assert can.max() <= 0xFFF
    assert [fp.limbs_to_int(c) for c in can] == [(x * x + x) % P for x in xs]


def test_canonical_handles_value_just_below_2_384():
    # Largest relaxed-representable stress value: all limbs at LIMB_MAX.
    arr = np.full((fp.NL,), fp.LIMB_MAX, np.int32)
    v = fp.limbs_to_int(arr)
    can = np.asarray(fp.canonical(arr))
    assert fp.limbs_to_int(can) == v % P
    assert can.max() <= 0xFFF


def test_mul_small():
    for k in (0, 1, 2, 3, 8, 12):
        X = _pack(EDGES)
        assert _val(fp.mul_small(X, k)) == [(v * k) % P for v in EDGES]


def test_eq_is_zero(rng):
    x = rng.randrange(P)
    X = _pack([x, x, 0, P - 1])
    Y = _pack([x, (x + 1) % P, 0, P - 1])
    # compare relaxed vs strict forms
    Xr = fp.add(X, _pack([0, 0, 0, 0]))
    assert list(np.asarray(fp.eq(Xr, Y))) == [True, False, True, True]
    Z = fp.sub(X, Y)
    assert list(np.asarray(fp.is_zero(Z))) == [True, False, True, True]


def test_pow_inv(rng):
    xs = _rand_elems(rng, 3) + [1, P - 1]
    X = _pack(xs)
    e = rng.randrange(1, P)
    assert _val(fp.pow_const(X, e)) == [pow(x, e, P) for x in xs]
    inv = _val(fp.inv(X))
    for x, i in zip(xs, inv):
        assert (x * i) % P == 1
    # inv(0) == 0 convention
    assert _val(fp.inv(_pack([0])))[0] == 0


# the ladder's edge exponents: the bit ladder's (1, 2, 3 and a 5-bit
# window's 2^w - 1, 2^w, 2^w + 1), digits at 2^w on the windowed path
# (2^100 - 1, 2^100, 2^100 + 1), |X|, and the exponents of the Fp2 square
# root and of Fermat inversion
LADDER_EXPONENTS = [
    1, 2, 3, 31, 32, 33, (1 << 100) - 1, 1 << 100, (1 << 100) + 1, -X,
    (P - 3) // 4, (P - 1) // 2, P - 2,
]


def ladder_id(e):
    return f"{e}" if e < 1000 else f"{e.bit_length()}bits-{e % 1000}"


@pytest.mark.parametrize("e", LADDER_EXPONENTS, ids=ladder_id)
def test_square_multiply_matches_pow(rng, e):
    xs = _rand_elems(rng, 4) + [0, 1, P - 1]
    got = _val(fp.square_multiply(_pack(xs), e, fp.sq, fp.mul))
    assert got == [pow(x, e, P) for x in xs]


def test_window_width_keeps_the_bit_ladder_for_short_exponents():
    # a window pays 2^w - 1 table multiplies: below 8 bits none wins
    assert [fp.window_width(e) for e in (1, 2, 3, 31, 32, 33, 127)] == [1] * 7
    assert fp.window_width(255) == 2
    assert fp.window_width(-X) == 3
    assert [fp.window_width(e) for e in ((P - 3) // 4, (P - 1) // 2, P - 2)] == [5] * 3


def test_ladder_counts():
    # (squarings, multiplies): the bit ladder always multiplies
    assert fp.ladder_counts(1) == (0, 0)
    assert fp.ladder_counts(127) == (6, 6)
    assert fp.ladder_counts(-X) == (63, 28)
    assert fp.ladder_counts((P - 3) // 4) == (375, 106)
    assert fp.ladder_counts((P - 1) // 2) == (375, 106)
    assert fp.ladder_counts(P - 2) == (380, 107)


@pytest.mark.parametrize("w", [2, 3, 5])
def test_window_digits_spell_the_exponent(rng, w):
    for e in [1, (1 << (4 * w)) - 1, 1 << (4 * w), P - 2, rng.randrange(1, P)]:
        digits = fp._digits_msb(e, w)
        assert all(1 <= d <= 1 << w for d in digits)
        acc = 0
        for d in digits:
            acc = (acc << w) + d
        assert acc == e


def test_select():
    X, Y = _pack([1, 2]), _pack([3, 4])
    out = _val(fp.select(np.array([True, False]), X, Y))
    assert out == [1, 4]


def test_broadcast_leading_dims(rng):
    xs = _rand_elems(rng, 6)
    X = _pack(xs).reshape(2, 3, fp.NL)
    out = np.asarray(fp.mul(X, X)).reshape(6, fp.NL)
    assert [fp.limbs_to_int(a) % P for a in out] == [x * x % P for x in xs]
