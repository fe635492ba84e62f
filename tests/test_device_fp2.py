"""Device Fp2 arithmetic vs the pure-Python Fq2 oracle.

Runs under the DEFAULT fp.mul implementation; re-collected under the
int8 limb-split engine by ``test_zgate1_fp_impl_matrix.py`` (tail-sorted,
see that module's docstring)."""

import numpy as np
import pytest

from lighthouse_tpu.crypto.params import P
from lighthouse_tpu.crypto.cpu.fields import Fq2
from lighthouse_tpu.crypto.device import fp, fp2

from test_device_fp import LADDER_EXPONENTS, ladder_id


def _pack(pairs):
    """[(c0, c1), ...] ints -> device fp2 batch [n, 2, 32]."""
    return np.stack(
        [np.stack([fp.int_to_limbs(a), fp.int_to_limbs(b)]) for a, b in pairs]
    )


def _val(arr):
    arr = np.asarray(arr)
    out = []
    for e in arr.reshape(-1, 2, fp.NL):
        out.append((fp.limbs_to_int(e[0]) % P, fp.limbs_to_int(e[1]) % P))
    return out


def _oracle(pairs):
    return [Fq2.from_ints(a, b) for a, b in pairs]


def _to_pair(f: Fq2):
    return (f.c0.n, f.c1.n)


EDGES = [(0, 0), (1, 0), (0, 1), (P - 1, P - 1), (1, P - 1), (P - 2, 3)]


def _rand_pairs(rng, n):
    return [(rng.randrange(P), rng.randrange(P)) for _ in range(n)]


def test_mul_sq_add_sub_neg(rng):
    xs = _rand_pairs(rng, 6) + EDGES
    ys = EDGES + _rand_pairs(rng, 6)
    X, Y = _pack(xs), _pack(ys)
    ox, oy = _oracle(xs), _oracle(ys)
    assert _val(fp2.mul(X, Y)) == [_to_pair(a * b) for a, b in zip(ox, oy)]
    assert _val(fp2.sq(X)) == [_to_pair(a.square()) for a in ox]
    assert _val(fp2.add(X, Y)) == [_to_pair(a + b) for a, b in zip(ox, oy)]
    assert _val(fp2.sub(X, Y)) == [_to_pair(a - b) for a, b in zip(ox, oy)]
    assert _val(fp2.neg(X)) == [_to_pair(-a) for a in ox]
    assert _val(fp2.conjugate(X)) == [_to_pair(a.conjugate()) for a in ox]


def test_mul_by_nonresidue(rng):
    xs = _rand_pairs(rng, 4) + EDGES
    X = _pack(xs)
    xi = Fq2.from_ints(1, 1)
    assert _val(fp2.mul_by_u_plus_1(X)) == [_to_pair(a * xi) for a in _oracle(xs)]


def test_inv(rng):
    xs = _rand_pairs(rng, 4) + [(1, 0), (0, 1), (P - 1, P - 1)]
    X = _pack(xs)
    got = _val(fp2.inv(X))
    for pair, g in zip(_oracle(xs), got):
        prod = pair * Fq2.from_ints(*g)
        assert prod == Fq2.one()
    # inv(0) == 0 convention
    assert _val(fp2.inv(_pack([(0, 0)])))[0] == (0, 0)


def test_eq_is_zero_select(rng):
    a = _rand_pairs(rng, 1)[0]
    X = _pack([a, a, (0, 0)])
    Y = _pack([a, (a[0], (a[1] + 1) % P), (0, 0)])
    assert list(np.asarray(fp2.eq(X, Y))) == [True, False, True]
    assert list(np.asarray(fp2.is_zero(fp2.sub(X, Y)))) == [True, False, True]
    out = _val(fp2.select(np.array([True, False, True]), X, Y))
    assert out == [a, (a[0], (a[1] + 1) % P), (0, 0)]


def test_pow_const_scale(rng):
    xs = _rand_pairs(rng, 3)
    X = _pack(xs)
    e = rng.randrange(2, 1 << 64)
    assert _val(fp2.pow_const(X, e)) == [_to_pair(a.pow(e)) for a in _oracle(xs)]
    k = rng.randrange(P)
    got = _val(fp2.scale(X, fp.const(k)))
    from lighthouse_tpu.crypto.cpu.fields import Fq

    assert got == [_to_pair(a.scale(Fq(k))) for a in _oracle(xs)]


@pytest.mark.parametrize("e", LADDER_EXPONENTS, ids=ladder_id)
def test_square_multiply_matches_oracle(rng, e):
    xs = _rand_pairs(rng, 3) + [(0, 0), (1, 0), (0, 1)]
    got = _val(fp.square_multiply(_pack(xs), e, fp2.sq, fp2.mul))
    assert got == [_to_pair(a.pow(e)) for a in _oracle(xs)]
