"""BLS12-381 quadratic extension Fp2 = Fp[u]/(u^2+1) on device.

An Fp2 element is ``int32[..., 2, 32]``: axis -2 stacks (c0, c1), axis -1
is the 12-bit limb axis of :mod:`.fp`. All ops broadcast over leading batch
dims, mirroring the host oracle ``crypto/cpu/fields.Fq2`` (tested for
bit-equality against it). Reference behaviour being reproduced: the Fp2
tower inside blst (``/root/reference/crypto/bls/src/impls/blst.rs`` links
the asm backend).

Every product here drains into :func:`fp.mul` and therefore inherits the
active ``FP_IMPL`` engine (int32 Toeplitz dot / int8 MXU decomposition /
Pallas kernel) without any change at this layer.

This layer has its OWN engine seam on top (ISSUE 16): the default
``composed`` implementation emits the Karatsuba recombination as separate
XLA ops around one batched ``fp.mul``; ``fused_pallas`` hands the whole
product — contraction, reduction and Karatsuba combine — to one Pallas
tile (:mod:`.pallas_fp2`). Select with ``LIGHTHOUSE_TPU_FP2_IMPL`` (env)
or :func:`set_impl` / the :func:`impl` context manager. Dispatch happens
at TRACE time: callers holding jitted programs must call
``device.reset_compiled_state()`` after switching, exactly like the fp
seam.
"""

from __future__ import annotations

import contextlib
import os

import numpy as np

import jax.numpy as jnp
from jax import lax

from . import fp

# Trailing element dims of an fp2 array: (2, NL).
ELEM_NDIM = 2


def pack(c0, c1):
    """Two fp elements [..., 32] -> one fp2 element [..., 2, 32]."""
    return jnp.stack([c0, c1], axis=-2)


def c0(x):
    return x[..., 0, :]


def c1(x):
    return x[..., 1, :]


def const(v0: int, v1: int):
    return pack(fp.const(v0), fp.const(v1))


def zeros(shape=()):
    return jnp.zeros((*shape, 2, fp.NL), jnp.int32)


def ones(shape=()):
    return pack(fp.ones(shape), fp.zeros(shape))


def add(x, y):
    return fp.add(x, y)  # limbwise; fp ops broadcast over the (2,) axis


def sub(x, y):
    return fp.sub(x, y)


def neg(x):
    return fp.neg(x)


def mul_small(x, k: int):
    return fp.mul_small(x, k)


def _bstack(elems, axis):
    """Stack with broadcasting to a common shape (constants vs batches)."""
    shapes = [e.shape for e in elems]
    nd = max(len(s) for s in shapes)
    target = jnp.broadcast_shapes(*[(1,) * (nd - len(s)) + s for s in shapes])
    return jnp.stack([jnp.broadcast_to(e, target) for e in elems], axis=axis)


def _mul_composed(x, y):
    """(a0 + a1 u)(b0 + b1 u) via Karatsuba, with the three Fp products
    stacked into ONE batched fp.mul — the whole tower funnels its
    component products into single big contractions this way (small HLO
    graphs, large batched matmuls: the TPU-native shape of blst's
    tower arithmetic)."""
    a0, a1 = c0(x), c1(x)
    b0, b1 = c0(y), c1(y)
    xs = _bstack([a0, a1, fp.add(a0, a1)], -2)
    ys = _bstack([b0, b1, fp.add(b0, b1)], -2)
    t = fp.mul(xs, ys)
    t0, t1, m = t[..., 0, :], t[..., 1, :], t[..., 2, :]
    return pack(fp.sub(t0, t1), fp.sub(m, fp.add(t0, t1)))


def _mul_fused(x, y):
    from . import pallas_fp2

    x, y = jnp.broadcast_arrays(x, y)
    return pallas_fp2.mul2(x, y)


def mul(x, y):
    """Fp2 product under the active implementation (see module docstring)."""
    return _IMPLS[_active_impl][0](x, y)


def mul_pairs(pairs):
    """[(x_i, y_i)] -> [x_i * y_i] with ALL products in one batched fp.mul.

    The workhorse of the Fp6/Fp12 layers: an Fp12 multiply is 27 Fp2
    products = 81 Fp products = one fp.mul call here.
    """
    xs = _bstack([p[0] for p in pairs], -3)
    ys = _bstack([p[1] for p in pairs], -3)
    out = mul(xs, ys)
    return [out[..., i, :, :] for i in range(len(pairs))]


def sq_batch(elems):
    """[x_i] -> [x_i^2] with ALL squarings in one batched call (the
    squaring sibling of :func:`mul_pairs`; used by the fused line-eval
    steps in pairing.py)."""
    xs = _bstack(elems, -3)
    out = sq(xs)
    return [out[..., i, :, :] for i in range(len(elems))]


def _sq_composed(x):
    """(a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u (one batched fp.mul)."""
    a0, a1 = c0(x), c1(x)
    xs = _bstack([fp.add(a0, a1), a0], -2)
    ys = _bstack([fp.sub(a0, a1), a1], -2)
    t = fp.mul(xs, ys)
    t2 = t[..., 1, :]
    return pack(t[..., 0, :], fp.add(t2, t2))


def _sq_fused(x):
    from . import pallas_fp2

    return pallas_fp2.sq2(x)


def sq(x):
    """Fp2 squaring under the active implementation."""
    return _IMPLS[_active_impl][1](x)


# ---------------------------------------------------------------------------
# Implementation selection (mirrors the fp.mul engine seam)
# ---------------------------------------------------------------------------

IMPL_COMPOSED = "composed"
IMPL_FUSED_PALLAS = "fused_pallas"

_IMPLS = {
    IMPL_COMPOSED: (_mul_composed, _sq_composed),
    IMPL_FUSED_PALLAS: (_mul_fused, _sq_fused),
}

_active_impl = os.environ.get("LIGHTHOUSE_TPU_FP2_IMPL", IMPL_COMPOSED)
if _active_impl not in _IMPLS:
    raise KeyError(
        f"LIGHTHOUSE_TPU_FP2_IMPL={_active_impl!r} unknown; "
        f"have {sorted(_IMPLS)}"
    )


def get_impl() -> str:
    return _active_impl


def set_impl(name: str) -> None:
    """Select the Fp2 implementation. Dispatch happens at TRACE time:
    callers holding jitted programs must call
    ``device.reset_compiled_state()`` afterwards (same contract as
    ``fp.set_impl``)."""
    global _active_impl
    if name not in _IMPLS:
        raise KeyError(f"unknown fp2 impl {name!r}; have {sorted(_IMPLS)}")
    _active_impl = name


@contextlib.contextmanager
def impl(name: str):
    """Scoped implementation switch (restores the previous choice)."""
    prev = _active_impl
    set_impl(name)
    try:
        yield
    finally:
        set_impl(prev)


def conjugate(x):
    return pack(c0(x), fp.neg(c1(x)))


def scale(x, k):
    """Multiply both components by an fp element ``k`` [..., 32]."""
    return pack(fp.mul(c0(x), k), fp.mul(c1(x), k))


def mul_by_u_plus_1(x):
    """Multiply by the sextic non-residue xi = 1 + u:
    (a0 + a1 u)(1 + u) = (a0 - a1) + (a0 + a1) u."""
    a0, a1 = c0(x), c1(x)
    return pack(fp.sub(a0, a1), fp.add(a0, a1))


def inv(x):
    """(a0 - a1 u) / (a0^2 + a1^2); inv(0) = 0 (callers mask)."""
    a0, a1 = c0(x), c1(x)
    s = fp.mul(_bstack([a0, a1], -2), _bstack([a0, a1], -2))
    d = fp.inv(fp.add(s[..., 0, :], s[..., 1, :]))
    t = fp.mul(_bstack([a0, a1], -2), d[..., None, :])
    return pack(t[..., 0, :], fp.neg(t[..., 1, :]))


def canonical(x):
    return fp.canonical(x)


def is_zero(x):
    return jnp.all(canonical(x) == 0, axis=(-1, -2))


def eq(x, y):
    return jnp.all(canonical(x) == canonical(y), axis=(-1, -2))


def select(mask, a, b):
    """mask [...] bool -> elementwise fp2 select."""
    return jnp.where(mask[..., None, None], a, b)


def pow_const(x, e: int):
    """x**e for a fixed Python-int exponent (shared ladder in fp)."""
    return fp.square_multiply(x, e, sq, mul)
