"""Fused Fp2 Pallas kernels: Karatsuba mul and squaring in ONE tile.

The composed :mod:`.fp2` path lowers every Fp2 product as one batched
``fp.mul`` (three Fp lanes) plus separate reduce/add/sub dispatches, and
leaves the Karatsuba recombination to XLA's fusion heuristics. These
kernels state the whole inner loop explicitly instead: the int8 dot
passes, the shift recombination, the column reduction AND the Karatsuba
combine all run inside one Pallas tile, so the product never round-trips
raw columns through HBM between the contraction and the combine.

Selected via ``LIGHTHOUSE_TPU_FP2_IMPL=fused_pallas`` (see ``fp2.py``);
on the CPU the kernels run in interpreter mode, so the full differential
matrix (vs the Python Fq2 oracle and the composed path) covers them on
any host.

Soundness notes (the same machine-checked regime as ``fp.py``):

* Operand sums (``a0+a1`` etc.) are carry-reduced by ``fp.add``/``fp.sub``
  BEFORE ``fp.split_int8`` — the int8 split is only valid for values in
  ``[0, LIMB_MAX]``.
* Inside the kernel, products are first reduced to the relaxed 32-limb
  form (``fp.reduce_cols`` with the full-band profile); the Karatsuba
  subtractions then use the saturated multiple ``fp.SAT`` so every limb
  stays non-negative: ``t0 - t1 == t0 + (SAT - t1) (mod p)`` with exact
  per-column bounds ``LIMB_MAX + SAT_i < 2**31`` asserted at trace time.
* Raw columns are NEVER combined pre-reduction: negative columns would
  break the carry shifts, and ``SAT`` only covers relaxed 32-limb values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_fp import TILE, _interpret


def _raw_cols(split_shift, xs_ref, bs_ref, r):
    """Shared contraction for operand row ``r``: the four int8 dot
    passes + shift recombination -> exact int32 product columns
    [T, NCOLS]."""
    from jax import lax

    def dot(a, b):
        # [T, 1, NL] x [T, NL, NCOLS] -> [T, NCOLS]; int32 acc. The unit
        # LHS row keeps a non-contracting dimension, which Mosaic's
        # batched matmul requires.
        return lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )[:, 0, :]

    xh, xl = xs_ref[0, r], xs_ref[1, r]
    bh, bl = bs_ref[0, r], bs_ref[1, r]
    return (
        (dot(xh, bh) << (2 * split_shift))
        + ((dot(xh, bl) + dot(xl, bh)) << split_shift)
        + dot(xl, bl)
    )


def _karatsuba_tile_kernel(split_shift, xs_ref, bs_ref, fold_ref, sat_ref,
                           out_ref):
    """One batch tile of the fused Fp2 product.

    xs [2, T, 3, NL] int8, bs [2, T, 3, NL, NCOLS] int8 — per lane the
    three Karatsuba operand rows (a0, a1, a0+a1) x (b0, b1, b0+b1) —
    -> out [T, 2, NL] int32 relaxed Fp2 elements. ``fold_ref``/``sat_ref``
    carry the reduction tables in (kernels cannot capture constants).
    """
    from . import fp

    sat = sat_ref[...]
    with fp.fold_table(fold_ref[...]):
        t0, t1, m = (
            fp.reduce_cols(_raw_cols(split_shift, xs_ref, bs_ref, r),
                           fp.MUL_COL_BOUNDS)       # [T, NL] relaxed
            for r in range(3)
        )
        # c0 = t0 - t1, c1 = m - t0 - t1, each in ONE reduction via SAT
        c0 = fp.reduce_cols(
            t0 + (sat - t1), [fp.LIMB_MAX + int(v) for v in fp.SAT]
        )
        c1 = fp.reduce_cols(
            m + (2 * sat - t0 - t1),
            [fp.LIMB_MAX + 2 * int(v) for v in fp.SAT],
        )
    out_ref[0] = c0
    out_ref[1] = c1


def _sq_tile_kernel(split_shift, xs_ref, bs_ref, fold_ref, sat_ref, out_ref):
    """Fused Fp2 squaring tile: rows (a0+a1, a0) x (a0-a1, a1) ->
    (t0, t1) with c0 = t0, c1 = 2 t1. xs [2, T, 2, NL] int8,
    bs [2, T, 2, NL, NCOLS] int8 -> out [T, 2, NL] int32."""
    from . import fp

    with fp.fold_table(fold_ref[...]):
        t0, t1 = (
            fp.reduce_cols(_raw_cols(split_shift, xs_ref, bs_ref, r),
                           fp.MUL_COL_BOUNDS)       # [T, NL]
            for r in range(2)
        )
        c1 = fp.reduce_cols(t1 + t1, [2 * fp.LIMB_MAX] * fp.NL)
    out_ref[0] = t0
    out_ref[1] = c1


def _run_rows(kernel, xrows, yrows):
    """Shared launch: per-lane operand rows [..., R, NL] (already
    carry-reduced) -> [..., 2, NL] fused Fp2 results."""
    from jax.experimental import pallas as pl

    from . import fp

    nrows = xrows.shape[-2]
    lead = xrows.shape[:-2]
    n = 1
    for d in lead:
        n *= d
    # row-major operand layout [R, n, ...]: each Karatsuba row is its
    # own [T, 1, NL] x [T, NL, NCOLS] batched matmul inside the kernel
    xf = jnp.moveaxis(xrows.reshape(n, nrows, fp.NL), 1, 0)
    bf = fp.band_matrix(jnp.moveaxis(yrows.reshape(n, nrows, fp.NL), 1, 0))

    pad = (-n) % TILE
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
        bf = jnp.pad(bf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    npad = n + pad

    xs = fp.split_int8(xf)[..., None, :]    # [2, R, npad, 1, NL]
    bs = fp.split_int8(bf)                  # [2, R, npad, NL, NCOLS]

    nfold = fp.FOLD.shape[0]
    out = pl.pallas_call(
        functools.partial(kernel, fp.SPLIT_SHIFT),
        grid=(npad // TILE,),
        in_specs=[
            pl.BlockSpec(
                (2, nrows, TILE, 1, fp.NL), lambda i: (0, 0, i, 0, 0)
            ),
            pl.BlockSpec(
                (2, nrows, TILE, fp.NL, fp.NCOLS),
                lambda i: (0, 0, i, 0, 0),
            ),
            pl.BlockSpec((nfold, fp.NL), lambda i: (0, 0)),
            pl.BlockSpec((fp.NL,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((2, TILE, fp.NL), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, npad, fp.NL), jnp.int32),
        interpret=_interpret(),
    )(xs, bs, jnp.asarray(fp.FOLD), jnp.asarray(fp.SAT))
    return jnp.moveaxis(out[:, :n], 0, 1).reshape(*lead, 2, fp.NL)


def mul2(x, y):
    """Fused Fp2 product; same contract as the composed ``fp2.mul``
    (relaxed limbs, identical canonical value)."""
    from . import fp

    x, y = jnp.broadcast_arrays(x, y)
    a0, a1 = x[..., 0, :], x[..., 1, :]
    b0, b1 = y[..., 0, :], y[..., 1, :]
    # the Karatsuba operand sums MUST be carry-reduced before split_int8
    xrows = jnp.stack([a0, a1, fp.add(a0, a1)], axis=-2)
    yrows = jnp.stack([b0, b1, fp.add(b0, b1)], axis=-2)
    return _run_rows(_karatsuba_tile_kernel, xrows, yrows)


def sq2(x):
    """Fused Fp2 squaring via (a0+a1)(a0-a1) | a0*a1."""
    from . import fp

    a0, a1 = x[..., 0, :], x[..., 1, :]
    xrows = jnp.stack([fp.add(a0, a1), a0], axis=-2)
    yrows = jnp.stack([fp.sub(a0, a1), a1], axis=-2)
    return _run_rows(_sq_tile_kernel, xrows, yrows)
