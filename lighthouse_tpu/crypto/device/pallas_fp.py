"""Pallas TPU kernel for the int8-decomposed banded-Toeplitz fp product.

This is the hand-placed fallback behind ``FP_IMPL=pallas_int8`` (see
``fp.py``): if XLA's ``dot_general`` lowering of the ``matmul_int8`` path
keeps the int8 contractions on the VPU, this kernel states the placement
explicitly — int8 operand tiles in VMEM, four s8 x s8 -> s32 dot passes
per batch tile, shift-recombined in-register before the columns leave the
kernel. On the CPU it runs in interpreter mode so the whole differential
test matrix (vs the Python oracle and the int32 path) still covers it.

The kernel computes RAW product columns only; the caller reduces them mod
p through ``fp.reduce_cols`` with the shared full-band bound profile
(``fp.MUL_COL_BOUNDS``) — one reduction engine, machine-checked bounds,
regardless of which engine produced the columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Batch lanes per kernel instance. 8 sublanes is the int32 native tile
# height; the int8 operands are padded by Mosaic as needed (the band is
# [NL=32, NCOLS=63] — below the 128-lane tile, acceptable for a stub).
TILE = 8


def _mul_tile_kernel(split_shift: int, xs_ref, bs_ref, out_ref):
    """One batch tile: xs [2, T, 1, NL] int8, bs [2, T, NL, NCOLS] int8
    -> out [T, NCOLS] int32 raw product columns."""
    from jax import lax

    def dot(a, b):
        # [T, 1, NL] x [T, NL, NCOLS] -> [T, NCOLS], batched over T, int32
        # acc. The unit LHS row keeps a non-contracting dimension, which
        # Mosaic's batched matmul requires.
        return lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )[:, 0, :]

    xh, xl = xs_ref[0], xs_ref[1]
    bh, bl = bs_ref[0], bs_ref[1]
    hh = dot(xh, bh)
    hl = dot(xh, bl)
    lh = dot(xl, bh)
    ll = dot(xl, bl)
    out_ref[:] = (
        (hh << (2 * split_shift)) + ((hl + lh) << split_shift) + ll
    )


@functools.cache
def _interpret() -> bool:
    """Compiled on a TPU, interpreted on the CPU (where the kernel is a
    reference semantics check, not a performance path). Any other
    platform is refused rather than silently interpreted."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for tpu and interpret on cpu; "
        f"platform {platform!r} is neither"
    )


def mul_cols_int8(x, y):
    """Raw banded product columns of two fp limb arrays via the Pallas
    kernel; same contract as the dot_general passes in
    ``fp._mul_matmul_int8`` (exact int32 schoolbook columns)."""
    from jax.experimental import pallas as pl

    from . import fp

    x, y = jnp.broadcast_arrays(x, y)
    lead = x.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    xf = x.reshape(n, fp.NL)
    bf = fp.band_matrix(y.reshape(n, fp.NL))

    pad = (-n) % TILE
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        bf = jnp.pad(bf, ((0, pad), (0, 0), (0, 0)))
    npad = n + pad

    xs = fp.split_int8(xf)[:, :, None, :]   # [2, npad, 1, NL]
    bs = fp.split_int8(bf)                  # [2, npad, NL, NCOLS]

    cols = pl.pallas_call(
        functools.partial(_mul_tile_kernel, fp.SPLIT_SHIFT),
        grid=(npad // TILE,),
        in_specs=[
            pl.BlockSpec((2, TILE, 1, fp.NL), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((2, TILE, fp.NL, fp.NCOLS), lambda i: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, fp.NCOLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, fp.NCOLS), jnp.int32),
        interpret=_interpret(),
    )(xs, bs)
    return cols[:n].reshape(*lead, fp.NCOLS)
