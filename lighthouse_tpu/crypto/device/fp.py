"""BLS12-381 base field Fp on device: 12-bit x 32 limb arithmetic in int32.

Design (TPU-first, not a port of blst's 64-bit asm):

* An Fp element is ``int32[..., 32]``: 32 little-endian limbs of 12 bits.
  381-bit values fit in 384 bits. Leading dims are batch dims; every op
  broadcasts, so the whole stack is batched without ``vmap``.
* 12-bit limbs are chosen so schoolbook products never overflow int32:
  a full-product column is at most ``16 * LIMB_MAX**2 < 2**31``. The TPU
  VPU has no 64-bit multiply-high; 12x12->24-bit products with 32-bit
  accumulation map directly onto int32 vector lanes.
* Multiplication = banded-Toeplitz matmul: gather ``y`` into a
  ``[..., 32, 63]`` band matrix, one batched ``dot_general`` computes all
  63 product columns (2016 MACs — the minimal schoolbook work), then the
  columns are reduced mod p by folding limbs >= 32 through a precomputed
  ``2**(12*i) mod p`` table (another small matmul). No Montgomery form:
  the fold table plays the role blst's Montgomery REDC plays
  (``/root/reference/crypto/bls/src/impls/blst.rs`` links the asm).
* Values are kept *relaxed*: limbs in ``[0, LIMB_MAX]``, value in
  ``[0, 2**384)``-ish, only congruent mod p. ``canonical`` produces the
  unique strict representative for equality/serialization.
* Every reduction plan is derived at trace time by exact interval
  arithmetic on per-limb bounds, asserting that no intermediate can
  overflow int32 — machine-checked, not hand-waved.

Subtraction uses a "saturated" multiple of p (every digit >= LIMB_MAX) so
``x - y + SAT`` is limb-wise non-negative — branch-free and select-free.

Multiplication exists in selectable implementations (``FP_IMPL``):

* ``toeplitz_int32`` — the original banded dot over int32 operands.
  Correct everywhere, but int32 multiplies execute on the TPU VPU
  (~2e12 MAC/s on v5e), which caps the whole verifier well below target
  (``docs/COST_MODEL.md``).
* ``matmul_int8`` — each limb is split into int8-ranged halves
  (``hi = limb >> SPLIT_SHIFT``, ``lo = limb & SPLIT_MASK``) and the
  banded product becomes FOUR int8 x int8 -> int32 ``dot_general``
  passes recombined with shifts — the dtype shape XLA lowers onto the
  MXU systolic array (~4.9e13 MAC/s envelope). Same column values,
  machine-checked to recombine without overflow.
* ``pallas_int8`` — the same int8 decomposition as a hand-placed Pallas
  kernel (``pallas_fp.py``), for when XLA keeps the int8 dots on the
  VPU; interpreted on the CPU, so it stays differential-testable.

Select with ``LIGHTHOUSE_TPU_FP_IMPL`` (env, like the BLS backend flag in
``crypto/backend.py``) or :func:`set_impl` / the :func:`impl` context
manager. NOTE: callers that hold jitted programs must call
``device.reset_compiled_state()`` (crypto/device/__init__.py) after
switching — dispatch happens at trace time, and that helper also resets
recompile tracking and the compile service's warm-shape registry.
"""

from __future__ import annotations

import contextlib
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..params import P

# ---------------------------------------------------------------------------
# Layout constants
# ---------------------------------------------------------------------------

ELEM_NDIM = 1             # trailing element dims of an fp array: (NL,)
W = 12                    # bits per limb
NL = 32                   # limbs per element (384 bits >= 381)
MASK = (1 << W) - 1       # 0xFFF
LIMB_MAX = 8191           # relaxed per-limb bound maintained by reduce_cols
NCOLS = 2 * NL - 1        # full-product column count

# Products are accumulated in int32 over *half* the limbs at a time
# (16 * LIMB_MAX**2 < 2**31); see mul().
assert (NL // 2) * (LIMB_MAX ** 2) < 2 ** 31, "half-conv columns must fit int32"

# ---------------------------------------------------------------------------
# int8 limb split (matmul_int8 / pallas_int8 implementations)
# ---------------------------------------------------------------------------
# A relaxed limb carries up to 13 bits (LIMB_MAX = 8191), so the paper-style
# high-8/low-4 split of a strict 12-bit digit does not fit the SIGNED int8
# operands the MXU consumes natively. The split point is therefore *derived*:
# the smallest shift whose high half fits int8, which lands at hi = limb >> 6
# (<= 127) and lo = limb & 63 — a (7+6)-bit split with identical algebra:
#     x*y = (xh*yh << 2S) + ((xh*yl + xl*yh) << S) + xl*yl,  S = SPLIT_SHIFT
_INT8_MAX = 127
SPLIT_SHIFT = next(
    s for s in range(1, 13) if (LIMB_MAX >> s) <= _INT8_MAX
)
SPLIT_MASK = (1 << SPLIT_SHIFT) - 1
assert (LIMB_MAX >> SPLIT_SHIFT) <= _INT8_MAX and SPLIT_MASK <= _INT8_MAX


# ---------------------------------------------------------------------------
# Host-side packing helpers
# ---------------------------------------------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    """Python int -> strict little-endian 12-bit limbs, int32[32]."""
    assert 0 <= x < 1 << (W * NL)
    return np.array([(x >> (W * i)) & MASK for i in range(NL)], np.int32)


def limbs_to_int(a) -> int:
    """Limb array (any relaxed representation) -> Python int value."""
    a = np.asarray(a)
    return sum(int(v) << (W * i) for i, v in enumerate(a.reshape(-1).tolist()))


def _digits(x: int, n: int) -> list[int]:
    return [(x >> (W * i)) & MASK for i in range(n)]


# ---------------------------------------------------------------------------
# Module-level tables (numpy; jnp converts on first use)
# ---------------------------------------------------------------------------

# Fold table: FOLD[i] = limbs of 2**(W*(NL+i)) mod p, for high limb NL+i.
_FOLD_HI = 64
FOLD = np.stack(
    [int_to_limbs(pow(1 << W, NL + i, P)) for i in range(_FOLD_HI)]
)  # [64, 32] int32, strict digits

# Banded-Toeplitz gather index/mask for multiplication.
_IDX = np.zeros((NL, NCOLS), np.int32)
_BANDMASK = np.zeros((NL, NCOLS), np.int32)
for _a in range(NL):
    for _c in range(NCOLS):
        _d = _c - _a
        if 0 <= _d < NL:
            _IDX[_a, _c] = _d
            _BANDMASK[_a, _c] = 1

# Saturated multiple of p for branch-free subtraction: SAT digits all in
# [LIMB_MAX, ...], value = m*p. Found by a small search.
def _saturated_multiple() -> tuple[np.ndarray, int]:
    S = sum(1 << (W * i) for i in range(NL))  # all-ones weight sum
    for m in range(10, 64):
        t = m * P - LIMB_MAX * S
        if t < 0:
            continue
        d = _digits(t, NL)
        if sum(v << (W * i) for i, v in enumerate(d)) != t:
            continue  # does not fit in 32 digits
        sat = [LIMB_MAX + v for v in d]
        if max(sat) * 2 < 2 ** 20:  # comfortably small
            return np.array(sat, np.int32), m
    raise AssertionError("no saturated multiple of p found")


SAT, _SAT_M = _saturated_multiple()
assert limbs_to_int(SAT) == _SAT_M * P

# Strict digits of 2**384 - k*p for canonical conditional subtraction.
_CSUB_KS = (8, 4, 2, 1)
CSUB = np.stack([np.array(_digits((1 << (W * NL)) - k * P, NL), np.int32)
                 for k in _CSUB_KS])

ZERO = int_to_limbs(0)
ONE = int_to_limbs(1)


# ---------------------------------------------------------------------------
# Reduction: columns -> relaxed 32-limb representative (mod p)
# ---------------------------------------------------------------------------

def _carry_round(cols, bounds):
    """One parallel carry round; widens by one limb. Exact value preserved."""
    assert all(b < 2 ** 31 for b in bounds), f"int32 overflow risk: {bounds}"
    r = cols & MASK
    c = cols >> W
    pad = [(0, 0)] * (cols.ndim - 1)
    r = jnp.pad(r, pad + [(0, 1)])
    c = jnp.pad(c, pad + [(1, 0)])
    rb = [min(b, MASK) for b in bounds] + [0]
    cb = [0] + [b >> W for b in bounds]
    return r + c, [a + b for a, b in zip(rb, cb)]


# Pallas kernels may not capture traced constants: a kernel that calls
# reduce_cols passes the FOLD table in through a ref and installs it here
# for the duration of its trace (see pallas_fp2.py). None -> the module
# table as usual.
_FOLD_OVERRIDE = None


@contextlib.contextmanager
def fold_table(table):
    """Scoped FOLD-table source override (trace-time, kernel-internal)."""
    global _FOLD_OVERRIDE
    prev = _FOLD_OVERRIDE
    _FOLD_OVERRIDE = table
    try:
        yield
    finally:
        _FOLD_OVERRIDE = prev


def _fold_round(cols, bounds):
    """Fold limbs >= NL through the 2**(12i) mod p table (exact mod p)."""
    n = len(bounds)
    k = n - NL
    assert k > 0
    lo, hi = cols[..., :NL], cols[..., NL:]
    if _FOLD_OVERRIDE is None:
        out = lo + jnp.einsum("...h,hl->...l", hi, jnp.asarray(FOLD[:k]),
                              preferred_element_type=jnp.int32)
    else:
        # inside a Pallas kernel: Mosaic has no int32 matmul, so the
        # fold is k broadcast multiply-adds on the VPU (same terms, same
        # non-negative partial sums, so the same bounds hold)
        out = lo
        for h in range(k):
            out = out + hi[..., h:h + 1] * _FOLD_OVERRIDE[h:h + 1, :]
    ob = [bounds[i] + sum(bounds[NL + h] * int(FOLD[h, i]) for h in range(k))
          for i in range(NL)]
    assert all(b < 2 ** 31 for b in ob), f"fold overflow risk: {ob}"
    return out, ob


def _fold_safe(bounds) -> bool:
    k = len(bounds) - NL
    if k <= 0:
        return False
    return all(
        bounds[i] + sum(bounds[NL + h] * int(FOLD[h, i]) for h in range(k))
        < 2 ** 31
        for i in range(NL)
    )


def reduce_cols(cols, bounds):
    """Reduce arbitrary product columns to the relaxed 32-limb form.

    ``bounds`` is a Python list of exact per-column upper bounds; the
    carry/fold schedule is chosen at trace time and asserts int32 safety
    for every intermediate.
    """
    bounds = list(bounds)
    assert cols.shape[-1] == len(bounds)
    for _ in range(32):
        if len(bounds) == NL and max(bounds) <= LIMB_MAX:
            return cols
        if _fold_safe(bounds):
            cols, bounds = _fold_round(cols, bounds)
        else:
            cols, bounds = _carry_round(cols, bounds)
    raise AssertionError(f"reduction did not converge: {bounds}")


# ---------------------------------------------------------------------------
# Field operations (all broadcast over leading dims)
# ---------------------------------------------------------------------------

_B_IN = [LIMB_MAX] * NL  # invariant bound on any input element


def add(x, y):
    return reduce_cols(x + y, [2 * LIMB_MAX] * NL)


def sub(x, y):
    return reduce_cols(x + (jnp.asarray(SAT) - y),
                       [LIMB_MAX + int(v) for v in SAT])


def neg(x):
    return reduce_cols(jnp.asarray(SAT) - x, [int(v) for v in SAT])


def mul_small(x, k: int):
    """Multiply by a small non-negative Python int (k * LIMB_MAX < 2**31)."""
    assert 0 <= k and k * LIMB_MAX < 2 ** 31
    return reduce_cols(x * k, [k * LIMB_MAX] * NL)


def _overlap(c: int, lo: int, hi: int) -> int:
    """Number of a in [lo, hi) with 0 <= c - a < NL (terms in column c)."""
    return max(0, min(c, hi - 1) - max(lo, c - (NL - 1)) + 1)


_H = NL // 2
_HALF_BOUNDS = [
    [_overlap(c, 0, _H) * LIMB_MAX ** 2 for c in range(NCOLS)],
    [_overlap(c, _H, NL) * LIMB_MAX ** 2 for c in range(NCOLS)],
]

# Exact per-column product bound for the FULL 32-term schoolbook band
# (the int8 decomposition recombines to the exact column value, so the
# full-width profile applies; peak 32 * 8191**2 = 2,146,959,392 < 2**31).
MUL_COL_BOUNDS = [_overlap(c, 0, NL) * LIMB_MAX ** 2 for c in range(NCOLS)]
assert max(MUL_COL_BOUNDS) < 2 ** 31, "full-band columns must fit int32"
# The shifted high-high partial is the largest recombination intermediate;
# machine-check it independently of the exact total.
assert (
    NL * (LIMB_MAX >> SPLIT_SHIFT) ** 2 << (2 * SPLIT_SHIFT)
) < 2 ** 31, "hh<<2S recombination must fit int32"


def band_matrix(y):
    """Gather ``y`` into the ``[..., NL, NCOLS]`` banded-Toeplitz matrix
    shared by every mul implementation."""
    return jnp.take(y, jnp.asarray(_IDX), axis=-1) * jnp.asarray(_BANDMASK)


def _mul_toeplitz_int32(x, y):
    """Banded-Toeplitz schoolbook product, split into two 16-limb dots so
    int32 accumulation cannot overflow at LIMB_MAX; each half gets one
    carry round before the halves are combined and reduced."""
    band = band_matrix(y)
    halves = []
    for i, sl in enumerate((slice(0, _H), slice(_H, NL))):
        cols = jnp.einsum("...a,...ac->...c", x[..., sl], band[..., sl, :],
                          preferred_element_type=jnp.int32)
        halves.append(_carry_round(cols, _HALF_BOUNDS[i]))
    (c0, b0), (c1, b1) = halves
    return reduce_cols(c0 + c1, [a + b for a, b in zip(b0, b1)])


def split_int8(a):
    """Stack the int8-ranged halves of limb array ``a`` on a NEW leading
    axis: ``out[0] = a >> SPLIT_SHIFT`` (<= 127), ``out[1] = a & SPLIT_MASK``
    (<= 63). Valid for any value in [0, LIMB_MAX]."""
    return jnp.stack([a >> SPLIT_SHIFT, a & SPLIT_MASK], axis=0).astype(
        jnp.int8
    )


def recombine_int8_passes(passes):
    """``passes[i, j] = (x half i) . (band half j)`` int32 columns ->
    exact product columns via shifts. Overflow-free by the module-level
    bound asserts (the recombined value equals the int32 schoolbook
    column, peak ``max(MUL_COL_BOUNDS) < 2**31``)."""
    hh, hl = passes[0, 0], passes[0, 1]
    lh, ll = passes[1, 0], passes[1, 1]
    return (
        (hh << (2 * SPLIT_SHIFT)) + ((hl + lh) << SPLIT_SHIFT) + ll
    )


def _mul_matmul_int8(x, y):
    """MXU-decomposed product: both operands split into int8 halves, all
    four half-products computed by ONE stacked ``dot_general`` over int8
    operands with int32 accumulation — the operand dtype XLA lowers to
    MXU matmul passes — then recombined with shifts. No per-half carry
    rounds are needed: the recombined columns carry the exact full-band
    bound profile (``MUL_COL_BOUNDS``) and ``reduce_cols`` derives its
    carry/fold schedule from that, machine-checked as always."""
    xs = split_int8(x)                      # [2, ..., NL] int8
    bs = split_int8(band_matrix(y))         # [2, ..., NL, NCOLS] int8
    passes = jnp.einsum(
        "i...a,j...ac->ij...c", xs, bs, preferred_element_type=jnp.int32
    )
    return reduce_cols(recombine_int8_passes(passes), MUL_COL_BOUNDS)


def _mul_pallas_int8(x, y):
    """The int8 decomposition as a hand-placed Pallas kernel (see
    ``pallas_fp.py``) for when the dot_general lowering refuses to leave
    the VPU; interpreted on the CPU so it stays differential-testable."""
    from . import pallas_fp

    return reduce_cols(pallas_fp.mul_cols_int8(x, y), MUL_COL_BOUNDS)


# ---------------------------------------------------------------------------
# Implementation switch (env-selectable, like crypto/backend.py's backend)
# ---------------------------------------------------------------------------

IMPL_TOEPLITZ_INT32 = "toeplitz_int32"
IMPL_MATMUL_INT8 = "matmul_int8"
IMPL_PALLAS_INT8 = "pallas_int8"

_MUL_IMPLS = {
    IMPL_TOEPLITZ_INT32: _mul_toeplitz_int32,
    IMPL_MATMUL_INT8: _mul_matmul_int8,
    IMPL_PALLAS_INT8: _mul_pallas_int8,
}

_active_impl = os.environ.get("LIGHTHOUSE_TPU_FP_IMPL", IMPL_TOEPLITZ_INT32)
if _active_impl not in _MUL_IMPLS:
    raise KeyError(
        f"LIGHTHOUSE_TPU_FP_IMPL={_active_impl!r} unknown; "
        f"have {sorted(_MUL_IMPLS)}"
    )


def get_impl() -> str:
    return _active_impl


def set_impl(name: str) -> None:
    """Select the fp.mul implementation. Dispatch happens at TRACE time:
    callers holding jitted programs (e.g. device/bls.py's staged pipeline)
    must call ``device.reset_compiled_state()`` afterwards or they keep
    the old kernels (and stale warm-shape routing)."""
    global _active_impl
    if name not in _MUL_IMPLS:
        raise KeyError(f"unknown fp impl {name!r}; have {sorted(_MUL_IMPLS)}")
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


def mul(x, y):
    """Schoolbook product mod p under the active implementation — the
    single funnel every fp2/fp6/fp12/curve/pairing multiply drains into."""
    return _MUL_IMPLS[_active_impl](x, y)


def sq(x):
    return mul(x, x)


# ---------------------------------------------------------------------------
# Canonicalization and predicates
# ---------------------------------------------------------------------------

def _seq_carry(cols):
    """Exact sequential carry over limbs -> (strict digits, carry_out)."""
    x = jnp.moveaxis(cols, -1, 0)

    def body(carry, col):
        s = col + carry
        return s >> W, s & MASK

    carry_out, digits = lax.scan(body, jnp.zeros(x.shape[1:], x.dtype), x)
    return jnp.moveaxis(digits, 0, -1), carry_out


def canonical(x):
    """Unique strict representative in [0, p), digits in [0, 4095]."""
    d, c = _seq_carry(x)
    # Relaxed values are < LIMB_MAX * sum(2^(12i)) < 2.0003 * 2**384, so the
    # first carry-out is at most 2; two fold-and-recarry rounds bring the
    # value strictly below 2**384 (each round: v -> v mod 2**384 + c * (2**384
    # mod p), and 2**384 mod p < 2**381).
    for _ in range(2):
        d = d + c[..., None] * jnp.asarray(FOLD[0])
        d, c = _seq_carry(d)
    # Now x < 2**384 < 16p: conditional cascade subtract 8p, 4p, 2p, p.
    for i in range(len(_CSUB_KS)):
        s, c = _seq_carry(d + jnp.asarray(CSUB[i]))
        d = jnp.where((c == 1)[..., None], s, d)
    return d


def is_zero(x):
    """Boolean [...] mask: value == 0 mod p."""
    return jnp.all(canonical(x) == 0, axis=-1)


def eq(x, y):
    return jnp.all(canonical(x) == canonical(y), axis=-1)


def select(mask, a, b):
    """mask [...] bool -> elementwise field select."""
    return jnp.where(mask[..., None], a, b)


# ---------------------------------------------------------------------------
# Exponentiation (fixed Python-int exponent) and inversion
# ---------------------------------------------------------------------------

def _bits_msb(e: int) -> np.ndarray:
    return np.array([int(b) for b in bin(e)[2:]], np.int32)


def _digits_msb(e: int, w: int) -> list[int]:
    """``e`` in bijective base 2**w (digits 1..2**w), most significant
    first. No digit is 0, so the ladder's table needs no one."""
    base, out = 1 << w, []
    while e:
        d = e % base or base
        out.append(d)
        e = (e - d) >> w
    return out[::-1]


def _window_muls(e: int, w: int) -> int:
    """Multiplies of the w-bit window ladder: 2**w - 1 to build the table
    x, x^2, ..., x^(2**w), one per digit after the leading one."""
    return (1 << w) - 1 + len(_digits_msb(e, w)) - 1


def window_width(e: int) -> int:
    """Window of the ladder for exponent ``e``: the ``w`` with the fewest
    multiplies, or 1 (the bit ladder, one multiply per bit after the
    first) where no window needs fewer. A pure function of ``e``."""
    n = e.bit_length()
    best = min(range(2, n.bit_length() + 1),
               key=lambda w: _window_muls(e, w), default=1)
    return best if best > 1 and _window_muls(e, best) < n - 1 else 1


def ladder_counts(e: int) -> tuple[int, int]:
    """(squarings, multiplies) that ``square_multiply`` spends on ``e``."""
    w = window_width(e)
    if w == 1:
        return (e.bit_length() - 1,) * 2
    return w * (len(_digits_msb(e, w)) - 1), _window_muls(e, w)


def square_multiply(x, e: int, sq_fn, mul_fn):
    """Shared fixed-exponent ladder (MSB first), for every pow_const in
    the device stack (fp/fp2/fp12) and ``htc.f2pow``. ``e`` must be >= 1.

    A fixed window of ``w = window_width(e)`` bits, run as one scan over a
    schedule fixed at trace time. The carry is a table ``T`` with
    ``T[i] = x^(i+1)`` for ``i < 2**w`` and the accumulator in
    ``T[2**w]``; each step sets ``T[dst] = T[src]^(2^nsq) * T[fac]``. The
    first ``2**w - 1`` steps fill the table (``nsq = 0``, ``fac = 0``);
    then each base-2**w digit of ``e`` after the leading one costs ``w``
    squarings and one multiply by the entry it indexes. ``sq_fn`` and
    ``mul_fn`` are emitted once each. At 381 bits that is w = 5 and 107
    multiplies in place of 380. Short exponents keep the bit ladder:
    square, then multiply and keep it where the bit is set. Every lane
    does the same work; x == 0 gives 0.
    """
    assert e >= 1
    w = window_width(e)
    if w == 1:
        bits = _bits_msb(e)
        if len(bits) == 1:
            return x

        def bit_step(acc, bit):
            acc = sq_fn(acc)
            return jnp.where(bit == 1, mul_fn(acc, x), acc), None

        acc, _ = lax.scan(bit_step, x, jnp.asarray(bits[1:]))
        return acc

    acc_slot = 1 << w
    digits = _digits_msb(e, w)
    schedule = [(j - 1, 0, 0, j) for j in range(1, acc_slot)]
    schedule.append((digits[0] - 1, w, digits[1] - 1, acc_slot))
    schedule += [(acc_slot, w, d - 1, acc_slot) for d in digits[2:]]

    def step(t, s):
        src, nsq, fac, dst = s
        acc = lax.fori_loop(0, nsq, lambda _, a: sq_fn(a),
                            lax.dynamic_index_in_dim(t, src, keepdims=False))
        acc = mul_fn(acc, lax.dynamic_index_in_dim(t, fac, keepdims=False))
        return lax.dynamic_update_index_in_dim(t, acc, dst, 0), None

    table = jnp.broadcast_to(x, (acc_slot + 1, *x.shape))
    table, _ = lax.scan(step, table, jnp.asarray(schedule, np.int32))
    return table[acc_slot]


def pow_const(x, e: int):
    """x**e for a fixed exponent (the shared window ladder)."""
    return square_multiply(x, e, sq, mul)


def inv(x):
    """Fermat inverse x**(p-2); inv(0) = 0 (callers mask separately)."""
    return pow_const(x, P - 2)


# ---------------------------------------------------------------------------
# Constants / conversion on device
# ---------------------------------------------------------------------------

def const(v: int):
    """Embed a fixed field value (shape [32]; broadcasts against batches)."""
    return jnp.asarray(int_to_limbs(v % P))


def zeros(shape=()):
    return jnp.zeros((*shape, NL), jnp.int32)


def ones(shape=()):
    return jnp.broadcast_to(jnp.asarray(ONE), (*shape, NL)).astype(jnp.int32)
