"""TPU-native batched BLS signature-set verification.

This is the device half of the north-star seam: the reference's
``verify_signature_sets`` (``/root/reference/crypto/bls/src/impls/blst.rs:36-119``)
re-designed as one fixed-shape, branch-free JAX program:

    per set i (batch lane i):
      agg_pk_i = sum of the set's pubkeys          (masked Jacobian sum)
      sig subgroup check: psi(sig) == [x] sig      (64-bit scan)
      r_i agg_pk_i, r_i sig_i                      (64-bit random scalars)
    sig_acc = sum_i r_i sig_i                      (scan reduction)
    ok = FE( prod_i ML(r_i agg_pk_i, H(m_i)) * ML(-g1, sig_acc) ) == 1
         AND all subgroup checks

The batch dimension is the data-parallel axis the reference spreads over
rayon cores (``block_signature_verifier.rs:374-382``); here it is the
device batch axis, shardable over chips via ``jax.sharding`` (see
``__graft_entry__.dryrun_multichip`` for the dp x tp mesh layout).

Shapes (B sets, K max pubkeys/set):
  pk_xy  int32[B, K, 2, 32]   pk_mask bool[B, K]
  sig_xy int32[B, 2, 2, 32]   (x, y) each Fp2
  msg_xy int32[B, 2, 2, 32]   H(m) on G2 (hash-to-curve)
  rand   int32[B, 64]          MSB-first nonzero 64-bit scalars
  set_mask bool[B]             False = padding lane (must not affect result)

Host-side padding/bucketing, randomness, and the reference's edge
semantics (empty batch / empty set / infinity signature => False) live in
:class:`TpuBackend` below.
"""

from __future__ import annotations

import secrets
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from ...utils import (
    fault_injection,
    flight_recorder,
    metrics,
    pipeline_profiler,
    tracing,
    transfer_ledger,
)
from ..params import DST, G1_X, G1_Y, P, R, X
from ..cpu.pairing import PSI_CX, PSI_CY
from ..cpu.hash_to_curve import hash_to_g2
from . import curve, fp, fp2, msm as msm_mod, pairing, tower
from .pairing import X_ABS
from .scopes import scoped

# psi constants (public, derived from xi; see cpu/pairing.py:22-27).
_PSI_CX = (PSI_CX.c0.n, PSI_CX.c1.n)
_PSI_CY = (PSI_CY.c0.n, PSI_CY.c1.n)

# -g1 generator, embedded as constants.
_NEG_G1 = (G1_X, (P - G1_Y) % P)


def _psi_jacobian(pt):
    """Untwist-Frobenius-twist endomorphism in Jacobian coords:
    (X, Y, Z) -> (conj(X) CX, conj(Y) CY, conj(Z))."""
    x, y, z = pt
    return (
        fp2.mul(fp2.conjugate(x), fp2.const(*_PSI_CX)),
        fp2.mul(fp2.conjugate(y), fp2.const(*_PSI_CY)),
        fp2.conjugate(z),
    )


def g2_in_subgroup(pt):
    """Scott's membership test for G2 on BLS12-381: Q in G2 iff
    psi(Q) == [x]Q (eigenvalue x of psi on the r-torsion; verified against
    the full [r]Q == O check in tests). Infinity passes."""
    xq = curve.scalar_mul_const(fp2, pt, X_ABS)
    xq = curve.neg(fp2, xq)  # x < 0
    return curve.eq(fp2, _psi_jacobian(pt), xq) | curve.is_infinity(fp2, pt)


def _bits64(r):
    """int32[..., 2] (hi, lo) -> MSB-first bits int32[..., 64]."""
    hi, lo = r[..., 0], r[..., 1]
    shifts = jnp.arange(31, -1, -1, dtype=jnp.int32)
    hb = (hi[..., None] >> shifts) & 1
    lb = (lo[..., None] >> shifts) & 1
    return jnp.concatenate([hb, lb], axis=-1)


_XBITS64 = np.array(
    [(X_ABS >> (63 - i)) & 1 for i in range(64)], np.int32
)
assert X_ABS.bit_length() == 64


def _verify_core(pk_xy, pk_mask, sig_xy, msg_aff, rand_bits, set_mask):
    """Shared verification core; ``msg_aff = (x, y, inf)`` are the hashed
    messages in G2 affine, one per lane."""
    B = pk_xy.shape[0]

    # --- aggregate pubkeys per set (masked sum over the K axis) ---------
    pk_pts = curve.from_affine(
        fp, pk_xy[..., 0, :], pk_xy[..., 1, :], ~pk_mask
    )
    agg_pk = curve.sum_points(fp, pk_pts, axis=1)  # [B] G1 Jacobian

    # --- signatures: subgroup check + random scaling --------------------
    # The subgroup check's [x]Q and the randomizer's [r]Q run through ONE
    # emitted double-and-add body: stack the points [2B] with per-lane
    # bit rows (constant |x| bits for the first half) — compile-size
    # lever (one scan body instead of two).
    sig_pts = curve.from_affine(fp2, sig_xy[..., 0, :, :], sig_xy[..., 1, :, :])
    bits = _bits64(rand_bits) if rand_bits.shape[-1] == 2 else rand_bits
    xbits = jnp.broadcast_to(jnp.asarray(_XBITS64), (B, 64))
    both = curve.scalar_mul_bits(
        fp2,
        tuple(jnp.concatenate([c, c], axis=0) for c in sig_pts),
        jnp.concatenate([xbits, bits], axis=0),
    )
    xq = tuple(c[:B] for c in both)                      # [|x|]Q
    r_sig = tuple(c[B:] for c in both)                   # [r]Q
    xq = curve.neg(fp2, xq)                              # x < 0
    sub_ok = (
        curve.eq(fp2, _psi_jacobian(sig_pts), xq)
        | curve.is_infinity(fp2, sig_pts)
        | ~set_mask
    )
    subgroup_ok = jnp.all(sub_ok)

    r_pk = curve.scalar_mul_bits(fp, agg_pk, bits)       # [B] G1

    # padding lanes must not contribute to the signature accumulator
    inf2 = curve.infinity(fp2)
    r_sig = curve.select(
        fp2, set_mask, r_sig,
        tuple(jnp.broadcast_to(c, o.shape) for c, o in zip(inf2, r_sig)),
    )
    sig_acc = curve.sum_points(fp2, r_sig, axis=0)       # single G2

    # --- assemble the multi-pairing: B lanes + the accumulator lane -----
    pk_x, pk_y, pk_inf = curve.to_affine(fp, r_pk)
    # padding lanes: force G1 point to infinity => Miller value 1
    pk_inf = pk_inf | ~set_mask

    g1_x = jnp.concatenate([pk_x, fp.const(_NEG_G1[0])[None]], axis=0)
    g1_y = jnp.concatenate([pk_y, fp.const(_NEG_G1[1])[None]], axis=0)
    g1_inf = jnp.concatenate([pk_inf, jnp.zeros((1,), bool)], axis=0)

    msg_x, msg_y, msg_inf = msg_aff
    acc_x, acc_y, acc_inf = curve.to_affine(fp2, sig_acc)
    g2_x = jnp.concatenate([msg_x, acc_x[None]], axis=0)
    g2_y = jnp.concatenate([msg_y, acc_y[None]], axis=0)
    g2_inf = jnp.concatenate([msg_inf, acc_inf[None]], axis=0)

    pairing_ok = pairing.multi_pairing_is_one(
        (g1_x, g1_y, g1_inf), (g2_x, g2_y, g2_inf)
    )

    # a real lane whose aggregate pubkey degenerated to infinity (e.g. sum
    # of pubkeys cancels) must fail rather than silently contribute 1
    agg_inf_bad = jnp.any(curve.is_infinity(fp, agg_pk) & set_mask)

    return pairing_ok & subgroup_ok & ~agg_inf_bad


def _fp_gt(a_digits, b_digits):
    """Strict canonical digits [..., NL] -> a > b (big-endian lexicographic:
    the most significant differing limb decides)."""
    diff = a_digits != b_digits
    gt = a_digits > b_digits
    idx = jnp.arange(fp.NL, dtype=jnp.int32)
    msd = jnp.max(jnp.where(diff, idx + 1, 0), axis=-1)  # 0 == all equal
    pick = jnp.take_along_axis(
        gt, jnp.maximum(msd - 1, 0)[..., None], axis=-1
    )[..., 0]
    return (msd > 0) & pick


@scoped("decompress")
def _decompress_pre(sig_x):
    """g(x) = x^3 + 4(1+u) — the radicand awaiting a sqrt ladder."""
    b2 = jnp.broadcast_to(fp2.const(4, 4), sig_x.shape).astype(jnp.int32)
    return fp2.add(fp2.mul(fp2.sq(sig_x), sig_x), b2)


@scoped("decompress")
def _decompress_post(sign_larger, y, ok):
    """Sign selection by the compressed flag's lexicographic-larger rule;
    ``y, ok`` are the sqrt outputs for ``_decompress_pre``'s radicand."""
    yc = fp2.canonical(y)
    neg_y = fp2.neg(y)
    negc = fp2.canonical(neg_y)
    c1_gt = _fp_gt(yc[..., 1, :], negc[..., 1, :])
    c1_eq = jnp.all(yc[..., 1, :] == negc[..., 1, :], axis=-1)
    c0_gt = _fp_gt(yc[..., 0, :], negc[..., 0, :])
    y_is_larger = c1_gt | (c1_eq & c0_gt)
    y_final = fp2.select(y_is_larger == sign_larger, y, neg_y)
    return y_final, ok


def decompress_g2(sig_x, sign_larger):
    """Device G2 decompression (the ~10 ms/signature host cost the gossip
    pipeline used to pay in pure Python): y = sqrt(x^3 + 4(1+u)), sign
    chosen by the compressed flag's lexicographic-larger rule.

    sig_x: fp2 [..., 2, NL]; sign_larger: bool [...]. -> (y, ok) where
    ``ok`` is False for x not on the curve."""
    from . import htc

    y, ok = htc.sqrt(_decompress_pre(sig_x))
    return _decompress_post(sign_larger, y, ok)


def verify_batch_raw_fn(
    pk_xy, pk_mask, sig_x, sig_larger, msg_u, msg_idx, rand_bits, set_mask
):
    """THE flagship program: raw compressed signatures + raw
    hash_to_field outputs in, verdict out. The host does byte wrangling
    only; decompression, hashing-to-curve, aggregation, subgroup checks
    and the multi-pairing all run on device.

    The signature-decompression square root and the 4M SSWU candidate
    square roots share ONE ladder (stacked [B + 4M] batch) — the two
    f2pow scans are the largest repeated body in the program."""
    from . import htc

    B = sig_x.shape[0]
    M = msg_u.shape[0]

    gx_sig = _decompress_pre(sig_x)                    # [B, 2, NL]
    x1, x2, g = htc.sswu_pre(msg_u)                    # g [M, 2, 2, 2, NL]
    stacked = jnp.concatenate(
        [gx_sig, g.reshape(4 * M, 2, fp.NL)], axis=0
    )
    roots, root_ok = htc.sqrt(stacked)                 # ONE shared ladder
    y, sig_ok = _decompress_post(
        sig_larger, roots[:B], root_ok[:B]
    )
    sig_xy = jnp.stack([sig_x, y], axis=1)  # [B, 2(x|y), 2, NL]

    msg_pts = htc.map_to_g2_post(
        msg_u,
        x1,
        x2,
        roots[B:].reshape(M, 2, 2, 2, fp.NL),
        root_ok[B:].reshape(M, 2, 2),
    )
    mx, my, minf = curve.to_affine(fp2, msg_pts)
    msg_aff = (
        jnp.take(mx, msg_idx, axis=0),
        jnp.take(my, msg_idx, axis=0),
        jnp.take(minf, msg_idx, axis=0),
    )
    core = _verify_core(pk_xy, pk_mask, sig_xy, msg_aff, rand_bits, set_mask)
    return core & jnp.all(sig_ok | ~set_mask)


def verify_batch_fn(pk_xy, pk_mask, sig_xy, msg_xy, rand_bits, set_mask):
    """One-shot device program over pre-hashed message points. Returns a
    scalar bool: True iff every real lane's set verifies."""
    B = pk_xy.shape[0]
    msg_aff = (msg_xy[:, 0], msg_xy[:, 1], jnp.zeros((B,), bool))
    return _verify_core(pk_xy, pk_mask, sig_xy, msg_aff, rand_bits, set_mask)


def verify_batch_hashed_fn(pk_xy, pk_mask, sig_xy, msg_u, msg_idx, rand_bits, set_mask):
    """END-TO-END device program: raw hash_to_field outputs in, verdict
    out. ``msg_u`` int32[M, 2, 2, NL] holds the unique messages' field
    elements; ``msg_idx`` int32[B] maps each lane to its message — dedup
    mirrors the reference's per-distinct-AttestationData hashing, but the
    hashing itself is the batched device map (see ``device/htc.py``)."""
    from . import htc

    msg_pts = htc.map_to_g2(msg_u)                       # [M] Jacobian
    mx, my, minf = curve.to_affine(fp2, msg_pts)
    msg_aff = (
        jnp.take(mx, msg_idx, axis=0),
        jnp.take(my, msg_idx, axis=0),
        jnp.take(minf, msg_idx, axis=0),
    )
    return _verify_core(pk_xy, pk_mask, sig_xy, msg_aff, rand_bits, set_mask)


verify_batch = jax.jit(verify_batch_fn)
verify_batch_hashed = jax.jit(verify_batch_hashed_fn)
verify_batch_raw = jax.jit(verify_batch_raw_fn)


# ---------------------------------------------------------------------------
# Staged pipeline: the same program as verify_batch_raw_fn split into three
# independently-jitted stages. Identical results; intermediate arrays stay
# on device. Motivation is COMPILE time (VERDICT r4 item #1): XLA's cost is
# superlinear-ish in program size, so three ~30k-HLO-line programs compile
# in roughly half the wall-clock of one ~90k-line program, cache
# independently in the persistent compile cache, and let a shape bump in
# one stage (e.g. more unique messages M) recompile only that stage.
# ---------------------------------------------------------------------------

@scoped("bls.stage1")
def _stage1_fn(sig_x, sig_larger, msg_u):
    """Decompression + hash-to-curve (all square roots in one ladder)."""
    from . import htc

    B = sig_x.shape[0]
    M = msg_u.shape[0]
    gx_sig = _decompress_pre(sig_x)
    x1, x2, g = htc.sswu_pre(msg_u)
    stacked = jnp.concatenate([gx_sig, g.reshape(4 * M, 2, fp.NL)], axis=0)
    roots, root_ok = htc.sqrt(stacked)
    y, sig_ok = _decompress_post(sig_larger, roots[:B], root_ok[:B])
    sig_xy = jnp.stack([sig_x, y], axis=1)
    msg_pts = htc.map_to_g2_post(
        msg_u,
        x1,
        x2,
        roots[B:].reshape(M, 2, 2, 2, fp.NL),
        root_ok[B:].reshape(M, 2, 2),
    )
    with jax.named_scope("to_affine"):
        mx, my, minf = curve.to_affine(fp2, msg_pts)
    return sig_xy, mx, my, minf, sig_ok


@scoped("bls.stage2")
def _stage2_fn(pk_xy, pk_mask, sig_xy, rand_bits, set_mask):
    """Aggregation + subgroup checks + random scaling -> affine pairing
    inputs for the G1 side and the G2 signature accumulator."""
    B = pk_xy.shape[0]
    with jax.named_scope("key_sum"):
        pk_pts = curve.from_affine(
            fp, pk_xy[..., 0, :], pk_xy[..., 1, :], ~pk_mask
        )
        agg_pk = curve.sum_points(fp, pk_pts, axis=1)

    sig_pts = curve.from_affine(fp2, sig_xy[..., 0, :, :], sig_xy[..., 1, :, :])
    bits = _bits64(rand_bits) if rand_bits.shape[-1] == 2 else rand_bits
    xbits = jnp.broadcast_to(jnp.asarray(_XBITS64), (B, 64))
    # one ladder serves the subgroup check's [|x|]Q and the random [r]Q
    with jax.named_scope("g2_ladder"):
        both = curve.scalar_mul_bits(
            fp2,
            tuple(jnp.concatenate([c, c], axis=0) for c in sig_pts),
            jnp.concatenate([xbits, bits], axis=0),
        )
    xq = curve.neg(fp2, tuple(c[:B] for c in both))
    r_sig = tuple(c[B:] for c in both)
    with jax.named_scope("psi_check"):
        sub_ok = (
            curve.eq(fp2, _psi_jacobian(sig_pts), xq)
            | curve.is_infinity(fp2, sig_pts)
            | ~set_mask
        )
        subgroup_ok = jnp.all(sub_ok)

    with jax.named_scope("g1_ladder"):
        r_pk = curve.scalar_mul_bits(fp, agg_pk, bits)
    inf2 = curve.infinity(fp2)
    r_sig = curve.select(
        fp2, set_mask, r_sig,
        tuple(jnp.broadcast_to(c, o.shape) for c, o in zip(inf2, r_sig)),
    )
    sig_acc = curve.sum_points(fp2, r_sig, axis=0)

    pk_x, pk_y, pk_inf = curve.to_affine(fp, r_pk)
    pk_inf = pk_inf | ~set_mask
    acc_x, acc_y, acc_inf = curve.to_affine(fp2, sig_acc)
    agg_inf_bad = jnp.any(curve.is_infinity(fp, agg_pk) & set_mask)
    return pk_x, pk_y, pk_inf, acc_x, acc_y, acc_inf, subgroup_ok & ~agg_inf_bad


@scoped("bls.stage3")
def _stage3_fn(pk_x, pk_y, pk_inf, msg_aff_x, msg_aff_y, msg_aff_inf,
               acc_x, acc_y, acc_inf):
    """The multi-pairing decision over B+1 lanes."""
    g1_x = jnp.concatenate([pk_x, fp.const(_NEG_G1[0])[None]], axis=0)
    g1_y = jnp.concatenate([pk_y, fp.const(_NEG_G1[1])[None]], axis=0)
    g1_inf = jnp.concatenate([pk_inf, jnp.zeros((1,), bool)], axis=0)
    g2_x = jnp.concatenate([msg_aff_x, acc_x[None]], axis=0)
    g2_y = jnp.concatenate([msg_aff_y, acc_y[None]], axis=0)
    g2_inf = jnp.concatenate([msg_aff_inf, acc_inf[None]], axis=0)
    return pairing.multi_pairing_is_one(
        (g1_x, g1_y, g1_inf), (g2_x, g2_y, g2_inf)
    )


@scoped("bls.gather")
def _gather_fn(table, agg, pk_idx):
    """Device-side pubkey gather (ISSUE 10): the static packer ships a
    ``(B, K)`` int32 index plane and this stage materializes the
    ``[B, K, 2, NL]`` limb planes from the device-resident key table —
    the pack's dominant operand (87–94% of H2D bytes at committee
    rungs, COST_MODEL.md) never crosses the host-device boundary again.
    Indices below ``table.shape[0]`` address the validator mirror;
    indices at/above it address the small aggregate-sum region ``agg``
    (cached epoch-stable committee sums, key_table.py) — two clipped
    takes and a select, so the regions stay separate device arrays and
    an aggregate insert never copies the big table.

    Runs as its own staged program ("gather", through ``_run_stage``)
    ahead of stage 2 rather than fused into stage 1's ~31k-HLO body:
    the table argument keys the compile on the table CAPACITY rung
    (key_table.CAPACITY_LADDER), and a table-growth recompile of this
    one-op program is sub-second while a stage-1 variant would re-pay a
    multi-minute XLA compile per capacity step. The gathered output
    feeds the UNCHANGED stage-2 program, so every warm stage-1/2/3 rung
    stays warm across table growth. Masked lanes gather row 0 — a REAL
    key's coordinates, unlike the raw packer's zero-filled padding rows
    — which is safe only because stage 2's ``from_affine(..., ~pk_mask)``
    forces masked lanes to infinity regardless of coordinates; nothing
    may come to rely on masked gather lanes holding invalid points."""
    B, K = pk_idx.shape
    flat_idx = pk_idx.reshape(-1)
    base = table.shape[0]
    from_val = jnp.take(table, jnp.clip(flat_idx, 0, base - 1), axis=0)
    from_agg = jnp.take(
        agg, jnp.clip(flat_idx - base, 0, agg.shape[0] - 1), axis=0
    )
    rows = jnp.where((flat_idx < base)[:, None, None], from_val, from_agg)
    return rows.reshape(B, K, *table.shape[1:])


_stage1 = jax.jit(_stage1_fn)
_stage2 = jax.jit(_stage2_fn)
_stage3 = jax.jit(_stage3_fn)
_gather = jax.jit(_gather_fn)
# MSM family (ISSUE 16): small independent programs keyed on their own
# N rung — they never disturb the warm stage-1/2/3 shapes.
_msm = jax.jit(msm_mod.msm_g1_fn)
_g2sum = jax.jit(msm_mod.sum_g2_fn)


# ---------------------------------------------------------------------------
# Hot-path telemetry (reference: beacon_chain/src/metrics.rs label-vector
# families). Per-stage wall time is measured dispatch-to-sync
# (block_until_ready): attribution needs the sync boundary, at the cost of
# host dispatch no longer running ahead of the device between stages —
# three extra host-device round trips per batch, microseconds against
# stage bodies that run for hundreds of milliseconds of device work.
# ---------------------------------------------------------------------------

_STAGE_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)
_STAGE_SECONDS = metrics.histogram_vec(
    "bls_device_stage_seconds",
    "staged device BLS verifier: per-stage wall time, dispatch to device "
    "sync (first observation per shape includes jit compile)",
    ("stage", "fp_impl"),
    buckets=_STAGE_BUCKETS,
)
_VERIFY_SECONDS = metrics.histogram_vec(
    "bls_device_verify_seconds",
    "end-to-end verify_signature_sets wall time (pack + all stages)",
    ("path", "fp_impl"),
    buckets=_STAGE_BUCKETS,
)
# bls_device_pack_seconds became a phase-labeled family owned by the
# data-movement ledger (utils/transfer_ledger.py, ISSUE 8): the raw
# packer observes decode/limb_split/pad/hash/device_put + total; the
# non-instrumented packers observe total only via this handle.
_PACK_TOTAL = transfer_ledger.PACK_SECONDS.with_labels("total")
_RECOMPILES = metrics.counter_vec(
    "bls_device_recompiles_total",
    "fresh (shape, dtype, fp_impl) argument signatures per staged program "
    "— each one costs an XLA compile, assuming callers follow the "
    "fp.set_impl contract (fp.py): switch impls only through "
    "device.reset_compiled_state()",
    ("stage",),
)
_LANES = metrics.counter_vec(
    "bls_device_batch_lanes_total",
    "batch geometry: requested vs padded lane counts per dimension "
    "(B sets, K pubkey slots, M unique messages)",
    ("dim", "kind"),
)
_PAD_WASTE = metrics.gauge(
    "bls_device_padding_waste_ratio",
    "1 - live lanes / padded lanes (B*K*M) for the most recent packed "
    "batch — the SAME formula as verification_scheduler_padding_waste_"
    "ratio (verification_service/planner.py; formula equality pinned "
    "by test). Values differ under a planned multi-sub-batch flush: "
    "this gauge holds the LAST packed batch, the scheduler gauge the "
    "whole plan",
)
_OUTCOMES = metrics.counter_vec(
    "bls_device_verify_outcomes_total",
    "verify_signature_sets verdicts (rejected = host pre-screen)",
    ("outcome",),
)

_seen_stage_shapes: set = set()
_seen_lock = threading.Lock()


def reset_recompile_tracking() -> None:
    """Forget seen argument signatures. Callers should not pair this
    with ``jax.clear_caches()`` by hand anymore — use
    ``device.reset_compiled_state()`` (crypto/device/__init__.py), which
    also invalidates the compile service's warm-shape registry: XLA will
    recompile every program, and the recompile counter should see the
    next dispatches as fresh rather than silently absorbing the cost."""
    with _seen_lock:
        _seen_stage_shapes.clear()


def _active_compile_service():
    """The process-global CompileService when one is attached and
    running (compile_service/service.py) — the warm-shape router the
    packers pad against. Lazy import: the service package is jax-free,
    but this module must not depend on it at import time."""
    from ...compile_service import service as _csvc

    return _csvc.get_active_service()


def _run_stage(stage: str, fn, *args):
    """One staged dispatch: recompile accounting keyed on the argument
    (shape, dtype, fp_impl, mesh shard) signature — a jitted program
    compiled for one chip is a FRESH compile on another (ISSUE 11) —
    span + labeled wall-time histogram closed at the device sync
    boundary. Returns ``(out, elapsed_s, fresh)`` so the caller can
    journal per-stage attribution."""
    from . import mesh as _mesh_mod

    # chaos seam (ISSUE 13): an armed `staged_dispatch` fault point
    # raises (or stalls) here — inside the sharded dispatch scope, so
    # the scheduler's failover/watchdog/probation machinery sees it
    # exactly where a real chip failure would surface
    fault_injection.fire("staged_dispatch")
    impl = fp.get_impl()
    shard = _mesh_mod.current_shard() or 0
    key = (
        stage,
        impl,
        # upper-layer engine seams (ISSUE 16): a fused fp2 kernel or a
        # restructured line-eval step is a different traced program, so
        # switching either makes the next dispatch a fresh compile
        fp2.get_impl(),
        pairing.get_line_impl(),
        shard,
        tuple((tuple(a.shape), str(a.dtype)) for a in args),
    )
    # shard attr: the dp mesh device the stage ran on
    with tracing.span(f"bls.{stage}", fp_impl=impl, shard=shard):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        elapsed = time.perf_counter() - t0
        _STAGE_SECONDS.with_labels(stage, impl).observe(elapsed)
    # seen only after a SUCCESSFUL dispatch: a failed first compile must
    # not consume the signature's fresh tick (the retry pays the compile)
    with _seen_lock:
        fresh = key not in _seen_stage_shapes
        if fresh:
            _seen_stage_shapes.add(key)
    if fresh:
        _RECOMPILES.with_labels(stage).inc()
    # pipeline profiler (ISSUE 12): this dispatch-to-sync wall is a
    # device BUSY interval on its shard; the gap since the shard's
    # previous sync is a bubble, attributed to pack/plan/compile/
    # queue_empty/other. A fresh dispatch's wall includes the XLA
    # compile, so it is also recorded as compile activity.
    pipeline_profiler.note_stage_wall(
        stage, shard, t0, t0 + elapsed, fresh=fresh
    )
    return out, elapsed, fresh


def stage_latency_summary(impl: str | None = None) -> dict:
    """Rows of {fp_impl, p50_s, p99_s, mean_s, count} read from the
    ``bls_device_stage_seconds`` family — the one reader bench.py and
    tools/trace_report.py share. With ``impl`` the rows are keyed by
    stage; with ``impl=None`` every engine is reported, keyed
    ``stage:fp_impl`` so one engine cannot shadow another. Quantiles are
    histogram-bucket upper bounds (None = beyond the top bucket); count
    says how many dispatches (compiles included) each row aggregates.

    Also reports the END-TO-END ``bls_device_verify_seconds`` rows
    (keyed ``verify:<path>``): the verdict-latency SLO layer
    (docs/TRAFFIC_REPLAY.md) attributes a deadline miss by holding the
    scheduler's submit-to-verdict tail against this device-side
    pack+dispatch tail — if the device p99 explains the miss, the fix
    is batch shape/compile warmth, not queueing."""
    import math

    def _finite(q):
        return q if math.isfinite(q) else None  # keep the JSON strict

    def _row(child, child_impl):
        total, sum_, _cum = child.snapshot()
        if not total:
            return None
        return {
            "fp_impl": child_impl,
            "p50_s": _finite(child.quantile(0.5)),
            "p99_s": _finite(child.quantile(0.99)),
            "mean_s": round(sum_ / total, 4),
            "count": total,
        }

    out = {}
    for (stage, child_impl), child in sorted(_STAGE_SECONDS.children().items()):
        if impl is not None and child_impl != impl:
            continue
        row = _row(child, child_impl)
        if row:
            out[stage if impl is not None else f"{stage}:{child_impl}"] = row
    for (path, child_impl), child in sorted(_VERIFY_SECONDS.children().items()):
        if impl is not None and child_impl != impl:
            continue
        row = _row(child, child_impl)
        if row:
            key = (
                f"verify:{path}"
                if impl is not None
                else f"verify:{path}:{child_impl}"
            )
            out[key] = row
    # host-pack phase attribution (data-movement ledger, ISSUE 8): the
    # pack family is phase-labeled, engine-independent — the rows ride
    # along keyed pack:<phase> so bench/trace readers see where host
    # pack time goes next to the device stage split
    for (phase,), child in sorted(
        transfer_ledger.PACK_SECONDS.children().items()
    ):
        row = _row(child, "-")
        if row:
            row.pop("fp_impl", None)
            out[f"pack:{phase}"] = row
    # device idle-gap attribution (pipeline profiler, ISSUE 12): the
    # bubble rows ride along keyed bubble:<cause> so bench/trace
    # readers see where device idle went next to the stage and pack
    # splits (sum_s/count/mean_s — counters, not histograms: no
    # quantiles to report)
    for cause, row in pipeline_profiler.bubble_rows().items():
        out[f"bubble:{cause}"] = row
    return out


def verify_batch_raw_staged(
    pk_xy, pk_mask, sig_x, sig_larger, msg_u, msg_idx, rand_bits, set_mask
):
    """Staged equivalent of ``verify_batch_raw`` (same inputs, same
    verdict): three device dispatches, intermediates stay on device.
    Each call journals one ``bls_stage_verify`` flight-recorder event
    (batch geometry, fp_impl, per-stage dispatch-to-sync seconds,
    verdict, recompile flag); a False verdict triggers
    ``dump_on_failure`` so the surrounding context is preserved."""
    return _staged_verify(
        pk_xy, pk_mask, sig_x, sig_larger, msg_u, msg_idx, rand_bits,
        set_mask,
    )


def verify_batch_raw_staged_gather(
    table, agg, pk_idx, pk_mask, sig_x, sig_larger, msg_u, msg_idx,
    rand_bits, set_mask,
):
    """Gathered variant of :func:`verify_batch_raw_staged` (ISSUE 10):
    the pubkey planes arrive as a ``(B, K)`` index plane into the
    device-resident key ``table`` (+ aggregate region ``agg``) and are
    materialized by the "gather" staged program; stages 1–3 are
    byte-identical to the raw path, so the verdict is too. The
    ``bls_stage_verify`` journal row carries the extra
    ``gather_s``/``gathered`` attribution."""
    try:
        pk_xy, sg, fg = _run_stage("gather", _gather, table, agg, pk_idx)
    except BaseException:
        # mirror the staged raise contract: the pack's ledger row lands
        transfer_ledger.commit_verify(None, d2h_bytes=0)
        raise
    return _staged_verify(
        pk_xy, pk_mask, sig_x, sig_larger, msg_u, msg_idx, rand_bits,
        set_mask, gather_record=(sg, fg),
    )


def _staged_verify(
    pk_xy, pk_mask, sig_x, sig_larger, msg_u, msg_idx, rand_bits, set_mask,
    gather_record=None,
):
    try:
        (sig_xy, mx, my, minf, sig_ok), s1, f1 = _run_stage(
            "stage1", _stage1, sig_x, sig_larger, msg_u
        )
        outs, s2, f2 = _run_stage(
            "stage2", _stage2, pk_xy, pk_mask, sig_xy, rand_bits, set_mask
        )
        pk_x, pk_y, pk_inf, acc_x, acc_y, acc_inf, flags_ok = outs
        # eager per-lane message gathers between the stage programs
        with tracing.span("bls.stage3_inputs"):
            msg_aff_x = jnp.take(mx, msg_idx, axis=0)
            msg_aff_y = jnp.take(my, msg_idx, axis=0)
            msg_aff_inf = jnp.take(minf, msg_idx, axis=0)
        pair_ok, s3, f3 = _run_stage(
            "stage3", _stage3,
            pk_x, pk_y, pk_inf, msg_aff_x, msg_aff_y, msg_aff_inf,
            acc_x, acc_y, acc_inf,
        )
    except BaseException:
        # the pack's bytes already shipped and were counted: its ledger
        # row must land (verdict null, nothing read back) — one journal
        # row per pack, raise or not, and never a stale staged row
        transfer_ledger.commit_verify(None, d2h_bytes=0)
        raise
    with tracing.span("bls.verdict"):
        out = pair_ok & flags_ok & jnp.all(sig_ok | ~set_mask)
        # every stage output is already synced, so the verdict read is
        # one small eager reduction and its read-back
        verdict = bool(out)
    geometry = {
        "b": int(pk_xy.shape[0]),
        "k": int(pk_xy.shape[1]),
        "m": int(msg_u.shape[0]),
        "fp_impl": fp.get_impl(),
    }
    gather_fields = {}
    recompiled = bool(f1 or f2 or f3)
    if gather_record is not None:
        sg, fg = gather_record
        gather_fields = {"gathered": True, "gather_s": round(sg, 6)}
        recompiled = recompiled or bool(fg)
    # the caller kind the scheduler scoped this dispatch to, and the
    # device the verdict was computed on (platform:id of the stage output)
    kind, _path = transfer_ledger.current_context()
    device = next(iter(out.devices()))
    flight_recorder.record(
        "bls_stage_verify",
        stage1_s=round(s1, 6), stage2_s=round(s2, 6), stage3_s=round(s3, 6),
        recompiled=recompiled, verdict=verdict, kind=kind,
        device=f"{device.platform}:{device.id}", **gather_fields, **geometry,
    )
    # the data-movement row this thread's pack staged (transfer_ledger):
    # the verdict read is the only device→host transfer of a staged
    # verify — intermediates stay on device by design
    transfer_ledger.commit_verify(verdict, d2h_bytes=int(out.nbytes))
    if not verdict:
        flight_recorder.dump_on_failure("stage_verify_failure", **geometry)
    return out


# ---------------------------------------------------------------------------
# MSM-family staged programs (ISSUE 16)
# ---------------------------------------------------------------------------

def run_msm_g1(pt_xy, pt_inf, scalars):
    """Dispatch the windowed G1 MSM staged program (device arrays in,
    device arrays out). Keyed like every staged program — recompile
    accounting, stage histogram (stage label "msm"), profiler span."""
    out, _s, _f = _run_stage("msm", _msm, pt_xy, pt_inf, scalars)
    return out


def run_g2_sum(pt_xy, pt_inf):
    """Dispatch the masked G2 point-sum staged program (the aggregate
    half of the MSM family; same "msm" stage label)."""
    out, _s, _f = _run_stage("msm", _g2sum, pt_xy, pt_inf)
    return out


def device_msm_g1(points, scalars, pad_n: int | None = None):
    """Host helper: cpu G1Point list + u64 scalars -> their device MSM
    as a cpu G1Point. N pads to the bucket ladder so repeated calls
    reuse warm MSM-rung programs; padding lanes are infinity with zero
    scalars (no contribution, complete group law)."""
    pts = list(points)
    sc = list(scalars)
    assert len(pts) == len(sc)
    N = pad_n or _round_up(max(len(pts), 1))
    xy = np.zeros((N, 2, fp.NL), np.int32)
    inf = np.ones((N,), bool)
    sw = np.zeros((N, 2), np.int32)
    if pts:
        pxy, pinf = curve.pack_g1(pts)
        xy[: len(pts)] = pxy
        inf[: len(pts)] = pinf
    for i, s in enumerate(sc):
        # u64 -> two's-complement int32 words (numpy rejects narrowing
        # casts of out-of-range Python ints; a view reinterprets safely)
        sw[i] = np.array(
            [(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], np.uint32
        ).view(np.int32)
    # data-movement attribution (ISSUE 17 satellite: msm can't run
    # dark): live lanes count as point/scalar bytes, pad lanes as
    # padding — the labels sum to the exact device_put nbytes, the
    # transfer ledger's invariant
    live = len(pts)
    live_b = live * (xy.nbytes // N + inf.nbytes // N) + live * (sw.nbytes // N)
    transfer_ledger.note_op_bytes(
        {
            "pubkeys": live * (xy.nbytes // N + inf.nbytes // N),
            "aux": live * (sw.nbytes // N),
            "padding": xy.nbytes + inf.nbytes + sw.nbytes - live_b,
        },
        kind="msm",
    )
    oxy, oinf = run_msm_g1(
        jnp.asarray(xy), jnp.asarray(inf), jnp.asarray(sw)
    )
    return curve.unpack_g1(np.asarray(oxy)[None], np.asarray(oinf)[None])[0]


def device_sum_g2(points, pad_n: int | None = None):
    """Host helper: cpu G2Point list -> their device point sum as a cpu
    G2Point (operation_pool's aggregation path). Padding lanes are
    infinity; an empty list returns infinity."""
    pts = list(points)
    N = pad_n or _round_up(max(len(pts), 1))
    xy = np.zeros((N, 2, 2, fp.NL), np.int32)
    inf = np.ones((N,), bool)
    if pts:
        pxy, pinf = curve.pack_g2(pts)
        xy[: len(pts)] = pxy
        inf[: len(pts)] = pinf
    # G2 points ride the signatures operand (they ARE signature points
    # — the op pool's aggregation inputs); pad lanes as padding
    live_b = len(pts) * (xy.nbytes // N + inf.nbytes // N)
    transfer_ledger.note_op_bytes(
        {
            "signatures": live_b,
            "padding": xy.nbytes + inf.nbytes - live_b,
        },
        kind="msm",
    )
    oxy, oinf = run_g2_sum(jnp.asarray(xy), jnp.asarray(inf))
    return curve.unpack_g2(np.asarray(oxy)[None], np.asarray(oinf)[None])[0]


# ---------------------------------------------------------------------------
# Host backend: padding, bucketing, randomness, reference edge semantics
# ---------------------------------------------------------------------------

# 48/96/192 are intermediate rungs for the flush planner's bin-packed
# sub-batches (verification_service/planner.py): observed traffic
# shapes a pure power-of-two ladder padded up to 64/128/256. The
# scheduler mirrors this tuple as BUCKET_LADDER (jax-free); the two are
# pinned equal by tests/test_verification_scheduler.py.
def _round_up(
    n: int,
    choices=(1, 2, 4, 8, 16, 32, 48, 64, 96, 128, 192, 256, 512, 1024),
) -> int:
    for c in choices:
        if n <= c:
            return c
    return ((n + 1023) // 1024) * 1024


def _rand_scalar_words() -> tuple[int, int]:
    while True:
        r = secrets.randbits(64)
        if r:
            return (r >> 32) & 0xFFFFFFFF, r & 0xFFFFFFFF


def _pack_common(sets, B: int, K: int):
    """Shared per-set packing: pubkeys, signatures, randomness, mask —
    used by both message-point and hashed packers."""
    pk_xy = np.zeros((B, K, 2, fp.NL), np.int32)
    pk_mask = np.zeros((B, K), bool)
    sig_xy = np.zeros((B, 2, 2, fp.NL), np.int32)
    rand = np.zeros((B, 2), np.int32)
    set_mask = np.zeros((B,), bool)
    for i, (sig, pks, _msg) in enumerate(sets):
        xy, _ = curve.pack_g1(pks)
        pk_xy[i, : len(pks)] = xy
        pk_mask[i, : len(pks)] = True
        sxy, _ = curve.pack_g2([sig])
        sig_xy[i] = sxy[0]
        hi, lo = _rand_scalar_words()
        rand[i] = (np.int32(np.uint32(hi)), np.int32(np.uint32(lo)))
        set_mask[i] = True
    # Padding lanes get a valid placeholder signature point (the real G2
    # generator) so the subgroup check vectorizes uniformly; their
    # contribution is masked out by set_mask.
    if B > len(sets):
        from ..cpu.curve import g2_generator

        gxy, _ = curve.pack_g2([g2_generator()])
        sig_xy[len(sets):] = gxy[0]
    return pk_xy, pk_mask, sig_xy, rand, set_mask


def _pad_sig_lanes(sig_x, n_live: int) -> None:
    """Padding lanes get the G2 generator's x (a valid curve x) so the
    device decompression stays uniform; their result is masked out by
    ``set_mask``. ONE definition for both halves of the static/dynamic
    packer split — the two packers must stay byte-identical in every
    non-pubkey plane."""
    if sig_x.shape[0] <= n_live:
        return
    from ..cpu.curve import g2_generator

    g = g2_generator()
    sig_x[n_live:, 0] = fp.int_to_limbs(g.x.c0.n)
    sig_x[n_live:, 1] = fp.int_to_limbs(g.x.c1.n)


def _pack_message_planes(sets, B: int, pad_m: int | None):
    """Shared message half of the raw/indexed packers: dedup + padded
    per-lane index plane + hash_to_field u-values. Returns
    ``(msg_u, msg_idx, m_req)``."""
    msgs, idx = _dedup_messages([m for _, _, m in sets], pad_m)
    m_req = int(idx.max()) + 1 if len(idx) else 1  # distinct live messages
    msg_idx = np.zeros((B,), np.int32)
    msg_idx[: len(sets)] = idx
    from . import htc

    msg_u = htc.messages_to_u(msgs, DST)
    return msg_u, msg_idx, m_req


def _dedup_messages(messages, pad_m: int | None):
    """-> (unique-message list padded to M, per-item index array)."""
    uniq: dict[bytes, int] = {}
    idx = np.zeros((len(messages),), np.int32)
    for i, m in enumerate(messages):
        idx[i] = uniq.setdefault(bytes(m), len(uniq))
    M = pad_m or _round_up(len(uniq))
    assert len(uniq) <= M, (
        f"pad_m={M} smaller than {len(uniq)} distinct messages"
    )
    msgs = sorted(uniq, key=uniq.get) + [b""] * (M - len(uniq))
    return msgs, idx


def pack_signature_sets(sets, pad_b: int | None = None, pad_k: int | None = None):
    """Host-side batch assembly: (sig_point, [pk_points], message) triples ->
    the fixed-shape device arrays of :func:`verify_batch_fn`. Sets must be
    pre-screened (non-empty, non-infinity signature). Shapes are padded to
    bucket sizes to bound jit recompiles."""
    sets = list(sets)
    B = pad_b or _round_up(len(sets))
    K = pad_k or _round_up(max(len(pks) for _, pks, _ in sets))
    pk_xy, pk_mask, sig_xy, rand, set_mask = _pack_common(sets, B, K)

    msg_xy = np.zeros((B, 2, 2, fp.NL), np.int32)
    msg_cache: dict[bytes, np.ndarray] = {}
    for i, (_sig, _pks, msg) in enumerate(sets):
        hxy = msg_cache.get(msg)
        if hxy is None:
            hxy = curve.pack_g2([hash_to_g2(msg, DST)])[0][0]
            msg_cache[msg] = hxy
        msg_xy[i] = hxy
    if B > len(sets):
        # same placeholder as the padding signature lanes
        msg_xy[len(sets):] = sig_xy[len(sets)]

    return (
        jnp.asarray(pk_xy),
        jnp.asarray(pk_mask),
        jnp.asarray(sig_xy),
        jnp.asarray(msg_xy),
        jnp.asarray(rand),
        jnp.asarray(set_mask),
    )


def pack_signature_sets_hashed(
    sets, pad_b: int | None = None, pad_k: int | None = None,
    pad_m: int | None = None,
):
    """End-to-end packing: like :func:`pack_signature_sets` but messages
    stay raw — the host computes only hash_to_field u-values (native
    SHA-256); the curve mapping runs on device inside
    :func:`verify_batch_hashed_fn`. This removes the 285 ms/message
    pure-Python ``hash_to_g2`` from the hot path (VERDICT weakness #2)."""
    from . import htc

    sets = list(sets)
    B = pad_b or _round_up(len(sets))
    K = pad_k or _round_up(max(len(pks) for _, pks, _ in sets))
    pk_xy, pk_mask, sig_xy, rand, set_mask = _pack_common(sets, B, K)

    msgs, idx = _dedup_messages([m for _, _, m in sets], pad_m)
    msg_idx = np.zeros((B,), np.int32)
    msg_idx[: len(sets)] = idx
    msg_u = htc.messages_to_u(msgs, DST)

    return (
        jnp.asarray(pk_xy),
        jnp.asarray(pk_mask),
        jnp.asarray(sig_xy),
        jnp.asarray(msg_u),
        jnp.asarray(msg_idx),
        jnp.asarray(rand),
        jnp.asarray(set_mask),
    )


def pack_signature_sets_raw(
    sets, pad_b: int | None = None, pad_k: int | None = None,
    pad_m: int | None = None,
):
    """Fully-raw packing for :func:`verify_batch_raw_fn`: ``sets`` are
    ``(Signature-object, [pk_points], message)`` triples. Signatures stay
    COMPRESSED — only byte parsing happens here; no host sqrt.

    DYNAMIC half of the static/dynamic packer split (ISSUE 10): this
    packer ships full G1 limb planes and serves out-of-table keys (VC
    tests, library callers, pre-admission gossip); sets whose pubkeys
    all resolve to device key-table indices go through
    :func:`pack_signature_sets_indexed` instead and ship a ``(B, K)``
    index plane (docs/DEVICE_CRYPTO.md).

    Instrumented as the data-movement ledger's measured pack (ISSUE 8):
    phases ``decode`` (signature byte parsing + randomness),
    ``limb_split`` (int→limb conversion + array fill), ``pad``
    (allocation + padding-lane fill), ``hash`` (message hash_to_field),
    ``device_put`` (host→device transfer) land in
    ``bls_device_pack_seconds{phase}``; per-operand byte splits and the
    packed pubkey rows feed ``utils/transfer_ledger.note_pack``."""
    t_start = time.perf_counter()
    sets = list(sets)
    B = pad_b or _round_up(len(sets))
    K = pad_k or _round_up(max(len(pks) for _, pks, _ in sets))

    pk_xy = np.zeros((B, K, 2, fp.NL), np.int32)
    pk_mask = np.zeros((B, K), bool)
    sig_x = np.zeros((B, 2, fp.NL), np.int32)
    sig_larger = np.zeros((B,), bool)
    rand = np.zeros((B, 2), np.int32)
    set_mask = np.zeros((B,), bool)
    t_pad = time.perf_counter() - t_start

    from .. import bls as _bls

    # with the ledger off, the packer must not pay for it either: no
    # per-pubkey blob copies, no device sync (note_pack would drop them)
    ledger_on = transfer_ledger.enabled()
    t_decode = t_limb = 0.0
    pk_blobs: list = []
    pk_slots = 0
    for i, (sig, pks, _msg) in enumerate(sets):
        t0 = time.perf_counter()
        x0, x1, larger = _bls.parse_compressed_g2_x(sig.serialize())
        hi, lo = _rand_scalar_words()
        t1 = time.perf_counter()
        t_decode += t1 - t0
        xy, _ = curve.pack_g1(pks)
        pk_xy[i, : len(pks)] = xy
        pk_mask[i, : len(pks)] = True
        sig_x[i, 0] = fp.int_to_limbs(x0)
        sig_x[i, 1] = fp.int_to_limbs(x1)
        sig_larger[i] = larger
        rand[i] = (np.int32(np.uint32(hi)), np.int32(np.uint32(lo)))
        set_mask[i] = True
        t_limb += time.perf_counter() - t1
        pk_slots += len(pks)
        if ledger_on:
            for j in range(len(pks)):
                pk_blobs.append(xy[j].tobytes())
    t0 = time.perf_counter()
    _pad_sig_lanes(sig_x, len(sets))
    t_pad += time.perf_counter() - t0

    t0 = time.perf_counter()
    msg_u, msg_idx, m_req = _pack_message_planes(sets, B, pad_m)
    t_hash = time.perf_counter() - t0

    t0 = time.perf_counter()
    fault_injection.fire("device_put")  # chaos seam (ISSUE 13)
    args = (
        jnp.asarray(pk_xy),
        jnp.asarray(pk_mask),
        jnp.asarray(sig_x),
        jnp.asarray(sig_larger),
        jnp.asarray(msg_u),
        jnp.asarray(msg_idx),
        jnp.asarray(rand),
        jnp.asarray(set_mask),
    )
    if ledger_on:
        # async backends (real TPU) return from asarray while the DMA
        # is in flight: block so the phase measures the TRANSFER, not
        # the enqueue — otherwise the effective-H2D-bandwidth evidence
        # is inflated exactly on the device it is meant to size. Gated:
        # with the ledger off the hot path keeps its transfer/dispatch
        # overlap and pays nothing, and the device_put semantics change
        # is DOCUMENTED in the family help (enqueue-only when disabled
        # on async backends)
        jax.block_until_ready(args)
    t_dput = time.perf_counter() - t0

    phases = {
        "decode": t_decode, "limb_split": t_limb, "pad": t_pad,
        "hash": t_hash, "device_put": t_dput,
    }
    total_s = time.perf_counter() - t_start
    # the pack histogram is always-on (it predates the ledger); only the
    # byte accounting below is behind the ledger knob
    transfer_ledger.observe_pack_phases(phases, total_s)
    transfer_ledger.note_pack(
        n_sets=len(sets), b=B, k=K, m=int(msg_u.shape[0]),
        pk_slots=pk_slots, m_req=m_req,
        phases=phases,
        total_s=total_s,
        operand_nbytes={
            "pubkeys": pk_xy.nbytes + pk_mask.nbytes,
            "signatures": sig_x.nbytes + sig_larger.nbytes,
            "messages": msg_u.nbytes + msg_idx.nbytes,
            "aux": rand.nbytes + set_mask.nbytes,
        },
        pubkey_blobs=pk_blobs,
    )
    # pipeline profiler (ISSUE 12): the whole pack is host activity —
    # a device gap overlapping it attributes to cause `pack`
    pipeline_profiler.note_pack_wall(t_start, t_start + total_s)
    return args


def pack_signature_sets_indexed(
    sets, indices, pad_b: int | None = None, pad_k: int | None = None,
    pad_m: int | None = None,
):
    """STATIC half of the raw packer split (ISSUE 10): for sets whose
    pubkeys all resolved to device key-table indices
    (``key_table.DeviceKeyTable.resolve_sets``), ship a ``(B, K)`` int32
    index plane + mask instead of the ``(B, K, 2, NL)`` G1 limb planes —
    ~5 bytes per pubkey slot instead of 257. ``indices`` is the per-set
    index list (aggregate-collapsed sets carry one index). Everything
    else (signature decode, randomness, message hashing) matches
    :func:`pack_signature_sets_raw`, and the ledger row is labeled
    ``indexed`` so byte accounting stays honest."""
    t_start = time.perf_counter()
    sets = list(sets)
    indices = list(indices)
    if len(indices) != len(sets):
        # a REAL raise, not an assert: under python -O a silent zip
        # truncation would leave trailing sets masked out — an
        # unverified signature accepted by a True batch verdict
        raise ValueError(
            f"indices must match sets one-to-one "
            f"({len(indices)} vs {len(sets)})"
        )
    B = pad_b or _round_up(len(sets))
    K = pad_k or _round_up(max((len(ix) for ix in indices), default=1))

    pk_idx = np.zeros((B, K), np.int32)
    pk_mask = np.zeros((B, K), bool)
    sig_x = np.zeros((B, 2, fp.NL), np.int32)
    sig_larger = np.zeros((B,), bool)
    rand = np.zeros((B, 2), np.int32)
    set_mask = np.zeros((B,), bool)
    t_pad = time.perf_counter() - t_start

    from .. import bls as _bls

    ledger_on = transfer_ledger.enabled()
    t_decode = t_fill = 0.0
    pk_slots = 0
    for i, ((sig, _pks, _msg), ix) in enumerate(zip(sets, indices)):
        t0 = time.perf_counter()
        x0, x1, larger = _bls.parse_compressed_g2_x(sig.serialize())
        hi, lo = _rand_scalar_words()
        t1 = time.perf_counter()
        t_decode += t1 - t0
        pk_idx[i, : len(ix)] = ix
        pk_mask[i, : len(ix)] = True
        sig_x[i, 0] = fp.int_to_limbs(x0)
        sig_x[i, 1] = fp.int_to_limbs(x1)
        sig_larger[i] = larger
        rand[i] = (np.int32(np.uint32(hi)), np.int32(np.uint32(lo)))
        set_mask[i] = True
        t_fill += time.perf_counter() - t1
        pk_slots += len(ix)
    t0 = time.perf_counter()
    _pad_sig_lanes(sig_x, len(sets))
    t_pad += time.perf_counter() - t0

    t0 = time.perf_counter()
    msg_u, msg_idx, m_req = _pack_message_planes(sets, B, pad_m)
    t_hash = time.perf_counter() - t0

    t0 = time.perf_counter()
    fault_injection.fire("device_put")  # chaos seam (ISSUE 13)
    args = (
        jnp.asarray(pk_idx),
        jnp.asarray(pk_mask),
        jnp.asarray(sig_x),
        jnp.asarray(sig_larger),
        jnp.asarray(msg_u),
        jnp.asarray(msg_idx),
        jnp.asarray(rand),
        jnp.asarray(set_mask),
    )
    if ledger_on:
        # same sync rationale as the raw packer: measure the TRANSFER
        jax.block_until_ready(args)
    t_dput = time.perf_counter() - t0

    phases = {
        "decode": t_decode, "limb_split": t_fill, "pad": t_pad,
        "hash": t_hash, "device_put": t_dput,
    }
    total_s = time.perf_counter() - t_start
    transfer_ledger.observe_pack_phases(phases, total_s)
    transfer_ledger.note_pack(
        n_sets=len(sets), b=B, k=K, m=int(msg_u.shape[0]),
        pk_slots=pk_slots, m_req=m_req,
        phases=phases,
        total_s=total_s,
        operand_nbytes={
            # the index plane IS the pubkey operand now
            "pubkeys": pk_idx.nbytes + pk_mask.nbytes,
            "signatures": sig_x.nbytes + sig_larger.nbytes,
            "messages": msg_u.nbytes + msg_idx.nbytes,
            "aux": rand.nbytes + set_mask.nbytes,
        },
        pubkey_blobs=(),  # nothing G1-shaped crossed the boundary
        indexed=True,
    )
    # pipeline profiler (ISSUE 12): same pack-activity contract as the
    # raw packer — the static half's wall is host time too
    pipeline_profiler.note_pack_wall(t_start, t_start + total_s)
    return args


def _active_key_table():
    """The process-global device key table when one is attached with
    resident rows (crypto/device/key_table.py). Lazy import mirrors
    ``_active_compile_service``."""
    from . import key_table as _kt

    return _kt.get_active_table()


class TpuBackend:
    """Runtime backend ``"tpu"`` (see crypto/backend.py). Presents the same
    protocol as the CPU oracle backend; internally packs fixed-shape
    batches and calls the jitted device program (compile cache keyed on
    padded (B, K, M) bucket shape).

    Pubkey subgroup checks are NOT repeated here: every ``PublicKey``
    enters the system through ``deserialize`` (KeyValidate — infinity +
    subgroup), mirroring the reference's decompress-once
    ``ValidatorPubkeyCache`` admission (``validator_pubkey_cache.rs:79``);
    the device program still rejects an aggregate that degenerates to
    infinity."""

    name = "tpu"

    # -- batch verification (the hot path) -------------------------------

    def verify_signature_sets(self, sets) -> bool:
        """``sets``: (Signature-object | G2Point, [pk_points], message).
        Signature objects keep their compressed bytes and are decompressed
        ON DEVICE (verify_batch_raw); bare points (oracle tests) fall back
        to the pre-decompressed program."""
        from .. import bls as _bls

        sets = list(sets)
        if not sets:
            _OUTCOMES.with_labels("rejected").inc()
            return False
        raw_mode = all(isinstance(s, _bls.Signature) for s, _, _ in sets)
        for sig, pks, _msg in sets:
            if not pks or sig.is_infinity():
                _OUTCOMES.with_labels("rejected").inc()
                return False
            if any(pk.is_infinity() for pk in pks):
                _OUTCOMES.with_labels("rejected").inc()
                return False
        path = "raw_staged" if raw_mode else "hashed"
        impl = fp.get_impl()
        # static/dynamic packer decision (ISSUE 10): when a device key
        # table is attached and EVERY pubkey of this batch resolves to a
        # resident index (identity-pinned to the host cache), the pack
        # ships a (B, K) index plane and the pubkey planes materialize
        # by device gather. Any out-of-table key (VC tests, library
        # callers, pre-admission gossip) falls the whole batch back to
        # the raw limb plane — the flush planner splits mixed flushes
        # into static/dynamic sub-batches upstream so one raw set does
        # not degrade a warm static batch.
        resolved = table_dev = agg_dev = None
        n_collapsed = 0
        table = _active_key_table()
        if raw_mode:
            if table is not None:
                res = table.resolve_sets(sets)
                if res is not None:
                    resolved, table_dev, agg_dev, n_collapsed = res
                    path = "raw_gather"
        elif table is not None:
            # hashed mode (bare points) can never gather: keep the hit
            # ratio's denominator honest about the fallback
            table.count_raw(len(sets))
        # requested geometry, computed ONCE for warm-shape routing and
        # the padding accounting (the packer's own dedup still runs — it
        # needs the index mapping, not just the count). The static path
        # pays the COLLAPSED K axis (a cached aggregate sum is one slot).
        if resolved is not None:
            k_req = max(len(ix) for ix in resolved)
            pk_slots = sum(len(ix) for ix in resolved)
        else:
            k_req = max(len(pks) for _, pks, _ in sets)
            pk_slots = None
        m_req = len({bytes(m) for _, _, m in sets})
        # warm-shape routing (compile_service): when a service is
        # attached and a warm rung covers this batch, pad UP to it so
        # the dispatch hits an already-compiled staged program instead
        # of paying a fresh XLA compile on the caller's thread
        pad_b = pad_k = pad_m = None
        svc = _active_compile_service() if raw_mode else None
        warm_epoch = None
        # the dp shard this dispatch runs on (ISSUE 11): the scheduler's
        # sharded sub-batch scope sets it thread-locally; 0 without a
        # mesh. Routing, recompile accounting and the organic-warmth
        # mark are all PER SHARD — one chip's warmth is not another's.
        from . import mesh as _mesh_mod

        shard = _mesh_mod.current_shard() or 0
        if svc is not None:
            # epoch BEFORE dispatch: if reset_compiled_state() lands while
            # we verify, the organic mark below must be rejected as stale
            warm_epoch = svc.registry.epoch
            # NOTE on collapse vs routing: aggregate collapse only
            # SHRINKS k_req, and warm coverage is >=-monotone in K
            # (planner.best_covering_rung filters K >= k_req), so the
            # collapsed request routes at least as warm as the
            # uncollapsed geometry decide_flush approved — collapse can
            # never turn a warm-approved flush into a cold stall
            rung = svc.pads_for(len(sets), k_req, m_req, device=shard)
            if rung is not None:
                pad_b, pad_k, pad_m = rung
        if resolved is not None:
            # the shipping-path accounting the health hit-ratio reads —
            # committed by the dispatcher, in one place, once the batch
            # is definitely taking the indexed path
            table.count_shipped(len(sets) - n_collapsed, n_collapsed)
        t_serve0 = time.perf_counter()  # rung-cost feed (ISSUE 14)
        with tracing.span(
            "bls.verify_signature_sets", path=path, n_sets=len(sets)
        ) as sp, _VERIFY_SECONDS.with_labels(path, impl).time():
            with tracing.span("bls.pack"):
                if resolved is not None:
                    # static packer: index plane only (the pubkey limbs
                    # are already device-resident)
                    args = pack_signature_sets_indexed(
                        sets, resolved,
                        pad_b=pad_b, pad_k=pad_k, pad_m=pad_m,
                    )
                elif raw_mode:
                    # the raw packer observes its own phase-labeled pack
                    # times (incl. total) into the data-movement ledger
                    args = pack_signature_sets_raw(
                        sets, pad_b=pad_b, pad_k=pad_k, pad_m=pad_m
                    )
                else:
                    with _PACK_TOTAL.time():
                        args = pack_signature_sets_hashed(sets)
            self._record_geometry(
                sets, args, k_req=k_req, m_req=m_req, pk_slots=pk_slots
            )
            if resolved is not None:
                out = bool(
                    verify_batch_raw_staged_gather(table_dev, agg_dev, *args)
                )
            elif raw_mode:
                out = bool(verify_batch_raw_staged(*args))
            else:
                out = bool(verify_batch_hashed(*args))
            sp.set(verdict=out)
        if raw_mode and svc is not None:
            # organic warmth: whatever rung this batch landed on is
            # compiled now ON THIS SHARD (whatever the verdict) —
            # routable without the AOT worker. OUTSIDE the timed window:
            # the first mark per rung writes the manifest to disk.
            svc.note_rung_verified(
                int(args[0].shape[0]),    # B (pk_xy)
                int(args[0].shape[1]),    # K
                int(args[4].shape[0]),    # M (msg_u)
                epoch=warm_epoch,
                device=shard,
                # the rung-cost feed (ISSUE 14): full serving wall
                # (pack + staged dispatch) per live set — the capacity
                # estimator's fallback cost input
                seconds=time.perf_counter() - t_serve0,
                n_sets=len(sets),
            )
        _OUTCOMES.with_labels("ok" if out else "fail").inc()
        return out

    @staticmethod
    def _record_geometry(
        sets, packed_args, k_req: int | None = None, m_req: int | None = None,
        pk_slots: int | None = None,
    ) -> None:
        """Batch-geometry accounting: requested vs padded B/K/M lanes and
        the padding-waste fraction of the pubkey plane (the device pays
        for padded lanes; the caller only needed the requested ones).
        ``k_req``/``m_req`` take the caller's already-computed request
        geometry so the message set is not hashed twice per batch;
        ``pk_slots`` overrides the live slot count for the indexed path,
        where an aggregate-collapsed committee set occupies ONE lane."""
        pk_xy = packed_args[0]  # raw: [B,K,2,NL]; indexed: idx plane [B,K]
        b_pad, k_pad = int(pk_xy.shape[0]), int(pk_xy.shape[1])
        # raw/hashed packers put msg_u [M, 2, 2, NL] at index 4/3
        m_pad = int(packed_args[4 if len(packed_args) == 8 else 3].shape[0])
        b_req = len(sets)
        if k_req is None:
            k_req = max(len(pks) for _, pks, _ in sets)
        if m_req is None:
            m_req = len({bytes(m) for _, _, m in sets})
        for dim, req, pad in (
            ("b", b_req, b_pad), ("k", k_req, k_pad), ("m", m_req, m_pad)
        ):
            _LANES.with_labels(dim, "requested").inc(req)
            _LANES.with_labels(dim, "padded").inc(pad)
        real_slots = (
            pk_slots
            if pk_slots is not None
            else sum(len(pks) for _, pks, _ in sets)
        )
        # ONE waste definition across the stack (lazy import: the
        # planner module is jax-free, but this module must not pull the
        # verification_service package in at import time)
        from ...verification_service import planner as _planner

        _PAD_WASTE.set(
            _planner.padding_waste_ratio(
                _planner.live_lanes(real_slots, m_req),
                _planner.padded_lanes(b_pad, k_pad, m_pad),
            )
        )

    # -- single-set entry points (same device program, B=1 semantics) ----

    def verify(self, pk, message, sig) -> bool:
        if pk.is_infinity():
            return False
        return self._verify_one(sig, [pk], message, aggregate=False)

    def fast_aggregate_verify(self, pks, message, sig) -> bool:
        pks = list(pks)
        if not pks:
            return False
        # Aggregation happens on device (masked sum); an aggregate that
        # degenerates to infinity fails inside the device program.
        return self._verify_one(sig, pks, message, aggregate=True)

    def aggregate_verify(self, pks, messages, sig) -> bool:
        """One signature over per-pubkey messages: prod e(pk_i, H(m_i)) *
        e(-g1, sig) == 1 with a subgroup-checked signature. Message
        hashing runs on device (htc.map_to_g2)."""
        from . import htc

        pks, messages = list(pks), list(messages)
        if not pks or len(pks) != len(messages):
            return False
        if any(pk.is_infinity() for pk in pks):
            return False
        n = len(pks)
        Bn = _round_up(n)
        pk_xy = np.zeros((Bn, 2, fp.NL), np.int32)
        pk_inf = np.ones((Bn,), bool)
        xy, _ = curve.pack_g1(pks)
        pk_xy[:n] = xy
        pk_inf[:n] = False
        msgs, idx = _dedup_messages(messages, None)
        msg_idx = np.zeros((Bn,), np.int32)
        msg_idx[:n] = idx
        msg_u = htc.messages_to_u(msgs, DST)

        sxy, s_inf = curve.pack_g2([sig])
        if s_inf[0]:
            return False
        return bool(
            _aggregate_verify_device(
                jnp.asarray(pk_xy),
                jnp.asarray(pk_inf),
                jnp.asarray(msg_u),
                jnp.asarray(msg_idx),
                jnp.asarray(sxy[0]),
            )
        )

    def _verify_one(self, sig, pks, message, aggregate: bool) -> bool:
        if sig.is_infinity():
            return False
        return self.verify_signature_sets([(sig, pks, message)])


@jax.jit
def _aggregate_verify_device(pk_xy, pk_inf, msg_u, msg_idx, sig_xy):
    from . import htc

    sig_pt = curve.from_affine(fp2, sig_xy[0], sig_xy[1])
    sub_ok = g2_in_subgroup(sig_pt)

    msg_pts = htc.map_to_g2(msg_u)
    mx, my, minf = curve.to_affine(fp2, msg_pts)

    g1_x = jnp.concatenate([pk_xy[:, 0], fp.const(_NEG_G1[0])[None]], axis=0)
    g1_y = jnp.concatenate([pk_xy[:, 1], fp.const(_NEG_G1[1])[None]], axis=0)
    g1_inf = jnp.concatenate([pk_inf, jnp.zeros((1,), bool)], axis=0)
    sx, sy, sinf = curve.to_affine(fp2, sig_pt)
    g2_x = jnp.concatenate([jnp.take(mx, msg_idx, axis=0), sx[None]], axis=0)
    g2_y = jnp.concatenate([jnp.take(my, msg_idx, axis=0), sy[None]], axis=0)
    # a padding pk lane is already infinity on the G1 side; message side
    # needs no mask
    g2_inf = jnp.concatenate(
        [jnp.take(minf, msg_idx, axis=0), sinf[None]], axis=0
    )

    return pairing.multi_pairing_is_one(
        (g1_x, g1_y, g1_inf), (g2_x, g2_y, g2_inf)
    ) & sub_ok
