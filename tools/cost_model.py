"""Analytic device cost model for the flagship BLS verification program
(VERDICT r4 item #2): static multiply counts per signature set, bytes
moved, and predicted sets/s/chip under explicit throughput assumptions.

Counting unit: one **fp lane** = one 32-limb x 32-limb banded-Toeplitz
product = 2016 int32 MACs (`crypto/device/fp.py` `mul`: 63 columns x 32
limbs schoolbook; the reduction's fold contraction adds ~1024 MACs and
the carry rounds ~300 adds — folded into the per-lane overhead factor).

Every formula cites the code it models. Run:  python tools/cost_model.py
(writes docs/COST_MODEL.md).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from lighthouse_tpu.crypto.device.fp import ladder_counts  # noqa: E402
from lighthouse_tpu.crypto.params import P, X  # noqa: E402
from lighthouse_tpu.utils import transfer_ledger  # noqa: E402  (jax-free)

# ---------------------------------------------------------------------------
# Primitive lane counts (cite: crypto/device/{fp,fp2,curve,tower,pairing}.py)
# ---------------------------------------------------------------------------

MACS_PER_LANE = 2016          # fp.mul: 32x63 banded dot (fp.py NCOLS)
LANE_OVERHEAD = 1.65          # fold contraction + carry rounds + adds, per lane

FP2_MUL = 3                   # fp2.mul: Karatsuba, one 3-lane fp.mul
FP2_SQ = 2                    # fp2.sq: (a0+a1)(a0-a1) | a0*a1
G1_ADD = 12                   # curve.add (RCB complete) over fp
G1_DBL = 8                    # curve.dbl over fp
G2_ADD = 12 * FP2_MUL         # same formulas over fp2
G2_DBL = 8 * FP2_MUL


def ladder(e: int, sq: int, mul: int) -> int:
    """Lanes of one fp.square_multiply ladder (its fixed-window schedule)."""
    n_sq, n_mul = ladder_counts(e)
    return n_sq * sq + n_mul * mul


NBITS_P = (P - 2).bit_length()          # 381: fp.inv exponent length
FP_INV = ladder(P - 2, 1, 1)            # fp.inv: fp.pow_const window ladder
SQRT_ELEM = ladder((P - 3) // 4, FP2_SQ, FP2_MUL) \
    + ladder((P - 1) // 2, FP2_SQ, FP2_MUL) + 6 * FP2_MUL
# htc.sqrt: two f2pow ladders (a1, b) + candidate muls, per batch element

SCALAR64_G2 = 64 * (G2_DBL + G2_ADD)    # curve.scalar_mul_bits, 64-bit
SCALAR64_G1 = 64 * (G1_DBL + G1_ADD)

X_BITS = (-X).bit_length()              # 64: Miller loop length (pairing.py)
TOWER_SQ = 18 * FP2_MUL                 # tower.sq: 18 fp2 products
TOWER_MUL = 27 * FP2_MUL                # tower.mul: 27 fp2 products
LINE_MUL = 18 * FP2_MUL                 # pairing.mul_by_line
DBL_STEP = 8 * FP2_MUL + 4 * FP2_SQ     # pairing._dbl_step (muls + squares)
ADD_STEP = 10 * FP2_MUL + 2 * FP2_SQ    # pairing._add_line
MILLER_PER_LANE = (X_BITS - 1) * (TOWER_SQ + LINE_MUL + DBL_STEP
                                  + ADD_STEP + LINE_MUL)
# per-bit body computes BOTH dbl and (selected) add legs — branch-free

# final_exp_is_one (pairing.py): easy part + 16-entry table + multi-exp scan
N_MULTIEXP = max(abs(l).bit_length() for l in [
    (X - 1) ** 2 * (X**3 - X) + 3,
    (X - 1) ** 2 * (X**2 - 1),
    (X - 1) ** 2 * X,
    (X - 1) ** 2,
])
TOWER_INV = 2 * (18 * FP2_MUL) + 15 * FP2_MUL + FP_INV + 10 * FP2_MUL
EASY_PART = TOWER_INV + 2 * TOWER_MUL + 15
FEXP_TABLE = 11 * TOWER_MUL + 4 * 15    # scan-built subset table + frobenii
FEXP = EASY_PART + FEXP_TABLE + N_MULTIEXP * (TOWER_SQ + TOWER_MUL)

# htc.map_to_g2 per unique message: 2 field elements x (SSWU pre ~10 fp2
# + sqrt over 2 candidates) + isogeny Horner (4 polys x 3 steps) + adds +
# clear_cofactor (2 x 64-bit G2 scalar mul + ~5 G2 adds + psi)
HTC_PER_MSG = (
    2 * (10 * FP2_MUL + 2 * SQRT_ELEM)
    + 4 * 3 * FP2_MUL + 2 * FP2_MUL + FP_INV
    + G2_ADD
    + 2 * SCALAR64_G2 + 5 * G2_ADD + 3 * 2 * FP2_MUL + G2_DBL
)
DECOMPRESS_PER_SIG = SQRT_ELEM + 2 * FP2_MUL + 8  # _decompress_pre/post

# to_affine: one field inv + 2 muls (amortized where noted)
TO_AFFINE_G1 = FP_INV + 2
TO_AFFINE_G2 = FP_INV + 4 + 2 * FP2_MUL


def lanes_per_set(K: int, B: int, M: int) -> dict:
    """Fp-mul lanes per signature set at bucket shape (B sets, K pubkey
    slots, M unique messages). Batch-level costs amortize over B."""
    per_set = {
        "pubkey_aggregation (K G1 adds)": K * G1_ADD,
        "subgroup + randomizer G2 scalar muls": 2 * SCALAR64_G2,
        "randomizer G1 scalar mul": SCALAR64_G1,
        "signature decompression": DECOMPRESS_PER_SIG,
        "per-lane Miller loop": MILLER_PER_LANE,
        "to_affine (pk, per set)": TO_AFFINE_G1,
    }
    amortized = {
        "hash_to_curve (M msgs / B sets)": M * HTC_PER_MSG / B,
        "final exponentiation / B": FEXP / B,
        "G2 accumulator + to_affine / B": (B * G2_ADD + TO_AFFINE_G2) / B,
    }
    total = sum(per_set.values()) + sum(amortized.values())
    return {"per_set": per_set, "amortized": amortized, "total": total}


def bytes_per_set(K: int) -> int:
    """HBM traffic per set for program INPUTS (int32 limb encodings,
    fp.py layout): pubkeys K x 2 x 32 x 4B, sig x 2 x 32 x 4B, masks,
    randomness. Intermediates are compiler-managed (VMEM-resident per
    fusion) and excluded."""
    return K * 2 * 32 * 4 + 2 * 32 * 4 + K + 8 + 1


SCENARIOS = {
    # label: (int32 MAC/s, assumption note)
    "v5e VPU int32": (
        2.0e12,
        "VPU-bound: 8x128 lanes x ~2 int32 MAC/lane/cycle x ~0.94 GHz "
        "(int32 multiplies do not hit the MXU natively)",
    ),
    "v5e MXU via 12-bit->int8 split": (
        4.9e13,
        "the FP_IMPL=matmul_int8 path (fp.py): limbs split into signed-"
        "int8 halves (hi=limb>>6, lo=limb&63), 4 int8 MXU passes: "
        "394 TOPS int8 / 4 passes / 2 (ops->MACs)",
    ),
}


def main() -> None:
    ks = [8, 16, 128, 512]
    rows = []
    for K in ks:
        B, M = 256, 8
        c = lanes_per_set(K, B, M)
        total_lanes = c["total"]
        total_macs = total_lanes * MACS_PER_LANE * LANE_OVERHEAD
        row = {
            "K": K,
            "lanes": int(total_lanes),
            "gmacs_per_set": total_macs / 1e9,
            "bytes_in": bytes_per_set(K),
        }
        for label, (rate, _) in SCENARIOS.items():
            row[label] = rate / total_macs
        rows.append(row)

    c16 = lanes_per_set(16, 256, 8)
    lines = []
    w = lines.append
    w("# COST_MODEL.md — analytic device cost of the flagship BLS program")
    w("")
    w("Generated by `tools/cost_model.py` (re-run after kernel changes).")
    w("Counting unit: one **fp lane** = one 32-limb banded-Toeplitz product")
    w(f"= {MACS_PER_LANE} int32 MACs (`crypto/device/fp.py` NCOLS x NL);")
    w(f"reduction overhead factor {LANE_OVERHEAD} covers the fold")
    w("contraction + carry rounds. Reference workload being modelled:")
    w("`/root/reference/consensus/state_processing/src/per_block_processing/"
      "block_signature_verifier.rs:374-382`.")
    w("")
    w("## Where the multiplies are (K=16, B=256, M=8; fp lanes per set)")
    w("")
    w("| component | lanes/set |")
    w("|---|---|")
    for name, v in c16["per_set"].items():
        w(f"| {name} | {int(v):,} |")
    for name, v in c16["amortized"].items():
        w(f"| {name} | {int(v):,} |")
    w(f"| **total** | **{int(c16['total']):,}** |")
    w("")
    w("Derived constants: fp.inv ladder = "
      f"{FP_INV} lanes ({NBITS_P}-bit Fermat window ladder); one Fp2 sqrt element = "
      f"{SQRT_ELEM:,} lanes (two ~381-bit ladders); 64-bit G2 scalar mul = "
      f"{SCALAR64_G2:,} lanes; Miller loop = {MILLER_PER_LANE:,} lanes/lane "
      f"({X_BITS - 1} bits x (Fp12 sq + 2 sparse-line muls + dbl + add)); "
      f"final exp = {FEXP:,} lanes once per batch "
      f"({N_MULTIEXP}-step shared-squaring multi-exp).")
    w("")
    w("## Predicted sets/s/chip by committee-size bucket")
    w("")
    hdr = "| K | lanes/set | GMAC/set | " + " | ".join(SCENARIOS) + " |"
    w(hdr)
    w("|" + "---|" * (3 + len(SCENARIOS)))
    for r in rows:
        w(
            f"| {r['K']} | {r['lanes']:,} | {r['gmacs_per_set']:.2f} | "
            + " | ".join(f"{r[label]:,.0f} /s" for label in SCENARIOS)
            + " |"
        )
    w("")
    w("Assumptions:")
    for label, (rate, note) in SCENARIOS.items():
        w(f"- **{label}**: {rate:.1e} int32 MAC/s — {note}.")
    w("")
    # Measured fp.mul constants (benches/bench_fp_mul.py artifact). The
    # analytic scenarios above are ENVELOPES; this table is what the two
    # contraction engines actually achieve on the backend the bench ran on.
    mpath = REPO / "BENCH_FP_MUL.json"
    if mpath.exists():
        m = json.loads(mpath.read_text())
        w("## Measured fp.mul throughput (benches/bench_fp_mul.py)")
        w("")
        w(f"Backend `{m['backend']}`, {m['n_lanes']} lanes x depth "
          f"{m['depth']} chained products, median of {m['reps']} reps, "
          f"{m['macs_per_lane']} MACs/lane; int8 split shift "
          f"{m['split_shift']} (hi = limb>>{m['split_shift']} <= 127).")
        w("")
        w("| FP_IMPL | achieved MAC/s | step_s | spread | compile_s |")
        w("|---|---|---|---|---|")
        for name, r in m["impls"].items():
            w(f"| {name} | {r['mac_per_sec']:.3e} | {r['step_s']:.5f} | "
              f"{r['rep_spread']} | {r['compile_s']} |")
        ratio = m.get("matmul_int8_vs_toeplitz_int32")
        if ratio is not None:
            w("")
            w(f"matmul_int8 / toeplitz_int32 achieved-MAC/s ratio: "
              f"**{ratio}x** on this backend. The MXU claim in the table "
              "above is only validated by a run with backend `tpu`; a CPU "
              "ratio measures XLA:CPU's int8 vs int32 vectorization.")
        w("")
    # Measured kernel-family constants (ISSUE 16): the grown Pallas
    # surface — fused fp2 tower ops, Miller-loop line-eval, windowed G1
    # MSM — benched per engine with cross-engine byte-identity pinned
    # inside the bench itself.
    kpath = REPO / "BENCH_KERNELS.json"
    if kpath.exists():
        km = json.loads(kpath.read_text())
        w("## Measured kernel-family throughput (benches/bench_fp_mul.py "
          "--families, BENCH_KERNELS.json)")
        w("")
        w(f"Backend `{km['backend']}` (fp impl `{km['fp_impl']}`), median "
          f"of {km['reps']} reps per engine. fp2/line rows are MAC/s over "
          "the family's fp-lane count (fp2 mul = 3 lanes, sq = 2, "
          "line-eval doubling step = 31); the MSM row is point-adds/s "
          "over the masked bucket-reduction lanes (N x 16 windows x 15 "
          "buckets). Off-TPU the `fused_pallas`/`fused` engines run the "
          "Pallas kernels in interpreter mode — their CPU rows are "
          "semantics checks, not speed claims; only a backend `tpu` run "
          "measures the fusion win. Cross-engine sha256 byte-identity of "
          "canonical outputs is asserted by the bench before any rate is "
          "reported.")
        w("")
        w("| kernel | shape | engine | rate | step_s | compile_s |")
        w("|---|---|---|---|---|---|")
        for kname, krec in km["kernels"].items():
            shape = f"N={krec['n']}" + (
                f" depth={krec['depth']}" if "depth" in krec else ""
            )
            for ename, r in krec["impls"].items():
                rate = r.get("mac_per_sec", r.get("point_adds_per_sec"))
                unit = "MAC/s" if "mac_per_sec" in r else "adds/s"
                w(f"| {kname} | {shape} | {ename} | {rate:.3e} {unit} | "
                  f"{r['step_s']:.5f} | {r['compile_s']} |")
        w("")
    # Data-movement table (ISSUE 8): the shared byte model
    # (utils/transfer_ledger.operand_bytes_model, pinned against the raw
    # packer's actual ndarray.nbytes by tests/test_transfer_ledger.py) at
    # the rungs the flush planner actually dispatches — the sizing input
    # for ROADMAP item 2 (device-resident pubkey table).
    w("## Bytes per set, host→device (data-movement ledger model)")
    w("")
    w("Per-operand H2D bytes of one raw-packed batch at dispatched "
      "rungs, divided by B (the `operand_bytes_model` in "
      "`utils/transfer_ledger.py` — equality with the packer's real "
      "`ndarray.nbytes` is pinned by test). `pubkey share` is the "
      "fraction of all shipped bytes that is G1 pubkeys — the ceiling "
      "of ROADMAP item 2's device-resident-table win; the MEASURED "
      "counterpart is `bls_device_h2d_bytes_total{operand,kind}` and "
      "the bench `data_movement` block — NOTE the base: the measured "
      "`pubkeys` label counts LIVE bytes with padded-lane bytes under "
      "the separate `padding` label, while this table charges the full "
      "padded rung, so compare measured shares against the live base "
      "(total − padding); at full occupancy the two coincide. The "
      "realized win is that share times the measured "
      "`bls_device_pubkey_reupload_ratio` (gossip steady-state models "
      "at >0.9 over a few epochs — `tools/transfer_report.py`).")
    w("")
    w("| rung BxKxM | pubkeys B/set | signatures | messages | aux | "
      "total B/set | pubkey share | with key table | total w/ table |")
    w("|---|---|---|---|---|---|---|---|---|")
    for b, k, m in (
        (64, 8, 4),      # headline bucket
        (48, 8, 4),      # exact headline rung (planner)
        (32, 1, 8),      # kind-homogeneous unaggregated
        (16, 16, 8),     # kind-homogeneous aggregate
        (256, 16, 8),    # the large-B end the scheduler amortizes to
    ):
        ops = transfer_ledger.operand_bytes_model(b, k, m)
        idx = transfer_ledger.operand_bytes_model(b, k, m, indexed=True)
        w(
            f"| {b}x{k}x{m} | {ops['pubkeys'] / b:,.0f} | "
            f"{ops['signatures'] / b:,.0f} | {ops['messages'] / b:,.0f} | "
            f"{ops['aux'] / b:,.0f} | {ops['total'] / b:,.0f} | "
            f"{ops['pubkeys'] / ops['total'] * 100:.1f}% | "
            f"{idx['pubkeys'] / b:,.0f} | {idx['total'] / b:,.0f} |"
        )
    w("")
    w("Pubkeys dominate at every committee width — exactly the operand "
      "the device-resident key table (ISSUE 10, "
      "`crypto/device/key_table.py`) removes from the hot path: "
      "`submit()` carries validator indices and the pack becomes a "
      "device-side gather. The `with key table` columns are the SAME "
      "model with `indexed=True` — the static packer ships an int32 "
      "index + mask per pubkey slot (5 B) instead of a limb-packed G1 "
      "row (257 B); epoch-stable committee tuples collapse further to "
      "ONE cached aggregate-sum slot (K=1). Measured counterparts: "
      "`bls_device_key_table_sets_total{path}` (hit ratio) and the "
      "bench `key_table_leg` (gated in `tools/bench_diff.py` on "
      "`pubkeys_bytes_per_set`). Host pack time is attributed per "
      "phase alongside (`bls_device_pack_seconds{phase}`: decode, "
      "limb_split, pad, hash, device_put), so the pack-second share of "
      "the claim is measured too ([OBSERVABILITY.md](OBSERVABILITY.md) "
      "data-movement section; per-verify rows in the `transfer_ledger` "
      "journal events, which now carry an `indexed` flag).")
    w("")
    # Capacity / headroom formula (ISSUE 14): the live dial the
    # timeseries sampler serves, written here so the analytic model and
    # the served estimator can never drift apart silently.
    w("## Capacity & headroom formula (live estimator, ISSUE 14)")
    w("")
    w("The saturation dial served in `/lighthouse/health`'s `capacity` "
      "block and at `/lighthouse/timeseries` "
      "(`utils/timeseries.estimate_capacity`):")
    w("")
    w("```")
    w("capacity_sets_per_sec = healthy_shards / cost_s_per_set")
    w("utilization           = arrival_sets_per_sec / capacity_sets_per_sec")
    w("headroom_ratio        = max(0, 1 - utilization)")
    w("```")
    w("")
    w("Measured inputs, in preference order (the source is reported, "
      "never fabricated):")
    w("")
    w("- `cost_s_per_set` — (1) Σ `bls_device_shard_verify_seconds` / "
      "Σ `bls_device_shard_sets_total` over recent SAMPLING-INTERVAL "
      "deltas (per-shard dispatch walls, current — a lifetime average "
      "would mask what serving costs right now — so capacity scales "
      "with the shard axis); (2) "
      "`compile_service_measured_cost_seconds_per_set` (the organic "
      "rung-cost feed `note_rung_verified` accumulates — per-rung "
      "splits in the compile service status); (3) the pipeline "
      "profiler's flush walls per fused set. The analytic counterpart "
      "is the lanes/set tables above divided by the achieved MAC/s.")
    w("- `healthy_shards` — `crypto/device/mesh.healthy_shard_count()` "
      "live (falls back to the `verification_scheduler_dp_shards` "
      "gauge; 1 when single-device).")
    w("- `arrival_sets_per_sec` — the rated "
      "`verification_scheduler_arrival_sets_total{kind,path}` counter "
      "(submission-time accounting, so demand keeps climbing past "
      "saturation instead of reading serving throughput back).")
    w("")
    w("The headroom dial is PREDICTIVE: on a `saturation_ramp` trace "
      "it crosses below 0.2 while utilization is still under 1.0, and "
      "the backlog integral needs further time to blow the SLO budget "
      "— so the crossing and the `slo_burn` burn-rate alert both land "
      "strictly before the first deadline-miss burst "
      "(`tests/test_timeseries_capacity.py`; modeled offline by "
      "`tools/capacity_report.py`, measured at the bench's headline "
      "cost in the `capacity_leg`, `headroom_ratio` learned by "
      "`tools/bench_diff.py`). This is the go/no-go input ROADMAP "
      "item 2's bulk-QoS admission control reads — the committee "
      "batch-verification cost model (arxiv 2302.00418) puts the "
      "nonlinear throughput-vs-load regime exactly where the 1M-"
      "validator firehose lives.")
    w("")
    w("## Reading the table")
    w("")
    w("- The 50k agg/s target (150k sets/s, BASELINE.json) needs ~"
      f"{150e3 * rows[1]['gmacs_per_set'] / 1e3:.0f} int32 TMAC/s at K=16 — "
      "only the MXU-decomposition scenario reaches that envelope; if XLA "
      "keeps int32 dots on the VPU, the ceiling is the VPU row and the "
      "kernel must move to an int8-decomposed Pallas matmul to go further.")
    w("- Scalar-mul + Miller dominate (~2/3 of lanes). Both are scan-bound "
      "with full-batch width, so they saturate whatever unit executes the "
      "banded dot; bytes/set "
      f"({bytes_per_set(16):,} B at K=16) against >GMAC/set arithmetic means "
      "the program is compute-bound on any plausible HBM bandwidth.")
    w("- Cross-check vs measured XLA:CPU (DP_SCALING.json): ~5 sets/s at "
      f"K=16 implies ~{5 * rows[1]['gmacs_per_set']:.0f} int32 GMAC/s "
      "achieved on one CPU core-ish — the right order for scalar int32 "
      "code, which says the lane count above is the true work, not "
      "padding waste.")
    w("- Measured counterpart: the `bls_device_stage_seconds` histogram "
      "family labeled `{stage, fp_impl}` (scraped at `/metrics`, surfaced "
      "as `stage_latency` in the bench JSON) gives the observed per-stage "
      "split to hold against this model — see "
      "[OBSERVABILITY.md](OBSERVABILITY.md).")
    w("- Per-batch counterpart: every staged verify journals a "
      "`bls_stage_verify` flight-recorder event (batch geometry, per-stage "
      "seconds, recompile flag, verdict), so a tail-latency outlier can be "
      "explained from its OWN stage split, not the aggregate histogram — "
      "`tools/forensics_report.py` renders the attribution from a dump "
      "([OBSERVABILITY.md](OBSERVABILITY.md), flight-recorder section).")
    w("- Amortization lever: the per-batch fixed overhead (pack + dispatch "
      "+ padded lanes) this model prices is what the verification "
      "scheduler exists to amortize — it fuses signature sets from MANY "
      "concurrent callers into one ladder-bucket batch under a latency "
      "deadline, so real traffic runs at the large-B end of these tables "
      "instead of one caller's burst size "
      "([VERIFICATION_SERVICE.md](VERIFICATION_SERVICE.md); occupancy and "
      "padding-waste gauges in "
      "[OBSERVABILITY.md](OBSERVABILITY.md)).")
    w("- Padded-lane cost: every lane count above is charged per PADDED "
      "lane, not per live set — the device pays B·K·M cells whatever the "
      "occupancy, so `padding_waste = 1 − live/(B·K·M)` multiplies "
      "straight into sets/s (the 0.6875 headline waste was a ~3.2x "
      "throughput loss no kernel work could recover). The flush planner "
      "splits a fused flush into kind-homogeneous, bin-packed sub-batches "
      "precisely to shrink that factor; its scoring unit is the same "
      "B·K·M cell this model counts, with a per-extra-dispatch overhead "
      "charge standing in for the fixed pack+dispatch cost above "
      "([VERIFICATION_SERVICE.md](VERIFICATION_SERVICE.md) flush-planner "
      "section; `verification_scheduler_plan_lanes_total{live,padded}` "
      "and the shared waste gauges in "
      "[OBSERVABILITY.md](OBSERVABILITY.md); plans inspectable offline "
      "via `tools/flush_plan_report.py`).")
    w("- Measured tails, not just means: the analytic per-batch cost "
      "above prices the MEAN dispatch; what a submitter experiences is "
      "the submit-to-verdict TAIL under a real arrival process (queue "
      "wait + the batch its flush landed in + any fallback/bisection "
      "detour). The traffic-replay harness drives the scheduler with "
      "mainnet-shaped arrival traces (epoch-boundary floods, "
      "sync-committee periods, backfill under gossip) and certifies "
      "per-kind p50/p99 and deadline-miss ratio against this model's "
      "per-batch costs — "
      "`verification_scheduler_verdict_latency_seconds{kind,path}` per "
      "resolution path, rolling window at `/lighthouse/health` `slo`, "
      "`replay_leg` in the bench JSON "
      "([TRAFFIC_REPLAY.md](TRAFFIC_REPLAY.md); families in "
      "[OBSERVABILITY.md](OBSERVABILITY.md)).")
    w("- Epoch-boundary cost, in chain time (ISSUE 17): the arrival "
      "process is not stationary — the attestation flood concentrates "
      "~8x demand into the two epoch-boundary slots, so a lifetime "
      "mean under-prices exactly the window where the per-batch costs "
      "above bind hardest (deep queues push flushes to the large-B "
      "rungs; cold shapes and parked bulk land there too). The slot "
      "ledger attributes every resolution, miss, byte and bubble to "
      "its beacon slot (`utils/slot_ledger.py`, per-slot report cards "
      "at `/lighthouse/slots`), and the SAME window is where the "
      "epoch-stable committee tuples make the key table's cached "
      "aggregate-sum slot (K=1 above) pay: the per-epoch "
      "`key_table_first_sighting_hit_ratio` dial says how much of the "
      "flood's K G1-add aggregation cost actually collapsed — the "
      "canonical flood replays near 0.8, i.e. ~4/5 of committee "
      "sightings skip the host EC sum entirely (the bench "
      "`epoch_flood_leg` tracks the per-slot p99 spread and the dial; "
      "[OBSERVABILITY.md](OBSERVABILITY.md) chain-time section; "
      "[TRAFFIC_REPLAY.md](TRAFFIC_REPLAY.md)).")
    w("- First-sighting cost goes to ~zero with duty lookahead "
      "(ISSUE 19): the remaining ~1/5 above is pure timing — next "
      "epoch's committee assignments are fully determined one epoch in "
      "advance, so the duty-lookahead worker "
      "(`duty_lookahead/`, [DUTY_LOOKAHEAD.md](DUTY_LOOKAHEAD.md)) "
      "computes each committee's K-point G1 sum OFF the hot path (a "
      "unit-scalar MSM at the smallest covering rung, host fold on "
      "fallback) past the mid-epoch trigger and pre-inserts the rows, "
      "bypassing the repeat-admission gate. The flood replay's dial "
      "moves 0.8 → 1.0 with zero host EC additions left inside verify "
      "spans — the K G1-add term above is prepaid in idle time, and "
      "the epoch-tagged two-epoch retention means the boundary no "
      "longer risks a wholesale region reset (the bench "
      "`lookahead_leg` measures the off/on pair; the watchtower floors "
      "the dial at 0.9).")
    w("- Per-chip scaling (ISSUE 11): every table above prices ONE "
      "chip, and the dp mesh multiplies it — flush plans gain a "
      "(dp_shard × rung) axis, each shard's kind-homogeneous sub-batch "
      "verifies on its own device, and aggregate sets/s is the SUM of "
      "per-chip rates at the busiest-shard wall-clock (shards run "
      "concurrently, so the planner scores the busiest shard's padded "
      "lanes, not the lane sum). The committee batch-verification cost "
      "model (arxiv 2302.00418) compounds with parallel lanes exactly "
      "at the big warm rungs the mesh serves (B=256/512, "
      "DP_SCALING.json); losing a chip degrades the multiplier by one "
      "instead of zeroing it ([MULTICHIP.md](MULTICHIP.md); per-chip "
      "`bls_device_shard_*` families and the `/lighthouse/health` "
      "`mesh` block in [OBSERVABILITY.md](OBSERVABILITY.md); 1-vs-2 "
      "device measurements in the bench `dp_leg`).")
    w("- Overlap potential (ISSUE 12): every per-batch cost above is "
      "charged as if pack and device compute were SERIAL — the flush "
      "thread packs, dispatches and blocks until sync, so the device "
      "idles for the whole host pack. The pipeline profiler measures "
      "that idle directly and attributes it per cause "
      "(`bls_device_bubble_seconds_total{shard,cause}`, flush "
      "critical-path `pipeline_flush` events) and projects the ROADMAP "
      "item 5 win: overlapping pack for flush N+1 with flush N's "
      "device time hides min(pack, device) per flush — the "
      "`overlap_potential` block in `/lighthouse/health` `pipeline` "
      "and the bench `pipeline_leg` carry the projected sets/s "
      "(`verification_scheduler_overlap_potential_ratio`; modeled "
      "offline by `tools/pipeline_report.py`; families in "
      "[OBSERVABILITY.md](OBSERVABILITY.md) pipeline section).")
    w("- Setup cost, not in these tables: the FIRST dispatch of each "
      "staged program at a fresh bucket shape pays the XLA compile "
      "(~120 s for the B=64 headline rung on this host, BENCH_r05 / the "
      "bench `startup` block). The compile service moves that cost off "
      "the hot path — AOT ladder warmup, pad-up routing to warm rungs, "
      "counted CPU fallback while a cold rung compiles, and a persistent "
      "executable cache so a restarted node pays it from disk "
      "([COMPILE_SERVICE.md](COMPILE_SERVICE.md); "
      "`compile_service_compile_seconds` per-stage histogram in "
      "[OBSERVABILITY.md](OBSERVABILITY.md)).")
    w("")
    out = REPO / "docs" / "COST_MODEL.md"
    out.write_text("\n".join(lines) + "\n")
    print(f"wrote {out}")
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
