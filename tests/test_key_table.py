"""Device-resident validator pubkey table (ISSUE 10): host-cache
mirroring, delta admission, identity pinning, the static/dynamic
resolution contract, the aggregate-sum cache, the planner split, and
the indexed byte model.

Device dispatches here are limited to the tiny gather program and eager
row uploads (sub-second on XLA:CPU); the full staged gathered pipeline
is gated by tests/test_zgate7_key_table.py (tail-sorted — it compiles
for minutes)."""

from __future__ import annotations

import types

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.device import key_table as kt


def _wrapper_cache(n, seed=1000):
    """n distinct PublicKey wrappers, as a ValidatorPubkeyCache-shaped
    shim (the table needs only an append-only ``pubkeys`` list of
    ``.point``-bearing objects)."""
    sks = [bls.SecretKey(seed + i) for i in range(n)]
    pks = [sk.public_key() for sk in sks]
    return sks, types.SimpleNamespace(pubkeys=pks)


def _store_cache(n, store=None, seed=2000):
    """A REAL ValidatorPubkeyCache admitted from a fake state (the
    store round-trips compressed bytes like the chain's does)."""
    from lighthouse_tpu.beacon_chain.pubkey_cache import ValidatorPubkeyCache

    sks = [bls.SecretKey(seed + i) for i in range(n)]
    state = types.SimpleNamespace(
        validators=[
            types.SimpleNamespace(pubkey=sk.public_key().serialize())
            for sk in sks
        ]
    )
    cache = ValidatorPubkeyCache(store)
    cache.import_new_pubkeys(state)
    return sks, state, cache


def _sets_for(sks, cache, msg=b"\x21" * 32, singles=None, committee=None):
    """(sig, [points], msg) triples resolved through ``cache`` — the
    prepared-triple shape the backend sees. ``singles``/``committee``
    are cache indices (singles defaults to every key)."""
    out = []
    if singles is None:
        singles = range(len(sks))
    for i in singles:
        sig = bls.Signature.deserialize(sks[i].sign(msg).serialize())
        out.append((sig, [cache.pubkeys[i].point], msg))
    if committee:
        from lighthouse_tpu.crypto.params import R

        sk_sum = sum(sk.k for sk in (sks[i] for i in committee)) % R
        agg = bls.Signature.deserialize(
            bls.SecretKey(sk_sum).sign(msg).serialize()
        )
        out.append((agg, [cache.pubkeys[i].point for i in committee], msg))
    return out


# ---------------------------------------------------------------------------
# Startup sync + identity
# ---------------------------------------------------------------------------


def test_startup_sync_mirrors_cache_to_index_identity():
    from lighthouse_tpu.crypto.device import curve

    _sks, cache = _wrapper_cache(5)
    t = kt.DeviceKeyTable(cache)
    assert t.sync(reason="startup") == 5
    assert len(t) == 5 == len(cache.pubkeys)
    dev = np.asarray(t.device_arrays()[0])
    for i, pk in enumerate(cache.pubkeys):
        expect, inf = curve.pack_g1([pk.point])
        assert not inf[0]
        assert (dev[i] == expect[0]).all(), f"row {i} != cache point"
        assert t.index_of_point(pk.point) == i
    st = t.status()
    assert st["validators_resident"] == 5
    assert st["identity_pinned"] is True
    assert st["upload_bytes"]["startup"] == 5 * kt.G1_ROW_BYTES
    # a second sync is a no-op (nothing new admitted)
    assert t.sync() == 0


def test_limb_layout_pinned_to_device_fp():
    # key_table must stay jax-free at import, so it carries its own NL;
    # this pin is what keeps it equal to the device layout
    from lighthouse_tpu.crypto.device import fp

    assert kt.NL == fp.NL


def test_capacity_ladder_round_up():
    assert kt.table_capacity(1) == 1024
    assert kt.table_capacity(1024) == 1024
    assert kt.table_capacity(1025) == 4096
    assert kt.table_capacity(1_000_000) == 1048576
    assert kt.table_capacity(1_100_000) == 2 * 1048576


# ---------------------------------------------------------------------------
# Delta admission (satellite): deposits extend, exits keep rows,
# restart-from-store reloads to identity, bad admission is atomic
# ---------------------------------------------------------------------------


def test_delta_admission_extends_to_index_identity():
    from lighthouse_tpu.crypto.device import curve

    sks, state, cache = _store_cache(3)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    cache.subscribe(lambda _c, _t=t: _t.sync(reason="delta"))

    # deposits: four more validators admitted past the current length
    more = [bls.SecretKey(9000 + i) for i in range(4)]
    state.validators.extend(
        types.SimpleNamespace(pubkey=sk.public_key().serialize())
        for sk in more
    )
    cache.import_new_pubkeys(state)  # listener delta-syncs the device
    assert len(t) == 7 == len(cache.pubkeys)
    dev = np.asarray(t.device_arrays()[0])
    for i in (3, 4, 5, 6):
        expect, _ = curve.pack_g1([cache.pubkeys[i].point])
        assert (dev[i] == expect[0]).all()
        assert t.index_of_point(cache.pubkeys[i].point) == i
    assert t.status()["upload_bytes"]["delta"] == 4 * kt.G1_ROW_BYTES

    # exits leave rows resident: indices are append-only, and an exited
    # validator's historical signatures still resolve
    cache.import_new_pubkeys(state)  # same state again: nothing changes
    assert len(t) == 7
    assert t.index_of_point(cache.pubkeys[0].point) == 0


def test_restart_from_store_reloads_to_identity():
    from lighthouse_tpu.store import MemoryStore

    store = types.SimpleNamespace(kv=MemoryStore())
    _sks, _state, cache = _store_cache(4, store=store)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")

    # restart: a fresh cache reloads from the store (re-validated), and
    # a fresh table mirrors IT — index identity and resolution both hold
    # against the reloaded objects
    from lighthouse_tpu.beacon_chain.pubkey_cache import ValidatorPubkeyCache

    cache2 = ValidatorPubkeyCache(store)
    assert len(cache2.pubkeys) == 4
    t2 = kt.DeviceKeyTable(cache2)
    t2.sync(reason="startup")
    assert np.array_equal(
        np.asarray(t.device_arrays()[0])[:4], np.asarray(t2.device_arrays()[0])[:4]
    )
    for i, pk in enumerate(cache2.pubkeys):
        assert t2.index_of_point(pk.point) == i
        # ...and the OLD table does NOT claim the reloaded objects: the
        # identity map never confuses equal-valued foreign points
        assert t.index_of_point(pk.point) is None


def test_invalid_admission_raises_before_device_mirror():
    sks, state, cache = _store_cache(2)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    cache.subscribe(lambda _c, _t=t: _t.sync(reason="delta"))

    # an invalid pubkey (off-curve bytes) raises in admission — the
    # listener never runs, and the device table is untouched
    state.validators.append(
        types.SimpleNamespace(pubkey=b"\xaa" + bytes(47))
    )
    with pytest.raises(bls.BlsError):
        cache.import_new_pubkeys(state)
    assert len(t) == 2
    assert t.status()["upload_bytes"]["delta"] == 0


def test_gap_and_invalid_rows_are_atomic_in_sync():
    _sks, cache = _wrapper_cache(3)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    before = np.asarray(t.device_arrays()[0]).copy()

    # invalid row mid-delta: sync raises and commits NOTHING — not even
    # the valid rows packed before the bad one
    good = bls.SecretKey(7777).public_key()
    cache.pubkeys.extend([good, types.SimpleNamespace(point=None)])
    with pytest.raises(kt.KeyTableError):
        t.sync()
    assert len(t) == 3
    assert t.index_of_point(good.point) is None
    assert np.array_equal(np.asarray(t.device_arrays()[0]), before)

    # a shrunken cache (gap below the resident rows) raises too
    del cache.pubkeys[1:]
    with pytest.raises(kt.KeyTableError):
        t.sync()
    assert len(t) == 3


# ---------------------------------------------------------------------------
# Resolution: identity pinning, fallback, aggregate collapse
# ---------------------------------------------------------------------------


def test_resolution_is_identity_pinned_not_equality():
    sks, cache = _wrapper_cache(3)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    sets = _sets_for(sks, cache)
    res = t.resolve_sets(sets)
    assert res is not None
    resolved, _dev, _agg, collapsed = res
    assert resolved == [[0], [1], [2]] and collapsed == 0

    # a byte-equal FOREIGN point (fresh deserialize — a different
    # state's resolver would produce this) must MISS: the whole batch
    # falls back to the raw plane rather than trust an equal-looking key
    foreign = bls.PublicKey.deserialize(cache.pubkeys[0].serialize())
    assert foreign.point is not cache.pubkeys[0].point
    bad = list(sets)
    bad[0] = (sets[0][0], [foreign.point], sets[0][2])
    assert t.resolve_sets(bad) is None
    assert t.status()["sets"]["raw"] >= len(bad)


def test_aggregate_collapse_on_repeat_and_region_reset():
    from lighthouse_tpu.crypto.device import curve

    sks, cache = _wrapper_cache(6)
    t = kt.DeviceKeyTable(cache, max_aggregates=1)
    t.sync(reason="startup")
    committee_a = _sets_for(sks, cache, singles=[], committee=[0, 1, 2])
    committee_b = _sets_for(sks, cache, singles=[], committee=[3, 4, 5])

    # first sighting ships K indices (no host sum paid for one-shots)
    r1, _, _, c1 = t.resolve_sets(committee_a)
    assert c1 == 0 and len(r1[0]) == 3
    # second sighting collapses to ONE aggregate-sum slot
    r2, dev, agg, c2 = t.resolve_sets(committee_a)
    assert c2 == 1 and len(r2[0]) == 1
    slot = r2[0][0]
    cap_v = t.status()["validator_capacity"]
    assert slot >= cap_v
    host_sum = cache.pubkeys[0].point
    for i in (1, 2):
        host_sum = host_sum + cache.pubkeys[i].point
    expect, _ = curve.pack_g1([host_sum])
    assert (np.asarray(agg[slot - cap_v]) == expect[0]).all()

    # region bound 1: a second committee's insert first marks the
    # region for a DEFERRED recycle (a mid-batch reset would invalidate
    # slots already handed out), then collapses once the recycle has
    # applied at the start of a following resolve
    for _ in range(4):
        r4, _, _, c4 = t.resolve_sets(committee_b)
        if c4:
            break
    assert c4 == 1 and len(r4[0]) == 1
    st = t.status()
    assert st["aggregate_resets"] >= 1
    # ...and the evicted tuple simply ships K indices again (then
    # re-inserts on its next repeat) — correctness never depends on
    # the cache
    r5, _, _, _ = t.resolve_sets(committee_a)
    assert len(r5[0]) in (1, 3)


def test_resolve_seconds_observe_the_phases_each_call_runs():
    """bls_device_key_table_resolve_seconds{phase}: every call observes
    total; single-key sets only the lookup; a committee's sightings
    the aggregate-cache consult, and its repeat the host sum and the
    row upload too. Each phase is a key_table.* span inside
    key_table.resolve."""
    from lighthouse_tpu.utils import tracing

    sks, cache = _wrapper_cache(4)
    t = kt.DeviceKeyTable(cache, max_aggregates=2)
    t.sync(reason="startup")
    phases = kt.RESOLVE_PHASES + ("total",)

    def resolve(sets):
        before = {p: kt._RESOLVE_CHILD[p].snapshot()[:2] for p in phases}
        assert t.resolve_sets(sets) is not None
        after = {p: kt._RESOLVE_CHILD[p].snapshot()[:2] for p in phases}
        seconds = {p: after[p][1] - before[p][1] for p in phases}
        assert all(v >= 0 for v in seconds.values())
        assert sum(seconds[p] for p in kt.RESOLVE_PHASES) <= seconds["total"]
        return {p: after[p][0] - before[p][0] for p in phases}

    assert resolve(_sets_for(sks, cache)) == {
        "lookup": 1, "aggregates": 0, "agg_sum": 0, "agg_upload": 0,
        "total": 1,
    }
    committee = _sets_for(sks, cache, singles=[], committee=[0, 1, 2])
    assert resolve(committee) == {
        "lookup": 1, "aggregates": 1, "agg_sum": 0, "agg_upload": 0,
        "total": 1,
    }
    was = tracing.enabled()
    tracing.enable()
    tracing.clear()
    try:
        assert resolve(committee) == {
            "lookup": 1, "aggregates": 1, "agg_sum": 1, "agg_upload": 1,
            "total": 1,
        }
        spans = {e["name"]: e["args"] for e in tracing.events()}
    finally:
        tracing.clear()
        if not was:
            tracing.disable()
    assert spans["key_table.resolve"] == {"sets": 1}
    for p in kt.RESOLVE_PHASES:
        assert spans[f"key_table.{p}"]["parent"] == "key_table.resolve"
    # a batch with a key the table does not hold still times the call
    other, _ = _wrapper_cache(1, seed=9000)
    n0 = kt._RESOLVE_CHILD["total"].snapshot()[0]
    assert t.resolve_sets(_sets_for(other, types.SimpleNamespace(
        pubkeys=[other[0].public_key()]))) is None
    assert kt._RESOLVE_CHILD["total"].snapshot()[0] == n0 + 1


def test_mid_batch_region_full_never_recycles_held_slots():
    """Regression (review round 4): a batch [cached-committee-A,
    insert-hungry-committee-B] with a FULL 1-slot region must not
    recycle A's slot under the batch — A's encoded index has to gather
    A's sum, and B simply ships K indices until the deferred recycle
    lands in a later batch."""
    from lighthouse_tpu.crypto.device import curve

    sks, cache = _wrapper_cache(6)
    t = kt.DeviceKeyTable(cache, max_aggregates=1, agg_min_repeats=1)
    t.sync(reason="startup")
    committee_a = _sets_for(sks, cache, singles=[], committee=[0, 1, 2])
    committee_b = _sets_for(sks, cache, singles=[], committee=[3, 4, 5])

    ra, _, _, ca = t.resolve_sets(committee_a)  # min_repeats=1: inserts
    assert ca == 1
    slot_a = ra[0][0]

    # the poisoned-shape batch: A hits its slot, B's insert finds the
    # region full mid-batch
    rr, _dev, agg, cc = t.resolve_sets(committee_a + committee_b)
    assert rr[0] == [slot_a], "A must keep its already-cached slot"
    assert len(rr[1]) == 3, "B must ship K indices, not a recycled slot"
    sum_a = cache.pubkeys[0].point
    for i in (1, 2):
        sum_a = sum_a + cache.pubkeys[i].point
    expect_a, _ = curve.pack_g1([sum_a])
    cap_v = t.status()["validator_capacity"]
    assert (np.asarray(agg[slot_a - cap_v]) == expect_a[0]).all(), (
        "A's encoded slot must still hold A's aggregate sum"
    )

    # the deferred recycle lands in a LATER batch; the earlier agg
    # snapshot is functional and keeps serving A's sum
    rb, _, agg2, cb = t.resolve_sets(committee_b)
    assert cb == 1
    assert (np.asarray(agg[slot_a - cap_v]) == expect_a[0]).all()
    sum_b = cache.pubkeys[3].point
    for i in (4, 5):
        sum_b = sum_b + cache.pubkeys[i].point
    expect_b, _ = curve.pack_g1([sum_b])
    assert (np.asarray(agg2[rb[0][0] - cap_v]) == expect_b[0]).all()


def test_infinity_aggregate_is_never_cached():
    sks, cache = _wrapper_cache(2)
    # a pubkey pair that sums to infinity: P and -P. Build -P directly.
    p = cache.pubkeys[0].point
    neg = type(p)(p.x, -p.y)
    cache.pubkeys[1] = types.SimpleNamespace(point=neg)
    t = kt.DeviceKeyTable(cache, agg_min_repeats=1)
    t.sync(reason="startup")
    sig = bls.Signature.deserialize(sks[0].sign(b"\x33" * 32).serialize())
    sets = [(sig, [cache.pubkeys[0].point, cache.pubkeys[1].point],
             b"\x33" * 32)]
    for _ in range(3):
        resolved, _, _, collapsed = t.resolve_sets(sets)
        # never collapsed: the device's agg_inf_bad screen keeps owning
        # the infinity-sum edge exactly like the raw path
        assert collapsed == 0 and len(resolved[0]) == 2
    assert t.status()["aggregates_resident"] == 0


# ---------------------------------------------------------------------------
# SignatureSet threading + planner split
# ---------------------------------------------------------------------------


def test_signature_set_carries_signing_indices():
    sks, cache = _wrapper_cache(2)
    msg = b"\x10" * 32
    sig = bls.Signature.deserialize(sks[0].sign(msg).serialize())
    s = bls.SignatureSet.single_pubkey(
        sig, cache.pubkeys[0], msg, signing_index=7
    )
    assert s.signing_indices == [7]
    s2 = bls.SignatureSet.multiple_pubkeys(
        sig, cache.pubkeys, msg, signing_indices=[0, 1]
    )
    assert s2.signing_indices == [0, 1]
    with pytest.raises(bls.BlsError):
        bls.SignatureSet.multiple_pubkeys(
            sig, cache.pubkeys, msg, signing_indices=[0]
        )
    # default stays None (library callers unchanged)
    assert bls.SignatureSet.single_pubkey(
        sig, cache.pubkeys[0], msg
    ).signing_indices is None


def test_covers_sets_prefilters_on_indices_and_pins_on_identity():
    sks, cache = _wrapper_cache(2)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    msg = b"\x11" * 32
    sig = bls.Signature.deserialize(sks[0].sign(msg).serialize())
    ok = bls.SignatureSet.single_pubkey(
        sig, cache.pubkeys[0], msg, signing_index=0
    )
    assert t.covers_sets([ok])
    # out-of-range advisory index fails fast
    stale = bls.SignatureSet.single_pubkey(
        sig, cache.pubkeys[0], msg, signing_index=99
    )
    assert not t.covers_sets([stale])
    # a foreign key fails the identity map even with a plausible index
    foreign = bls.PublicKey.deserialize(cache.pubkeys[1].serialize())
    alien = bls.SignatureSet.single_pubkey(sig, foreign, msg, signing_index=1)
    assert not t.covers_sets([alien])


def test_planner_splits_static_from_dynamic():
    from lighthouse_tpu.verification_service.planner import FlushPlanner

    sks, cache = _wrapper_cache(4)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    msg = b"\x12" * 32

    def _sub(kind, wrappers):
        sets = []
        for i, w in enumerate(wrappers):
            sig = bls.Signature.deserialize(sks[0].sign(msg).serialize())
            sets.append(bls.SignatureSet.single_pubkey(sig, w, msg))
        return types.SimpleNamespace(kind=kind, sets=sets)

    static_sub = _sub("unaggregated", [cache.pubkeys[0], cache.pubkeys[1]])
    foreign = bls.PublicKey.deserialize(cache.pubkeys[2].serialize())
    dynamic_sub = _sub("unaggregated", [foreign])

    kt.set_table(t)
    try:
        plan = FlushPlanner(enabled=True).plan([static_sub, dynamic_sub])
        # same kind, but static/dynamic separation forces the split: one
        # out-of-table submission must not drag the static one back to
        # the raw plane (the backend's decision is all-or-nothing)
        assert plan.mode == "planned"
        statics = {sb.static for sb in plan.sub_batches}
        assert statics == {True, False}
        for sb in plan.sub_batches:
            if sb.static:
                assert static_sub in sb.subs and dynamic_sub not in sb.subs
        # without a table: byte-identical pre-ISSUE-10 behavior — one
        # kind, one bin, single-rung plan
        kt.clear_table(t)
        plan2 = FlushPlanner(enabled=True).plan([static_sub, dynamic_sub])
        assert plan2.mode == "single"
        assert plan2.sub_batches[0].static is False
    finally:
        kt.clear_table()


# ---------------------------------------------------------------------------
# Indexed packer: byte model pin + gather plane identity
# ---------------------------------------------------------------------------


def test_indexed_pack_bytes_match_model_and_gather_matches_raw():
    import jax

    from lighthouse_tpu.crypto.device import bls as dbls
    from lighthouse_tpu.utils import transfer_ledger as tl

    sks, cache = _wrapper_cache(5)
    t = kt.DeviceKeyTable(cache)
    t.sync(reason="startup")
    sets = _sets_for(sks, cache, singles=[0, 1], committee=[0, 1, 2, 3, 4])
    res = t.resolve_sets(sets)
    assert res is not None
    resolved, dev, agg, _ = res

    B, K, M = 4, 8, 1
    args_idx = dbls.pack_signature_sets_indexed(
        sets, resolved, pad_b=B, pad_k=K, pad_m=M
    )
    args_raw = dbls.pack_signature_sets_raw(sets, pad_b=B, pad_k=K, pad_m=M)

    # the analytic indexed model IS the packer's ndarray.nbytes
    model = tl.operand_bytes_model(B, K, M, indexed=True)
    assert args_idx[0].nbytes + args_idx[1].nbytes == model["pubkeys"]
    assert args_idx[2].nbytes + args_idx[3].nbytes == model["signatures"]
    assert args_idx[4].nbytes + args_idx[5].nbytes == model["messages"]
    assert args_idx[6].nbytes + args_idx[7].nbytes == model["aux"]
    assert sum(a.nbytes for a in args_idx) == model["total"]
    # and the pubkey plane shrank by the documented ~98% at this rung
    raw_model = tl.operand_bytes_model(B, K, M)
    assert model["pubkeys"] / raw_model["pubkeys"] < 0.02

    # the gathered planes are byte-identical to the raw pack's on every
    # live slot (masked slots differ by design: raw zero-fills, gather
    # clips — both screened by pk_mask)
    gathered = np.asarray(jax.block_until_ready(dbls._gather(dev, agg, args_idx[0])))
    raw_pk = np.asarray(args_raw[0])
    mask = np.asarray(args_idx[1])
    assert gathered.shape == raw_pk.shape
    assert (np.asarray(args_raw[1]) == mask).all()
    assert (gathered[mask] == raw_pk[mask]).all()

    # every non-pubkey plane of the two packers agrees in shape/dtype
    for a, b in zip(args_idx[2:], args_raw[2:]):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_transfer_report_models_key_table_hit_ratio():
    from lighthouse_tpu.verification_service import traffic
    from tools.transfer_report import replay_model

    events = traffic.gossip_steady(seed=7, duration_s=16.0)
    rep = replay_model(events)
    km = rep["key_table_model"]
    assert km["sets_indexed"] + km["sets_raw"] > 0
    assert 0.0 < km["hit_ratio"] <= 1.0
    # steady-state repeats dominate: most sets index-ship, and the
    # modeled pubkey plane shrinks substantially
    assert km["hit_ratio"] > 0.5
    assert km["pubkey_bytes_with_table"] < km["pubkey_bytes_raw_plane"]
    assert km["pubkey_reduction_ratio"] > 0.4
    assert (
        km["pubkey_bytes_saved"]
        == km["pubkey_bytes_raw_plane"] - km["pubkey_bytes_with_table"]
    )


def test_concurrent_resolve_always_gathers_the_right_sum():
    """8 threads x overlapping committees x a 2-slot region in constant
    churn: whatever each resolve returns — collapsed slot or K indices —
    gathering its rows from ITS OWN snapshot must reproduce exactly its
    committee's points/sum. Pins the generation-guarded commit (the
    lock is dropped around host summation)."""
    import threading

    from lighthouse_tpu.crypto.device import curve

    sks, cache = _wrapper_cache(8)
    t = kt.DeviceKeyTable(cache, max_aggregates=2, agg_min_repeats=1)
    t.sync(reason="startup")
    committees = [[0, 1, 2], [3, 4, 5], [2, 3, 6], [1, 5, 7]]
    expected = {}
    for ci, members in enumerate(committees):
        s = cache.pubkeys[members[0]].point
        for i in members[1:]:
            s = s + cache.pubkeys[i].point
        expected[ci] = curve.pack_g1([s])[0][0]
    sets_by_c = {
        ci: _sets_for(sks, cache, singles=[], committee=members)
        for ci, members in enumerate(committees)
    }
    errors = []

    def worker(tid):
        try:
            for rep in range(25):
                ci = (tid + rep) % len(committees)
                res = t.resolve_sets(sets_by_c[ci])
                assert res is not None
                resolved, dev, agg, _c = res
                idxs = resolved[0]
                if len(idxs) == 1 and idxs[0] >= dev.shape[0]:
                    row = np.asarray(agg[idxs[0] - dev.shape[0]])
                    assert (row == expected[ci]).all(), (
                        f"committee {ci} gathered a foreign sum"
                    )
                else:
                    got = [np.asarray(dev[i]) for i in idxs]
                    want = [
                        curve.pack_g1([cache.pubkeys[i].point])[0][0]
                        for i in committees[ci]
                    ]
                    for g, w in zip(got, want):
                        assert (g == w).all()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors


def test_capacity_growth_rebases_cached_aggregate_indices():
    """Regression (review round 5): a cached aggregate slot's ENCODED
    index is cap_v + slot; capacity growth moves the base, so the
    encoding must always come from the same locked section that
    snapshots the arrays — a stale base would gather a validator row
    where the aggregate region used to begin."""
    from lighthouse_tpu.crypto.device import curve

    sks, cache = _wrapper_cache(3)
    t = kt.DeviceKeyTable(cache, agg_min_repeats=1)
    t.sync(reason="startup")
    committee = _sets_for(sks, cache, singles=[], committee=[0, 1, 2])
    r1, _, _, c1 = t.resolve_sets(committee)
    assert c1 == 1 and r1[0][0] == 1024  # cap_v 1024, slot 0

    # deposits push the cache past the capacity rung: the validator
    # array grows device-side, the aggregate ROW survives, and the
    # encoding rebases to the new cap_v
    cache.pubkeys.extend(
        bls.SecretKey(50_000 + i).public_key() for i in range(1022)
    )
    t.sync(reason="delta")
    assert t.status()["validator_capacity"] == 4096
    r2, dev, agg, c2 = t.resolve_sets(committee)
    assert c2 == 1 and r2[0][0] == 4096  # rebased, same slot 0
    sum_pt = cache.pubkeys[0].point
    for i in (1, 2):
        sum_pt = sum_pt + cache.pubkeys[i].point
    expect, _ = curve.pack_g1([sum_pt])
    assert (np.asarray(agg[r2[0][0] - dev.shape[0]]) == expect[0]).all()
