"""Native C backend (`_native/bls12381.c`, backend "cpu-native"):
correctness vs the pure-Python oracle and vs by-construction truth.

Reference contract being matched: ``crypto/bls/src/impls/blst.rs:36-119``
(random-linear-combination batch verification, subgroup-checked
signatures, empty-set/infinity edge rules) and the DST pinned at
``blst.rs:14``.
"""

import hashlib
import secrets

import pytest

from lighthouse_tpu.crypto import backend, bls
from lighthouse_tpu.crypto.params import DST, P

try:
    from lighthouse_tpu.crypto.native import NativeBackend, lib

    _NATIVE = NativeBackend()
except Exception as e:  # no compiler in this environment
    _NATIVE = None
    _REASON = str(e)

pytestmark = pytest.mark.skipif(
    _NATIVE is None, reason="native backend unavailable"
)

SK = [bls.SecretKey(i + 1) for i in range(12)]
PK = [s.public_key() for s in SK]


def _msg(i: int) -> bytes:
    return hashlib.sha256(b"native-%d" % i).digest()


def _valid_set(i: int, n_pks: int = 1):
    m = _msg(i)
    agg = bls.AggregateSignature.infinity()
    pts = []
    for j in range(n_pks):
        agg.add_assign(SK[(i + j) % len(SK)].sign(m))
        pts.append(PK[(i + j) % len(SK)].point)
    return (agg, pts, m)


def test_selftest_and_hash_parity():
    import ctypes

    assert lib().bls_selftest() == 1
    from lighthouse_tpu.crypto.cpu.hash_to_curve import hash_to_g2

    buf = (ctypes.c_uint8 * 192)()
    for msg in (b"\x00" * 32, bytes(range(32))):
        assert lib().bls_hash_to_g2(msg, 32, DST, len(DST), buf) == 1
        got = bytes(buf)
        vals = tuple(
            int.from_bytes(got[i * 48 : (i + 1) * 48], "big") for i in range(4)
        )
        ref = hash_to_g2(msg, DST)
        assert vals == (ref.x.c0.n, ref.x.c1.n, ref.y.c0.n, ref.y.c1.n)


def test_valid_batches_verify():
    sets = [_valid_set(i, n) for i, n in enumerate((1, 1, 2, 3, 5))]
    assert _NATIVE.verify_signature_sets(sets) is True
    # single-set forms
    sig, pks, m = _valid_set(40)
    assert _NATIVE.verify_signature_sets([(sig, pks, m)]) is True
    assert _NATIVE.fast_aggregate_verify(pks, m, sig) is True


def test_duplicate_messages_share_hash_cache():
    m = _msg(77)
    sets = []
    for i in range(6):
        agg = bls.AggregateSignature.infinity()
        agg.add_assign(SK[i].sign(m))
        sets.append((agg, [PK[i].point], m))
    assert _NATIVE.verify_signature_sets(sets) is True


def test_invalid_cases_fail():
    good = [_valid_set(i) for i in range(3)]
    # corrupted signature bytes (still a valid x -> wrong point or off-curve)
    sig, pks, m = _valid_set(10)
    raw = bytearray(sig.serialize())
    raw[50] ^= 0x01
    bad_sig = bls.Signature.deserialize(bytes(raw))
    assert _NATIVE.verify_signature_sets(good + [(bad_sig, pks, m)]) is False
    # wrong message
    sig, pks, m = _valid_set(11)
    assert _NATIVE.verify_signature_sets(good + [(sig, pks, _msg(999))]) is False
    # wrong pubkey
    sig, pks, m = _valid_set(12)
    assert _NATIVE.verify_signature_sets(good + [(sig, [PK[7].point], m)]) is False
    # empty batch / empty pks / infinity signature
    assert _NATIVE.verify_signature_sets([]) is False
    assert _NATIVE.verify_signature_sets([(good[0][0], [], good[0][2])]) is False
    inf = bls.Signature.deserialize(bls.INFINITY_SIGNATURE)
    assert _NATIVE.verify_signature_sets([(inf, good[0][1], good[0][2])]) is False


def test_wrong_subgroup_signature_rejected():
    # An on-curve G2 point NOT in the subgroup: SSWU+iso output before
    # cofactor clearing (the cofactor is ~2^636, so a random mapped point
    # is in G2 only with negligible probability).
    from lighthouse_tpu.crypto.cpu.hash_to_curve import (
        hash_to_field_fq2,
        iso3_map,
        map_to_curve_sswu,
    )

    u0, _ = hash_to_field_fq2(b"subgroup-test", DST, 2)
    q = iso3_map(*map_to_curve_sswu(u0))
    assert not q.in_subgroup()
    raw = q.compress()
    rogue = bls.Signature.deserialize(raw)
    sig, pks, m = _valid_set(20)
    assert _NATIVE.verify_signature_sets([(rogue, pks, m)]) is False


def test_aggregate_verify_distinct_messages():
    msgs = [_msg(100 + i) for i in range(4)]
    agg = bls.AggregateSignature.infinity()
    for i, m in enumerate(msgs):
        agg.add_assign(SK[i].sign(m))
    pts = [PK[i].point for i in range(4)]
    assert _NATIVE.aggregate_verify(pts, msgs, agg) is True
    assert _NATIVE.aggregate_verify(pts, list(reversed(msgs)), agg) is False
    assert _NATIVE.aggregate_verify(pts[:3], msgs, agg) is False


def test_differential_vs_python_oracle():
    """A few randomized cases against the slow oracle backend — the
    armies of by-construction cases above cover the rest."""
    cpu = backend._REGISTRY["cpu"]()
    rng_cases = []
    for i in range(3):
        good = i != 1
        sig, pks, m = _valid_set(200 + i, n_pks=2)
        if not good:
            m = _msg(4000 + i)
        rng_cases.append(((sig, pks, m), good))
    for case, expected in rng_cases:
        assert _NATIVE.verify_signature_sets([case]) is expected
        assert cpu.verify_signature_sets([case]) is expected


def _g1_encoding(x: int, sign: bool = False) -> bytes:
    raw = bytearray(x.to_bytes(48, "big"))
    raw[0] |= 0x80 | (0x20 if sign else 0)
    return bytes(raw)


def _g1_x(on_curve: bool) -> int:
    """Smallest x whose curve equation y^2 = x^3 + 4 has (or lacks) a
    root. An on-curve x found this way is outside the r-order subgroup
    (the cofactor is ~2^126), which the test asserts."""
    from lighthouse_tpu.crypto.cpu.curve import G1Point

    for x in range(1, 200):
        try:
            G1Point.decompress(_g1_encoding(x))
            found = True
        except ValueError:
            found = False
        if found == on_curve:
            return x
    raise AssertionError("no such x below 200")


def _admission_case(name: str) -> bytes:
    if name.startswith("valid"):
        return PK[int(name[-1])].serialize()
    if name == "off_curve":
        return _g1_encoding(_g1_x(on_curve=False))
    if name == "non_subgroup":
        from lighthouse_tpu.crypto.cpu.curve import G1Point

        raw = _g1_encoding(_g1_x(on_curve=True))
        assert not G1Point.decompress(raw).in_subgroup()
        return raw
    if name == "infinity":
        return bls.INFINITY_PUBLIC_KEY
    if name == "malformed_infinity":
        return bytes([0xC0] + [0] * 46 + [1])
    if name == "x_out_of_range":
        return _g1_encoding(P)
    if name == "uncompressed_flag":
        return bytes([PK[0].serialize()[0] & 0x7F]) + PK[0].serialize()[1:]
    raise AssertionError(name)


@pytest.mark.parametrize(
    "name",
    [
        "valid0", "valid5", "off_curve", "non_subgroup", "infinity",
        "malformed_infinity", "x_out_of_range", "uncompressed_flag",
    ],
)
def test_pubkey_admission_matches_oracle(name):
    """Native KeyValidate (the admission path of PublicKey.deserialize)
    accepts and rejects exactly what the pure-Python oracle does, and
    yields the same point."""
    raw = _admission_case(name)
    try:
        want = bls.PublicKey.deserialize_oracle(raw)
    except bls.BlsError:
        want = None
    try:
        got = bls.PublicKey.deserialize(raw)
    except bls.BlsError:
        got = None
    assert (got is None) == (want is None)
    assert (want is not None) == name.startswith("valid")
    if want is not None:
        assert (got.point.x.n, got.point.y.n) == (want.point.x.n, want.point.y.n)
        assert got.serialize() == want.serialize() == raw


def test_backend_registry_selection():
    backend.set_backend("cpu-native")
    try:
        assert backend.active_name() == "cpu-native"
        sig, pks, m = _valid_set(300)
        assert (
            bls.verify_signature_sets(
                [
                    bls.SignatureSet.multiple_pubkeys(
                        sig, [bls.PublicKey(p) for p in pks], m
                    )
                ]
            )
            is True
        )
        assert backend.active().verify_signature_sets([(sig, pks, m)]) is True
    finally:
        backend.set_backend("cpu")


def test_native_build_is_keyed_and_replaces_stale(tmp_path, monkeypatch):
    """A library is named by the hash of its source, local headers,
    flags and compiler; editing an included header builds a new one and
    deletes the old, while a build in progress is left alone."""
    import sysconfig

    from lighthouse_tpu import _native

    monkeypatch.setattr(_native, "_DIR", tmp_path)
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    (tmp_path / "probe.c").write_text(
        '#include "probe.h"\nint probe(void) { return PROBE; }\n'
    )
    (tmp_path / "probe.h").write_text("#define PROBE 1\n")
    legacy = tmp_path / f"libprobe{suffix}"
    legacy.write_bytes(b"")
    first = _native._build_and_load_uncached("probe")
    assert first is not None and first.probe() == 1
    built = sorted(p.name for p in tmp_path.glob("libprobe*"))
    assert len(built) == 1 and built[0] != legacy.name
    in_progress = tmp_path / (built[0][: -len(suffix)] + ".tmp999999")
    in_progress.write_bytes(b"")

    (tmp_path / "probe.h").write_text("#define PROBE 2\n")
    second = _native._build_and_load_uncached("probe")
    assert second is not None and second.probe() == 2
    now = sorted(p.name for p in tmp_path.glob(f"libprobe*{suffix}"))
    assert len(now) == 1 and now != built
    assert in_progress.exists()
