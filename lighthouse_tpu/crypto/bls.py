"""Public BLS API: the generic-backend seam.

Mirrors the reference's ``crypto/bls`` generic layer
(``/root/reference/crypto/bls/src/lib.rs:99-140``): wrapper types carry both
serialized bytes and the decompressed point; *all* serious cryptography is
deferred to a runtime-selectable backend (``fake`` / ``cpu`` / ``tpu``),
where the reference selects ``blst``/``milagro``/``fake_crypto`` at compile
time. Deserialization rules follow the reference:

* public keys: 48 bytes, must decompress onto the curve, subgroup-checked,
  infinity rejected (``generic_public_key.rs``);
* signatures: 96 bytes, the all-zero encoding is the valid "empty"
  (infinity) signature (``generic_signature.rs``, ``INFINITY_SIGNATURE``).
"""

from __future__ import annotations

from typing import Optional, Sequence

from . import backend as _backend
from .cpu import bls as _cpu
from .cpu.curve import G1Point, G2Point
from .params import P as P_MOD, DST, PUBLIC_KEY_BYTES, R, SECRET_KEY_BYTES, SIGNATURE_BYTES

INFINITY_SIGNATURE = bytes([0xC0] + [0] * 95)
INFINITY_PUBLIC_KEY = bytes([0xC0] + [0] * 47)


class BlsError(ValueError):
    pass


class PublicKey:
    """A decompressed, subgroup-checked G1 public key."""

    __slots__ = ("point", "_bytes")

    def __init__(self, point: G1Point, raw: Optional[bytes] = None):
        self.point = point
        self._bytes = raw

    @classmethod
    def deserialize(cls, data: bytes) -> "PublicKey":
        if len(data) != PUBLIC_KEY_BYTES:
            raise BlsError(f"invalid pubkey length {len(data)}")
        data = bytes(data)
        # Fast path through the C library (same KeyValidate rules); the
        # pure-Python path below stays as the oracle where it cannot load.
        from .native import NativeUnavailable, native_pubkey_xy

        try:
            xy = native_pubkey_xy(data)
        except NativeUnavailable:
            return cls.deserialize_oracle(data)
        if xy is None:
            raise BlsError("invalid public key encoding")
        from .cpu.fields import Fq

        return cls(G1Point(Fq(xy[0]), Fq(xy[1])), data)

    @classmethod
    def deserialize_oracle(cls, data: bytes) -> "PublicKey":
        """Pure-Python KeyValidate: the reference the native path is
        differential-tested against."""
        if len(data) != PUBLIC_KEY_BYTES:
            raise BlsError(f"invalid pubkey length {len(data)}")
        try:
            point = G1Point.decompress(data)
        except ValueError as e:
            raise BlsError(str(e)) from e
        if point.is_infinity():
            raise BlsError("infinity public key is invalid")
        if not point.in_subgroup():
            raise BlsError("public key not in subgroup")
        return cls(point, bytes(data))

    def serialize(self) -> bytes:
        if self._bytes is None:
            self._bytes = self.point.compress()
        return self._bytes

    def __eq__(self, o):
        return isinstance(o, PublicKey) and self.serialize() == o.serialize()

    def __hash__(self):
        return hash(self.serialize())

    def __repr__(self):
        return f"PublicKey(0x{self.serialize().hex()})"


def parse_compressed_g2_x(data: bytes) -> tuple[int, int, bool]:
    """Structural parse of a compressed G2 encoding -> (x0, x1,
    sign_larger). Validates length, compression flag, range; the on-curve
    check (sqrt) is the caller's business (host decompress or device)."""
    if len(data) != SIGNATURE_BYTES:
        raise BlsError(f"invalid signature length {len(data)}")
    data = bytes(data)
    flags = data[0] >> 5
    if not flags & 0x4:
        raise BlsError("uncompressed G2 encoding not supported")
    if flags & 0x2:
        raise BlsError("infinity encoding has no x")
    x1 = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if x0 >= P_MOD or x1 >= P_MOD:
        raise BlsError("x out of range")
    return x0, x1, bool(flags & 0x1)


class Signature:
    """A G2 signature; ``point`` is None for the "empty" (infinity)
    encoding.

    Decompression is LAZY: ``deserialize`` performs only the cheap
    structural checks (length, flags, x-range, infinity well-formedness)
    and defers the square root until ``point`` is touched — the TPU
    backend never touches it (G2 decompression runs ON DEVICE,
    ``crypto/device/bls.py``), which removes ~10 ms of host big-int math
    per gossip signature. A non-curve x (sqrt fails) therefore surfaces
    at USE time as BlsError; batch verifiers contain it as a normal
    invalid-signature outcome."""

    __slots__ = ("_point", "_bytes", "_decompressed")

    def __init__(self, point: Optional[G2Point] = None, raw: Optional[bytes] = None):
        self._point = point
        self._bytes = raw
        self._decompressed = point is not None or raw is None

    @property
    def point(self) -> Optional[G2Point]:
        if not self._decompressed:
            if bytes(self._bytes) == INFINITY_SIGNATURE:
                self._point = None
            else:
                try:
                    self._point = G2Point.decompress(self._bytes)
                except ValueError as e:
                    raise BlsError(str(e)) from e
            self._decompressed = True
        return self._point

    @classmethod
    def deserialize(cls, data: bytes) -> "Signature":
        if len(data) != SIGNATURE_BYTES:
            raise BlsError(f"invalid signature length {len(data)}")
        data = bytes(data)
        if (data[0] >> 5) & 0x2:  # infinity must be the canonical encoding
            if data != INFINITY_SIGNATURE:
                raise BlsError("malformed infinity encoding")
            return cls(None, INFINITY_SIGNATURE)
        parse_compressed_g2_x(data)  # structural validation
        return cls(None, data)  # sqrt (on-curve check) deferred

    @classmethod
    def infinity(cls) -> "Signature":
        return cls(None, INFINITY_SIGNATURE)

    def serialize(self) -> bytes:
        if self._bytes is None:
            self._bytes = (
                INFINITY_SIGNATURE if self.point is None else self.point.compress()
            )
        return self._bytes

    def point_or_infinity(self) -> G2Point:
        return G2Point.infinity() if self.point is None else self.point

    def is_infinity(self) -> bool:
        if not self._decompressed and self._bytes is not None:
            return bytes(self._bytes) == INFINITY_SIGNATURE
        return self._point is None or self._point.is_infinity()

    def verify(self, pk: PublicKey, message: bytes) -> bool:
        return _backend.active().verify(pk.point, message, self.point_or_infinity())

    def __eq__(self, o):
        return isinstance(o, Signature) and self.serialize() == o.serialize()

    def __hash__(self):
        return hash(self.serialize())

    def __repr__(self):
        return f"Signature(0x{self.serialize().hex()})"


class AggregateSignature(Signature):
    """A signature accumulating others by point addition (reference:
    generic_aggregate_signature.rs add_assign / add_assign_aggregate)."""

    @classmethod
    def infinity(cls) -> "AggregateSignature":
        return cls(None, INFINITY_SIGNATURE)

    def add_assign(self, other: Signature) -> None:
        if other.point is None:
            return
        if self.point is None:
            self._point = other.point
        else:
            self._point = self.point + other.point
        self._decompressed = True
        self._bytes = None

    def fast_aggregate_verify(self, message: bytes, pks: Sequence[PublicKey]) -> bool:
        if not pks:
            return False
        return _backend.active().fast_aggregate_verify(
            [pk.point for pk in pks], message, self.point_or_infinity()
        )

    def aggregate_verify(
        self, messages: Sequence[bytes], pks: Sequence[PublicKey]
    ) -> bool:
        if not pks or len(pks) != len(messages):
            return False
        return _backend.active().aggregate_verify(
            [pk.point for pk in pks], list(messages), self.point_or_infinity()
        )


class SecretKey:
    __slots__ = ("k",)

    def __init__(self, k: int):
        if not 0 < k < R:
            raise BlsError("secret key out of range")
        self.k = k

    @classmethod
    def deserialize(cls, data: bytes) -> "SecretKey":
        if len(data) != SECRET_KEY_BYTES:
            raise BlsError("invalid secret key length")
        return cls(int.from_bytes(data, "big"))

    def serialize(self) -> bytes:
        return self.k.to_bytes(SECRET_KEY_BYTES, "big")

    def public_key(self) -> PublicKey:
        # Fast path through the C library when a toolchain exists (a
        # pure-Python G1 scalar mul is ~100 ms — it made large interop
        # genesis states take minutes); oracle fallback otherwise.
        try:
            from .cpu.fields import Fq
            from .native import native_sk_to_pk_xy

            x, y = native_sk_to_pk_xy(self.k)
            return PublicKey(G1Point(Fq(x), Fq(y)))
        except Exception:
            return PublicKey(_cpu.sk_to_pk(self.k))

    def sign(self, message: bytes) -> Signature:
        # Same native fast path as public_key(): ~2 ms vs ~200 ms for the
        # oracle's pure-Python hash-to-curve + G2 scalar mul.
        try:
            from .native import native_sign

            return Signature.deserialize(native_sign(self.k, bytes(message)))
        except Exception:
            return Signature(_cpu.sign(self.k, message))


class SignatureSet:
    """A signature over one message by one or more public keys — the unit
    of batch verification (reference: generic_signature_set.rs:61-107).

    ``signing_indices`` optionally carries the validator indices the
    keys were resolved at (state_transition/signature_sets.py threads
    them): the device key table's flush-planner classification uses
    them as a fast static/dynamic pre-filter
    (crypto/device/key_table.py). They are advisory — the backend's
    index resolution is identity-pinned to the host pubkey cache's own
    point objects, so a stale or foreign index can cost a raw-plane
    fallback but never a wrong-key verification."""

    __slots__ = ("signature", "signing_keys", "message", "signing_indices")

    def __init__(
        self,
        signature: Signature,
        signing_keys: Sequence[PublicKey],
        message: bytes,
        signing_indices: "Optional[Sequence[int]]" = None,
    ):
        if len(message) != 32:
            raise BlsError("message must be a 32-byte signing root")
        self.signature = signature
        self.signing_keys = list(signing_keys)
        self.message = bytes(message)
        if signing_indices is not None:
            signing_indices = [int(i) for i in signing_indices]
            if len(signing_indices) != len(self.signing_keys):
                raise BlsError(
                    "signing_indices must match signing_keys one-to-one"
                )
        self.signing_indices = signing_indices

    @classmethod
    def single_pubkey(
        cls, signature: Signature, signing_key: PublicKey, message: bytes,
        signing_index: "Optional[int]" = None,
    ) -> "SignatureSet":
        return cls(
            signature, [signing_key], message,
            None if signing_index is None else [signing_index],
        )

    @classmethod
    def multiple_pubkeys(
        cls, signature: Signature, signing_keys: Sequence[PublicKey],
        message: bytes, signing_indices: "Optional[Sequence[int]]" = None,
    ) -> "SignatureSet":
        return cls(signature, signing_keys, message, signing_indices)

    def verify(self) -> bool:
        """Verify just this set (fast_aggregate_verify)."""
        return AggregateSignature(
            self.signature.point, self.signature.serialize()
        ).fast_aggregate_verify(self.message, self.signing_keys)


def verify_signature_sets(sets: Sequence[SignatureSet]) -> bool:
    """Batch-verify; `True` iff every set verifies (modulo the standard
    2^-64 random-linear-combination soundness).

    Backends receive the SIGNATURE OBJECTS (not decompressed points): the
    tpu backend ships raw compressed bytes to the device and decompresses
    there; the cpu backend materializes points lazily. A signature whose
    x is not on the curve (lazy decompress fails) is an ordinary invalid
    outcome, never an exception."""
    sets = list(sets)
    if not sets:
        return False
    prepared = []
    for s in sets:
        # An "empty" (infinity-encoded) signature fails the whole batch
        # before reaching any backend (blst.rs:77-83).
        if s.signature.is_infinity():
            return False
        prepared.append(
            (s.signature, [pk.point for pk in s.signing_keys], s.message)
        )
    try:
        return _backend.active().verify_signature_sets(prepared)
    except BlsError:
        return False


__all__ = [
    "AggregateSignature",
    "BlsError",
    "DST",
    "INFINITY_SIGNATURE",
    "PublicKey",
    "SecretKey",
    "Signature",
    "SignatureSet",
    "verify_signature_sets",
]
