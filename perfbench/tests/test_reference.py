"""The reference verifier (``ref/bls.py``) against known answers and the
group laws, and against the C signer that makes the traffic.

Known answers: the ten interop keypairs of the consensus clients
(eth2_interop_keypairs, keygen_10_validators.yaml) and the EIP-2335
test-vector keypair pin [sk] g1 and the compressed G1 encoding. The
rest is checked structurally: points land on their curves and in G2,
the pairing is bilinear and not degenerate, and the batch equation
accepts what is signed and rejects what is not.
"""

import hashlib
import random

import pytest

from ref import bls

INTEROP = [
    (0x25295F0D1D592A90B333E26E85149708208E9F8E8BC18F6C77BD62F8AD7A6866,
     "a99a76ed7796f7be22d5b7e85deeb7c5677e88e511e0b337618f8c4eb61349b4bf2d153f649f7b53359fe8b94a38e44c"),
    (0x51D0B65185DB6989AB0B560D6DEED19C7EAD0E24B9B6372CBECB1F26BDFAD000,
     "b89bebc699769726a318c8e9971bd3171297c61aea4a6578a7a4f94b547dcba5bac16a89108b6b6a1fe3695d1a874a0b"),
    (0x315ED405FAFE339603932EEBE8DBFD650CE5DAFA561F6928664C75DB85F97857,
     "a3a32b0f8b4ddb83f1a0a853d81dd725dfe577d4f4c3db8ece52ce2b026eca84815c1a7e8e92a4de3d755733bf7e4a9b"),
    (0x25B1166A43C109CB330AF8945D364722757C65ED2BFED5444B5A2F057F82D391,
     "88c141df77cd9d8d7a71a75c826c41a9c9f03c6ee1b180f3e7852f6a280099ded351b58d66e653af8e42816a4d8f532e"),
    (0x3F5615898238C4C4F906B507EE917E9EA1BB69B93F1DBD11A34D229C3B06784B,
     "81283b7a20e1ca460ebd9bbd77005d557370cabb1f9a44f530c4c4c66230f675f8df8b4c2818851aa7d77a80ca5a4a5e"),
    (0x055794614BC85ED5436C1F5CAB586AAB6CA84835788621091F4F3B813761E7A8,
     "ab0bdda0f85f842f431beaccf1250bf1fd7ba51b4100fd64364b6401fda85bb0069b3e715b58819684e7fc0b10a72a34"),
    (0x1023C68852075965E0F7352DEE3F76A84A83E7582C181C10179936C6D6348893,
     "9977f1c8b731a8d5558146bfb86caea26434f3c5878b589bf280a42c9159e700e9df0e4086296c20b011d2e78c27d373"),
    (0x3A941600DC41E5D20E818473B817A28507C23CDFDB4B659C15461EE5C71E41F5,
     "a8d4c7c27795a725961317ef5953a7032ed6d83739db8b0e8a72353d1b8b4439427f7efa2c89caa03cc9f28f8cbab8ac"),
    (0x066E3BDC0415530E5C7FED6382D5C822C192B620203CF669903E1810A8C67D06,
     "a6d310dbbfab9a22450f59993f87a4ce5db6223f3b5f1f30d2c4ec718922d400e0b3c7741de8e59960f72411a0ee10a7"),
    (0x2B3B88A041168A1C4CD04BDD8DE7964FD35238F95442DC678514F9DADB81EC34,
     "9893413c00283a3f9ed9fd9845dda1cea38228d22567f9541dccc357e54a2d6a6e204103c92564cbc05f4905ac7c493a"),
]
EIP2335 = (0x000000000019D6689C085AE165831E934FF763AE46A2A6C172B3F1B60A8CE26F,
           "9612d7a727c9d0a22e185a1c768478dfe919cada9266988cb32359c11f2b7b27f4ae4040902382ae2910c15e2b420d07")


@pytest.mark.parametrize("sk,pk", INTEROP + [EIP2335])
def test_public_keys_match_known_answers(sk, pk):
    p = bls.g1_mul_base(sk)
    assert bls.g1_compress(p).hex() == pk
    assert bls.same_point(bls.g1_decompress(bytes.fromhex(pk)), p)
    assert bls.same_point(bls.g_mul(bls.G1, sk), p)


def test_interop_secret_key_derivation():
    # sk_i = int_LE(sha256(i as 32 bytes LE)) mod r
    h = hashlib.sha256((0).to_bytes(32, "little")).digest()
    assert int.from_bytes(h, "little") % bls.R == INTEROP[0][0]


def test_g1_generator_has_order_r():
    assert bls.on_curve(bls.G1, bls.B1)
    assert bls.is_inf(bls.g_mul(bls.G1, bls.R))


def test_hash_to_g2_lands_in_g2():
    rng = random.Random(1)
    for _ in range(3):
        u = (rng.randrange(bls.P), rng.randrange(bls.P))
        x, y = bls.map_to_curve_sswu(u)
        assert bls.f2_sqr(y) == bls._iso_curve(x)
        q = bls.iso_map(x, y)
        assert bls.on_curve(q, bls.B2)
        # E' points outside G2 fail both membership tests
        assert not bls.in_g2(q) and not bls.is_inf(bls.g_mul(q, bls.R))
    h = bls.hash_to_g2(b"abc")
    assert bls.on_curve(h, bls.B2)
    assert bls.in_g2(h) and bls.is_inf(bls.g_mul(h, bls.R))


def test_pairing_is_bilinear_and_not_degenerate():
    q = bls.hash_to_g2(b"bilinear")
    a = 0xDEADBEEF12345
    assert bls.pairing_product_is_one(
        [(bls.g_mul(bls.G1, a), q), (bls.g_neg(bls.G1), bls.g_mul(q, a))])
    assert not bls.pairing_product_is_one([(bls.G1, q)])
    f = bls.miller_loop([((bls.G1_X, bls.G1_Y), bls.to_affine(q))])
    assert bls.f12_mul(f, bls.f12_inv(f)) == bls.F12_ONE


def test_batch_accepts_signed_and_rejects_tampered():
    sks = [11, 22, 33]
    msgs = [b"m0", b"m1", b"m2"]
    sigs = [bls.sign(sk, m) for sk, m in zip(sks, msgs)]
    assert bls.g2_compress(bls.g2_decompress(sigs[0])) == sigs[0]
    sets = list(zip(sks, msgs, sigs))
    assert bls.verify_sets(sets, [3, 5, 7])
    assert not bls.verify_sets([(sks[0], b"other", sigs[0])], [3])
    # +D on one set, -D on another: each invalid, the unweighted sum not
    d = bls.hash_to_g2(b"d")
    bad = list(sets)
    for k, t in ((0, 1), (1, -1)):
        sk, m, sig = bad[k]
        q = bls.g_add(bls.g2_decompress(sig), d if t > 0 else bls.g_neg(d))
        bad[k] = (sk, m, bls.g2_compress(q))
    assert not bls.verify_sets(bad, [3, 5, 7])
    assert bls.verify_sets(bad, [1, 1, 1])      # the control's blind spot
    # public keys as points, summed, give the same verdicts
    pts = [([bls.g1_mul_base(sk - 1), bls.g1_mul_base(1)], m, s) for sk, m, s in sets]
    assert bls.verify_sets(pts, [3, 5, 7])


def test_c_signer_agrees_with_reference():
    """Keys and signatures from the C signer verify under the reference:
    two implementations that share no code agree on [sk] g1,
    hash-to-curve and the encodings."""
    from harness import registry, signer

    raw = signer.keygen(registry.SK0 + 7, 1)
    (x, _), (y, _) = bls.to_affine(bls.g1_mul_base(registry.SK0 + 7))
    assert raw == x.to_bytes(48, "big") + y.to_bytes(48, "big")
    sk = registry.summed_secret_key([3, 9, 27])
    msg = hashlib.sha256(b"c-signer").digest()
    sig = signer.sign_batch(msg, [sk])[0]
    assert bls.verify_sets([(sk, msg, sig)], [12345])
    shifted = signer.g2_shift(sig, b"d", 1)
    assert not bls.verify_sets([(sk, msg, shifted)], [12345])
