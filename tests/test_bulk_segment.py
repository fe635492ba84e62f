"""Chain-segment verification through the scheduler's bulk class
(``backend_verify_bulk``, the call ``signature_verify_chain_segment``
makes) against the benchmark's independent reference verifier
(``perfbench/ref/bls.py``).

A segment is cut into ``bulk_flush_sets`` chunks, each its own bulk
submission, and its verdict is the AND of the chunks'. Upstream's
verdict is one batch verification of every set of the segment. The two
agree on real signatures (cpu-native backend, random scalars) for a
clean segment, a pair of signature errors that cancel in an unweighted
sum inside one chunk and split across two chunks, one bad set in the
partial last chunk, and an empty segment. The segment's span, chunk
counter and bulk-wait histogram count what was submitted.
"""

import importlib.util
import os
import random
import types

import pytest

from lighthouse_tpu.crypto import backend, bls
from lighthouse_tpu.utils import metrics, tracing
from lighthouse_tpu.verification_service import (
    VerificationScheduler,
    backend_verify_bulk,
)

CHUNK = 4
KIND = "chain_segment"
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
VALIDATORS = [1000 + 7 * i for i in range(12)]


def _reference():
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "perfbench",
        "ref", "bls.py",
    )
    spec = importlib.util.spec_from_file_location("perfbench_ref_bls", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def pubkeys():
    return [bls.SecretKey(sk).public_key() for sk in VALIDATORS]


@pytest.fixture
def sched():
    backend.set_backend("cpu-native")
    s = VerificationScheduler(bulk_flush_sets=CHUNK, bulk_linger_ms=1.0)
    s.start()
    try:
        yield s
    finally:
        s.stop()
        backend.set_backend("cpu")


def _segment(seed: int, n_sets: int):
    """``n_sets`` specs ``(signer positions, message)`` as a segment's
    blocks give them: aggregates of 1-4 signers over messages that
    repeat (consecutive blocks carry the same committees' data)."""
    rng = random.Random(seed)
    msgs = [bytes([seed % 251, i]) * 16 for i in range(max(1, n_sets // 2))]
    return [
        (sorted(rng.sample(range(len(VALIDATORS)), rng.randint(1, 4))),
         rng.choice(msgs))
        for _ in range(n_sets)
    ]


def _shift(sig: bls.Signature, t: int, d: bytes) -> bls.Signature:
    """``sig + [t] H(d)``: with t and -t on two sets the errors cancel
    in an unweighted sum."""
    agg = bls.AggregateSignature.infinity()
    agg.add_assign(sig)
    agg.add_assign(bls.SecretKey(t % R).sign(d))
    return bls.Signature.deserialize(agg.serialize())


def _sign(specs, pubkeys, tamper=None, bad=()):
    """The program's SignatureSets and the reference's (summed secret
    key, message, signature) triples. ``tamper`` is a pair of positions
    whose errors cancel; ``bad`` positions sign the wrong message."""
    sets, triples = [], []
    for i, (who, msg) in enumerate(specs):
        sk = sum(VALIDATORS[w] for w in who) % R
        sig = bls.SecretKey(sk).sign(b"\xee" * 32 if i in bad else msg)
        if tamper is not None and i in tamper:
            sig = _shift(sig, 99 if i == tamper[0] else -99, b"\x5a" * 32)
        sets.append(bls.SignatureSet.multiple_pubkeys(
            sig, [pubkeys[w] for w in who], msg, signing_indices=who))
        triples.append((sk, msg, sig.serialize()))
    return sets, triples


def _ref_verdict(ref, triples, seed: int) -> bool:
    rng = random.Random(seed)
    return ref.verify_sets(triples, [rng.getrandbits(64) | 1 for _ in triples])


def _counts():
    chunks = metrics.get("verification_scheduler_bulk_chunks_total")
    wait = metrics.get("verification_scheduler_bulk_wait_seconds")
    c = chunks.children().get((KIND,)) if chunks else None
    w = wait.children().get((KIND,)) if wait else None
    return (c.value if c else 0), (w.total if w else 0)


@pytest.mark.parametrize("case,n_sets,tamper,bad", [
    ("clean", 14, None, ()),
    ("pair_in_one_chunk", 14, (4, 6), ()),
    ("pair_across_chunks", 14, (3, 9), ()),
    ("bad_set_in_partial_last_chunk", 14, None, (13,)),
])
def test_segment_verdict_matches_reference(
    ref, pubkeys, sched, case, n_sets, tamper, bad,
):
    seed = sum(map(ord, case))
    sets, triples = _sign(_segment(seed, n_sets), pubkeys, tamper, bad)
    want = _ref_verdict(ref, triples, seed)
    assert want is (case == "clean")
    if tamper is not None:
        # the control (unweighted sum) accepts the cancelling pair: the
        # random scalars are what reject it
        assert ref.verify_sets(triples, [1] * len(triples))
    chain = types.SimpleNamespace(verification_scheduler=sched)
    before = _counts()
    tracing.clear()
    tracing.enable()
    try:
        got = backend_verify_bulk(chain, sets, KIND)
    finally:
        tracing.disable()
    assert got is want
    n_chunks = -(-n_sets // CHUNK)
    chunks, waits = _counts()
    assert chunks - before[0] == n_chunks
    assert waits - before[1] == n_chunks
    spans = [e for e in tracing.events() if e["name"] == "scheduler.bulk_segment"]
    assert len(spans) == 1
    assert spans[0]["args"] == {
        "kind": KIND, "n_sets": n_sets, "n_chunks": n_chunks, "verdict": want,
    }
    assert sched.status()["bulk"]["sets_flushed_total"] == n_sets


def test_empty_segment_is_false_and_submits_no_chunk(sched):
    chain = types.SimpleNamespace(verification_scheduler=sched)
    before = _counts()
    tracing.clear()
    tracing.enable()
    try:
        assert backend_verify_bulk(chain, [], KIND) is False
    finally:
        tracing.disable()
    assert _counts() == before
    spans = [e for e in tracing.events() if e["name"] == "scheduler.bulk_segment"]
    assert [s["args"]["n_chunks"] for s in spans] == [0]
    assert sched.status()["bulk"]["flushes_total"] == 0
