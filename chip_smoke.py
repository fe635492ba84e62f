"""Bring-up smoke: the node's BLS verification path on a TPU chip.

Drives the path ``python -m lighthouse_tpu bn --bls-backend tpu`` runs,
once, in this one process (the process that holds the chip starts no
child that touches JAX): ClientBuilder -> HTTP API -> chain verifiers ->
VerificationScheduler -> flush planner -> compile service -> device key
table -> staged device program.

The node is a mainnet-preset, Altair-at-genesis chain of 65,536 interop
validators (16 full 128-key committees per slot, a 512-key sync
committee), genesis time now, weights-free: every key is the interop key
of its index. It serves, from several client threads at once:

1. ``POST /eth/v1/validator/aggregate_and_proofs``: one aggregate per
   committee of one slot (3 signature sets each);
2. ``POST /eth/v1/beacon/pool/attestations``: 64 unaggregated
   attestations from distinct validators, plus one with a tampered
   signature that must be rejected while its neighbours are accepted;
3. ``POST /eth/v1/validator/contribution_and_proofs``: one sync
   contribution per subcommittee, the 512-key sync committee in all;
4. ``POST /eth/v1/beacon/blocks``: the block the node itself produced
   for the next slot (``GET /eth/v2/validator/blocks``), holding those
   aggregates and the sync aggregate — the whole-block path.

The scheduler flushes when the burst's last set arrives, so the burst
is one fused flush, and the compile service is pinned to the exact
buckets of that flush and of the block: no flush needs a rung it lacks.
Every scheduler submission's verdict is checked against the
``cpu-native`` backend on the same sets, and the node's own counters
must show that the device did the work: no ``fallback``/``shed``
resolutions, no cold routes during traffic, ``bls_stage_verify`` rows
for every kind computed on the chip from the key table.

``--chips 4`` runs only the served dp mesh across four chips: one
fused flush of 64 unaggregated sets split over four shards, each shard
verifying on its own device from its own key-table replica. The compile service warms the rung on the four chips one after
another.

    python chip_smoke.py                 # one TPU chip
    python chip_smoke.py --chips 4       # the dp mesh on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --validators 4096   # CPU rehearsal

Without a TPU it exits non-zero and prints no result. The one exception
is the CPU rehearsal, asked for explicitly: ``JAX_PLATFORMS=cpu`` and a
``--validators`` count; its last line names ``cpu``. The last line of
the output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

VALIDATORS = 65_536
# one fused flush of 64 unaggregated sets over four shards (dp_rung)
DP_SETS = 64
DP_SHARDS = 4
UNAGGREGATED = 64
SYNC_SUBNETS = 4
SYNC_SUBCOMMITTEE = 128
COMPILE_TIMEOUT_S = 900
# attempts at signing a slot's traffic before the slot starts
SIGN_ATTEMPTS = 3
# the burst's flush fires when its last set arrives, never on the clock
BURST_DEADLINE_MS = 60_000.0


def slot_committees(n_validators: int):
    """(committees per slot, largest committee size) under the mainnet
    preset."""
    committees = max(1, min(64, n_validators // 32 // 128))
    return committees, -(-n_validators // (32 * committees))


def gossip_burst(n_validators: int):
    """(sets, exact bucket) of the one flush the gossip burst makes:
    3 sets per aggregate (one per committee), the unaggregated
    attestations and the tampered one, 3 sets per sync contribution (one
    per subcommittee). Its distinct messages are the aggregators' slot,
    each aggregate-and-proof, each committee's attestation data (shared
    with the unaggregated ones), each subcommittee's selection data and
    contribution-and-proof, and the sync committee's block root."""
    from lighthouse_tpu.verification_service import round_up_bucket

    committees, size = slot_committees(n_validators)
    sets = 3 * committees + UNAGGREGATED + 1 + 3 * SYNC_SUBNETS
    messages = 1 + 2 * committees + 2 * SYNC_SUBNETS + 1
    rung = (
        round_up_bucket(sets),
        round_up_bucket(max(size, SYNC_SUBCOMMITTEE)),
        round_up_bucket(messages),
    )
    return sets, rung


def dp_rung(n_validators: int):
    """The exact bucket of each shard's sub-batch in the dp phase. The
    planner deals one-set submissions to the shards in turn, so shard s
    holds picks s, s+4, ...: 16 sets, a ladder size, so one sub-batch
    (a shard's share off the ladder would be split into bins of ladder
    sizes). The picks come four from each committee in turn, so a
    shard's one-key sets carry one message per committee of the first
    16."""
    from lighthouse_tpu.verification_service import round_up_bucket

    committees, _ = slot_committees(n_validators)
    per_shard = DP_SETS // DP_SHARDS
    return (
        round_up_bucket(per_shard), 1,
        round_up_bucket(min(per_shard, committees)),
    )


def block_rung(n_validators: int):
    """The exact bucket of the block this traffic produces: proposal,
    randao, one aggregate per committee of the slot and the 512-key sync
    aggregate, each over its own message."""
    from lighthouse_tpu.verification_service import round_up_bucket

    committees, _ = slot_committees(n_validators)
    sets = round_up_bucket(3 + committees)
    return (sets, 512, sets)


def log(msg: str, **fields) -> None:
    line = msg + (" " + json.dumps(fields, sort_keys=True) if fields else "")
    print(line, flush=True)


def require_device(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    cpu_rehearsal = (
        rehearsal and platform == "cpu"
        and os.environ.get("JAX_PLATFORMS") == "cpu"
    )
    if platform != "tpu" and not cpu_rehearsal:
        sys.exit(f"no TPU: JAX found {platform!r} (the CPU rehearsal "
                 f"needs JAX_PLATFORMS=cpu and --validators)")
    if len(devices) < chips:
        sys.exit(f"--chips {chips} needs {chips} devices, JAX found "
                 f"{len(devices)}")
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Api:
    """A thin JSON client for the node's HTTP API."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                raw = r.read()
                return r.status, (json.loads(raw) if raw else None)
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode(errors="replace")[:300]


class SubmissionLog:
    """Records every set list the scheduler is asked to verify, with the
    verdict the node returned, so each can be checked against cpu-native
    on the same sets afterwards."""

    def __init__(self, sched):
        self.rows = []
        self._lock = threading.Lock()
        submit, verify_now = sched.submit, sched.verify_now

        def logged_submit(sets, kind, qos="deadline"):
            sets = list(sets)
            fut = submit(sets, kind, qos=qos)
            with self._lock:
                self.rows.append((kind, sets, fut.result))
            return fut

        def logged_verify_now(sets, kind="block"):
            sets = list(sets)
            out = verify_now(sets, kind)
            with self._lock:
                self.rows.append((kind, sets, lambda: out))
            return out

        sched.submit = logged_submit
        sched.verify_now = logged_verify_now

    def check_against_cpu_native(self) -> dict:
        from lighthouse_tpu.crypto.native import NativeBackend

        native = NativeBackend()
        per_kind: dict = {}
        with self._lock:
            rows = list(self.rows)
        for kind, sets, result in rows:
            got = bool(result())
            want = bool(native.verify_signature_sets(
                [(s.signature, [pk.point for pk in s.signing_keys], s.message)
                 for s in sets]
            ))
            if got != want:
                raise AssertionError(
                    f"{kind}: node verdict {got} != cpu-native {want} "
                    f"on {len(sets)} sets"
                )
            rec = per_kind.setdefault(kind, {"true": 0, "false": 0})
            rec["true" if got else "false"] += 1
        return per_kind


def counter_total(name: str, **labels) -> float:
    from lighthouse_tpu.utils import metrics

    fam = metrics.get(name)
    if fam is None:
        return 0.0
    if not hasattr(fam, "children"):
        return fam.value
    total = 0.0
    names = fam.labelnames
    for key, child in fam.children().items():
        row = dict(zip(names, key))
        if all(row.get(k) == v for k, v in labels.items()):
            total += child.value
    return total


def latency_samples_by_path() -> dict:
    from lighthouse_tpu.utils import metrics

    fam = metrics.get("verification_scheduler_verdict_latency_seconds")
    out: dict = {}
    if fam is None:
        return out
    names = fam.labelnames
    for key, child in fam.children().items():
        path = dict(zip(names, key))["path"]
        out[path] = out.get(path, 0) + child.snapshot()[0]
    return out


# ---------------------------------------------------------------------------
# Node
# ---------------------------------------------------------------------------

def build_node(n_validators: int, rungs, dp_devices: int, burst_sets: int):
    """The node, with the compile service pinned to ``rungs`` and the
    scheduler flushing when ``burst_sets`` sets are pending, so that a
    burst fuses into one flush whose exact bucket is known."""
    import dataclasses

    from lighthouse_tpu.client import ClientBuilder, ClientConfig
    from lighthouse_tpu.types.chain_spec import ChainSpec

    cfg = ClientConfig(
        preset_base="mainnet",
        http_host="127.0.0.1",
        http_port=free_port(),
        bls_backend="tpu",
        compile_rungs=tuple(rungs),
        dp_devices=dp_devices,
        scheduler_deadline_ms=BURST_DEADLINE_MS,
        scheduler_max_batch_sets=burst_sets,
    )
    spec = dataclasses.replace(ChainSpec(), altair_fork_epoch=0)
    builder = ClientBuilder(cfg, spec)
    t0 = time.perf_counter()
    builder.with_interop_genesis(
        n_validators, genesis_time=int(time.time()), fork_name="altair"
    )
    t_genesis = time.perf_counter() - t0
    t1 = time.perf_counter()
    client = builder.build().start()
    log("node built", validators=n_validators,
        genesis_s=round(t_genesis, 1),
        build_s=round(time.perf_counter() - t1, 1),
        http_port=client.api.port)
    return client


def wait_compiled(client, rungs, devices) -> None:
    """Wait for every rung's compile_ready on every device (the compile
    service walks them one by one); print each stage's compile seconds."""
    from lighthouse_tpu.crypto.device import fp
    from lighthouse_tpu.utils import flight_recorder

    svc = client.chain.compile_service
    want = {(tuple(r), d) for r in rungs for d in devices}
    deadline = time.monotonic() + COMPILE_TIMEOUT_S * len(devices)
    while True:
        warm = {
            (tuple(r), d) for r, d in want
            if svc.registry.is_warm(r, fp.get_impl(), device=d)
        }
        if warm == want:
            break
        failed = flight_recorder.events(kinds=("compile_failed",))
        if failed:
            raise RuntimeError(f"compile failed: {failed[-1]['fields']}")
        if time.monotonic() > deadline:
            raise TimeoutError(f"rungs still cold: {sorted(want - warm)}")
        time.sleep(1.0)
    for e in flight_recorder.events(kinds=("compile_ready",)):
        f = e["fields"]
        log("compiled", rung=[f["b"], f["k"], f["m"]], device=f["device"],
            seconds=f["seconds"], stage1_s=f["stage1_s"],
            stage2_s=f["stage2_s"], stage3_s=f["stage3_s"])
    gather_errors = counter_total(
        "compile_service_compiles_total", stage="gather", outcome="error"
    )
    if gather_errors:
        raise RuntimeError("the key-table gather program failed to compile")


def key_table_check(client, n_validators: int, replicas) -> dict:
    st = client.chain.device_key_table.status()
    log("key table", validators_resident=st["validators_resident"],
        validator_capacity=st["validator_capacity"],
        device_bytes=st["device_bytes"], replicas=st["replicas"],
        sets=st["sets"])
    if st["validators_resident"] != n_validators:
        raise AssertionError(f"key table holds {st['validators_resident']} rows")
    if st["replicas"] != list(replicas):
        raise AssertionError(f"key-table replicas {st['replicas']}")
    return st


def stop_node(client) -> None:
    client.stop()
    alive = sorted(
        t.name for t in threading.enumerate()
        if t is not threading.current_thread()
    )
    log("node stopped", threads_alive=alive)


# ---------------------------------------------------------------------------
# Traffic
# ---------------------------------------------------------------------------

class Duties:
    """Everything a validator client would sign for slot ``slot``, signed
    with the native signer (an aggregate with the summed secret key: the
    same group element as the sum of its members' signatures)."""

    def __init__(self, client, api: Api, slot: int):
        from lighthouse_tpu.ssz.json import from_json

        self.chain = chain = client.chain
        self.api = api
        self.slot = slot
        self.t = chain.types
        self.preset = chain.preset
        self.state = chain.head_state
        self.epoch = slot // self.preset.SLOTS_PER_EPOCH
        self.data = []
        self.committees = []
        index = 0
        while True:
            code, doc = api.call(
                "GET", f"/eth/v1/validator/attestation_data?slot={slot}"
                f"&committee_index={index}",
            )
            if code != 200:
                raise RuntimeError(f"attestation_data: {code} {doc}")
            data = from_json(self.t.AttestationData, doc["data"])
            cache = chain.shuffling_cache.get(
                chain, self.epoch, bytes(data.target.root)
            )
            self.data.append(data)
            self.committees.append([int(v) for v in cache.committee(slot, index)])
            index += 1
            if index >= cache.committees_per_slot:
                break

    @staticmethod
    def sk(index: int):
        from lighthouse_tpu.state_transition.genesis import interop_secret_key

        return interop_secret_key(index)

    def summed_sk(self, indices):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.crypto.params import R

        return bls.SecretKey(sum(self.sk(i).k for i in indices) % R)

    def root(self, tpe, obj, domain_type: int) -> bytes:
        from lighthouse_tpu.types import compute_signing_root, get_domain

        domain = get_domain(self.chain.spec, self.state, domain_type, self.epoch)
        return compute_signing_root(tpe, obj, domain)

    def aggregates(self) -> list:
        from lighthouse_tpu import ssz
        from lighthouse_tpu.beacon_chain.attestation_verification import (
            _is_aggregator,
        )
        from lighthouse_tpu.ssz.json import to_json
        from lighthouse_tpu.types import (
            DOMAIN_AGGREGATE_AND_PROOF,
            DOMAIN_BEACON_ATTESTER,
            DOMAIN_SELECTION_PROOF,
        )

        t = self.t
        sel_root = self.root(ssz.Uint64, self.slot, DOMAIN_SELECTION_PROOF)
        out = []
        for data, committee in zip(self.data, self.committees):
            for v in committee:
                proof = self.sk(v).sign(sel_root).serialize()
                if _is_aggregator(len(committee), proof):
                    break
            else:
                raise RuntimeError("no aggregator in committee")
            att_root = self.root(t.AttestationData, data, DOMAIN_BEACON_ATTESTER)
            aggregate = t.Attestation(
                aggregation_bits=[True] * len(committee),
                data=data,
                signature=self.summed_sk(committee).sign(att_root).serialize(),
            )
            msg = t.AggregateAndProof(
                aggregator_index=v, aggregate=aggregate, selection_proof=proof
            )
            sig = self.sk(v).sign(
                self.root(t.AggregateAndProof, msg, DOMAIN_AGGREGATE_AND_PROOF)
            )
            signed = t.SignedAggregateAndProof(
                message=msg, signature=sig.serialize()
            )
            out.append([to_json(t.SignedAggregateAndProof, signed)])
        return out

    def unaggregated(self, n: int, run: int = 1):
        """``n`` single-validator attestations, ``run`` members from each
        of the slot's committees in turn, and one more whose signature
        is tampered (signed over other data)."""
        from lighthouse_tpu.ssz.json import to_json
        from lighthouse_tpu.types import DOMAIN_BEACON_ATTESTER

        t = self.t
        picks = []
        pos = 0
        while len(picks) < n + 1:
            for ci, committee in enumerate(self.committees):
                for p in range(pos, min(pos + run, len(committee))):
                    if len(picks) < n + 1:
                        picks.append((ci, p))
            pos += run
        out = []
        for j, (ci, pos) in enumerate(picks):
            committee, data = self.committees[ci], self.data[ci]
            signed_data = data
            if j == n:  # the tampered one
                signed_data = t.AttestationData(
                    slot=data.slot, index=data.index,
                    beacon_block_root=bytes(32),
                    source=data.source, target=data.target,
                )
            bits = [False] * len(committee)
            bits[pos] = True
            sig = self.sk(committee[pos]).sign(
                self.root(t.AttestationData, signed_data, DOMAIN_BEACON_ATTESTER)
            )
            att = t.Attestation(
                aggregation_bits=bits, data=data, signature=sig.serialize()
            )
            out.append([to_json(t.Attestation, att)])
        return out[:n], out[n]

    def contributions(self) -> list:
        from lighthouse_tpu.beacon_chain.sync_committee_verification import (
            is_sync_committee_aggregator,
            sync_committee_pubkeys,
        )
        from lighthouse_tpu.ssz.json import to_json
        from lighthouse_tpu.types import (
            DOMAIN_CONTRIBUTION_AND_PROOF,
            DOMAIN_SYNC_COMMITTEE,
            DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF,
        )

        t, P, chain = self.t, self.preset, self.chain
        committee = sync_committee_pubkeys(chain, self.slot)
        members = [chain.pubkey_cache.get_index(bytes(pk)) for pk in committee]
        head = chain.head_block_root
        msg_root = self.root(None, head, DOMAIN_SYNC_COMMITTEE)
        sub = P.sync_subcommittee_size
        out = []
        for subc in range(P.SYNC_COMMITTEE_SUBNET_COUNT):
            seats = members[subc * sub:(subc + 1) * sub]
            sel_data = t.SyncAggregatorSelectionData(
                slot=self.slot, subcommittee_index=subc
            )
            sel_root = self.root(
                t.SyncAggregatorSelectionData, sel_data,
                DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF,
            )
            for v in seats:
                proof = self.sk(v).sign(sel_root).serialize()
                if is_sync_committee_aggregator(P, proof):
                    break
            else:
                raise RuntimeError("no sync aggregator in subcommittee")
            contribution = t.SyncCommitteeContribution(
                slot=self.slot, beacon_block_root=head,
                subcommittee_index=subc,
                aggregation_bits=[True] * sub,
                # one signature per seat: a validator holding two seats
                # signs twice, exactly as the aggregate over positions
                signature=self.summed_sk(seats).sign(msg_root).serialize(),
            )
            msg = t.ContributionAndProof(
                aggregator_index=v, contribution=contribution,
                selection_proof=proof,
            )
            sig = self.sk(v).sign(
                self.root(t.ContributionAndProof, msg,
                          DOMAIN_CONTRIBUTION_AND_PROOF)
            )
            signed = t.SignedContributionAndProof(
                message=msg, signature=sig.serialize()
            )
            out.append([to_json(t.SignedContributionAndProof, signed)])
        return out


def wait_for_slot(client, slot: int) -> None:
    while client.slot_clock.now() < slot:
        time.sleep(0.05)


def signed_ahead(client, api: Api):
    """The gossip traffic of a slot, signed before that slot starts.

    A sync contribution is accepted only within a slot of its own slot
    (``sync_committee_verification._slot_window_ok``), and the first
    duties of a new epoch make the node advance a full-registry state
    across the boundary, which can take longer than a slot. So the
    duties of slot S are fetched and signed while the clock is still
    before S, and the burst goes out as S starts. A signing that ran
    into S is done again for a later slot, with the epoch's caches warm
    by then. Returns ``(slot, aggregates, unaggregated, tampered,
    contributions)``."""
    clock = client.slot_clock
    lead = 2
    for attempt in range(1, SIGN_ATTEMPTS + 1):
        slot = clock.now() + lead
        t0 = time.perf_counter()
        duties = Duties(client, api, slot)
        aggregates = duties.aggregates()
        unaggregated, tampered = duties.unaggregated(UNAGGREGATED)
        contributions = duties.contributions()
        seconds = time.perf_counter() - t0
        log("traffic signed", slot=slot, attempt=attempt,
            committees=len(duties.committees),
            committee_size=len(duties.committees[0]),
            aggregates=len(aggregates), unaggregated=len(unaggregated),
            contributions=len(contributions), seconds=round(seconds, 2))
        if clock.now() < slot:
            return slot, aggregates, unaggregated, tampered, contributions
        lead = 2 + int(seconds // clock.seconds_per_slot)
    raise TimeoutError(f"signing outlasted its lead {SIGN_ATTEMPTS} times")


def post_block(client, api: Api, slot: int):
    """Ask the node for its block at ``slot`` (as a validator client
    does), sign it with the proposer's key and publish it."""
    from lighthouse_tpu import ssz
    from lighthouse_tpu.ssz.json import from_json, to_json
    from lighthouse_tpu.types import (
        DOMAIN_BEACON_PROPOSER,
        DOMAIN_RANDAO,
        compute_signing_root,
        get_domain,
    )

    chain = client.chain
    t, P = chain.types, chain.preset
    epoch = slot // P.SLOTS_PER_EPOCH
    code, duties = api.call("GET", f"/eth/v1/validator/duties/proposer/{epoch}")
    if code != 200:
        raise RuntimeError(f"proposer duties: {code} {duties}")
    proposer = next(
        int(d["validator_index"]) for d in duties["data"]
        if int(d["slot"]) == slot
    )
    sk = Duties.sk(proposer)
    state = chain.head_state
    randao = sk.sign(compute_signing_root(
        ssz.Uint64, epoch, get_domain(chain.spec, state, DOMAIN_RANDAO, epoch)
    )).serialize()
    t0 = time.perf_counter()
    code, doc = api.call(
        "GET", f"/eth/v2/validator/blocks/{slot}?randao_reveal=0x{randao.hex()}"
    )
    if code != 200:
        raise RuntimeError(f"block production: {code} {doc}")
    produce_s = time.perf_counter() - t0
    fork = doc["version"]
    block = from_json(t.block[fork], doc["data"])
    sig = sk.sign(compute_signing_root(
        type(block), block,
        get_domain(chain.spec, state, DOMAIN_BEACON_PROPOSER, epoch),
    ))
    signed = t.signed_block[fork](message=block, signature=sig.serialize())
    body = block.body
    t1 = time.perf_counter()
    code, resp = api.call(
        "POST", "/eth/v1/beacon/blocks",
        {"version": fork, "data": to_json(type(signed), signed)},
    )
    log("block", slot=slot, proposer=proposer, http=code,
        attestations=len(body.attestations),
        sync_participants=sum(bool(b) for b in
                              body.sync_aggregate.sync_committee_bits),
        produce_s=round(produce_s, 2),
        import_s=round(time.perf_counter() - t1, 2))
    if code != 200:
        raise AssertionError(f"block rejected: {code} {resp}")
    if len(body.attestations) == 0 or not any(
        body.sync_aggregate.sync_committee_bits
    ):
        raise AssertionError("the block carries no attestations or sync aggregate")


def serve(api: Api, bodies_by_path: dict) -> dict:
    """POST every body from its own client thread, all at once and
    interleaved across paths, so the scheduler fuses across callers.
    Returns ``{path: [(status, response), ...]}`` in body order."""
    jobs = [
        (path, i, body)
        for path, bodies in bodies_by_path.items()
        for i, body in enumerate(bodies)
    ]
    jobs.sort(key=lambda j: (j[1], j[0]))
    out = {path: [None] * len(b) for path, b in bodies_by_path.items()}
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        futs = [
            (path, i, pool.submit(api.call, "POST", path, body))
            for path, i, body in jobs
        ]
        for path, i, fut in futs:
            out[path][i] = fut.result()
    wall = time.perf_counter() - t0
    for path, rows in out.items():
        hist: dict = {}
        for code, _ in rows:
            hist[code] = hist.get(code, 0) + 1
        log("served", path=path, requests=len(rows), http=hist,
            wall_s=round(wall, 3))
    return out


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def cold_buckets(since: float) -> list:
    """The exact buckets of flushes routed cold since ``since``."""
    from lighthouse_tpu.utils import flight_recorder

    return sorted({
        (f["exact_b"], f["exact_k"], f["exact_m"], f["action"])
        for e in flight_recorder.events(kinds=("cold_route",))
        if e["t"] >= since
        for f in (e["fields"],)
    })


def stage_rows(since: float) -> list:
    from lighthouse_tpu.utils import flight_recorder

    return [
        e["fields"] for e in flight_recorder.events(kinds=("bls_stage_verify",))
        if e["t"] >= since
    ]


def one_chip(args, device: dict) -> None:
    n = args.validators
    burst_sets, gossip_rung = gossip_burst(n)
    rungs = (gossip_rung, block_rung(n))
    client = build_node(n, rungs, dp_devices=1, burst_sets=burst_sets)
    try:
        chain = client.chain
        wait_compiled(client, rungs, devices=(0,))
        key_table_check(client, n, replicas=[0])
        api = Api(client.api.port)
        log_sub = SubmissionLog(chain.verification_scheduler)

        slot, aggregates, unaggregated, tampered, contributions = (
            signed_ahead(client, api)
        )
        wait_for_slot(client, slot)

        cold_before = counter_total("compile_service_cold_routes_total")
        bisect_before = counter_total("verification_scheduler_bisections_total")
        lat_before = latency_samples_by_path()
        t_traffic = time.time()
        # the tampered attestation goes out mid-burst, among neighbours
        mid = len(unaggregated) // 2
        gossip = {
            "/eth/v1/validator/aggregate_and_proofs": aggregates,
            "/eth/v1/beacon/pool/attestations":
                unaggregated[:mid] + [tampered] + unaggregated[mid:],
            "/eth/v1/validator/contribution_and_proofs": contributions,
        }
        results = serve(api, gossip)
        att_codes = [c for c, _ in results["/eth/v1/beacon/pool/attestations"]]
        if att_codes.pop(mid) != 400 or set(att_codes) != {200}:
            raise AssertionError(f"attestations: {att_codes}")
        for path in ("/eth/v1/validator/aggregate_and_proofs",
                     "/eth/v1/validator/contribution_and_proofs"):
            if {c for c, _ in results[path]} != {200}:
                raise AssertionError(f"{path}: {results[path]}")
        bisections = (
            counter_total("verification_scheduler_bisections_total")
            - bisect_before
        )
        if bisections <= 0:
            raise AssertionError("the tampered attestation was never fused "
                                 "with its neighbours")

        wait_for_slot(client, slot + 1)
        post_block(client, api, slot + 1)

        verdicts = log_sub.check_against_cpu_native()
        log("verdicts match cpu-native", per_kind=verdicts,
            bisections=bisections)
        cold = counter_total("compile_service_cold_routes_total") - cold_before
        lat_after = latency_samples_by_path()
        lat = {p: lat_after.get(p, 0) - lat_before.get(p, 0) for p in lat_after}
        log("resolution paths", samples=lat, cold_routes=cold,
            cold_buckets=cold_buckets(t_traffic))
        if cold or lat.get("fallback", 0) or lat.get("shed", 0):
            raise AssertionError("traffic was answered off the device")

        rows = stage_rows(t_traffic)
        kinds = {}
        for r in rows:
            # a fused flush carries several kinds ("aggregate+unaggregated")
            for kind in r["kind"].split("+"):
                rec = kinds.setdefault(kind, {"rows": 0, "gathered": 0})
                rec["rows"] += 1
                rec["gathered"] += bool(r.get("gathered"))
        log("bls_stage_verify", per_kind=kinds,
            devices=sorted({r["device"] for r in rows}),
            geometries=sorted({(r["b"], r["k"], r["m"]) for r in rows}),
            recompiled=sum(bool(r["recompiled"]) for r in rows))
        want_kinds = {"aggregate", "unaggregated", "sync_contribution", "block"}
        if not want_kinds <= set(kinds):
            raise AssertionError(f"no device rows for {want_kinds - set(kinds)}")
        if any(r["gathered"] != r["rows"] for r in kinds.values()):
            raise AssertionError("a table-resident batch shipped raw keys")
        if {r["device"].split(":")[0] for r in rows} != {device["platform"]}:
            raise AssertionError("a stage output is not on the device")
        st = key_table_check(client, n, replicas=[0])
        if st["sets"]["indexed"] <= 0:
            raise AssertionError("no set was verified from the key table")
        from lighthouse_tpu.crypto.device.bls import stage_latency_summary

        log("stage latency", **{
            k: v for k, v in stage_latency_summary(None).items()
            if k.startswith(("stage", "gather", "verify"))
        })
    finally:
        stop_node(client)


def four_chips(args, device: dict) -> None:
    n = args.validators
    devices = tuple(range(DP_SHARDS))
    rung = dp_rung(n)
    client = build_node(n, (rung,), dp_devices=DP_SHARDS, burst_sets=DP_SETS)
    try:
        chain = client.chain
        wait_compiled(client, (rung,), devices=devices)
        key_table_check(client, n, replicas=list(devices))
        api = Api(client.api.port)
        sched = chain.verification_scheduler
        log_sub = SubmissionLog(sched)

        slot = client.slot_clock.now() + 1
        wait_for_slot(client, slot)
        duties = Duties(client, api, slot)
        good, tampered = duties.unaggregated(DP_SETS - 1, run=DP_SHARDS)
        # the node's own set construction for each attestation
        from lighthouse_tpu.beacon_chain.attestation_verification import (
            _structural_unaggregated,
        )
        from lighthouse_tpu.ssz.json import from_json
        from lighthouse_tpu.state_transition.signature_sets import (
            indexed_attestation_set,
        )

        submissions = []
        for body in good + [tampered]:
            att = from_json(chain.types.Attestation, body[0])
            indexed, _ = _structural_unaggregated(chain, att, slot)
            submissions.append([indexed_attestation_set(
                chain.preset, chain.spec, chain.head_state, indexed,
                chain.pubkey_cache.resolver(),
            )])
        shard_before = {
            s: counter_total("bls_device_shard_sets_total", shard=str(s))
            for s in devices
        }
        cold_before = counter_total("compile_service_cold_routes_total")
        t_flush = time.time()
        futs = [sched.submit(sets, "unaggregated") for sets in submissions]
        verdicts = [f.result(timeout=600) for f in futs]
        per_shard = {
            s: counter_total("bls_device_shard_sets_total", shard=str(s))
            - shard_before[s] for s in devices
        }
        rows = stage_rows(t_flush)
        log("dp flush", sets=len(submissions), per_shard_sets=per_shard,
            verdicts=[int(v) for v in verdicts],
            row_devices=sorted({r["device"] for r in rows}),
            cold_routes=counter_total("compile_service_cold_routes_total")
            - cold_before, cold_buckets=cold_buckets(t_flush))
        expected = [True] * (DP_SETS - 1) + [False]
        if verdicts != expected:
            raise AssertionError(f"dp verdicts {verdicts}")
        if cold_buckets(t_flush):
            raise AssertionError("a shard's sub-batch was routed cold")
        if any(v <= 0 for v in per_shard.values()):
            raise AssertionError(f"a shard verified nothing: {per_shard}")
        import jax

        on_device = {r["device"] for r in rows}
        want = {f"{device['platform']}:{d.id}" for d in jax.devices()[:DP_SHARDS]}
        if not want <= on_device:
            raise AssertionError(f"stage outputs on {on_device}, not {want}")
        # the same submissions, each verified alone on one chip
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.crypto.device import mesh as mesh_mod

        with mesh_mod.dispatch_to(0):
            one_chip_verdicts = [
                bls.verify_signature_sets(sets) for sets in submissions
            ]
        if one_chip_verdicts != verdicts:
            raise AssertionError(f"one-chip verdicts {one_chip_verdicts}")
        log("verdicts match one chip and cpu-native",
            per_kind=log_sub.check_against_cpu_native())
        key_table_check(client, n, replicas=list(devices))
    finally:
        stop_node(client)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--validators", type=int, default=None,
        help=f"registry size (default {VALIDATORS}); with JAX_PLATFORMS=cpu "
        f"it asks for the CPU rehearsal",
    )
    args = ap.parse_args()
    device = require_device(args.chips, rehearsal=args.validators is not None)
    if args.validators is None:
        args.validators = VALIDATORS
    from lighthouse_tpu.utils import flight_recorder

    # room for every journal row of the run (the checks read them back)
    flight_recorder.configure(capacity=1 << 16)
    if device["platform"] == "tpu" and args.validators < VALIDATORS:
        log("validator count cut", validators=args.validators,
            full=VALIDATORS)
    log("device", **device)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, device)
    else:
        one_chip(args, device)
    log("done", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
