"""The segment loop (``loops/segments.py``) of ``segment-1m.range-sync``
on the CPU: the comparison that decides ``correct`` catches the control
and the planted faults, and the reference's verdict of a segment is the
AND of its blocks' verdicts.

Each window drives the program's bulk path (``backend_verify_bulk``,
the scheduler's bulk class and planner) with the pure-Python reference
in the device's place, as ``test_harness.py`` does for the block cell;
the compile service is left out, so nothing compiles. Segments are cut
to 4 blocks of 8 attestations (44 sets) and the bulk chunk to 16 sets,
so that a segment spans three chunks, the last one partial, and a
window of a few seconds holds several segments and tampered ones among
them; the registry is 16,384 validators.
"""

import itertools

import pytest

VALIDATORS = 16384
WORKLOAD = "segment-1m.range-sync"
CHUNK = 16
_names = itertools.count()


def small_cell(monkeypatch):
    from harness import cell as C

    cell = C.Cell(WORKLOAD)
    cell.mix["block"]["attestations"] = 8
    cell.cfg["segment_blocks"] = 4
    cell.cfg["scheduler"]["bulk_flush_sets"] = CHUNK
    # the scheduler takes its chunk size from the environment, as a
    # node's does when no setting is passed
    monkeypatch.setenv("LIGHTHOUSE_TPU_SCHED_BULK_FLUSH_SETS", str(CHUNK))
    return cell


def run_window(monkeypatch, seconds, seed, **standin):
    import jax

    from harness import cell as C
    from harness import check, standins

    cell = small_cell(monkeypatch)
    name = standins.register(f"test-segments-{next(_names)}", **standin)
    setup = C.Setup(cell, VALIDATORS, [], threads=4, backend=name,
                    compile_service=False)
    try:
        assert setup.svc.scheduler.bulk_flush_sets == CHUNK
        setup.warm_up(seed)
        w = C.window(setup, seed, seconds, False, jax.devices()[0])
    finally:
        setup.svc.stop()
    n = w.res.due
    got = check.compare(w.subs[:n], w.res.verdicts[:n], seed, 4)
    got["chunks"] = w.delta.count("verification_scheduler_bulk_chunks_total",
                                  kind="chain_segment")
    got["sets"] = sum(len(s.specs) for s in w.subs[:n])
    return got


def test_sound_standin_reads_no_mismatch(monkeypatch):
    got = run_window(monkeypatch, 3.0, 2**33 + 11, scalars="random")
    assert got["tampered"] > 0
    assert got["rejected_by_reference"] == got["tampered"], got
    assert got["mismatches"] == 0, got
    # 44 sets a segment in chunks of 16: three chunks each
    assert got["sets"] == 44 * got["compared"]
    assert got["chunks"] == 3 * got["compared"], got


@pytest.mark.parametrize("standin", [
    {"scalars": "ones"},
    {"scalars": "random", "fault": "flip"},
    {"scalars": "random", "fault": "half"},
    {"scalars": "random", "fault": "stale"},
], ids=["control", "flip", "half", "stale"])
def test_control_and_faults_read_not_correct(monkeypatch, standin):
    got = run_window(monkeypatch, 3.0, 2**33 + 13, **standin)
    assert got["mismatches"] > 0, got


def test_segment_verdict_is_the_and_of_its_blocks(monkeypatch):
    """Upstream's segment semantics: one batch over every set of the
    segment, which fails when any block fails. The reference's verdict
    of a tampered and of a clean segment equals the AND of the verdicts
    of its blocks, each verified alone."""
    from harness import check, signer, traffic
    from harness.traffic import Sub
    from loops import segments

    cell = small_cell(monkeypatch)
    cfg = dict(cell.cfg, validators=VALIDATORS)
    signer.lib()
    subs = segments.traffic(cfg, cell.mix, 2**33 + 17, "window", 0.0, None)
    assert [s.tampered for s in subs] == [True, False]
    traffic.sign_all(subs, 4)
    per = 2 + cell.mix["block"]["attestations"] + 1
    for seg in subs:
        blocks = []
        for at in range(0, len(seg.specs), per):
            b = Sub(0.0, "block", seg.specs[at:at + per])
            b.sigs = seg.sigs[at:at + per]
            blocks.append(b)
        assert len(blocks) == cfg["segment_blocks"]
        want = [check._verdicts(check._job(s, 7 + i), False)[0]
                for i, s in enumerate([seg] + blocks)]
        assert want[0] == all(want[1:])
        assert want[0] is not seg.tampered
        assert sum(not w for w in want[1:]) == int(seg.tampered)
