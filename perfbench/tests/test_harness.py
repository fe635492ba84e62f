"""The comparison that decides ``correct`` catches the control and the
planted faults.

Each case drives a real window of the cell's traffic through the
program's scheduler, with a stand-in in the device's place (the
pure-Python reference verifier; the compile service is left out, so
nothing compiles), then runs the run's own comparison. A sound stand-in
reads 0 mismatches; the control (random scalars all 1) and each fault a
cell can have read more, so ``correct`` comes out false:

* ``flip``: an answer altered where it is produced (every 3rd);
* ``half``: the first half of each batch left out of the check;
* ``stale``: each call answers with the previous call's verdict, the
  verifier's form of a step that returns its state unchanged.

A one-chip cell has no exchange between chips to leave out. Blocks are
cut to 8 attestations and every other block is tampered, so that a
window of a few seconds on the CPU holds several blocks and tampered
ones among them; the registry is 16,384 validators.
"""

import itertools

import pytest

VALIDATORS = 16384
_names = itertools.count()


def run_window(workload, seconds, seed, **standin):
    import jax

    from harness import cell as C
    from harness import check, standins

    cell = C.Cell(workload)
    cell.mix["block"]["attestations"] = 8
    cell.mix.update(tampered_every_blocks=2, warmup_blocks=1)
    name = standins.register(f"test-standin-{next(_names)}", **standin)
    setup = C.Setup(cell, VALIDATORS, [], threads=4, backend=name,
                    compile_service=False)
    try:
        setup.warm_up(seed)
        w = C.window(setup, seed, seconds, False, jax.devices()[0])
    finally:
        setup.svc.stop()
    n = w.res.due
    return check.compare(w.subs[:n], w.res.verdicts[:n], seed, 4)


WORKLOAD = "block-1m.sync"


def test_sound_standin_reads_no_mismatch():
    got = run_window(WORKLOAD, 3.0, 2**33 + 5, scalars="random")
    assert got["tampered"] > 0
    assert got["mismatches"] == 0, got


@pytest.mark.parametrize("standin", [
    {"scalars": "ones"},
    {"scalars": "random", "fault": "flip"},
    {"scalars": "random", "fault": "half"},
    {"scalars": "random", "fault": "stale"},
], ids=["control", "flip", "half", "stale"])
def test_control_and_faults_read_not_correct(standin):
    got = run_window(WORKLOAD, 3.0, 2**33 + 7, **standin)
    assert got["mismatches"] > 0, got
