"""Device time by named scope and idle under the program's spans
(``harness/scopes.py``), on a small trace recorded on a v5e chip
(``data/scopes.xplane.pb``, made by ``tools/record_scopes_trace.py``:
one ``key_table.resolve`` program span over a 20 ms host sleep, then a
program under ``bls.stage1`` with a loop under ``sqrt`` and one under
``bls.stage3`` with loops under ``miller_loop`` and ``final_exp``), and
on the earlier ``data/small.xplane.pb``, whose program has no scopes."""

import os
import types

import pytest

from harness import cell, scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "scopes.xplane.pb")
UNSCOPED = os.path.join(DATA, "small.xplane.pb")
NEW_READERS = ("keys_ms.block", "keys_idle_pct.block", "stage1_dev_ms.block",
               "stage2_dev_ms.block", "miller_dev_ms.block",
               "final_exp_dev_ms.block")


def _trace(path):
    with open(path, "rb") as f:
        return scopes.Trace(f.read())


def test_matches_scope_paths_in_order():
    name = "jit(_stage3_fn)/bls.stage3/miller_loop/while"
    assert scopes.matches(name, "bls.stage3")
    assert scopes.matches(name, "bls.stage3/miller_loop")
    assert not scopes.matches(name, "miller_loop/bls.stage3")
    assert not scopes.matches(name, "bls.stage3/final_exp")
    assert not scopes.matches(name, "bls.stage1")


def test_overlap_of_merged_intervals():
    busy = [[0.0, 10.0], [20.0, 30.0], [40.0, 50.0]]
    starts = [iv[0] for iv in busy]
    assert scopes._overlap(busy, starts, 5.0, 45.0) == 20.0
    assert scopes._overlap(busy, starts, 10.0, 20.0) == 0.0
    assert scopes._overlap(busy, starts, 25.0, 26.0) == 1.0


def test_scopes_and_idle_on_a_recorded_chip_trace():
    tr = _trace(SCOPED)
    s1 = tr.device_seconds("bls.stage1")
    s3 = tr.device_seconds("bls.stage3")
    sqrt = tr.device_seconds("bls.stage1/sqrt")
    miller = tr.device_seconds("bls.stage3/miller_loop")
    final = tr.device_seconds("bls.stage3/final_exp")
    assert 0 < sqrt <= s1 and 0 < miller and 0 < final
    assert miller + final <= s3
    # the loops are the programs' work; the scopes cover the device
    busy = tr.busy_seconds()
    assert 0.9 * busy <= s1 + s3 <= 1.0001 * busy
    assert tr.device_seconds("bls.stage2") == 0.0
    # the program span sat over a 20 ms sleep with the device idle
    names = [name for name, _s, _e in tr.spans]
    assert names == ["key_table.resolve"]
    idle = tr.idle_under("key_table.")
    window = (tr.w1 - tr.w0) / 1e9
    assert 0.018 < idle < 0.05 and idle <= window - busy
    assert tr.idle_under("bls.") is None


def _space(modules, ops, hlo, window=(0, 1000)):
    """A small XSpace: ``modules`` and ``ops`` as ``(name, start_ns,
    end_ns)`` on one device, ``hlo`` as ``{module: {op: op_name}}``."""
    m = scopes._messages()
    space = m["XSpace"]()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = trace.WINDOW
    line = host.lines.add(name="python")
    line.events.add(metadata_id=1, offset_ps=window[0] * 1000,
                    duration_ps=(window[1] - window[0]) * 1000)
    dev = space.planes.add(name="/device:TPU:0")
    ids = {}
    for name, _s, _e in modules + ops:
        if name not in ids:
            ids[name] = len(ids) + 1
            dev.event_metadata.add(key=ids[name]).value.name = name
    for line_name, events in ((trace.MODULES_LINE, modules),
                              (trace.OPS_LINE, ops)):
        line = dev.lines.add(name=line_name)
        for name, s, e in events:
            line.events.add(metadata_id=ids[name], offset_ps=s * 1000,
                            duration_ps=(e - s) * 1000)
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=7).value.name = scopes.HLO_PROTO_STAT
    for i, (module, names) in enumerate(hlo.items()):
        proto = m["HloProto"]()
        comp = proto.hlo_module.computations.add()
        for op, op_name in names.items():
            comp.instructions.add(name=op).metadata.op_name = op_name
        em = meta.event_metadata.add(key=i + 1).value
        em.name = module
        em.stats.add(metadata_id=7, bytes_value=proto.SerializeToString())
    return space.SerializeToString()


def test_ops_take_the_scope_of_the_program_run_that_holds_them():
    """Programs are found by time, whatever their names' order; an op
    without a scope takes its program's top scope."""
    data = _space(
        modules=[("jit_b(2)", 0, 100), ("jit_a(1)", 200, 300)],
        ops=[("%while.1 = (s32[]) while(...)", 10, 90),
             ("%fusion.3 = s32[4] fusion(...)", 20, 30),   # nested
             ("%copy.2 = s32[4] copy(...)", 90, 100),
             ("%while.1 = (s32[]) while(...)", 210, 280)],
        hlo={"jit_b(2)": {"while.1": "jit(b)/bls.stage3/miller_loop/while",
                          "copy.2": "x"},
             "jit_a(1)": {"while.1": "jit(a)/bls.stage1/sqrt/while"}})
    tr = scopes.Trace(data)
    assert tr.device_seconds("bls.stage3/miller_loop") == pytest.approx(80e-9)
    assert tr.device_seconds("bls.stage3") == pytest.approx(90e-9)
    assert tr.device_seconds("bls.stage1/sqrt") == pytest.approx(70e-9)
    assert tr.busy_seconds() == pytest.approx(200e-9)


def test_per_run_device_time_counts_the_runs_wholly_in_the_window():
    """Two whole runs of a program and one cut by the window's end: the
    window holds 80 + 60 + 50 ns under the scope, a run 70 ns."""
    loop = "%while.1 = (s32[]) while(...)"
    data = _space(
        modules=[("jit_c(3)", 100, 200), ("jit_c(3)", 300, 400),
                 ("jit_c(3)", 950, 1050)],
        ops=[(loop, 110, 190), (loop, 320, 380), (loop, 950, 1030)],
        hlo={"jit_c(3)": {"while.1": "jit(c)/bls.stage2/g2_ladder/while"}})
    tr = scopes.Trace(data)
    assert tr.device_seconds("bls.stage2") == pytest.approx(190e-9)
    assert tr.device_seconds_per_run("bls.stage2/g2_ladder") == pytest.approx(
        70e-9)
    assert tr.device_seconds_per_run("bls.stage3") == 0.0


def test_a_program_without_scopes_reads_none():
    tr = _trace(UNSCOPED)
    assert tr.busy_seconds() > 0
    assert tr.device_seconds("bls.stage1") is None
    assert tr.idle_under("key_table.") is None


def _ctx(path, submissions=4):
    reduced = trace.reduce(*trace.load(path))
    run = {"window_s": 2 * reduced["window_s"], "submissions": submissions,
           "sets": 131 * submissions, "setup_s": 1.0}
    delta = types.SimpleNamespace(after={}, hist=lambda *a, **k: (0, 0.0))
    return types.SimpleNamespace(delta=delta, trace=reduced, run=run)


def test_readers_on_the_recorded_trace(monkeypatch):
    scoped = _trace(SCOPED)
    monkeypatch.setattr(scopes, "load", lambda ctx: scoped)
    ctx = _ctx(SCOPED)
    got = {name: cell.reader(name)(ctx) for name in NEW_READERS}
    # the trace has no key-table counter; the rest read the trace
    assert got.pop("keys_ms.block") is None
    # one run of each program in the window: a block's worth each
    assert got["stage1_dev_ms.block"] == pytest.approx(
        1e3 * scoped.device_seconds("bls.stage1"))
    assert got["miller_dev_ms.block"] == pytest.approx(
        1e3 * scoped.device_seconds("bls.stage3/miller_loop"))
    assert got["stage2_dev_ms.block"] == 0.0
    assert 0 < got["keys_idle_pct.block"] <= ctx.trace["idle_pct"]
    assert got["final_exp_dev_ms.block"] > 0


def test_readers_read_none_without_what_they_read(monkeypatch):
    """A run with no trace, and a program with neither scopes, spans
    nor the key-table family (the parent of this change), read None."""
    ctx = _ctx(UNSCOPED)
    unscoped = _trace(UNSCOPED)
    monkeypatch.setattr(scopes, "load", lambda c: unscoped)
    assert all(cell.reader(n)(ctx) is None for n in NEW_READERS)
    ctx.trace = None
    monkeypatch.undo()
    assert all(cell.reader(n)(ctx) is None for n in NEW_READERS)


def test_keys_ms_reads_the_resolve_histogram():
    rows = {"bls_device_key_table_resolve_seconds": {}}
    ctx = types.SimpleNamespace(
        delta=types.SimpleNamespace(
            after=rows, hist=lambda name, **labels: (8, 0.4)),
        trace=None, run={"submissions": 4})
    assert cell.reader("keys_ms.block")(ctx) == pytest.approx(100.0)
