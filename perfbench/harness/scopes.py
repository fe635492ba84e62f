"""Device time by named scope, and device idle under the program's own
spans, from the profiler trace of a traced run (``--trace 1``).

The staged verify program names its operations: each stage traces under
a top ``jax.named_scope`` (``bls.gather``, ``bls.stage1``..``3``) and its
algorithmic pieces under inner ones (``sqrt``, ``miller_loop``, ...;
``lighthouse_tpu/crypto/device/scopes.py``). A scope lands in the
``op_name`` metadata of every operation traced under it. The trace
keeps each program's optimised HLO (the ``Hlo Proto`` stat of the
``/host:metadata`` plane), which names the same top-level operations the
device's ``XLA Ops`` line times (``while.24``); an operation's
``op_name`` is read from there. (The device events' own ``tf_op`` stat
is no substitute: on a v5e chip a loop's body ops carry it, the loop
itself, the top-level op, does not.) An operation outside every program
scope (a copy or loop XLA or a library put in) counts under the top
scope its program's other operations carry.

The program's spans (``utils/tracing.py``) reach the trace as profiler
TraceMes on the device's clock; the idle share under a span family is
the part of the window in which the device ran nothing while a span of
that family was open.

A device's ``XLA Ops`` line holds one event per op per loop iteration,
millions in a few seconds of the staged program, so the file is parsed
once with protobuf's own parser (only the fields read; ``_SCHEMA``) and
the events reduced with numpy; every reader of a run shares that one
load, and it is ``None`` when the run made no trace. The window is the
harness's ``perfbench.window`` span and device time and busy time are
taken as ``harness/trace.py`` takes them: top-level operations (not
nested in an earlier one), the union of program executions, averaged
over the device planes that ran in the window.
"""

from __future__ import annotations

import bisect
import collections

from . import cell, trace

# host spans the program emits (utils/tracing.py)
PROGRAM_PREFIXES = ("bls.", "key_table.", "scheduler.", "compile_service.")
HLO_PROTO_STAT = "Hlo Proto"
# the program's top scopes: an operation outside them counts under the
# one its program's other operations carry
TOP_PREFIX = "bls."

_loaded: dict = {}


def load(ctx):
    """The trace of the run ``ctx`` describes, parsed once, or None when
    the run made none."""
    if ctx.trace is None:
        return None
    path = trace.find_xplane(cell.TRACE_DIR)
    if path not in _loaded:
        with open(path, "rb") as f:
            _loaded[path] = Trace(f.read())
    return _loaded[path]


def device_ms_per_block(ctx, *paths):
    """Device ms per block of the top-level operations under the scopes
    ``paths``: a block runs each staged program once, so this is the
    device time under the scopes per run of the programs that hold them
    (``Trace.device_seconds_per_run``); None when the run made no trace
    or its program has no named scopes."""
    tr = load(ctx)
    if tr is None:
        return None
    parts = [tr.device_seconds_per_run(p) for p in paths]
    return None if None in parts else 1e3 * sum(parts)


def idle_pct_under(ctx, prefix: str):
    """Share of the traced window, in %, in which the device ran nothing
    while a program span named ``prefix*`` was open; None when the run
    made no trace or its program no such span."""
    tr = load(ctx)
    idle = tr.idle_under(prefix) if tr else None
    return None if idle is None else 100.0 * idle / ctx.trace["window_s"]


def matches(op_name: str, path: str) -> bool:
    """Whether ``op_name`` (``jit(f)/bls.stage3/miller_loop/while``) lies
    under the scope ``path`` (``bls.stage3/miller_loop``): the path's
    parts appear among the name's parts, in order."""
    parts = iter(op_name.split("/"))
    return all(any(p == want for p in parts) for want in path.split("/"))


class Trace:
    """A parsed ``.xplane.pb``, reduced to the window: per device plane
    that ran in it, the busy intervals, the device seconds by
    ``op_name``, and the same for the program runs that lie wholly in
    the window, with their count by program; and the program's host
    spans."""

    def __init__(self, data: bytes):
        space = _messages()["XSpace"]()
        space.ParseFromString(data)
        hlo = _OpNames()
        device_planes, spans, windows = [], [], []
        for plane in space.planes:
            names = {e.key: e.value.name for e in plane.event_metadata}
            if plane.name == "/host:metadata":
                hlo.add(plane)
            elif plane.name.startswith("/device:"):
                lines = {line.name: line for line in plane.lines}
                if trace.OPS_LINE in lines:
                    device_planes.append((names, lines))
            elif plane.name.startswith("/host:"):
                want = {k for k, n in names.items()
                        if n == trace.WINDOW or n.startswith(PROGRAM_PREFIXES)}
                for line in plane.lines:
                    for ev in line.events:
                        if ev.metadata_id in want:
                            s = line.timestamp_ns + ev.offset_ps / 1e3
                            e = s + ev.duration_ps / 1e3
                            name = names[ev.metadata_id]
                            (windows if name == trace.WINDOW else spans).append(
                                (name, s, e))
        if not windows:
            raise ValueError(f"trace has no {trace.WINDOW} span")
        self.w0, self.w1 = windows[0][1], windows[0][2]
        self.spans = spans
        self.devices = [d for d in (self._device(names, lines, hlo)
                                    for names, lines in device_planes)
                        if d["busy"]]

    def _device(self, names, lines, hlo) -> dict:
        """One device plane: busy intervals in the window (its program
        executions, or its ops where it has no programs line) and device
        seconds in the window by ``op_name``."""
        import numpy as np

        w0, w1 = self.w0, self.w1
        mline = lines.get(trace.MODULES_LINE)
        modules = sorted(
            ((names[ev.metadata_id], s, s + ev.duration_ps / 1e3)
             for ev in (mline.events if mline is not None else ())
             for s in [mline.timestamp_ns + ev.offset_ps / 1e3]),
            key=lambda m: m[1])
        ops = lines[trace.OPS_LINE]
        n = len(ops.events)
        mid = np.fromiter((ev.metadata_id for ev in ops.events), np.int64, n)
        start = ops.timestamp_ns + np.fromiter(
            (ev.offset_ps for ev in ops.events), np.float64, n) / 1e3
        end = start + np.fromiter(
            (ev.duration_ps for ev in ops.events), np.float64, n) / 1e3
        # top level: not nested in an earlier event (trace._top_level)
        order = np.lexsort((-end, start))
        mid, start, end = mid[order], start[order], end[order]
        before = np.maximum.accumulate(np.concatenate(([-np.inf], end[:-1])))
        top = (start >= before) & (end > w0) & (start < w1)
        mid, start, end = mid[top], start[top], end[top]
        mstarts = [m[1] for m in modules]
        by_op = collections.Counter()
        in_runs = collections.defaultdict(collections.Counter)
        for m, s, e in zip(mid.tolist(), start.tolist(), end.tolist()):
            i = bisect.bisect_right(mstarts, s) - 1
            held = i >= 0 and modules[i][2] >= e
            module = modules[i][0] if held else ""
            name = hlo.op_name(module, names[m].split(" = ")[0].lstrip("%"))
            by_op[name] += (min(e, w1) - max(s, w0)) / 1e9
            if held and modules[i][1] >= w0 and modules[i][2] <= w1:
                in_runs[module][name] += (e - s) / 1e9
        runs = collections.Counter(
            m[0] for m in modules if m[1] >= w0 and m[2] <= w1)
        execs = modules or list(zip(start.tolist(), end.tolist()))
        busy = trace._union(
            (max(r[-2], w0), min(r[-1], w1))
            for r in execs if r[-1] > w0 and r[-2] < w1)
        return {"busy": busy, "by_op": by_op, "in_runs": in_runs,
                "runs": runs}

    def device_seconds(self, path: str):
        """Device seconds in the window of the top-level operations under
        scope ``path``; None when no operation of the trace carries a
        program scope (a program without named scopes)."""
        if not any(_top_part(n) for d in self.devices for n in d["by_op"]):
            return None
        return sum(sec for d in self.devices for n, sec in d["by_op"].items()
                   if matches(n, path)) / len(self.devices)

    def device_seconds_per_run(self, path: str):
        """Device seconds under scope ``path`` per run of the program
        that holds it, over the runs wholly in the window (summed over
        programs when several hold it); None as ``device_seconds``."""
        if self.device_seconds(path) is None:
            return None
        return sum(sum(sec for n, sec in ops.items() if matches(n, path))
                   / d["runs"][program]
                   for d in self.devices
                   for program, ops in d["in_runs"].items()) / len(self.devices)

    def busy_seconds(self):
        if not self.devices:
            return None
        return sum(e - s for d in self.devices
                   for s, e in d["busy"]) / 1e9 / len(self.devices)

    def idle_under(self, prefix: str):
        """Seconds of the window in which the device ran nothing while a
        program span named ``prefix*`` was open; None when the trace has
        no such span."""
        covered = trace._union(
            (max(s, self.w0), min(e, self.w1))
            for name, s, e in self.spans
            if name.startswith(prefix) and e > self.w0 and s < self.w1)
        if not covered or not self.devices:
            return None
        total = 0.0
        for dev in self.devices:
            busy = dev["busy"]
            starts = [iv[0] for iv in busy]
            for a, b in covered:
                total += (b - a) - _overlap(busy, starts, a, b)
        return total / 1e9 / len(self.devices)


def _overlap(busy, starts, a, b) -> float:
    """Length of [a, b] covered by the sorted, merged ``busy`` (whose
    interval starts are ``starts``)."""
    out = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    for s, e in busy[i:]:
        if s >= b:
            break
        out += max(0.0, min(e, b) - max(s, a))
    return out


def _top_part(op_name: str) -> str:
    """The program scope (``bls.stage2``) in ``op_name``, or ''."""
    return next((p for p in op_name.split("/") if p.startswith(TOP_PREFIX)), "")


class _OpNames:
    """``op_name`` of each operation, from each program's HLO the trace
    keeps, by module name (as the ``XLA Modules`` line names it)."""

    def __init__(self):
        self.hlo = {}          # module -> {instruction: op_name}
        self._top = {}

    def add(self, plane) -> None:
        """The programs of the ``/host:metadata`` plane."""
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) == HLO_PROTO_STAT:
                    proto = _messages()["HloProto"]()
                    proto.ParseFromString(st.bytes_value)
                    self.hlo[entry.value.name] = {
                        ins.name: ins.metadata.op_name
                        for comp in proto.hlo_module.computations
                        for ins in comp.instructions}

    def op_name(self, module, op) -> str:
        name = self.hlo.get(module, {}).get(op, "")
        return name if _top_part(name) else self.top_scope(module)

    def top_scope(self, module) -> str:
        """The program scope most of ``module``'s operations carry."""
        if module not in self._top:
            tops = collections.Counter(
                filter(None, map(_top_part, self.hlo.get(module, {}).values())))
            self._top[module] = tops.most_common(1)[0][0] if tops else ""
        return self._top[module]


_MESSAGES: dict = {}

# the fields read, with the numbers of tsl/profiler/protobuf/xplane.proto
# and xla/service/hlo.proto (the parser skips every other field)
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("bytes_value", 6, "bytes", False)],
    "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    "HloModuleProto": [("computations", 3, "HloComputationProto", True)],
    "HloComputationProto": [("instructions", 2, "HloInstructionProto", True)],
    "HloInstructionProto": [("name", 1, "string", False),
                            ("metadata", 7, "OpMetadata", False)],
    "OpMetadata": [("op_name", 2, "string", False)],
}


def _messages() -> dict:
    """Message classes for the subset of the profiler's and XLA's protos
    in ``_SCHEMA``, built once from a descriptor (protobuf's own parser:
    no generated modules needed)."""
    if _MESSAGES:
        return _MESSAGES
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "bytes": F.TYPE_BYTES,
              "int64": F.TYPE_INT64}
    pkg = "perfbench_trace_subset"
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".{pkg}.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    for msg in _SCHEMA:
        _MESSAGES[msg] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{pkg}.{msg}"))
    return _MESSAGES
