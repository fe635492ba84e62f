"""Reduction of a profiler trace (``.xplane.pb``) of one window to the
device numbers: busy time as the union of the intervals in which an
operation ran on the device, the idle share, device time per operation,
and the device's idle gaps attributed to what the host was doing.

Host and device events share the profile's clock. The window is the
host span ``perfbench.window``; device intervals are clipped to it.
Busy time is the union of the device's program executions (the ``XLA
Modules`` line; the ``XLA Ops`` line where a plane has no programs).
Device time per operation sums the top-level events of the ``XLA Ops``
line (an op inside a loop body is counted in its loop), named
``<program>/<op>``. A gap is attributed to the innermost
``perfbench.*`` host span that covers its midpoint (the harness's own
calls into each layer), else to ``idle``: no call of the harness was in
flight.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW = "perfbench.window"
PREFIX = "perfbench."
# lines of a TPU device plane that hold operations and programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _top_level(events):
    """Events not nested inside an earlier one of the same line, with
    the op's name only (``%fusion.12 = ...`` -> ``fusion.12``)."""
    out, end = [], float("-inf")
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if s >= end:
            out.append((name.split(" = ")[0].lstrip("%"), s, e))
            end = e
    return out


def load(path: str):
    """(device planes, host spans) of a trace: ``devices`` maps a device
    plane name to ``{"ops": [...], "modules": [...]}`` event lists and
    ``spans`` holds every ``perfbench.*`` host event, each as
    ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            ops = (_top_level(_events(lines[OPS_LINE]))
                   if OPS_LINE in lines else [])
            modules = (list(_events(lines[MODULES_LINE]))
                       if MODULES_LINE in lines else [])
            if ops or modules:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(PREFIX))
    return devices, spans


def reduce(devices: dict, spans: list, top: int = 10) -> dict:
    """The window's device numbers, averaged over the device planes that
    ran an operation in it."""
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW} span")
    w0, w1 = windows[0][1], windows[0][2]
    window_s = (w1 - w0) / 1e9
    inner = sorted((s for s in spans if s[0] != WINDOW),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]
    longest = max((s[2] - s[1] for s in inner), default=0.0)
    busy_total, n_dev = 0.0, 0
    op_time, gap_time, gaps = {}, {}, []
    for dev in devices.values():
        clipped = [(max(s, w0), min(e, w1))
                   for _, s, e in (dev["modules"] or dev["ops"])
                   if e > w0 and s < w1]
        busy = _union(clipped)
        if not busy:
            continue
        n_dev += 1
        busy_total += sum(e - s for s, e in busy) / 1e9
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mstarts = [m[1] for m in mods]
        for name, s, e in dev["ops"]:
            if e <= w0 or s >= w1:
                continue
            i = bisect.bisect_right(mstarts, s) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] >= e else ""
            key = f"{_short(mod)}/{name}" if mod else name
            op_time[key] = op_time.get(key, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            label = _cover(inner, starts, (a + b) / 2, longest)
            gap_time[label] = gap_time.get(label, 0.0) + (b - a) / 1e9
            gaps.append(((b - a) / 1e9, label))
    if not n_dev:
        raise ValueError("no device operation ran inside the window")
    busy_s = busy_total / n_dev
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": sorted(([k, v / n_dev] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gap_time.items()),
                            key=lambda kv: -kv[1])[:top],
        "longest_gaps": [[lbl, s] for s, lbl in sorted(gaps, reverse=True)[:top]],
    }


def _short(module: str) -> str:
    """``jit__stage1_fn(123)`` -> ``stage1_fn``."""
    name = module.split("(")[0]
    for p in ("jit__", "jit_"):
        if name.startswith(p):
            return name[len(p):]
    return name


def _cover(spans, starts, t, longest):
    """Innermost span covering time ``t``, by name; ``idle`` if none.
    Only spans that start within ``longest`` ns before ``t`` can cover
    it."""
    best = None
    lo = bisect.bisect_left(starts, t - longest)
    for name, s, e in spans[lo:bisect.bisect_right(starts, t)]:
        if e >= t and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len(PREFIX):] if best else "idle"
