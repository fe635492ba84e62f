"""One cell of ``BENCHMARK.json``: its files, found by name, and the
stages of a run (device check, set-up, window, comparison, report).

Files of a cell ``<config>.<traffic>``:

* ``configs/<config>.json`` — the deployment (registry, preset sizes,
  scheduler settings), named in ``BENCHMARK.json``;
* ``traffic/<traffic>.json`` — the mix: its loop and that loop's
  parameters;
* ``loops/<loop>.py`` — the loop named by the mix: ``traffic()`` makes
  the signed submissions of a warm-up or a window, ``drive()`` drives
  them through the program's entry point and returns a :class:`Result`;
* ``rungs/<workload>.json`` — the compile ladder the cell pins;
* ``metrics/<metric>.py`` — one reader per metric.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, "build", "trace")
# the traced run traces the window's first seconds
TRACE_SECONDS = 3.0
# a first run in a checkout compiles every pinned rung (~2 min each)
WARM_TIMEOUT_S = 1100.0


def log(msg: str, **fields) -> None:
    """Progress on standard error (standard output carries the result)."""
    line = msg + (" " + json.dumps(fields, sort_keys=True) if fields else "")
    print(line, file=sys.stderr, flush=True)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"perfbench_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Result:
    """What a loop's window produced: ``due`` submissions were due in
    it, each with its verdict (bool, or the exception the caller saw;
    None if it never came) and its latency; ``t_start``/``t_end`` on the
    ``time.perf_counter`` clock."""

    def __init__(self, n: int):
        self.verdicts = [None] * n
        self.latency_s = []
        self.t_start = self.t_end = None
        self.due = 0


class Cell:
    """A workload of ``BENCHMARK.json``."""

    def __init__(self, name: str):
        self.bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
        self.name = name
        self.spec = cells[name]
        conf = {c["name"]: c for c in self.bench["configs"]}[self.spec["config"]]
        self.cfg = _load_json(os.path.join(ROOT, conf["file"]))
        self.mix = _load_json(os.path.join(HERE, "traffic", self.spec["traffic"] + ".json"))
        self.rungs = [tuple(r) for r in _load_json(
            os.path.join(HERE, "rungs", name + ".json"))["rungs"]]
        self.end_to_end = [m for m in self.bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in self.bench["per_layer"] if self._has(m)]

    def _has(self, metric) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def require_device(chips: int, rehearsal: bool) -> dict:
    """The device a run measures on. No TPU, or fewer chips than the cell
    asks for, ends the run with no result; the one exception is the CPU
    rehearsal, asked for by ``JAX_PLATFORMS=cpu`` and a registry size."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (
        rehearsal and platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu"
    ):
        raise SystemExit(f"no TPU: JAX found {platform!r} (the CPU rehearsal "
                         f"needs JAX_PLATFORMS=cpu and --validators)")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if platform == "tpu":
        peaks = _load_json(os.path.join(HERE, "harness", "peaks.json"))["devices"]
        if kind not in peaks:
            raise SystemExit(f"device {kind!r} is not in the peaks table")
    return {"platform": platform, "kind": kind, "count": len(devices),
            "chips": chips}


def reader(metric: str):
    return _load_module("metrics", metric).read


class Setup:
    """Everything built before the window, and what it cost."""

    def __init__(self, cell: Cell, validators: int, rungs, threads: int,
                 verify_fn=None, backend: str = "tpu", compile_service=True):
        from lighthouse_tpu.utils import flight_recorder

        from . import registry, signer
        from .service import Service

        # room for every journal row of a run (routes() reads them back)
        flight_recorder.configure(capacity=1 << 17)
        self.cell = cell
        self.loop = _load_module("loops", cell.mix["loop"])
        self.warm = None
        self.phases = {}
        t = time.perf_counter()
        signer.lib()
        self.phases["signer_build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        cfg = dict(cell.cfg, validators=validators)
        self.cfg = cfg
        self.reg = registry.Registry(validators, threads)
        self.phases["registry_s"] = time.perf_counter() - t
        self.threads = threads
        self.svc = Service(cfg, self.reg, rungs, verify_fn=verify_fn,
                           backend=backend, compile_service=compile_service)
        self.phases.update(self.svc.phases)
        t = time.perf_counter()
        self.compiled = self.svc.wait_warm(WARM_TIMEOUT_S)
        self.phases["compile_s"] = time.perf_counter() - t

    def traffic(self, seed: int, seconds: float, stream: str):
        """Signed submissions of the warm-up or, once it has run, of a
        window of ``seconds``."""
        from . import traffic

        subs = self.loop.traffic(self.cfg, self.cell.mix, seed, stream,
                                 seconds, self.warm)
        traffic.sign_all(subs, self.threads)
        traffic.build_sets(subs, self.reg)
        return subs

    def drive(self, subs, seconds: float):
        return self.loop.drive(self.svc.scheduler, subs, seconds)

    def warm_up(self, seed: int) -> None:
        """Drive the cell's own mix, from a stream of its own, through the
        same path before the window: the window then meets no first-use
        cost of the shapes its traffic uses."""
        t = time.perf_counter()
        subs = self.traffic(seed, 0.0, "warm-up")
        self.warm = self.drive(subs, math.inf)
        self.phases["warm_up_s"] = time.perf_counter() - t
        gc.collect()
        gc.freeze()


class _TraceSlice:
    """A profiler trace of the window's first ``seconds``: a thread of its
    own marks the slice with the span ``perfbench.window``, which the
    trace reduction reads, and stops the trace when the slice ends, so
    that the device tracer's buffers, the trace's export and its
    reduction hold that slice only, and the export overlaps the rest of
    the window. Host events are kept at level 1 (the harness's spans)."""

    def __init__(self, seconds: float):
        import shutil
        import threading

        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(seconds,), name="perfbench-trace")
        self._thread.start()

    def _run(self, seconds: float) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("perfbench.window"):
            self._done.wait(seconds)
        jax.profiler.stop_trace()

    def close(self) -> None:
        self._done.set()
        self._thread.join()


def window(setup: Setup, seed: int, seconds: float, trace: bool, device):
    """Sign the window's traffic, drive it, and read everything the
    report needs; returns a ``types.SimpleNamespace``."""
    from . import counters

    t = time.perf_counter()
    subs = setup.traffic(seed, seconds, "window")
    setup.phases["traffic_s"] = time.perf_counter() - t
    setup.phases["window_submissions"] = len(subs)
    gc.collect()
    out = types.SimpleNamespace(subs=subs, t_wall=time.time())
    before = counters.snapshot()
    tracer = _TraceSlice(TRACE_SECONDS) if trace else None
    try:
        res = setup.drive(subs, seconds)
    finally:
        if tracer is not None:
            tracer.close()
    after = counters.snapshot()
    out.res = res
    out.delta = counters.Delta(before, after)
    stats = device.memory_stats() if hasattr(device, "memory_stats") else None
    out.memory_peak_bytes = int((stats or {}).get("peak_bytes_in_use", 0))
    return out


def routes(since: float) -> dict:
    """How the window's flushes were routed, from the program's flight
    recorder: dispatched sub-batch rungs, and cold routes by exact bucket
    and action (what a pinned ladder missed)."""
    import collections

    from lighthouse_tpu.utils import flight_recorder

    plans, cold = collections.Counter(), collections.Counter()
    for e in flight_recorder.events(kinds=("scheduler_plan", "cold_route")):
        if e["t"] < since:
            continue
        f = e["fields"]
        if e["kind"] == "scheduler_plan":
            for r in str(f.get("rungs", "")).split("+"):
                plans[r] += 1
        else:
            cold[f"{f['exact_b']}x{f['exact_k']}x{f['exact_m']}:{f['action']}"] += 1
    return {"rungs": dict(plans.most_common()), "cold": dict(cold.most_common())}


def in_window_compiles(delta) -> dict:
    return {
        "staged_recompiles": delta.count("bls_device_recompiles_total"),
        "compile_service_compiles": delta.count("compile_service_compiles_total"),
    }
