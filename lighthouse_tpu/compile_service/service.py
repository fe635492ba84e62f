"""CompileService: ahead-of-time warmup, warm-shape routing, and
persistent executable caching for the staged device BLS pipeline.

The headline bench pays a ~120 s XLA warmup compile before the FIRST
staged verify at a fresh bucket shape (BENCH_r05), and before this
module the node had no defense: the verification scheduler fuses
cross-caller traffic onto the bucket ladder, but the first flush onto a
*cold* rung blocked a gossip-hot thread on a multi-minute compile while
queues backed up. Serving stacks solve this with ahead-of-time
compilation and shape-aware routing — the same pattern that makes
fixed-function pipelines viable on AI ASICs ("Enabling AI ASICs for
Zero Knowledge Proof", PAPERS.md) and that amortizes batch-verification
setup cost in committee-based consensus (arxiv 2302.00418). This module
is that layer:

* **AOT warmup** — a bounded background worker walks the bucket ladder
  under the active ``fp_impl`` in priority order at client startup and
  warms the staged programs off the hot path
  (:func:`~lighthouse_tpu.compile_service.lowering.warm_staged`: the
  REAL module-level jitted stage callables, dispatched through
  ``bls._run_stage`` so every cache and counter sees exactly what
  traffic will see), maintaining a thread-safe warm-shape registry.
* **Warm-shape routing** — :meth:`CompileService.route` answers "can
  rung (B, K, M) dispatch without compiling?": ``warm`` (exact bucket
  compiled), ``padded`` (a larger warm rung covers it — pad up), or
  ``shed`` (nothing warm — the scheduler serves the flush via the
  counted synchronous CPU-native fallback while the cold rung compiles
  in the background). A cold rung never stalls a flush.
* **Persistent executable caching** — the JAX persistent compilation
  cache (in ``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``)
  plus a manifest (see :mod:`.cache`) make a restarted node's
  warmup walk hit disk instead of XLA: zero fresh staged compiles on
  warm start, prebaked by ``tools/warmup.py``.

The module imports no jax at import time (the metrics lint imports it
on a box that must not initialize a backend); everything device-shaped
is imported lazily.
"""

from __future__ import annotations

import os
import random
import threading
import time
from collections import deque
from typing import Callable, Iterable, Optional, Sequence, Tuple

from ..utils import (
    fault_injection,
    flight_recorder,
    metrics,
    pipeline_profiler,
    tracing,
    transfer_ledger,
)
from ..verification_service import planner as _planner
from ..verification_service import round_up_bucket
from . import cache as _cache

Rung = Tuple[int, int, int]  # (B, K, M) padded bucket shape

# Ladder walk order (priority): the gossip-aggregate headline bucket
# first (the 120 s problem in BENCH_r05), then the flush planner's
# kind-homogeneous sub-batch shapes (ISSUE 6: a planned split of the
# 48-set headline flush lands unaggregated/sync-message sets on K=1
# rungs and committee sets on small-B K=16 rungs), the intermediate
# B rungs (48/96/192) the bin-packer targets for the observed traffic
# shapes, then the scheduler's large fused bucket and descending rungs
# for trickle/single-set traffic. K=16/M=8 are the bench headline pads
# (committee sets pad K up; the message-dedup plane rarely exceeds 8
# uniques per flush). The BULK rungs (ISSUE 15) close the ladder at
# LOWEST priority: B=512/256 is where the bulk QoS class drains
# (bulk_flush_sets chunks — DP_SCALING.json measures the best sets/s
# at B=256/512, exactly where the committee cost model's batching
# gains peak) — gossip's headline rungs must all be warm before the
# AOT walk spends minutes on backfill's. Their geometry is the REAL
# wired bulk callers' (chain-segment import + checkpoint backfill =
# proposal signatures: K=1, one DISTINCT message per set, so M pads
# to B — an M=8 rung could never cover a drain whose unique-message
# count scales with its set count); committee-carrying bulk ingest
# (slasher-style, K>1) re-bins onto whatever warm coverage exists or
# sheds to the fallback until an operator adds its rung via
# LIGHTHOUSE_TPU_COMPILE_RUNGS.
DEFAULT_RUNGS: Tuple[Rung, ...] = (
    (64, 16, 8),
    (48, 16, 8),
    (32, 1, 8),
    (16, 16, 8),
    (64, 1, 8),
    (256, 16, 8),
    (96, 16, 8),
    (192, 16, 8),
    (4, 16, 8),
    (1, 16, 8),
    (512, 1, 512),
    (256, 1, 256),
)

# device MSM ladder (ISSUE 16): padded point counts N the G1 windowed
# MSM / G2 point-sum staged programs are warmed at. These programs are
# keyed on their OWN rung (the point axis), NOT on (B, K, M) — an MSM
# dispatch can never perturb the staged-verify ladder's warm shapes.
# 512 covers a full mainnet committee; the smaller rungs are the
# operation_pool's greedy-merge and sync-contribution batch sizes.
# Warming is OFF unless a caller opts in (ClientConfig.device_msm ->
# set_msm_warm_enabled): nodes not running the device aggregation path
# must not spend AOT minutes on programs they never dispatch.
MSM_RUNGS: Tuple[int, ...] = (64, 128, 256, 512)

_msm_warm_enabled = False


def set_msm_warm_enabled(on: bool) -> None:
    """Opt the AOT walk into warming the MSM ladder alongside the first
    staged rung (per fp-impl x device). Process-global because the
    service is constructed before the client config is applied."""
    global _msm_warm_enabled
    _msm_warm_enabled = bool(on)


def msm_warm_enabled() -> bool:
    return _msm_warm_enabled


_ENV_ENABLED = "LIGHTHOUSE_TPU_COMPILE_SERVICE"
_ENV_RUNGS = "LIGHTHOUSE_TPU_COMPILE_RUNGS"
# compile retry (ISSUE 13): a compile_failed rung re-queues with
# bounded exponential backoff + jitter instead of dying — a transient
# XLA error must not leave a rung permanently cold — capped at
# a per-rung attempt budget so a deterministic failure cannot spin
_ENV_RETRY_MAX = "LIGHTHOUSE_TPU_COMPILE_RETRY_MAX"
_ENV_RETRY_BASE = "LIGHTHOUSE_TPU_COMPILE_RETRY_BASE_S"
_ENV_RETRY_CAP = "LIGHTHOUSE_TPU_COMPILE_RETRY_MAX_S"

DEFAULT_RETRY_MAX_ATTEMPTS = 3
DEFAULT_RETRY_BASE_S = 1.0
DEFAULT_RETRY_MAX_S = 60.0


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default

_COMPILE_BUCKETS = (
    0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 30.0, 60.0, 120.0, 300.0, 600.0, 1200.0,
)

_IN_FLIGHT = metrics.gauge(
    "compile_service_compiles_in_flight",
    "staged-program compiles the background worker is running right now",
)
_WARM_RUNGS = metrics.gauge(
    "compile_service_warm_rungs",
    "bucket rungs (B, K, M) x fp_impl x mesh device whose three staged "
    "programs are compiled and routable (single-device nodes only ever "
    "count device 0)",
)
_QUEUE_DEPTH = metrics.gauge(
    "compile_service_queue_depth",
    "bucket rungs queued for background compilation",
)
_COMPILES = metrics.counter_vec(
    "compile_service_compiles_total",
    "per-stage AOT warmup compiles by outcome (ok includes "
    "persistent-cache hits — those are compiles XLA served from disk)",
    ("stage", "outcome"),
)
_COMPILE_SECONDS = metrics.histogram_vec(
    "compile_service_compile_seconds",
    "per-stage AOT warmup wall time per rung (a persistent-cache hit is "
    "the sub-second mode; a fresh XLA compile the minutes mode)",
    ("stage",),
    buckets=_COMPILE_BUCKETS,
)
_COLD_ROUTES = metrics.counter_vec(
    "compile_service_cold_routes_total",
    "scheduler flushes that arrived at a cold bucket: padded = served "
    "on a larger warm rung, shed = served via the synchronous CPU-native "
    "fallback while the rung compiles in the background",
    ("action",),
)
_COMPILE_RETRIES = metrics.counter(
    "compile_service_compile_retries_total",
    "failed rung compiles re-queued with backoff by the retry layer "
    "(ISSUE 13; see the compile_retry journal kind) — retries beyond "
    "the per-rung attempt cap are NOT scheduled and the rung stays "
    "cold until invalidate()/demand re-queues it",
)
_FALLBACK_SECONDS = metrics.histogram(
    "compile_service_fallback_verify_seconds",
    "wall time of one synchronous CPU fallback verify of a shed flush — "
    "the latency a submission pays on the SLO layer's `fallback` "
    "resolution path (verification_scheduler_verdict_latency_seconds"
    "{path=fallback}) while the cold rung compiles behind it",
)
_MEASURED_COST = metrics.gauge(
    "compile_service_measured_cost_seconds_per_set",
    "organically measured WARM serving cost per signature set: "
    "cumulative staged-verify wall / cumulative sets across every rung "
    "note_rung_verified reported, EXCLUDING each rung's first dispatch "
    "(whose wall includes the XLA compile — one cold compile must not "
    "read the capacity dial as saturated for thousands of sets). The "
    "rung-cost feed the capacity/headroom estimator reads when no "
    "per-shard mesh walls exist (ISSUE 14); per-rung splits incl. "
    "first dispatches in status()['rung_costs'] / measured_rung_costs()",
)


def _env_rungs() -> Optional[Tuple[Rung, ...]]:
    """Parse LIGHTHOUSE_TPU_COMPILE_RUNGS=\"B:K:M,B:K:M\"; None when unset
    or malformed (malformed falls back to the default plan, loudly)."""
    raw = os.environ.get(_ENV_RUNGS)
    if not raw:
        return None
    try:
        rungs = tuple(
            tuple(int(p) for p in chunk.split(":"))
            for chunk in raw.split(",")
            if chunk.strip()
        )
        if rungs and all(len(r) == 3 and all(v > 0 for v in r) for r in rungs):
            return rungs  # type: ignore[return-value]
    except ValueError:
        pass
    from ..utils import logging as tlog

    tlog.log("warn", "malformed LIGHTHOUSE_TPU_COMPILE_RUNGS ignored", raw=raw[:80])
    return None


def _geometry(sets) -> Tuple[int, int, int]:
    """(n_sets, max pubkeys/set, unique messages) of a flush — the three
    padded dims the packers derive, computed WITHOUT importing the
    device stack. ONE definition, shared with the flush planner
    (verification_service/planner.py): items are SignatureSet objects or
    (sig, pks, msg) triples; anything else conservatively counts as a
    1-pubkey set with its own message (over-reserving only risks extra
    padding)."""
    return _planner.flush_geometry(sets)


class WarmShapeRegistry:
    """Thread-safe set of (B, K, M, fp_impl, device) rungs whose staged
    programs are compiled — ``device`` is the dp-mesh shard index
    (ISSUE 11; always 0 on a single-device node, and a jitted program
    compiled for one chip is NOT routable on another: each device key
    is its own compile). ``invalidate()`` bumps an epoch so an
    in-flight compile that started before e.g. an ``fp.set_impl``
    switch + ``device.reset_compiled_state()`` cannot resurrect a stale
    rung."""

    def __init__(self):
        self._lock = threading.Lock()
        self._warm: set = set()
        self._epoch = 0

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    def mark_ready(
        self, rung: Rung, impl: str, epoch: int | None = None,
        device: int = 0,
    ) -> bool:
        """Record ``rung`` warm under ``impl`` on mesh ``device``; False
        when the mark is stale (epoch advanced since the compile
        started) or already present."""
        with self._lock:
            if epoch is not None and epoch != self._epoch:
                return False
            key = (*rung, impl, int(device))
            if key in self._warm:
                return False
            self._warm.add(key)
            _WARM_RUNGS.set(len(self._warm))
            return True

    def is_warm(self, rung: Rung, impl: str, device: int = 0) -> bool:
        with self._lock:
            return (*rung, impl, int(device)) in self._warm

    def best_covering(
        self, n_sets: int, k_req: int, m_req: int, impl: str,
        device: int = 0,
    ) -> Optional[Rung]:
        """Cheapest warm rung ON ``device`` that can hold the request
        padded up (B >= n_sets, K >= k_req, M >= m_req). ONE covering
        policy: delegates to ``planner.best_covering_rung`` (min padded
        lanes B*K*M), so the rung the flush planner scores a sub-batch
        at is the rung this routing actually lands it on. None when
        nothing warm covers it."""
        with self._lock:
            warm = [
                (b, k, m)
                for (b, k, m, i, d) in self._warm
                if i == impl and d == int(device)
            ]
        return _planner.best_covering_rung(warm, n_sets, k_req, m_req)

    def warm_rungs(self) -> list:
        """Device-0 view as (B, K, M, fp_impl) tuples — the
        single-device surface every pre-mesh caller and test reads;
        :meth:`warm_rungs_all` carries the device axis."""
        with self._lock:
            return sorted(
                (b, k, m, i) for (b, k, m, i, d) in self._warm if d == 0
            )

    def warm_rungs_all(self) -> list:
        """Every warm (B, K, M, fp_impl, device) key."""
        with self._lock:
            return sorted(self._warm)

    def invalidate(self) -> None:
        with self._lock:
            self._warm.clear()
            self._epoch += 1
            _WARM_RUNGS.set(0)


class CompileService:
    """Background AOT compiler + warm-shape router for the staged device
    BLS pipeline (see module docstring). ``compile_rung_fn`` and
    ``fallback_verify_fn`` are injectable for tests; the defaults are
    :func:`lowering.warm_staged` and a CPU-native (falling back to
    CPU-oracle) ``verify_signature_sets``."""

    def __init__(
        self,
        rungs: Optional[Iterable[Rung]] = None,
        compile_rung_fn: Optional[Callable[[int, int, int], dict]] = None,
        fallback_verify_fn: Optional[Callable[[list], bool]] = None,
    ):
        self.plan: Tuple[Rung, ...] = tuple(
            tuple(r) for r in (rungs or _env_rungs() or DEFAULT_RUNGS)
        )
        self.cache_dir: Optional[str] = None
        self.cache_status: dict = {"enabled": False, "dir": None, "reason": "not started"}
        self.manifest: Optional[_cache.Manifest] = None
        self._compile_rung_fn = compile_rung_fn
        self._fallback_fn = fallback_verify_fn
        self._fallback_backend = None
        self.registry = WarmShapeRegistry()
        self._cv = threading.Condition()
        # work items are (rung, device): the mesh ladder (ISSUE 11) —
        # a single-device node only ever queues device 0
        self._queue: deque = deque()
        self._queued: set = set()
        self._in_flight = None  # (rung, device) | None
        self._devices: Tuple[int, ...] = (0,)
        self._stopped = True
        self._thread: Optional[threading.Thread] = None
        self._compiled_total = 0
        self._failed_total = 0
        self._cold_routes = {"padded": 0, "shed": 0}
        # compile retry (ISSUE 13): per-(rung, device) failed-attempt
        # counts and the delayed re-queue the worker promotes when due
        self.retry_max_attempts = max(
            1, _env_int(_ENV_RETRY_MAX, DEFAULT_RETRY_MAX_ATTEMPTS)
        )
        self.retry_base_s = _env_float(_ENV_RETRY_BASE, DEFAULT_RETRY_BASE_S)
        self.retry_max_s = _env_float(_ENV_RETRY_CAP, DEFAULT_RETRY_MAX_S)
        self._attempts: dict = {}   # (rung, device) -> failures so far
        self._retry_at: dict = {}   # (rung, device) -> due monotonic time
        # MSM ladder (ISSUE 16): (fp_impl, device) pairs whose MSM rungs
        # are already warm — the ladder rides the FIRST staged rung
        # compile per pair, not every rung
        self._msm_warmed: set = set()
        self._retries_total = 0
        # rung-cost feed (ISSUE 14): measured verify cost from
        # note_rung_verified — bounded by ladder size x mesh width (the
        # registry only ever sees padded ladder rungs)
        # (rung, device) -> [dispatches, sum_s, sum_sets]
        self._rung_costs: dict = {}
        self._cost_sum_s = 0.0
        self._cost_sum_sets = 0

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "CompileService":
        with self._cv:
            if self._thread is not None and self._thread.is_alive():
                return self
            if self._compile_rung_fn is None:
                # only a service that compiles through XLA keeps a cache
                # (an injected compile fn — stub replays — never imports
                # jax)
                self.cache_status = _cache.enable_persistent_cache()
            # manifest only over a LIVE cache: entries written while the
            # cache is off would claim a warm start that holds no
            # executables
            if self.cache_status["enabled"]:
                self.cache_dir = self.cache_status["dir"]
                self.manifest = _cache.Manifest(self.cache_dir)
            # mesh ladder (ISSUE 11): with a served dp mesh attached the
            # walk is rung x device, HEADLINE RUNGS FIRST — every chip
            # gets the big warm rung before any chip gets the next one,
            # so the dp axis is servable at the headline shape as early
            # as possible. Without a mesh this is the pre-mesh walk.
            self._devices = self._mesh_devices()
            for rung in self.plan:
                for dev in self._devices:
                    self._enqueue_locked((rung, dev), front=False)
            self._stopped = False
            self._thread = threading.Thread(
                target=self._loop, name="compile-service", daemon=True
            )
            self._thread.start()
            # wake any SUPERSEDED worker blocked in _cv.wait() so it can
            # observe it is no longer self._thread and exit
            self._cv.notify_all()
        return self

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=10)
        self._thread = None

    def active(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive() and not self._stopped

    def invalidate(self) -> None:
        """Drop every warm rung (the ``device.reset_compiled_state()``
        hook: jit caches are gone, so the registry must not keep routing
        to shapes that would now recompile) and re-queue the configured
        plan so the background worker re-warms under the new state."""
        self.registry.invalidate()
        with self._cv:
            self._queue.clear()
            self._queued.clear()
            # retry state is per-epoch: the re-queued plan below starts
            # every rung with a fresh failure budget
            self._retry_at.clear()
            self._attempts.clear()
            # the new epoch's jit caches are empty: the MSM ladder must
            # re-warm alongside the re-queued plan
            self._msm_warmed.clear()
            for rung in self.plan:
                for dev in self._devices:
                    # even_in_flight: a rung compiling RIGHT NOW finishes
                    # against the old epoch (its mark_ready is stale), so
                    # it must be queued again or it would stay cold until
                    # traffic demand-pages it
                    self._enqueue_locked(
                        (rung, dev), front=False, even_in_flight=True
                    )
            self._cv.notify_all()

    @staticmethod
    def _mesh_devices() -> Tuple[int, ...]:
        """Shard indices the ladder walks: the attached mesh's full
        shard axis, (0,) without one. Lazy seam read — the mesh module
        is jax-free at import."""
        try:
            from ..crypto.device import mesh as _mesh

            m = _mesh.get_active_mesh()
            if m is not None:
                return tuple(m.all_shards())
        except Exception:
            pass
        return (0,)

    def _device_healthy(self, dev: int) -> bool:
        if dev == 0 and len(self._devices) == 1:
            return True  # single-device node: no mesh to consult
        try:
            from ..crypto.device import mesh as _mesh

            m = _mesh.get_active_mesh()
            # a PROBING shard's rungs are live work: the recovery
            # worker's re-warm (ISSUE 13) queues them before the shard
            # is re-admitted, so they must not be skipped as dead
            return m is None or m.is_healthy(dev) or m.is_probing(dev)
        except Exception:
            return True

    # -- queueing ---------------------------------------------------------

    def _enqueue_locked(
        self, item, front: bool, even_in_flight: bool = False
    ) -> None:
        if item in self._queued:
            # already queued: a demand-paged request (front=True) still
            # PROMOTES it — live traffic's shape must compile next, not
            # wait behind the remaining plan walk
            if front and self._queue and self._queue[0] != item:
                self._queue.remove(item)
                self._queue.appendleft(item)
            return
        if item == self._in_flight and not even_in_flight:
            return
        self._queued.add(item)
        if front:
            self._queue.appendleft(item)
        else:
            self._queue.append(item)
        _QUEUE_DEPTH.set(len(self._queue))
        self._cv.notify()

    def request(self, b: int, k: int, m: int, device: int = 0) -> None:
        """Ask the background worker to compile rung (b, k, m) on mesh
        ``device`` next — demand-paged warming for traffic the
        configured plan missed."""
        with self._cv:
            self._enqueue_locked(
                ((int(b), int(k), int(m)), int(device)), front=True
            )

    # -- routing ----------------------------------------------------------

    @staticmethod
    def _impl() -> str:
        from ..crypto.device import fp

        return fp.get_impl()

    def route(
        self, n_sets: int, k_req: int = 1, m_req: int = 1,
        device: int = 0,
    ) -> dict:
        """Routing decision for a flush of ``n_sets`` sets with up to
        ``k_req`` pubkeys/set and ``m_req`` distinct messages on mesh
        ``device``: ``{"action": warm|padded|shed, "rung": (B,K,M)|None,
        "exact": (B,K,M), "fp_impl": impl, "device": device}``. Pure
        registry read — counting/journaling belongs to
        :meth:`decide_flush`. Warmth is PER DEVICE: a rung compiled for
        one chip does not make another chip's dispatch warm."""
        impl = self._impl()
        exact = (
            round_up_bucket(n_sets),
            round_up_bucket(k_req),
            round_up_bucket(m_req),
        )
        if self.registry.is_warm(exact, impl, device=device):
            return {
                "action": "warm", "rung": exact, "exact": exact,
                "fp_impl": impl, "device": device,
            }
        covering = self.registry.best_covering(
            n_sets, k_req, m_req, impl, device=device
        )
        if covering is not None:
            return {
                "action": "padded", "rung": covering, "exact": exact,
                "fp_impl": impl, "device": device,
            }
        return {
            "action": "shed", "rung": None, "exact": exact,
            "fp_impl": impl, "device": device,
        }

    def decide_flush(
        self, sets, caller: str = "flush",
        geometry: Optional[Tuple[int, int, int]] = None,
        device_index: int = 0,
    ) -> dict:
        """The scheduler-facing entry: route the flush, account cold
        buckets (``compile_service_cold_routes_total``, ``cold_route``
        journal event) and queue the exact rung for background
        compilation so the NEXT flush of this shape runs on device.
        ``geometry`` is the caller's precomputed (n_sets, k_req, m_req)
        — the flush planner already derived it per plan element, so it
        is not re-extracted from the sets here. ``device_index`` is the
        dp shard the sub-batch will dispatch on (ISSUE 11): a rung that
        is warm on another chip but COLD on this one sheds to the
        fallback instead of stalling the shard's flush on a compile."""
        n, k, m = geometry if geometry is not None else _geometry(sets)
        decision = self.route(n, k, m, device=int(device_index))
        if decision["action"] == "padded" and get_active_service() is not self:
            # the pad-up itself happens inside the device backend, which
            # consults the process-global seam (set_service) — a service
            # injected into the scheduler but never registered there
            # cannot deliver it, and claiming "padded" would send the
            # flush into the exact cold-compile stall the route promises
            # to avoid. Downgrade to shed: the fallback never stalls.
            decision = {
                "action": "shed",
                "rung": None,
                "exact": decision["exact"],
                "fp_impl": decision["fp_impl"],
                "device": decision["device"],
            }
        if decision["action"] != "warm":
            action = decision["action"]
            with self._cv:  # flush thread AND verify_now caller threads
                self._cold_routes[action] += 1
            _COLD_ROUTES.with_labels(action).inc()
            eb, ek, em = decision["exact"]
            rung = decision["rung"]
            flight_recorder.record(
                "cold_route",
                action=action,
                caller=caller,
                n_sets=n,
                k_req=k,
                m_req=m,
                exact_b=eb, exact_k=ek, exact_m=em,
                warm_b=None if rung is None else rung[0],
                warm_k=None if rung is None else rung[1],
                warm_m=None if rung is None else rung[2],
                fp_impl=decision["fp_impl"],
                device=decision["device"],
            )
            self.request(eb, ek, em, device=int(device_index))
        return decision

    def warm_rungs_active(self, device: int = 0) -> list:
        """Warm (B, K, M) rungs under the ACTIVE fp engine on mesh
        ``device`` — the rung set the flush planner bin-packs onto (a
        planned sub-batch must land warm or the plan falls back to the
        single rung)."""
        impl = self._impl()
        return [
            (b, k, m)
            for (b, k, m, i, d) in self.registry.warm_rungs_all()
            if i == impl and d == int(device)
        ]

    def warm_rungs_by_shard(self, shards) -> dict:
        """``{shard: [(B, K, M), ...]}`` under the active engine — the
        planner's mesh-aware warm view (ISSUE 11): a shard whose rung
        set is empty plans COLD there and the sub-batch sheds to the
        fallback instead of stalling the flush."""
        impl = self._impl()
        out = {int(s): [] for s in shards}
        for (b, k, m, i, d) in self.registry.warm_rungs_all():
            if i == impl and d in out:
                out[d].append((b, k, m))
        return out

    def pads_for(
        self, n_sets: int, k_req: int, m_req: int, device: int = 0
    ) -> Optional[Rung]:
        """Pad target for the device packers: the warm rung a
        warm/padded route lands on for this mesh device, or None when
        nothing warm covers the request (the packers then use their
        default round-up — the pre-service behavior)."""
        decision = self.route(n_sets, k_req, m_req, device=int(device))
        return decision["rung"]

    # -- fallback ---------------------------------------------------------

    def fallback_verify(self, sets) -> bool:
        """Synchronous CPU verification for shed flushes: CPU-native (the
        C backend) when buildable, the pure-Python oracle otherwise.
        Verdict-identical to the device call by the backend-differential
        invariant the whole test suite pins — including the device
        backend's infinity pre-screens, and exceptions PROPAGATE like the
        direct call's would (the scheduler's bisection delivers them to
        exactly the leaf submission that caused them)."""
        t0 = time.perf_counter()
        try:
            with tracing.span(
                "compile_service.fallback_verify", n_sets=len(sets)
            ), _FALLBACK_SECONDS.time():
                if self._fallback_fn is not None:
                    return bool(self._fallback_fn(list(sets)))
                from ..crypto import bls as _bls

                prepared = []
                for item in sets:
                    if isinstance(item, _bls.SignatureSet):
                        if not item.signing_keys or item.signature.is_infinity():
                            return False
                        if any(pk.point.is_infinity() for pk in item.signing_keys):
                            return False
                        prepared.append(
                            (
                                item.signature,
                                [pk.point for pk in item.signing_keys],
                                item.message,
                            )
                        )
                    else:
                        prepared.append(item)
                return bool(
                    self._fallback_backend_inst().verify_signature_sets(
                        prepared
                    )
                )
        finally:
            # data-movement ledger: a CPU resolution ships ZERO
            # host→device bytes — the zero row keeps byte attribution
            # exactly-once across resolution paths (kind/path from the
            # scheduler's attribution context on this thread). In a
            # finally so a raising verify still journals exactly one
            # row, mirroring the device path's raise behavior
            transfer_ledger.record_cpu(len(sets))
            # pipeline profiler (ISSUE 12): a shed flush resolving on
            # the CPU is exactly the window the device idles for a
            # compile-caused reason — the wall lands as `compile`
            # activity (bubble attribution) and as the current flush
            # record's `fallback` phase
            pipeline_profiler.note_fallback_wall(t0, time.perf_counter())

    def _fallback_backend_inst(self):
        if self._fallback_backend is None:
            from ..crypto import backend as _backend

            try:
                self._fallback_backend = _backend._REGISTRY["cpu-native"]()
            except Exception:  # no C toolchain: the oracle is always there
                self._fallback_backend = _backend.CpuBackend()
        return self._fallback_backend

    # -- warmth notification ---------------------------------------------

    def note_rung_verified(
        self, b: int, k: int, m: int, epoch: int | None = None,
        device: int = 0, seconds: float | None = None,
        n_sets: int | None = None,
    ) -> None:
        """Organic warmth: a staged verify at (b, k, m) just succeeded on
        the dispatch path — on mesh ``device`` — so its three programs
        are compiled there: routable without the background worker ever
        touching the rung. ``epoch`` is the registry epoch the caller
        captured BEFORE dispatching: a verify racing
        ``device.reset_compiled_state()`` must not resurrect a rung
        whose jit caches were just dropped.

        ``seconds``/``n_sets`` (ISSUE 14) is the rung-cost feed: the
        dispatcher reports the verify's full serving wall (pack +
        staged dispatch) and live set count, accumulated per rung and
        mirrored into ``compile_service_measured_cost_seconds_per_set``
        — the cost input the capacity/headroom estimator
        (``utils/timeseries.py``) falls back to when no per-shard mesh
        walls exist. First-sighting walls include the XLA compile, so
        the per-rung record keeps the dispatch count: a cost dominated
        by one compiled dispatch washes out as the rung serves."""
        rung = (int(b), int(k), int(m))
        if seconds is not None and n_sets:
            with self._cv:
                # keyed per (rung, DEVICE): compiles are per chip, so a
                # failover re-verify on a shard where the rung is still
                # cold pays the compile again — its wall must be
                # excluded exactly like device 0's first sighting was
                rec = self._rung_costs.setdefault(
                    (rung, int(device)), [0, 0.0, 0]
                )
                warm = rec[0] > 0
                rec[0] += 1
                rec[1] += float(seconds)
                rec[2] += int(n_sets)
                # the GAUGE excludes each (rung, device)'s FIRST
                # dispatch: its wall includes the XLA compile (~minutes
                # over a few sets), and a cumulative average would read
                # the capacity dial as saturated for thousands of sets
                # after one cold compile. The per-rung record keeps
                # every dispatch (the compile cost is real and
                # reportable); only the serving-cost feed is warm-only.
                if warm:
                    self._cost_sum_s += float(seconds)
                    self._cost_sum_sets += int(n_sets)
                    if self._cost_sum_sets:
                        _MEASURED_COST.set(
                            self._cost_sum_s / self._cost_sum_sets
                        )
        impl = self._impl()
        if self.registry.mark_ready(rung, impl, epoch=epoch, device=device):
            # persisted=False: the compile happened inside the verify,
            # with no before/after cache probe around it — organic warmth
            # is in-process routing knowledge only and never writes
            # manifest entries (the AOT walk and warmup CLI, which DO
            # probe, own the warm-start claims)
            self._record_ready(
                rung, impl, seconds=None, source="organic",
                persisted=False, device=device,
            )

    def measured_rung_costs(self) -> dict:
        """Per-(rung, device) measured serving cost (the ISSUE 14
        rung-cost feed): ``"BxKxM@devD" -> {dispatches, sum_s,
        sum_sets, s_per_set}`` (ALL dispatches, first-sighting compile
        walls included) plus the aggregate warm-only ``s_per_set`` the
        estimator reads via the
        ``compile_service_measured_cost_seconds_per_set`` gauge (each
        (rung, device)'s first dispatch excluded — see the gauge
        help)."""
        with self._cv:
            rungs = {
                "x".join(str(v) for v in rung) + f"@dev{dev}": {
                    "dispatches": n,
                    "sum_s": round(s, 6),
                    "sum_sets": sets,
                    "s_per_set": round(s / sets, 9) if sets else None,
                }
                for (rung, dev), (n, s, sets)
                in sorted(self._rung_costs.items())
            }
            total_s, total_sets = self._cost_sum_s, self._cost_sum_sets
        return {
            "rungs": rungs,
            "s_per_set": (
                round(total_s / total_sets, 9) if total_sets else None
            ),
            "sum_sets": total_sets,
        }

    def _cache_files(self) -> Optional[set]:
        """Executable entries currently in the cache dir (None when no
        live manifest/cache): the before half of the probe that keeps
        the manifest at least as conservative as the cache."""
        if self.manifest is None or not self.cache_dir:
            return None
        return _cache.executable_entries(self.cache_dir)

    def _record_ready(
        self,
        rung: Rung,
        impl: str,
        seconds: float | None,
        source: str,
        persisted: bool = True,
        device: int = 0,
        stage_seconds: Optional[dict] = None,
    ) -> None:
        with self._cv:  # worker thread AND organic-warmth verify threads
            self._compiled_total += 1
        if self.manifest is not None and persisted:
            try:
                env_key = _cache.environment_key(impl)
                self.manifest.add_many(
                    [
                        _cache.manifest_key(
                            env_key, stage, *rung, device=device
                        )
                        for stage in ("stage1", "stage2", "stage3")
                    ],
                    source=source,
                )
            except Exception:
                pass  # manifest is an optimization, never a failure source
        flight_recorder.record(
            "compile_ready",
            b=rung[0], k=rung[1], m=rung[2],
            fp_impl=impl,
            seconds=None if seconds is None else round(seconds, 3),
            source=source,
            persisted=persisted,
            device=device,
            **{
                f"{stage}_s": (stage_seconds or {}).get(stage)
                for stage in ("stage1", "stage2", "stage3")
            },
        )

    # -- background worker ------------------------------------------------

    def _loop(self) -> None:
        # identity check: stop() gives up joining after 10 s (a compile
        # cannot be cancelled) and a subsequent start() spawns a fresh
        # worker — when THIS thread is no longer self._thread it has been
        # superseded and must exit instead of double-draining the queue
        me = threading.current_thread()
        while True:
            with self._cv:
                while True:
                    if self._stopped or self._thread is not me:
                        return
                    self._promote_due_retries_locked()
                    if self._queue:
                        break
                    # sleep until the earliest pending retry is due (or
                    # indefinitely when none is scheduled)
                    wait = None
                    if self._retry_at:
                        wait = max(
                            0.01,
                            min(self._retry_at.values()) - time.monotonic(),
                        )
                    self._cv.wait(wait)
                rung = self._queue.popleft()
                self._queued.discard(rung)
                self._in_flight = rung
                _QUEUE_DEPTH.set(len(self._queue))
            try:
                self._compile_rung(rung)
            finally:
                with self._cv:
                    # a superseding worker may already be mid-compile on
                    # its own rung: only clear OUR marker (and the gauge —
                    # a superseded worker's cleanup must not zero it under
                    # the replacement's active compile)
                    if self._in_flight == rung:
                        self._in_flight = None
                        _IN_FLIGHT.set(0)

    def _promote_due_retries_locked(self) -> None:
        """Move due retry items back onto the work queue (called under
        the cv by the worker loop)."""
        if not self._retry_at:
            return
        now = time.monotonic()
        due = [it for it, t in self._retry_at.items() if t <= now]
        for it in due:
            del self._retry_at[it]
            if it not in self._queued and it != self._in_flight:
                self._queued.add(it)
                self._queue.append(it)
        if due:
            _QUEUE_DEPTH.set(len(self._queue))

    def _schedule_retry(self, rung: Rung, dev: int, impl: str,
                        error: BaseException) -> None:
        """One rung compile failed: re-queue it with bounded backoff +
        jitter unless its per-rung attempt budget is spent (the
        monitoring.py retry shape — a deterministic failure must not
        spin, a transient one must not leave the rung cold forever)."""
        key = (rung, int(dev))
        with self._cv:
            attempts = self._attempts.get(key, 0) + 1
            self._attempts[key] = attempts
            if attempts >= self.retry_max_attempts:
                return  # budget spent: the rung stays cold (journaled)
            if key in self._queued or key in self._retry_at:
                return
            delay = min(
                self.retry_max_s,
                self.retry_base_s * (2.0 ** (attempts - 1)),
            ) * random.uniform(0.5, 1.0)
            self._retry_at[key] = time.monotonic() + delay
            self._retries_total += 1
            self._cv.notify_all()
        _COMPILE_RETRIES.inc()
        b, k, m = rung
        flight_recorder.record(
            "compile_retry",
            b=b, k=k, m=m, fp_impl=impl, device=dev,
            attempt=attempts,
            max_attempts=self.retry_max_attempts,
            delay_s=round(delay, 3),
            error=repr(error)[:200],
        )

    def _compile_rung(self, item) -> None:
        # item is ((B, K, M), device); a bare (B, K, M) means device 0
        # (direct callers/tests that predate the mesh ladder)
        if len(item) == 2 and isinstance(item[0], tuple):
            rung, dev = item
        else:
            rung, dev = tuple(item), 0
        impl = self._impl()
        if self.registry.is_warm(rung, impl, device=dev):
            return  # warmed organically while queued
        if not self._device_healthy(dev):
            return  # a lost shard's rungs are dead weight, not work
        epoch = self.registry.epoch
        b, k, m = rung
        flight_recorder.record(
            "compile_started", b=b, k=k, m=m, fp_impl=impl, source="aot",
            device=dev,
        )
        _IN_FLIGHT.set(1)
        files_before = self._cache_files()
        t0 = time.perf_counter()
        try:
            with tracing.span(
                "compile_service.compile", b=b, k=k, m=m, fp_impl=impl,
                device=dev,
            ):
                # chaos seam (ISSUE 13): an armed `compile` fault point
                # raises here and exercises the retry layer exactly
                # like a real XLA failure would
                fault_injection.fire("compile")
                if self._compile_rung_fn is not None:
                    stages = self._compile_rung_fn(b, k, m)
                else:
                    from . import lowering

                    stages = lowering.warm_staged(b, k, m, shard=dev)
        except Exception as e:  # a failed compile must not kill the worker
            with self._cv:
                self._failed_total += 1
            # stage-attributed accounting: stages that DID compile before
            # the failure count ok (with their durations); only the stage
            # that raised counts error. A non-staged exception (injected
            # compile fns, import failures) attributes all three.
            partial = getattr(e, "partial", None) or {}
            failed_stage = getattr(e, "stage", None)
            for stage, rec in partial.items():
                _COMPILES.with_labels(stage, "ok").inc()
                _COMPILE_SECONDS.with_labels(stage).observe(
                    float(rec.get("seconds", 0.0))
                )
            failed = (
                (failed_stage,)
                if failed_stage is not None
                else tuple(
                    s for s in ("stage1", "stage2", "stage3")
                    if s not in partial
                )
            )
            for stage in failed:
                _COMPILES.with_labels(stage, "error").inc()
            flight_recorder.record(
                "compile_failed", b=b, k=k, m=m, fp_impl=impl,
                error=repr(e)[:200], device=dev,
                attempt=self._attempts.get((rung, dev), 0) + 1,
            )
            from ..utils import logging as tlog

            tlog.log(
                "warn", "compile service rung failed",
                b=b, k=k, m=m, fp_impl=impl, device=dev,
                error=repr(e)[:120],
            )
            # retry with backoff (ISSUE 13): the rung re-queues instead
            # of dying, up to the per-rung attempt cap
            self._schedule_retry(rung, dev, impl, e)
            return
        seconds = time.perf_counter() - t0
        # a success retires the rung's failure budget: the next
        # transient failure (after an invalidate) starts fresh
        with self._cv:
            self._attempts.pop((rung, dev), None)
        for stage, rec in (stages or {}).items():
            _COMPILES.with_labels(stage, "ok").inc()
            _COMPILE_SECONDS.with_labels(stage).observe(
                float(rec.get("seconds", 0.0))
            )
        # gathered variant (ISSUE 10): with a device key table attached,
        # this rung's traffic dispatches the "gather" program ahead of
        # stage 2 — warm it alongside so the first static batch at the
        # rung pays zero fresh compiles. Sub-second; a failure degrades
        # the gathered variant only (the raw rung above is already warm)
        # and must not fail the rung.
        if self._compile_rung_fn is None:
            try:
                from ..crypto.device import key_table as _kt

                tbl = _kt.get_active_table()
                if tbl is not None:
                    from . import lowering

                    # the replicated key table's gather is warmed per
                    # device against THAT device's replica (ISSUE 11)
                    grec = lowering.warm_gather(b, k, tbl, shard=dev)
                    _COMPILES.with_labels("gather", "ok").inc()
                    _COMPILE_SECONDS.with_labels("gather").observe(
                        float(grec.get("seconds", 0.0))
                    )
            except Exception:
                _COMPILES.with_labels("gather", "error").inc()
        # MSM ladder (ISSUE 16): when the node opted into device
        # aggregation, warm the windowed-MSM / G2-sum programs alongside
        # staged rung compiles — ONE cold MSM rung per staged compile,
        # smallest first, so the background chunk stays bounded (a full
        # 4-rung interpret-mode warm monopolizes the worker — and the
        # GIL — for minutes, starving health serving and shutdown). They
        # are keyed on their own point-count rung, so this never disturbs
        # the staged shapes above; a failure degrades the device-MSM path
        # only (the operation_pool falls back to host sums, and a cold
        # MSM rung compiles on first use) and must not fail the rung.
        if self._compile_rung_fn is None and msm_warm_enabled():
            for n in MSM_RUNGS:
                mkey = (impl, dev, n)
                if mkey in self._msm_warmed or self._stopped:
                    continue
                from . import lowering

                try:
                    mrec = lowering.warm_msm(n, shard=dev)
                    _COMPILES.with_labels("msm", "ok").inc()
                    _COMPILE_SECONDS.with_labels("msm").observe(
                        float(mrec.get("seconds", 0.0))
                    )
                    self._msm_warmed.add(mkey)
                except Exception:
                    _COMPILES.with_labels("msm", "error").inc()
                break
        # manifest honesty: a FRESH compile that left no new executable
        # in the cache dir must not add manifest entries — the manifest
        # stays at least as conservative as the cache
        persisted = _cache.persisted_after(
            self.cache_dir,
            files_before,
            any(rec.get("fresh") for rec in (stages or {}).values()),
        )
        if self.registry.mark_ready(rung, impl, epoch=epoch, device=dev):
            self._record_ready(
                rung, impl, seconds=seconds, source="aot",
                persisted=persisted, device=dev,
                stage_seconds={
                    stage: round(float(rec.get("seconds", 0.0)), 3)
                    for stage, rec in (stages or {}).items()
                },
            )

    # -- introspection ----------------------------------------------------

    def status(self) -> dict:
        """One document for /lighthouse/health: warm surface, queue,
        cold-route pressure and the persistent-cache state."""
        with self._cv:
            queue = list(self._queue)
            in_flight = self._in_flight
            compiled_total = self._compiled_total
            failed_total = self._failed_total
            cold_routes = dict(self._cold_routes)
            devices = self._devices
            now = time.monotonic()
            retry_pending = [
                [*rung, dev, round(max(0.0, due - now), 2)]
                for (rung, dev), due in sorted(self._retry_at.items())
            ]
            retries_total = self._retries_total
        prebaked = []
        if self.manifest is not None:
            try:
                prebaked = self.manifest.prebaked_rungs(
                    _cache.environment_key(self._impl())
                )
            except Exception:
                prebaked = []
        multi = len(devices) > 1

        def _item(it):
            # single-device nodes keep the pre-mesh [B, K, M] rendering;
            # a mesh walk appends the device so operators can see WHICH
            # chip a queued compile is for
            (b, k, m), dev = it
            return [b, k, m, dev] if multi else [b, k, m]

        doc = {
            "running": self.active(),
            "plan": [list(r) for r in self.plan],
            "warm_rungs": [list(r) for r in self.registry.warm_rungs()],
            "queue": [_item(it) for it in queue],
            "in_flight": None if in_flight is None else _item(in_flight),
            "compiled_total": compiled_total,
            "failed_total": failed_total,
            "cold_routes": cold_routes,
            "retry": {
                "max_attempts": self.retry_max_attempts,
                "base_s": self.retry_base_s,
                "retries_total": retries_total,
                "pending": retry_pending,
            },
            "cache": {**self.cache_status, "prebaked_rungs": [list(r) for r in prebaked]},
            # the ISSUE 14 rung-cost feed the capacity estimator reads
            "rung_costs": self.measured_rung_costs(),
        }
        if multi:
            doc["mesh_devices"] = list(devices)
            doc["warm_rungs_by_device"] = [
                list(r) for r in self.registry.warm_rungs_all()
            ]
        return doc


# ---------------------------------------------------------------------------
# Process-global service (the seam bls.TpuBackend and
# device.reset_compiled_state reach without plumbing a handle through
# every caller; the client builder owns the lifecycle).
# ---------------------------------------------------------------------------

_service_lock = threading.Lock()
_service: Optional[CompileService] = None


def set_service(svc: Optional[CompileService]) -> None:
    global _service
    with _service_lock:
        _service = svc


def clear_service(svc: Optional[CompileService] = None) -> None:
    """Detach the global service (only if it still IS ``svc`` when one
    is given — a racing rebuild must not lose its fresh service)."""
    global _service
    with _service_lock:
        if svc is None or _service is svc:
            _service = None


def get_service() -> Optional[CompileService]:
    return _service


def get_active_service() -> Optional[CompileService]:
    svc = _service
    if svc is not None and svc.active():
        return svc
    return None


def invalidate_registry() -> None:
    """``device.reset_compiled_state()`` hook: invalidate the global
    service's warm-shape registry (no-op without one)."""
    svc = _service
    if svc is not None:
        svc.invalidate()


def env_enabled() -> bool:
    return os.environ.get(_ENV_ENABLED, "1") not in ("", "0")
