"""Headroom-driven admission control for the bulk QoS class (ISSUE 15).

The scheduler's bulk class (``batcher.py``) exists so chain-segment
backfill and slasher-style ingest can saturate the device WITHOUT
moving gossip's p99. Queue priority alone is not enough: once demand
crosses serving capacity, every bulk set the scheduler still admits is
a set the deadline class will eventually queue behind. This module is
the valve — it watches the two signals PR 14 built exactly for this
decision and pauses bulk admission while either says the node is out
of slack:

* **capacity headroom** (``utils/timeseries.py``,
  ``deadline_headroom_ratio`` = max(0, 1 − deadline demand/capacity)):
  the headroom the deadline class leaves. Admitted bulk service is
  left out of it (it is in ``capacity_headroom_ratio``): bulk flushes
  only at gossip idle, so a lone range sync that fills the device must
  not read its own service as lost headroom and throttle itself. When
  the live estimate drops below ``floor`` (default 0.10,
  ``LIGHTHOUSE_TPU_SCHED_BULK_HEADROOM_FLOOR``) the node is close
  enough to saturation that bulk must stop feeding the queue. The dial
  is PREDICTIVE — on a saturation ramp it crosses before the first
  deadline-miss burst (pinned by ``tests/test_timeseries_capacity.py``)
  — so the throttle lands before gossip pays, not after. An UNKNOWN
  headroom (sampler disabled, no cost measured yet) is treated as "no
  signal", never as "no headroom": a box without the estimator keeps
  the pre-admission-control behavior instead of banning bulk forever.
* **the SLO burn latch** (``slo.py``, ``latched_kinds()``): a confirmed
  ``slo_burn`` excursion on ANY deadline-class kind — bulk samples
  never reach the burn buckets, so any latch IS a gossip kind — pauses
  bulk immediately. This is the retrospective backstop for whatever the
  estimator did not foresee.

**Hysteresis.** Throttle state resumes only when BOTH signals clear:
the burn latch must have expired (no confirmed alert for a full fast
window) AND headroom must have recovered past ``resume_headroom``
(default 0.20, ``LIGHTHOUSE_TPU_SCHED_BULK_RESUME_HEADROOM``), not just
back above the floor — a dial oscillating around the floor must not
flap the valve once per sample.

**One journal event per excursion.** Entering the throttled state
journals ONE ``bulk_throttle`` flight-recorder event (with the reason,
the headroom reading and the latched kinds); leaving it journals ONE
``bulk_resume`` (with the excursion's duration). A continuing excursion
re-confirms silently — the journal records state TRANSITIONS, the
``verification_scheduler_bulk_throttled`` gauge records state.

**Degradation order** (docs/VERIFICATION_SERVICE.md): losing headroom
sheds bulk FIRST — bulk flushes pause while queued bulk waits; a bulk
queue overflow degrades the submission to its CALLER's thread (the
self-paced pre-scheduler behavior — never to gossip's flush thread);
gossip's deadline class is untouched throughout.

Deliberately **jax-free** (the verification_service import rule) and
dependency-injected: ``headroom_fn`` defaults to the live
``timeseries.last_estimate()`` read but tests drive the controller with
a scripted dial, so every transition is pinned deterministically
(``tests/test_bulk_qos.py``).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from ..utils import flight_recorder, metrics

DEFAULT_HEADROOM_FLOOR = 0.10
DEFAULT_RESUME_HEADROOM = 0.20
# evaluate() is called on every bulk submit and every flush-loop wake;
# the signals only move at sampler cadence, so re-reads are throttled
DEFAULT_MIN_INTERVAL_S = 0.05

_ENV_FLOOR = "LIGHTHOUSE_TPU_SCHED_BULK_HEADROOM_FLOOR"
_ENV_RESUME = "LIGHTHOUSE_TPU_SCHED_BULK_RESUME_HEADROOM"

_env_float = flight_recorder._env_float

_THROTTLED = metrics.gauge(
    "verification_scheduler_bulk_throttled",
    "1 while bulk admission is paused (headroom below the floor or a "
    "gossip slo_burn latch live), 0 while bulk flows — state; the "
    "transitions are journaled as bulk_throttle/bulk_resume events and "
    "counted in verification_scheduler_bulk_throttle_events_total",
)
_THROTTLE_EVENTS = metrics.counter_vec(
    "verification_scheduler_bulk_throttle_events_total",
    "bulk-admission throttle excursions entered, by triggering reason "
    "(headroom = capacity_headroom_ratio below the floor, slo_burn = a "
    "deadline-class burn latch) — one tick per excursion, not per "
    "evaluation; resumes are the bulk_resume journal events",
    ("reason",),
)


def _live_headroom() -> Optional[float]:
    """The default headroom feed: the capacity estimator's latest
    ``deadline_headroom_ratio`` (None when the sampler is off or no
    cost has been measured — 'no signal', never 'no headroom'). Lazy
    import so this module stays cheap and jax-free at import."""
    try:
        from ..utils import timeseries

        est = timeseries.last_estimate()
        if est is None:
            return None
        return est.get("deadline_headroom_ratio")
    except Exception:
        return None


class BulkAdmissionController:
    """The bulk-admission valve (module docstring). ``evaluate()``
    returns True while bulk may flush/admit; the scheduler calls it on
    every bulk submit and every flush-loop wake. ``tracker`` is bound
    by the scheduler to ITS SloTracker when not injected."""

    def __init__(
        self,
        headroom_fn: Optional[Callable[[], Optional[float]]] = None,
        tracker=None,
        floor: float | None = None,
        resume_headroom: float | None = None,
        min_interval_s: float = DEFAULT_MIN_INTERVAL_S,
    ):
        self.headroom_fn = headroom_fn or _live_headroom
        self.tracker = tracker
        self.floor = max(0.0, float(
            floor if floor is not None
            else _env_float(_ENV_FLOOR, DEFAULT_HEADROOM_FLOOR)
        ))
        self.resume_headroom = max(self.floor, float(
            resume_headroom if resume_headroom is not None
            else _env_float(_ENV_RESUME, DEFAULT_RESUME_HEADROOM)
        ))
        self.min_interval_s = max(0.0, float(min_interval_s))
        self._lock = threading.Lock()
        self._throttled = False
        self._reason: Optional[str] = None
        self._since: Optional[float] = None
        self._last_eval = -float("inf")
        self._last_headroom: Optional[float] = None
        self._excursions = 0
        # the process-global gauge is deliberately NOT reset here: a
        # second controller constructed in-process (a replay tool, a
        # test helper, another scheduler) must not wipe a live
        # scheduler's throttle state off /metrics — gauges register at
        # 0 and only TRANSITIONS write it

    # -- the valve ---------------------------------------------------------

    def throttled(self) -> bool:
        with self._lock:
            return self._throttled

    def evaluate(self, now: float | None = None, force: bool = False) -> bool:
        """Re-read the signals and drive the throttle latch; returns
        True when bulk is admitted. Rate-limited internally (the
        signals move at sampler cadence); transitions journal exactly
        once per excursion. ``force`` skips the rate limit — the
        scheduler forces on every bulk ARRIVAL so the first submission
        after a signal collapse journals its ``bulk_throttle`` before
        any of its sets could queue (bulk arrivals are big, self-paced
        chunks; the per-arrival re-read is cheap and the rate limit
        exists for the flush loop's tight wake cadence, not for them).
        Never raises — a broken signal read must not take the flush
        thread down, and reads as 'no signal'."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            if not force and now - self._last_eval < self.min_interval_s:
                return not self._throttled
            self._last_eval = now
        try:
            headroom = self.headroom_fn()
        except Exception:
            headroom = None
        try:
            latched = (
                self.tracker.latched_kinds(now)
                if self.tracker is not None else []
            )
        except Exception:
            latched = []
        fire = resume = None
        with self._lock:
            self._last_headroom = headroom
            if not self._throttled:
                reason = None
                if latched:
                    reason = "slo_burn"
                elif headroom is not None and headroom < self.floor:
                    reason = "headroom"
                if reason is not None:
                    self._throttled = True
                    self._reason = reason
                    self._since = now
                    self._excursions += 1
                    fire = reason
            else:
                # hysteresis: BOTH signals must clear, and headroom must
                # recover past resume_headroom, not just the floor
                if not latched and (
                    headroom is None or headroom >= self.resume_headroom
                ):
                    resume = round(now - (self._since or now), 3)
                    self._throttled = False
                    self._reason = None
                    self._since = None
            admitted = not self._throttled
        if fire is not None:
            _THROTTLED.set(1)
            _THROTTLE_EVENTS.with_labels(fire).inc()
            flight_recorder.record(
                "bulk_throttle",
                reason=fire,
                headroom=headroom,
                floor=self.floor,
                resume_headroom=self.resume_headroom,
                latched_kinds=",".join(latched),
            )
        elif resume is not None:
            _THROTTLED.set(0)
            flight_recorder.record(
                "bulk_resume",
                headroom=headroom,
                resume_headroom=self.resume_headroom,
                throttled_s=resume,
            )
        return admitted

    # -- introspection -----------------------------------------------------

    def status(self) -> dict:
        """The admission block of the scheduler's health document."""
        with self._lock:
            return {
                "throttled": self._throttled,
                "reason": self._reason,
                "throttled_s": (
                    round(time.monotonic() - self._since, 3)
                    if self._since is not None else None
                ),
                "excursions_total": self._excursions,
                "headroom_floor": self.floor,
                "resume_headroom": self.resume_headroom,
                "last_headroom": self._last_headroom,
            }
