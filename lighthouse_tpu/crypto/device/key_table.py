"""Device-resident validator pubkey table (ISSUE 10, ROADMAP item 2).

PR 8's data-movement ledger measured the claim this module acts on: G1
pubkeys are 87–94% of all host→device bytes at committee rungs
(COST_MODEL.md bytes-per-set table) and ``bls_device_pubkey_reupload_
ratio`` sits above 0.9 on gossip steady state — every verify re-packs
and re-ships the same ~known validators. The FPGA verification-engine
paper (PAPERS.md, arxiv 2112.02229) keeps precomputed keys resident
next to the verifier core; this is that pattern for the JAX device
backend:

* **One device array, index-keyed** — limb-packed G1 affine rows
  (``int32[cap, 2, NL]``, the exact layout ``curve.pack_g1`` produces
  and ``_stage2_fn`` consumes) uploaded ONCE from the host
  :class:`~lighthouse_tpu.beacon_chain.pubkey_cache.ValidatorPubkeyCache`
  and delta-updated when ``import_new_pubkeys`` admits deposits. Row
  index == validator index, append-only (exits leave their rows
  resident — an exited validator's historical signatures still verify).
  Uploads are CHUNKED (``upload_chunk_rows``) so a 1M-validator table
  never needs one giant host buffer in flight; capacity grows on a
  coarse ladder so the gather program's compile is keyed on a handful
  of shapes, and growth copies the old rows DEVICE-side (no re-upload).
* **Identity pinned to the host cache** — the table resolves a packed
  set's pubkey POINTS through an ``id(point) -> index`` map built only
  from the cache's own immortal point objects (the cache list is
  append-only and the table holds the cache alive, so a hit proves the
  argument IS that exact object). A set built from any other
  state/cache — VC tests, library callers, pre-admission gossip — can
  never silently verify against the wrong key: it misses the map and
  falls back to the raw limb-plane pack.
* **Epoch-stable aggregate-pubkey sums** — committee sets whose index
  tuple repeats (sync-committee periods, identical attestation
  aggregates; the committee cost model arxiv 2302.00418 makes these
  epoch-stable) collapse to a SINGLE table row holding the host-summed
  aggregate point, so a K-wide committee set ships one index and pays
  one K=1 gather lane. Sums are inserted on the SECOND sighting of a
  tuple (``agg_min_repeats``) so one-shot participation subsets never
  pay the host point-add cost. The region is EPOCH-TAGGED (ISSUE 19):
  each entry carries the epoch it serves, entries are retained for two
  epochs (committees reshuffle each epoch but late attestations for
  the prior one still arrive) and evicted per-epoch onto a slot
  free-list as the chain clock advances — the wholesale
  reset-when-full recycle survives only as the last resort when the
  region fills inside a single epoch. :meth:`insert_precomputed` is
  the duty-lookahead entry (``duty_lookahead/``): a committee sum
  computed off the hot path, inserted for a FUTURE epoch, bypassing
  ``agg_min_repeats`` so the committee's first sighting already ships
  K=1 — the reactive path's admission rules are untouched.

The verdict is IDENTICAL by construction: the gathered rows are the
same limb encodings the raw packer ships, and an aggregate row is the
same group element the device's masked K-axis sum produces (a sum that
degenerates to infinity is never cached — it keeps failing through the
device's ``agg_inf_bad`` screen like the raw path).

jax-free at import (the flush planner and the metrics lint import this
module on boxes that must not initialize a backend); every device
operation imports jax lazily. The process-global seam
(:func:`set_table` / :func:`get_active_table`) mirrors the compile
service's: the client builder owns the lifecycle, ``TpuBackend`` and
the flush planner reach the table without plumbing a handle through
every caller.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import random
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...utils import (
    fault_injection,
    flight_recorder,
    metrics,
    slot_clock,
    slot_ledger,
    tracing,
)

# limbs per field element; pinned == fp.NL by test (this module must not
# import the device fp module, which pulls jax)
NL = 32
G1_ROW_SHAPE = (2, NL)          # affine (x, y) limb rows
G1_ROW_BYTES = 2 * NL * 4       # int32

# Validator-region capacity ladder: the gather program's compile is
# keyed on the table array shape, so capacity moves in coarse steps —
# log-many shapes between genesis and a 1M-validator registry.
CAPACITY_LADDER = (1024, 4096, 16384, 65536, 262144, 1048576)

_ENV_ENABLED = "LIGHTHOUSE_TPU_KEY_TABLE"
_ENV_MAX_AGG = "LIGHTHOUSE_TPU_KEY_TABLE_MAX_AGG"
_ENV_CHUNK = "LIGHTHOUSE_TPU_KEY_TABLE_CHUNK"
# re-sync retry (ISSUE 13): a failed admission-listener delta schedules
# a full-sync retry with capped exponential backoff + jitter instead of
# degrading to raw packs forever (sync always catches the mirror up to
# the whole host cache, so one retry covers any number of missed deltas)
_ENV_RESYNC_BASE = "LIGHTHOUSE_TPU_KEY_TABLE_RESYNC_BASE_S"
_ENV_RESYNC_MAX = "LIGHTHOUSE_TPU_KEY_TABLE_RESYNC_MAX_S"

DEFAULT_MAX_AGGREGATES = 4096
DEFAULT_UPLOAD_CHUNK_ROWS = 65536
DEFAULT_AGG_MIN_REPEATS = 2
DEFAULT_RESYNC_BASE_S = 1.0
DEFAULT_RESYNC_MAX_S = 60.0
# the repeat-counting sketch is bounded too: when it exceeds this many
# distinct tuples it resets wholesale (it only gates INSERTS; losing it
# costs one extra sighting before a tuple collapses again)
_AGG_SEEN_CAP = 65536


def table_capacity(n: int) -> int:
    """Validator-region capacity for ``n`` resident rows: the smallest
    ladder rung covering it (beyond the ladder: next 1M multiple)."""
    for c in CAPACITY_LADDER:
        if n <= c:
            return c
    top = CAPACITY_LADDER[-1]
    return ((n + top - 1) // top) * top


def env_enabled() -> bool:
    return os.environ.get(_ENV_ENABLED, "1") not in ("", "0")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


class KeyTableError(RuntimeError):
    """Host-cache/device-table identity cannot be maintained (gap,
    shrunken cache, invalid row). Raised BEFORE any device mutation —
    sync is all-or-nothing."""


# ---------------------------------------------------------------------------
# Telemetry (families under the existing bls_device_ prefix; documented
# in docs/OBSERVABILITY.md, linted by tests/test_zgate4_metrics_lint.py)
# ---------------------------------------------------------------------------

_ENTRIES = metrics.gauge_vec(
    "bls_device_key_table_entries",
    "rows resident in the device pubkey table, by region (validators = "
    "index-identical mirror of ValidatorPubkeyCache, append-only; "
    "aggregates = cached epoch-stable aggregate-pubkey sums)",
    ("region",),
)
_DEVICE_BYTES = metrics.gauge(
    "bls_device_key_table_device_bytes",
    "device bytes held by the pubkey table array (validator capacity + "
    "aggregate region, limb-packed G1 rows)",
)
_UPLOAD_BYTES = metrics.counter_vec(
    "bls_device_key_table_upload_bytes_total",
    "host→device bytes uploaded into the key table, by reason (startup "
    "= initial mirror, delta = deposit admissions, aggregate = cached "
    "committee sums). Capacity growth copies device-side and uploads "
    "nothing",
    ("reason",),
)
_SETS = metrics.counter_vec(
    "bls_device_key_table_sets_total",
    "signature sets by pubkey-shipping path: indexed = shipped as table "
    "indices (device gather), collapsed = shipped as ONE cached "
    "aggregate-sum index (K=1), raw = table attached but at least one "
    "key not resident, so the whole batch fell back to the G1 limb "
    "plane. hit ratio = (indexed+collapsed) / all",
    ("path",),
)
_RESYNCS = metrics.counter_vec(
    "bls_device_key_table_resyncs_total",
    "full-sync retries after a failed mirror sync (ISSUE 13): "
    "scheduled = a retry timer armed with backoff, ok = a retry "
    "caught the mirror up, error = a retry failed (and re-scheduled) "
    "— a failed admission delta degrades batches to raw packs only "
    "until the retry lands, never forever",
    ("outcome",),
)
_AGG_EVENTS = metrics.counter_vec(
    "bls_device_key_table_agg_events_total",
    "aggregate-sum cache LOOKUP events: hit (cached tuple found — warm "
    "routing may still ship it un-collapsed; sets_total{collapsed} is "
    "the shipping truth), miss (tuple not cached), insert (host sum "
    "computed + row uploaded), precomputed (duty-lookahead pre-insert, "
    "ISSUE 19), evict (entry dropped by two-epoch retention, slot "
    "freed), reset (region recycled wholesale — the same-epoch-full "
    "last resort)",
    ("event",),
)
_RESOLVE_SECONDS = metrics.histogram_vec(
    "bls_device_key_table_resolve_seconds",
    "resolve_sets wall time by phase: lookup (per-key index "
    "resolution), aggregates (aggregate-cache consult, seen counts and "
    "slot-ledger sightings), agg_sum (host EC sums + pack_g1 of insert "
    "candidates), agg_upload (one aggregate row's device writes), total "
    "(the whole call, lock waits included). Each phase is also the "
    "trace span key_table.<phase> (the whole call: key_table.resolve)",
    ("phase",),
)
RESOLVE_PHASES = ("lookup", "aggregates", "agg_sum", "agg_upload")
_RESOLVE_CHILD = {
    p: _RESOLVE_SECONDS.with_labels(p) for p in RESOLVE_PHASES + ("total",)
}


@contextlib.contextmanager
def _phase(name: str):
    """One phase of :meth:`DeviceKeyTable.resolve_sets`: a trace span
    and an observation of ``bls_device_key_table_resolve_seconds``."""
    t0 = time.perf_counter()
    with tracing.span(f"key_table.{name}"):
        yield
    _RESOLVE_CHILD[name].observe(time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# The table
# ---------------------------------------------------------------------------


class DeviceKeyTable:
    """Device mirror of a host pubkey cache (see module docstring).

    ``cache`` needs only a ``pubkeys`` list of ``bls.PublicKey``-shaped
    objects (``.point`` attribute) that is append-only for the table's
    lifetime — the chain's ``ValidatorPubkeyCache`` and the bench's shim
    both qualify. The table holds ``cache`` alive, which is what makes
    the ``id(point)`` identity map sound."""

    def __init__(
        self,
        cache,
        max_aggregates: Optional[int] = None,
        upload_chunk_rows: Optional[int] = None,
        agg_min_repeats: int = DEFAULT_AGG_MIN_REPEATS,
    ):
        self.cache = cache
        if max_aggregates is None:
            try:
                max_aggregates = int(os.environ.get(_ENV_MAX_AGG, ""))
            except ValueError:
                max_aggregates = DEFAULT_MAX_AGGREGATES
        if upload_chunk_rows is None:
            try:
                upload_chunk_rows = int(os.environ.get(_ENV_CHUNK, ""))
            except ValueError:
                upload_chunk_rows = DEFAULT_UPLOAD_CHUNK_ROWS
        self.max_aggregates = max(0, int(max_aggregates))
        self.upload_chunk_rows = max(1, int(upload_chunk_rows))
        self.agg_min_repeats = max(1, int(agg_min_repeats))
        self._lock = threading.Lock()
        # TWO device arrays — REPLICATED per dp-mesh shard (ISSUE 11;
        # dict shard -> array, single key 0 without a mesh): the
        # validator mirror [cap_v, 2, NL] and the small aggregate region
        # [max(1, max_agg), 2, NL]. Separate so an aggregate insert's
        # functional .at.set copies ~1 MB per replica, not the whole
        # (potentially 256 MB) validator table, and so cached sums
        # survive validator-capacity growth (the encoded index cap_v +
        # slot is recomputed against the CURRENT base on every resolve).
        # Replication keeps the all-or-nothing sync contract: one delta
        # admission commits on EVERY replica or none (the new arrays for
        # all shards are fully assembled before any commit).
        self._dev: Dict[int, object] = {}
        self._agg_dev: Dict[int, object] = {}
        self._cap_v = 0                     # validator-region capacity
        self._n = 0                         # validator rows resident
        self._point_ids: Dict[int, int] = {}
        # aggregate-sum region (slots live at index cap_v + slot).
        # Resets are DEFERRED (_agg_reset_pending) to the start of the
        # next resolve_sets call and guarded by a generation counter: a
        # slot handed out earlier in a batch must stay valid until that
        # batch's snapshot is taken — a mid-batch recycle would point an
        # already-encoded index at a different committee's sum.
        self._agg_slots: Dict[bytes, Optional[int]] = {}  # None = never cache
        self._agg_seen: Dict[bytes, int] = {}
        self._agg_next = 0                  # slot high-water mark
        self._agg_resets = 0
        self._agg_gen = 0
        self._agg_reset_pending = False
        # epoch-tagged retention (ISSUE 19): each occupied entry carries
        # the epoch it serves; entries older than two epochs are evicted
        # onto the slot free-list at the chain clock's epoch roll (and
        # on demand when the region fills), replacing the wholesale
        # reset as the steady-state recycler
        self._agg_epochs: Dict[bytes, int] = {}
        self._agg_free: List[int] = []
        self._agg_resident = 0
        self._agg_epoch_seen: Optional[int] = None
        self._agg_evictions = 0
        self._agg_precomputed = 0
        # shadow counters for status() (the health endpoint should not
        # parse the exposition to describe the table)
        self._uploads = {"startup": 0, "delta": 0, "aggregate": 0}
        self._sets = {"indexed": 0, "collapsed": 0, "raw": 0}
        self._agg_hits = 0
        self._agg_inserts = 0
        # re-sync retry state (ISSUE 13): one pending timer at a time,
        # backoff grows with consecutive failures, close() cancels
        self._resync_lock = threading.Lock()
        self._resync_base_s = _env_float(_ENV_RESYNC_BASE, DEFAULT_RESYNC_BASE_S)
        self._resync_max_s = _env_float(_ENV_RESYNC_MAX, DEFAULT_RESYNC_MAX_S)
        self._resync_failures = 0
        self._resync_timer: Optional[threading.Timer] = None
        self._resyncs = {"scheduled": 0, "ok": 0, "error": 0}
        self._closed = False

    # -- mesh replication helpers (ISSUE 11) ------------------------------

    @staticmethod
    def _mesh():
        try:
            from . import mesh as mesh_mod

            return mesh_mod.get_active_mesh()
        except Exception:
            return None

    def _replica_shards(self) -> List[int]:
        """The shard set this table mirrors onto: every mesh shard
        (lost chips included — their replicas are already paid for and
        a restored chip must find its rows), else the single default
        shard 0. Pinned to the FIRST sync's answer so replicas never
        silently change set mid-life."""
        if self._dev:
            return sorted(self._dev)
        mesh = self._mesh()
        if mesh is not None:
            return mesh.all_shards()
        return [0]

    def _device_of(self, shard: int):
        mesh = self._mesh()
        return mesh.device_for(shard) if mesh is not None else None

    def _resolve_shard_locked(self) -> Optional[int]:
        """The replica the CURRENT dispatch thread should gather from:
        the thread-local mesh shard when set (the scheduler's sharded
        sub-batch scope), else the lowest replica. None when that shard
        has no replica — the caller then falls back to the raw pack
        (self-consistent: its planes land on the dispatch device)."""
        try:
            from . import mesh as mesh_mod

            shard = mesh_mod.current_shard()
        except Exception:
            shard = None
        if shard is None:
            return min(self._dev) if self._dev else None
        return shard if shard in self._dev else None

    # -- sync (startup + delta admission) ---------------------------------

    def sync(self, reason: str = "delta") -> int:
        """Mirror host-cache rows [resident, len(cache)) onto the device.
        ALL-OR-NOTHING: rows are validated and packed, and the new device
        array fully assembled, before any table state commits — a gap or
        invalid row raises :class:`KeyTableError` and leaves the table
        exactly as it was. Returns the number of rows added.

        The expensive work — pure-Python limb packing of every new row
        and the host→device upload — runs OUTSIDE the table lock against
        snapshots (same discipline as ``resolve_sets``' EC sums): a
        multi-thousand-validator catch-up delta must not stall every
        verifier thread and the block-import listener behind host
        packing. The commit re-checks the snapshots and retries on the
        (rare: builder + admission listener) concurrent-sync race."""
        # chaos seam (ISSUE 13): an armed `key_table_sync` fault point
        # raises here — before any state is touched, like every real
        # sync failure — and exercises the re-sync retry layer
        fault_injection.fire("key_table_sync")
        shards = self._replica_shards()
        for _attempt in range(16):
            with self._lock:
                n_start = self._n
                cap_start = self._cap_v
                dev_start = dict(self._dev)  # shard -> array snapshot
                pubkeys = list(self.cache.pubkeys)
            n_host = len(pubkeys)
            if n_host < n_start:
                raise KeyTableError(
                    f"host cache shrank to {n_host} rows below the "
                    f"{n_start} resident device rows — the cache contract "
                    f"is append-only"
                )
            if n_host == n_start:
                return 0
            new = pubkeys[n_start:n_host]
            rows, points = self._pack_rows(new, base_index=n_start)
            # build EVERY replica's new array before any commit: the
            # all-or-nothing contract spans the mesh (ISSUE 11) — one
            # delta admission commits on every replica or none. A raise
            # mid-build leaves nothing behind (every write is
            # functional).
            cap_v = table_capacity(n_host)
            new_dev: Dict[int, object] = {}
            grew = False
            for s in shards:
                dev_s, _cap_s, grew_s = self._grown_array(
                    dev_start.get(s), cap_start, n_start, n_host,
                    device=self._device_of(s),
                )
                new_dev[s] = self._write_rows(
                    dev_s, n_start, rows, device=self._device_of(s)
                )
                grew = grew or grew_s
            fresh_agg = None
            if not self._agg_dev:  # first sync only (benign racy read)
                import jax.numpy as jnp

                # max(1, ...): a zero-row array would make the gather's
                # take degenerate; with max_aggregates=0 no aggregate
                # index is ever issued, the row is just dead ballast
                fresh_agg = {}
                for s in shards:
                    dev = self._device_of(s)
                    if dev is not None:
                        import jax

                        with jax.default_device(dev):
                            fresh_agg[s] = jnp.zeros(
                                (max(1, self.max_aggregates),
                                 *G1_ROW_SHAPE), jnp.int32,
                            )
                    else:
                        fresh_agg[s] = jnp.zeros(
                            (max(1, self.max_aggregates), *G1_ROW_SHAPE),
                            jnp.int32,
                        )
            nbytes = int(rows.nbytes) * len(shards)
            with self._lock:
                if self._n != n_start or (
                    shards and self._dev.get(shards[0])
                    is not dev_start.get(shards[0])
                ):
                    continue  # a concurrent sync committed first: redo
                # commit only now, replica dict replaced WHOLESALE (all
                # shards or none). Aggregate rows live in their own
                # arrays and SURVIVE capacity growth — their encoded
                # index (cap_v + slot) is recomputed against the new
                # base on every resolve.
                self._dev = new_dev
                if not self._agg_dev:
                    # fresh_agg is non-None here: _agg_dev only ever
                    # goes empty -> populated, so empty at commit
                    # implies the snapshot read above also saw empty
                    # and built one
                    self._agg_dev = fresh_agg
                self._cap_v = cap_v
                for i, p in enumerate(points):
                    self._point_ids[id(p)] = n_start + i
                added = n_host - n_start
                self._n = n_host
                self._uploads[reason] = (
                    self._uploads.get(reason, 0) + nbytes
                )
                cap_total = sum(
                    int(d.shape[0]) for d in self._dev.values()
                ) + sum(int(a.shape[0]) for a in self._agg_dev.values())
            break
        else:
            raise KeyTableError("sync starved by concurrent syncs")
        _ENTRIES.with_labels("validators").set(self._n)
        _DEVICE_BYTES.set(cap_total * G1_ROW_BYTES)
        _UPLOAD_BYTES.with_labels(reason).inc(nbytes)
        flight_recorder.record(
            "key_table_sync",
            reason=reason,
            added=added,
            resident=self._n,
            capacity=self._cap_v,
            upload_bytes=nbytes,
            replicas=len(shards),
            grew=grew,
        )
        return added

    def _pack_rows(self, new: Sequence, base_index: int):
        """Validate + limb-pack host pubkeys into int32[n, 2, NL] rows.
        Raises before any device state is touched."""
        from . import curve

        points = []
        for off, pk in enumerate(new):
            point = getattr(pk, "point", None)
            if point is None or point.is_infinity():
                raise KeyTableError(
                    f"invalid pubkey at cache index {base_index + off}: "
                    f"{'infinity' if point is not None else 'no point'} — "
                    f"admission must reject it before the device mirror"
                )
            points.append(point)
        rows, inf = curve.pack_g1(points)
        if inf.any():
            raise KeyTableError("infinity row survived packing")
        if rows.shape[1:] != G1_ROW_SHAPE:
            raise KeyTableError(
                f"packed row shape {rows.shape[1:]} != {G1_ROW_SHAPE} — "
                f"fp.NL drifted from key_table.NL"
            )
        return np.ascontiguousarray(rows, np.int32), points

    @staticmethod
    def _on_device(device):
        """``jax.default_device`` scope for one replica's writes (no-op
        when the mesh has no real device object for the shard)."""
        if device is None:
            import contextlib

            return contextlib.nullcontext()
        import jax

        return jax.default_device(device)

    def _grown_array(self, dev_start, cap_start: int, n_start: int,
                     n_host: int, device=None):
        """(device array sized for n_host, cap_v, grew): reuses the
        snapshot array when capacity suffices, else allocates the next
        ladder rung ON ``device`` and copies resident validator rows
        DEVICE-side. Pure function of its snapshots — runs outside the
        lock."""
        import jax.numpy as jnp

        cap_v = table_capacity(n_host)
        if dev_start is not None and cap_v <= cap_start:
            return dev_start, cap_start, False
        with self._on_device(device):
            dev = jnp.zeros((cap_v, *G1_ROW_SHAPE), jnp.int32)
            if dev_start is not None and n_start:
                dev = dev.at[:n_start].set(dev_start[:n_start])
        return dev, cap_v, dev_start is not None

    def _write_rows(self, dev, offset: int, rows: np.ndarray, device=None):
        """Host→device upload of ``rows`` at ``offset`` (onto the
        replica's own device): the transfer is chunked
        (``upload_chunk_rows`` bounds each host→device DMA) but the
        functional table update happens ONCE — each eager ``.at.set``
        copies the whole table array, so a per-chunk update loop would
        pay a full-table device copy per chunk."""
        import jax.numpy as jnp

        with self._on_device(device):
            parts = [
                jnp.asarray(rows[i: i + self.upload_chunk_rows])
                for i in range(0, len(rows), self.upload_chunk_rows)
            ]
            staged = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            return dev.at[offset: offset + len(rows)].set(staged)

    # -- re-sync retry (ISSUE 13) -----------------------------------------

    def sync_or_schedule(self, reason: str = "delta") -> Optional[int]:
        """The admission listener's entry: try the sync; on failure
        schedule a full-sync retry with backoff and return None instead
        of raising into the admission path. The table serves what it
        has meanwhile — non-resident keys fall back to the raw pack,
        verdict-identical, until the retry catches the mirror up."""
        try:
            n = self.sync(reason=reason)
        except Exception as e:
            self._schedule_resync(e)
            return None
        with self._resync_lock:
            self._resync_failures = 0
        return n

    def _schedule_resync(self, error: BaseException) -> None:
        with self._resync_lock:
            if self._closed:
                return
            self._resync_failures += 1
            fails = self._resync_failures
            if self._resync_timer is not None:
                return  # one pending retry at a time; it re-syncs fully
            delay = min(
                self._resync_max_s,
                self._resync_base_s * (2.0 ** (fails - 1)),
            ) * random.uniform(0.5, 1.0)
            t = threading.Timer(delay, self._resync_run)
            t.daemon = True
            self._resync_timer = t
            self._resyncs["scheduled"] += 1
            t.start()
        _RESYNCS.with_labels("scheduled").inc()
        from ...utils import logging as tlog

        tlog.log(
            "warn",
            "key-table sync failed — full-sync retry scheduled",
            failures=fails, delay_s=round(delay, 3),
            error=repr(error)[:120],
        )

    def _resync_run(self) -> None:
        with self._resync_lock:
            self._resync_timer = None
            if self._closed:
                return
        try:
            self.sync(reason="recovery")
        except Exception as e:
            with self._resync_lock:
                self._resyncs["error"] += 1
            _RESYNCS.with_labels("error").inc()
            self._schedule_resync(e)
            return
        with self._resync_lock:
            self._resync_failures = 0
            self._resyncs["ok"] += 1
        _RESYNCS.with_labels("ok").inc()

    def close(self) -> None:
        """Stop the retry machinery (``Client.stop()``): cancel any
        pending re-sync timer and refuse new ones — a stopped client's
        table must not keep syncing in the background."""
        with self._resync_lock:
            self._closed = True
            t = self._resync_timer
            self._resync_timer = None
        if t is not None:
            t.cancel()

    # -- resolution (the static/dynamic packer decision) ------------------

    def index_of_point(self, point) -> Optional[int]:
        """Validator index of ``point`` IF it is the host cache's own
        object (identity, not equality — see module docstring)."""
        return self._point_ids.get(id(point))

    def resolve_sets(self, sets):
        """Resolve prepared ``(sig, [G1Point...], msg)`` triples to table
        indices. Returns ``None`` when ANY pubkey is not table-resident
        (the caller falls back to the raw limb-plane pack — per
        sub-batch, the flush planner keeps mixed flushes split), else
        ``(per_set_index_lists, validator_array, aggregate_array,
        n_collapsed)`` where the two device snapshots are guaranteed to
        contain every returned index. Resolution is TWO-PHASE: every
        set's indices resolve before any aggregate-cache mutation, so a
        batch that falls back raw never pays host sums, row uploads or
        aggregate telemetry for its leading sets.

        Shipping-path accounting (``sets_total{indexed|collapsed}``) is
        the DISPATCHER's job via :meth:`count_shipped` — one commit
        point, once the batch is definitely taking the indexed path.
        Only the ``raw`` fallback is counted here (it is final).

        Timed by phase (``bls_device_key_table_resolve_seconds`` and the
        ``key_table.*`` trace spans)."""
        t0 = time.perf_counter()
        try:
            with tracing.span("key_table.resolve", sets=len(sets)):
                return self._resolve_sets(sets)
        finally:
            _RESOLVE_CHILD["total"].observe(time.perf_counter() - t0)

    def _resolve_sets(self, sets):
        with self._lock:
            if not self._dev:
                return None
            # the replica the CURRENT dispatch shard gathers from
            # (ISSUE 11), resolved FIRST: a shard with no replica falls
            # back raw before any aggregate-cache work (its packed
            # planes then land on its own device consistently), keeping
            # the two-phase no-side-effects-before-fallback discipline
            shard = self._resolve_shard_locked()
            if shard is None:
                n = len(sets)
                self._sets["raw"] += n
                _SETS.with_labels("raw").inc(n)
                return None
            # epoch-tagged retention (ISSUE 19): applied only HERE,
            # before any slot of this batch is handed out, so every
            # slot a batch encodes stays valid until its snapshot below.
            # At an epoch roll, entries older than two epochs move to
            # the free-list; the wholesale reset fires only when the
            # region filled and eviction freed nothing (everything
            # resident is still inside its retention window).
            cur_epoch = slot_clock.get_clock().current_epoch()
            if self._agg_epoch_seen != cur_epoch:
                self._agg_epoch_seen = cur_epoch
                self._evict_stale_locked(cur_epoch, journal=True)
            if self._agg_reset_pending:
                self._agg_reset_pending = False
                if not self._agg_free:
                    if not self._evict_stale_locked(cur_epoch, journal=True):
                        self._reset_aggregates_locked(journal=True)
            resolved: List[List[int]] = []
            with _phase("lookup"):
                for _sig, pks, _msg in sets:
                    idxs = []
                    for p in pks:
                        i = self._point_ids.get(id(p))
                        if i is None:
                            n = len(sets)
                            self._sets["raw"] += n
                            _SETS.with_labels("raw").inc(n)
                            return None
                        idxs.append(i)
                    resolved.append(idxs)
            # the batch is fully resident — NOW consult the aggregate
            # cache: hits take their slot, repeat tuples become insert
            # candidates (sum computed OUTSIDE the lock below). Hits
            # record the RAW slot — encoding against the validator
            # capacity happens in the commit lock, because a concurrent
            # capacity-growing sync() between the two phases moves the
            # region base (slots never move; the base does)
            hits: Dict[int, int] = {}       # set position -> RAW agg slot
            miss_positions: Dict[bytes, List[int]] = {}
            cand_keys: Dict[bytes, list] = {}  # key -> pks, ONE sum per key
            if self.max_aggregates and any(len(x) > 1 for x in resolved):
                with _phase("aggregates"):
                    for j, (idxs, (_sig, pks, _msg)) in enumerate(
                        zip(resolved, sets)
                    ):
                        if len(idxs) <= 1:
                            continue
                        key = self._agg_key(idxs)
                        slot = self._agg_slots.get(key, -1)
                        if slot is None:
                            continue  # known-uncacheable (sum is infinity)
                        if slot >= 0:
                            self._agg_hits += 1
                            _AGG_EVENTS.with_labels("hit").inc()
                            # chain-time: a collapsed K=1 row
                            # served this committee — the numerator of the
                            # per-epoch first-sighting dial
                            slot_ledger.note_committee_sighting("hit")
                            hits[j] = slot
                            continue
                        _AGG_EVENTS.with_labels("miss").inc()
                        # first sighting: the host EC sum territory — the
                        # denominator's other half (first + hits = committee
                        # sightings, conservation-pinned)
                        slot_ledger.note_committee_sighting("first")
                        miss_positions.setdefault(key, []).append(j)
                        if len(self._agg_seen) >= _AGG_SEEN_CAP:
                            self._agg_seen.clear()
                        seen = self._agg_seen.get(key, 0) + 1
                        self._agg_seen[key] = seen
                        if seen >= self.agg_min_repeats:
                            # dedup by key: N repeats of one tuple in one
                            # batch pay ONE host sum, and the slot applies
                            # to every position below
                            cand_keys.setdefault(key, list(pks))
            gen = self._agg_gen
        # host EC summation + packing WITHOUT the lock: a 512-member
        # sync-committee sum is hundreds of pure-Python point adds, and
        # holding the table lock for it would serialize every verifier
        # thread and the admission listener behind host arithmetic
        prepared: List[Tuple[bytes, Optional[np.ndarray]]] = []
        if cand_keys:
            with _phase("agg_sum"):
                prepared = self._sum_candidates(cand_keys)
        collapsed = 0
        with self._lock:
            if self._agg_gen != gen:
                # a reset raced this batch: every slot assigned above may
                # have been recycled — ship K indices (correct, just not
                # collapsed) rather than gather someone else's sum
                hits = {}
            else:
                for key, row in prepared:
                    if row is None:
                        self._agg_slots[key] = None
                        continue
                    slot = self._agg_slots.get(key, -1)
                    if slot is None:
                        continue
                    if slot < 0:
                        if self._agg_free:
                            # slots recycled by per-epoch eviction are
                            # reused before the high-water mark grows
                            slot = self._agg_free.pop()
                        elif self._agg_next < self.max_aggregates:
                            slot = self._agg_next
                            self._agg_next += 1
                        else:
                            # bounded region: recycle at the START of
                            # the next batch (see ctor comment) —
                            # eviction first, wholesale reset only if
                            # nothing is stale
                            self._agg_reset_pending = True
                            continue
                        # the insert copies only the SMALL aggregate
                        # arrays (~max_agg rows each), never the
                        # validator table — and writes EVERY replica
                        # under the same lock, so the mesh's aggregate
                        # regions can never disagree on what a slot
                        # holds. The seen count is KEPT: after a region
                        # reset an evicted hot tuple re-inserts on its
                        # very next sighting
                        with _phase("agg_upload"):
                            for s in list(self._agg_dev):
                                self._agg_dev[s] = self._write_rows(
                                    self._agg_dev[s], slot, row,
                                    device=self._device_of(s),
                                )
                        self._agg_slots[key] = slot
                        self._agg_epochs[key] = cur_epoch
                        self._agg_resident += 1
                        self._agg_inserts += 1
                        # counted PER REPLICA, like sync(): the row
                        # really crossed the boundary once per chip
                        row_bytes = G1_ROW_BYTES * max(
                            1, len(self._agg_dev)
                        )
                        self._uploads["aggregate"] += row_bytes
                        _AGG_EVENTS.with_labels("insert").inc()
                        _UPLOAD_BYTES.with_labels("aggregate").inc(
                            row_bytes
                        )
                        _ENTRIES.with_labels("aggregates").set(
                            self._agg_resident
                        )
                    # slot >= 0 here covers the raced-duplicate-insert
                    # case too: another thread cached the same tuple
                    # between our phases — reuse its row (for EVERY
                    # position of this tuple in the batch)
                    for j in miss_positions.get(key, ()):
                        hits[j] = slot
            # encode against the CURRENT base, inside the same lock the
            # dev/agg snapshots are taken under: a capacity growth
            # between the phases moved the base, and a stale encoding
            # would gather a VALIDATOR row where the aggregate region
            # begins
            for j, slot in hits.items():
                resolved[j] = [self._cap_v + slot]
            collapsed = len(hits)
            # snapshot the phase-1 shard's replica (replica dicts are
            # only ever replaced wholesale, so the key still exists)
            dev = self._dev[shard]
            agg_dev = self._agg_dev.get(shard)
        return resolved, dev, agg_dev, collapsed

    @staticmethod
    def _sum_candidates(cand_keys):
        """Host EC sum and limb packing of each insert candidate:
        ``[(key, row or None)]``, None for a sum at infinity."""
        from . import curve

        prepared: List[Tuple[bytes, Optional[np.ndarray]]] = []
        for key, pks in cand_keys.items():
            agg = pks[0]
            for p in pks[1:]:
                agg = agg + p
            if agg.is_infinity():
                # never cache: the raw path fails this set through the
                # device agg_inf_bad screen, and a cached infinity row
                # would instead trip the backend's infinity pre-screen —
                # same verdict, different screen; keep ONE behavior
                prepared.append((key, None))
            else:
                rows, _inf = curve.pack_g1([agg])
                prepared.append(
                    (key, np.ascontiguousarray(rows, np.int32))
                )
        return prepared

    def covers_sets(self, sets) -> bool:
        """jax-free eligibility predicate for the flush planner: would
        :meth:`resolve_sets` succeed for these sets? Accepts
        ``SignatureSet`` objects or ``(sig, pks, msg)`` triples.
        ``signing_indices`` (threaded by state_transition/signature_sets)
        is a fast pre-filter; the identity map is the ground truth
        either way, so a planner misprediction costs padding, never
        correctness."""
        if self._n == 0:
            return False
        for item in sets:
            keys = getattr(item, "signing_keys", None)
            if keys is None and isinstance(item, (tuple, list)) and len(item) == 3:
                keys = item[1]
            if not keys:
                return False
            idxs = getattr(item, "signing_indices", None)
            if idxs is not None and any(
                not 0 <= int(i) < self._n for i in idxs
            ):
                return False
            for pk in keys:
                point = getattr(pk, "point", pk)
                if id(point) not in self._point_ids:
                    return False
        return True

    # -- aggregate-sum cache ----------------------------------------------

    @staticmethod
    def _agg_key(idxs: Sequence[int]) -> bytes:
        # order-insensitive: the sum is commutative, so two aggregates
        # over the same participant set share one row
        h = hashlib.blake2b(digest_size=16)
        for i in sorted(idxs):
            h.update(int(i).to_bytes(8, "little"))
        return h.digest()

    def insert_precomputed(self, idxs, point, epoch: Optional[int] = None) -> str:
        """Duty-lookahead entry (ISSUE 19): pre-insert the aggregate sum
        ``point`` for validator-index tuple ``idxs``, computed OFF the
        hot path, tagged for ``epoch`` (default: the clock's NEXT epoch
        — the shuffle a lookahead walks is deterministic an epoch
        ahead). Bypasses ``agg_min_repeats`` — a lookahead-sourced
        committee's FIRST sighting already ships K=1 — while leaving the
        reactive path's admission rules untouched. Never forces the
        wholesale reset: when the region is full and per-epoch eviction
        frees nothing, the pre-insert is declined (``"full"``) and the
        reactive path keeps owning the recycle policy.

        Returns an outcome string: ``inserted`` | ``exists`` (already
        cached — the retention tag is extended through the target
        epoch) | ``infinity`` (never cached, marked so the device
        ``agg_inf_bad`` screen keeps owning the edge) | ``never_cache``
        (previously marked infinity) | ``full`` | ``unsynced`` (no
        device region yet) | ``disabled``. The caller journals failures
        (``lookahead_insert_failed``) — this method stays jax-free
        until a row is actually written."""
        idxs = [int(i) for i in idxs]
        if self.max_aggregates <= 0 or len(idxs) <= 1:
            return "disabled"
        key = self._agg_key(idxs)
        if point is None or point.is_infinity():
            with self._lock:
                self._agg_slots[key] = None
            return "infinity"
        from . import curve

        rows, inf = curve.pack_g1([point])
        if inf.any():
            return "infinity"
        row = np.ascontiguousarray(rows, np.int32)
        with self._lock:
            if not self._agg_dev:
                return "unsynced"
            cur_epoch = slot_clock.get_clock().current_epoch()
            tag = (cur_epoch + 1) if epoch is None else int(epoch)
            existing = self._agg_slots.get(key, -1)
            if existing is None:
                return "never_cache"
            if existing >= 0:
                # the reactive path cached it first: keep that row but
                # extend retention through the lookahead's target epoch
                self._agg_epochs[key] = max(
                    self._agg_epochs.get(key, tag), tag
                )
                return "exists"
            if self._agg_free:
                slot = self._agg_free.pop()
            elif self._agg_next < self.max_aggregates:
                slot = self._agg_next
                self._agg_next += 1
            else:
                self._evict_stale_locked(cur_epoch, journal=True)
                if not self._agg_free:
                    return "full"
                slot = self._agg_free.pop()
            for s in list(self._agg_dev):
                self._agg_dev[s] = self._write_rows(
                    self._agg_dev[s], slot, row,
                    device=self._device_of(s),
                )
            self._agg_slots[key] = slot
            self._agg_epochs[key] = tag
            self._agg_resident += 1
            self._agg_precomputed += 1
            row_bytes = G1_ROW_BYTES * max(1, len(self._agg_dev))
            self._uploads["aggregate"] += row_bytes
            resident = self._agg_resident
        _AGG_EVENTS.with_labels("precomputed").inc()
        _UPLOAD_BYTES.with_labels("aggregate").inc(row_bytes)
        _ENTRIES.with_labels("aggregates").set(resident)
        return "inserted"

    def _evict_stale_locked(self, cur_epoch: int, journal: bool) -> int:
        """Two-epoch retention (ISSUE 19): drop every entry whose epoch
        tag is two or more epochs behind ``cur_epoch`` — its committee
        reshuffled away and even straggler attestations for it are past
        — returning the slots to the free-list for reuse. ``_agg_seen``
        survives like the wholesale reset's contract; the generation
        bump tells any batch that already took slots to ship K indices
        instead of a recycled row. Returns entries evicted (0 = nothing
        stale, no generation bump)."""
        stale = [
            k for k, e in self._agg_epochs.items() if e + 2 <= cur_epoch
        ]
        if not stale:
            return 0
        dropped_epochs = sorted({self._agg_epochs[k] for k in stale})
        for k in stale:
            slot = self._agg_slots.pop(k, None)
            del self._agg_epochs[k]
            if slot is not None and slot >= 0:
                self._agg_free.append(slot)
        freed = len(stale)
        self._agg_resident = max(0, self._agg_resident - freed)
        self._agg_evictions += freed
        self._agg_gen += 1
        _AGG_EVENTS.with_labels("evict").inc(freed)
        _ENTRIES.with_labels("aggregates").set(self._agg_resident)
        if journal:
            flight_recorder.record(
                "key_table_reset",
                region="aggregates",
                mode="evict_epochs",
                dropped=freed,
                epochs=",".join(str(e) for e in dropped_epochs),
                retained=self._agg_resident,
                current_epoch=cur_epoch,
            )
        return freed

    def _reset_aggregates_locked(self, journal: bool) -> None:
        """Recycle the bounded aggregate region WHOLESALE — since
        ISSUE 19 only the last resort, when the region filled inside a
        single epoch and per-epoch eviction freed nothing. ``_agg_seen``
        survives (it has its own cap) so an evicted hot tuple re-inserts
        on its next sighting; the generation bump tells any batch that
        already took slots to ship K indices instead of a recycled
        row."""
        had = self._agg_resident
        self._agg_slots.clear()
        self._agg_epochs.clear()
        self._agg_free.clear()
        self._agg_next = 0
        self._agg_resident = 0
        self._agg_resets += 1
        self._agg_gen += 1
        _AGG_EVENTS.with_labels("reset").inc()
        _ENTRIES.with_labels("aggregates").set(0)
        if journal:
            flight_recorder.record(
                "key_table_reset", region="aggregates", mode="wholesale",
                dropped=had,
            )

    # -- accounting helpers ------------------------------------------------

    def count_shipped(self, n_indexed: int, n_collapsed: int) -> None:
        """Commit a dispatched batch's final shipping-path accounting —
        called by the dispatcher once the batch is definitely taking
        the indexed path (resolution alone is not shipping)."""
        with self._lock:
            self._sets["indexed"] += int(n_indexed)
            self._sets["collapsed"] += int(n_collapsed)
        if n_indexed:
            _SETS.with_labels("indexed").inc(int(n_indexed))
        if n_collapsed:
            _SETS.with_labels("collapsed").inc(int(n_collapsed))

    def count_raw(self, n_sets: int) -> None:
        """A batch fell back to the raw plane for a reason resolve_sets
        did not see (e.g. non-Signature raw-mode screen)."""
        with self._lock:
            self._sets["raw"] += int(n_sets)
        _SETS.with_labels("raw").inc(int(n_sets))

    def device_arrays(self, shard: Optional[int] = None):
        """(validator array, aggregate array) snapshot for one replica
        — the pair the gather program dispatches against (indices >=
        the validator array's length address the aggregate region).
        ``shard=None`` resolves the current dispatch shard (falling
        back to the lowest replica); ``(None, None)`` when that shard
        has no replica or the table is empty."""
        with self._lock:
            if not self._dev:
                return None, None
            if shard is None:
                s = self._resolve_shard_locked()
                if s is None:
                    s = min(self._dev)
            else:
                s = int(shard)
                if s not in self._dev:
                    return None, None
            return self._dev[s], self._agg_dev.get(s)

    def __len__(self) -> int:
        return self._n

    # -- introspection -----------------------------------------------------

    def status(self) -> dict:
        """One document for the /lighthouse/health ``key_table`` block."""
        with self._lock:
            sets = dict(self._sets)
            shipped = sets["indexed"] + sets["collapsed"]
            total = shipped + sets["raw"]
            cap_total = sum(
                int(d.shape[0]) for d in self._dev.values()
            ) + sum(int(a.shape[0]) for a in self._agg_dev.values())
            return {
                "replicas": sorted(self._dev),
                "validators_resident": self._n,
                "host_cache_len": len(self.cache.pubkeys),
                "validator_capacity": self._cap_v,
                "aggregates_resident": self._agg_resident,
                "aggregate_capacity": self.max_aggregates,
                "aggregate_resets": self._agg_resets,
                "aggregate_hits": self._agg_hits,
                "aggregate_inserts": self._agg_inserts,
                "aggregate_precomputed": self._agg_precomputed,
                "aggregate_evictions": self._agg_evictions,
                "aggregate_free_slots": len(self._agg_free),
                "aggregate_epochs": sorted(
                    set(self._agg_epochs.values())
                ),
                "device_bytes": cap_total * G1_ROW_BYTES,
                "upload_bytes": dict(self._uploads),
                "sets": sets,
                "hit_ratio": round(shipped / total, 4) if total else None,
                "identity_pinned": self._n <= len(self.cache.pubkeys),
                "resyncs": dict(self._resyncs),
                "resync_failures": self._resync_failures,
                "resync_pending": self._resync_timer is not None,
            }


# ---------------------------------------------------------------------------
# Process-global table (the seam bls.TpuBackend and the flush planner
# reach without plumbing a handle; the client builder owns the lifecycle)
# ---------------------------------------------------------------------------

_table_lock = threading.Lock()
_table: Optional[DeviceKeyTable] = None


def set_table(table: Optional[DeviceKeyTable]) -> None:
    global _table
    with _table_lock:
        _table = table


def clear_table(table: Optional[DeviceKeyTable] = None) -> None:
    """Detach the global table (only if it still IS ``table`` when one
    is given — a racing rebuild must not lose its fresh table)."""
    global _table
    with _table_lock:
        if table is None or _table is table:
            _table = None


def get_table() -> Optional[DeviceKeyTable]:
    return _table


def get_active_table() -> Optional[DeviceKeyTable]:
    """The attached table, when it has resident rows to gather from."""
    t = _table
    if t is not None and len(t):
        return t
    return None
