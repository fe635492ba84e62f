"""Metrics-hygiene gate (ISSUE 2 satellite): the telemetry surface the
dashboards scrape must stay well-formed as instrumentation accretes.

Checks, against the live process-global registry after importing every
instrumented hot-path module:

1. every registered family name is snake_case under a known subsystem
   prefix (new subsystems add their prefix HERE, consciously);
2. one name = one metric type (the registry enforces it; the gate pins
   the enforcement);
3. ``gather()`` output parses cleanly as Prometheus text format — no
   family, labeled or not, can corrupt the scrape;
4. a DISABLED trace span costs < 1 microsecond per enter/exit on this
   box, so hot-path instrumentation can stay always-on.

Named ``test_zgate4_*`` so it sorts after the functional suite inside
the tier-1 wall-clock window (see tests/conftest.py discipline).
"""

import re
import time

import pytest

from lighthouse_tpu.utils import metrics, tracing

# One prefix per subsystem; adding a family under a new prefix means
# adding it here with a matching entry in docs/OBSERVABILITY.md.
KNOWN_PREFIXES = (
    "attestation_",
    "beacon_block_",
    "beacon_processor_",
    "block_",
    "bls_device_",
    "capacity_",  # timeseries sampler + headroom estimator (ISSUE 14)
    "compile_service_",
    "device_",  # device_memory_bytes (utils/transfer_ledger.py, ISSUE 8)
    "duty_lookahead_",  # duty-lookahead precompute (duty_lookahead/, ISSUE 19)
    "fault_",  # fault-injection layer (utils/fault_injection.py, ISSUE 13)
    "flight_recorder_",
    "head_",
    "http_api_",
    "key_table_",  # epoch first-sighting dial (utils/slot_ledger.py, ISSUE 17)
    "log_",
    "monitoring_",
    "network_",
    "op_pool_",
    "slasher_",
    "slot_",  # chain-time slot ledger (utils/slot_ledger.py, ISSUE 17)
    "store_",
    "sync_",
    "testm_",  # test-only families from tests/test_metrics_depth.py
    "validator_monitor_",
    "vc_",
    "verification_scheduler_",
    "watchtower_",  # anomaly watchtower (utils/watchtower.py, ISSUE 18)
)

_NAME = re.compile(r"[a-z][a-z0-9_]*$")


def _import_instrumented_modules():
    """Every module that registers hot-path families (network/vc modules
    need the absent ``cryptography`` dep, so their families are asserted
    by test_metrics_depth instead)."""
    import lighthouse_tpu.beacon_chain.attestation_verification  # noqa: F401
    import lighthouse_tpu.beacon_chain.block_verification  # noqa: F401
    import lighthouse_tpu.beacon_chain.validator_monitor  # noqa: F401
    import lighthouse_tpu.beacon_processor.processor  # noqa: F401
    import lighthouse_tpu.compile_service.service  # noqa: F401
    import lighthouse_tpu.crypto.device.bls  # noqa: F401
    import lighthouse_tpu.crypto.device.key_table  # noqa: F401
    import lighthouse_tpu.crypto.device.mesh  # noqa: F401
    import lighthouse_tpu.duty_lookahead  # noqa: F401
    import lighthouse_tpu.http_api.server  # noqa: F401
    import lighthouse_tpu.utils.fault_injection  # noqa: F401
    import lighthouse_tpu.utils.flight_recorder  # noqa: F401
    import lighthouse_tpu.utils.logging  # noqa: F401
    import lighthouse_tpu.utils.monitoring  # noqa: F401
    import lighthouse_tpu.utils.slot_ledger  # noqa: F401
    import lighthouse_tpu.utils.timeseries  # noqa: F401
    import lighthouse_tpu.utils.watchtower  # noqa: F401
    import lighthouse_tpu.verification_service.batcher  # noqa: F401


def test_registered_names_snake_case_with_known_prefix():
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    assert reg, "registry must not be empty after imports"
    for name in reg:
        assert _NAME.match(name), f"metric name not snake_case: {name!r}"
        assert name.startswith(KNOWN_PREFIXES), (
            f"metric {name!r} has no known subsystem prefix; add the "
            f"prefix to KNOWN_PREFIXES and document the family in "
            f"docs/OBSERVABILITY.md"
        )


def test_one_name_one_type_enforced():
    _import_instrumented_modules()
    # log_messages_total is a CounterVec (utils/logging.py, ISSUE 3
    # replaced the unlabeled log_lines_total); any re-registration under
    # another type must raise, not silently alias
    with pytest.raises(TypeError):
        metrics.gauge("log_messages_total")
    with pytest.raises(TypeError):
        metrics.histogram_vec("log_messages_total", labelnames=("x",))
    # the replaced name must be GONE: a dashboard scraping the old
    # unlabeled family should find nothing, not a stale twin
    assert metrics.get("log_lines_total") is None
    # and a family is never registered under two types already
    kinds = {}
    for name, m in metrics.registry_snapshot().items():
        assert name not in kinds
        kinds[name] = m.kind
        assert m.kind in ("counter", "gauge", "histogram"), (name, m.kind)


def test_gather_parses_cleanly():
    _import_instrumented_modules()
    out = metrics.gather()
    # the shared grammar (metrics.parse_exposition) raises on any
    # malformed sample line
    samples = metrics.parse_exposition(out)
    assert samples
    seen_help, seen_type = set(), set()
    for line in out.splitlines():
        if line.startswith("# HELP "):
            seen_help.add(line.split(" ", 3)[2])
        elif line.startswith("# TYPE "):
            seen_type.add(line.split(" ", 3)[2])
    # samples only appear under their family's HELP/TYPE headers
    for name, _labels, _value in samples:
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if base.endswith(suffix) and base[: -len(suffix)] in seen_type:
                base = base[: -len(suffix)]
                break
        assert base in seen_type and base in seen_help, name


def test_new_observability_families_registered():
    """ISSUE 3 families exist under their declared types + labels."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "log_messages_total": ("counter", ("level",)),
        "monitoring_push_total": ("counter", ("outcome",)),
        "flight_recorder_events_total": ("counter", ("kind",)),
        "flight_recorder_dumps_total": ("counter", ("trigger",)),
        "validator_monitor_failures_total": ("counter", ("kind", "reason")),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        assert m.labelnames == labels, (name, m.labelnames)


def test_verification_scheduler_families_registered():
    """ISSUE 4 families (verification_service/batcher.py) exist under
    their declared types + labels."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "verification_scheduler_fused_batches_total": ("counter", ("kinds",)),
        "verification_scheduler_submissions_total": (
            "counter", ("kind", "outcome"),
        ),
        "verification_scheduler_sets_total": ("counter", ("kind",)),
        "verification_scheduler_flushes_total": ("counter", ("trigger",)),
        "verification_scheduler_shed_total": ("counter", ("kind",)),
        "verification_scheduler_bypass_total": ("counter", ("kind",)),
        "verification_scheduler_batch_occupancy_ratio": ("gauge", None),
        "verification_scheduler_padding_waste_ratio": ("gauge", None),
        "verification_scheduler_queue_depth": ("gauge", None),
        "verification_scheduler_queue_wait_seconds": ("histogram", None),
        "verification_scheduler_bisections_total": ("counter", None),
        # ISSUE 6: flush-planner families (shape-aware sub-batch plans)
        "verification_scheduler_plans_total": ("counter", ("mode",)),
        "verification_scheduler_plan_subbatches_total": ("counter", ("kind",)),
        "verification_scheduler_plan_lanes_total": ("counter", ("lane",)),
        # ISSUE 7: verdict-latency SLO layer — every resolution path
        # feeds the same end-to-end histogram, and the deadline is an
        # SLO (miss counter), not just a flush trigger
        "verification_scheduler_verdict_latency_seconds": (
            "histogram", ("kind", "path"),
        ),
        "verification_scheduler_deadline_misses_total": (
            "counter", ("kind",),
        ),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family


def test_compile_service_families_registered():
    """ISSUE 5 families (compile_service/service.py) exist under their
    declared types + labels."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "compile_service_compiles_in_flight": ("gauge", None),
        "compile_service_warm_rungs": ("gauge", None),
        "compile_service_queue_depth": ("gauge", None),
        "compile_service_compiles_total": ("counter", ("stage", "outcome")),
        "compile_service_compile_seconds": ("histogram", ("stage",)),
        "compile_service_cold_routes_total": ("counter", ("action",)),
        # ISSUE 7: the shed-flush fallback's wall time (the latency a
        # submission pays on the SLO layer's `fallback` path)
        "compile_service_fallback_verify_seconds": ("histogram", None),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family


def test_transfer_ledger_families_registered():
    """ISSUE 8 families (utils/transfer_ledger.py) exist under their
    declared types + labels, and the old unlabeled pack histogram is
    REPLACED by the phase-labeled family (same name, new shape)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "bls_device_h2d_bytes_total": ("counter", ("operand", "kind")),
        "bls_device_d2h_bytes_total": ("counter", None),
        "bls_device_pack_seconds": ("histogram", ("phase",)),
        "bls_device_pubkey_reupload_ratio": ("gauge", ("kind",)),
        "device_memory_bytes": ("gauge", ("kind",)),
        "bls_device_ledger_rows_total": ("counter", ("path",)),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    # pack seconds is labeled now: re-registering unlabeled must raise
    with pytest.raises(TypeError):
        metrics.histogram("bls_device_pack_seconds")
    # the ledger's phase catalogue is what the family carries
    from lighthouse_tpu.utils import transfer_ledger

    assert set(transfer_ledger.PACK_PHASES) == {
        "decode", "limb_split", "pad", "hash", "device_put",
    }
    # and both new tools import cleanly (jax-freedom is
    # subprocess-pinned in tests/test_transfer_ledger.py)
    import tools.bench_diff  # noqa: F401
    import tools.transfer_report  # noqa: F401


def test_key_table_families_registered():
    """ISSUE 10 families (crypto/device/key_table.py) exist under their
    declared types + labels, and the module stays importable jax-free
    (it registers families on boxes that must not initialize a
    backend)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "bls_device_key_table_entries": ("gauge", ("region",)),
        "bls_device_key_table_device_bytes": ("gauge", None),
        "bls_device_key_table_upload_bytes_total": ("counter", ("reason",)),
        "bls_device_key_table_sets_total": ("counter", ("path",)),
        "bls_device_key_table_agg_events_total": ("counter", ("event",)),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    # the limb layout the table mirrors must match the device fp layout
    # WITHOUT key_table importing the (jax-pulling) fp module
    from lighthouse_tpu.crypto.device import key_table

    assert key_table.NL == 32
    assert key_table.G1_ROW_BYTES == 2 * key_table.NL * 4
    # the capacity ladder is sorted and strictly increasing (the gather
    # program's compile count is bounded by its length)
    lad = key_table.CAPACITY_LADDER
    assert list(lad) == sorted(set(lad))


def test_dp_mesh_families_registered():
    """ISSUE 11 families (crypto/device/mesh.py + the scheduler's dp
    counters) exist under their declared types + labels, and the mesh
    module stays importable jax-free."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "bls_device_shard_sets_total": ("counter", ("shard",)),
        "bls_device_shard_verify_seconds": ("histogram", ("shard",)),
        "bls_device_shard_failures_total": ("counter", ("shard",)),
        "bls_device_shard_health": ("gauge", ("shard",)),
        "bls_device_shard_memory_bytes": ("gauge", ("shard",)),
        "verification_scheduler_dp_shards": ("gauge", None),
        "verification_scheduler_dp_subbatches_total": (
            "counter", ("shard",),
        ),
        "verification_scheduler_dp_sets_total": ("counter", ("shard",)),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    # jax-free import is subprocess-pinned (a mesh of placeholder
    # devices must never initialize a backend)
    import os
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from lighthouse_tpu.crypto.device import mesh\n"
         "m = mesh.DeviceMesh(devices=[None, None])\n"
         "assert m.healthy_shards() == [0, 1]\n"
         "with mesh.dispatch_to(0):\n"
         "    pass\n"
         "assert 'jax' not in sys.modules, 'mesh must stay jax-free'\n"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr


def test_robustness_families_registered():
    """ISSUE 13 families (fault injection + self-healing mesh +
    watchdog + compile retry + key-table re-sync) exist under their
    declared types + labels, the fault-point catalogue stays sorted,
    and the fault-injection module is importable jax-free with a
    sub-microsecond disarmed fire() seam (subprocess-pinned here; the
    full behavioral suite is tests/test_fault_injection.py)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "fault_injections_total": ("counter", ("point", "action")),
        "fault_points_armed": ("gauge", None),
        "bls_device_shard_probation": ("gauge", ("shard",)),
        "bls_device_shard_probes_total": ("counter", ("shard", "outcome")),
        "bls_device_shard_recoveries_total": ("counter", ("shard",)),
        "verification_scheduler_watchdog_reaped_total": (
            "counter", ("shard",),
        ),
        "compile_service_compile_retries_total": ("counter", None),
        "bls_device_key_table_resyncs_total": ("counter", ("outcome",)),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    from lighthouse_tpu.utils import fault_injection

    # the fault-point catalogue is a registry like EVENT_KINDS: sorted,
    # unique, snake_case, and fire()/arm() reject undeclared points
    pts = fault_injection.FAULT_POINTS
    assert list(pts) == sorted(pts) and len(set(pts)) == len(pts)
    for p in pts:
        assert _NAME.match(p), f"fault point not snake_case: {p!r}"
    with pytest.raises(ValueError):
        fault_injection.arm("zgate4_undeclared_point", nth=1)
    # jax-free import + arm/fire round trip, subprocess-pinned (the
    # mesh recovery worker and metrics lint import this module on
    # boxes that must not initialize a backend)
    import os
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from lighthouse_tpu.utils import fault_injection as fi\n"
         "fi.arm('staged_dispatch', nth=1)\n"
         "try:\n"
         "    fi.fire('staged_dispatch')\n"
         "    raise SystemExit('expected InjectedFault')\n"
         "except fi.InjectedFault:\n"
         "    pass\n"
         "fi.clear()\n"
         "fi.fire('staged_dispatch')  # disarmed: free no-op\n"
         "assert 'jax' not in sys.modules, 'fault layer must stay jax-free'\n"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr


def test_pipeline_profiler_families_registered():
    """ISSUE 12 families (utils/pipeline_profiler.py) exist under their
    declared types + labels, the phase/cause catalogues stay pinned
    (the bubble attribution priority and the flush timeline are API
    surfaces the docs and tools read), and the report tool imports
    cleanly (jax-freedom is subprocess-pinned in
    tests/test_pipeline_profiler.py)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "bls_device_bubble_seconds_total": ("counter", ("shard", "cause")),
        "bls_device_shard_busy_seconds_total": ("counter", ("shard",)),
        "verification_scheduler_flush_phase_seconds_total": (
            "counter", ("phase",),
        ),
        "verification_scheduler_flush_thread_saturation": ("gauge", None),
        "verification_scheduler_overlap_potential_ratio": ("gauge", None),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    from lighthouse_tpu.utils import pipeline_profiler

    assert pipeline_profiler.BUBBLE_CAUSES == (
        "pack", "plan", "compile", "queue_empty", "other",
    )
    assert pipeline_profiler.FLUSH_PHASES == (
        "queue_wait", "plan", "pack", "device", "fallback", "resolve",
    )
    import tools.pipeline_report  # noqa: F401


def test_capacity_timeseries_and_burn_families_registered():
    """ISSUE 14 families (utils/timeseries.py + the SLO burn layer +
    the scheduler's arrival accounting + the compile service's
    rung-cost feed) exist under their declared types + labels, the
    sampler allowlist stays a sorted documented registry like
    EVENT_KINDS, and the report tool imports cleanly (jax-freedom is
    subprocess-pinned in tests/test_timeseries_capacity.py)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "capacity_estimated_sets_per_sec": ("gauge", None),
        "capacity_utilization": ("gauge", None),
        "capacity_headroom_ratio": ("gauge", None),
        "capacity_sampler_samples_total": ("counter", None),
        "capacity_sampler_errors_total": ("counter", None),
        "capacity_sampler_memory_bytes": ("gauge", None),
        "verification_scheduler_arrival_sets_total": (
            "counter", ("kind", "path"),
        ),
        "verification_scheduler_slo_burn_rate": (
            "gauge", ("kind", "window"),
        ),
        "verification_scheduler_slo_burn_events_total": (
            "counter", ("kind",),
        ),
        "compile_service_measured_cost_seconds_per_set": ("gauge", None),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    # the sampler allowlist is a registry: sorted, unique, snake_case,
    # capacity_-prefixed, every family documented in OBSERVABILITY.md —
    # an undeclared series cannot silently appear in the endpoint
    import os

    from lighthouse_tpu.utils import timeseries

    fams = [s.family for s in timeseries.SAMPLE_FAMILIES]
    assert fams, "sampler allowlist must not be empty"
    assert fams == sorted(fams)
    assert len(set(fams)) == len(fams)
    docs = open(
        os.path.join(
            os.path.dirname(__file__), "..", "docs", "OBSERVABILITY.md"
        )
    ).read()
    for spec in timeseries.SAMPLE_FAMILIES:
        assert _NAME.match(spec.family), spec.family
        assert spec.family.startswith(("capacity_", "slot_")), spec.family
        assert f"`{spec.family}`" in docs, (
            f"sampler family {spec.family!r} missing from "
            f"docs/OBSERVABILITY.md — the allowlist must stay documented"
        )
        assert spec.mode in ("gauge", "rate", "ratio", "derived"), spec.mode
        # non-derived families read a real registry family by name
        if spec.mode != "derived":
            assert spec.source, spec.family
    # the timeseries schema is a versioned identifier like the trace
    # schema, and the tier catalogue is pinned (docs + endpoint grammar)
    assert re.fullmatch(
        r"lighthouse_tpu\.timeseries/\d+", timeseries.SCHEMA
    ), timeseries.SCHEMA
    assert timeseries.TIER_NAMES == ("raw", "1m", "10m")
    import tools.capacity_report  # noqa: F401


def test_bulk_qos_families_registered():
    """ISSUE 15 families (the bulk QoS class: verification_service/
    batcher.py queues + admission.py throttle) exist under their
    declared types + labels, the journal kinds are in the sorted
    catalogue, the sampler allowlist carries the bulk series, and the
    trace schema's qos axis is the declared pair."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "verification_scheduler_bulk_queue_depth": ("gauge", None),
        "verification_scheduler_bulk_sets_total": ("counter", ("kind",)),
        "verification_scheduler_bulk_shed_total": ("counter", ("kind",)),
        "verification_scheduler_bulk_throttled": ("gauge", None),
        "verification_scheduler_bulk_throttle_events_total": (
            "counter", ("reason",),
        ),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    from lighthouse_tpu.utils import flight_recorder, timeseries
    from lighthouse_tpu.verification_service import traffic

    assert "bulk_throttle" in flight_recorder.EVENT_KINDS
    assert "bulk_resume" in flight_recorder.EVENT_KINDS
    fams = {s.family for s in timeseries.SAMPLE_FAMILIES}
    assert {
        "capacity_bulk_queue_depth",
        "capacity_bulk_sets_per_sec",
        "capacity_bulk_throttled",
    } <= fams
    assert traffic._QOS == ("deadline", "bulk")
    # the bulk AOT rungs close the compile ladder at LOWEST priority:
    # gossip's headline rungs must all warm before backfill's. Their
    # geometry is the real wired bulk callers' (proposal signatures:
    # K=1, one distinct message per set => M pads to B — an M=8 rung
    # could never cover a bulk drain)
    from lighthouse_tpu.compile_service import DEFAULT_RUNGS

    assert DEFAULT_RUNGS[0] == (64, 16, 8)
    assert DEFAULT_RUNGS[-2:] == ((512, 1, 512), (256, 1, 256))
    for b, k, m in DEFAULT_RUNGS[-2:]:
        assert m >= b, "a bulk rung must cover per-set-distinct messages"


def test_slot_ledger_families_registered():
    """ISSUE 17 families (utils/slot_ledger.py) exist under their
    declared types + labels, the event catalogue stays a sorted
    registry, the schema is versioned like the trace schema, and the
    report tool imports cleanly (jax-freedom is subprocess-pinned in
    tests/test_slot_ledger.py)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "slot_ledger_slots": ("gauge", None),
        "slot_ledger_evicted_total": ("counter", None),
        "slot_ledger_events_total": ("counter", ("event",)),
        "key_table_first_sighting_hit_ratio": ("gauge", ("epoch",)),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    from lighthouse_tpu.utils import slot_ledger

    # the event catalogue reads as a registry: sorted, unique, snake
    evs = slot_ledger.EVENTS
    assert evs and list(evs) == sorted(evs) and len(set(evs)) == len(evs)
    for ev in evs:
        assert _NAME.match(ev), f"slot event not snake_case: {ev!r}"
    assert re.fullmatch(
        r"lighthouse_tpu\.slot_ledger/\d+", slot_ledger.SCHEMA
    ), slot_ledger.SCHEMA
    # note_committee_sighting refuses outcomes outside the pair — the
    # conservation invariant (first + hits == sightings) depends on it
    if slot_ledger.enabled():
        with pytest.raises(ValueError):
            slot_ledger.note_committee_sighting("zgate4_undeclared")
    import tools.slot_report  # noqa: F401


def test_duty_lookahead_families_registered():
    """ISSUE 19 families (duty_lookahead/) exist under their declared
    types + labels, the journal kinds are in the sorted catalogue, the
    fault point is declared, the key table's slot-ledger seam carries
    the lookahead counters, and the package stays importable jax-free
    (subprocess-pinned: the replay driver imports it on boxes that
    must not initialize a backend)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "duty_lookahead_epochs_total": ("counter", ("outcome",)),
        "duty_lookahead_committees_total": ("counter", ("path",)),
        "duty_lookahead_inserts_total": ("counter", ("outcome",)),
        "duty_lookahead_warm_seconds": ("gauge", None),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    from lighthouse_tpu.utils import fault_injection, flight_recorder
    from lighthouse_tpu.utils import slot_ledger

    assert "lookahead_epoch_warmed" in flight_recorder.EVENT_KINDS
    assert "lookahead_insert_failed" in flight_recorder.EVENT_KINDS
    assert "duty_lookahead" in fault_injection.FAULT_POINTS
    assert "lookahead" in slot_ledger.EVENTS
    # jax-free import + a virtual-mode warm round trip, subprocess-pinned
    import os
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from lighthouse_tpu import duty_lookahead as dl\n"
         "w = dl.DutyLookahead(lambda e: [(1, 2, 3)])\n"
         "out = w.warm_epoch(5)\n"
         "assert out['counts']['virtual'] == 1, out\n"
         "assert w.status()['warmed_epoch'] == 5\n"
         "assert 'jax' not in sys.modules, "
         "'duty_lookahead must stay jax-free'\n"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr


def test_watchtower_families_and_catalogue_registered():
    """ISSUE 18 families (utils/watchtower.py) exist under their
    declared types + labels, the detector catalogue reads as a registry
    (sorted, unique, snake_case, every detector documented in
    docs/OBSERVABILITY.md with a sane declaration), the incident journal
    kinds are in the sorted catalogue, and tools/incident_report.py
    imports cleanly + dry-runs jax-free (subprocess-pinned)."""
    _import_instrumented_modules()
    reg = metrics.registry_snapshot()
    want = {
        "watchtower_evaluations_total": ("counter", None),
        "watchtower_evaluator_errors_total": ("counter", None),
        "watchtower_incidents_total": ("counter", ("detector", "severity")),
        "watchtower_incidents_open": ("gauge", None),
        "watchtower_detector_state": ("gauge", ("detector",)),
        "watchtower_bundles_written_total": ("counter", None),
    }
    for name, (kind, labels) in want.items():
        m = reg.get(name)
        assert m is not None, f"family {name} not registered"
        assert m.kind == kind, (name, m.kind)
        if labels is not None:
            assert m.labelnames == labels, (name, m.labelnames)
        else:
            assert not hasattr(m, "labelnames"), name  # unlabeled family
    import os

    from lighthouse_tpu.utils import flight_recorder, watchtower

    # the detector catalogue is a registry like EVENT_KINDS: sorted,
    # unique, snake_case, declared severities/algos only, documented
    names = [d.name for d in watchtower.DETECTORS]
    assert names, "detector catalogue must not be empty"
    assert names == sorted(names)
    assert len(set(names)) == len(names)
    docs = open(
        os.path.join(
            os.path.dirname(__file__), "..", "docs", "OBSERVABILITY.md"
        )
    ).read()
    for d in watchtower.DETECTORS:
        assert _NAME.match(d.name), f"detector not snake_case: {d.name!r}"
        assert d.severity in watchtower.SEVERITIES, (d.name, d.severity)
        assert d.algo in watchtower.ALGOS, (d.name, d.algo)
        assert d.window_s > 0 and d.min_points >= 1 and d.sustain >= 1, d.name
        assert d.source.startswith(("series:", "probe:")), (d.name, d.source)
        if d.source.startswith("probe:"):
            assert d.source.partition(":")[2] in watchtower.PROBES, d.source
        assert d.doc, f"detector {d.name!r} has no doc string"
        assert f"`{d.name}`" in docs, (
            f"detector {d.name!r} missing from docs/OBSERVABILITY.md — "
            f"the catalogue must stay documented"
        )
    # the incident schema is versioned like the trace schema, and the
    # journal kinds are in the sorted recorder catalogue
    assert re.fullmatch(
        r"lighthouse_tpu\.incident/\d+", watchtower.SCHEMA
    ), watchtower.SCHEMA
    assert "incident_opened" in flight_recorder.EVENT_KINDS
    assert "incident_resolved" in flight_recorder.EVENT_KINDS
    # the renderer imports cleanly and its --list-detectors dry run
    # stays jax-free (the forensic path must work on a dying node
    # without touching a backend)
    import subprocess
    import sys

    import tools.incident_report  # noqa: F401

    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import tools.incident_report as ir\n"
         "ir.main(['--list-detectors'])\n"
         "assert 'jax' not in sys.modules, "
         "'incident_report must stay jax-free'\n"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "headroom_floor" in r.stdout


def test_warmup_tool_imports_and_dry_run_lists_ladder(capsys, monkeypatch):
    """ISSUE 5 CI satellite: ``tools/warmup.py`` must import cleanly and
    ``--dry-run`` must list the ladder walk WITHOUT compiling anything
    (the compile path is boobytrapped here to prove it stays untouched)."""
    import tools.warmup as warmup
    from lighthouse_tpu.compile_service import DEFAULT_RUNGS, lowering

    def boom(*a, **k):  # pragma: no cover — reaching this is the failure
        raise AssertionError("--dry-run must not compile")

    monkeypatch.setattr(lowering, "warm_staged", boom)
    monkeypatch.setattr(lowering, "warm_msm", boom)
    monkeypatch.setattr(lowering, "timed_lower_compile", boom)
    # the operator knob must not leak into the DEFAULT_RUNGS assertion
    monkeypatch.delenv("LIGHTHOUSE_TPU_COMPILE_RUNGS", raising=False)
    assert warmup.main(["--dry-run"]) == 0
    out = capsys.readouterr().out
    for b, k, m in DEFAULT_RUNGS:
        assert f"B={b} K={k} M={m}" in out, out
    # ISSUE 10: the gathered rungs (key-table gather programs, one per
    # distinct (B, K)) are listed too, so the prebake story is honest
    # about the in-node-only warm surface
    assert "gathered rungs" in out, out
    for b, k in sorted({(b, k) for (b, k, _m) in DEFAULT_RUNGS}):
        assert f"gather B={b} K={k}" in out, out
    # ISSUE 16: the MSM ladder (opt-in device aggregation programs) is
    # listed too — same honesty contract as the gather rungs
    from lighthouse_tpu.compile_service.service import MSM_RUNGS

    assert "msm rungs" in out, out
    for n in MSM_RUNGS:
        assert f"msm N={n}" in out, out
    # an explicit plan overrides the default and is echoed verbatim
    assert warmup.main(["--dry-run", "--rungs", "4:1:1"]) == 0
    out = capsys.readouterr().out
    assert "B=4 K=1 M=1" in out
    assert "gather B=4 K=1" in out
    # ISSUE 11: --devices renders the mesh ladder — rung x device,
    # headline rungs first across every chip (still compile-free: the
    # boobytrap above is live for this call too)
    assert warmup.main(
        ["--dry-run", "--devices", "2", "--rungs", "4:1:1,64:16:8"]
    ) == 0
    out = capsys.readouterr().out
    assert "mesh ladder walk (2 rungs x 2 devices" in out
    assert out.index("B=4 K=1 M=1 dev=0") < out.index("B=4 K=1 M=1 dev=1")
    assert out.index("B=4 K=1 M=1 dev=1") < out.index("B=64 K=16 M=8 dev=0")


def test_trace_schema_version_and_generators_documented():
    """ISSUE 7 CI satellite: the arrival-trace schema constant is a
    versioned identifier (bumping the format means bumping the version,
    consciously), and the schema string + every generator in the
    catalogue is documented in docs/TRAFFIC_REPLAY.md — a trace format
    is an API surface like the metric names are."""
    import os

    from lighthouse_tpu.verification_service import traffic

    assert re.fullmatch(
        r"lighthouse_tpu\.traffic_trace/\d+", traffic.TRACE_SCHEMA
    ), traffic.TRACE_SCHEMA
    assert traffic.TRACE_SCHEMA.endswith(f"/{traffic.TRACE_VERSION}")
    docs = open(
        os.path.join(
            os.path.dirname(__file__), "..", "docs", "TRAFFIC_REPLAY.md"
        )
    ).read()
    assert f"`{traffic.TRACE_SCHEMA}`" in docs, (
        "the trace schema version must be documented in "
        "docs/TRAFFIC_REPLAY.md"
    )
    assert traffic.GENERATORS, "generator catalogue must not be empty"
    for name in traffic.GENERATORS:
        assert _NAME.match(name), f"generator name not snake_case: {name!r}"
        assert f"`{name}`" in docs, (
            f"generator {name!r} missing from docs/TRAFFIC_REPLAY.md — "
            f"the catalogue must stay documented"
        )
    # the replay driver imports cleanly (its jax-free property is
    # subprocess-pinned in tests/test_traffic_replay.py)
    import tools.traffic_replay  # noqa: F401


def test_journal_event_kinds_snake_case_and_documented():
    """Every flight-recorder event kind is snake_case, sorted (so the
    catalogue reads as a registry, not an accretion), and documented in
    docs/OBSERVABILITY.md — the journal is an API surface like the
    metric names are."""
    import os

    from lighthouse_tpu.utils import flight_recorder

    kinds = flight_recorder.EVENT_KINDS
    assert kinds, "event-kind catalogue must not be empty"
    assert list(kinds) == sorted(kinds)
    assert len(set(kinds)) == len(kinds)
    docs = open(
        os.path.join(os.path.dirname(__file__), "..", "docs", "OBSERVABILITY.md")
    ).read()
    for kind in kinds:
        assert _NAME.match(kind), f"event kind not snake_case: {kind!r}"
        assert f"`{kind}`" in docs, (
            f"event kind {kind!r} missing from docs/OBSERVABILITY.md — the "
            f"journal catalogue must stay documented"
        )
    # and the recorder refuses kinds outside the catalogue
    if flight_recorder.enabled():
        with pytest.raises(ValueError):
            flight_recorder.record("zgate4_undeclared_kind")


def test_disabled_span_costs_under_one_microsecond():
    was = tracing.enabled()
    tracing.disable()
    try:
        n = 20_000
        span = tracing.span  # the hot-path spelling caches the lookup too
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with span("zgate4.noop"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 1e-6, (
            f"disabled span enter/exit costs {best * 1e9:.0f} ns — too "
            f"expensive to leave always-on in the verification hot path"
        )
    finally:
        if was:
            tracing.enable()


def test_disabled_span_with_jaxlib_loaded_costs_under_one_microsecond():
    """The off path once jaxlib is imported: one profiler-state check
    (``TraceMe.is_enabled()``) with no session recording, still no span
    object and no TraceMe built."""
    import jaxlib._profiler  # noqa: F401

    was = tracing.enabled()
    tracing.disable()
    try:
        n = 20_000
        span = tracing.span
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with span("zgate4.noop", shard=0):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 1e-6, (
            f"disabled span with jaxlib loaded costs {best * 1e9:.0f} ns "
            f"— the profiler check must stay off the allocation path"
        )
    finally:
        if was:
            tracing.enable()
