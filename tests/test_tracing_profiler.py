"""The span API's second sink: while a ``jax.profiler`` session records,
``tracing.span`` also emits a profiler TraceMe of the same name with its
attrs as arguments, so the program's spans land in the ``.xplane.pb``
beside the device operations. The Python buffer stays gated by
``tracing.enable()``."""

import glob

import pytest

from lighthouse_tpu.utils import tracing


@pytest.fixture
def buffer_off():
    was = tracing.enabled()
    tracing.disable()
    tracing.clear()
    yield
    tracing.clear()
    if was:
        tracing.enable()


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tspan."):
                        out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def _traced(log_dir, body):
    import jax

    with jax.profiler.trace(str(log_dir)):
        body()
    return _host_events(log_dir)


def test_spans_land_in_the_profiler_trace_with_attrs(tmp_path, buffer_off):
    def body():
        with tracing.span("tspan.outer", kind="block", n_sets=3) as sp:
            with tracing.span("tspan.inner", stage="stage1"):
                pass
            sp.set(verdict=True)

    events = {name: (s, e, st) for name, s, e, st in _traced(tmp_path, body)}
    assert set(events) == {"tspan.outer", "tspan.inner"}
    s0, e0, outer = events["tspan.outer"]
    s1, e1, inner = events["tspan.inner"]
    assert outer["kind"] == "block" and outer["n_sets"] == 3
    assert outer["verdict"] in (True, 1)  # set mid-span
    assert inner["stage"] == "stage1"
    assert s0 <= s1 <= e1 <= e0  # nested on the same clock
    # the profiler is a second sink, not the buffer: nothing recorded
    assert tracing.events() == []


def test_buffer_and_profiler_record_the_same_span(tmp_path, buffer_off):
    tracing.enable()

    def body():
        with pytest.raises(ValueError):
            with tracing.span("tspan.both", rung="192x512x192"):
                raise ValueError("boom")

    events = _traced(tmp_path, body)
    assert [(n, st["rung"], st["error"]) for n, _s, _e, st in events] == [
        ("tspan.both", "192x512x192", "ValueError")
    ]
    (ev,) = tracing.events()
    assert ev["name"] == "tspan.both"
    assert ev["args"] == {"rung": "192x512x192", "error": "ValueError"}


def test_off_path_is_the_shared_noop_with_jaxlib_loaded(buffer_off):
    import jaxlib._profiler  # noqa: F401  (loaded, no session recording)

    assert tracing.span("tspan.off") is tracing.span("tspan.off2")
