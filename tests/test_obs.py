"""Unit tests for the zero-dependency tracing + metrics layer.

Contracts under test:

* a DISABLED tracer is a near-free no-op — ``span`` hands back a shared
  singleton and records nothing — while ``timespan`` still MEASURES (its
  ``.dur`` is what AccessStats books, so disabling the trace must not
  zero the accounting);
* lane sums count only TOPLEVEL spans (a read nested inside a read is
  detail, not double-counted time);
* the ring buffer is bounded: overflow evicts oldest and counts
  ``dropped`` instead of growing without limit;
* the Chrome export is well-formed per ``Timeline.load_chrome`` (the
  same validator CI runs against the uploaded artifacts);
* metrics snapshots carry exact count/sum/max and windowed percentiles.

This module deliberately imports only ``repro.obs`` — the observability
layer must stay importable (and testable) without jax.
"""
import json
import threading
import time

import pytest

from repro.obs import (ACCESS, COMPUTE, DRIVER, EPOCH, H2D, LANES,
                       NULL_TRACER, Metrics, NullMetrics, TracePolicy, Tracer,
                       Timeline)


# ----------------------------------------------------------- tracer core ----

def test_disabled_tracer_records_nothing_and_reuses_noop_span():
    t = Tracer(enabled=False)
    s1 = t.span("a", ACCESS)
    s2 = t.span("b", H2D)
    assert s1 is s2              # shared singleton: no per-call allocation
    with s1 as sp:
        sp.set(bytes=123)        # must not raise
    assert t.timeline().events == []


def test_disabled_timespan_still_measures_duration():
    """The anti-drift contract: stats book ``timespan(...).dur`` whether or
    not the trace records, so a disabled tracer must still time."""
    t = Tracer(enabled=False)
    with t.timespan("read", ACCESS) as sp:
        time.sleep(0.01)
    assert sp.dur >= 0.009
    assert t.timeline().events == []


def test_enabled_span_records_name_lane_args_and_duration():
    t = Tracer()
    with t.span("read", ACCESS, scheme="cyclic") as sp:
        time.sleep(0.005)
        sp.set(bytes=4096)
    (ev,) = t.timeline().events
    assert ev.name == "read" and ev.lane == ACCESS
    assert ev.args == {"scheme": "cyclic", "bytes": 4096}
    assert ev.dur >= 0.004
    assert ev.toplevel


def test_nested_same_lane_spans_count_once_in_lane_totals():
    t = Tracer()
    with t.span("outer", ACCESS):
        time.sleep(0.005)
        with t.span("inner", ACCESS):
            time.sleep(0.005)
    with t.span("other", COMPUTE):
        pass
    tl = t.timeline()
    by_name = {e.name: e for e in tl.events}
    assert by_name["outer"].toplevel and not by_name["inner"].toplevel
    totals = tl.lane_totals()
    # outer alone — counting inner too would double-book its 5ms
    assert abs(totals[ACCESS] - by_name["outer"].dur) < 1e-9
    assert totals[ACCESS] >= 0.009


def test_cross_lane_nesting_keeps_both_toplevel():
    """A gather reshard nests inside the H2D stage span on the staging
    thread, but lives on its own lane — both must stay toplevel (the
    stats analogue: gather_s is a subset of h2d_s, booked separately)."""
    t = Tracer()
    with t.span("stage", H2D):
        with t.span("reshard", "gather"):
            pass
    assert all(e.toplevel for e in t.timeline().events)


def test_ring_buffer_bounds_memory_and_counts_dropped():
    t = Tracer(buffer=16)
    for i in range(50):
        with t.span(f"s{i}", COMPUTE):
            pass
    tl = t.timeline()
    assert len(tl.events) == 16
    assert tl.dropped == 34
    assert [e.name for e in tl.events] == [f"s{i}" for i in range(34, 50)]


def test_event_api_books_externally_timed_interval():
    t = Tracer()
    t.event("h2d", H2D, t0=0.5, dur=0.25, bytes=10)
    (ev,) = t.timeline().events
    assert ev.dur == 0.25 and ev.args["bytes"] == 10
    assert t.timeline().lane_totals()[H2D] == 0.25


def test_tracer_is_thread_safe_under_concurrent_spans():
    t = Tracer(buffer=1 << 14)

    def work(k):
        for i in range(200):
            with t.span(f"w{k}", COMPUTE, i=i):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    tl = t.timeline()
    assert len(tl.events) == 800 and tl.dropped == 0
    assert all(e.toplevel for e in tl.events)  # stacks are per-thread


class _Annotations:
    """A fake ``jax.profiler.TraceAnnotation``: records (what, name)."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


def test_disabled_tracer_never_calls_the_annotation_factory():
    ann = _Annotations()
    t = Tracer(enabled=False, annotate=ann)
    with t.span("objective", DRIVER):
        pass
    with t.timespan("read", ACCESS):
        pass
    t.event("compile", DRIVER, t0=0.0, dur=1.0)
    assert ann.log == [] and t.timeline().events == []
    assert NULL_TRACER.annotate is None
    assert TracePolicy(enabled=False).make_tracer().annotate is None


def test_enabled_tracer_annotates_each_recorded_span_around_its_body():
    ann = _Annotations()
    t = Tracer(annotate=ann)
    with t.span("warmup", DRIVER):
        with t.timespan("read", ACCESS):
            ann.log.append(("body", None))
    t.event("compile", DRIVER, t0=time.perf_counter(), dur=0.0)
    assert ann.log == [("enter", "driver:warmup"), ("enter", "access:read"),
                       ("body", None), ("exit", "access:read"),
                       ("exit", "driver:warmup")]
    assert len(t.timeline().events) == 3


def test_timeline_origin_places_spans_on_perf_counter():
    before = time.perf_counter()
    t = Tracer()
    time.sleep(0.002)
    t0 = time.perf_counter()
    with t.span("layout", DRIVER):
        time.sleep(0.002)
    t1 = time.perf_counter()
    tl = t.timeline()
    (ev,) = tl.events
    assert before <= tl.origin_s == t.epoch <= t0
    assert t0 <= tl.origin_s + ev.ts
    assert tl.origin_s + ev.ts + ev.dur <= t1


def test_jax_event_books_compile_stages_on_the_driver_lane():
    t = Tracer()
    t.jax_event("/jax/core/compile/jaxpr_trace_duration", 0.25)
    t.jax_event("/jax/core/compile/backend_compile_duration", 0.5)
    t.jax_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    t.jax_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 1.0)
    now = time.perf_counter() - t.epoch
    tl = t.timeline()
    assert [(e.lane, e.name, e.args["stage"], e.dur) for e in tl.events] == [
        (DRIVER, "compile", "traces", 0.25),
        (DRIVER, "compile", "compiles", 0.5),
        (DRIVER, "compile", "cache_loads", 0.125)]
    assert all(e.ts + e.dur <= now for e in tl.events)
    assert tl.metrics["counters"] == {"jit.traces": 1, "jit.compiles": 1,
                                      "jit.cache_loads": 1}
    off = Tracer(enabled=False)
    off.jax_event("/jax/core/compile/jaxpr_trace_duration", 0.25)
    assert off.timeline().events == [] and off.metrics.snapshot() == {
        "counters": {}, "histograms": {}}


# --------------------------------------------------------- chrome export ----

def test_chrome_export_is_valid_and_microsecond_scaled(tmp_path):
    t = Tracer()
    with t.span("epoch", EPOCH):
        with t.span("read", ACCESS, bytes=1):
            time.sleep(0.002)
    path = tmp_path / "trace.json"
    t.timeline().save(path)
    doc = Timeline.load_chrome(path)      # raises on malformed events
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {EPOCH, ACCESS} <= names
    read = next(e for e in xs if e["name"] == "read")
    assert read["dur"] >= 1500            # 2ms in MICROseconds, not seconds
    assert read["args"]["bytes"] == 1


def test_chrome_lane_rows_follow_canonical_order(tmp_path):
    t = Tracer()
    for lane in reversed(LANES):
        with t.span("x", lane):
            pass
    doc = Timeline.load_chrome(t.timeline().save(tmp_path / "t.json"))
    rows = [e for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"]
    rows.sort(key=lambda e: e["tid"])
    assert [r["args"]["name"] for r in rows] == list(LANES)


def test_load_chrome_rejects_malformed_documents(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "n", "pid": 0, "tid": 0, "ts": 1, "dur": -5}]}))
    with pytest.raises(ValueError):
        Timeline.load_chrome(bad)
    bad.write_text(json.dumps({"nope": []}))
    with pytest.raises(ValueError):
        Timeline.load_chrome(bad)


# ---------------------------------------------------------------- metrics ----

def test_metrics_counters_gauges_and_histograms_snapshot():
    m = Metrics()
    m.counter("ls.invocations").inc(3)
    m.counter("ls.invocations").inc()
    h = m.histogram("span_s.access.read")
    for v in range(1, 101):
        h.observe(float(v))
    snap = m.snapshot()
    assert snap["counters"]["ls.invocations"] == 4
    assert set(snap) == {"counters", "histograms"}
    hist = snap["histograms"]["span_s.access.read"]
    assert hist["count"] == 100 and hist["max"] == 100.0
    assert 45 <= hist["p50"] <= 55 and 90 <= hist["p95"] <= 100


def test_histogram_window_bounds_percentiles_but_not_totals():
    m = Metrics()
    h = m.histogram("w")
    n = 5000                       # past the 4096-sample percentile window
    for v in range(n):
        h.observe(1.0)
    s = h.snapshot()
    assert s["count"] == n and s["sum"] == pytest.approx(float(n))


def test_null_metrics_accepts_everything_and_snapshots_empty():
    nm = NullMetrics()
    nm.counter("a").inc(5)
    nm.histogram("c").observe(0.1)
    assert nm.snapshot() == {"counters": {}, "histograms": {}}


def test_tracer_feeds_span_histograms():
    t = Tracer()
    with t.span("read", ACCESS):
        pass
    snap = t.metrics.snapshot()
    assert f"span_s.{ACCESS}.read" in snap["histograms"]


# ----------------------------------------------------------- trace policy ----

def test_trace_policy_validates_and_builds_the_right_tracer(tmp_path):
    pol = TracePolicy(path=str(tmp_path / "t.json"))  # str normalizes ok
    pol.validate()
    assert pol.make_tracer().enabled
    off = TracePolicy(enabled=False)
    off.validate()
    assert off.make_tracer() is NULL_TRACER
    with pytest.raises(ValueError):
        TracePolicy(buffer=4).validate()


def test_null_tracer_singleton_is_disabled():
    assert not NULL_TRACER.enabled
    assert NULL_TRACER.timeline().events == []
