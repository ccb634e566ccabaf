"""AccessStats ↔ span-timeline invariants across the execution backends.

The tracer and :class:`AccessStats` share one measurement by construction
(stats book ``timespan(...).dur``), so a traced run must reconcile:

* every accounting lane's toplevel span sum equals what stats booked
  (``verify_timeline``'s exact layer) and tracks :meth:`breakdown` within
  tolerance — on all four backends (streamed-eager, resident-eager,
  sparse-csr, and sharded-streamed in a 2-device subprocess);
* component times are non-negative and ``h2d_saved_s`` is earned ONLY by
  resident placement (streamed restages every epoch — nothing is saved);
* sharded runs split staged bytes evenly: per-device H2D bytes times the
  shard count returns the total;
* tracing is strictly additive — AccessStats of a traced run stays
  bit-for-bit the accounting an untraced run produces.
"""
import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

from repro.api import (RESIDENT, SPARSE_CSR, STREAMED, STREAMED_EAGER,
                       DataSource, ExperimentSpec, Timeline, TracePolicy,
                       execute, plan)
from repro.data import dataset, sparse
from repro.obs import (ACCESS, CHECKPOINT, COMPUTE, CONVERT, DRIVER, EPOCH,
                       H2D, WAIT)
from tests.util import run_py

ROWS, FEATS, B = 600, 12, 100
SFEATS = 64


@pytest.fixture(scope="module")
def dense_corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("inv") / "dense.bin"
    dataset.synth_erm_corpus(path, rows=ROWS, features=FEATS, seed=11)
    return path


@pytest.fixture(scope="module")
def csr_corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("inv") / "sparse.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=SFEATS,
                                       density=0.05, seed=12)
    return path


def _traced_spec(data, **kw):
    kw.setdefault("step_size", 0.05)
    kw.setdefault("batch_size", B)
    kw.setdefault("epochs", 2)
    kw.setdefault("trace", TracePolicy())
    return ExperimentSpec(data=data, **kw)


def _assert_stats_invariants(res):
    st = res.stats
    assert st.access_s >= 0 and st.h2d_s >= 0 and st.h2d_saved_s >= 0
    assert st.gather_s >= 0 and st.gather_s <= st.h2d_s + 1e-9
    assert res.compute_s >= 0
    bd = res.breakdown()
    for k in ("access_s_per_epoch", "h2d_s_per_epoch",
              "compute_s_per_epoch"):
        assert bd[k] >= 0, (k, bd)


# ---------------------------------------------------- per-backend runs ----

def test_streamed_traced_run_reconciles(dense_corpus):
    res = execute(plan(_traced_spec(DataSource.corpus(dense_corpus),
                                    placement=STREAMED)))
    _assert_stats_invariants(res)
    assert res.stats.h2d_saved_s == 0.0      # restaged every epoch
    report = res.verify_timeline()
    assert all(v["ok"] for v in report.values()), report
    lanes = res.timeline.lane_totals()
    assert {ACCESS, H2D, COMPUTE, EPOCH} <= set(lanes)


def test_resident_traced_run_reconciles_and_saves_h2d(dense_corpus):
    res = execute(plan(_traced_spec(DataSource.corpus(dense_corpus),
                                    placement=RESIDENT)))
    _assert_stats_invariants(res)
    # epochs=2: one staging paid, one avoided — the paper's resident win
    assert res.stats.h2d_saved_s > 0.0
    assert all(v["ok"] for v in res.verify_timeline().values())
    stage = [e for e in res.timeline.events
             if e.lane == H2D and e.name == "stage_resident"]
    assert len(stage) == 1                   # staged ONCE, not per epoch


def test_sparse_traced_run_reconciles_and_isolates_convert(csr_corpus):
    p = plan(_traced_spec(DataSource.corpus(csr_corpus)))
    assert p.backend == SPARSE_CSR
    res = execute(p)
    _assert_stats_invariants(res)
    assert all(v["ok"] for v in res.verify_timeline().values())
    # ELL padding is compute-shaping, not data access: it must live on its
    # own lane or it would inflate the access lane past what stats booked
    assert any(e.lane == CONVERT for e in res.timeline.events)


def test_sharded_streamed_h2d_splits_per_device(dense_corpus):
    code = f"""
    import json
    import jax
    from repro.api import (DataSource, ExperimentSpec, STREAMED, TracePolicy,
                           execute, plan)
    mesh = jax.make_mesh((2,), ("data",))
    spec = ExperimentSpec(data=DataSource.corpus(r"{dense_corpus}"),
                          step_size=0.05, batch_size={B}, epochs=2,
                          placement=STREAMED, mesh=mesh,
                          trace=TracePolicy())
    res = execute(plan(spec))
    report = res.verify_timeline()
    st = res.stats
    print(json.dumps({{
        "ok": all(v["ok"] for v in report.values()),
        "shards": st.shards,
        "per_device": st.h2d_bytes_per_device,
        "total": st.bytes_staged,
        "gather_s": st.gather_s,
        "gather_lane": res.timeline.lane_totals().get("gather", 0.0),
    }}))
    """
    r = run_py(code, devices=2)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["ok"], out
    assert out["shards"] == 2
    # even split: per-device bytes x shards covers the staged total
    assert out["per_device"] * out["shards"] == out["total"] > 0
    # default sharded-streamed reduction is gather: the reshard spans must
    # carry exactly the booked gather_s
    assert out["gather_lane"] == pytest.approx(out["gather_s"], abs=1e-6)


# ------------------------------------------------- tracing is additive ----

def test_traced_stats_match_untraced_bit_for_bit(dense_corpus):
    src = DataSource.corpus(dense_corpus)
    plain = execute(plan(_traced_spec(src, trace=None)))
    traced = execute(plan(_traced_spec(src)))
    assert plain.timeline is None and traced.timeline is not None
    # identical optimization, identical byte accounting — timings differ
    assert traced.objective == plain.objective
    assert traced.stats.bytes_read == plain.stats.bytes_read
    assert traced.stats.bytes_staged == plain.stats.bytes_staged
    assert traced.stats.batches == plain.stats.batches


def test_disabled_policy_runs_and_keeps_no_timeline(dense_corpus):
    res = execute(plan(_traced_spec(DataSource.corpus(dense_corpus),
                                    trace=TracePolicy(enabled=False))))
    assert res.timeline is None
    assert res.to_json()["metrics"] == {}
    with pytest.raises(ValueError):
        res.verify_timeline()


# ------------------------------------------------------ result surface ----

def test_line_search_invocations_counted(dense_corpus):
    res = execute(plan(_traced_spec(DataSource.corpus(dense_corpus),
                                    step_mode="line_search",
                                    step_size=1.0)))
    m = res.timeline.metrics
    assert m["counters"]["ls.invocations"] == res.plan.num_batches * 2
    blob = res.to_json()
    assert blob["schema"] == 3
    assert blob["metrics"]["counters"]["ls.invocations"] == \
        res.plan.num_batches * 2


def test_checkpoint_saves_land_on_checkpoint_lane(dense_corpus, tmp_path):
    from repro.api import CheckpointPolicy
    res = execute(plan(_traced_spec(
        DataSource.corpus(dense_corpus),
        checkpoint=CheckpointPolicy(tmp_path / "ck"))))
    names = {e.name for e in res.timeline.events if e.lane == CHECKPOINT}
    assert {"snapshot", "serialize", "commit"} <= names


def test_save_trace_writes_valid_chrome_json(dense_corpus, tmp_path):
    out = tmp_path / "trace.json"
    res = execute(plan(_traced_spec(DataSource.corpus(dense_corpus),
                                    trace=TracePolicy(path=out))))
    assert out.exists()                       # written by execute() itself
    Timeline.load_chrome(out)
    again = tmp_path / "again.json"
    res.save_trace(again)
    assert Timeline.load_chrome(again)["traceEvents"]


def test_trace_policy_rejected_at_plan_time(dense_corpus):
    from repro.api import PlanError
    with pytest.raises(PlanError, match="buffer"):
        plan(dataclasses.replace(
            _traced_spec(DataSource.corpus(dense_corpus)),
            trace=TracePolicy(buffer=2)))


def test_metrics_round_trip_through_json(dense_corpus):
    from repro.api import RunResult
    p = plan(_traced_spec(DataSource.corpus(dense_corpus)))
    res = execute(p)
    j = res.to_json()
    r2 = RunResult.from_json(j, p)
    assert r2.to_json() == j                  # schema-3 bit-for-bit
    assert r2.timeline.metrics == res.timeline.metrics


# ------------------------------------------- execute()'s phases, named ----

BIG_ROWS, BIG_FEATS = 400_000, 28


@pytest.fixture(scope="module")
def big_dense_corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("inv") / "big.bin"
    dataset.synth_erm_corpus(path, rows=BIG_ROWS, features=BIG_FEATS, seed=13)
    return path


def _untraced_s(timeline, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] covered by no span, spans placed by origin_s."""
    covered, reach = 0.0, t0
    for a, b in sorted((timeline.origin_s + e.ts,
                        timeline.origin_s + e.ts + e.dur)
                       for e in timeline.events):
        a, b = max(a, reach), min(b, t1)
        if b > a:
            covered, reach = covered + b - a, b
    return (t1 - t0) - covered


def _spans(res, lane, name):
    return [e for e in res.timeline.events
            if e.lane == lane and e.name == name]


@pytest.mark.parametrize("placement", [RESIDENT, STREAMED])
def test_traced_job_names_every_phase_of_execute(big_dense_corpus,
                                                 placement):
    spec = _traced_spec(DataSource.corpus(big_dense_corpus),
                        placement=placement, epochs=4, batch_size=1000)
    execute(plan(spec))                       # compiles
    p = plan(spec)
    t0 = time.perf_counter()
    res = execute(p)
    t1 = time.perf_counter()
    assert all(v["ok"] for v in res.verify_timeline().values())
    assert len(_spans(res, DRIVER, "warmup")) == 1
    assert _spans(res, DRIVER, "init")
    objective = _spans(res, DRIVER, "objective")
    assert [e.args["epoch"] for e in objective] == [0, 1, 2, 3]
    nbytes = BIG_ROWS * (BIG_FEATS + 1) * 4
    if placement == RESIDENT:
        # the read lays X and y out itself: a file corpus has no layout copy
        assert not _spans(res, DRIVER, "layout")
        (read,) = _spans(res, ACCESS, "read_all")
        assert read.args["bytes"] == nbytes
        assert read.args["blocks"] == res.timeline.metrics["counters"][
            "read_all.blocks"] >= 1
        assert 1 <= read.args["workers"] <= min(8, read.args["blocks"])
        (release,) = _spans(res, DRIVER, "release")
        assert release.args["bytes"] == nbytes
    else:
        # the pass reads blocks of one training chunk's rows
        block_rows = res.plan.chunk * spec.batch_size
        assert all(e.args == {"epoch": e.args["epoch"], "rows": BIG_ROWS,
                              "chunks": -(-BIG_ROWS // block_rows),
                              "bytes": nbytes} for e in objective)
        waits = _spans(res, WAIT, "chunk")
        counters = res.timeline.metrics["counters"]
        # one wait per chunk taken; the driver stops after the last one
        assert len(waits) == counters["stager.gets"]
        assert counters["stager.gets"] == 4 * -(-res.plan.num_batches
                                                 // res.plan.chunk)
        assert all(e.parent == "train_epoch" for e in waits)
    assert _untraced_s(res.timeline, t0, t1) < 0.1 * (t1 - t0)


@pytest.mark.parametrize("kind", ["resident", "streamed", "csr"])
def test_untraced_job_reaches_no_profiler_listener_or_counter(
        dense_corpus, csr_corpus, monkeypatch, kind):
    """With the tracer off, execute() touches no profiler annotation, no
    jax.monitoring listener and no counter."""
    import jax
    from repro.obs import NULL_TRACER

    def refuse(*a, **k):
        raise AssertionError("reached with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener", refuse)
    monkeypatch.setattr(NULL_TRACER.metrics, "counter", refuse)
    src = DataSource.corpus(csr_corpus if kind == "csr" else dense_corpus)
    kw = {} if kind == "csr" else {"placement": kind}
    res = execute(plan(_traced_spec(src, trace=None, **kw)))
    assert res.timeline is None and np.isfinite(res.objective)


def test_profiler_capture_holds_each_span_annotation(tmp_path):
    """An enabled policy's tracer writes every span into a jax.profiler
    capture as ``<lane>:<name>``, with the span's own duration."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tracer = TracePolicy().make_tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("warmup", DRIVER):
            time.sleep(0.02)
            with tracer.timespan("read", ACCESS):
                time.sleep(0.01)
        with tracer.span("chunk", WAIT):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
    found = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    found.setdefault(ev.name, []).append(
                        ev.duration_ns * 1e-9)
    for ev in tracer.timeline().events:
        (dur,) = found[f"{ev.lane}:{ev.name}"]
        assert abs(dur - ev.dur) <= max(100e-6, 0.05 * ev.dur), (ev.name,
                                                                 dur, ev.dur)


def test_ell_counters_count_nonzeros_and_slots_exactly(csr_corpus):
    epochs = 2
    res = execute(plan(_traced_spec(DataSource.corpus(csr_corpus),
                                    prefetch=0, epochs=epochs)))
    csr = sparse.open_csr_corpus(csr_corpus)
    assert ROWS % B == 0                      # each epoch reads every row
    counters = res.timeline.metrics["counters"]
    assert counters["ell.nonzeros"] == epochs * csr.nnz
    assert counters["ell.slots"] == (epochs * res.plan.num_batches * B
                                     * csr.kmax)


def test_compile_listener_books_a_forced_retrace_and_goes_away(
        dense_corpus):
    import jax
    from jax._src import monitoring
    from repro.core import experiment
    spec = _traced_spec(DataSource.corpus(dense_corpus), placement=RESIDENT)
    execute(plan(spec))
    listeners = list(monitoring._event_duration_secs_listeners)
    experiment._objective_jit.clear_cache()   # the next call retraces
    res = execute(plan(spec))
    assert monitoring._event_duration_secs_listeners == listeners
    traces = [e for e in _spans(res, DRIVER, "compile")
              if e.args["stage"] == "traces"]
    assert len(traces) == res.timeline.metrics["counters"]["jit.traces"] >= 1
    # the objective's first call is the warm-up's: the retrace lies there
    assert {e.parent for e in traces} == {"warmup"}
    # a compile after execute() returns reaches no tracer
    jax.jit(lambda x: x * 3 + 1)(np.float32(2))
    assert monitoring._event_duration_secs_listeners == listeners


def _stager_source(n, delay):
    for i in range(n):
        time.sleep(delay)
        yield np.full(4, i, np.float32)


@pytest.mark.parametrize("enabled", [True, False])
def test_stager_wait_spans_and_counters(enabled):
    from repro.data.pipeline import DeviceStager
    from repro.obs import Tracer
    tracer = Tracer(enabled=enabled)
    stager = DeviceStager(_stager_source(5, 0.01), put=lambda a: a,
                          depth=2, tracer=tracer)
    got = [int(a[0]) for a in stager]
    assert got == [0, 1, 2, 3, 4]
    tl = tracer.timeline()
    if not enabled:
        assert tl.events == [] and tl.metrics == {"counters": {},
                                                  "histograms": {}}
        return
    waits = [e for e in tl.events if e.lane == WAIT]
    counters = tl.metrics["counters"]
    assert len(waits) == 6 and counters["stager.gets"] == 5
    # the producer sleeps before each item: the consumer finds it empty
    assert 1 <= counters["stager.starved"] <= 6
    assert sum(e.dur for e in waits) >= 0.03


# ----------------------------------------- the benchmark's span readers ----

BENCH_DIR = Path(__file__).resolve().parents[1] / "bench"
READERS = {"objective_s_per_epoch": 3, "warmup_s": 3, "untraced_s": 3,
           "chunk_wait_s_per_epoch": 2, "ell_fill_share": 1}
# cell -> (rows, features, batch, backend the CPU planner picks)
SMALL_CELLS = {"higgs-resident-ss": (6000, 28, 200, "resident-eager"),
               "higgs-streamed-rs": (6000, 28, 200, "streamed-eager"),
               "rcv1-streamed-ss": (3001, 2000, 100, "sparse-csr")}


@pytest.fixture(scope="module")
def bench_harness():
    import sys
    sys.path.insert(0, str(BENCH_DIR))
    from harness import jobs, main, spec
    return jobs, main, spec


@pytest.fixture(scope="module")
def traced_records(bench_harness, tmp_path_factory):
    """One CPU-traced job per cell at a small size, as the benchmark's
    Record."""
    from harness import corpus
    jobs, main, spec = bench_harness
    out = {}
    for name, (rows, feats, b, backend) in SMALL_CELLS.items():
        cell = spec.find_cell(name)
        cell.config = json.loads(json.dumps(cell.config))
        cell.config["corpus"].update(rows=rows, features=feats)
        cell.config["method"]["batch_size"] = b
        cell.traffic = dict(cell.traffic, backend=backend)
        seed = 2**31 + 21
        path, _ = corpus.ensure(cell.config, seed,
                                tmp_path_factory.mktemp("bench"))
        job = jobs.run_job(cell.config, cell.traffic, path, 1, seed, None,
                           trace_buffer=1 << 16)
        assert not job.error, job.error
        out[name] = main.Record(cell=cell, seed=seed, corpus=path,
                                setup_s=0.0, jobs=[job],
                                elapsed_s=job.wall_s, memory=[],
                                traced=job)
    return out


def _metric_cells():
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    return [(name, cell) for name in READERS
            for cell in per_layer[name]["workloads"]]


@pytest.mark.parametrize("metric,cell", _metric_cells())
def test_span_reader_reads_a_cpu_traced_job(bench_harness, traced_records,
                                            metric, cell):
    value = bench_harness[2].metric_reader(metric)(traced_records[cell])
    assert isinstance(value, float) and value >= 0.0, value
    if metric == "ell_fill_share":
        assert 0.0 < value <= 100.0
    if metric == "untraced_s":
        assert value < traced_records[cell].traced.execute_s


@pytest.mark.parametrize("metric", sorted(READERS))
def test_span_reader_gives_nothing_for_a_program_without_the_spans(
        bench_harness, traced_records, metric):
    """A program without the driver/wait spans, origin_s or counters (as
    before they existed) gives no number, and no error."""
    import copy
    rec = copy.copy(traced_records["rcv1-streamed-ss"])
    job = copy.copy(rec.traced)
    job.timeline = Timeline(events=[e for e in job.timeline.events
                                    if e.lane not in (DRIVER, WAIT)])
    rec.traced = job
    assert bench_harness[2].metric_reader(metric)(rec) is None
