"""The ``url-resident-ss`` cell at a small size on the CPU, on the path it
runs on the chip: a CSR corpus held whole on the device as padded ELL (the
``resident-sparse`` backend), systematic sampling, mini-batch SGD.  Its own
limits pass a sound run and fail the control and each planted fault; a
traced run gives ``ell_resident_roofline``, ``ell_resident_fill_share`` and
``csr_load_s`` what they read, and each reader is silent without it."""
import copy
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import check, corpus, devtrace, faults, jobs, main, \
    reference, work  # noqa: E402
from harness.spec import find_cell, metric_reader  # noqa: E402
from test_harness_correct import jax_cache_restored  # noqa: E402,F401

NAME = "url-resident-ss"
# url's row length (about 116 nonzeros) at a width the CPU replays quickly
ROWS, FEATURES, DENSITY, BATCH = 3000, 20000, 0.0058, 100
METRICS = ("ell_resident_roofline", "ell_resident_fill_share", "csr_load_s")


def small_cell():
    cell = find_cell(NAME)
    cell.config = copy.deepcopy(cell.config)
    cell.config["corpus"].update(rows=ROWS, features=FEATURES,
                                 density=DENSITY)
    cell.config["method"]["batch_size"] = BATCH
    return cell


def run(tmp_path, trace=False):
    return main.run_cell(small_cell(), 2**31 + 23, 0.2, trace,
                         cache=tmp_path, out=tmp_path / "out",
                         require_tpu=False)


def test_sound_run_is_correct(tmp_path, jax_cache_restored):
    res = run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(fault, tmp_path, jax_cache_restored):
    with faults.FAULTS[fault]():
        res = run(tmp_path)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(tmp_path):
    cell = small_cell()
    path, _ = corpus.ensure(cell.config, 7, tmp_path)
    seed = jobs.job_seed(7, 1)
    ref = check.replay(cell.config, cell.traffic, path, seed)
    ctrl = check.replay(cell.config, cell.traffic, path, seed,
                        reference.CONTROL)
    ok, rows = check.verdict(check.numbers(ctrl[0], ctrl[1], *ref),
                             cell.checks)
    assert not ok, rows


def test_traced_run_reports_the_resident_sparse_metrics(
        tmp_path, monkeypatch, jax_cache_restored):
    """The program's spans and counters give the three metrics their
    inputs.  The CPU trace has no TPU plane, so the device reduction is a
    fixed one whose idle gaps lie outside the job: its epochs read busy
    for their whole spans."""
    import jax
    import numpy as np
    fake = devtrace.DeviceTrace(
        devices=1, window_s=1.0, busy_s=0.25, ops={"while.4": (2, 0.2)},
        gaps=np.array([[0.0, 5e8], [6e8, 1e9]]), offset_ns=0.0)
    monkeypatch.setattr(devtrace, "load", lambda path: None)
    monkeypatch.setattr(devtrace, "reduce", lambda *a, **k: fake)
    monkeypatch.setattr(main, "chips",
                        lambda cell, require_tpu: jax.devices()[:1])
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    captured = {}
    window = jobs.window

    def keep(*a, **k):
        done, elapsed = window(*a, **k)
        captured["job"] = done[0]
        return done, elapsed

    monkeypatch.setattr(jobs, "window", keep)
    res = main.run_cell(small_cell(), 2**31 + 11, 0.2, True, cache=tmp_path,
                        out=tmp_path / "out")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"]
                                   for m in small_cell().metrics_layer}
    assert set(METRICS) <= set(res["metrics"])

    path = tmp_path / "corpus" / "url-mbsgd" / "corpus"
    meta = json.loads((path / "meta.json").read_text())
    fill = res["metrics"]["ell_resident_fill_share"]["value"]
    assert fill == pytest.approx(100 * meta["nnz"]
                                 / (ROWS * meta["max_row_nnz"]))

    tl = captured["job"].timeline
    epochs = [e.dur for e in tl.events
              if (e.lane, e.name) == ("compute", "resident_epoch")]
    assert len(epochs) == small_cell().traffic["epochs"]
    need = work.job(small_cell().config, small_cell().traffic, path)
    assert res["metrics"]["ell_resident_roofline"]["value"] == \
        pytest.approx(100 * need.seconds(v5e) / sum(epochs), rel=1e-6)
    load = sum(e.dur for e in tl.events if (e.lane, e.name) in {
        ("access", "read_csr"), ("convert", "ell_resident"),
        ("h2d", "stage_resident")})
    assert res["metrics"]["csr_load_s"]["value"] == pytest.approx(load)
    assert load > 0


def test_resident_sparse_metrics_are_silent_without_their_inputs():
    """A program without the resident ELL spans and counters, or a run
    without a device trace, gives no value: each reader returns nothing
    and does not raise."""
    import numpy as np
    timeline = types.SimpleNamespace(metrics={"counters": {}}, events=[])
    trace = devtrace.DeviceTrace(devices=1, window_s=1.0, busy_s=0.5,
                                 ops={"while.1": (3, 0.1)},
                                 gaps=np.zeros((0, 2)), offset_ns=0.0)
    traced = types.SimpleNamespace(timeline=timeline, t_execute=0.0)
    rec = types.SimpleNamespace(cell=small_cell(), device=trace,
                                peaks=work.peaks("TPU v5 lite"),
                                traced=traced)
    bare = types.SimpleNamespace(cell=small_cell(), device=None, peaks=None,
                                 traced=None)
    for name in METRICS:
        assert metric_reader(name)(rec) is None
        assert metric_reader(name)(bare) is None
