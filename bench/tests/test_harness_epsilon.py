"""The ``epsilon-resident-rs`` cell at a small size on the CPU, on the path
it runs on the chip: resident, random sampling, the per-row DMA kernel
``fused_grad_rows`` (interpret mode here, so the traffic forces
``kernel="fused"``, which the chip's planner picks by itself).  Its own
limits pass a sound run and fail the control and each planted fault; a
traced run gives ``fused_rows_roofline`` and ``row_dma_fill_share`` what
they read."""
import copy
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import check, corpus, devtrace, faults, jobs, main, \
    reference, work  # noqa: E402
from harness.spec import find_cell, metric_reader  # noqa: E402
from test_harness_correct import jax_cache_restored  # noqa: E402,F401

NAME = "epsilon-resident-rs"
# 200 features pad to 256 lanes: neither at most 128 nor a multiple of it
ROWS, FEATURES, BATCH, EPOCHS = 2000, 200, 64, 2


def small_cell():
    cell = find_cell(NAME)
    cell.config = copy.deepcopy(cell.config)
    cell.config["corpus"].update(rows=ROWS, features=FEATURES)
    cell.config["method"]["batch_size"] = BATCH
    cell.traffic = dict(cell.traffic, kernel="fused", epochs=EPOCHS)
    return cell


def run(tmp_path, trace=False):
    return main.run_cell(small_cell(), 2**31 + 21, 0.2, trace,
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


def test_traced_run_reports_the_kernel_metrics(tmp_path, monkeypatch,
                                               jax_cache_restored):
    """The program's counters give ``row_dma_fill_share`` its value; the
    CPU trace has no TPU plane, so ``fused_rows_roofline`` reads a fixed
    device reduction holding the rows kernel's calls."""
    import jax
    import numpy as np
    m = -(-ROWS // BATCH)
    calls, secs = EPOCHS * m, 0.05
    fake = devtrace.DeviceTrace(
        devices=1, window_s=1.0, busy_s=0.25,
        ops={"fused_grad_rows.3": (calls, secs), "fusion.1": (7, 0.2)},
        gaps=np.array([[0.0, 5e8], [6e8, 1e9]]), offset_ns=0.0)
    monkeypatch.setattr(devtrace, "load", lambda path: None)
    monkeypatch.setattr(devtrace, "reduce", lambda *a, **k: fake)
    monkeypatch.setattr(main, "chips",
                        lambda cell, require_tpu: jax.devices()[:1])
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    res = main.run_cell(small_cell(), 2**31 + 11, 0.2, True, cache=tmp_path,
                        out=tmp_path / "out")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {x["name"]
                                   for x in small_cell().metrics_layer}

    fill = res["metrics"]["row_dma_fill_share"]["value"]
    assert fill == pytest.approx(100 * FEATURES / (8 * 256))
    per_call = work.dense_batch(BATCH, FEATURES) + work.Work(
        0.0, 2.0 * FEATURES * work.F32)
    assert res["metrics"]["fused_rows_roofline"]["value"] == pytest.approx(
        100 * calls * per_call.seconds(v5e) / secs)


def test_kernel_metrics_are_silent_without_their_inputs():
    """A program without the counters, or a trace without the rows kernel,
    gives no value: the reader returns nothing and does not raise."""
    import types
    cell = small_cell()
    timeline = types.SimpleNamespace(metrics={"counters": {}})
    trace = devtrace.DeviceTrace(devices=1, window_s=1.0, busy_s=0.5,
                                 ops={"fused_grad_block.1": (3, 0.1)},
                                 gaps=None, offset_ns=0.0)
    rec = types.SimpleNamespace(cell=cell, device=trace,
                                peaks=work.peaks("TPU v5 lite"),
                                traced=types.SimpleNamespace(
                                    timeline=timeline))
    assert metric_reader("row_dma_fill_share")(rec) is None
    assert metric_reader("fused_rows_roofline")(rec) is None
