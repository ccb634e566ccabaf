"""The ``higgs-streamed-ss`` cell at a small size on the CPU, on the path it
runs on the chip: a dense corpus streamed in contiguous blocks, systematic
sampling, SAGA (the ``streamed-eager`` backend).  Its own limits pass a
sound run and fail the control and each planted fault; a traced run
reports the per-layer metrics of the random-read cell it is the control
of."""
import copy
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import check, corpus, faults, jobs, main, reference  # noqa: E402
from harness.spec import find_cell  # noqa: E402
from test_harness_correct import jax_cache_restored  # noqa: E402,F401

NAME = "higgs-streamed-ss"
ROWS, FEATURES, BATCH = 6000, 28, 200


def small_cell():
    cell = find_cell(NAME)
    cell.config = copy.deepcopy(cell.config)
    cell.config["corpus"].update(rows=ROWS, features=FEATURES)
    cell.config["method"]["batch_size"] = BATCH
    return cell


def run(tmp_path):
    return main.run_cell(small_cell(), 2**31 + 29, 0.2, False,
                         cache=tmp_path, require_tpu=False)


def test_sound_run_is_correct(tmp_path, jax_cache_restored):
    res = run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_rows_per_s", "setup_s"}


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


def test_cell_reads_what_the_random_streamed_cell_reads():
    """As the contiguous-read control of ``higgs-streamed-rs`` the cell
    reports the same per-layer metrics."""
    rs = find_cell("higgs-streamed-rs")
    assert ([m["name"] for m in small_cell().metrics_layer]
            == [m["name"] for m in rs.metrics_layer])


def test_traced_run_reports_its_per_layer_metrics(tmp_path, monkeypatch,
                                                  jax_cache_restored):
    """A ``--trace 1`` run gives each listed metric a value.  The CPU
    trace has no TPU plane, so the device reduction is a fixed one."""
    import jax
    import numpy as np
    from harness import devtrace, work
    fake = devtrace.DeviceTrace(
        devices=1, window_s=1.0, busy_s=0.25, ops={"while.1": (2, 0.2)},
        gaps=np.array([[0.0, 5e8], [6e8, 1e9]]), offset_ns=0.0)
    monkeypatch.setattr(devtrace, "load", lambda path: None)
    monkeypatch.setattr(devtrace, "reduce", lambda *a, **k: fake)
    monkeypatch.setattr(main, "chips",
                        lambda cell, require_tpu: jax.devices()[:1])
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    cell = small_cell()
    res = main.run_cell(cell, 2**31 + 11, 0.2, True, cache=tmp_path,
                        out=tmp_path / "out")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in cell.metrics_layer}
    assert all(v["value"] >= 0 for v in res["metrics"].values()), res
