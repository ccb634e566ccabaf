"""The harness is driven by files: cells, configurations, traffic, limits and
metrics are found by name, a new one is a new file and entry, and the
yardstick's pieces (peaks, work counts, generators) hold on their own."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import corpus, work  # noqa: E402
from harness.spec import find_cell, load_benchmark, metric_reader  # noqa: E402


def test_every_named_file_is_found():
    bench = load_benchmark()
    for w in bench["workloads"]:
        cell = find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.checks and cell.traffic["epochs"] >= 1
        assert {"setup_s", "train_rows_per_s"} <= {
            m["name"] for m in cell.metrics_e2e}
        assert cell.metrics_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(metric_reader(m["name"]))


def test_a_new_cell_config_and_metric_are_only_files(tmp_path):
    """Add a configuration, a traffic mix, a cell and a metric by adding
    files and entries; nothing that exists changes."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("_cache", "_out"))
    bench = load_benchmark()
    cfg = json.loads((REPO / "bench/configs/higgs-saga.json").read_text())
    cfg.update(name="epsilon-saga")
    cfg["corpus"].update(rows=400_000, features=2000)
    (root / "bench/configs/epsilon-saga.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/resident-ss-e1.json").write_text(json.dumps(
        {"scheme": "systematic", "placement": "resident", "kernel": "auto",
         "mesh": 1, "reduction": "auto", "epochs": 1,
         "backend": "resident-fused"}))
    (root / "bench/checks/epsilon-resident-ss.json").write_text(json.dumps(
        {"loss_gap": {"limit": 1e-5}, "w_norm_gap": {"limit": 1e-4},
         "w_dist": {"limit": 1e-4}}))
    (root / "bench/metrics/epochs_per_job.py").write_text(
        "def read(rec):\n    return rec.epochs\n")
    bench["configs"].append({"name": "epsilon-saga", "source": "x",
                             "file": "bench/configs/epsilon-saga.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "epsilon-resident-ss",
                               "config": "epsilon-saga",
                               "traffic": "resident-ss-e1", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "epochs_per_job", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "driver",
                               "moves": "train_rows_per_s",
                               "workloads": ["epsilon-resident-ss"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*.json")}
    cell = find_cell("epsilon-resident-ss", root=root)
    assert cell.config["corpus"]["features"] == 2000
    assert cell.traffic["epochs"] == 1
    assert "epochs_per_job" in [m["name"] for m in cell.metrics_layer]
    reader = metric_reader("epochs_per_job", root=root / "bench")
    assert reader(type("R", (), {"epochs": 1})()) == 1
    assert before == {p: p.read_bytes()
                      for p in (REPO / "bench").rglob("*.json")}


def test_peaks_unknown_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_work_counts_from_shapes(tmp_path):
    cfg = json.loads((REPO / "bench/configs/higgs-saga.json").read_text())
    traffic = {"epochs": 2}
    w = work.job(cfg, traffic, tmp_path)
    m = 11_000
    rows_bytes = 11_000_000 * 29 * 4
    state_bytes = m * 6 * 28 * 4
    assert w.bytes == 2 * (rows_bytes + state_bytes)
    assert w.flops == 2 * (4.0 * 11_000_000 * 28 + m * 4 * 28)
    assert w.seconds({"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}) \
        == w.bytes / 819e9

    csr = json.loads((REPO / "bench/configs/rcv1-mbsgd.json").read_text())
    csr["corpus"].update(rows=1000, features=50)
    csr["method"]["batch_size"] = 300
    (tmp_path / "meta.json").write_text(json.dumps({"nnz": 10_000}))
    w = work.job(csr, {"epochs": 1}, tmp_path)
    nnz = 10_000 * 1200 / 1000            # 4 batches of 300 read 1200 rows
    assert w.flops == 4 * nnz + 4 * 4 * 50
    assert w.bytes == nnz * 8 + 4 * (301 * 8 + 300 * 4) + 4 * 2 * 50 * 4


def test_generators_are_seeded_and_shaped(tmp_path):
    a = corpus.dense_logistic(tmp_path / "a.bin", 2**31 + 3, rows=3000,
                              features=7, separation=2.0)
    b = corpus.dense_logistic(tmp_path / "b.bin", 2**31 + 3, rows=3000,
                              features=7, separation=2.0)
    assert a.read_bytes() == b.read_bytes()
    X = np.fromfile(a, np.float32).reshape(3000, 8)
    assert set(np.unique(X[:, 7])) == {-1.0, 1.0}
    assert abs(X[:, :7].std() - 1) < 0.05

    d = corpus.csr_logistic(tmp_path / "c", 5, rows=70_000, features=4000,
                            density=0.01, separation=2.0)
    indptr = np.fromfile(d / "indptr.bin", np.int64)
    ids = np.fromfile(d / "indices.bin", np.int32)
    lens = np.diff(indptr)
    assert abs(lens.mean() - 40) < 0.5 and lens.min() >= 1
    for r in range(0, 70_000, 997):
        row = ids[indptr[r]:indptr[r + 1]]
        assert np.all(np.diff(row) > 0) and row.max() < 4000
    assert json.loads((d / "meta.json").read_text())["nnz"] == indptr[-1]
    # ids spread evenly over the features
    counts = np.bincount(ids, minlength=4000)
    assert counts.std() / counts.mean() < 0.2


def test_corpus_cache_reuses_by_stamp(tmp_path):
    cfg = {"name": "tiny", "corpus": {"format": "dense",
                                      "generator": "dense_logistic",
                                      "rows": 100, "features": 3,
                                      "separation": 2.0}}
    p1, made1 = corpus.ensure(cfg, 1, tmp_path)
    p2, made2 = corpus.ensure(cfg, 1, tmp_path)
    p3, made3 = corpus.ensure(cfg, 2, tmp_path)
    assert (made1, made2, made3) == (True, False, True) and p1 == p3


def test_run_exits_without_a_tpu(tmp_path):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    args = ["--workload", "higgs-resident-ss", "--seed", str(2**31 + 9),
            "--seconds", "1", "--trace", "0"]
    proc = subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr
    # a checkout holding only the benchmark has no program to run
    bare = tmp_path / "bare"
    shutil.copytree(BENCH, bare / "bench",
                    ignore=shutil.ignore_patterns("_cache", "_out"))
    shutil.copy(REPO / "BENCHMARK.json", bare)
    proc = subprocess.run([sys.executable, "bench/run.py", *args],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=bare)
    assert proc.returncode not in (0, None) and proc.stdout.strip() == ""
