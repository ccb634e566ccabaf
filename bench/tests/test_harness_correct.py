"""``correct`` on the CPU at small sizes: each cell's own limits pass a sound
run of the program, and fail the control (the reference in bfloat16 put in
the program's place) and a run with each fault the cell can have planted in
the timed path.  Only the look for a chip is skipped."""
import copy
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import check, corpus, faults, jobs, main, reference  # noqa: E402
from harness.spec import find_cell, load_benchmark  # noqa: E402

# (rows, features, batch, backend the CPU planner picks) per cell
SMALL = {
    "higgs-resident-ss": (6000, 28, 200, "resident-eager"),
    "higgs-streamed-rs": (6000, 28, 200, "streamed-eager"),
    "rcv1-streamed-ss": (3001, 2000, 100, "sparse-csr"),
    "higgs-streamed-ss-mesh4": (6000, 28, 200, "sharded-streamed"),
}
FAULTS = {cell: ["state_unchanged", "half_batch"] for cell in SMALL}
FAULTS["higgs-streamed-ss-mesh4"].append("no_exchange")


# a cell whose files are in bench/ and whose entry is not yet in
# BENCHMARK.json: no chip run has proven it (PERF.md, Open questions)
PENDING = {
    "configs": [],
    "workloads": [{"name": "higgs-streamed-ss-mesh4", "config": "higgs-saga",
                   "traffic": "streamed-ss-mesh4-e2", "chips": 4}]}


def small_cell(name):
    bench = load_benchmark()
    for key in ("configs", "workloads"):
        known = {e["name"] for e in bench[key]}
        bench[key] = bench[key] + [e for e in PENDING[key]
                                   if e["name"] not in known]
    cell = find_cell(name, bench)
    rows, feats, b, backend = SMALL[name]
    cell.config = copy.deepcopy(cell.config)
    cell.config["corpus"].update(rows=rows, features=feats)
    cell.config["method"]["batch_size"] = b
    cell.traffic = dict(cell.traffic, backend=backend)
    return cell


@pytest.fixture
def jax_cache_restored():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def run(cell, tmp_path):
    return main.run_cell(cell, 2**31 + 5, 0.2, False, cache=tmp_path,
                         require_tpu=False)


ONE_CHIP = [c for c in SMALL if c != "higgs-streamed-ss-mesh4"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name, tmp_path, jax_cache_restored):
    res = run(small_cell(name), tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name,fault", [(c, f) for c in ONE_CHIP
                                        for f in FAULTS[c]])
def test_fault_is_not_correct(name, fault, tmp_path, jax_cache_restored):
    cell = small_cell(name)
    with faults.FAULTS[fault]():
        res = run(cell, tmp_path)
    assert not res["correct"], res["checks"]


def test_a_failed_job_is_counted_and_not_correct(tmp_path, monkeypatch,
                                                 jax_cache_restored):
    """The warm job passes, the window's first job fails: the run reports
    it as failed and not correct, and its line is strict JSON."""
    import repro.api
    execute, calls = repro.api.execute, []

    def second_fails(p):
        calls.append(p)
        if len(calls) == 2:
            raise RuntimeError("planted failure")
        return execute(p)

    monkeypatch.setattr(repro.api, "execute", second_fails)
    res = run(small_cell("higgs-streamed-rs"), tmp_path)
    assert not res["correct"] and res["failed"] == 1
    assert all(c["value"] is None for c in res["checks"].values())
    json.dumps(res, allow_nan=False)


@pytest.mark.parametrize("name", [w["name"] for w in load_benchmark()[
    "workloads"] if w["chips"] == 1])
def test_traced_run_reports_its_per_layer_metrics(name, tmp_path, monkeypatch,
                                                  jax_cache_restored):
    """A ``--trace 1`` run drives the program's span lanes and every
    per-layer reader: each metric the cell lists is in its line.  The CPU
    trace has no TPU plane, so the device reduction is a fixed one."""
    import jax
    import numpy as np
    from harness import devtrace, work
    fake = devtrace.DeviceTrace(
        devices=1, window_s=1.0, busy_s=0.25,
        ops={"fused_grad_block.3": (40, 0.01), "fusion.1": (7, 0.2)},
        gaps=np.array([[0.0, 5e8], [6e8, 1e9]]), offset_ns=0.0)
    monkeypatch.setattr(devtrace, "load", lambda path: None)
    monkeypatch.setattr(devtrace, "reduce", lambda *a, **k: fake)
    monkeypatch.setattr(main, "chips",
                        lambda cell, require_tpu: jax.devices()[:1])
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    cell = small_cell(name)
    res = main.run_cell(cell, 2**31 + 11, 0.2, True, cache=tmp_path,
                        out=tmp_path / "out")
    assert res["correct"], res["checks"]
    want = {m["name"] for m in cell.metrics_layer}
    assert set(res["metrics"]) == want
    assert all(v["value"] >= 0 for v in res["metrics"].values()), res
    assert res["device"]["busy_s"] == 0.25
    assert res["breakdown"]["device_ops"][0][0] == "fusion.1"


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name, tmp_path):
    cell = small_cell(name)
    path, _ = corpus.ensure(cell.config, 7, tmp_path)
    seed = jobs.job_seed(7, 1)
    ref = check.replay(cell.config, cell.traffic, path, seed)
    ctrl = check.replay(cell.config, cell.traffic, path, seed,
                        reference.CONTROL)
    ok, rows = check.verdict(check.numbers(ctrl[0], ctrl[1], *ref),
                             cell.checks)
    assert not ok, rows


def test_mesh_cell_on_four_cpu_devices(tmp_path):
    """The four-chip cell on four virtual CPU devices, in a process of its
    own: sound, then with each of its faults."""
    code = f"""
        import json, sys
        sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / 'src')!r}]
        sys.path.insert(0, {str(Path(__file__).parent)!r})
        from pathlib import Path
        from test_harness_correct import small_cell, FAULTS
        from harness import faults, main
        cell = small_cell("higgs-streamed-ss-mesh4")
        out = {{}}
        def run():
            return main.run_cell(cell, 2**31 + 5, 0.2, False,
                                 cache=Path({str(tmp_path)!r}),
                                 require_tpu=False)["correct"]
        out["sound"] = run()
        for f in FAULTS["higgs-streamed-ss-mesh4"]:
            with faults.FAULTS[f]():
                out[f] = run()
        print(json.dumps(out))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count=4 {flags}"
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "state_unchanged": False,
                   "half_batch": False, "no_exchange": False}, out
