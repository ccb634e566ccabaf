"""Record a small profiler trace on the chip, the fixture of the trace
reduction's test (``bench/tests/test_harness_trace.py``).

    python3 bench/tools/record_trace_fixture.py --out bench/tests/data

One resident SAGA job on a HIGGS-shaped corpus cut to 8,192 rows, the path
``higgs-resident-ss`` runs (the fused block kernel), traced the way a
``--trace 1`` run traces its first job.  Writes ``tpu_trace.xplane.pb`` and
``tpu_trace.json``: the sync marker's ``perf_counter`` time, the job's
window on that clock, the batches a job epoch has, its epochs and the
device kind.
"""
from __future__ import annotations

import argparse
import copy
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

ROWS = 8192
EPOCHS = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    from harness import corpus, devtrace, jobs, main as harness
    from harness.spec import find_cell

    cell = find_cell("higgs-resident-ss")
    harness.chips(cell, require_tpu=True)
    harness.use_compile_cache(harness.CACHE / "jax")
    config = copy.deepcopy(cell.config)
    config["name"] = "trace-fixture"
    config["corpus"]["rows"] = ROWS
    traffic = dict(cell.traffic, epochs=EPOCHS)
    path, _ = corpus.ensure(config, 1, harness.CACHE / "corpus")
    warm = jobs.run_job(config, traffic, path, 0, 1, None)
    if warm.error:
        raise RuntimeError(warm.error)
    with devtrace.capture(harness.OUT / "fixture") as cap:
        job = jobs.run_job(config, traffic, path, 1, 1, None)
    if job.error:
        raise RuntimeError(job.error)
    args.out.mkdir(parents=True, exist_ok=True)
    shutil.copy(cap.xplane(), args.out / "tpu_trace.xplane.pb")
    import jax
    meta = {"sync_perf_ns": cap.sync_perf_ns,
            "window_perf_s": [job.t0, job.t0 + job.wall_s],
            "batches_per_epoch": -(-ROWS // config["method"]["batch_size"]),
            "epochs": EPOCHS, "device_kind": jax.devices()[0].device_kind}
    (args.out / "tpu_trace.json").write_text(json.dumps(meta, indent=1))
    print(json.dumps(meta))
    return 0


if __name__ == "__main__":
    sys.exit(main())
