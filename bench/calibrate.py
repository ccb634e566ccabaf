"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell>[,<cell>...] --seed <first> \
        --seeds 12 --control 3 --faults half_batch --fault-seeds 3 \
        --out <file.jsonl>

In one process on the chip, for each of ``--seeds`` seeds from ``--seed``
on and each named cell: generate the corpus (cells of one configuration
share it), run job 1 of a run with that seed through the timed path
(``plan()`` then ``execute()``, as the window runs it) and compare it with
the plain reference: the lower readings.  On the first ``--control`` seeds
also compare the control (the reference computed in bfloat16) with the
reference, and on the first ``--fault-seeds`` run the job again with each
named fault planted in the program: the upper readings.  One JSON line per
reading goes to ``--out`` and to standard output; the last lines sum them
up per cell and number.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    from harness import check, corpus, faults, jobs, main as harness, \
        reference
    from harness.spec import find_cell

    cells = [find_cell(name) for name in args.workload.split(",")]
    harness.chips(max(cells, key=lambda c: c.chips), require_tpu=True)
    harness.use_compile_cache(harness.CACHE / "jax")
    meshes = {c.name: jobs.make_mesh(c.traffic) for c in cells}
    args.out.parent.mkdir(parents=True, exist_ok=True)
    readings = []

    def emit(cell, kind, seed, nums, **extra):
        row = {"cell": cell.name, "kind": kind, "seed": seed, **nums,
               **extra}
        readings.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    def job_numbers(cell, path, seed, ref):
        job = jobs.run_job(cell.config, cell.traffic, path, 1, seed,
                           meshes[cell.name])
        if job.error:
            return {k: float("inf") for k in check.NUMBERS}, job
        return check.numbers(job.w, job.history, *ref), job

    for i in range(args.seeds):
        seed = args.seed + i
        for cell in cells:
            path, _ = corpus.ensure(cell.config, seed,
                                    harness.CACHE / "corpus")
            if i == 0:
                jobs.run_job(cell.config, cell.traffic, path, 0, seed,
                             meshes[cell.name])
            ref = check.replay(cell.config, cell.traffic, path,
                               jobs.job_seed(seed, 1))
            nums, job = job_numbers(cell, path, seed, ref)
            emit(cell, "program", seed, nums, wall_s=job.wall_s,
                 error=job.error,
                 history=[float(h) for h in job.history]
                 if job.history is not None else None)
            if i < args.control:
                ctrl = check.replay(cell.config, cell.traffic, path,
                                    jobs.job_seed(seed, 1),
                                    reference.CONTROL)
                emit(cell, "control", seed,
                     check.numbers(ctrl[0], ctrl[1], *ref))
            if i < args.fault_seeds:
                for name in filter(None, args.faults.split(",")):
                    with faults.FAULTS[name]():
                        nums, job = job_numbers(cell, path, seed, ref)
                    emit(cell, name, seed, nums, error=job.error)

    for cell in cells:
        summary = {}
        mine = [r for r in readings if r["cell"] == cell.name]
        for kind in sorted({r["kind"] for r in mine}):
            rows = [r for r in mine if r["kind"] == kind]
            pick = max if kind == "program" else min
            summary[kind] = {k: pick(r[k] for r in rows)
                             for k in check.NUMBERS}
        print(json.dumps({"cell": cell.name, "summary": summary}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
