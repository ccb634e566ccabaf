"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` (``workloads``).  The run
generates the cell's corpus from ``--seed`` (or reuses it from
``bench/_cache``), runs one warm job of one epoch that compiles every
program, then runs whole training jobs (``plan()`` then ``execute()``, from
w = 0 for the cell's epochs) back to back for ``--seconds``.  One job of the
window is checked against a plain reference.  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace and the program's span timeline of the
first job.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit;
the same numbers are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import repro  # noqa: F401  (the system under test, from src/)
    from harness import main as harness
    from harness.spec import find_cell

    cell = find_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
