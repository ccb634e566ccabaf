"""CI static-analysis gate: hazard-lint the tree, audit every backend cell.

Two execution-free passes, both of which must come back clean:

1. **Lint** — :mod:`repro.analysis.lint` over ``src/repro`` (REPRO001-004,
   dormant-seed allowlist on).  Any finding fails the gate.
2. **Audit** — :func:`repro.analysis.audit.audit` across the full backend
   matrix.  Each cell is planned from a tiny synthetic corpus, its epoch
   functions are lowered from abstract shapes (nothing runs, no data is
   read past the header probe), and the optimized HLO is checked against
   the access contract: collective inventory vs reduction mode, buffer
   donation, dtype discipline, host callbacks, epoch-stable cache keys,
   and H2D byte reconciliation with the planner's ``AccessStats`` model.

The sharded cells lower against an 8-way mesh, which on a CPU runner
needs ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exported
BEFORE python starts (the CI job does).  Without enough devices those
cells are skipped with a warning — pass ``--strict`` (CI does) to turn
the skip into a failure so the matrix can never silently shrink.

The per-cell :class:`AuditReport` JSON lands in ``--out`` for artifact
upload; exit is nonzero on any lint finding, audit failure, or (strict)
skipped cell.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python benchmarks/audit_gate.py --strict --out /tmp/audit
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

import jax

from repro.analysis.audit import audit
from repro.analysis.lint import lint_paths
from repro.api import (GATHER, PSUM, RESIDENT, STREAMED, DataSource,
                       ExperimentSpec, plan)
from repro.data import dataset, sparse

REPO = Path(__file__).resolve().parents[1]

ROWS, FEATS, B = 1001, 16, 64


def _cells(dense, csr, mesh):
    """name -> ExperimentSpec covering every backend the planner selects:
    streamed/resident x dense/CSR x eager/fused x single/gather/psum."""
    def spec(data, **kw):
        kw.setdefault("solver", "mbsgd")
        kw.setdefault("batch_size", B)
        kw.setdefault("step_size", 0.05)
        return ExperimentSpec(data=data, **kw)

    cells = {
        "streamed-eager": spec(DataSource.corpus(dense),
                               placement=STREAMED, solver="svrg", chunk=4),
        "sparse-csr": spec(DataSource.corpus(csr), solver="saga", chunk=4),
        "resident-eager": spec(DataSource.corpus(dense), solver="sag"),
        "resident-fused": spec(DataSource.corpus(dense), kernel="fused"),
        # the importance-weighted adaptive engines (PR 10): the extra
        # (k,) weight aval rides the chunk payload and the batch dim is a
        # BOUND — padded buffers must still reconcile H2D bytes exactly
        "adaptive-streamed[chunk_importance]": spec(
            DataSource.corpus(dense), scheme="chunk_importance", chunk=4),
        "adaptive-csr[stochastic_batch]": spec(
            DataSource.corpus(csr), scheme="stochastic_batch", chunk=4),
    }
    if mesh is not None:
        cells.update({
            "sharded-streamed[gather]": spec(
                DataSource.corpus(dense), placement=STREAMED, mesh=mesh,
                reduction=GATHER, chunk=4),
            "sharded-streamed[psum]": spec(
                DataSource.corpus(dense), placement=STREAMED, mesh=mesh,
                reduction=PSUM, chunk=4),
            "sharded-resident[gather]": spec(
                DataSource.corpus(dense), placement=RESIDENT, mesh=mesh,
                reduction=GATHER),
            "sharded-resident[psum]": spec(
                DataSource.corpus(dense), placement=RESIDENT, mesh=mesh,
                reduction=PSUM),
        })
    return cells


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, default=None,
                    help="directory for audit_report.json (artifact upload)")
    ap.add_argument("--strict", action="store_true",
                    help="fail (instead of warn) when the sharded cells "
                         "cannot lower for lack of devices — CI sets this "
                         "so the audited matrix can never silently shrink")
    ap.add_argument("--skip-lint", action="store_true",
                    help="audit only (the lint half has its own CLI: "
                         "python -m repro.analysis.lint)")
    a = ap.parse_args(argv)

    failures = 0

    # ---- pass 1: hazard lint over the live tree --------------------------
    if not a.skip_lint:
        findings = lint_paths([REPO / "src" / "repro"],
                              root=REPO / "src")
        for f in findings:
            print(f"LINT {f}")
        print(f"lint: {len(findings)} finding(s)")
        failures += len(findings)

    # ---- pass 2: static audit across the backend matrix ------------------
    ndev = jax.device_count()
    mesh = jax.make_mesh((8,), ("data",)) if ndev >= 8 else None
    if mesh is None:
        msg = (f"only {ndev} device(s) visible: sharded cells cannot "
               f"lower (export XLA_FLAGS="
               f"--xla_force_host_platform_device_count=8)")
        if a.strict:
            print(f"AUDIT FAIL: {msg}")
            failures += 1
        else:
            print(f"audit: WARNING {msg} — skipping sharded cells")

    with tempfile.TemporaryDirectory() as tmp:
        dense = Path(tmp) / "dense.bin"
        csr = Path(tmp) / "csr.bin"
        dataset.synth_erm_corpus(dense, rows=ROWS, features=FEATS, seed=5)
        sparse.synth_sparse_classification(csr, rows=ROWS, features=64,
                                           density=0.05, seed=5)
        reports = {}
        for name, spec in _cells(dense, csr, mesh).items():
            report = audit(plan(spec))
            reports[name] = report.to_json()
            verdict = "ok" if report.ok else "FAIL"
            print(f"audit: {name:28s} backend={report.backend:18s} "
                  f"{verdict}")
            if not report.ok:
                failures += 1
                for unit, r in report.failures():
                    print(f"  {unit}: [{r.rule}] {r.evidence}")

    if a.out is not None:
        a.out.mkdir(parents=True, exist_ok=True)
        (a.out / "audit_report.json").write_text(json.dumps(
            {"device_count": ndev, "strict": a.strict,
             "cells": reports}, indent=2))
        print(f"audit: report -> {a.out / 'audit_report.json'}")

    print(f"audit_gate: {len(reports)} cell(s) audited, "
          f"{failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
