"""Benchmark harness: one function per paper table/figure + system benches,
plus the budgeted sweep driver over the spec surface.

  erm_timing       paper Tables 2-4 (training time + objective, 5 solvers x
                   2 step rules x 3 samplings, memmap-streamed)
  erm_convergence  paper Figs 1-4 (gap vs time curves, device-resident)
  access_time      §1-2 raw access-time microbench (host memmap + device)
  roofline         §Roofline consolidation of the dry-run artifacts
  kernels          Pallas kernel interpret-mode sanity timings

Prints ``name,us_per_call,derived`` CSV. Full-scale knobs:
  python -m benchmarks.erm_timing --rows 2000000 --epochs 30

``python -m benchmarks.run sweep`` runs :func:`run_sweep` — a budgeted,
``RunResult``-resumable grid driver (lifted from ``examples/erm_sweep.py``'s
grid loop): cells advance round-robin a few epochs at a time via
``execute(plan, resume=prev)``, so a wall-clock budget cuts the grid
fairly mid-flight and every partial cell remains resumable; the demo grid
is the constant vs line-search axis.  ``--json-out`` emits a BENCH-style
JSON per grid.

``python -m benchmarks.run run`` executes ONE spec cell from CLI axes and
``--trace out.json`` attaches a :class:`~repro.api.TracePolicy` — the
quickest way from zero to a Chrome/Perfetto timeline of the access / H2D /
compute overlap (open the JSON at ``ui.perfetto.dev``).  ``sweep --trace
DIR`` does the same per grid cell (``DIR/cell_<i>.json``; round-robin
resume overwrites each file per turn, so a finished sweep leaves each
cell's FINAL segment).
"""
from __future__ import annotations

import sys
import time
import traceback


def _kernel_rows():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rows = []
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (4096, 256))
    t0 = time.perf_counter()
    out = ops.block_gather(data, jnp.asarray(2, jnp.int32), batch_size=256)
    jax.block_until_ready(out)
    rows.append(("kernel_block_gather_interp", (time.perf_counter() - t0) * 1e6,
                 "grid=1;one-DMA-per-batch"))
    idx = jax.random.randint(key, (256,), 0, 4096, jnp.int32)
    t0 = time.perf_counter()
    out = ops.random_gather(data, idx)
    jax.block_until_ready(out)
    rows.append(("kernel_random_gather_interp", (time.perf_counter() - t0) * 1e6,
                 "grid=b;one-DMA-per-row"))
    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(key, (1, 256, 2, 64))
    v = jax.random.normal(key, (1, 256, 2, 64))
    t0 = time.perf_counter()
    o = ops.flash_attention(q, k, v, causal=True)
    jax.block_until_ready(o)
    err = float(jnp.max(jnp.abs(o - ref.attention(q, k, v, causal=True))))
    rows.append(("kernel_flash_attention_interp", (time.perf_counter() - t0) * 1e6,
                 f"max_err_vs_ref={err:.1e}"))
    return rows


SECTIONS = []


# ---------------------------------------------------------------------------
# budgeted, resumable sweep over a grid of ExperimentSpecs
# ---------------------------------------------------------------------------

def run_sweep(grid, *, budget_s=None, round_epochs=1, json_out=None,
              checkpoint_dir=None, trace_dir=None, coalesce=False,
              max_cells=None, log=print):
    """Drive a grid of ``ExperimentSpec``s under a wall-clock budget.

    Cells advance ROUND-ROBIN, ``round_epochs`` at a time, resuming each
    cell from its own previous ``RunResult`` (``execute(plan,
    resume=prev)`` — same batch schedule an uninterrupted run would use).
    When ``budget_s`` runs out mid-grid every cell keeps whatever epochs it
    finished and stays resumable; with no budget the sweep runs every cell
    to its spec's epoch budget.  Returns ``[(spec, RunResult), ...]`` in
    grid order (cells that never got a turn carry ``None``).

    ``coalesce=True`` routes each round through the super-cell backend:
    plan-compatible cells (same corpus, scheme, batch size, chunk shape,
    placement, remaining budget) ride ONE staged data stream via
    :func:`repro.api.execute_supercell` — one read / convert / H2D feeding
    S solver updates — while incompatible cells keep their solo turns.
    Per-cell trajectories are bit-identical either way, so the two modes'
    grid JSONs differ only in the timing columns (``wall_s`` /
    ``access_s`` shrink ~S-fold for coalesced cells; diff them with
    ``bench_diff.py --metrics wall_s,access_s``).

    ``checkpoint_dir`` makes the sweep CRASH-resumable, not just
    budget-resumable: each cell checkpoints to ``<dir>/cell_<i>`` (a
    :class:`~repro.checkpoint.CheckpointPolicy` attached to its spec), and
    a restarted sweep over the same grid restores every cell from its
    newest complete snapshot before granting any turns — a SIGKILL
    mid-grid costs at most the epochs since each cell's last snapshot.
    Cell directories are keyed by grid ORDER, so the restart must rebuild
    the same grid (the fingerprint check rejects a reordered one).

    ``trace_dir`` attaches a :class:`~repro.api.TracePolicy` per cell
    (``<dir>/cell_<i>.json``).  Tracing is excluded from the plan
    fingerprint, so it composes with ``checkpoint_dir``: a crash-restarted
    sweep may toggle tracing freely.  Each round-robin turn rewrites the
    cell's file, so the trace on disk is the cell's latest segment.
    """
    import dataclasses
    from pathlib import Path

    from repro.api import (CheckpointPolicy, DEFAULT_MAX_CELLS, TracePolicy,
                           execute, execute_supercell, plan, resume_from)
    from repro.api import coalesce as coalesce_plans

    max_cells = DEFAULT_MAX_CELLS if max_cells is None else max_cells

    if checkpoint_dir is not None:
        root = Path(checkpoint_dir)
        grid = [dataclasses.replace(
                    s, checkpoint=CheckpointPolicy(root / f"cell_{i:03d}"))
                for i, s in enumerate(grid)]
    if trace_dir is not None:
        troot = Path(trace_dir)
        troot.mkdir(parents=True, exist_ok=True)
        grid = [dataclasses.replace(
                    s, trace=TracePolicy(path=troot / f"cell_{i:03d}.json"))
                for i, s in enumerate(grid)]
    # wall_s / access_s / h2d_s accumulate across THIS sweep's round-robin
    # turns (execute's per-call timings), so a cell's row reports the real
    # per-cell cost the sweep paid for it — amortized shares when coalesced
    cells = [{"spec": s, "plan": plan(s), "result": None,
              "wall_s": 0.0, "access_s": 0.0, "h2d_s": 0.0, "cells": 1}
             for s in grid]
    for i, c in enumerate(cells):
        if c["spec"].checkpoint is None:
            continue
        try:
            c["result"] = resume_from(c["spec"].checkpoint.directory,
                                      c["plan"])
        except FileNotFoundError:
            continue            # fresh cell: no snapshot yet
        log(f"# cell {i} resumed at epoch {c['result'].epochs_done}"
            f"/{c['spec'].epochs}")
    t0 = time.perf_counter()
    exhausted = False
    progressed = True

    def _grant(c):
        done = c["result"].epochs_done if c["result"] else 0
        return min(round_epochs, c["spec"].epochs - done)

    def _book(c, res, s_cells):
        c["result"] = res
        c["wall_s"] += res.train_s
        c["access_s"] += res.stats.access_s
        c["h2d_s"] += res.stats.h2d_s
        c["cells"] = s_cells

    while progressed and not exhausted:
        progressed = False
        if coalesce:
            # one coalescing pass per round: compatible cells (epochs done
            # is part of the key, so they stay in lockstep round to round)
            # share one staged stream; the rest keep their solo turns
            live = [c for c in cells if _grant(c) > 0]
            done0s = [c["result"].epochs_done if c["result"] else 0
                      for c in live]
            for batch in coalesce_plans([c["plan"] for c in live],
                                        max_cells=max_cells, done0s=done0s):
                if budget_s is not None \
                        and time.perf_counter() - t0 >= budget_s:
                    exhausted = True
                    break
                group = [live[j] for j in batch.indices]
                results = execute_supercell(
                    batch.plans, resumes=[c["result"] for c in group],
                    epochs=_grant(group[0]))
                for c, res in zip(group, results):
                    _book(c, res, batch.size)
                progressed = True
            continue
        for c in cells:
            if _grant(c) <= 0:
                continue
            if budget_s is not None and time.perf_counter() - t0 >= budget_s:
                exhausted = True
                break
            _book(c, execute(c["plan"], resume=c["result"],
                             epochs=_grant(c)), 1)
            progressed = True
    if exhausted:
        log(f"# budget {budget_s:.0f}s exhausted after "
            f"{time.perf_counter() - t0:.1f}s")

    results = []
    seen = {}
    for c in cells:
        spec, res = c["spec"], c["result"]
        name = f"sweep_{spec.solver}_{spec.step_mode}_{spec.scheme}"
        # grids may vary on axes the name doesn't carry (batch size, reg,
        # ls_mode, ...) — disambiguate collisions instead of emitting
        # duplicate row names
        if name in seen:
            seen[name] += 1
            name = f"{name}#{seen[name]}"
        else:
            seen[name] = 0
        if res is not None:
            b = res.breakdown()
            row = {"name": name, "solver": spec.solver,
                   "step_mode": spec.step_mode,
                   "ls_mode": res.plan.cfg.ls_mode
                              if spec.step_mode == "line_search" else None,
                   "scheme": spec.scheme, "backend": res.plan.backend,
                   "epochs_done": res.epochs_done,
                   "epochs_budget": spec.epochs,
                   "wall_s": c["wall_s"], "access_s": c["access_s"],
                   "h2d_s": c["h2d_s"], "cells": c["cells"], **b}
            log(f"{name},{b['epoch_s'] * 1e6:.2f},"
                f"objective={res.objective:.10f};"
                f"epochs={res.epochs_done}/{spec.epochs};"
                f"backend={res.plan.backend};"
                f"wall_s={c['wall_s']:.3f};cells={c['cells']}")
        else:
            row = {"name": name, "solver": spec.solver,
                   "step_mode": spec.step_mode, "scheme": spec.scheme,
                   "epochs_done": 0, "epochs_budget": spec.epochs}
            log(f"{name},,epochs=0/{spec.epochs} (budget ran out)")
        results.append(row)

    if json_out:
        import json as jsonmod
        import jax
        from repro.checkpoint import atomic_write_text
        payload = {"meta": {"schema": 2, "budget_s": budget_s,
                            "round_epochs": round_epochs,
                            "coalesce": bool(coalesce),
                            "max_cells": max_cells,
                            "checkpoint_dir": (str(checkpoint_dir)
                                               if checkpoint_dir else None),
                            "backend": jax.default_backend(),
                            "unit": "seconds per epoch"},
                   "results": results}
        # tmp + os.replace: a crash mid-write must leave the previous grid
        # JSON intact, never a truncated one a restart would choke on
        atomic_write_text(json_out, jsonmod.dumps(payload, indent=2) + "\n")
    return [(c["spec"], c["result"]) for c in cells]


def demo_sweep_grid(rows=8192, features=32, epochs=6, placement="memory"):
    """The demo grid: constant vs (vectorized) line-search axis across
    three solvers — the step-rule comparison the paper's tables make, as a
    sweep.  ``placement="memory"`` (default) runs on in-memory synthetic
    arrays; ``"streamed"`` builds/reuses the memmapped corpus under
    ``artifacts/bench`` and streams it, which is the regime where
    ``--coalesce`` pays: every grid cell shares one read + H2D stream
    instead of re-reading the corpus six times."""
    import dataclasses
    import itertools
    from pathlib import Path

    from repro.api import DataSource, ExperimentSpec

    if placement == "memory":
        import jax as _jax
        from repro.core import synth_classification
        X, y, _ = synth_classification(_jax.random.PRNGKey(0), rows,
                                       features, separation=2.0)
        data, kw = DataSource.arrays(X, y), {}
    else:
        from repro.data import dataset
        corpus_dir = Path("artifacts/bench")
        corpus_dir.mkdir(parents=True, exist_ok=True)
        corpus = corpus_dir / f"erm_{rows}x{features}.bin"
        if not corpus.exists():
            dataset.synth_erm_corpus(corpus, rows=rows, features=features)
        data, kw = DataSource.corpus(corpus), {"placement": placement}
    base = ExperimentSpec(data=data, loss="logistic", reg=1e-3,
                          batch_size=256, epochs=epochs, **kw)
    return [dataclasses.replace(base, solver=solver, step_mode=step_mode,
                                step_size=1.0 if step_mode == "line_search"
                                else None)
            for solver, step_mode in itertools.product(
                ("mbsgd", "svrg", "saga"), ("constant", "line_search"))]


def sweep_main(argv) -> None:
    import argparse
    ap = argparse.ArgumentParser(prog="benchmarks.run sweep")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="wall-clock budget; cells stay resumable when it "
                         "runs out mid-grid")
    ap.add_argument("--round-epochs", type=int, default=1,
                    help="epochs granted per cell per round-robin turn")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--epochs", type=int, default=6,
                    help="epoch budget per cell")
    ap.add_argument("--json-out", type=str, default=None)
    ap.add_argument("--checkpoint-dir", type=str, default=None,
                    help="per-cell checkpoints under this dir; a restarted "
                         "sweep (same grid) picks up mid-grid after a crash")
    ap.add_argument("--trace", type=str, default=None, metavar="DIR",
                    help="per-cell Chrome traces under this dir "
                         "(cell_<i>.json; latest round-robin segment)")
    ap.add_argument("--coalesce", action="store_true",
                    help="batch plan-compatible cells into super-cells "
                         "(one staged stream per batch; bit-identical "
                         "trajectories, amortized access)")
    ap.add_argument("--max-cells", type=int, default=None,
                    help="super-cell width cap (default "
                         "repro.api.DEFAULT_MAX_CELLS)")
    ap.add_argument("--placement", default="memory",
                    choices=("memory", "streamed", "resident"),
                    help="demo-grid data placement; streamed is where "
                         "--coalesce amortizes access across the grid")
    a = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run_sweep(demo_sweep_grid(rows=a.rows, epochs=a.epochs,
                              placement=a.placement),
              budget_s=a.budget_s, round_epochs=a.round_epochs,
              json_out=a.json_out, checkpoint_dir=a.checkpoint_dir,
              trace_dir=a.trace, coalesce=a.coalesce, max_cells=a.max_cells)


def run_main(argv) -> None:
    """``python -m benchmarks.run run``: one spec cell, optionally traced.

    The cell streams (or stages resident) a synthetic memmapped corpus —
    the same artifact ``erm_timing`` builds — so a single command yields a
    span timeline of the exact regime the paper times.
    """
    import argparse
    from pathlib import Path

    ap = argparse.ArgumentParser(prog="benchmarks.run run")
    ap.add_argument("--solver", default="mbsgd")
    ap.add_argument("--scheme", default="systematic",
                    help="random | cyclic | systematic")
    ap.add_argument("--step-mode", default="constant",
                    help="constant | line_search")
    ap.add_argument("--placement", default="streamed",
                    choices=("streamed", "resident"))
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--trace", type=Path, default=None, metavar="OUT.json",
                    help="write a Chrome/Perfetto trace of the run here "
                         "and verify it reconciles with the breakdown")
    ap.add_argument("--json-out", type=Path, default=None,
                    help="write the RunResult JSON here")
    a = ap.parse_args(argv)

    from repro.api import (DataSource, ExperimentSpec, TracePolicy, execute,
                           plan)
    from repro.data import dataset

    corpus_dir = Path("artifacts/bench")
    corpus_dir.mkdir(parents=True, exist_ok=True)
    corpus = corpus_dir / f"erm_{a.rows}x{a.features}.bin"
    if not corpus.exists():
        dataset.synth_erm_corpus(corpus, rows=a.rows, features=a.features)
    spec = ExperimentSpec(
        data=DataSource.corpus(corpus), loss="logistic", reg=1e-4,
        solver=a.solver, scheme=a.scheme, step_mode=a.step_mode,
        batch_size=a.batch, epochs=a.epochs, placement=a.placement,
        record_objective=False,
        trace=TracePolicy(path=a.trace) if a.trace is not None else None)
    p = plan(spec)
    res = execute(p)
    b = res.breakdown()
    print("name,us_per_call,derived")
    print(f"run_{a.solver}_{a.step_mode}_{a.scheme},"
          f"{b['epoch_s'] * 1e6:.2f},"
          f"objective={res.objective:.10f};backend={p.backend};"
          f"access_ms={b['access_s_per_epoch'] * 1e3:.3f};"
          f"h2d_ms={b['h2d_s_per_epoch'] * 1e3:.3f};"
          f"compute_ms={b['compute_s_per_epoch'] * 1e3:.3f}")
    if a.trace is not None:
        report = res.verify_timeline()
        print(f"# trace -> {a.trace} ({len(res.timeline.events)} events; "
              f"{len(report)} reconciliation checks OK; open at "
              f"ui.perfetto.dev)")
    if a.json_out is not None:
        res.save_json(a.json_out)


def main() -> None:
    from benchmarks import access_time, erm_convergence, erm_timing, roofline

    sections = [
        ("access_time", access_time.main),
        ("erm_timing", erm_timing.main),
        ("erm_convergence", erm_convergence.main),
        ("roofline", roofline.main),
        ("kernels", _kernel_rows),
    ]
    failures = []
    print("name,us_per_call,derived")
    for name, fn in sections:
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.2f},{derived}", flush=True)
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {[n for n, _ in failures]}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    from repro import compile_cache
    compile_cache.enable()
    if len(sys.argv) > 1 and sys.argv[1] == "sweep":
        sweep_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "run":
        run_main(sys.argv[2:])
    else:
        main()
