"""Paper Tables 2-4: training time + final objective for 5 solvers x
2 step rules x 3 sampling schemes on a memmapped dataset.

Every cell is one ``ExperimentSpec`` lowered by ``repro.api.plan`` and run
by ``execute`` — the benchmark owns NO execution wiring anymore.  The
planner picks the backend per cell:

* default — ``placement='streamed'`` forces the paper's regime (data
  streams from storage each epoch): DataPipeline prefetch (access time),
  DeviceStager double buffering (H2D time), and the chunked epoch engine
  scanning K staged batches per device call (compute time).
* ``--sparse`` — CSR corpus sweep over ``--densities`` x schemes through
  the ``sparse-csr`` backend; emits the ``BENCH_sparse.json`` schema with
  nnz-proportional access-MB columns.  This is the paper's largest-win
  regime (news20/rcv1-like data).
* ``--resident`` — fused host mode: the corpus is staged on device ONCE
  and epochs run fully in-graph; the avoided per-epoch restaging is
  reported as ``h2d_saved_s_per_epoch``.  On TPU the planner also selects
  the fused Pallas kernels for constant-step cells automatically.

The access/H2D/compute breakdown per scheme comes straight from
``RunResult.breakdown()`` and is printed and written to ``BENCH_erm.json``
so the perf trajectory is tracked across PRs.

Output CSV (stdout): name,us_per_call,derived where name =
erm_<solver>_<stepmode>_<scheme>, us_per_call = training time per epoch
(us), derived = final objective + breakdown + speedup vs RS.

Default scale is a laptop-class reduction (the paper used 11M-point HIGGS on
a MacBook; CI-friendly defaults reproduce the *ratios*, and --rows/--epochs
scale it up).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from functools import partial
from pathlib import Path

import jax
import numpy as np

from repro import compile_cache
from repro.api import (AUTO, CONSTANT, DataSource, ExperimentSpec,
                       LINE_SEARCH, LS_MODES, RESIDENT, SEQUENTIAL, SOLVERS,
                       STREAMED, TracePolicy, VECTORIZED, execute, plan)

# --ls-mode both: time BOTH ls rules per LS cell, interleaved, and report
# the vectorized row with the sequential baseline alongside — the only
# comparison that survives a noisy shared machine (see benchmarks/README)
BOTH = "both"
from repro.core import samplers
from repro.data import dataset, sparse

DEFAULT_JSON = Path(__file__).resolve().parent / "BENCH_erm.json"
DEFAULT_SPARSE_JSON = Path(__file__).resolve().parent / "BENCH_sparse.json"
DEFAULT_SUPERCELL_JSON = (Path(__file__).resolve().parent
                          / "BENCH_supercell.json")
DEFAULT_ADAPTIVE_JSON = (Path(__file__).resolve().parent
                         / "BENCH_adaptive.json")

# the adaptive table: the three uniform schemes plus the two new ones
ADAPTIVE_SCHEMES = ("random", "cyclic", "systematic",
                    "chunk_importance", "stochastic_batch")


def _annotate_vs_rs(r, times, access):
    """Fill the vs-RS ratio columns; schemes iterate with random FIRST."""
    times[r["scheme"]] = r["epoch_s"]
    access[r["scheme"]] = r["access_s_per_epoch"]
    r["speedup_vs_rs"] = (times["random"] / r["epoch_s"]
                          if "random" in times else 1.0)
    # resident cells all perform the identical one-time contiguous read —
    # an access ratio there would report only timer jitter
    if (not r.get("resident") and "random" in access
            and r["access_s_per_epoch"] > 0):
        r["access_ratio_vs_rs"] = (access["random"]
                                   / r["access_s_per_epoch"])


def run_one(corpus: Path, solver: str, step_mode: str, scheme: str, *,
            batch: int, epochs: int, reg: float = 1e-4,
            chunk: int | None = None, prefetch: int = 2,
            resident: bool = False, ls_mode: str = AUTO, mesh=None,
            reduction: str = AUTO, trace_dir: Path | None = None):
    """Train and time one (solver, step rule, scheme) cell through
    plan()/execute(); returns the BENCH_erm result-dict schema.  LS cells
    carry the resolved ``ls_mode`` column (``vectorized`` trial-ladder
    sweep by default; ``--ls-mode sequential`` re-times the old
    per-batch backtracking ``while_loop`` baseline).  With ``mesh`` the
    planner lowers to the sharded backends and the row gains ``devices`` /
    per-device H2D columns.  ``trace_dir`` writes the cell's Chrome trace
    to ``<dir>/<row-name>.json`` (repeats overwrite — the file holds the
    last measurement; note the spans themselves add a small overhead the
    timing columns then include, see benchmarks/README)."""
    spec = ExperimentSpec(
        data=DataSource.corpus(corpus), loss="logistic", reg=reg,
        solver=solver, scheme=scheme, step_mode=step_mode, ls_mode=ls_mode,
        batch_size=batch, epochs=epochs, chunk=chunk, prefetch=prefetch,
        placement=RESIDENT if resident else STREAMED,
        record_objective=False, mesh=mesh, reduction=reduction)
    p = plan(spec)
    name = (f"erm_{solver}_{step_mode}_{scheme}"
            + ("_resident" if resident else "")
            + (f"_d{p.shards}" if p.shards > 1 else ""))
    if trace_dir is not None:
        # shard-count suffix comes from the plan, so attach the policy and
        # re-plan (planning is pure validation — cheap) with the final name
        spec = dataclasses.replace(
            spec, trace=TracePolicy(path=Path(trace_dir) / f"{name}.json"))
        p = plan(spec)
    res = execute(p)
    r = {
        "name": name,
        "solver": solver, "step_mode": step_mode, "scheme": scheme,
        "epochs": epochs, "chunk": p.chunk, "backend": p.backend,
        "devices": p.shards,
        **res.breakdown(),
    }
    if step_mode == LINE_SEARCH:
        r["ls_mode"] = p.cfg.ls_mode
    if resident:
        r["resident"] = True
    if p.shards > 1:
        r["reduction"] = p.reduction
    return r


def run_one_sparse(corpus: Path, solver: str, step_mode: str, scheme: str, *,
                   batch: int, epochs: int, reg: float = 1e-4,
                   chunk: int | None = None, prefetch: int = 2,
                   trace_dir: Path | None = None, tag: str = ""):
    """Sparse (CSR) counterpart of :func:`run_one`: the planner routes the
    cell through the ``sparse-csr`` backend (SparsePipeline streaming
    padded-ELL batches into the sparse chunked epoch engine) and access
    bytes are nnz-proportional — the regime where the paper's RS-vs-CS/SS
    gap is widest.  ``tag`` lands in the row name AND the trace filename
    (the density suffix — so per-density traces don't overwrite)."""
    name = f"erm_sparse_{solver}_{step_mode}_{scheme}{tag}"
    spec = ExperimentSpec(
        data=DataSource.corpus(corpus), loss="logistic", reg=reg,
        solver=solver, scheme=scheme, step_mode=step_mode,
        batch_size=batch, epochs=epochs, chunk=chunk, prefetch=prefetch,
        record_objective=False,
        trace=(TracePolicy(path=Path(trace_dir) / f"{name}.json")
               if trace_dir is not None else None))
    p = plan(spec)
    res = execute(p)
    return {
        "name": name,
        "solver": solver, "step_mode": step_mode, "scheme": scheme,
        "epochs": epochs, "chunk": p.chunk, "backend": p.backend,
        "sparse": True, "density": p.density, "kmax": p.kmax, "nnz": p.nnz,
        **res.breakdown(),
    }


def _derived_csv(r) -> str:
    s = (f"objective={r['objective']:.10f};"
         f"access_ms={r['access_s_per_epoch']*1e3:.3f};"
         f"h2d_ms={r['h2d_s_per_epoch']*1e3:.3f};"
         f"compute_ms={r['compute_s_per_epoch']*1e3:.3f};"
         f"access_mb={r['access_mb_per_epoch']:.3f};"
         f"speedup_vs_rs={r['speedup_vs_rs']:.2f}")
    if "h2d_saved_s_per_epoch" in r:
        s += f";h2d_saved_ms={r['h2d_saved_s_per_epoch']*1e3:.3f}"
    if "access_ratio_vs_rs" in r:
        s += f";access_ratio_vs_rs={r['access_ratio_vs_rs']:.2f}"
    return s


def main(rows=100_000, features=64, batch=500, epochs=3,
         solvers_=SOLVERS, corpus_dir=Path("artifacts/bench"),
         chunk=None, json_out=None, resident=False, ls_mode=AUTO,
         repeats=1, devices=1, reduction=AUTO, trace_dir=None):
    if trace_dir is not None:
        Path(trace_dir).mkdir(parents=True, exist_ok=True)
    corpus_dir.mkdir(parents=True, exist_ok=True)
    corpus = corpus_dir / f"erm_{rows}x{features}.bin"
    if not corpus.exists():
        dataset.synth_erm_corpus(corpus, rows=rows, features=features)
    mesh = None
    if devices > 1:
        if len(jax.devices()) < devices:
            raise SystemExit(
                f"--devices {devices} but only {len(jax.devices())} jax "
                f"devices visible; on CPU run under XLA_FLAGS="
                f"--xla_force_host_platform_device_count={devices}")
        mesh = jax.make_mesh((devices,), ("data",))
    out, results = [], []
    for solver in solvers_:
        for step_mode in (CONSTANT, LINE_SEARCH):
            times, access = {}, {}
            for scheme in samplers.SCHEMES:
                cell = partial(run_one, corpus, solver, step_mode, scheme,
                               batch=batch, epochs=epochs, chunk=chunk,
                               resident=resident, mesh=mesh,
                               reduction=reduction if mesh is not None
                               else AUTO, trace_dir=trace_dir)
                if step_mode == LINE_SEARCH and ls_mode == BOTH:
                    # interleave the two rules within each repeat so the
                    # comparison is time-local (shared machines drift by
                    # 2x between runs minutes apart), keep the min epoch
                    # per rule, report the vectorized row with the
                    # sequential baseline alongside
                    best = {}
                    for _ in range(repeats):
                        for m in (SEQUENTIAL, VECTORIZED):
                            rr = cell(ls_mode=m)
                            if (m not in best
                                    or rr["epoch_s"] < best[m]["epoch_s"]):
                                best[m] = rr
                    r = best[VECTORIZED]
                    r["sequential_epoch_s"] = best[SEQUENTIAL]["epoch_s"]
                    r["ls_speedup_vs_sequential"] = (
                        best[SEQUENTIAL]["epoch_s"] / r["epoch_s"])
                else:
                    r = None
                    # constant cells under --ls-mode both: no rule to A/B
                    mode = AUTO if ls_mode == BOTH else ls_mode
                    for _ in range(repeats):
                        rr = cell(ls_mode=mode)
                        if r is None or rr["epoch_s"] < r["epoch_s"]:
                            r = rr
                _annotate_vs_rs(r, times, access)
                results.append(r)
                out.append((r["name"], r["epoch_s"] * 1e6, _derived_csv(r)))
    if json_out:
        payload = {
            "meta": {"schema": 1, "rows": rows, "features": features,
                     "batch": batch, "epochs": epochs, "resident": resident,
                     "ls_mode": (ls_mode if ls_mode != AUTO
                                 else "vectorized"),
                     "repeats": repeats, "devices": devices,
                     "backend": jax.default_backend(),
                     "unit": "seconds per epoch"},
            "results": results,
        }
        Path(json_out).write_text(json.dumps(payload, indent=2) + "\n")
    return out


def main_sparse(rows=100_000, features=65_536, batch=500, epochs=3,
                densities=(0.0005, 0.002), solvers_=("mbsgd",),
                corpus_dir=Path("artifacts/bench"), chunk=None,
                json_out=None, trace_dir=None):
    """Sparse trajectory: access/H2D/compute per scheme x density.

    Constant step only (the paper's sparse tables are dominated by access
    time, which line search does not change); ``access_ratio_vs_rs`` is the
    headline column — expected to EXCEED the dense run's ratio at matched
    scale, since RS pays a seek per row segment while CS/SS read one
    contiguous nnz-proportional range.  The default width is news20-like
    (65536 features): narrow sparse corpora fit entirely in CPU cache,
    where no access pattern can matter.
    """
    if trace_dir is not None:
        Path(trace_dir).mkdir(parents=True, exist_ok=True)
    corpus_dir.mkdir(parents=True, exist_ok=True)
    out, results = [], []
    for density in densities:
        corpus = corpus_dir / f"erm_sparse_{rows}x{features}_d{density}.csr"
        if not (corpus / "meta.json").exists():
            sparse.synth_sparse_classification(
                corpus, rows=rows, features=features, density=density)
        for solver in solvers_:
            times, access = {}, {}
            for scheme in samplers.SCHEMES:
                r = run_one_sparse(corpus, solver, CONSTANT, scheme,
                                   batch=batch, epochs=epochs, chunk=chunk,
                                   trace_dir=trace_dir, tag=f"_d{density}")
                _annotate_vs_rs(r, times, access)
                results.append(r)
                out.append((r["name"], r["epoch_s"] * 1e6, _derived_csv(r)))
    if json_out:
        payload = {
            "meta": {"schema": 1, "sparse": True, "rows": rows,
                     "features": features, "densities": list(densities),
                     "batch": batch, "epochs": epochs,
                     "backend": jax.default_backend(),
                     "unit": "seconds per epoch"},
            "results": results,
        }
        Path(json_out).write_text(json.dumps(payload, indent=2) + "\n")
    return out


def main_supercell(rows=100_000, features=64, batch=500, epochs=3, cells=8,
                   solver="saga", scheme="systematic",
                   corpus_dir=Path("artifacts/bench"), chunk=None,
                   json_out=None):
    """Super-cell amortization bench: S plan-compatible cells (one solver,
    S step sizes) ride ONE staged stream vs S sequential solo runs.

    Emits the ``BENCH_supercell.json`` schema: the solo per-cell
    access/H2D baseline; the S-cell amortized per-cell costs with the
    headline ``access_h2d_amortization`` ratio (expected ~S: the shared
    stream does the same read/convert/H2D work ONCE for S cells) and
    ``trajectory_max_dw`` — the max |w_solo - w_supercell| across cells,
    exactly 0.0 (the super-cell contract, see tests/test_supercell.py);
    and the train-wall comparison (span-measured epoch time, compile
    excluded).
    """
    import numpy as np

    from repro.api import execute_supercell

    corpus_dir.mkdir(parents=True, exist_ok=True)
    corpus = corpus_dir / f"erm_{rows}x{features}.bin"
    if not corpus.exists():
        dataset.synth_erm_corpus(corpus, rows=rows, features=features)
    steps = [0.01 + 0.01 * i for i in range(cells)]
    specs = [ExperimentSpec(
        data=DataSource.corpus(corpus), loss="logistic", reg=1e-4,
        solver=solver, scheme=scheme, step_mode=CONSTANT,
        step_size=float(s), batch_size=batch, epochs=epochs, chunk=chunk,
        placement=STREAMED, record_objective=False) for s in steps]
    plans = [plan(s) for s in specs]
    solos = [execute(p) for p in plans]
    supers = execute_supercell(plans)

    mean = lambda xs: sum(xs) / len(xs)                      # noqa: E731
    ah = lambda b: b["access_s_per_epoch"] + b["h2d_s_per_epoch"]  # noqa: E731
    solo_b = [r.breakdown() for r in solos]
    sup_b = [r.breakdown() for r in supers]
    solo_ah, sup_ah = mean([ah(b) for b in solo_b]), mean([ah(b) for b in sup_b])

    # train_s sums are span-measured epoch walls (compile/warmup excluded);
    # the supercell's per-cell train_s is wall/S, so the sum IS its wall
    solo_wall = sum(r.train_s for r in solos)
    super_wall = sum(r.train_s for r in supers)

    def _row(tag, rs, bs, n_cells):
        return {"name": f"supercell_{tag}_{solver}_{scheme}",
                "solver": solver, "scheme": scheme, "cells": n_cells,
                "backend": rs[0].plan.backend, "chunk": rs[0].plan.chunk,
                "epochs": epochs,
                "epoch_s": mean([b["epoch_s"] for b in bs]),
                "access_s_per_epoch": mean([b["access_s_per_epoch"]
                                            for b in bs]),
                "h2d_s_per_epoch": mean([b["h2d_s_per_epoch"] for b in bs]),
                "compute_s_per_epoch": mean([b["compute_s_per_epoch"]
                                             for b in bs]),
                "objective": mean([b["objective"] for b in bs])}

    r_solo = _row("solo", solos, solo_b, 1)
    r_sup = _row(f"s{cells}", supers, sup_b, cells)
    r_sup["access_h2d_amortization"] = (solo_ah / sup_ah
                                        if sup_ah > 0 else float("inf"))
    r_sup["trajectory_max_dw"] = max(float(np.max(np.abs(s.w - c.w)))
                                     for s, c in zip(solos, supers))
    r_wall = {"name": f"supercell_wall_{solver}_{scheme}",
              "solver": solver, "scheme": scheme, "cells": cells,
              "epochs": epochs, "solo_train_wall_s": solo_wall,
              "supercell_train_wall_s": super_wall,
              "wall_speedup": (solo_wall / super_wall
                               if super_wall > 0 else float("inf"))}
    results = [r_solo, r_sup, r_wall]
    if json_out:
        payload = {"meta": {"schema": 1, "supercell": True, "rows": rows,
                            "features": features, "batch": batch,
                            "epochs": epochs, "cells": cells,
                            "solver": solver, "scheme": scheme,
                            "backend": jax.default_backend(),
                            "unit": "seconds per epoch"},
                   "results": results}
        Path(json_out).write_text(json.dumps(payload, indent=2) + "\n")
    out = []
    for r in (r_solo, r_sup):
        d = (f"objective={r['objective']:.10f};"
             f"access_ms={r['access_s_per_epoch']*1e3:.3f};"
             f"h2d_ms={r['h2d_s_per_epoch']*1e3:.3f};"
             f"compute_ms={r['compute_s_per_epoch']*1e3:.3f}")
        if "access_h2d_amortization" in r:
            d += (f";access_h2d_amortization="
                  f"{r['access_h2d_amortization']:.2f}"
                  f";trajectory_max_dw={r['trajectory_max_dw']:.1e}")
        out.append((r["name"], r["epoch_s"] * 1e6, d))
    out.append((r_wall["name"], super_wall * 1e6,
                f"solo_wall_s={solo_wall:.3f};"
                f"supercell_wall_s={super_wall:.3f};"
                f"wall_speedup={r_wall['wall_speedup']:.2f}"))
    return out


def synth_heterogeneous_libsvm(path: Path, *, rows: int, features: int,
                               batch: int, seed: int = 0,
                               hard_every: int = 10, hard_scale: float = 25.0,
                               nnz: int = 30, easy_sep: float = 3.0,
                               flip: float = 0.25) -> None:
    """Write a block-heterogeneous LIBSVM text file (news20-like shape).

    Rows come in contiguous blocks of ``batch`` (the chunk granularity
    :class:`~repro.core.schemes.ChunkImportance` stages).  Every
    ``hard_every``-th block is HARD: rows live on the rare quarter of the
    feature space with ``hard_scale``-times larger values and ``flip``
    label noise — non-separable, so their logistic curvature never
    saturates and their loss floor stays high.  The rest are EASY:
    well-separated rows on the common three quarters that a couple of
    passes drive to near-zero loss.  One constant step size serves both
    regimes only if it is small enough for the stiff hard blocks — which
    is exactly the regime where loss-proportional chunk importance
    sampling wins epoch-wise: its ``1/(m p_j)`` weights shrink the
    effective step on the oversampled stiff blocks (many small stable
    steps per epoch) while the uniform schemes take one full-size
    oscillating step each visit.  See benchmarks/README."""
    rng = np.random.default_rng(seed)
    rare0 = (features * 3) // 4
    w_common = rng.normal(size=rare0)
    w_rare = rng.normal(size=features - rare0)
    with open(path, "w") as fh:
        for r in range(rows):
            if (r // batch) % hard_every == 0:
                cols = np.sort(rng.choice(features - rare0, size=nnz,
                                          replace=False)) + rare0
                vals = (rng.normal(size=nnz) * hard_scale).astype(np.float32)
                y = 1.0 if vals @ w_rare[cols - rare0] >= 0 else -1.0
                if rng.random() < flip:
                    y = -y
            else:
                cols = np.sort(rng.choice(rare0, size=nnz, replace=False))
                wv = w_common[cols]
                y = 1.0 if rng.random() < 0.5 else -1.0
                vals = (y * easy_sep * wv / max(np.linalg.norm(wv), 1e-9)
                        + rng.normal(size=nnz)).astype(np.float32)
            fh.write(f"{y:+.0f} " + " ".join(
                f"{c + 1}:{v:.5f}" for c, v in zip(cols, vals)) + "\n")


def run_one_adaptive(corpus: Path, scheme: str, *, batch: int, epochs: int,
                     step: float, reg: float = 1e-6, solver: str = "mbsgd",
                     prefetch: int = 2):
    """One scheme row of the adaptive table: constant-step ``solver`` with
    the per-epoch objective trace recorded (the epochs-to-tolerance axis
    needs it).  Adaptive schemes are planned exactly like uniform ones —
    the planner forces streamed placement and zero prefetch itself."""
    spec = ExperimentSpec(
        data=DataSource.corpus(corpus), loss="logistic", reg=reg,
        solver=solver, scheme=scheme, step_mode=CONSTANT, step_size=step,
        batch_size=batch, epochs=epochs, prefetch=prefetch,
        record_objective=True)
    p = plan(spec)
    res = execute(p)
    return {
        "name": f"erm_adaptive_{solver}_{scheme}",
        "solver": solver, "scheme": scheme,
        "scheme_params": p.scheme_obj.params(),
        "epochs": epochs, "chunk": p.chunk, "backend": p.backend,
        "history": [round(float(h), 6) for h in res.history],
        **res.breakdown(),
    }


def _epochs_to(history, tol):
    for e, h in enumerate(history):
        if h <= tol:
            return e + 1
    return None


def main_adaptive(rows=40_000, features=4096, batch=500, epochs=12,
                  step=0.5, corpus_dir=Path("artifacts/bench"),
                  json_out=None, libsvm=None, solver="mbsgd",
                  tol_rtol=0.002, seed=0):
    """Adaptive-scheme trajectory: access time AND epochs-to-tolerance for
    the five schemes on one CSR corpus ingested through
    :func:`repro.data.sparse.ingest_libsvm`.

    ``--libsvm`` points at a real LIBSVM text file (news20.binary,
    rcv1_train.binary); without it a block-heterogeneous synthetic corpus
    with the same access profile is generated and ingested through the
    SAME text path — the ``meta.source`` column says which one a committed
    artifact measured.

    Tolerance is the uniform-CS (cyclic) FINAL objective at the epoch
    budget, relaxed by ``tol_rtol``; ``epochs_to_tol`` is the first epoch
    at or under it.  The headline block asserts the PR 10 acceptance
    criteria: chunk_importance keeps >= 80% of the best uniform
    contiguous scheme's access advantage over RS while reaching the
    tolerance in fewer epochs than both CS and SS."""
    corpus_dir.mkdir(parents=True, exist_ok=True)
    if libsvm is not None:
        src = Path(libsvm)
        source = src.name
        corpus = corpus_dir / (src.stem + ".csr")
        if not (corpus / "meta.json").exists():
            sparse.ingest_libsvm(src, corpus)
    else:
        source = "synthetic block-heterogeneous libsvm"
        txt = corpus_dir / f"adaptive_{rows}x{features}_b{batch}.libsvm"
        if not txt.exists():
            synth_heterogeneous_libsvm(txt, rows=rows, features=features,
                                       batch=batch, seed=seed)
        corpus = corpus_dir / f"adaptive_{rows}x{features}_b{batch}.csr"
        if not (corpus / "meta.json").exists():
            sparse.ingest_libsvm(txt, corpus, features=features)
    out, results = [], []
    times, access = {}, {}
    for scheme in ADAPTIVE_SCHEMES:
        r = run_one_adaptive(corpus, scheme, batch=batch, epochs=epochs,
                             step=step, solver=solver)
        _annotate_vs_rs(r, times, access)
        results.append(r)
    tol = None
    by = {r["scheme"]: r for r in results}
    if "cyclic" in by:
        tol = by["cyclic"]["history"][-1] * (1.0 + tol_rtol)
        for r in results:
            r["epochs_to_tol"] = _epochs_to(r["history"], tol)
    headline = {}
    if tol is not None and all(s in by for s in ADAPTIVE_SCHEMES):
        uniform_ratio = min(by["cyclic"].get("access_ratio_vs_rs", 1.0),
                            by["systematic"].get("access_ratio_vs_rs", 1.0))
        ci = by["chunk_importance"]
        e_ci, e_cs = ci["epochs_to_tol"], by["cyclic"]["epochs_to_tol"]
        e_ss = by["systematic"]["epochs_to_tol"]
        headline = {
            "tolerance": tol,
            "uniform_contiguous_access_ratio_vs_rs": uniform_ratio,
            "chunk_importance_access_ratio_vs_rs":
                ci.get("access_ratio_vs_rs"),
            "chunk_importance_access_retention":
                (ci.get("access_ratio_vs_rs", 0.0) / uniform_ratio
                 if uniform_ratio > 0 else None),
            "epochs_to_tol": {s: by[s]["epochs_to_tol"]
                              for s in ADAPTIVE_SCHEMES},
            "acceptance": {
                "access_retention_ge_0.8":
                    ci.get("access_ratio_vs_rs", 0.0) >= 0.8 * uniform_ratio,
                "fewer_epochs_than_uniform_cs_ss":
                    (e_ci is not None
                     and (e_cs is None or e_ci < e_cs)
                     and (e_ss is None or e_ci < e_ss)),
            },
        }
    for r in results:
        d = _derived_csv(r)
        if r.get("epochs_to_tol") is not None:
            d += f";epochs_to_tol={r['epochs_to_tol']}"
        out.append((r["name"], r["epoch_s"] * 1e6, d))
    if json_out:
        payload = {
            "meta": {"schema": 1, "adaptive": True, "source": source,
                     "rows": rows if libsvm is None else None,
                     "features": features if libsvm is None else None,
                     "batch": batch, "epochs": epochs, "step_size": step,
                     "solver": solver, "tol_rtol": tol_rtol,
                     "backend": jax.default_backend(),
                     "unit": "seconds per epoch",
                     "headline": headline},
            "results": results,
        }
        Path(json_out).write_text(json.dumps(payload, indent=2) + "\n")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=None,
                    help="default: 100000 (40000 adaptive)")
    ap.add_argument("--features", type=int, default=None,
                    help="default: 64 dense, 65536 sparse")
    ap.add_argument("--batch", type=int, default=500)
    ap.add_argument("--epochs", type=int, default=None,
                    help="default: 3 (12 adaptive — the epochs-to-tolerance\n                    axis needs headroom)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="batches per device call (default: planner budget)")
    ap.add_argument("--solvers", type=str, default=None,
                    help="comma-separated subset of " + ",".join(SOLVERS)
                         + " (default: all dense, mbsgd sparse)")
    ap.add_argument("--sparse", action="store_true",
                    help="CSR corpus sweep: schemes x --densities, "
                         f"emitting the {DEFAULT_SPARSE_JSON.name} schema")
    ap.add_argument("--adaptive", action="store_true",
                    help="five-scheme adaptive table (access time + "
                         "epochs-to-tolerance) on a LIBSVM-ingested CSR "
                         f"corpus, emitting the {DEFAULT_ADAPTIVE_JSON.name} "
                         "schema")
    ap.add_argument("--libsvm", type=Path, default=None, metavar="FILE",
                    help="adaptive mode: ingest this real LIBSVM text file "
                         "(news20.binary/rcv1) instead of the synthetic "
                         "block-heterogeneous corpus")
    ap.add_argument("--step", type=float, default=0.5,
                    help="adaptive mode: the shared constant step size")
    ap.add_argument("--tol-rtol", type=float, default=0.002,
                    help="adaptive mode: relative slack on the cyclic-final "
                         "tolerance target")
    ap.add_argument("--cells", type=int, default=None, metavar="S",
                    help="super-cell amortization bench: S step-size cells "
                         "of one solver ride a single staged stream vs S "
                         "sequential solo runs, emitting the "
                         f"{DEFAULT_SUPERCELL_JSON.name} schema")
    ap.add_argument("--densities", type=str, default="0.0005,0.002",
                    help="comma-separated nnz densities (sparse mode)")
    ap.add_argument("--resident", action="store_true",
                    help="fused host mode: stage the corpus on device once "
                         "and run epochs in-graph (dense only)")
    ap.add_argument("--ls-mode", choices=(AUTO, BOTH) + LS_MODES,
                    default=AUTO,
                    help="line-search cells: vectorized trial-ladder sweep "
                         "(default), the sequential backtracking while_loop "
                         "baseline, or 'both' — time the two interleaved "
                         "and record the sequential baseline next to the "
                         "vectorized row")
    ap.add_argument("--repeats", type=int, default=1,
                    help="measurements per cell; the minimal-epoch_s run "
                         "is kept (noise floor on shared machines)")
    ap.add_argument("--devices", type=int, default=1,
                    help="data-parallel mesh width: chunks stage sharded "
                         "across this many devices and every row gains a "
                         "devices column; on CPU run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--reduction", choices=(AUTO, "gather", "psum"),
                    default=AUTO,
                    help="sharded combine mode: gather (default; bit-"
                         "identical to single host, access-sharded) or "
                         "psum (compute-sharded, ulp-level drift)")
    ap.add_argument("--json-out", type=Path, default=None,
                    help=f"write the breakdown JSON here; opt-in so ad-hoc "
                         f"runs don't clobber the committed {DEFAULT_JSON.name}"
                         f"/{DEFAULT_SPARSE_JSON.name}")
    ap.add_argument("--trace", type=Path, default=None, metavar="DIR",
                    help="write a Chrome trace per cell under DIR "
                         "(<row-name>.json); span recording adds a small "
                         "overhead the timing columns then include — don't "
                         "compare traced timings against untraced baselines")
    a = ap.parse_args()
    if a.sparse and a.resident:
        ap.error("--resident stages a dense corpus; drop --sparse")
    if a.adaptive and (a.sparse or a.resident or a.cells is not None
                       or a.devices > 1):
        ap.error("--adaptive is its own table; drop "
                 "--sparse/--resident/--cells/--devices")
    if a.libsvm is not None and not a.adaptive:
        ap.error("--libsvm only feeds the --adaptive table")
    if a.cells is not None:
        if a.cells < 2:
            ap.error("--cells S needs S >= 2 (S=1 IS the solo baseline)")
        if a.sparse or a.resident or a.devices > 1:
            ap.error("--cells times the streamed dense super-cell; drop "
                     "--sparse/--resident/--devices")
    if a.devices > 1:
        if a.sparse:
            ap.error("--devices shards dense chunks; sharded CSR staging "
                     "is a follow-on — drop --sparse")
        if a.batch % a.devices:
            ap.error(f"--batch {a.batch} must divide across --devices "
                     f"{a.devices} (the planner rejects uneven shards)")
    elif a.reduction != AUTO:
        # surface the mistake the planner would catch, instead of silently
        # benchmarking single-host rows labeled as a sharded request
        ap.error(f"--reduction {a.reduction} needs --devices N>1 "
                 f"(it picks how a mesh combines per-device work)")
    compile_cache.enable()
    rows_n = a.rows or (40_000 if a.adaptive else 100_000)
    epochs_n = a.epochs or (12 if a.adaptive else 3)
    if a.adaptive:
        rows_out = main_adaptive(
            rows_n, a.features or 4096, a.batch, epochs_n, step=a.step,
            json_out=a.json_out, libsvm=a.libsvm,
            solver=(a.solvers or "mbsgd").split(",")[0],
            tol_rtol=a.tol_rtol)
    elif a.cells is not None:
        rows_out = main_supercell(
            rows_n, a.features or 64, a.batch, epochs_n, cells=a.cells,
            solver=(a.solvers or "saga").split(",")[0], chunk=a.chunk,
            json_out=a.json_out)
    elif a.sparse:
        sel = tuple(s for s in (a.solvers or "mbsgd").split(",") if s)
        rows_out = main_sparse(
            rows_n, a.features or 65_536, a.batch, epochs_n,
            densities=tuple(float(d) for d in a.densities.split(",") if d),
            solvers_=sel, chunk=a.chunk, json_out=a.json_out,
            trace_dir=a.trace)
    else:
        sel = tuple(s for s in (a.solvers or ",".join(SOLVERS)).split(",")
                    if s)
        rows_out = main(rows_n, a.features or 64, a.batch, epochs_n,
                        solvers_=sel, chunk=a.chunk, json_out=a.json_out,
                        resident=a.resident, ls_mode=a.ls_mode,
                        repeats=a.repeats, devices=a.devices,
                        reduction=a.reduction, trace_dir=a.trace)
    for name, us, derived in rows_out:
        print(f"{name},{us:.2f},{derived}")
