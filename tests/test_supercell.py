"""Super-cell execution + the coalescing experiment service.

The contract under test: S plan-compatible cells driven off ONE staged
data stream produce per-cell trajectories BIT-IDENTICAL to their solo
``execute()`` runs, with the shared access/H2D cost attributed per cell
as ``shared / S`` (so ``verify_timeline`` still reconciles per cell), and
checkpoints/resume behaving exactly as the solo runs would.  The
coalescer must NEVER co-batch plan-incompatible specs, and the service
front-end must contain any per-request failure without sinking the queue.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.api import (CheckpointPolicy, DataSource, ExperimentSpec,
                       RESIDENT, STREAMED, TracePolicy, execute,
                       execute_supercell, coalesce, plan, resume_from,
                       serve, supercell_key)
from repro.core import synth_classification
from repro.core.supercell import DEFAULT_MAX_CELLS
from repro.data import dataset, sparse
from tests.util import REPO, run_py

ROWS, FEATS, B = 600, 12, 50
SOLVER_CELLS = (("mbsgd", 0.02), ("sag", 0.05), ("saga", 0.05),
                ("saga", 0.1), ("svrg", 0.05), ("saag2", 0.05))


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("supercell") / "dense.bin"
    dataset.synth_erm_corpus(path, rows=ROWS, features=FEATS, seed=5)
    return path


@pytest.fixture(scope="module")
def csr_corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("supercell") / "sparse.csr"
    sparse.synth_sparse_classification(path, rows=400, features=512,
                                       density=0.02, seed=3)
    return path


def _spec(data, solver="saga", step=0.05, **kw):
    kw.setdefault("scheme", "systematic")
    kw.setdefault("batch_size", B)
    kw.setdefault("epochs", 3)
    kw.setdefault("seed", 3)
    return ExperimentSpec(data=data, solver=solver, step_size=step, **kw)


def _assert_cellwise_identical(solos, supers):
    assert len(solos) == len(supers)
    for s, r in zip(solos, supers):
        np.testing.assert_array_equal(s.w, r.w)
        np.testing.assert_array_equal(s.history, r.history)
        assert s.epochs_done == r.epochs_done
        assert s.sampler_state == r.sampler_state


# ------------------------------------------------- bit-identical parity ----

@pytest.mark.parametrize("placement", [STREAMED, RESIDENT])
@pytest.mark.parametrize("scheme", ["random", "cyclic", "systematic"])
def test_supercell_bitwise_matches_solo_all_solvers(corpus, placement,
                                                    scheme):
    """All 5 solvers (plus a second saga step cell) in ONE super-cell,
    per scheme and placement: every cell lands bit-identically on its
    solo trajectory."""
    plans = [plan(_spec(DataSource.corpus(corpus), solver=s, step=a,
                        scheme=scheme, placement=placement))
             for s, a in SOLVER_CELLS]
    solos = [execute(p) for p in plans]
    supers = execute_supercell(plans)
    _assert_cellwise_identical(solos, supers)


def test_supercell_line_search_cell_matches_solo(corpus):
    plans = [plan(_spec(DataSource.corpus(corpus), solver=s, step=a,
                        step_mode=m, placement=STREAMED))
             for s, a, m in (("mbsgd", 1.0, "line_search"),
                             ("saga", 0.05, "constant"),
                             ("saga", 0.1, "constant"))]
    solos = [execute(p) for p in plans]
    supers = execute_supercell(plans)
    _assert_cellwise_identical(solos, supers)


def test_supercell_arrays_resident_matches_solo():
    X, y, _ = synth_classification(jax.random.PRNGKey(0), ROWS, FEATS,
                                   separation=2.0)
    plans = [plan(_spec(DataSource.arrays(X, y), solver=s, step=a,
                        scheme="random"))
             for s, a in (("saga", 0.05), ("saga", 0.1), ("svrg", 0.05),
                          ("mbsgd", 0.02))]
    solos = [execute(p) for p in plans]
    supers = execute_supercell(plans)
    _assert_cellwise_identical(solos, supers)


def test_supercell_sparse_csr_matches_solo(csr_corpus):
    plans = [plan(_spec(DataSource.corpus(csr_corpus), solver=s, step=a,
                        batch_size=40, epochs=2))
             for s, a in (("saga", 0.05), ("saga", 0.1), ("svrg", 0.05))]
    assert all(p.backend == "sparse-csr" for p in plans)
    solos = [execute(p) for p in plans]
    supers = execute_supercell(plans)
    _assert_cellwise_identical(solos, supers)


@pytest.mark.parametrize("placement", [STREAMED, RESIDENT])
def test_single_cell_supercell_is_exactly_solo(corpus, placement):
    p = plan(_spec(DataSource.corpus(corpus), placement=placement))
    [r] = execute_supercell([p])
    s = execute(p)
    np.testing.assert_array_equal(s.w, r.w)
    np.testing.assert_array_equal(s.history, r.history)


# ------------------------------------------------------------ coalescer ----

def _pool(corpus, other_corpus):
    data = DataSource.corpus(corpus)
    other = DataSource.corpus(other_corpus)
    return [
        _spec(data, solver="saga", step=0.05),
        _spec(data, solver="saga", step=0.1),          # same plan: groups
        _spec(data, solver="mbsgd", step=0.02),        # same plan: groups
        _spec(data, solver="saga", step=0.05, seed=9),          # seed
        _spec(data, solver="saga", step=0.05, batch_size=100),  # batch
        _spec(data, solver="saga", step=0.05, scheme="random"), # scheme
        _spec(data, solver="saga", step=0.05, epochs=5),        # budget
        _spec(other, solver="saga", step=0.05),                 # corpus
    ]


def test_coalesce_never_groups_incompatible_plans(corpus, tmp_path):
    other = tmp_path / "other.bin"
    dataset.synth_erm_corpus(other, rows=ROWS, features=FEATS, seed=6)
    plans = [plan(s) for s in _pool(corpus, other)]
    batches = coalesce(plans)
    # exact partition of the inputs
    seen = sorted(i for b in batches for i in b.indices)
    assert seen == list(range(len(plans)))
    # within every batch all plans share one non-None key
    for b in batches:
        keys = {supercell_key(p) for p in b.plans}
        assert len(keys) == 1
        if b.size > 1:
            assert b.key is not None
    # across batches, co-batched pairs always share keys; the 5 mutated
    # specs must each sit alone
    batch_of = {}
    for bi, b in enumerate(batches):
        for i in b.indices:
            batch_of[i] = bi
    assert batch_of[0] == batch_of[1] == batch_of[2]
    singles = [batch_of[i] for i in range(3, 8)]
    assert len(set(singles)) == 5
    assert all(batches[bi].size == 1 for bi in singles)


def test_coalesce_caps_group_width(corpus):
    plans = [plan(_spec(DataSource.corpus(corpus), step=0.01 * (i + 1)))
             for i in range(DEFAULT_MAX_CELLS + 3)]
    batches = coalesce(plans)
    assert [b.size for b in batches] == [DEFAULT_MAX_CELLS, 3]
    batches = coalesce(plans, max_cells=4)
    assert [b.size for b in batches] == [4, 4, 3]


def test_fused_and_sharded_plans_fall_back_solo(corpus):
    fused = plan(_spec(DataSource.corpus(corpus), placement=RESIDENT,
                       kernel="fused"))
    assert supercell_key(fused) is None
    if jax.device_count() >= 2:
        mesh = jax.make_mesh((2,), ("data",))
        sharded = plan(_spec(DataSource.corpus(corpus), batch_size=B,
                             placement=RESIDENT, mesh=mesh))
        if sharded.shards > 1:
            assert supercell_key(sharded) is None
    batches = coalesce([fused, fused])
    assert [b.size for b in batches] == [1, 1]
    assert all(b.key is None for b in batches)


def test_execute_supercell_rejects_incompatible_cells(corpus):
    p1 = plan(_spec(DataSource.corpus(corpus), seed=3))
    p2 = plan(_spec(DataSource.corpus(corpus), seed=9))
    with pytest.raises(ValueError, match="data plan"):
        execute_supercell([p1, p2])


# --------------------------------------------------- checkpoint + resume ----

@pytest.mark.parametrize("placement", [STREAMED, RESIDENT])
def test_supercell_segment_resume_matches_uninterrupted(corpus, tmp_path,
                                                        placement):
    """2 epochs coalesced + 2 resumed == 4 uninterrupted solo epochs,
    bitwise, in memory AND from the per-cell checkpoint directories."""
    specs = [_spec(DataSource.corpus(corpus), solver=s, step=a, epochs=4,
                   placement=placement,
                   checkpoint=CheckpointPolicy(
                       tmp_path / placement / f"c{i}"))
             for i, (s, a) in enumerate(
                 (("saga", 0.05), ("svrg", 0.05), ("mbsgd", 0.02)))]
    plans = [plan(s) for s in specs]
    solos = [execute(plan(dataclasses.replace(s, checkpoint=None)))
             for s in specs]
    seg1 = execute_supercell(plans, epochs=2)
    assert all(r.epochs_done == 2 for r in seg1)
    seg2 = execute_supercell(plans, resumes=seg1, epochs=2)
    for s, r in zip(solos, seg2):
        np.testing.assert_array_equal(s.w, r.w)
        np.testing.assert_array_equal(s.history, r.history)
    # the per-cell directories carry the epoch-4 snapshots
    for s, spec in zip(solos, specs):
        rb = resume_from(spec.checkpoint.directory)
        assert rb.epochs_done == 4
        np.testing.assert_array_equal(s.w, rb.w)


def test_supercell_rejects_resume_from_different_plan(corpus):
    p1 = plan(_spec(DataSource.corpus(corpus), step=0.05))
    p2 = plan(_spec(DataSource.corpus(corpus), step=0.1))
    seg = execute_supercell([p1, p2], epochs=1)
    with pytest.raises(ValueError, match="different plan"):
        execute_supercell([p1, p2], resumes=[seg[1], seg[0]], epochs=1)


# ------------------------------------------------- per-cell attribution ----

@pytest.mark.parametrize("placement", [STREAMED, RESIDENT])
def test_supercell_per_cell_timeline_reconciles(corpus, tmp_path, placement):
    specs = [_spec(DataSource.corpus(corpus), solver=s, step=a,
                   placement=placement,
                   trace=TracePolicy(path=tmp_path / f"cell{i}.json"))
             for i, (s, a) in enumerate(
                 (("saga", 0.05), ("saga", 0.1), ("svrg", 0.05)))]
    plans = [plan(s) for s in specs]
    results = execute_supercell(plans)
    S = len(plans)
    shared_access = None
    for r in results:
        assert r.timeline is not None
        report = r.verify_timeline()       # raises on any mismatch
        assert report
        # replayed shared spans carry the amortization width
        access = [e for e in r.timeline.events if e.lane == "access"]
        assert access and all(e.args.get("cells") == S for e in access)
        if shared_access is None:
            shared_access = r.stats.access_s
        else:                              # equal shares of ONE stream
            assert abs(r.stats.access_s - shared_access) < 1e-12
    # the attributed per-cell wall sums back to the real wall of the batch
    assert all(r.train_s == results[0].train_s for r in results)


@pytest.mark.parametrize("placement", [STREAMED, RESIDENT])
def test_supercell_stats_are_amortized(corpus, placement):
    # no read-ahead: a stream then reads exactly the batches it trains on,
    # so solo and super-cell read counts compare exactly
    plans = [plan(_spec(DataSource.corpus(corpus), step=0.01 * (i + 1),
                        placement=placement, prefetch=0))
             for i in range(4)]
    solo = execute(plans[0])
    supers = execute_supercell(plans)
    for r in supers:
        # same batch count as solo, ~quarter the attributed access bytes
        assert r.stats.batches == solo.stats.batches
        assert r.stats.bytes_read == solo.stats.bytes_read // 4


# ------------------------------------------- coalesced sweep, kill-resume ----

SWEEP_SNIPPET = """
import json
from pathlib import Path
import numpy as np
from repro.api import DataSource, ExperimentSpec
from repro.data import dataset
from benchmarks.run import run_sweep

work = Path(r"{work}")
corpus = Path(r"{corpus}")
grid = [ExperimentSpec(data=DataSource.corpus(corpus), solver="saga",
                       scheme="systematic", step_size=s, batch_size=100,
                       epochs=4, placement="streamed")
        for s in (0.02, 0.04, 0.06, 0.08)]
out = run_sweep(grid, coalesce=True, checkpoint_dir=work / "ck",
                round_epochs=1, log=lambda *_: None)
payload = {{"done": [r.epochs_done for _, r in out],
           "ws": [np.asarray(r.w).tolist() for _, r in out]}}
(work / "out_{tag}.json").write_text(json.dumps(payload))
print("SWEEP-DONE", flush=True)
"""


def test_coalesced_sweep_killed_and_restarted_matches_reference(tmp_path):
    """SIGKILL a coalesced checkpointed sweep mid-grid; the restarted
    sweep resumes every cell from its per-cell directory and lands
    bit-identically on the uninterrupted reference."""
    corpus = tmp_path / "corpus.bin"
    dataset.synth_erm_corpus(corpus, rows=ROWS, features=FEATS, seed=7)

    ref = tmp_path / "ref"
    ref.mkdir()
    r = run_py(SWEEP_SNIPPET.format(work=ref, corpus=corpus, tag="ref"),
               timeout=900)
    assert "SWEEP-DONE" in r.stdout, r.stdout + r.stderr

    crash = tmp_path / "crash"
    crash.mkdir()
    proc = subprocess.Popen(
        [sys.executable, "-c",
         SWEEP_SNIPPET.format(work=crash, corpus=corpus, tag="victim")],
        env=os.environ | {"PYTHONPATH": str(REPO / "src")},
        cwd=REPO, stdout=subprocess.PIPE, text=True)
    deadline = time.time() + 600
    while time.time() < deadline:
        if (crash / "ck" / "cell_000" / "LATEST").exists():
            break
        time.sleep(0.2)
    proc.kill()
    proc.wait()

    r2 = run_py(SWEEP_SNIPPET.format(work=crash, corpus=corpus,
                                     tag="survivor"), timeout=900)
    assert "SWEEP-DONE" in r2.stdout, r2.stdout + r2.stderr

    ref_out = json.loads((ref / "out_ref.json").read_text())
    got = json.loads((crash / "out_survivor.json").read_text())
    assert got["done"] == ref_out["done"] == [4, 4, 4, 4]
    for a, b in zip(ref_out["ws"], got["ws"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- service front-end ----

def test_serve_coalesces_contains_errors_and_matches_solo(corpus):
    specs = [_spec(DataSource.corpus(corpus), solver="saga", step=0.05),
             _spec(DataSource.corpus(corpus), solver="saga", step=0.1),
             _spec(DataSource.corpus(corpus), solver="mbsgd", step=0.02),
             _spec(DataSource.corpus(corpus), solver="saga", step=0.05,
                   seed=9),
             ExperimentSpec(data=DataSource.corpus(corpus), solver="nope",
                            epochs=1)]
    outs = serve(specs)
    assert [o.index for o in outs] == list(range(5))
    assert [o.cells for o in outs] == [3, 3, 3, 1, 0]
    assert outs[4].error is not None and "plan" in outs[4].error
    assert all(o.ok for o in outs[:4])
    for o in outs[:4]:
        ref = execute(plan(o.spec))
        np.testing.assert_array_equal(ref.w, o.result.w)


def test_serve_checkpoint_root_resumes_without_rerunning(corpus, tmp_path):
    specs = [_spec(DataSource.corpus(corpus), solver="saga", step=0.05),
             _spec(DataSource.corpus(corpus), solver="saga", step=0.1)]
    root = tmp_path / "svc"
    first = serve(specs, checkpoint_root=root)
    assert all(o.ok and o.cells == 2 for o in first)
    assert (root / "cell_000" / "LATEST").exists()
    again = serve(specs, checkpoint_root=root)
    for a, b in zip(first, again):
        assert b.resumed and b.cells == 0      # restored, nothing re-run
        assert b.result.epochs_done == a.result.epochs_done
        np.testing.assert_array_equal(a.result.w, b.result.w)
