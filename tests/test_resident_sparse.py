"""The resident sparse engine (``resident-sparse``): a CSR corpus read once,
padded to ELL and held whole on the device, epochs driven in-graph.

It must train exactly what the resident dense engine trains on the same
corpus densified: same batch schedule (one ``dynamic_slice`` of ELL rows per
CS/SS batch, one row gather per RS batch), same update.  The two differ only
in float32 summation order (ELL margins sum kmax products per row and the
gradient is a scatter-add, where the dense path runs matrix products), so
weights agree to 1e-5 of their scale and objectives to 1e-5 relative after
two epochs; the trajectories measured 1e-7 apart."""
import types

import jax
import numpy as np
import pytest

from repro.api import (CSR, EAGER, FUSED, RESIDENT, RESIDENT_SPARSE,
                       SPARSE_CSR, ChunkImportance, DataSource,
                       ExperimentSpec, PlanError, TracePolicy, execute,
                       execute_supercell, plan)
from repro.core import samplers, solvers
from repro.core.erm import ERMProblem
from repro.data import pipeline, sparse

ROWS, FEATS, DENSITY, B = 2000, 300, 0.05, 100
TOL = 1e-5


@pytest.fixture(scope="module")
def csr_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("resident_sparse") / "c.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=3)
    return path


@pytest.fixture(scope="module")
def dense(csr_path):
    return sparse.open_csr_corpus(csr_path).densify()


def _spec(data, **kw):
    kw.setdefault("batch_size", B)
    kw.setdefault("epochs", 2)
    kw.setdefault("seed", 5)
    kw.setdefault("reg", 1e-3)
    return ExperimentSpec(data=data, **kw)


@pytest.mark.parametrize("step_mode", [solvers.CONSTANT, solvers.LINE_SEARCH])
@pytest.mark.parametrize("scheme", samplers.SCHEMES)
@pytest.mark.parametrize("solver", solvers.SOLVERS)
def test_resident_sparse_matches_resident_dense(csr_path, dense, solver,
                                                scheme, step_mode):
    kw = dict(solver=solver, scheme=scheme, step_mode=step_mode)
    ps = plan(_spec(DataSource.corpus(csr_path), placement=RESIDENT, **kw))
    assert ps.backend == RESIDENT_SPARSE
    step = ps.cfg.step_size if step_mode == solvers.CONSTANT else None
    pd = plan(_spec(DataSource.arrays(*dense), step_size=step, **kw))
    assert pd.placement == RESIDENT and not pd.cfg.sparse
    rs, rd = execute(ps), execute(pd)
    scale = float(np.max(np.abs(rd.w)))
    np.testing.assert_allclose(rs.w, rd.w, rtol=0, atol=TOL * scale)
    np.testing.assert_allclose(rs.history, rd.history, rtol=TOL)
    assert len(rs.history) == 2


def test_plan_selects_resident_sparse_and_says_why(csr_path):
    p = plan(_spec(DataSource.corpus(csr_path), placement=RESIDENT))
    assert p.backend == RESIDENT_SPARSE and p.fmt == CSR
    assert p.placement == RESIDENT and p.kernel == EAGER
    assert p.chunk == p.num_batches and p.cfg.sparse
    assert any("padded ELL" in w for w in p.why)
    assert RESIDENT_SPARSE in p.describe()


def test_auto_placement_keeps_csr_streamed(csr_path):
    p = plan(_spec(DataSource.corpus(csr_path)))
    assert p.backend == SPARSE_CSR and p.placement != RESIDENT


@pytest.mark.parametrize("kw,match", [
    (dict(kernel=FUSED), "dense-only"),
    (dict(scheme=ChunkImportance()), "streamed"),
    (dict(resident_budget=1 << 10), "budget"),
    (dict(mesh=types.SimpleNamespace(axis_names=("data",),
                                     devices=np.empty(4, object))), "CSR"),
], ids=["fused", "adaptive", "budget", "mesh"])
def test_plan_refuses_what_resident_sparse_cannot_run(csr_path, kw, match):
    with pytest.raises(PlanError, match=match):
        plan(_spec(DataSource.corpus(csr_path), placement=RESIDENT, **kw))


def test_budget_counts_ell_at_lane_padding(csr_path):
    """The budget check counts rows x kmax rounded up to 128 lanes, int32
    ids and float32 values, plus the labels: not the file's bytes."""
    csr = sparse.open_csr_corpus(csr_path)
    ell = ROWS * (-(-csr.kmax // 128) * 128) * 8 + ROWS * 4
    assert ell > csr.meta.nbytes
    data = DataSource.corpus(csr_path)
    plan(_spec(data, placement=RESIDENT, resident_budget=ell))
    with pytest.raises(PlanError, match="budget"):
        plan(_spec(data, placement=RESIDENT, resident_budget=ell - 1))


def test_counters_and_spans_of_the_resident_load(csr_path):
    p = plan(_spec(DataSource.corpus(csr_path), placement=RESIDENT,
                   trace=TracePolicy()))
    res = execute(p)
    csr = sparse.open_csr_corpus(csr_path)
    counters = res.timeline.metrics["counters"]
    assert counters["ell_resident.nonzeros"] == csr.nnz
    assert counters["ell_resident.slots"] == ROWS * csr.kmax
    assert counters["ell_resident.bytes"] == ROWS * csr.kmax * 8 + ROWS * 4
    spans = {(e.lane, e.name): e for e in res.timeline.events}
    read = spans[("access", "read_csr")]
    assert read.args["blocks"] >= 1 and read.args["workers"] >= 1
    assert read.args["bytes"] == csr.nnz * 8 + (ROWS + 1) * 8 + ROWS * 4
    assert spans[("convert", "ell_resident")].args == {
        "nnz": csr.nnz, "slots": ROWS * csr.kmax}
    assert spans[("h2d", "stage_resident")].args["fmt"] == "ell"
    epochs = [e for e in res.timeline.events
              if (e.lane, e.name) == ("compute", "resident_epoch")]
    assert [e.args["kernel"] for e in epochs] == ["ell", "ell"]
    assert ("driver", "objective") in spans
    assert ("driver", "release") in spans
    res.verify_timeline()


def test_read_all_pads_every_row_in_blocks(csr_path, monkeypatch):
    """Blocks of a few rows over several workers give the ELL arrays the
    corpus's rows, column ids and values, zero-padded."""
    monkeypatch.setattr(sparse, "READ_BLOCK_BYTES", 4000)
    pipe = sparse.SparsePipeline(pipeline.PipelineConfig(
        corpus=csr_path, batch_size=B, prefetch=0, resident=True))
    cols, vals, y = pipe.read_all()
    csr = pipe.csr
    assert cols.shape == vals.shape == (ROWS, csr.kmax)
    X = np.zeros((ROWS, FEATS), np.float32)
    np.add.at(X, (np.repeat(np.arange(ROWS), csr.kmax), cols.ravel()),
              vals.ravel())
    Xd, yd = csr.densify()
    np.testing.assert_array_equal(X, Xd)
    np.testing.assert_array_equal(y, yd)
    lens = np.diff(np.asarray(csr.indptr))
    assert ((vals != 0).sum(axis=1) <= lens).all()
    assert pipe.stats.batches == 1


def test_supercell_runs_resident_sparse_cells(csr_path):
    """Cells sharing one resident ELL corpus ride one super-cell: each
    trains exactly what it trains alone."""
    data = DataSource.corpus(csr_path)
    plans = [plan(_spec(data, placement=RESIDENT, solver=s, step_size=0.05))
             for s in (solvers.MBSGD, solvers.SAGA, solvers.SVRG)]
    together = execute_supercell(plans)
    for p, r in zip(plans, together):
        alone = execute(p)
        np.testing.assert_array_equal(r.w, alone.w)
        np.testing.assert_array_equal(r.history, alone.history)


def test_audit_lowers_the_resident_ell_epoch(csr_path):
    from repro.api import audit
    p = plan(_spec(DataSource.corpus(csr_path), placement=RESIDENT,
                   solver=solvers.SVRG))
    report = audit(p)
    assert report.ok, report.failures()
    assert report.backend == RESIDENT_SPARSE


@pytest.mark.parametrize("block", [ROWS, 300, 7])
def test_ell_passes_match_dense_full_corpus(csr_path, dense, block,
                                            monkeypatch):
    """The blocked whole-corpus objective and gradient over ELL equal the
    dense ones, block sizes that do not divide the rows included."""
    from repro.core import erm
    monkeypatch.setattr(erm, "ELL_BLOCK_ROWS", block)
    X, y = dense
    pipe = sparse.SparsePipeline(pipeline.PipelineConfig(
        corpus=csr_path, batch_size=B, prefetch=0, resident=True))
    cols, vals, yy = pipe.read_all()
    prob = ERMProblem(reg=1e-3)
    w = jax.random.normal(jax.random.PRNGKey(2), (FEATS,)) * 0.3
    np.testing.assert_allclose(prob.ell_objective(w, cols, vals, yy),
                               prob.objective(w, X, y), rtol=1e-6)
    for data_only in (False, True):
        ref = (prob.batch_grad_data(w, X, y) if data_only
               else prob.full_grad(w, X, y))
        np.testing.assert_allclose(
            prob.ell_full_grad(w, cols, vals, yy, data_only), ref,
            rtol=1e-5, atol=1e-7)


def test_resumed_run_continues_the_schedule(csr_path):
    """One epoch, then a resumed second, trains what two epochs in one
    call train: the epoch keys replay from the seed."""
    spec = _spec(DataSource.corpus(csr_path), placement=RESIDENT,
                 solver=solvers.SAGA)
    p = plan(spec)
    whole = execute(p)
    first = execute(p, epochs=1)
    second = execute(p, resume=first, epochs=1)
    np.testing.assert_array_equal(second.w, whole.w)
    np.testing.assert_array_equal(second.history, whole.history)
