"""Sparse fused-kernel parity and CSR solver-wiring tests.

The acceptance contract: the CSR fused gradient matches the densified
``fused_batch_grad`` to <= 1e-5 for all three losses and all three sampling
schemes, and all five solvers run on CSR (padded-ELL chunks) without ever
densifying the corpus."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import samplers, solvers
from repro.core.erm import ERMProblem
from repro.core.solvers import SolverConfig
from repro.data import pipeline, sparse
from repro.kernels.fused_erm import LOSSES, fused_batch_grad_data
from repro.kernels.sparse_erm import (CSRDevice, csr_to_device,
                                      sparse_batch_grad,
                                      sparse_batch_grad_data,
                                      sparse_batch_margins,
                                      sparse_batch_objective,
                                      sparse_grad_block, sparse_grad_rows)

ROWS, FEATS, B = 57, 48, 10          # 57 % 10 != 0: clamped last block
DENSITY = 0.15
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("csr") / "kern.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=11)
    return sparse.open_csr_corpus(path)


@pytest.fixture(scope="module")
def dev(corpus):
    return csr_to_device(corpus)


@pytest.fixture(scope="module")
def dense(corpus):
    X, y = corpus.densify()
    return jnp.asarray(X), jnp.asarray(y)


@pytest.fixture(scope="module")
def w():
    return jax.random.normal(jax.random.PRNGKey(9), (FEATS,)) * 0.3


# ------------------------------------------------------- kernel parity ----

@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("start", [0, 20, 55])   # 55 clamps to l-b = 47
def test_sparse_block_matches_densified_fused(corpus, dev, dense, w, loss,
                                              start):
    """CS/SS: CSR fused gradient == dense fused kernel on densify(), incl.
    dynamic_slice clamping of the overlapping last batch."""
    X, y = dense
    prob = ERMProblem(loss=loss, reg=1e-3)
    g = sparse_batch_grad_data(prob, dev, w, start=jnp.asarray(start),
                               batch_size=B, interpret=True)
    ref = fused_batch_grad_data(prob, X, y, w, start=jnp.asarray(start),
                                batch_size=B, interpret=True)
    assert g.shape == ref.shape == (FEATS,)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("loss", LOSSES)
def test_sparse_rows_matches_densified_fused(dev, dense, w, loss):
    """RS: scattered CSR rows, duplicates and wrap-around ids included."""
    X, y = dense
    prob = ERMProblem(loss=loss, reg=1e-3)
    idx = jnp.asarray([5, 51, 0, 56, 7, 7, 30, 21, 2, 44], jnp.int32)
    g = sparse_batch_grad_data(prob, dev, w, idx=idx, interpret=True)
    ref = fused_batch_grad_data(prob, X, y, w, idx=idx, interpret=True)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("scheme", samplers.SCHEMES)
def test_sparse_epoch_schedule_parity(dev, dense, w, loss, scheme):
    """Every batch of a full epoch schedule, all 3 schemes x all 3 losses —
    the acceptance matrix."""
    X, y = dense
    prob = ERMProblem(loss=loss, reg=1e-3)
    key = jax.random.PRNGKey(4)
    if scheme in (samplers.CYCLIC, samplers.SYSTEMATIC):
        for s in np.asarray(samplers.batch_slice_starts(scheme, key, ROWS, B)):
            g = sparse_batch_grad_data(prob, dev, w, start=jnp.asarray(s),
                                       batch_size=B, interpret=True)
            ref = fused_batch_grad_data(prob, X, y, w, start=jnp.asarray(s),
                                        batch_size=B, interpret=True)
            np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
    else:
        idx_mat = samplers.epoch_indices(scheme, key, ROWS, B)
        for j in range(idx_mat.shape[0]):
            g = sparse_batch_grad_data(prob, dev, w, idx=idx_mat[j],
                                       interpret=True)
            ref = fused_batch_grad_data(prob, X, y, w, idx=idx_mat[j],
                                        interpret=True)
            np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["block", "rows"])
def test_sparse_margins_match_densified(dev, dense, w, mode):
    """CSR margin kernels (the line-search trial-objective pass) == dense
    margins on densify(), block and rows, plus the composed objective."""
    X, y = dense
    prob = ERMProblem(loss="logistic", reg=1e-3)
    if mode == "block":
        kw = dict(start=jnp.asarray(20), batch_size=B)
        Xb, yb = X[20:30], y[20:30]
    else:
        idx = jnp.asarray([5, 51, 0, 56, 7, 7, 30, 21, 2, 44], jnp.int32)
        kw = dict(idx=idx)
        Xb, yb = X[idx], y[idx]
    z = sparse_batch_margins(dev, w, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(z), np.asarray(Xb @ w),
                               rtol=1e-5, atol=1e-6)
    obj = sparse_batch_objective(prob, dev, w, interpret=True, **kw)
    np.testing.assert_allclose(float(obj),
                               float(prob.batch_objective(w, Xb, yb)),
                               rtol=1e-5)


def test_sparse_kernels_feature_tiled_parity(tmp_path):
    """Feature counts above one VMEM tile (n > 1024 → tn < n) run the tiled
    one-hot densify: gradients AND margins still match the densified
    reference — the news20-scale VMEM follow-on."""
    path = tmp_path / "wide.csr"
    n_wide = 2048                       # _feature_tile -> 1024, 2 tiles
    sparse.synth_sparse_classification(path, rows=80, features=n_wide,
                                       density=0.01, seed=5)
    csr = sparse.open_csr_corpus(path)
    d = csr_to_device(csr, batch_size=16)
    X, y = csr.densify()
    X, y = jnp.asarray(X), jnp.asarray(y)
    ww = jax.random.normal(jax.random.PRNGKey(3), (n_wide,)) * 0.1
    prob = ERMProblem(loss="logistic", reg=1e-3)
    g = sparse_batch_grad_data(prob, d, ww, start=jnp.asarray(10),
                               batch_size=16, interpret=True)
    ref = prob.batch_grad_data(ww, X[10:26], y[10:26])
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    idx = jnp.asarray([0, 79, 7, 33, 7, 12, 60, 41], jnp.int32)
    g2 = sparse_batch_grad_data(prob, d, ww, idx=idx, interpret=True)
    ref2 = prob.batch_grad_data(ww, X[idx], y[idx])
    np.testing.assert_allclose(np.asarray(g2), np.asarray(ref2),
                               rtol=1e-5, atol=1e-6)
    z = sparse_batch_margins(d, ww, idx=idx, interpret=True)
    np.testing.assert_allclose(np.asarray(z), np.asarray(X[idx] @ ww),
                               rtol=1e-5, atol=1e-6)


def test_sparse_grad_handles_empty_row(tmp_path, w):
    """A zero-nnz row contributes exactly the zero gradient (masked window)."""
    indptr = np.array([0, 2, 2, 3], np.int64)     # row 1 is empty
    meta = sparse.write_csr_corpus(
        tmp_path / "e.csr", indptr=indptr,
        indices=np.array([1, 5, 2], np.int32),
        values=np.array([1.5, -2.0, 0.5], np.float32),
        labels=np.array([1, -1, 1], np.float32), features=FEATS)
    assert meta.nnz == 3
    csr = sparse.open_csr_corpus(tmp_path / "e.csr")
    d = csr_to_device(csr)
    X, y = csr.densify()
    prob = ERMProblem(loss="logistic", reg=1e-3)
    g = sparse_grad_block(d.vals, d.cols, d.indptr, d.y, w,
                          jnp.asarray(0), loss="logistic", batch_size=3,
                          kmax=d.kmax, interpret=True)
    ref = prob.batch_grad_data(w, jnp.asarray(X), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    g2 = sparse_grad_rows(d.vals, d.cols, d.indptr, d.y, w,
                          jnp.arange(3, dtype=jnp.int32), loss="logistic",
                          kmax=d.kmax, interpret=True)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_sparse_wrapper_argument_validation(dev, w):
    prob = ERMProblem()
    with pytest.raises(ValueError):
        sparse_batch_grad_data(prob, dev, w)
    with pytest.raises(ValueError):
        sparse_batch_grad_data(prob, dev, w, start=jnp.asarray(0),
                               idx=jnp.arange(4))
    with pytest.raises(ValueError):
        sparse_batch_grad_data(prob, dev, w, start=jnp.asarray(0))


def test_sparse_batch_grad_adds_regularizer(dev, dense, w):
    prob = ERMProblem(reg=1e-2)
    gd = sparse_batch_grad_data(prob, dev, w, start=jnp.asarray(0),
                                batch_size=B, interpret=True)
    g = sparse_batch_grad(prob, dev, w, start=jnp.asarray(0),
                          batch_size=B, interpret=True)
    np.testing.assert_allclose(np.asarray(g - gd), np.asarray(prob.reg * w),
                               rtol=1e-6, atol=1e-7)


def test_csr_to_device_layout(corpus, dev):
    assert isinstance(dev, CSRDevice)
    assert dev.indptr.dtype == jnp.int32 and dev.cols.dtype == jnp.int32
    assert dev.rows == ROWS and dev.features == FEATS
    assert dev.kmax == corpus.kmax
    # staging pre-pads the DMA tail once; the padding must be zeros
    assert dev.nnz == corpus.nnz and dev.vals.shape[0] > dev.nnz
    assert not np.any(np.asarray(dev.vals[dev.nnz:]))


def test_csr_to_device_batch_hint_parity(corpus, dense, w):
    """batch_size staging (block window pre-padded, no per-call pad) gives
    the same gradients as the unhinted staging's pad fallback."""
    X, y = dense
    prob = ERMProblem(reg=1e-3)
    hinted = csr_to_device(corpus, batch_size=B)
    need = B * max(corpus.kmax, 1)
    assert hinted.vals.shape[0] >= hinted.nnz + need
    g = sparse_batch_grad_data(prob, hinted, w, start=jnp.asarray(10),
                               batch_size=B, interpret=True)
    ref = fused_batch_grad_data(prob, X, y, w, start=jnp.asarray(10),
                                batch_size=B, interpret=True)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------- solver-level CSR wiring ----

def _ell_epoch_chunks(corpus, scheme, epochs=1, seed=0):
    """Stream one ELL chunk per epoch via SparsePipeline (prefetch=0)."""
    p = sparse.SparsePipeline(pipeline.PipelineConfig(
        corpus=corpus, batch_size=B, sampling=scheme, seed=seed, prefetch=0))
    m = p.sampler.m
    out = []
    for _ in range(epochs):
        batches = [p.read_batch() for _ in range(m)]
        out.append((np.stack([b.cols for b in batches]),
                    np.stack([b.vals for b in batches]),
                    np.stack([b.y for b in batches])))
    return out, m


@pytest.mark.parametrize("solver", solvers.SOLVERS)
@pytest.mark.parametrize("step_mode", [solvers.CONSTANT, solvers.LINE_SEARCH])
def test_sparse_epoch_fn_matches_dense_epoch_fn(tmp_path, solver, step_mode):
    """All five solvers x both step rules: the sparse chunked epoch engine
    on ELL batches == the dense engine on the densified batches."""
    path = tmp_path / "s.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=5)
    csr = sparse.open_csr_corpus(path)
    prob = ERMProblem(reg=1e-3)
    chunks, m = _ell_epoch_chunks(path, samplers.SYSTEMATIC, epochs=2)
    cfg_s = SolverConfig(solver=solver, step_mode=step_mode, step_size=0.05,
                         sparse=True)
    cfg_d = SolverConfig(solver=solver, step_mode=step_mode, step_size=0.05)

    def densified(colsc, valsc):
        K, b, kmax = colsc.shape
        Xc = np.zeros((K, b, FEATS), np.float32)
        for k in range(K):
            for i in range(b):
                np.add.at(Xc[k, i], colsc[k, i], valsc[k, i])
        return Xc

    js = jnp.arange(m)
    st_s = solvers.init_state(solver, jnp.zeros(FEATS), m)
    st_d = solvers.init_state(solver, jnp.zeros(FEATS), m)
    ep_s = solvers.make_epoch_fn(prob, cfg_s)
    ep_d = solvers.make_epoch_fn(prob, cfg_d)
    fg = lambda w: jnp.asarray(sparse.csr_full_grad(
        prob, csr, w, data_term_only=(solver == solvers.SAAG2)))
    for colsc, valsc, yc in chunks:
        if solver in (solvers.SVRG, solvers.SAAG2):
            st_s = solvers.epoch_begin(prob, cfg_s, st_s, fg)
            st_d = solvers.epoch_begin(prob, cfg_d, st_d, fg)
        st_s = ep_s(st_s, jnp.asarray(colsc), jnp.asarray(valsc),
                    jnp.asarray(yc), js)
        st_d = ep_d(st_d, jnp.asarray(densified(colsc, valsc)),
                    jnp.asarray(yc), js)
    np.testing.assert_allclose(np.asarray(st_s.w), np.asarray(st_d.w),
                               rtol=1e-4, atol=1e-5)


def test_sparse_step_fn_matches_sparse_batch_step(tmp_path):
    path = tmp_path / "st.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=7)
    chunks, m = _ell_epoch_chunks(path, samplers.CYCLIC)
    colsc, valsc, yc = chunks[0]
    prob = ERMProblem(reg=1e-3)
    cfg = SolverConfig(solver=solvers.SAGA, step_size=0.05, sparse=True)
    step = solvers.make_step_fn(prob, cfg)
    st = solvers.init_state(solvers.SAGA, jnp.zeros(FEATS), m)
    st_ref = solvers.init_state(solvers.SAGA, jnp.zeros(FEATS), m)
    for j in range(m):
        st = step(st, jnp.asarray(colsc[j]), jnp.asarray(valsc[j]),
                  jnp.asarray(yc[j]), jnp.asarray(j))
        st_ref = solvers.sparse_batch_step(
            prob, cfg, st_ref, jnp.asarray(colsc[j]), jnp.asarray(valsc[j]),
            jnp.asarray(yc[j]), jnp.asarray(j))
    np.testing.assert_allclose(np.asarray(st.w), np.asarray(st_ref.w),
                               rtol=1e-6, atol=1e-7)


def test_run_rejects_sparse_config(dense):
    X, y = dense
    with pytest.raises(ValueError, match="CSR"):
        solvers.run(ERMProblem(), SolverConfig(sparse=True),
                    samplers.CYCLIC, X, y, jnp.zeros(FEATS),
                    batch_size=B, epochs=1)


def test_resident_epoch_fn_rejects_sparse():
    """The resident ELL engine runs single-device on the eager path: a
    sparse config with the fused kernels, or over a padded (sharded
    'psum') corpus, is refused."""
    with pytest.raises(ValueError, match="resident"):
        solvers.make_resident_epoch_fn(ERMProblem(),
                                       SolverConfig(sparse=True,
                                                    use_fused=True),
                                       samplers.CYCLIC, B)
    with pytest.raises(ValueError, match="resident"):
        solvers.make_resident_epoch_fn(ERMProblem(),
                                       SolverConfig(sparse=True),
                                       samplers.CYCLIC, B, rows=ROWS)


@pytest.mark.parametrize("scheme", samplers.SCHEMES)
def test_resident_epoch_fn_matches_run(dense, scheme):
    """Fused host mode drives the same in-graph epoch as solvers.run."""
    X, y = dense
    prob = ERMProblem(reg=1e-3)
    cfg = SolverConfig(solver=solvers.MBSGD, step_size=0.05)
    w_run, _ = solvers.run(prob, cfg, scheme, X, y, jnp.zeros(FEATS),
                           batch_size=B, epochs=2, seed=3,
                           record_objective=False)
    ep = solvers.make_resident_epoch_fn(prob, cfg, scheme, B)
    st = solvers.init_state(solvers.MBSGD, jnp.zeros(FEATS),
                            samplers.num_batches(ROWS, B))
    key = jax.random.PRNGKey(3)
    for _ in range(2):
        key, sub = jax.random.split(key)
        st = ep(st, X, y, sub)
    np.testing.assert_allclose(np.asarray(w_run), np.asarray(st.w),
                               rtol=1e-6, atol=1e-7)
